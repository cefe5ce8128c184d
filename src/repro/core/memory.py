"""Outer memory hierarchy access cost (paper Sec. IV-A: "reading and
writing from higher-level memories ... accounted for through integration
of the model into the ZigZag DSE framework"; Sec. VI data-traffic bars).

A two-level model above the macro:

* **global buffer** (on-chip SRAM): every operand entering/leaving a
  macro crosses it; per-bit access energy scales with the node's C_inv
  like any other capacitance in the unified model;
* **off-chip DRAM**: only crossed when a tensor exceeds the buffer —
  for the tinyMLPerf case studies everything fits on chip, matching
  the paper's setup, but the level exists for the LM case studies.

The traffic *volumes* this module prices are schedule-parameterized
upstream (``mapping.evaluate`` computes ``weight_bits`` /
``input_bits`` / ``psum_bits`` from the active
:class:`repro.core.schedule.Schedule`: weight-stationary refetches
inputs per K tile and spills psums, output-stationary restreams
weights and never spills) — the per-bit *pricing* here is
schedule-agnostic, so every engine shares these functions unchanged.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import tech as _tech
from .energy import fold_add
from .mapping import MappingCost, MappingCostBatch

#: Global-buffer read/write energy per bit, in units of C_inv * V^2.
#: A ~256 KB SRAM access at 28 nm/0.8 V costs a few fJ/bit; 20x C_inv V^2
#: reproduces that magnitude and scales across nodes with the same
#: regression the rest of the model uses.
SRAM_CINV_FACTOR = 20.0

#: Off-chip DRAM access energy per bit [fJ] (LPDDR4-class, node-independent).
DRAM_FJ_PER_BIT = 4000.0

#: Off-chip HBM access energy per bit [fJ] (HBM2e-class incl. PHY,
#: node-independent — the KV-cache spill tier for LM serving).
HBM_FJ_PER_BIT = 3500.0

#: Chip-to-chip fabric energy per bit [fJ] (NVLink/ICI-class SerDes) —
#: paid on top of HBM when the live KV overflows one chip's HBM.
FABRIC_FJ_PER_BIT = 10000.0


@dataclasses.dataclass(frozen=True)
class MemoryModel:
    tech_nm: float
    vdd: float
    buffer_bytes: int = 1 << 20           # 1 MiB global buffer
    dram_fj_per_bit: float = DRAM_FJ_PER_BIT

    def sram_fj_per_bit(self) -> float:
        return (SRAM_CINV_FACTOR * _tech.c_inv_ff(self.tech_nm)
                * self.vdd * self.vdd)

    def traffic_energy_fj(self, cost: MappingCost,
                          resident_bytes: int = 0) -> dict[str, float]:
        """Price a mapping's traffic.  ``resident_bytes`` is the layer's
        total working set; spill to DRAM happens if it exceeds the buffer."""
        per_bit = self.sram_fj_per_bit()
        off_chip = resident_bytes > self.buffer_bytes
        if off_chip:
            per_bit_w = per_bit + self.dram_fj_per_bit
        else:
            per_bit_w = per_bit
        return {
            "weights": cost.weight_bits * per_bit_w,
            "inputs": cost.input_bits * per_bit,
            "outputs": cost.output_bits * per_bit,
            "psums": cost.psum_bits * per_bit,
        }

    def total_traffic_energy_fj(self, cost: MappingCost,
                                resident_bytes: int = 0) -> float:
        return fold_add(self.traffic_energy_fj(cost, resident_bytes).values())

    def traffic_energy_batch(self, costs: MappingCostBatch,
                             resident_bytes: int = 0) -> dict:
        """Vectorized :meth:`traffic_energy_fj` over a candidate batch.

        Same per-bit pricing and the same off-chip decision (the
        working set is a property of the layer, not the mapping), so
        each entry is bitwise-equal to the scalar path's.
        """
        per_bit = self.sram_fj_per_bit()
        off_chip = resident_bytes > self.buffer_bytes
        if off_chip:
            per_bit_w = per_bit + self.dram_fj_per_bit
        else:
            per_bit_w = per_bit
        return {
            "weights": costs.weight_bits * per_bit_w,
            "inputs": costs.input_bits * per_bit,
            "outputs": costs.output_bits * per_bit,
            "psums": costs.psum_bits * per_bit,
        }


# --------------------------------------------------------------------------- #
# KV-cache byte hierarchy (LLM serving)                                        #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class KVCacheHierarchy:
    """Bytes-based memory tiers for the serving KV cache.

    Three tiers above the macro: an **on-chip SRAM KV buffer**
    (``sram_kv_bytes`` capacity, priced at the design's per-bit SRAM
    rate like every other on-chip operand), **off-chip HBM**
    (``hbm_bytes`` capacity per chip) and the **chip-to-chip fabric**
    for live caches too big for one chip's HBM.  Tier selection is by
    the phase's *live* working set (``kv_live_bytes``): all of a
    phase's KV traffic is priced at the rate of the tier the live cache
    lands in — off-chip tiers still cross the on-chip buffer on the way
    to the macro, so their rates add to the SRAM rate exactly like the
    DRAM spill term in :func:`traffic_energy_grid`.
    """

    sram_kv_bytes: int = 8 << 20          # 8 MiB on-chip KV buffer
    hbm_bytes: int = 16 << 30             # 16 GiB HBM per chip
    hbm_fj_per_bit: float = HBM_FJ_PER_BIT
    fabric_fj_per_bit: float = FABRIC_FJ_PER_BIT

    def fj_per_bit(self, per_bit_sram: float, live_bytes: float) -> float:
        """Scalar per-bit KV rate for one design (the oracle the grid
        path must match bitwise)."""
        if live_bytes <= self.sram_kv_bytes:
            return per_bit_sram
        if live_bytes <= self.hbm_bytes:
            return per_bit_sram + self.hbm_fj_per_bit
        return per_bit_sram + (self.hbm_fj_per_bit + self.fabric_fj_per_bit)

    def traffic_energy_fj(self, per_bit_sram: float, read_bytes: float,
                          write_bytes: float, live_bytes: float) -> float:
        """Scalar KV traffic energy of one phase on one design [fJ]:
        ``(read + write) bytes * 8 * tier rate`` — reads and writes
        share the tier rate (both cross the same levels)."""
        rate = self.fj_per_bit(per_bit_sram, live_bytes)
        return (read_bytes + write_bytes) * 8.0 * rate


def kv_traffic_energy_grid(per_bit_sram, read_bytes: float,
                           write_bytes: float, live_bytes,
                           hier: KVCacheHierarchy = KVCacheHierarchy()
                           ) -> np.ndarray:
    """Per-design KV traffic energy [fJ], shape (D,).

    ``per_bit_sram`` is a scalar or a (D,) array
    (:func:`sram_fj_per_bit_grid`); ``live_bytes`` may be per-design
    too.  The tier rate is an elementwise selection between the same
    precomputed values the scalar :meth:`KVCacheHierarchy.fj_per_bit`
    branch chooses from, and the energy expression keeps its float
    association — so every entry is bitwise what the per-design scalar
    oracle returns.
    """
    per_bit = np.atleast_1d(np.asarray(per_bit_sram, dtype=np.float64))
    live = np.asarray(live_bytes)
    rate = np.where(
        live <= hier.sram_kv_bytes, per_bit,
        np.where(live <= hier.hbm_bytes, per_bit + hier.hbm_fj_per_bit,
                 per_bit + (hier.hbm_fj_per_bit + hier.fabric_fj_per_bit)))
    return (read_bytes + write_bytes) * 8.0 * rate


# --------------------------------------------------------------------------- #
# design-axis broadcasting                                                     #
# --------------------------------------------------------------------------- #
def sram_fj_per_bit_grid(tech_nm: np.ndarray, vdd: np.ndarray) -> np.ndarray:
    """Vectorized :meth:`MemoryModel.sram_fj_per_bit` over design arrays.

    Same float association as the scalar method (``20 * C_inv * V * V``
    left to right), so a per-design entry is bitwise what a per-design
    :class:`MemoryModel` would return.
    """
    tech_nm = np.asarray(tech_nm, dtype=np.float64)
    vdd = np.asarray(vdd, dtype=np.float64)
    c_inv = _tech.CINV_SLOPE_FF_PER_NM * tech_nm + _tech.CINV_OFFSET_FF
    return SRAM_CINV_FACTOR * c_inv * vdd * vdd


def traffic_energy_grid(per_bit: np.ndarray | float, costs,
                        resident_bytes: int | np.ndarray = 0,
                        buffer_bytes: int = 1 << 20,
                        dram_fj_per_bit: float = DRAM_FJ_PER_BIT) -> dict:
    """Traffic pricing over a (design x candidate) grid.

    ``per_bit`` is either one scalar (a shared memory system) or a (D,)
    array of per-design SRAM costs (:func:`sram_fj_per_bit_grid`); each
    returned entry is (D, C) and bitwise equals the per-design scalar
    path.  The off-chip spill decision is a property of the layer's
    working set, shared by every design, exactly as in the scalar model.

    ``costs`` is any struct carrying ``weight_bits`` / ``input_bits`` /
    ``output_bits`` / ``psum_bits`` candidate rows — a
    :class:`~repro.core.mapping.MappingCostGrid` (one layer) or a
    :class:`~repro.core.mapping.NetworkCostGrid` (fused workload
    lattice).  For the fused case ``resident_bytes`` is a per-*lane*
    array (each lane inherits its layer's working set), and the weight
    rate becomes an elementwise selection between the same two
    precomputed per-bit values the scalar branch chooses from — so
    every lane still prices bitwise like its own per-layer call.
    """
    per_bit = np.atleast_1d(np.asarray(per_bit, dtype=np.float64))[:, None]
    off_chip = np.asarray(resident_bytes) > buffer_bytes
    if off_chip.ndim == 0:
        per_bit_w = per_bit + dram_fj_per_bit if off_chip else per_bit
    else:
        per_bit_w = np.where(off_chip, per_bit + dram_fj_per_bit, per_bit)
    return {
        "weights": costs.weight_bits * per_bit_w,
        "inputs": costs.input_bits * per_bit,
        "outputs": costs.output_bits * per_bit,
        "psums": costs.psum_bits * per_bit,
    }


def spill_pricing_columns(per_bit: np.ndarray | float,
                          dram_fj_per_bit: float = DRAM_FJ_PER_BIT):
    """Host-side prep for pricing traffic *inside* a jit graph.

    Splits :func:`traffic_energy_grid`'s NumPy work into the pieces a
    device reduction can consume: the buffered rate column and the
    spill rate column (the same ``per_bit + dram`` sum the host
    ``np.where`` arms compute, done here once in NumPy so the device
    never re-adds it).  The per-lane spill decision is the caller's
    ``resident_bytes > buffer_bytes``.  Returns ``(per_bit, per_bit_spill)``,
    each (D,) float64 ((1,) for a scalar rate).
    """
    per_bit = np.atleast_1d(np.asarray(per_bit, dtype=np.float64))
    return per_bit, per_bit + dram_fj_per_bit


def traffic_terms(xp, per_bit, per_bit_spill, off_chip,
                  weight_bits, input_bits, output_bits, psum_bits):
    """The four :func:`traffic_energy_grid` products, composable into a
    reduction graph (``xp`` is ``jax.numpy`` there, ``numpy`` in tests).

    Only products — no adds — so the caller can fence them (e.g. with
    ``lax.optimization_barrier``) before summing, keeping the chain
    FMA-free and bitwise equal to the host oracle's ``bits * rate``
    multiplies.
    """
    per_bit_w = xp.where(off_chip, per_bit_spill, per_bit)
    return (weight_bits * per_bit_w,
            input_bits * per_bit,
            output_bits * per_bit,
            psum_bits * per_bit)
