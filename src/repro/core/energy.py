"""Unified AIMC/DIMC datapath energy model (paper Sec. IV, Eq. 1-11).

    E_total = E_MUL + E_ACC + E_peripherals                      (Eq. 1)
    E_MUL   = E_cell + E_logic                                   (Eq. 2)
    E_cell  = (E_WL + E_BL) * CC_prech                           (Eq. 3)
    E_WL    = C_WL V^2 B_w D1                                    (Eq. 4)  [per row]
    E_BL    = C_BL V^2 B_w D2 M                                  (Eq. 5)  [per weight word]
    E_logic = V^2 C_gate G_MUL * MACs                            (Eq. 6)
    E_ACC   = E_ADC + E_adder_tree                               (Eq. 7)
    E_ADC   = (k1 ADC_res + k2 4^ADC_res) V^2 B_w (MACs / D2)    (Eq. 8)
    E_tree  = C_gate G_FA V^2 D1 F CC_acc                        (Eq. 9)
    F       = B N + N - B + log2 N - 1                           (Eq. 10)
    E_DAC   = k3 DAC_res V^2 CC_BS                               (Eq. 11) [per row]

The paper states Eq. 4 per driven wordline and Eq. 5 per weight-word
column group; this module multiplies them out over the rows/columns a
mapped tile actually occupies and over the cycles in which lines toggle
(``CC_prech``), which is where AIMC and DIMC genuinely differ:

* **AIMC** recomputes the analog dot product every cycle, so bitlines
  toggle on every one of the ``CC_BS`` conversion cycles of every input.
* **DIMC (BPBS)** keeps weights latched: with ``M = 1`` the read
  bitlines only toggle when weights are (re)loaded; with ``M``-way
  muxing the selected row changes ``M`` times per input vector.

A switching-activity factor ``alpha`` models the 50 % operand sparsity
protocol the paper uses for its comparisons (Sec. III).

All energies are in femtojoules (fJ); see ``tech.py`` for units.

Temporal schedules
------------------
The per-phase toggle counts are parameterized on a
:class:`repro.core.schedule.Schedule`.  Most of the schedule dependence
enters through ``MacroTile.weight_loads`` (the mapper computes it from
the schedule: 1 for weight-stationary, one reload per temporal input
iteration for output-stationary), which the model already prices — the
weight-write term and the DIMC ``M = 1`` precharge count scale with it.
The one term the tile arguments cannot carry is the **output-stationary
AIMC pass-boundary conversion phase**: every weight reload drains the
resident partials through the ADCs (one conversion per active weight
word) and re-drives the inputs through the row DACs.  DIMC pays
nothing there — its partials sit in digital accumulator registers and
a reload is a plain SRAM write — which is exactly the dataflow
flexibility asymmetry the paper argues for (Sec. III).

Batched evaluation
------------------
``tile_energy`` prices ONE tile; the DSE prices thousands of candidate
tiles per layer.  :func:`tile_energy_batch` evaluates Eq. 1-11 for a
whole struct-of-arrays batch of tiles on one macro in a single
vectorized NumPy pass, returning an :class:`EnergyBreakdownBatch`.

Scalar-reference contract: ``tile_energy`` is the oracle.  The batched
path performs the *same floating-point operations in the same order*
(each scalar sub-expression is hoisted, each per-tile factor is applied
in the scalar code's left-to-right association), so for every index
``i``::

    tile_energy_batch(macro, ...).at(i) == tile_energy(macro, tile_i)

bitwise, not merely approximately.  ``tests/core/test_batched_parity.py``
enforces this property; any edit to one path must be mirrored in the
other.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from .. import obs
from . import tech as _tech
from .hardware import IMCMacro
from .schedule import WEIGHT_STATIONARY, Schedule

#: Activity factor at the paper's 50 % operand-sparsity protocol.  Not all
#: nodes toggle rail-to-rail every cycle; calibrated once against the DIMC
#: anchor designs (tests/core/test_validation.py) and then frozen.
DEFAULT_ALPHA = 0.35

#: SRAM write energy per bit, in units of C_inv V^2 (WL + both BLs driven
#: plus write-driver overhead).  Used for weight (re)loads — the effect the
#: paper's DeepAutoEncoder case hinges on (Sec. VI).
WRITE_CINV_FACTOR = 4.0


def fold_add(values):
    """``((a + b) + c) + ...``, left to right: the association every
    pricing path reproduces bitwise.  Not ``sum()``, which compensates
    float rounding from Python 3.12 on and so can differ in the last
    bit."""
    total = 0
    for v in values:
        total = total + v
    return total


@dataclasses.dataclass(frozen=True)
class MacroTile:
    """One tiled MVM execution resident on a macro.

    The mapper (``mapping.py``) produces these: ``rows_used`` /
    ``cols_used`` describe the occupied sub-array (utilization), and the
    temporal loop supplies ``n_inputs`` distinct input vectors that reuse
    one weight load (``weight_loads`` counts (re)writes of the tile).
    """

    n_inputs: int          # input vectors streamed through the loaded weights
    rows_used: int         # accumulation depth occupied (<= R)
    cols_used: int         # weight words occupied (<= D1)
    weight_loads: int = 1  # times this tile's weights are written

    def macs(self) -> float:
        return float(self.n_inputs) * self.rows_used * self.cols_used


@dataclasses.dataclass(frozen=True)
class EnergyBreakdown:
    """Per-component energy [fJ] for a tile execution (paper Fig. 7 bars)."""

    e_wl: float
    e_bl: float
    e_logic: float
    e_adc: float
    e_adder_tree: float
    e_dac: float
    e_weight_write: float
    macs: float

    @property
    def e_cell(self) -> float:
        return self.e_wl + self.e_bl

    @property
    def e_mul(self) -> float:
        return self.e_cell + self.e_logic

    @property
    def e_acc(self) -> float:
        return self.e_adc + self.e_adder_tree

    @property
    def e_peripherals(self) -> float:
        return self.e_dac

    @property
    def total_fj(self) -> float:
        """E_total (Eq. 1) + weight-write extension."""
        return self.e_mul + self.e_acc + self.e_peripherals + self.e_weight_write

    @property
    def fj_per_mac(self) -> float:
        return self.total_fj / max(self.macs, 1.0)

    @property
    def tops_per_watt(self) -> float:
        """2 ops per MAC; 1 fJ/op == 1000 TOP/s/W."""
        return 2.0 * 1e3 / max(self.fj_per_mac, 1e-30)

    def scaled(self, k: float) -> "EnergyBreakdown":
        return EnergyBreakdown(
            *(getattr(self, f.name) * k for f in dataclasses.fields(self)))

    def __add__(self, other: "EnergyBreakdown") -> "EnergyBreakdown":
        return EnergyBreakdown(
            *(getattr(self, f.name) + getattr(other, f.name)
              for f in dataclasses.fields(self)))

    @staticmethod
    def zero() -> "EnergyBreakdown":
        return EnergyBreakdown(0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0)


def tile_energy(macro: IMCMacro, tile: MacroTile,
                alpha: float = DEFAULT_ALPHA,
                schedule: Schedule = WEIGHT_STATIONARY) -> EnergyBreakdown:
    """Evaluate Eq. 1-11 for one tile execution under ``schedule``.

    The schedule mostly acts through ``tile.weight_loads`` (the mapper
    sets it); the only explicit branch here is the output-stationary
    AIMC pass-boundary conversion phase (module docstring)."""
    tp = macro.tech_params()
    v2 = macro.vdd * macro.vdd
    c_wl = tp.c_inv_ff           # C_WL ~ C_inv (paper Sec. IV-B1)
    c_bl = tp.c_inv_ff           # C_BL ~ C_inv
    c_gate = tp.c_gate_ff        # ~ 2 C_inv (paper Sec. IV-B2)
    bw, bi = macro.bw, macro.bi
    d1, d2, m = macro.d1, macro.d2, macro.m_mux
    macs = tile.macs()

    rows_drv = min(tile.rows_used, macro.rows)           # driven wordlines
    words = min(tile.cols_used, d1)                      # active weight words
    mux_rows = math.ceil(rows_drv / m)                   # rows per cycle (DIMC)

    # --- E_cell (Eq. 3-5) ----------------------------------------------------
    # Eq. 4 per wordline: the physical line spans the full row (Bw * D1 cells).
    e_wl_line = c_wl * v2 * bw * d1
    # Eq. 5 per weight word: the (local) bitlines span D2 * M cells.
    e_bl_word = c_bl * v2 * bw * d2 * m

    if macro.analog:
        # All rows jointly activated; bitlines re-develop every conversion
        # cycle: CC_prech = CC_BS per input vector.
        cc_prech = macro.cc_bs * tile.n_inputs
        e_wl = e_wl_line * rows_drv * cc_prech * alpha
        e_bl = e_bl_word * words * cc_prech * alpha
    else:
        # Weights stationary (BPBS): wordlines/read-bitlines toggle on row
        # (re)selection only — M phases per input vector when muxed, else
        # once per weight load.
        if m > 1:
            cc_prech = m * tile.n_inputs
            e_wl = e_wl_line * mux_rows * cc_prech * alpha
            e_bl = e_bl_word * words * cc_prech * alpha
        else:
            cc_prech = tile.weight_loads
            e_wl = e_wl_line * rows_drv * cc_prech * alpha
            e_bl = e_bl_word * words * cc_prech * alpha

    # --- E_logic (Eq. 6), DIMC only -------------------------------------------
    # G_MUL = Bw 1-b multipliers per MAC; each is exercised on every one of
    # the Bi bit-serial cycles.
    if macro.analog:
        e_logic = 0.0
    else:
        # Eq. 6 literal: G_MUL = Bw gates per 1-b-input multiplier, one
        # toggle-set per (full-precision) MAC — the bit-serial cycling is
        # folded into "total MACs" by the paper's definition.  Booth
        # recoding ([42]) halves the partial products actually evaluated.
        g_mul = float(bw) * macro.cc_bs / bi
        e_logic = v2 * c_gate * g_mul * macs * alpha

    # --- E_ACC (Eq. 7-10) ------------------------------------------------------
    if macro.analog:
        conversions = bw * (macs / max(d2, 1))          # Eq. 8: Bw * MACs / D2
        e_adc = _tech.adc_energy_fj(macro.adc_res, macro.vdd) * conversions \
            / macro.cols_per_adc
        n_tree, b_tree = max(2, bw), macro.adc_res       # recombine weight bits
        f_tree = _tech.adder_tree_full_adders(n_tree, b_tree)
        cc_acc = macro.cc_bs * tile.n_inputs
        e_tree = c_gate * _tech.G_FA * v2 * words * f_tree * cc_acc * alpha
    else:
        e_adc = 0.0
        n_tree, b_tree = d2, bw                          # Eq. 10: N=D2, B=Bw
        f_tree = _tech.adder_tree_full_adders(n_tree, b_tree)
        # Tree is exercised every bit-serial cycle of every mux phase, but
        # only the sub-tree spanning the occupied rows toggles.
        occupancy = min(1.0, rows_drv / max(d2 * m, 1))
        cc_acc = macro.cc_bs * m * tile.n_inputs
        e_tree = (c_gate * _tech.G_FA * v2 * words * f_tree * occupancy
                  * cc_acc * alpha)

    # --- E_peripherals (Eq. 11), AIMC only --------------------------------------
    if macro.analog:
        cc_bs = macro.cc_bs * tile.n_inputs              # conversions per row
        e_dac = _tech.dac_energy_fj(macro.dac_res, macro.vdd) * rows_drv * cc_bs
    else:
        e_dac = 0.0

    # --- OS pass-boundary conversion phases (AIMC only) --------------------------
    # Streaming a new weight tile into an analog array drains the resident
    # partials through the ADCs (one conversion per active weight word) and
    # re-drives the inputs through the row DACs, once per reload.  DIMC
    # reloads are plain SRAM writes (already in e_weight_write).
    if macro.analog and schedule.output_stationary:
        reloads = tile.weight_loads
        e_adc = e_adc + _tech.adc_energy_fj(macro.adc_res, macro.vdd) \
            * words * reloads / macro.cols_per_adc
        e_dac = e_dac + _tech.dac_energy_fj(macro.dac_res, macro.vdd) \
            * rows_drv * reloads

    # --- weight (re)write extension --------------------------------------------
    bits_written = tile.weight_loads * rows_drv * words * bw
    e_write = WRITE_CINV_FACTOR * tp.c_inv_ff * v2 * bits_written

    return EnergyBreakdown(
        e_wl=e_wl, e_bl=e_bl, e_logic=e_logic, e_adc=e_adc,
        e_adder_tree=e_tree, e_dac=e_dac, e_weight_write=e_write, macs=macs)


# --------------------------------------------------------------------------- #
# batched (struct-of-arrays) evaluation                                         #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class EnergyBreakdownBatch:
    """Struct-of-arrays :class:`EnergyBreakdown` over N candidate tiles.

    Every field is a float64 ndarray of shape (N,); ``at(i)`` extracts
    one candidate as a scalar :class:`EnergyBreakdown`.  ``total_fj``
    reproduces the scalar property's exact summation order
    ``(e_mul + e_acc) + e_peripherals) + e_weight_write``.
    """

    e_wl: np.ndarray
    e_bl: np.ndarray
    e_logic: np.ndarray
    e_adc: np.ndarray
    e_adder_tree: np.ndarray
    e_dac: np.ndarray
    e_weight_write: np.ndarray
    macs: np.ndarray

    def __len__(self) -> int:
        return len(self.e_wl)

    @property
    def e_cell(self) -> np.ndarray:
        return self.e_wl + self.e_bl

    @property
    def e_mul(self) -> np.ndarray:
        return self.e_cell + self.e_logic

    @property
    def e_acc(self) -> np.ndarray:
        return self.e_adc + self.e_adder_tree

    @property
    def e_peripherals(self) -> np.ndarray:
        return self.e_dac

    @property
    def total_fj(self) -> np.ndarray:
        return self.e_mul + self.e_acc + self.e_peripherals \
            + self.e_weight_write

    @property
    def fj_per_mac(self) -> np.ndarray:
        return self.total_fj / np.maximum(self.macs, 1.0)

    def scaled(self, k: np.ndarray | float) -> "EnergyBreakdownBatch":
        return EnergyBreakdownBatch(
            *(getattr(self, f.name) * k for f in dataclasses.fields(self)))

    def at(self, i: int) -> EnergyBreakdown:
        return EnergyBreakdown(
            *(float(getattr(self, f.name)[i])
              for f in dataclasses.fields(self)))


def tile_energy_batch(macro: IMCMacro,
                      n_inputs: np.ndarray,
                      rows_used: np.ndarray,
                      cols_used: np.ndarray,
                      weight_loads: np.ndarray | int = 1,
                      alpha: float = DEFAULT_ALPHA,
                      schedule_os: np.ndarray | bool = False
                      ) -> EnergyBreakdownBatch:
    """Vectorized :func:`tile_energy` over N tiles on one macro.

    Arguments are integer arrays of shape (N,) (``weight_loads`` may be
    a scalar).  ``schedule_os`` marks output-stationary tiles (bool,
    broadcastable), which adds the AIMC pass-boundary conversion term.
    Bitwise-identical to the scalar oracle per the module docstring's
    scalar-reference contract.
    """
    n_inputs = np.asarray(n_inputs, dtype=np.int64)
    rows_used = np.asarray(rows_used, dtype=np.int64)
    cols_used = np.asarray(cols_used, dtype=np.int64)
    weight_loads = np.broadcast_to(
        np.asarray(weight_loads, dtype=np.int64), n_inputs.shape)

    tp = macro.tech_params()
    v2 = macro.vdd * macro.vdd
    c_wl = tp.c_inv_ff
    c_bl = tp.c_inv_ff
    c_gate = tp.c_gate_ff
    bw, bi = macro.bw, macro.bi
    d1, d2, m = macro.d1, macro.d2, macro.m_mux
    macs = n_inputs.astype(np.float64) * rows_used * cols_used

    rows_drv = np.minimum(rows_used, macro.rows)
    words = np.minimum(cols_used, d1)
    mux_rows = np.ceil(rows_drv / m)

    e_wl_line = c_wl * v2 * bw * d1
    e_bl_word = c_bl * v2 * bw * d2 * m

    if macro.analog:
        cc_prech = macro.cc_bs * n_inputs
        e_wl = e_wl_line * rows_drv * cc_prech * alpha
        e_bl = e_bl_word * words * cc_prech * alpha
    else:
        if m > 1:
            cc_prech = m * n_inputs
            e_wl = e_wl_line * mux_rows * cc_prech * alpha
            e_bl = e_bl_word * words * cc_prech * alpha
        else:
            cc_prech = weight_loads
            e_wl = e_wl_line * rows_drv * cc_prech * alpha
            e_bl = e_bl_word * words * cc_prech * alpha

    if macro.analog:
        e_logic = np.zeros_like(macs)
    else:
        g_mul = float(bw) * macro.cc_bs / bi
        e_logic = v2 * c_gate * g_mul * macs * alpha

    if macro.analog:
        conversions = bw * (macs / max(d2, 1))
        e_adc = _tech.adc_energy_fj(macro.adc_res, macro.vdd) * conversions \
            / macro.cols_per_adc
        n_tree, b_tree = max(2, bw), macro.adc_res
        f_tree = _tech.adder_tree_full_adders(n_tree, b_tree)
        cc_acc = macro.cc_bs * n_inputs
        e_tree = c_gate * _tech.G_FA * v2 * words * f_tree * cc_acc * alpha
    else:
        e_adc = np.zeros_like(macs)
        n_tree, b_tree = d2, bw
        f_tree = _tech.adder_tree_full_adders(n_tree, b_tree)
        occupancy = np.minimum(1.0, rows_drv / max(d2 * m, 1))
        cc_acc = macro.cc_bs * m * n_inputs
        e_tree = (c_gate * _tech.G_FA * v2 * words * f_tree * occupancy
                  * cc_acc * alpha)

    if macro.analog:
        cc_bs = macro.cc_bs * n_inputs
        e_dac = _tech.dac_energy_fj(macro.dac_res, macro.vdd) * rows_drv \
            * cc_bs
    else:
        e_dac = np.zeros_like(macs)

    # OS pass-boundary conversion phases (AIMC only; WS lanes add +0.0,
    # which is a bitwise no-op on the non-negative energy columns).
    if macro.analog and np.any(schedule_os):
        os_mask = np.broadcast_to(
            np.asarray(schedule_os, dtype=bool), n_inputs.shape)
        e_adc = e_adc + np.where(
            os_mask,
            _tech.adc_energy_fj(macro.adc_res, macro.vdd)
            * words * weight_loads / macro.cols_per_adc, 0.0)
        e_dac = e_dac + np.where(
            os_mask,
            _tech.dac_energy_fj(macro.dac_res, macro.vdd)
            * rows_drv * weight_loads, 0.0)

    bits_written = weight_loads * rows_drv * words * bw
    e_write = WRITE_CINV_FACTOR * tp.c_inv_ff * v2 * bits_written

    return EnergyBreakdownBatch(
        e_wl=np.asarray(e_wl, dtype=np.float64),
        e_bl=np.asarray(e_bl, dtype=np.float64),
        e_logic=e_logic, e_adc=e_adc,
        e_adder_tree=e_tree,
        e_dac=np.asarray(e_dac, dtype=np.float64),
        e_weight_write=np.asarray(e_write, dtype=np.float64), macs=macs)


# --------------------------------------------------------------------------- #
# grid (design x candidate) evaluation, JAX-jitted                              #
# --------------------------------------------------------------------------- #
# The design axis (see ``designs.MacroBatch``) broadcasts against the
# candidate axis: per-design constants enter as (D, 1) columns, per-tile
# arguments as (1, C) rows (or full (D, C) grids), and one fused XLA pass
# prices the whole lattice.
#
# Bitwise contract with the scalar oracle: the jitted kernel below is
# deliberately *addition-free* in float — every float output is a pure
# product/division/min/max/where chain, which XLA:CPU evaluates exactly
# like NumPy.  (Float add-of-product expressions are NOT safe under XLA,
# which contracts ``a*b + c`` into a fused multiply-add; the summations
# of Eq. 1/7 therefore live in ``EnergyBreakdownBatch``'s properties,
# evaluated on the returned NumPy arrays in the scalar association.)

_GRID_KERNEL = None          # lazily-built jax.jit closure
_RAW_GRID_KERNEL = None      # the unjitted kernel fn (shared with the
                             # reduced route's fused kernel)

#: dispatch/compile bookkeeping for the fused grid kernel.  jax caches
#: compiled executables per argument-shape signature, so the number of
#: distinct signatures seen is a faithful proxy for XLA compile count —
#: the quantity the workload-axis fused sweep exists to minimize
#: (``BENCH_sweep.json`` records both).  Counts live in the
#: process-global metrics registry (``repro.obs``, ``energy.kernel.*``);
#: only the shape *set* stays module-local (the registry holds its
#: cardinality as a gauge).
_C_KERNEL_CALLS = obs.counter("energy.kernel.calls")
_G_KERNEL_SHAPES = obs.gauge("energy.kernel.distinct_shapes")
_GRID_KERNEL_SHAPES: set[tuple] = set()


def grid_kernel_info() -> dict[str, int]:
    """Fused-kernel dispatch stats: total ``calls`` and
    ``distinct_shapes`` (compile-count proxy) since the last reset.  A
    view over the registry's ``energy.kernel.*`` metrics."""
    return {"calls": _C_KERNEL_CALLS.value,
            "distinct_shapes": len(_GRID_KERNEL_SHAPES)}


def grid_kernel_reset() -> None:
    obs.reset("energy.kernel.")
    _GRID_KERNEL_SHAPES.clear()


def _raw_grid_kernel():
    """The pure elementwise kernel fn (built once, jit-agnostic)."""
    global _RAW_GRID_KERNEL
    if _RAW_GRID_KERNEL is None:
        import jax.numpy as jnp

        def kernel(analog, mmux1, rows, d1, bw, m, cc_bs,
                   e_wl_line, e_bl_word, p_logic, adc_e, denom_adc,
                   cols_per_adc, f_tree_a, f_tree_d, p_tree, denom_occ,
                   dac_e, p_write,
                   n_inputs, rows_used, cols_used, weight_loads, sched_os,
                   alpha):
            macs = n_inputs.astype(jnp.float64) * rows_used * cols_used
            rows_drv = jnp.minimum(rows_used, rows)
            words = jnp.minimum(cols_used, d1)
            mux_rows = jnp.ceil(rows_drv / m)

            # E_cell (Eq. 3-5): cc_prech and the wordline count are the
            # only branch-dependent factors.
            cc_prech = jnp.where(
                analog, cc_bs * n_inputs,
                jnp.where(mmux1, weight_loads, m * n_inputs))
            wl_rows = jnp.where(analog | mmux1, rows_drv, mux_rows)
            e_wl = e_wl_line * wl_rows * cc_prech * alpha
            e_bl = e_bl_word * words * cc_prech * alpha

            # E_logic (Eq. 6), DIMC only.
            e_logic = jnp.where(analog, 0.0, p_logic * macs * alpha)

            # E_ADC (Eq. 8), AIMC only.
            conversions = bw * (macs / denom_adc)
            e_adc = jnp.where(analog, adc_e * conversions / cols_per_adc, 0.0)

            # E_adder_tree (Eq. 9-10).
            cc_acc_a = cc_bs * n_inputs
            e_tree_a = p_tree * words * f_tree_a * cc_acc_a * alpha
            occupancy = jnp.minimum(1.0, rows_drv / denom_occ)
            cc_acc_d = (cc_bs * m) * n_inputs
            e_tree_d = (p_tree * words * f_tree_d * occupancy
                        * cc_acc_d * alpha)
            e_tree = jnp.where(analog, e_tree_a, e_tree_d)

            # E_DAC (Eq. 11), AIMC only.
            e_dac = jnp.where(analog,
                              dac_e * rows_drv * (cc_bs * n_inputs), 0.0)

            # OS pass-boundary conversion phases (AIMC only).  Returned
            # as separate masked terms: the scalar association
            # ``e_adc + extra`` is an addition, which must happen
            # outside the kernel to stay safe from FMA contraction.
            os_analog = analog & sched_os
            x_adc = jnp.where(
                os_analog, adc_e * words * weight_loads / cols_per_adc, 0.0)
            x_dac = jnp.where(
                os_analog, dac_e * rows_drv * weight_loads, 0.0)

            # weight (re)write extension
            bits_written = weight_loads * rows_drv * words * bw
            e_write = p_write * bits_written
            return (e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write,
                    macs, x_adc, x_dac)

        _RAW_GRID_KERNEL = kernel
    return _RAW_GRID_KERNEL


def _grid_kernel():
    global _GRID_KERNEL
    if _GRID_KERNEL is None:
        import jax

        from .compilecache import enable_compilation_cache
        enable_compilation_cache()
        _GRID_KERNEL = jax.jit(_raw_grid_kernel())
    return _GRID_KERNEL


def tile_energy_grid(designs, n_inputs, rows_used, cols_used,
                     weight_loads: np.ndarray | int = 1,
                     alpha: float = DEFAULT_ALPHA,
                     schedule_os: np.ndarray | bool = False
                     ) -> EnergyBreakdownBatch:
    """Vectorized :func:`tile_energy` over a (design x tile) lattice.

    ``designs`` is a :class:`repro.core.designs.MacroBatch` of D macro
    design points; the tile arguments are integer arrays broadcastable
    to a common (..., C) shape, which is crossed with the design axis
    into (D, C) outputs.  ``schedule_os`` marks output-stationary tile
    columns (bool, broadcastable against the tile axis).  One fused
    ``jax.jit`` pass (on whatever backend JAX finds; float64 via
    ``jax.enable_x64``) prices the lattice; the result is
    bitwise identical to running the scalar oracle at every
    (design, tile) pair — the same contract ``tile_energy_batch``
    honours per macro, extended over designs.

    Leading layer axis: tile arguments may also be 2-D ``(L, C)``
    stacks (one row per layer of a padded workload lattice), in which
    case the design axis is inserted *between* the layer and candidate
    axes and every output is ``(L, D, C)``.  The kernel is purely
    elementwise, so each ``[l, d, c]`` entry is bitwise what the 1-D
    call on layer ``l``'s row alone would produce — the workload-fused
    sweep (``dse.sweep``/``sweep_networks``) relies on this to price a
    whole network in one compile.
    """
    import jax

    n_inputs = np.atleast_1d(np.asarray(n_inputs, dtype=np.int64))
    rows_used = np.atleast_1d(np.asarray(rows_used, dtype=np.int64))
    cols_used = np.atleast_1d(np.asarray(cols_used, dtype=np.int64))
    weight_loads = np.broadcast_to(
        np.asarray(weight_loads, dtype=np.int64), n_inputs.shape)
    sched_os = np.broadcast_to(
        np.asarray(schedule_os, dtype=bool), n_inputs.shape)
    # 1-D tile args broadcast straight against the (D, 1) design columns;
    # layer-stacked (..., L, C) args get the design axis spliced in
    # before the candidate axis.
    tile = (lambda a: a) if n_inputs.ndim == 1 else (lambda a: a[..., None, :])

    _C_KERNEL_CALLS.inc()
    _GRID_KERNEL_SHAPES.add((n_inputs.shape, len(designs.rows)))
    _G_KERNEL_SHAPES.set(len(_GRID_KERNEL_SHAPES))

    cst = _design_constants(designs)
    with jax.enable_x64(True):
        parts = _grid_kernel()(
            *(cst[k][:, None] for k in _RAW_DESIGN_ARGS),
            tile(n_inputs), tile(rows_used), tile(cols_used),
            tile(weight_loads), tile(sched_os), alpha)
        parts = tuple(np.asarray(p, dtype=np.float64) for p in parts)
    (e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, macs,
     x_adc, x_dac) = parts
    # OS conversion-phase terms fold in with the scalar association
    # (``e_adc + extra``); WS/DIMC lanes carry masked +0.0 — a bitwise
    # no-op on the non-negative energy columns.
    if sched_os.any():
        e_adc = e_adc + x_adc
        e_dac = e_dac + x_dac
    parts = (e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, macs)
    # design-independent fields (e.g. macs) come back (C,); give every
    # field the full (D, C) face so indexing is uniform.
    shape = np.broadcast_shapes(*(p.shape for p in parts))
    return EnergyBreakdownBatch(*(np.broadcast_to(p, shape) for p in parts))


# --------------------------------------------------------------------------- #
# the reduced sweep route: grid kernel, terms and argmin on the device         #
# --------------------------------------------------------------------------- #
#: finite masked-lane sentinels for the fused argmin (shared with the
#: host reference in ``dse``).  Illegal and padded lanes never carry
#: inf/NaN: their well-defined finite garbage is replaced by the largest
#: representable value of the objective dtype, which any real candidate
#: cost undercuts — so the argmin stays FMA-safe (no 0*inf / inf-inf
#: patterns for XLA or NumPy to mangle) and tie-breaks are untouched
#: (every (layer, design) pair has at least one legal lane: the all-ones
#: mapping is always legal).
SENTINEL_F64 = np.float64(np.finfo(np.float64).max)
SENTINEL_I64 = np.int64(np.iinfo(np.int64).max)

#: the reduced route's jit caches: ``has_os -> fused grid-and-terms
#: closure`` and ``(objective, n_segments) -> argmin closure``.
#:
#: WHY TWO EXECUTABLES: XLA:CPU contracts ``a*b + c`` into a fused
#: multiply-add during LLVM codegen whenever a float product feeds an
#: add inside one compiled module — and ``lax.optimization_barrier``
#: does NOT stop it (measured on this backend: identical 1-ULP drift
#: with and without the barrier; a double ``bitcast_convert_type``
#: fence gets folded away too).  Splitting at the executable boundary
#: is the one fence codegen cannot see through, so each bucket runs one
#: chain of two: the *terms* executable (:func:`_reduced_fused_kernel`:
#: the grid kernel, the OS fold, the active-macro scaling and the
#: traffic products) is addition-free in float except for uncontractable
#: adds (see below), and the *argmin* executable
#: (:func:`_reduce_argmin_kernel`) consumes its materialized term
#: buffers as program parameters, so its chained adds have no producer
#: multiply in scope.  Both dispatches stay asynchronous and the
#: intermediate term buffers never leave the device.
#:
#: WHY THE TERMS EXECUTABLE IS SAFE: the raw grid kernel body
#: (:func:`_raw_grid_kernel`) contains NO float additions — every
#: energy term is a chain of multiplies, divides and selects — so
#: fusing the scaling and traffic *products* into the same module
#: leaves nothing for LLVM to contract.  The OS fold adds
#: (``e_adc + x_adc`` / ``e_dac + x_dac``) are the only in-module adds,
#: and both operands terminate in ``fdiv`` or ``select`` instructions
#: (never a bare ``fmul``), while the folded sums feed *multiplies* —
#: FMA contraction needs a multiply feeding an add, so neither side of
#: the fold can contract.  The add CHAIN (the objective total) is what
#: must stay behind the executable boundary.
_REDUCED_FUSED_KERNELS: dict = {}
_REDUCE_ARGMIN_KERNELS: dict = {}

#: Packed arguments of the reduced route.  Each host array handed to a
#: jit call is its own host-to-device transfer (about 0.1 ms apiece on a
#: TPU v5e host), so the route's arguments cross as a few row-packed
#: blocks: the per-design ``(rows, D)`` pair below, put once per sweep
#: (:func:`put_design_block`), and one int64 ``(rows, Ctot)`` lane block
#: per bucket.  Every column keeps its dtype — floats ride the float64
#: block, ints and bools the int64 ones (bools as 0/1, restored inside
#: the kernels) — so the kernels only slice rows and run the same float
#: ops as on loose arguments.  ``seg_starts`` holds the S segment starts
#: padded to the lane width (every segment spans at least one lane).
_DESIGN_F64_ROWS = ("e_wl_line", "e_bl_word", "p_logic", "adc_e",
                    "f_tree_a", "f_tree_d", "p_tree", "dac_e", "p_write",
                    "per_bit", "per_bit_spill", "alpha")
_DESIGN_I64_ROWS = ("analog", "mmux1", "rows", "d1", "bw", "m", "cc_bs",
                    "denom_adc", "cols_per_adc", "denom_occ",
                    "cc_per_input", "design_class")
_LANE_ROWS = ("n_inputs", "rows_used", "cols_used", "weight_loads",
              "schedule_os", "active_macros", "weight_tiles",
              "weight_bits", "input_bits", "output_bits", "psum_bits",
              "off_chip", "wt_ipt", "write_cycles", "seg_ids",
              "seg_starts")
_BOOL_ROWS = frozenset(("analog", "mmux1", "schedule_os", "off_chip"))
#: the raw grid kernel's design columns, in its parameter order
_RAW_DESIGN_ARGS = ("analog", "mmux1", "rows", "d1", "bw", "m", "cc_bs",
                    "e_wl_line", "e_bl_word", "p_logic", "adc_e",
                    "denom_adc", "cols_per_adc", "f_tree_a", "f_tree_d",
                    "p_tree", "denom_occ", "dac_e", "p_write")

#: host arrays handed to the device by the reduced route: the design
#: block's two at each put, and each bucket's dispatch (its lane block
#: and ``legal_rows``).
#: Kept with the sweep's counters, so ``dse.cache_clear`` resets it.
_C_H2D = obs.counter("dse.h2d_arrays")


def _pack(names, values: dict, dtype, n: int) -> np.ndarray:
    """Stack ``values[name]`` (each broadcastable to ``(n,)``) as the
    rows of one ``(len(names), n)`` block.  A float value never enters
    an int block nor an int one the float block: that would change the
    op chain the kernels run."""
    kinds = "f" if np.dtype(dtype).kind == "f" else "biu"
    out = np.empty((len(names), n), dtype=dtype)
    for r, name in enumerate(names):
        v = np.asarray(values[name])
        if v.dtype.kind not in kinds:
            raise TypeError(f"{name} ({v.dtype}) cannot ride a {out.dtype} "
                            f"block")
        out[r] = v
    return out


class _Rows:
    """Named rows of packed blocks inside a kernel, bools restored.

    ``row`` gives a lane row as (1, n), which broadcasts against the
    (D, 1) design columns exactly as a (n,) row does; ``col`` a design
    row as (n, 1), sliced from the block transposed once.  Each is
    sliced once, with ``lax`` primitives, so the kernel's trace stays
    close to its size on loose arguments.
    """

    def __init__(self, *blocks):
        self._at = {name: (block, r) for block, names in blocks
                    for r, name in enumerate(names)}
        self._memo: dict = {}

    def _get(self, key, make):
        if key not in self._memo:
            self._memo[key] = make()
        return self._memo[key]

    def _bool(self, name, x):
        from jax import lax
        # bool(x) is x != 0
        return (lax.convert_element_type(x, np.bool_)
                if name in _BOOL_ROWS else x)

    def row(self, name):
        """Row ``name`` as (1, n)."""
        from jax import lax
        block, r = self._at[name]
        return self._get(("row", name), lambda: self._bool(
            name, lax.slice_in_dim(block, r, r + 1)))

    def col(self, name):
        """Row ``name`` as an (n, 1) column."""
        from jax import lax
        block, r = self._at[name]
        t = self._get(("t", id(block)),
                      lambda: lax.transpose(block, (1, 0)))
        return self._get(("col", name), lambda: self._bool(
            name, lax.slice_in_dim(t, r, r + 1, axis=1)))

    def vec(self, name):
        """Row ``name`` as (n,)."""
        from jax import lax
        x = self.row(name)
        return self._get(("vec", name),
                         lambda: lax.reshape(x, x.shape[1:]))

    def first(self, name):
        """The first entry of row ``name``, a scalar."""
        from jax import lax
        block, r = self._at[name]
        return self._get(("first", name), lambda: lax.reshape(
            lax.slice(block, (r, 0), (r + 1, 1)), ()))


@dataclasses.dataclass(frozen=True)
class DesignBlock:
    """The reduced route's per-design arguments, on the device.

    ``f64`` holds :data:`_DESIGN_F64_ROWS` and ``i64``
    :data:`_DESIGN_I64_ROWS`, each ``(rows, D)``.  ``design_class`` is
    the host copy of the classes the block holds: every bucket priced
    with the block must carry the same.
    """

    f64: object                  # jax float64 (len(_DESIGN_F64_ROWS), D)
    i64: object                  # jax int64 (len(_DESIGN_I64_ROWS), D)
    design_class: np.ndarray     # (D,) int


def put_design_block(designs, design_class, *, alpha: float, per_bit,
                     per_bit_spill, cc_per_input) -> DesignBlock:
    """Put the per-design arguments of every bucket of one sweep over
    ``designs`` on the device: :func:`_design_constants`, the traffic
    rates ``per_bit`` / ``per_bit_spill`` and ``cc_per_input`` (each
    (D,) or a scalar), ``alpha`` and each design's legality class."""
    import jax

    n = len(design_class)
    rows = dict(_design_constants(designs), alpha=alpha, per_bit=per_bit,
                per_bit_spill=per_bit_spill, cc_per_input=cc_per_input,
                design_class=design_class)
    f64 = _pack(_DESIGN_F64_ROWS, rows, np.float64, n)
    i64 = _pack(_DESIGN_I64_ROWS, rows, np.int64, n)
    # outside enable_x64 the put would demote both blocks to 32 bits
    with jax.enable_x64(True):
        f64, i64 = jax.device_put((f64, i64))
    _C_H2D.inc(2)
    return DesignBlock(f64=f64, i64=i64,
                       design_class=np.asarray(design_class))


def _terms(parts, f, lanes, has_os: bool):
    """The terms half of :func:`_reduced_fused_kernel`: OS fold +
    active-macro scaling + traffic products.

    ``parts`` are the raw grid kernel's seven energy terms and its two
    OS extras; ``f`` and ``lanes`` give the float design rows and the
    lane rows (:class:`_Rows`).  Reproduces the host reference's per-term
    float ops exactly: the fold (``e_adc + x_adc`` on raw kernel
    outputs, before scaling — adds whose operands end in
    ``fdiv``/``select``, uncontractable), the two-multiply ``(x *
    active_macros) * weight_tiles`` scaling, and the four
    ``memory.traffic_terms`` products.  Returns the eleven term grids;
    no float term is ever added to another here.
    """
    import jax.numpy as jnp

    from .memory import traffic_terms
    e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, x_adc, x_dac = parts
    if has_os:
        e_adc = e_adc + x_adc
        e_dac = e_dac + x_dac

    def scale2(x):
        return (x * lanes.row("active_macros")) * lanes.row("weight_tiles")

    terms = [scale2(p) for p in
             (e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write)]
    terms += list(traffic_terms(
        jnp, f.col("per_bit"), f.col("per_bit_spill"),
        lanes.row("off_chip"), lanes.row("weight_bits"),
        lanes.row("input_bits"), lanes.row("output_bits"),
        lanes.row("psum_bits")))
    return tuple(terms)


def _reduced_fused_kernel(has_os: bool):
    """The first executable of each bucket: the grid kernel and the
    terms (:func:`_terms`) in ONE jit module, on the packed arguments
    ``(f64, i64, lanes)``.

    Composes :func:`_raw_grid_kernel` — fed (D, 1) design columns and
    (Ctot,) lane rows sliced from the blocks — with :func:`_terms`, so
    the grid kernel's ten (D, C) float64 intermediates are never
    materialized as buffers between executables — for a full
    4M-element bucket that saves ~640 MB of memory traffic per dispatch
    plus one compile.

    Bitwise safety (see the cache-block comment above): the raw kernel
    body has no float adds, the OS fold adds operands end in
    ``fdiv``/``select`` and their sums feed multiplies, so the module
    exposes no ``fmul``→``fadd`` edge for LLVM to contract — every float
    op lands exactly as in the host reference's NumPy chain
    (property-pinned in ``tests/core/test_reduced_sweep.py``).  Row
    slices add no float arithmetic.
    """
    fn = _REDUCED_FUSED_KERNELS.get(has_os)
    if fn is None:
        import jax

        from .compilecache import enable_compilation_cache
        enable_compilation_cache()
        raw = _raw_grid_kernel()

        def kernel(f64, i64, lanes):
            d = _Rows((f64, _DESIGN_F64_ROWS), (i64, _DESIGN_I64_ROWS))
            ln = _Rows((lanes, _LANE_ROWS))
            parts = raw(*(d.col(k) for k in _RAW_DESIGN_ARGS),
                        *(ln.row(k) for k in ("n_inputs", "rows_used",
                                              "cols_used", "weight_loads",
                                              "schedule_os")),
                        d.first("alpha"))
            return _terms(parts[:7] + parts[8:], d, ln, has_os)

        fn = jax.jit(kernel)
        _REDUCED_FUSED_KERNELS[has_os] = fn
    return fn


def _reduce_argmin_kernel(objective: str, n_segments: int):
    """The second executable of each bucket: the exact scalar add
    association + masked argmin.

    The eleven term grids enter as program parameters, so the chained
    adds below — the same ``(((e_wl+e_bl)+e_logic)+(e_adc+e_tree))+...``
    / ``((w+i)+o)+p`` association ``dse._price_buckets`` runs in NumPy
    — have no producer multiply for LLVM to contract with.  Then the
    int64 design block (``cc_per_input``, ``design_class``), the lane
    block (``wt_ipt``, ``write_cycles``, ``seg_ids``) and
    ``legal_rows``.  Cycles are int64 (exact on device); the objective
    column replaces illegal and padded lanes with the finite sentinels.
    Legality arrives per class — ``legal_rows`` (U, Ctot) and each
    design's row ``design_class`` — and is gathered to (D, Ctot) here
    on the device, so no per-design mask crosses from the host.

    The per-segment argmin runs as two ``segment_min`` passes over the
    lane axis instead of one ``jnp.argmin`` per static segment slice —
    an S-sliced module took XLA:CPU ~1 s to compile for a 29-segment
    bucket (dominating the cold sweep wall) where the segment form
    compiles in ~0.1 s and re-specializes only on the segment *count*
    and array shapes, not the bounds, so same-shaped buckets share the
    executable.  Bitwise: ``min`` is exact and order-free, and "first
    lane whose value equals its segment min" is precisely the first
    minimum — ``np.argmin``'s tie-break.  Pad lanes carry segment id
    ``S`` (a dummy row sliced off before returning), so they cannot
    perturb any real segment even as sentinels.
    """
    key = (objective, n_segments)
    fn = _REDUCE_ARGMIN_KERNELS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax import lax

        from .compilecache import enable_compilation_cache
        enable_compilation_cache()

        def kernel(s_wl, s_bl, s_logic, s_adc, s_tree, s_dac, s_write,
                   m_w, m_i, m_o, m_p, i64, lanes, legal_rows):
            dz = _Rows((i64, _DESIGN_I64_ROWS))
            ln = _Rows((lanes, _LANE_ROWS))
            legal = legal_rows[dz.vec("design_class")]
            total = s_wl + s_bl
            total = total + s_logic
            total = total + (s_adc + s_tree)
            total = total + s_dac
            total = total + s_write
            mem_total = m_w + m_i
            mem_total = mem_total + m_o
            mem_total = mem_total + m_p
            total = total + mem_total
            cycles = (ln.row("wt_ipt") * dz.col("cc_per_input")
                      + ln.row("write_cycles"))
            if objective == "energy":
                col = jnp.where(legal, total, SENTINEL_F64)
            elif objective == "latency":
                col = jnp.where(legal, cycles, SENTINEL_I64)
            else:                                 # edp
                col = jnp.where(legal, total * cycles, SENTINEL_F64)
            col_t = col.T                          # (Ctot, D), lanes lead
            seg_ids = ln.vec("seg_ids")
            seg_min = jax.ops.segment_min(
                col_t, seg_ids, num_segments=n_segments + 1,
                indices_are_sorted=True)           # (S+1, D)
            lane = jnp.arange(col_t.shape[0], dtype=jnp.int64)[:, None]
            first = jax.ops.segment_min(
                jnp.where(col_t == seg_min[seg_ids], lane, SENTINEL_I64),
                seg_ids, num_segments=n_segments + 1,
                indices_are_sorted=True)[:n_segments]  # (S, D) global lane
            seg_starts = lax.slice_in_dim(ln.row("seg_starts"), 0,
                                          n_segments, axis=1)  # (1, S)
            best = first - seg_starts.T            # within-segment index
            d = jnp.arange(total.shape[0])[None, :]
            return best, total[d, first], cycles[d, first]

        fn = jax.jit(kernel)
        _REDUCE_ARGMIN_KERNELS[key] = fn
    return fn


def reduce_objective_grid(*, block: DesignBlock, objective: str,
                          seg_bounds: tuple, has_os: bool, n_inputs,
                          rows_used, cols_used, weight_loads, schedule_os,
                          active_macros, weight_tiles, wt_ipt,
                          write_cycles, weight_bits, input_bits,
                          output_bits, psum_bits, off_chip, legal_rows,
                          design_class):
    """The reduced sweep's whole device chain: grid kernel + fold +
    scale + traffic + sentinel-masked per-segment argmin,
    returning ``(best_idx, total, cycles)`` as (S, D) jax arrays — S
    segment rows of (D,) winners, the only data that ever reaches the
    host.

    Per-design arguments come from ``block`` (:func:`put_design_block`,
    already on the device); the lane columns, each (Ctot,) or
    broadcastable to it, are packed into one int64 lane block and put
    once for both executables.  Legality is ``mapping.NetworkGrid``'s
    compact pair: ``legal_rows`` (U, Ctot), one row per legality class,
    handed over as it is, and ``design_class`` (D,), which must be the
    block's.  So a bucket hands the device two host arrays.

    Two executables: the grid kernel and the term products in ONE
    (:func:`_reduced_fused_kernel` — no ten-grid materialization
    between them), then the argmin (:func:`_reduce_argmin_kernel`),
    bitwise identical to the host reference.

    The dispatch is asynchronous (nothing is blocked on here); callers
    pipeline over it and attribute device time where they synchronize.
    ``energy.kernel.calls`` advances one per bucket exactly like
    :func:`tile_energy_grid`, and the reduction registers its own
    distinct kernel-shape entry (the compile-count proxy — it re-traces
    per (lane count, segment count, objective)).
    """
    import jax

    if not np.array_equal(design_class, block.design_class):
        raise ValueError("design_class differs from the design block's")
    n_classes, lanes = legal_rows.shape
    n_designs = len(design_class)
    _GRID_KERNEL_SHAPES.add(
        ((lanes,), n_designs, "reduce", objective, len(seg_bounds), has_os,
         n_classes))
    _G_KERNEL_SHAPES.set(len(_GRID_KERNEL_SHAPES))
    argmin_k = _reduce_argmin_kernel(objective, len(seg_bounds))
    # lane -> segment id, pads (the tail past the last bound) mapped to
    # the dummy segment S the kernel slices off
    widths = [s1 - s0 for s0, s1 in seg_bounds]
    seg_ids = np.repeat(np.arange(len(seg_bounds) + 1),
                        widths + [lanes - seg_bounds[-1][1]])
    seg_starts = np.zeros(lanes, dtype=np.int64)
    seg_starts[:len(seg_bounds)] = [s0 for s0, _ in seg_bounds]
    lane_block = _pack(_LANE_ROWS, dict(
        n_inputs=n_inputs, rows_used=rows_used, cols_used=cols_used,
        weight_loads=weight_loads, schedule_os=schedule_os,
        active_macros=active_macros, weight_tiles=weight_tiles,
        weight_bits=weight_bits, input_bits=input_bits,
        output_bits=output_bits, psum_bits=psum_bits, off_chip=off_chip,
        wt_ipt=wt_ipt, write_cycles=write_cycles, seg_ids=seg_ids,
        seg_starts=seg_starts),
        np.int64, lanes)

    with jax.enable_x64(True):
        lane_block = jax.device_put(lane_block)
        _C_H2D.inc(2)                       # the lane block, legal_rows
        _C_KERNEL_CALLS.inc()
        _GRID_KERNEL_SHAPES.add(((lanes,), n_designs))
        _G_KERNEL_SHAPES.set(len(_GRID_KERNEL_SHAPES))
        terms = _reduced_fused_kernel(has_os)(block.f64, block.i64,
                                              lane_block)
        return argmin_k(*terms, block.i64, lane_block, legal_rows)


def _design_constants(designs) -> dict[str, np.ndarray]:
    """Per-design scalar prefactors of Eq. 1-11, shape (D,).

    Computed in NumPy float64 with exactly the scalar oracle's
    left-to-right association, so the jitted kernel only ever sees the
    same floats :func:`tile_energy` works with.
    """
    tech = np.asarray(designs.tech_nm, dtype=np.float64)
    vdd = np.asarray(designs.vdd, dtype=np.float64)
    v2 = vdd * vdd
    c_inv = _tech.CINV_SLOPE_FF_PER_NM * tech + _tech.CINV_OFFSET_FF
    c_gate = _tech.GATE_CAP_FACTOR * c_inv
    bw = designs.bw
    d1, d2, m = designs.d1, designs.d2, designs.m_mux
    cc_bs = designs.cc_bs

    e_wl_line = c_inv * v2 * bw * d1
    e_bl_word = c_inv * v2 * bw * d2 * m
    # p_logic * macs * alpha == v2 * c_gate * g_mul * macs * alpha: the
    # scalar path's ((v2 * c_gate) * g_mul) prefix is design-constant.
    g_mul = bw.astype(np.float64) * cc_bs / designs.bi
    p_logic = v2 * c_gate * g_mul
    adc_e = (_tech.K1_ADC_FJ * designs.adc_res
             + _tech.K2_ADC_FJ * 4.0 ** designs.adc_res) * vdd * vdd
    dac_e = _tech.K3_DAC_FJ * designs.dac_res * vdd * vdd
    f_tree_a = _adder_tree_fa_arr(np.maximum(2, bw), designs.adc_res)
    f_tree_d = _adder_tree_fa_arr(d2, bw)
    p_tree = c_gate * _tech.G_FA * v2
    p_write = WRITE_CINV_FACTOR * c_inv * v2
    return dict(
        analog=np.asarray(designs.analog, dtype=bool),
        mmux1=np.asarray(m == 1, dtype=bool),
        rows=designs.rows, d1=d1, bw=bw, m=m, cc_bs=cc_bs,
        e_wl_line=e_wl_line, e_bl_word=e_bl_word, p_logic=p_logic,
        adc_e=adc_e, denom_adc=np.maximum(d2, 1),
        cols_per_adc=designs.cols_per_adc,
        f_tree_a=f_tree_a, f_tree_d=f_tree_d, p_tree=p_tree,
        denom_occ=np.maximum(d2 * m, 1), dac_e=dac_e, p_write=p_write)


def _adder_tree_fa_arr(n_inputs: np.ndarray, b_in: np.ndarray) -> np.ndarray:
    """Vectorized :func:`repro.core.tech.adder_tree_full_adders`."""
    n = n_inputs.astype(np.float64)
    b = b_in.astype(np.float64)
    with np.errstate(divide="ignore"):
        f = b * n + n - b - np.log2(n) - 1.0
    return np.where(n_inputs <= 1, 0.0, f)


def peak_energy(macro: IMCMacro, alpha: float = DEFAULT_ALPHA,
                n_inputs: int = 4096) -> EnergyBreakdown:
    """Peak-efficiency protocol: full array, weights loaded once, long
    input stream (matches how macro papers report TOP/s/W, Sec. III)."""
    tile = MacroTile(n_inputs=n_inputs, rows_used=macro.rows,
                     cols_used=macro.d1, weight_loads=1)
    bd = tile_energy(macro, tile, alpha=alpha)
    # Peak protocols exclude the one-off weight load.
    return dataclasses.replace(bd, e_weight_write=0.0)


def peak_tops_per_watt(macro: IMCMacro, alpha: float = DEFAULT_ALPHA) -> float:
    return peak_energy(macro, alpha=alpha).tops_per_watt


def peak_tops(macro: IMCMacro) -> float:
    """Peak throughput [TOP/s] across all macros."""
    return 2.0 * macro.macs_per_cycle * macro.n_macros * macro.f_clk_ghz * 1e-3


def peak_tops_per_mm2(macro: IMCMacro) -> float:
    return peak_tops(macro) / macro.area_mm2
