"""Spatial/temporal mapping of 8-nested-loop layers onto IMC macros
(paper Sec. II-A, Fig. 2).

Spatial unrolling rules from the paper:

* **columns** (D1, weight words per row): the K loop — irrelevant for
  inputs, so one input broadcast along a wordline feeds many outputs;
* **rows** (R, accumulation axis): the C / FX / FY loops — irrelevant
  for outputs, so products accumulate on the bitline / adder tree;
* **macros**: OX / OY / G (weight duplication across macros) and K
  (weight split, no duplication) — paper Sec. II-A & VI.

The temporal schedule is a pluggable :class:`repro.core.schedule.Schedule`
— a third lattice axis next to the mapping candidates and the macro
designs.  Weight-stationary (the IMC-natural choice) writes a weight
tile once and streams all B*OX*OY input vectors through it, spilling
partial sums to the outer memory when the accumulation depth C*FX*FY
exceeds the rows; output-stationary keeps the partials resident and
streams the weight tiles instead (see ``schedule.py`` for the cost
asymmetry between AIMC and DIMC).  Every engine below defaults to
weight-stationary only, preserving the historical behavior.

Batched evaluation
------------------
:func:`evaluate` prices ONE (layer, mapping) pair; the DSE prices the
whole candidate lattice.  :func:`candidate_batch` flattens a mapping
sequence into struct-of-arrays unroll factors (:class:`MappingBatch`)
and :func:`evaluate_batch` prices all of them in one vectorized NumPy
pass (:class:`MappingCostBatch`), built on
``energy.tile_energy_batch``.

Scalar-reference contract: :func:`evaluate` is the oracle.  The batched
path mirrors its arithmetic operation-for-operation (same tiling
counts, same left-to-right float association), so per-candidate costs
are bitwise identical and an argmin over the batch selects exactly the
mapping the scalar loop would (ties break to the first candidate in
enumeration order in both paths).  Enforced by
``tests/core/test_batched_parity.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterator, Mapping, Sequence

import numpy as np

from .. import obs
from .energy import (EnergyBreakdown, EnergyBreakdownBatch, MacroTile,
                     tile_energy, tile_energy_batch)
from .hardware import IMCMacro
from .schedule import (OS_CODE, WEIGHT_STATIONARY, WS_CODE, Schedule,
                       by_code as _schedule_by_code,
                       normalize as _normalize_schedules)
from .workloads import Layer

COL_DIMS = ("K",)
ROW_DIMS = ("C", "FX", "FY")
MACRO_DUP_DIMS = ("OX", "OY", "G")    # duplication: weights copied per macro
MACRO_SPLIT_DIMS = ("K",)             # split: different weights per macro


@dataclasses.dataclass(frozen=True)
class SpatialMapping:
    """Unroll factors per loop dim for each physical axis."""

    cols: Mapping[str, int]
    rows: Mapping[str, int]
    macros: Mapping[str, int]

    def col_unroll(self) -> int:
        return math.prod(self.cols.values()) if self.cols else 1

    def row_unroll(self) -> int:
        return math.prod(self.rows.values()) if self.rows else 1

    def macro_unroll(self) -> int:
        return math.prod(self.macros.values()) if self.macros else 1

    def unroll_of(self, dim: str) -> int:
        return (self.cols.get(dim, 1) * self.rows.get(dim, 1)
                * self.macros.get(dim, 1))

    def describe(self) -> str:
        fmt = lambda m: ",".join(f"{k}:{v}" for k, v in m.items()) or "-"
        return (f"cols[{fmt(self.cols)}] rows[{fmt(self.rows)}] "
                f"macros[{fmt(self.macros)}]")


def is_legal(layer: Layer, macro: IMCMacro, sm: SpatialMapping) -> bool:
    if sm.col_unroll() > macro.d1 or sm.row_unroll() > macro.rows:
        return False
    if sm.macro_unroll() > macro.n_macros:
        return False
    for dims, allowed in ((sm.cols, COL_DIMS), (sm.rows, ROW_DIMS),
                          (sm.macros, MACRO_DUP_DIMS + MACRO_SPLIT_DIMS)):
        for d, u in dims.items():
            if d not in allowed or u < 1:
                return False
    for d in set(list(sm.cols) + list(sm.rows) + list(sm.macros)):
        if sm.unroll_of(d) > layer.dim(d):
            return False
    return True


@dataclasses.dataclass(frozen=True)
class MappingCost:
    """Full cost of one layer under one (spatial mapping, schedule)."""

    mapping: SpatialMapping
    macro_energy: EnergyBreakdown        # datapath energy (Eq. 1-11)
    weight_tiles: int                    # distinct weight tiles written
    inputs_per_tile: int                 # input vectors streamed per tile
    cycles: float                        # latency in macro cycles
    spatial_utilization: float           # fraction of array cells doing MACs
    # outer-memory traffic in bits (memory.py prices it):
    weight_bits: float
    input_bits: float
    output_bits: float
    psum_bits: float
    schedule: Schedule = WEIGHT_STATIONARY   # temporal dataflow priced

    @property
    def total_traffic_bits(self) -> float:
        return self.weight_bits + self.input_bits + self.output_bits \
            + self.psum_bits


def evaluate(layer: Layer, macro: IMCMacro, sm: SpatialMapping,
             alpha: float | None = None,
             schedule: Schedule = WEIGHT_STATIONARY) -> MappingCost:
    """Cost one layer under one spatial mapping and temporal schedule."""
    from .energy import DEFAULT_ALPHA
    alpha = DEFAULT_ALPHA if alpha is None else alpha

    k_cols = sm.cols.get("K", 1)
    k_macros = sm.macros.get("K", 1)
    row_un = sm.row_unroll()
    dup_macros = math.prod(v for d, v in sm.macros.items()
                           if d in MACRO_DUP_DIMS) or 1

    # --- tiling counts --------------------------------------------------------
    n_k_tiles = math.ceil(layer.dim("K") / (k_cols * k_macros))
    n_acc_tiles = math.ceil(layer.accumulation_depth / row_un)
    # temporal iterations of the duplicated spatial dims
    n_spatial_temporal = 1
    spatial_total = 1
    for d in MACRO_DUP_DIMS:
        u = sm.macros.get(d, 1)
        n_spatial_temporal *= math.ceil(layer.dim(d) / u)
        spatial_total *= layer.dim(d)
    weight_tiles = n_k_tiles * n_acc_tiles            # per duplicated macro set
    inputs_per_tile = layer.dim("B") * n_spatial_temporal

    # --- per-tile energy (all macros of the duplicated set together) ----------
    # The schedule sets the reload count: WS writes the tile once, OS
    # streams it back in on every temporal input iteration.
    rows_used = min(row_un, layer.accumulation_depth)
    cols_used = min(k_cols, layer.dim("K"))
    weight_loads = schedule.weight_loads(inputs_per_tile)
    tile = MacroTile(n_inputs=inputs_per_tile, rows_used=rows_used,
                     cols_used=cols_used, weight_loads=weight_loads)
    active_macros = k_macros * dup_macros
    e_tile = tile_energy(macro, tile, alpha=alpha,
                         schedule=schedule).scaled(active_macros)
    macro_energy = e_tile.scaled(weight_tiles)

    # --- utilization -----------------------------------------------------------
    useful_macs = layer.macs
    occupied = (rows_used * cols_used * macro.bw * active_macros
                * weight_tiles * inputs_per_tile)
    capacity = (macro.rows * macro.cols * macro.n_macros
                * weight_tiles * inputs_per_tile)
    spatial_utilization = occupied / capacity

    # --- latency ---------------------------------------------------------------
    cc_per_input = (macro.cc_bs * macro.adc_share if macro.analog
                    else macro.cc_bs * macro.m_mux)
    # one row write per cycle, repeated per schedule-mandated reload
    write_cycles = rows_used * weight_tiles * weight_loads
    cycles = weight_tiles * inputs_per_tile * cc_per_input + write_cycles

    # --- outer-memory traffic ----------------------------------------------------
    # Weights: each element enters the macro once under WS (refetched per
    # input iteration under OS), duplicated dup_macros times (paper:
    # OX/OY/G duplication cost).
    weight_bits = (layer.weight_elems * layer.w_prec * dup_macros
                   * schedule.weight_refetch(inputs_per_tile))
    # Inputs: WS refetches once per temporal K tile (columns already
    # share); OS fetches each input exactly once.
    input_bits = (layer.input_elems * layer.i_prec
                  * schedule.input_refetch(n_k_tiles))
    # Outputs written once...
    output_bits = layer.output_elems * layer.psum_prec
    # ...plus partial-sum spill/refill when the accumulation is split
    # (WS only; OS keeps partials resident in the accumulators).
    psum_bits = (layer.output_elems * layer.psum_prec
                 * schedule.psum_transfers(n_acc_tiles))
    return MappingCost(
        mapping=sm, macro_energy=macro_energy, weight_tiles=weight_tiles,
        inputs_per_tile=inputs_per_tile, cycles=cycles,
        spatial_utilization=spatial_utilization, weight_bits=weight_bits,
        input_bits=input_bits, output_bits=output_bits, psum_bits=psum_bits,
        schedule=schedule)


# --------------------------------------------------------------------------- #
# mapping enumeration                                                          #
# --------------------------------------------------------------------------- #
def _unroll_candidates(dim_size: int, cap: int) -> list[int]:
    """Candidate unroll factors: powers of two plus the exact bounds."""
    cap = max(1, min(dim_size, cap))
    cands = {1, cap}
    p = 2
    while p < cap:
        cands.add(p)
        p *= 2
    if dim_size <= cap:
        cands.add(dim_size)
    return sorted(cands)


def enumerate_mappings(layer: Layer, macro: IMCMacro,
                       max_candidates: int = 4096) -> Iterator[SpatialMapping]:
    """Enumerate legal spatial mappings (bounded powers-of-two lattice)."""
    k = layer.dim("K")
    count = 0
    for k_col in _unroll_candidates(k, macro.d1):
        # rows: greedy lattice over C, FX, FY
        row_opts = []
        for c_un in _unroll_candidates(layer.dim("C"), macro.rows):
            rem = macro.rows // c_un
            for fx_un in _unroll_candidates(layer.dim("FX"), rem):
                rem2 = rem // fx_un
                for fy_un in _unroll_candidates(layer.dim("FY"), rem2):
                    row_opts.append({"C": c_un, "FX": fx_un, "FY": fy_un})
        for rows in row_opts:
            # macros: either split K further, or duplicate over OX/OY/G
            macro_opts: list[dict[str, int]] = [{}]
            if macro.n_macros > 1:
                for d in MACRO_DUP_DIMS:
                    for u in _unroll_candidates(layer.dim(d), macro.n_macros):
                        if u > 1:
                            macro_opts.append({d: u})
                for u in _unroll_candidates(
                        max(1, k // k_col), macro.n_macros):
                    if u > 1:
                        macro_opts.append({"K": u})
            for mac in macro_opts:
                sm = SpatialMapping(cols={"K": k_col}, rows=dict(rows),
                                    macros=mac)
                if is_legal(layer, macro, sm):
                    yield sm
                    count += 1
                    if count >= max_candidates:
                        return


# --------------------------------------------------------------------------- #
# batched (struct-of-arrays) evaluation                                        #
# --------------------------------------------------------------------------- #
#: macro-axis option codes stored in ``MappingBatch.mac_dim``.
_MAC_NONE = 0
_MAC_CODES = {d: i + 1 for i, d in enumerate(MACRO_DUP_DIMS)}   # OX/OY/G
_MAC_K = len(MACRO_DUP_DIMS) + 1
_MAC_NAMES = {v: k for k, v in _MAC_CODES.items()}


@dataclasses.dataclass
class MappingBatch:
    """N (spatial mapping, schedule) candidates for one layer, flattened
    to arrays.

    Built directly as struct-of-arrays in *exact* scalar-oracle order —
    ``enumerate_mappings`` order for the spatial axis, crossed
    mapping-outer / schedule-inner when more than one schedule is
    enabled — so an argmin index translates straight to the oracle's
    pick.  ``mapping_at(i)`` / ``schedule_at(i)`` materialize one
    candidate on demand (only the winner usually is); ``mappings``
    builds the whole spatial tuple for tests/debugging (each mapping
    appears once per enabled schedule).
    """

    k_cols: np.ndarray        # cols["K"] per candidate
    k_macros: np.ndarray      # macros.get("K", 1)
    c_un: np.ndarray          # rows["C"]
    fx_un: np.ndarray         # rows["FX"]
    fy_un: np.ndarray         # rows["FY"]
    row_un: np.ndarray        # c_un * fx_un * fy_un
    mac_dim: np.ndarray       # option code (_MAC_NONE / OX / OY / G / _MAC_K)
    mac_un: np.ndarray        # unroll of the chosen macro dim (1 if none)
    dup_macros: np.ndarray    # OX/OY/G macro unroll product (>= 1)
    n_spatial_temporal: np.ndarray  # prod_d ceil(dim_d / macro_unroll_d)
    schedule: np.ndarray | None = None   # Schedule.code per candidate

    def __post_init__(self) -> None:
        if self.schedule is None:
            self.schedule = np.full(len(self.k_cols), WS_CODE,
                                    dtype=np.int64)

    def __len__(self) -> int:
        return len(self.k_cols)

    def schedule_at(self, i: int) -> Schedule:
        return _schedule_by_code(int(self.schedule[i]))

    def mapping_at(self, i: int) -> SpatialMapping:
        code = int(self.mac_dim[i])
        if code == _MAC_NONE:
            mac: dict[str, int] = {}
        elif code == _MAC_K:
            mac = {"K": int(self.mac_un[i])}
        else:
            mac = {_MAC_NAMES[code]: int(self.mac_un[i])}
        return SpatialMapping(
            cols={"K": int(self.k_cols[i])},
            rows={"C": int(self.c_un[i]), "FX": int(self.fx_un[i]),
                  "FY": int(self.fy_un[i])},
            macros=mac)

    @property
    def mappings(self) -> tuple[SpatialMapping, ...]:
        return tuple(self.mapping_at(i) for i in range(len(self)))


def _with_schedule_axis(batch: MappingBatch,
                        schedules: Sequence[Schedule]) -> MappingBatch:
    """Cross a spatial candidate batch with the schedule axis, mapping
    outer / schedule inner — the scalar oracle's enumeration order, so
    argmin tie-breaks stay bitwise-faithful to the per-candidate loop.

    A single weight-stationary schedule (the default everywhere) is the
    identity; the ``max_candidates`` truncation is always applied to the
    *spatial* lattice before this expansion, matching the scalar
    generator's cap on mappings (schedules multiply inside the cap).
    """
    for s in schedules:
        if s.code not in (WS_CODE, OS_CODE):
            # The np.where selections in evaluate_batch/_grid only know
            # the builtin closed forms; pricing an unknown schedule as
            # WS would silently break the scalar-parity contract.
            raise NotImplementedError(
                f"batched engines only vectorize the builtin schedules "
                f"(ws/os); got {s.name!r} (code {s.code}) — use "
                f"engine='scalar' or vectorize its factor hooks here")
    if len(schedules) == 1 and schedules[0].code == WS_CODE:
        return batch
    codes = np.asarray([s.code for s in schedules], dtype=np.int64)
    s = len(codes)
    rep = lambda a: np.repeat(a, s)
    return MappingBatch(
        k_cols=rep(batch.k_cols), k_macros=rep(batch.k_macros),
        c_un=rep(batch.c_un), fx_un=rep(batch.fx_un),
        fy_un=rep(batch.fy_un), row_un=rep(batch.row_un),
        mac_dim=rep(batch.mac_dim), mac_un=rep(batch.mac_un),
        dup_macros=rep(batch.dup_macros),
        n_spatial_temporal=rep(batch.n_spatial_temporal),
        schedule=np.tile(codes, len(batch)))


def candidate_batch(layer: Layer, macro: IMCMacro,
                    max_candidates: int = 4096,
                    schedules=None) -> MappingBatch:
    """Flatten the legal-mapping lattice of ``layer`` on ``macro`` into a
    :class:`MappingBatch` without materializing per-candidate objects.

    Replicates the ``enumerate_mappings`` nesting (k_col outer, row
    lattice middle, macro option inner) with ``np.repeat``/``np.tile``;
    ``schedules`` (``schedule.normalize`` forms) crosses in the dataflow
    axis, schedule-minor.  Every lattice point is legal by construction
    (all factor lists are capped by both the loop bound and the physical
    axis; legality is schedule-independent), which
    ``tests/core/test_batched_parity.py`` cross-checks against the
    generator.
    """
    scheds = _normalize_schedules(schedules)
    k = layer.dim("K")
    kcs = _unroll_candidates(k, macro.d1)

    # --- row lattice (shared by every k_col) ----------------------------------
    rc, rfx, rfy = [], [], []
    for c_un in _unroll_candidates(layer.dim("C"), macro.rows):
        rem = macro.rows // c_un
        for fx_un in _unroll_candidates(layer.dim("FX"), rem):
            rem2 = rem // fx_un
            for fy_un in _unroll_candidates(layer.dim("FY"), rem2):
                rc.append(c_un)
                rfx.append(fx_un)
                rfy.append(fy_un)
    row_c = np.asarray(rc, dtype=np.int64)
    row_fx = np.asarray(rfx, dtype=np.int64)
    row_fy = np.asarray(rfy, dtype=np.int64)
    n_rows = len(row_c)

    # --- macro options: the OX/OY/G (duplication) part is k_col-independent ---
    dup_dim, dup_un = [_MAC_NONE], [1]
    if macro.n_macros > 1:
        for d in MACRO_DUP_DIMS:
            for u in _unroll_candidates(layer.dim(d), macro.n_macros):
                if u > 1:
                    dup_dim.append(_MAC_CODES[d])
                    dup_un.append(u)
    spatial_total = math.prod(layer.dim(d) for d in MACRO_DUP_DIMS)
    dup_nst = [spatial_total if c == _MAC_NONE else
               math.ceil(layer.dim(_MAC_NAMES[c]) / u)
               * (spatial_total // layer.dim(_MAC_NAMES[c]))
               for c, u in zip(dup_dim, dup_un)]

    chunks = []
    for k_col in kcs:
        mac_dim = list(dup_dim)
        mac_un = list(dup_un)
        mac_nst = list(dup_nst)
        if macro.n_macros > 1:
            for u in _unroll_candidates(max(1, k // k_col), macro.n_macros):
                if u > 1:
                    mac_dim.append(_MAC_K)
                    mac_un.append(u)
                    mac_nst.append(spatial_total)
        n_mac = len(mac_dim)
        # enumeration order: rows outer, macro option inner
        chunks.append((
            np.full(n_rows * n_mac, k_col, dtype=np.int64),
            np.repeat(row_c, n_mac), np.repeat(row_fx, n_mac),
            np.repeat(row_fy, n_mac),
            np.tile(np.asarray(mac_dim, dtype=np.int64), n_rows),
            np.tile(np.asarray(mac_un, dtype=np.int64), n_rows),
            np.tile(np.asarray(mac_nst, dtype=np.int64), n_rows),
        ))

    k_cols, c_un, fx_un, fy_un, mac_dim_a, mac_un_a, nst = (
        np.concatenate(parts)[:max_candidates]
        for parts in zip(*chunks))
    is_k = mac_dim_a == _MAC_K
    is_dup = (mac_dim_a != _MAC_NONE) & ~is_k
    return _with_schedule_axis(MappingBatch(
        k_cols=k_cols,
        k_macros=np.where(is_k, mac_un_a, 1),
        c_un=c_un, fx_un=fx_un, fy_un=fy_un,
        row_un=c_un * fx_un * fy_un,
        mac_dim=mac_dim_a, mac_un=mac_un_a,
        dup_macros=np.where(is_dup, mac_un_a, 1),
        n_spatial_temporal=nst), scheds)


# --------------------------------------------------------------------------- #
# grid (design x candidate) evaluation                                          #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class MappingGrid:
    """The union candidate lattice of one layer over D macro designs.

    Different designs have different legal-mapping lattices (the unroll
    caps depend on ``d1`` / ``rows`` / ``n_macros``), so the grid holds
    the *union* lattice as one flat :class:`MappingBatch` of C
    candidates plus per-design legality.  The union is ordered
    exactly like ``enumerate_mappings`` orders candidates (k_col outer,
    row triple middle, macro option inner, each axis ascending), and a
    design's legal subsequence *is* its own enumeration order — so a
    masked argmin over the candidate axis tie-breaks identically to the
    scalar oracle's first-wins loop, per design.

    Legality only depends on a design's ``(d1, rows, n_macros)``, so it
    is held per legality *class*: ``legal_rows`` has one (C,) row per
    class and ``design_class`` names each design's row.  ``legal`` is
    the (D, C) mask ``legal_rows[design_class]``, expanded on first
    read.
    """

    cand: MappingBatch        # union lattice, flat candidate axis (C,)
    legal_rows: np.ndarray    # (U, C) bool: candidate j legal in class u
    design_class: np.ndarray  # (D,) int32: legality class of design i

    @property
    def n_designs(self) -> int:
        return len(self.design_class)

    @functools.cached_property
    def legal(self) -> np.ndarray:
        """(D, C) bool: candidate j legal on design i."""
        return self.legal_rows[self.design_class]

    def __len__(self) -> int:
        return len(self.cand)

    def mappings_for(self, d: int) -> tuple[SpatialMapping, ...]:
        """Design ``d``'s legal candidates, in its enumeration order.
        With multiple schedules enabled each spatial mapping appears
        once per schedule (legality is schedule-independent)."""
        row = self.legal_rows[self.design_class[d]]
        return tuple(self.cand.mapping_at(int(j))
                     for j in np.flatnonzero(row))


def _pow2_member(u: np.ndarray, dim: int | np.ndarray,
                 cap: np.ndarray) -> np.ndarray:
    """Vectorized membership in ``_unroll_candidates(dim, cap)``.

    The generator emits {1} | {powers of two < cap'} | {cap'} | {dim if
    dim <= cap'} with cap' = max(1, min(dim, cap)); this predicate
    reproduces that set exactly for any broadcastable (u, dim, cap).
    """
    u = np.asarray(u, dtype=np.int64)
    cap2 = np.maximum(1, np.minimum(dim, cap))
    is_pow2 = (u & (u - 1)) == 0            # u >= 1 everywhere in the lattice
    return ((u == 1) | (u == cap2) | (is_pow2 & (u < cap2))
            | ((u == dim) & (dim <= cap2)))


def candidate_grid_loop(layer: Layer, designs,
                        max_candidates: int = 4096,
                        schedules=None) -> MappingGrid:
    """Reference (loop) builder for the union mapping lattice.

    The original Python-loop construction of :func:`candidate_grid`,
    kept verbatim as the enumeration-order oracle: the vectorized
    builder must reproduce its output bit-for-bit (every candidate
    field, the legality mask, the ``max_candidates`` truncation, the
    schedule crossing — property-tested in
    ``tests/core/test_lattice_vectorized.py``).  Never called on the
    hot path.
    """
    scheds = _normalize_schedules(schedules)
    k = layer.dim("K")
    d1s = sorted(set(int(v) for v in designs.d1))
    rows_vals = sorted(set(int(v) for v in designs.rows))
    nm_vals = sorted(set(int(v) for v in designs.n_macros))

    kcs = sorted({u for d1 in d1s for u in _unroll_candidates(k, d1)})

    triples: set[tuple[int, int, int]] = set()
    for rows in rows_vals:
        for c_un in _unroll_candidates(layer.dim("C"), rows):
            rem = rows // c_un
            for fx_un in _unroll_candidates(layer.dim("FX"), rem):
                rem2 = rem // fx_un
                for fy_un in _unroll_candidates(layer.dim("FY"), rem2):
                    triples.add((c_un, fx_un, fy_un))
    row_triples = sorted(triples)

    spatial_total = math.prod(layer.dim(d) for d in MACRO_DUP_DIMS)
    dup_opts: set[tuple[int, int]] = set()
    for nm in nm_vals:
        if nm <= 1:
            continue
        for d in MACRO_DUP_DIMS:
            for u in _unroll_candidates(layer.dim(d), nm):
                if u > 1:
                    dup_opts.add((_MAC_CODES[d], u))

    kc_l, c_l, fx_l, fy_l, mc_l, mu_l = [], [], [], [], [], []
    for k_col in kcs:
        mac_opts = [(_MAC_NONE, 1)] + sorted(dup_opts)
        ksplit_dim = max(1, k // k_col)
        ks: set[int] = set()
        for nm in nm_vals:
            if nm > 1:
                ks.update(u for u in _unroll_candidates(ksplit_dim, nm)
                          if u > 1)
        mac_opts += [(_MAC_K, u) for u in sorted(ks)]
        for (c_un, fx_un, fy_un) in row_triples:
            for code, u in mac_opts:
                kc_l.append(k_col)
                c_l.append(c_un)
                fx_l.append(fx_un)
                fy_l.append(fy_un)
                mc_l.append(code)
                mu_l.append(u)

    arr = lambda x: np.asarray(x, dtype=np.int64)
    k_cols, c_un, fx_un, fy_un = arr(kc_l), arr(c_l), arr(fx_l), arr(fy_l)
    mac_dim, mac_un = arr(mc_l), arr(mu_l)
    is_k = mac_dim == _MAC_K
    is_dup = (mac_dim != _MAC_NONE) & ~is_k
    dup_dim_size = np.ones(len(mac_dim), dtype=np.int64)
    nst = np.full(len(mac_dim), spatial_total, dtype=np.int64)
    for code, name in _MAC_NAMES.items():
        sel = mac_dim == code
        if not sel.any():
            continue
        dim_sz = layer.dim(name)
        dup_dim_size[sel] = dim_sz
        nst[sel] = (-(-dim_sz // mac_un[sel])) * (spatial_total // dim_sz)
    cand = MappingBatch(
        k_cols=k_cols, k_macros=np.where(is_k, mac_un, 1),
        c_un=c_un, fx_un=fx_un, fy_un=fy_un,
        row_un=c_un * fx_un * fy_un,
        mac_dim=mac_dim, mac_un=mac_un,
        dup_macros=np.where(is_dup, mac_un, 1),
        n_spatial_temporal=nst)

    # per-design legality, original form: the full (D, C) membership
    # test with no distinct-knob dedup (the vectorized builder dedups;
    # the oracle keeps the verbatim original cost and shape)
    d1_d = designs.d1[:, None]
    rows_d = designs.rows[:, None]
    nm_d = designs.n_macros[:, None]
    legal = _pow2_member(k_cols, k, d1_d)
    legal &= _pow2_member(c_un, layer.dim("C"), rows_d)
    cap_fx = rows_d // c_un
    legal &= _pow2_member(fx_un, layer.dim("FX"), cap_fx)
    legal &= _pow2_member(fy_un, layer.dim("FY"), cap_fx // fx_un)
    ksplit_dim = np.maximum(1, k // k_cols)
    mac_ok = np.where(
        mac_dim == _MAC_NONE, True,
        np.where(is_k, _pow2_member(mac_un, ksplit_dim, nm_d),
                 _pow2_member(mac_un, dup_dim_size, nm_d)))
    legal &= mac_ok
    legal &= np.cumsum(legal, axis=1) <= max_candidates
    cand = _with_schedule_axis(cand, scheds)
    if len(cand) != legal.shape[1]:
        legal = np.repeat(legal, len(scheds), axis=1)
    return MappingGrid(cand=cand, legal_rows=legal,
                       design_class=np.arange(len(legal), dtype=np.int32))


def _assemble_grid(layer: Layer, designs, scheds, max_candidates: int,
                   k_cols: np.ndarray, c_un: np.ndarray, fx_un: np.ndarray,
                   fy_un: np.ndarray, mac_dim: np.ndarray,
                   mac_un: np.ndarray) -> MappingGrid:
    """Shared tail of the loop/vectorized lattice builders: derived
    candidate columns, legality (computed and kept once per *distinct*
    legality-relevant design triple), ``max_candidates`` truncation, and
    the schedule crossing."""
    k = layer.dim("K")
    spatial_total = math.prod(layer.dim(d) for d in MACRO_DUP_DIMS)
    is_k = mac_dim == _MAC_K
    is_dup = (mac_dim != _MAC_NONE) & ~is_k
    dup_dim_size = np.ones(len(mac_dim), dtype=np.int64)
    nst = np.full(len(mac_dim), spatial_total, dtype=np.int64)
    for code, name in _MAC_NAMES.items():
        sel = mac_dim == code
        if not sel.any():
            continue
        dim_sz = layer.dim(name)
        dup_dim_size[sel] = dim_sz
        nst[sel] = (-(-dim_sz // mac_un[sel])) * (spatial_total // dim_sz)
    cand = MappingBatch(
        k_cols=k_cols, k_macros=np.where(is_k, mac_un, 1),
        c_un=c_un, fx_un=fx_un, fy_un=fy_un,
        row_un=c_un * fx_un * fy_un,
        mac_dim=mac_dim, mac_un=mac_un,
        dup_macros=np.where(is_dup, mac_un, 1),
        n_spatial_temporal=nst)

    # --- per-class legality: membership of every component -------------------
    # Legality only sees (d1, rows, n_macros); compute the mask on the
    # distinct triples (U rows, typically 10-100x fewer than D designs)
    # and keep it there: ``inv`` names each design's row.
    d1_a = np.asarray(designs.d1, dtype=np.int64)
    rows_a = np.asarray(designs.rows, dtype=np.int64)
    nm_a = np.asarray(designs.n_macros, dtype=np.int64)
    # pack the triple into one int64 key: 1-D unique sidesteps the
    # row-sort of np.unique(axis=0); uniq order is irrelevant because
    # the gather goes through ``inv`` either way
    key = (d1_a << 42) | (rows_a << 21) | nm_a
    uniq_key, first, inv = np.unique(key, return_index=True,
                                     return_inverse=True)
    d1_d = d1_a[first][:, None]
    rows_d = rows_a[first][:, None]
    nm_d = nm_a[first][:, None]
    legal = _pow2_member(k_cols, k, d1_d)
    legal &= _pow2_member(c_un, layer.dim("C"), rows_d)
    cap_fx = rows_d // c_un
    legal &= _pow2_member(fx_un, layer.dim("FX"), cap_fx)
    legal &= _pow2_member(fy_un, layer.dim("FY"), cap_fx // fx_un)
    ksplit_dim = np.maximum(1, k // k_cols)
    mac_ok = np.where(
        mac_dim == _MAC_NONE, True,
        np.where(is_k, _pow2_member(mac_un, ksplit_dim, nm_d),
                 _pow2_member(mac_un, dup_dim_size, nm_d)))
    legal &= mac_ok
    legal &= np.cumsum(legal, axis=1) <= max_candidates
    cand = _with_schedule_axis(cand, scheds)
    if len(cand) != legal.shape[1]:
        legal = np.repeat(legal, len(scheds), axis=1)
    return MappingGrid(cand=cand, legal_rows=legal,
                       design_class=inv.astype(np.int32))


def _unroll_pool(dim: int, caps: np.ndarray) -> np.ndarray:
    """Sorted superset of ``union(_unroll_candidates(dim, cap) for cap
    in caps)``: {1} | {powers of two <= the largest effective cap} |
    {each effective cap} | {dim}.  Values outside the true union are
    culled afterwards by the :func:`_pow2_member` membership test, so a
    superset is all the crossing builders need."""
    caps = np.asarray(caps, dtype=np.int64).ravel()
    if len(caps) == 0:
        return np.asarray([1], dtype=np.int64)
    caps_eff = np.maximum(1, np.minimum(dim, caps))
    hi = int(caps_eff.max())
    pows = (1 << np.arange(max(1, hi).bit_length(), dtype=np.int64))
    return np.unique(np.concatenate([
        np.asarray([1, dim], dtype=np.int64), pows, caps_eff]))


def _member_union(u: np.ndarray, dim, caps: np.ndarray) -> np.ndarray:
    """(|u|,) bool: ``u`` in the union of ``_unroll_candidates(dim,
    cap)`` over ``caps`` (vectorized over both axes)."""
    caps = np.asarray(caps, dtype=np.int64).ravel()
    if len(caps) == 0:
        return np.zeros(len(u), dtype=bool)
    return _pow2_member(u[None, :], dim, caps[:, None]).any(axis=0)


def _cum0(counts: np.ndarray) -> np.ndarray:
    """Exclusive prefix sum: segment start offsets for ``counts``."""
    out = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=out[1:])
    return out[:-1]


def lattice_key(layer: Layer) -> tuple[int, ...]:
    """The loop bounds a :class:`MappingGrid` depends on: ``K, C, FX,
    FY`` (column and row unrolls) and the macro-duplication dims ``OX,
    OY, G`` (their options, and their product in
    ``n_spatial_temporal``).

    :func:`candidate_grid` and :func:`candidate_grid_loop` read nothing
    else of the layer — not ``B``, not a precision, not the name — so
    two layers with the same key get bitwise-identical grids over the
    same designs, schedules and ``max_candidates``."""
    return tuple(layer.dim(d) for d in COL_DIMS + ROW_DIMS + MACRO_DUP_DIMS)


def candidate_grid(layer: Layer, designs,
                   max_candidates: int = 4096,
                   schedules=None) -> MappingGrid:
    """Build the union mapping lattice of ``layer`` over a
    :class:`repro.core.designs.MacroBatch`, with per-design legality.

    Union axes are assembled from the *distinct* knob values in the
    batch (never per design), so construction cost scales with the knob
    ranges, not with D.  Per-design legality is the vectorized
    membership test of every lattice component against that design's
    caps — by construction the masked rows reproduce
    ``enumerate_mappings(layer, designs.macro_at(d))`` element for
    element (property-tested in ``tests/core/test_grid_parity.py``),
    including the ``max_candidates`` truncation, applied per design in
    enumeration order via a cumulative count.  ``schedules`` crosses the
    dataflow axis into the candidate axis (mapping outer, schedule
    inner) after truncation; legality is schedule-independent, so the
    mask rows simply repeat along the new inner axis.

    Construction is fully array-based: every union axis (k_col
    candidates, row triples, macro-dup and K-split options) is a
    candidate *pool* filtered by the same :func:`_pow2_member`
    predicate that defines legality, and the k_col x row-triple x
    macro-option crossing is pure ``repeat``/gather index arithmetic —
    no per-candidate Python.  :func:`candidate_grid_loop` keeps the
    original nested-loop construction as the bitwise enumeration-order
    oracle.
    """
    _C_BUILDS.inc()
    return _candidate_grid_impl(layer, designs, max_candidates, schedules)


_C_BUILDS = obs.counter("mapping.lattice.builds")


def _candidate_grid_impl(layer: Layer, designs, max_candidates: int,
                         schedules) -> MappingGrid:
    scheds = _normalize_schedules(schedules)
    k = layer.dim("K")
    c_dim, fx_dim, fy_dim = (layer.dim("C"), layer.dim("FX"),
                             layer.dim("FY"))
    d1s = np.unique(np.asarray(designs.d1, dtype=np.int64))
    rows_vals = np.unique(np.asarray(designs.rows, dtype=np.int64))
    nm_vals = np.unique(np.asarray(designs.n_macros, dtype=np.int64))
    nm_gt1 = nm_vals[nm_vals > 1]

    # --- k_col union: pool + membership (sorted ascending) -------------------
    kc_pool = _unroll_pool(k, d1s)
    kcs = kc_pool[_member_union(kc_pool, k, d1s)]

    # --- row-triple union: 4-D (rows, c, fx, fy) membership ------------------
    # The fx/fy caps are the floor quotients rows//c (then //fx); their
    # pools derive from every quotient the crossing can produce.
    c_pool = _unroll_pool(c_dim, rows_vals)
    rem_pool = np.unique(rows_vals[:, None] // c_pool[None, :])
    fx_pool = _unroll_pool(fx_dim, rem_pool)
    rem2_pool = np.unique(rem_pool[:, None] // fx_pool[None, :])
    fy_pool = _unroll_pool(fy_dim, rem2_pool)
    rows_b = rows_vals[:, None, None, None]
    c_b = c_pool[None, :, None, None]
    fx_b = fx_pool[None, None, :, None]
    fy_b = fy_pool[None, None, None, :]
    ok = _pow2_member(c_b, c_dim, rows_b)
    rem_b = rows_b // c_b
    ok = ok & _pow2_member(fx_b, fx_dim, rem_b)
    ok = ok & _pow2_member(fy_b, fy_dim, rem_b // fx_b)
    # any-rows + row-major nonzero == sorted(set(triples)) lexicographic
    ci, fxi, fyi = np.nonzero(ok.any(axis=0))
    row_c, row_fx, row_fy = c_pool[ci], fx_pool[fxi], fy_pool[fyi]
    n_rows = len(row_c)

    # --- macro options: shared duplication part + per-k_col K splits ---------
    # sorted(dup_opts) == codes ascending (OX<OY<G), u ascending within.
    dup_codes_l, dup_uns_l = [], []
    for d in MACRO_DUP_DIMS:                     # 3 fixed iterations
        pool = _unroll_pool(layer.dim(d), nm_gt1)
        us = pool[_member_union(pool, layer.dim(d), nm_gt1) & (pool > 1)]
        dup_codes_l.append(np.full(len(us), _MAC_CODES[d], dtype=np.int64))
        dup_uns_l.append(us)
    base_codes = np.concatenate(
        [np.asarray([_MAC_NONE], dtype=np.int64)] + dup_codes_l)
    base_uns = np.concatenate(
        [np.asarray([1], dtype=np.int64)] + dup_uns_l)
    n_base = len(base_codes)

    ksplit_dims = np.maximum(1, k // kcs)        # (|kcs|,)
    if len(nm_gt1):
        ks_pool = np.unique(np.concatenate([
            np.asarray([1], dtype=np.int64),
            1 << np.arange(int(np.maximum(nm_gt1.max(), 1)).bit_length(),
                           dtype=np.int64),
            nm_gt1, ksplit_dims]))
        # (|kcs|, |pool|): u in union over nm of cands(k//k_col, nm), u>1
        ks_member = _pow2_member(
            ks_pool[None, :, None], ksplit_dims[:, None, None],
            nm_gt1[None, None, :]).any(axis=2) & (ks_pool[None, :] > 1)
    else:
        ks_pool = np.asarray([], dtype=np.int64)
        ks_member = np.zeros((len(kcs), 0), dtype=bool)
    n_ks = ks_member.sum(axis=1).astype(np.int64)    # (|kcs|,)

    # flattened per-k_col macro-option tables: base options then the
    # K-split options of that k_col (np.nonzero row-major order is
    # exactly per-k_col ascending u).
    n_mac = n_base + n_ks
    mac_starts = _cum0(n_mac)
    total_mac = int(n_mac.sum())
    mac_codes_flat = np.empty(total_mac, dtype=np.int64)
    mac_uns_flat = np.empty(total_mac, dtype=np.int64)
    base_idx = (mac_starts[:, None]
                + np.arange(n_base, dtype=np.int64)).ravel()
    mac_codes_flat[base_idx] = np.tile(base_codes, len(kcs))
    mac_uns_flat[base_idx] = np.tile(base_uns, len(kcs))
    kci, ui = np.nonzero(ks_member)
    rank = np.arange(len(kci), dtype=np.int64) - np.repeat(_cum0(n_ks), n_ks)
    ks_idx = mac_starts[kci] + n_base + rank
    mac_codes_flat[ks_idx] = _MAC_K
    mac_uns_flat[ks_idx] = ks_pool[ui]

    # --- the crossing: k_col outer, row triple middle, macro inner -----------
    block = n_rows * n_mac                       # candidates per k_col
    n_cand = int(block.sum())
    kc_of = np.repeat(np.arange(len(kcs), dtype=np.int64), block)
    within = np.arange(n_cand, dtype=np.int64) - np.repeat(_cum0(block),
                                                           block)
    nm_per = n_mac[kc_of]
    row_i = within // nm_per
    mac_i = mac_starts[kc_of] + within % nm_per
    return _assemble_grid(layer, designs, scheds, max_candidates,
                          kcs[kc_of], row_c[row_i], row_fx[row_i],
                          row_fy[row_i], mac_codes_flat[mac_i],
                          mac_uns_flat[mac_i])


@dataclasses.dataclass(frozen=True)
class MappingCostGrid:
    """Struct-of-arrays mapping costs over a (design x candidate) grid.

    Energy fields are (D, C); the tiling counts and outer-memory traffic
    are properties of (layer, candidate) alone — independent of the
    design — and stay (C,) row vectors that broadcast against the design
    axis.  Illegal (design, candidate) pairs hold well-defined garbage;
    consumers must mask with ``grid.legal`` before reducing.
    """

    grid: MappingGrid
    macro_energy: EnergyBreakdownBatch   # (D, C), scaled to all tiles/macros
    weight_tiles: np.ndarray             # (C,) int64
    inputs_per_tile: np.ndarray          # (C,) int64
    cycles: np.ndarray                   # (D, C) int64
    spatial_utilization: np.ndarray      # (D, C) float64
    weight_bits: np.ndarray              # (C,) int64
    input_bits: np.ndarray               # (C,) int64
    output_bits: np.ndarray              # (C,) int64
    psum_bits: np.ndarray                # (C,) int64

    def __len__(self) -> int:
        return len(self.grid)

    @property
    def total_traffic_bits(self) -> np.ndarray:
        return self.weight_bits + self.input_bits + self.output_bits \
            + self.psum_bits


def _design_cc_per_input(designs) -> np.ndarray:
    """Cycles per streamed input operand of each design, (D,) int64:
    AIMC waits on its shared ADCs, DIMC on its row mux."""
    return np.where(designs.analog, designs.cc_bs * designs.adc_share,
                    designs.cc_bs * designs.m_mux)


def evaluate_grid(layer: Layer, designs, grid: MappingGrid,
                  alpha: float | None = None) -> MappingCostGrid:
    """Vectorized :func:`evaluate` over the full (design x candidate)
    lattice: ``energy.tile_energy_grid`` prices the tile energies in one
    fused JAX pass, the (cheap, candidate-only) tiling counts and
    traffic stay in NumPy.  Per the grid docstrings, every legal entry
    is bitwise identical to the scalar oracle / per-design batch path.
    """
    from .energy import DEFAULT_ALPHA, tile_energy_grid
    alpha = DEFAULT_ALPHA if alpha is None else alpha
    batch = grid.cand

    k_dim = layer.dim("K")
    acc_depth = layer.accumulation_depth
    b_dim = layer.dim("B")

    n_k_tiles = np.ceil(k_dim / (batch.k_cols * batch.k_macros)
                        ).astype(np.int64)
    n_acc_tiles = np.ceil(acc_depth / batch.row_un).astype(np.int64)
    weight_tiles = n_k_tiles * n_acc_tiles
    inputs_per_tile = b_dim * batch.n_spatial_temporal

    # schedule-dependent factors (exact integer np.where selections
    # between the two Schedule closed forms — see schedule.py)
    is_os = batch.schedule == OS_CODE
    weight_loads = np.where(is_os, inputs_per_tile, np.int64(1))

    rows_used = np.minimum(batch.row_un, acc_depth)
    cols_used = np.minimum(batch.k_cols, k_dim)
    active_macros = batch.k_macros * batch.dup_macros
    e_tile = tile_energy_grid(designs, n_inputs=inputs_per_tile,
                              rows_used=rows_used, cols_used=cols_used,
                              weight_loads=weight_loads,
                              alpha=alpha, schedule_os=is_os)
    macro_energy = e_tile.scaled(active_macros).scaled(weight_tiles)

    occupied = (rows_used * cols_used
                * designs.bw.astype(np.float64)[:, None]
                * active_macros * weight_tiles * inputs_per_tile)
    capacity = ((designs.rows * designs.cols
                 * designs.n_macros).astype(np.float64)[:, None]
                * weight_tiles * inputs_per_tile)
    spatial_utilization = occupied / capacity

    write_cycles = rows_used * weight_tiles * weight_loads
    cycles = (weight_tiles * inputs_per_tile
              * _design_cc_per_input(designs)[:, None] + write_cycles)

    # OS restreams the weight tensor once per reload pass — the same
    # closed form as weight_loads (schedule.weight_refetch == .weight_loads)
    weight_bits = (layer.weight_elems * layer.w_prec * batch.dup_macros
                   * weight_loads)
    input_bits = (layer.input_elems * layer.i_prec
                  * np.where(is_os, np.int64(1), n_k_tiles))
    output_bits = np.full(len(batch), layer.output_elems * layer.psum_prec,
                          dtype=np.int64)
    psum_bits = (layer.output_elems * layer.psum_prec
                 * np.where(is_os, np.int64(0),
                            2 * np.maximum(0, n_acc_tiles - 1)))
    return MappingCostGrid(
        grid=grid, macro_energy=macro_energy, weight_tiles=weight_tiles,
        inputs_per_tile=inputs_per_tile, cycles=cycles,
        spatial_utilization=spatial_utilization, weight_bits=weight_bits,
        input_bits=input_bits, output_bits=output_bits, psum_bits=psum_bits)


# --------------------------------------------------------------------------- #
# network (layer x design x candidate) fused lattice                            #
# --------------------------------------------------------------------------- #
#: lane-axis quantum: padded lattices round their lane count up to a
#: multiple of this, so sweeps over different workloads land on a small
#: set of compiled kernel shapes instead of one per lattice width.
PAD_QUANTUM = 64

#: benign filler for padded lanes: a trivial all-ones weight-stationary
#: candidate.  Every downstream formula stays finite on it (no NaN/inf
#: arithmetic anywhere in the fused pass — the masked argmin relies on
#: finite sentinel costs only), and the validity/legality masks keep it
#: out of every reduction.
_PAD_LANE = dict(k_cols=1, k_macros=1, c_un=1, fx_un=1, fy_un=1, row_un=1,
                 mac_dim=_MAC_NONE, mac_un=1, dup_macros=1,
                 n_spatial_temporal=1, schedule=WS_CODE)

_CAND_FIELDS = tuple(_PAD_LANE)


@dataclasses.dataclass(frozen=True)
class NetworkGrid:
    """The fused candidate lattice of L layer shapes over D designs.

    The workload axis is *ragged* — every layer shape has its own union
    lattice width — so instead of a rectangular (L, C_max) pad, the
    per-shape lattices are concatenated along one flat **lane axis** of
    ``Ctot`` lanes (segment ``s`` spans ``starts[s]:starts[s+1]``, in
    the shape's own enumeration order), then padded up to a
    :data:`PAD_QUANTUM` multiple with benign :data:`_PAD_LANE` filler.
    ``lane_layer`` maps each lane back to its segment so per-layer loop
    bounds enter the vectorized cost formulas as gathered columns, and
    one jit dispatch prices every (layer, design, candidate) triple of
    the bucket (:func:`reduce_network_grid`, or
    :func:`evaluate_network_grid` for the full grids).

    Masks: ``valid`` (Ctot,) marks real (non-pad) lanes; legality is
    held per class as in :class:`MappingGrid` — ``legal_rows`` (U, Ctot)
    with all-False pad lanes, ``design_class`` (D,) shared by every
    segment — and ``legal`` is the expanded (D, Ctot) mask.  A design's
    legal subsequence of a segment *is* that layer's scalar enumeration
    order, so masked per-segment argmins tie-break exactly like the
    per-layer scalar oracle.
    """

    layers: tuple[Layer, ...]          # one representative per segment
    grids: tuple[MappingGrid, ...]     # per-shape unpadded grids
    shape_indices: tuple[int, ...]     # caller's slot id per segment
    starts: np.ndarray                 # (S+1,) int64 segment bounds
    cand: MappingBatch                 # flat lane axis (Ctot,)
    lane_layer: np.ndarray             # (Ctot,) int64 segment per lane
    legal_rows: np.ndarray             # (U, Ctot) bool, per class
    design_class: np.ndarray           # (D,) int32 row of each design
    valid: np.ndarray                  # (Ctot,) bool, False on pad lanes

    def __len__(self) -> int:
        return len(self.cand)

    @property
    def n_designs(self) -> int:
        return len(self.design_class)

    @functools.cached_property
    def legal(self) -> np.ndarray:
        """(D, Ctot) bool: lane j legal on design i."""
        return self.legal_rows[self.design_class]

    @property
    def pad_lanes(self) -> int:
        return len(self) - int(self.valid.sum())

    def segment(self, s: int) -> slice:
        """Lane range of segment ``s`` (its shape's real lanes only)."""
        return slice(int(self.starts[s]), int(self.starts[s + 1]))


def network_grid(layers: Sequence[Layer], designs,
                 schedules=None, max_candidates: int = 4096,
                 grids: Sequence[MappingGrid] | None = None,
                 pad_quantum: int = PAD_QUANTUM,
                 max_lanes: int | None = None) -> tuple[NetworkGrid, ...]:
    """Fuse the union lattices of ``layers`` into flat
    :class:`NetworkGrid` buckets over a ``designs.MacroBatch``.

    ``grids`` supplies prebuilt per-shape :class:`MappingGrid` objects
    (e.g. from the DSE's lattice cache); by default each shape's grid
    is built fresh.  Buckets split the lane axis greedily in input
    order whenever the running lane count would exceed ``max_lanes``
    (``None`` = single bucket) — this bounds peak (D x Ctot) memory;
    padding waste is bounded separately by ``pad_quantum`` (at most
    ``pad_quantum - 1`` filler lanes per bucket), so fusing never
    explodes the lattice the way a rectangular (L, C_max) pad would.
    """
    return _network_grid_impl(layers, designs, schedules, max_candidates,
                              grids, pad_quantum, max_lanes)


def _network_grid_impl(layers, designs, schedules, max_candidates,
                       grids, pad_quantum, max_lanes
                       ) -> tuple[NetworkGrid, ...]:
    if grids is None:
        grids = [candidate_grid(l, designs, max_candidates=max_candidates,
                                schedules=schedules) for l in layers]
    if len(grids) != len(layers):
        raise ValueError(f"network_grid: {len(layers)} layers but "
                         f"{len(grids)} grids")
    if not layers:
        raise ValueError("network_grid: no layers")

    buckets: list[list[int]] = [[]]
    lanes = 0
    for s, g in enumerate(grids):
        if buckets[-1] and max_lanes is not None and lanes + len(g) > max_lanes:
            buckets.append([])
            lanes = 0
        buckets[-1].append(s)
        lanes += len(g)

    out = []
    for members in buckets:
        segs = [grids[s] for s in members]
        widths = [len(g) for g in segs]
        starts = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
        ctot = int(starts[-1])
        padded = -(-max(ctot, 1) // pad_quantum) * pad_quantum
        pad = padded - ctot

        fields = {}
        for f in _CAND_FIELDS:
            parts = [getattr(g.cand, f) for g in segs]
            if pad:
                parts.append(np.full(pad, _PAD_LANE[f], dtype=np.int64))
            fields[f] = np.concatenate(parts)
        lane_layer = np.repeat(np.arange(len(segs), dtype=np.int64), widths)
        if pad:
            lane_layer = np.concatenate(
                [lane_layer, np.zeros(pad, dtype=np.int64)])
        # every segment built over the same designs numbers its classes
        # alike; grids of other provenance fall back to one class per design
        design_class = segs[0].design_class
        if all(np.array_equal(g.design_class, design_class)
               for g in segs[1:]):
            rows = [g.legal_rows for g in segs]
        else:
            rows = [g.legal for g in segs]
            design_class = np.arange(len(design_class), dtype=np.int32)
        if pad:
            rows.append(np.zeros((len(rows[0]), pad), dtype=bool))
        valid = np.zeros(padded, dtype=bool)
        valid[:ctot] = True
        out.append(NetworkGrid(
            layers=tuple(layers[s] for s in members),
            grids=tuple(segs),
            shape_indices=tuple(members),
            starts=starts, cand=MappingBatch(**fields),
            lane_layer=lane_layer,
            legal_rows=np.concatenate(rows, axis=1),
            design_class=design_class, valid=valid))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class NetworkCostGrid:
    """Struct-of-arrays mapping costs over one fused
    (layer x design x candidate) bucket.

    Field semantics match :class:`MappingCostGrid` with the candidate
    axis replaced by the bucket's flat lane axis: energy/cycles are
    (D, Ctot), the candidate-only tiling counts and traffic are (Ctot,)
    rows.  Pad and illegal lanes hold finite, well-defined garbage;
    consumers must mask with ``net.legal`` before reducing.  The
    reporting-only ``spatial_utilization`` column is deliberately
    absent — the fused hot path never reads it; rebuild winners through
    the scalar oracle (``dse.SweepResult.network_result``) or the
    per-layer :func:`evaluate_grid` when it is needed.
    """

    net: NetworkGrid
    macro_energy: EnergyBreakdownBatch   # (D, Ctot)
    weight_tiles: np.ndarray             # (Ctot,) int64
    inputs_per_tile: np.ndarray          # (Ctot,) int64
    cycles: np.ndarray                   # (D, Ctot) int64
    weight_bits: np.ndarray              # (Ctot,) int64
    input_bits: np.ndarray               # (Ctot,) int64
    output_bits: np.ndarray              # (Ctot,) int64
    psum_bits: np.ndarray                # (Ctot,) int64

    def __len__(self) -> int:
        return len(self.net)


@dataclasses.dataclass(frozen=True)
class ReducedNetworkCost:
    """Device-resident winners of one fused bucket
    (:func:`reduce_network_grid`).

    ``best_idx`` / ``total`` / ``cycles`` are (S, D) *jax* arrays — one
    row per shape slot of the bucket, still on device and possibly
    still being computed (the reduction dispatch is asynchronous, so a
    pipelined caller can overlap the next bucket's dispatch with this
    one's finalization).  ``transfer_bytes`` is the device→host volume
    the three arrays cost when realized — the whole point: 3·S·D
    winners instead of the full (D, Ctot) component grids.
    """

    net: NetworkGrid
    objective: str
    best_idx: object                     # (S, D) jax int
    total: object                        # (S, D) jax float64
    cycles: object                       # (S, D) jax int64
    transfer_bytes: int


def _network_lanes(net: NetworkGrid) -> dict[str, np.ndarray]:
    """The integer lane columns of a fused bucket, each (Ctot,): tiling
    counts, the tile arguments of the energy kernel, write cycles and
    traffic bits.  Per-layer loop bounds enter gathered through
    ``net.lane_layer``, so each lane sees exactly the scalars the
    per-layer :func:`evaluate_grid` path would.  Integer and bool only,
    so exact by construction."""
    batch = net.cand
    lay = net.lane_layer

    per = lambda fn: np.asarray([fn(l) for l in net.layers],
                                dtype=np.int64)[lay]
    k_dim = per(lambda l: l.dim("K"))
    acc_depth = per(lambda l: l.accumulation_depth)
    b_dim = per(lambda l: l.dim("B"))
    w_elems = per(lambda l: l.weight_elems)
    i_elems = per(lambda l: l.input_elems)
    o_elems = per(lambda l: l.output_elems)
    w_prec = per(lambda l: l.w_prec)
    i_prec = per(lambda l: l.i_prec)
    p_prec = per(lambda l: l.psum_prec)

    n_k_tiles = np.ceil(k_dim / (batch.k_cols * batch.k_macros)
                        ).astype(np.int64)
    n_acc_tiles = np.ceil(acc_depth / batch.row_un).astype(np.int64)
    weight_tiles = n_k_tiles * n_acc_tiles
    inputs_per_tile = b_dim * batch.n_spatial_temporal

    # schedule-dependent factors (exact integer np.where selections)
    is_os = batch.schedule == OS_CODE
    weight_loads = np.where(is_os, inputs_per_tile, np.int64(1))

    rows_used = np.minimum(batch.row_un, acc_depth)
    cols_used = np.minimum(batch.k_cols, k_dim)
    active_macros = batch.k_macros * batch.dup_macros

    write_cycles = rows_used * weight_tiles * weight_loads

    # OS restreams the weight tensor once per reload pass — the same
    # closed form as weight_loads (schedule.weight_refetch == .weight_loads)
    weight_bits = w_elems * w_prec * batch.dup_macros * weight_loads
    input_bits = (i_elems * i_prec
                  * np.where(is_os, np.int64(1), n_k_tiles))
    output_bits = o_elems * p_prec
    psum_bits = (o_elems * p_prec
                 * np.where(is_os, np.int64(0),
                            2 * np.maximum(0, n_acc_tiles - 1)))
    return dict(inputs_per_tile=inputs_per_tile, rows_used=rows_used,
                cols_used=cols_used, weight_loads=weight_loads,
                is_os=is_os, active_macros=active_macros,
                weight_tiles=weight_tiles, write_cycles=write_cycles,
                weight_bits=weight_bits, input_bits=input_bits,
                output_bits=output_bits, psum_bits=psum_bits)


def evaluate_network_grid(net: NetworkGrid, designs,
                          alpha: float | None = None) -> NetworkCostGrid:
    """Vectorized :func:`evaluate` over a fused workload bucket: one
    ``energy.tile_energy_grid`` jit dispatch for every layer shape in
    the bucket, its (D, Ctot) cost grids realized on the host.  Every
    legal lane is bitwise identical to the per-layer
    :func:`evaluate_grid` path (and hence to the scalar oracle).  The
    sweep's own route is :func:`reduce_network_grid`; this face is its
    full-grid reference."""
    from .energy import DEFAULT_ALPHA, tile_energy_grid
    alpha = DEFAULT_ALPHA if alpha is None else alpha
    ln = _network_lanes(net)
    inputs_per_tile, weight_tiles = ln["inputs_per_tile"], ln["weight_tiles"]
    active_macros = ln["active_macros"]

    e_tile = tile_energy_grid(designs, n_inputs=inputs_per_tile,
                              rows_used=ln["rows_used"],
                              cols_used=ln["cols_used"],
                              weight_loads=ln["weight_loads"],
                              alpha=alpha, schedule_os=ln["is_os"])

    # (f * active_macros) * weight_tiles with one temporary per field —
    # the in-place second multiply performs the identical float op the
    # chained ``.scaled().scaled()`` would, so lanes stay bitwise.
    def _scale2(x: np.ndarray) -> np.ndarray:
        y = x * active_macros
        y *= weight_tiles
        return y

    macro_energy = EnergyBreakdownBatch(
        *(_scale2(getattr(e_tile, f.name))
          for f in dataclasses.fields(e_tile)))

    cycles = (weight_tiles * inputs_per_tile
              * _design_cc_per_input(designs)[:, None]
              + ln["write_cycles"])

    return NetworkCostGrid(
        net=net, macro_energy=macro_energy, weight_tiles=weight_tiles,
        inputs_per_tile=inputs_per_tile, cycles=cycles,
        weight_bits=ln["weight_bits"], input_bits=ln["input_bits"],
        output_bits=ln["output_bits"], psum_bits=ln["psum_bits"])


def reduced_design_block(designs, design_class, *, per_bit,
                         alpha: float | None = None,
                         dram_fj_per_bit: float | None = None):
    """The per-design arguments of :func:`reduce_network_grid`, put on
    the device once for every bucket of a sweep over ``designs`` whose
    legality classes are ``design_class``
    (:func:`repro.core.energy.put_design_block`).  ``per_bit`` is the
    SRAM traffic rate (scalar or (D,)), as
    :func:`~repro.core.memory.traffic_energy_grid` takes it."""
    from .energy import DEFAULT_ALPHA, put_design_block
    from .memory import DRAM_FJ_PER_BIT, spill_pricing_columns
    pb, pb_spill = spill_pricing_columns(
        per_bit, DRAM_FJ_PER_BIT if dram_fj_per_bit is None
        else dram_fj_per_bit)
    return put_design_block(
        designs, design_class,
        alpha=DEFAULT_ALPHA if alpha is None else alpha,
        per_bit=pb, per_bit_spill=pb_spill,
        cc_per_input=_design_cc_per_input(designs))


def reduce_network_grid(net: NetworkGrid, *, objective: str,
                        design_block, resident_bytes,
                        buffer_bytes: int) -> ReducedNetworkCost:
    """The sweep's pricing of one fused bucket, on the device: the
    energy kernel, the energy-total chain (same scalar add association,
    FMA-fenced), the traffic pricing (``resident_bytes`` /
    ``buffer_bytes`` and the rates of ``design_block``, as
    :func:`~repro.core.memory.traffic_energy_grid` would price them) and
    the sentinel-masked first-min argmin
    (:func:`repro.core.energy.reduce_objective_grid`).  Returns the
    per-segment (S, D) winners, asynchronously, without blocking.

    ``design_block`` (:func:`reduced_design_block`) carries every
    per-design argument, the designs' constants and ``alpha`` included,
    already on the device.  Bitwise identical to reducing
    :func:`evaluate_network_grid`'s :class:`NetworkCostGrid` on the host
    (property-pinned in ``tests/core/test_reduced_sweep.py``).  All host
    work here is integer/bool prep (exact by construction)."""
    from .energy import reduce_objective_grid
    if objective not in ("energy", "latency", "edp"):
        raise KeyError(objective)
    ln = _network_lanes(net)
    seg_bounds = tuple((int(net.starts[s]), int(net.starts[s + 1]))
                       for s in range(len(net.layers)))
    best_idx, total, cycles = reduce_objective_grid(
        block=design_block, objective=objective,
        seg_bounds=seg_bounds, has_os=bool(ln["is_os"].any()),
        n_inputs=ln["inputs_per_tile"], rows_used=ln["rows_used"],
        cols_used=ln["cols_used"], weight_loads=ln["weight_loads"],
        schedule_os=ln["is_os"], active_macros=ln["active_macros"],
        weight_tiles=ln["weight_tiles"],
        wt_ipt=ln["weight_tiles"] * ln["inputs_per_tile"],
        write_cycles=ln["write_cycles"],
        weight_bits=ln["weight_bits"], input_bits=ln["input_bits"],
        output_bits=ln["output_bits"], psum_bits=ln["psum_bits"],
        off_chip=np.asarray(resident_bytes) > buffer_bytes,
        legal_rows=net.legal_rows, design_class=net.design_class)
    nbytes = sum(a.dtype.itemsize * a.size
                 for a in (best_idx, total, cycles))
    return ReducedNetworkCost(net=net, objective=objective,
                              best_idx=best_idx, total=total,
                              cycles=cycles, transfer_bytes=int(nbytes))


@dataclasses.dataclass(frozen=True)
class MappingCostBatch:
    """Struct-of-arrays :class:`MappingCost` over N candidates."""

    batch: MappingBatch
    macro_energy: EnergyBreakdownBatch   # already scaled to all tiles/macros
    weight_tiles: np.ndarray             # int64
    inputs_per_tile: np.ndarray          # int64
    cycles: np.ndarray                   # int64 (exact; scalar path is int too)
    spatial_utilization: np.ndarray      # float64
    weight_bits: np.ndarray              # int64
    input_bits: np.ndarray               # int64
    output_bits: np.ndarray              # int64
    psum_bits: np.ndarray                # int64

    def __len__(self) -> int:
        return len(self.batch)

    @property
    def total_traffic_bits(self) -> np.ndarray:
        return self.weight_bits + self.input_bits + self.output_bits \
            + self.psum_bits

    def at(self, i: int, layer: Layer, macro: IMCMacro,
           alpha: float | None = None) -> MappingCost:
        """Rebuild candidate ``i`` through the scalar oracle — the DSE
        returns oracle-exact objects, the arrays only steer the argmin."""
        return evaluate(layer, macro, self.batch.mapping_at(i), alpha=alpha,
                        schedule=self.batch.schedule_at(i))


def evaluate_batch(layer: Layer, macro: IMCMacro, batch: MappingBatch,
                   alpha: float | None = None) -> MappingCostBatch:
    """Vectorized :func:`evaluate` over all candidates in ``batch``.

    Mirrors the scalar oracle operation-for-operation (see module
    docstring); utilization is the one field computed in float64
    throughout (the scalar path forms exact big-int products first), so
    it may differ in the last ulp — it is reporting-only, never an
    objective.
    """
    from .energy import DEFAULT_ALPHA
    alpha = DEFAULT_ALPHA if alpha is None else alpha

    k_dim = layer.dim("K")
    acc_depth = layer.accumulation_depth
    b_dim = layer.dim("B")

    # --- tiling counts (scalar: math.ceil of true division) ------------------
    n_k_tiles = np.ceil(k_dim / (batch.k_cols * batch.k_macros)
                        ).astype(np.int64)
    n_acc_tiles = np.ceil(acc_depth / batch.row_un).astype(np.int64)
    weight_tiles = n_k_tiles * n_acc_tiles
    inputs_per_tile = b_dim * batch.n_spatial_temporal

    # schedule-dependent factors (exact integer np.where selections)
    is_os = batch.schedule == OS_CODE
    weight_loads = np.where(is_os, inputs_per_tile, np.int64(1))

    # --- per-tile energy, scaled as the scalar path does ----------------------
    rows_used = np.minimum(batch.row_un, acc_depth)
    cols_used = np.minimum(batch.k_cols, k_dim)
    active_macros = batch.k_macros * batch.dup_macros
    e_tile = tile_energy_batch(macro, n_inputs=inputs_per_tile,
                               rows_used=rows_used, cols_used=cols_used,
                               weight_loads=weight_loads,
                               alpha=alpha, schedule_os=is_os)
    macro_energy = e_tile.scaled(active_macros).scaled(weight_tiles)

    # --- utilization -----------------------------------------------------------
    occupied = (rows_used * cols_used * float(macro.bw) * active_macros
                * weight_tiles * inputs_per_tile)
    capacity = (float(macro.rows * macro.cols * macro.n_macros)
                * weight_tiles * inputs_per_tile)
    spatial_utilization = occupied / capacity

    # --- latency (ints throughout, exact) --------------------------------------
    cc_per_input = (macro.cc_bs * macro.adc_share if macro.analog
                    else macro.cc_bs * macro.m_mux)
    write_cycles = rows_used * weight_tiles * weight_loads
    cycles = weight_tiles * inputs_per_tile * cc_per_input + write_cycles

    # --- outer-memory traffic ----------------------------------------------------
    # OS restreams the weight tensor once per reload pass — the same
    # closed form as weight_loads (schedule.weight_refetch == .weight_loads)
    weight_bits = (layer.weight_elems * layer.w_prec * batch.dup_macros
                   * weight_loads)
    input_bits = (layer.input_elems * layer.i_prec
                  * np.where(is_os, np.int64(1), n_k_tiles))
    output_bits = np.full(len(batch), layer.output_elems * layer.psum_prec,
                          dtype=np.int64)
    psum_bits = (layer.output_elems * layer.psum_prec
                 * np.where(is_os, np.int64(0),
                            2 * np.maximum(0, n_acc_tiles - 1)))
    return MappingCostBatch(
        batch=batch, macro_energy=macro_energy, weight_tiles=weight_tiles,
        inputs_per_tile=inputs_per_tile, cycles=cycles,
        spatial_utilization=spatial_utilization, weight_bits=weight_bits,
        input_bits=input_bits, output_bits=output_bits, psum_bits=psum_bits)
