"""ZigZag-lite design-space exploration (paper Sec. VI).

For each layer of a workload, enumerate legal spatial mappings
(``mapping.enumerate_mappings``) crossed with the enabled temporal
dataflows (``schedule.SCHEDULES``; weight-stationary only by default),
price each with the unified energy model + the outer-memory traffic
model, and keep the best under the chosen objective (energy, latency,
or EDP).  This reproduces the role ZigZag plays in the paper: "find
the optimal spatial and temporal mapping for each architecture and
each network layer" — with the temporal half now an explicit DSE axis.

Engines
-------
``best_mapping`` supports two engines:

* ``"batch"`` (default) — flatten the candidate lattice into
  struct-of-arrays (``mapping.candidate_batch``), price every candidate
  in one vectorized NumPy pass (``mapping.evaluate_batch`` +
  ``MemoryModel.traffic_energy_batch``) and ``argmin`` the objective
  column.  The winning index is handed back through the scalar oracle,
  so the returned :class:`LayerResult` is bitwise identical to the
  scalar engine's.
* ``"scalar"`` — the original per-candidate Python loop, kept verbatim
  as the reference oracle (``best_mapping_scalar``).

The batched objective columns replicate the scalar objective's float
operation order exactly (see ``mapping``/``energy`` module docstrings),
so the argmin — including first-wins tie-breaking — selects the same
candidate.  ``tests/core/test_batched_parity.py`` pins this.

Layer-result cache
------------------
Deep networks repeat layer shapes (e.g. the autoencoder's 128x128
stack); ``best_mapping`` memoizes results keyed on the *cost-relevant*
layer signature (loop bounds + precisions — not the name), the macro,
the memory model, the objective, and alpha.  ``cache_clear`` /
``cache_info`` expose it; the scalar oracle never touches the cache.

Design-space sweeps
-------------------
:func:`sweep` adds the second batching axis: instead of one macro, it
takes a whole ``designs.MacroBatch`` (typically from
``designs.macro_grid``) and prices every (design x mapping-candidate)
pair of every layer in one fused pass (``mapping.network_grid`` /
``mapping.reduce_network_grid``: two jitted executables per bucket,
only the winners brought back).  Per design it keeps the per-layer
argmin under the chosen objective — the same winner, bitwise, that
running ``best_mapping`` per design would keep — and returns a
:class:`SweepResult`:

Workload-axis fusion (padding/bucketing invariants)
---------------------------------------------------
The layer axis is the fourth fused lattice dimension: instead of one
jit dispatch (and one XLA compile per distinct lattice width) per
layer shape, all distinct shapes of a sweep — or of *several* networks
at once via :func:`sweep_networks` — are priced together.  The
invariants the engine maintains:

* **Slot dedup** — layers sharing ``_shape_key`` (loop bounds +
  precisions, not the name) occupy one lattice slot, across networks;
  ``cache_info()`` reports slot counts and padding waste.  Slots that
  agree on ``mapping.lattice_key`` (the loop bounds a lattice reads,
  not ``B`` or a precision) share one memoized lattice: a serving
  lowering's projection at several token counts is built once.
* **Flat lane axis** — per-shape union lattices are *concatenated*
  (``mapping.NetworkGrid``), never padded to a rectangular
  (L, C_max): each segment keeps its own scalar enumeration order, so
  per-segment masked argmins tie-break exactly like the per-layer
  scalar oracle, and fusing adds no per-layer waste.
* **Quantum padding** — the lane axis is rounded up to a
  ``mapping.PAD_QUANTUM`` multiple with benign all-ones filler lanes
  (``valid``/``legal`` both False there), so unrelated sweeps land on
  a small set of compiled kernel shapes.
* **Finite sentinels** — illegal and padded lanes enter the argmin as
  the largest finite value of the objective dtype, never as inf/NaN
  arithmetic (every (layer, design) pair has at least one legal lane,
  so sentinels can never win).
* **Memory bucketing** — the lane axis splits into buckets only when
  ``D * Ctot`` would exceed ``_BUCKET_ELEMS`` (shapes never split), so
  peak array memory is bounded; each bucket is one jit dispatch.

* ``energy_fj`` / ``cycles`` / ``edp`` / ``area_mm2`` — (D,) network
  totals per design, bitwise equal to ``map_network`` on that design;
* ``pareto_mask()`` / ``pareto()`` — the non-dominated designs over
  (energy, latency, area), the paper-style efficiency frontier;
* ``best()`` — argmin design index under the sweep objective;
* ``network_result(d)`` — the full scalar-oracle
  :class:`NetworkResult` for design ``d``, rebuilt from the stored
  winning mappings without re-searching.

Typical use::

    grid = designs.macro_grid(rows=(256, 512), adc_bits=(4, 6, 8))
    res = dse.sweep("resnet8", workloads.resnet8(), grid)
    for d in res.pareto():
        print(res.designs.macro_at(d).name, res.energy_fj[d])

Joint accuracy x cost frontier
------------------------------
:func:`joint_frontier` fuses a :class:`SweepResult` with per-design
accuracy from ``repro.fidelity.evaluate_grid`` (computed on the same
``MacroBatch``) into a :class:`JointFrontier` — the (accuracy, energy,
latency) Pareto view of the paper's three-way AIMC/DIMC trade
(``benchmarks/accuracy_sweep.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Callable, Sequence

import numpy as np

from .. import obs
from ..faults.model import (FaultSpec, SurvivorMask, fault_legal,
                            mapping_survives, survivor_mask)
from .designs import MacroBatch
from .energy import EnergyBreakdown, fold_add
from .hardware import IMCMacro
from .mapping import (MappingCost, candidate_batch, enumerate_mappings,
                      evaluate, evaluate_batch)
from .memory import KVCacheHierarchy, MemoryModel, kv_traffic_energy_grid
from .schedule import (names as _schedule_names,
                       normalize as _normalize_schedules)
from .workloads import Layer, ServingPoint


@dataclasses.dataclass(frozen=True)
class LayerResult:
    layer: Layer
    cost: MappingCost
    memory_energy_fj: dict[str, float]

    @property
    def macro_energy_fj(self) -> float:
        return self.cost.macro_energy.total_fj

    @property
    def total_energy_fj(self) -> float:
        return self.macro_energy_fj + fold_add(self.memory_energy_fj.values())

    @property
    def edp(self) -> float:
        return self.total_energy_fj * self.cost.cycles

    def breakdown_fj(self) -> dict[str, float]:
        e = self.cost.macro_energy
        return {
            "cell (WL+BL)": e.e_cell,
            "mult logic": e.e_logic,
            "ADC": e.e_adc,
            "adder tree": e.e_adder_tree,
            "DAC": e.e_dac,
            "weight write": e.e_weight_write,
            "mem: weights": self.memory_energy_fj["weights"],
            "mem: inputs": self.memory_energy_fj["inputs"],
            "mem: outputs": self.memory_energy_fj["outputs"],
            "mem: psums": self.memory_energy_fj["psums"],
        }


@dataclasses.dataclass(frozen=True)
class NetworkResult:
    network: str
    macro_name: str
    layers: tuple[LayerResult, ...]

    @property
    def total_energy_fj(self) -> float:
        return fold_add(l.total_energy_fj for l in self.layers)

    @property
    def total_cycles(self) -> float:
        return sum(l.cost.cycles for l in self.layers)

    @property
    def total_macs(self) -> int:
        return sum(l.layer.macs for l in self.layers)

    @property
    def fj_per_mac(self) -> float:
        return self.total_energy_fj / max(1, self.total_macs)

    @property
    def effective_tops_w(self) -> float:
        return 2.0 * 1e3 / self.fj_per_mac

    @property
    def mean_utilization(self) -> float:
        w = sum(l.layer.macs for l in self.layers)
        return sum(l.cost.spatial_utilization * l.layer.macs
                   for l in self.layers) / max(1, w)

    def traffic_bits(self) -> dict[str, float]:
        keys = ("weight_bits", "input_bits", "output_bits", "psum_bits")
        return {k: sum(getattr(l.cost, k) for l in self.layers) for k in keys}

    def breakdown_fj(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for l in self.layers:
            for k, v in l.breakdown_fj().items():
                out[k] = out.get(k, 0.0) + v
        return out


Objective = Callable[[LayerResult], float]

OBJECTIVES: dict[str, Objective] = {
    "energy": lambda r: r.total_energy_fj,
    "latency": lambda r: r.cost.cycles,
    "edp": lambda r: r.edp,
}


def _layer_resident_bytes(layer: Layer) -> int:
    return (layer.weight_elems * layer.w_prec
            + layer.input_elems * layer.i_prec
            + layer.output_elems * layer.psum_prec) // 8


def best_mapping_scalar(layer: Layer, macro: IMCMacro, mem: MemoryModel,
                        objective: str = "energy",
                        alpha: float | None = None,
                        schedules=None,
                        survivors: tuple[int, int] | None = None
                        ) -> LayerResult:
    """Reference oracle: the original per-candidate Python loop.

    Candidates are (mapping, schedule) pairs, mapping outer / schedule
    inner (``schedules=None`` keeps the historical weight-stationary-only
    search).  ``survivors=(cols, macros)`` restricts the search to
    mappings that fit a degraded macro (the fault axis; see
    ``repro.faults``) — the fused engine's survivor-masked argmin is
    validated bitwise against this filtered loop.  Never cached, never
    vectorized — keep it boring.
    """
    obj = OBJECTIVES[objective]
    scheds = _normalize_schedules(schedules)
    best: LayerResult | None = None
    resident = _layer_resident_bytes(layer)
    for sm in enumerate_mappings(layer, macro):
        if survivors is not None and not mapping_survives(sm, *survivors):
            continue
        for sched in scheds:
            cost = evaluate(layer, macro, sm, alpha=alpha, schedule=sched)
            res = LayerResult(
                layer=layer, cost=cost,
                memory_energy_fj=mem.traffic_energy_fj(cost, resident))
            if best is None or obj(res) < obj(best):
                best = res
    if best is None:
        raise ValueError(f"no legal mapping for {layer.name} on {macro.name}")
    return best


def best_mapping_batched(layer: Layer, macro: IMCMacro, mem: MemoryModel,
                         objective: str = "energy",
                         alpha: float | None = None,
                         schedules=None) -> LayerResult:
    """Vectorized search: one NumPy pass over all candidates + argmin.

    The objective columns replicate the scalar objective's float
    operation order, so ``argmin`` (first minimum wins) picks exactly
    the candidate ``best_mapping_scalar`` keeps — the flattened
    (mapping, schedule) axis shares its enumeration order; the winner
    is then re-priced through the scalar oracle so the returned object
    is bitwise identical.
    """
    resident = _layer_resident_bytes(layer)
    batch = candidate_batch(layer, macro, schedules=schedules)
    if len(batch) == 0:
        raise ValueError(f"no legal mapping for {layer.name} on {macro.name}")
    costs = evaluate_batch(layer, macro, batch, alpha=alpha)
    mem_fj = mem.traffic_energy_batch(costs, resident)
    # Scalar association: fold_add(dict.values()) == ((w + i) + o) + p, then
    # macro total + memory total.
    mem_total = ((mem_fj["weights"] + mem_fj["inputs"])
                 + mem_fj["outputs"]) + mem_fj["psums"]
    total_energy = costs.macro_energy.total_fj + mem_total
    if objective == "energy":
        col = total_energy
    elif objective == "latency":
        col = costs.cycles
    elif objective == "edp":
        col = total_energy * costs.cycles
    else:
        raise KeyError(objective)
    i = int(np.argmin(col))
    cost = evaluate(layer, macro, batch.mapping_at(i), alpha=alpha,
                    schedule=batch.schedule_at(i))
    return LayerResult(layer=layer, cost=cost,
                       memory_energy_fj=mem.traffic_energy_fj(cost, resident))


_ENGINES = {"batch": best_mapping_batched, "scalar": best_mapping_scalar}

#: layer-result memo cache: (layer signature, macro, mem, objective,
#: alpha) -> LayerResult.  LRU-bounded: a long-running process sweeping
#: many layers over many macros (the per-design loop engines) would
#: otherwise grow this without limit.  Hits refresh recency.
_CACHE: "collections.OrderedDict[tuple, LayerResult]" = \
    collections.OrderedDict()
_CACHE_MAX = 4096

#: union-lattice memo: (``mapping.lattice_key``, designs signature,
#: schedules, max_candidates) -> mapping.MappingGrid.  Keyed by the loop
#: bounds a lattice reads, not by ``_shape_key`` (which keys results):
#: shapes that differ only in ``B`` or a precision share one grid, so a
#: cold sweep builds each distinct lattice once.  Repeated sweeps over
#: the same design grid (the warm path of the fused engine) skip lattice
#: construction entirely.  Bounded LRU: grids carry (C,) candidate
#: columns and per-class legality rows, so beyond ``_LATTICE_CACHE_MAX``
#: entries the least-recently-used are evicted — a long-lived process
#: refining many different design grids stays flat.
_LATTICE_CACHE: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_LATTICE_CACHE_MAX = 512

#: all dse bookkeeping lives in the process-global metrics registry
#: (``repro.obs``) under the ``dse.`` subsystem; ``cache_info()`` is a
#: compatibility view over it.  Handles are bound once so hot-path
#: increments are a single method call.
_C_HITS = obs.counter("dse.cache.hits")
_C_MISSES = obs.counter("dse.cache.misses")
_C_EVICTIONS = obs.counter("dse.cache.evictions")
_C_LAT_EVICTIONS = obs.counter("dse.lattice.evictions")
#: ``_grid_for`` lookups answered by the lattice memo
_C_LAT_HITS = obs.counter("dse.lattice.hits")
#: fused-lattice bookkeeping: distinct shape slots priced, eligible
#: layers they covered, and the lane/padding-waste tally of every
#: bucket dispatched (see ``cache_info``).
_C_LAT_SLOTS = obs.counter("dse.lattice.slots")
_C_LAT_LAYERS = obs.counter("dse.lattice.layers")
_C_LAT_LANES = obs.counter("dse.lattice.lanes")
_C_LAT_PAD_LANES = obs.counter("dse.lattice.pad_lanes")
#: per-bucket wall-time split: ``first_call`` buckets dispatched a
#: kernel shape XLA had not seen this process (their wall includes
#: trace+compile — or a persistent-cache deserialize when
#: ``compilecache`` has the shape on disk); ``warm`` buckets are pure
#: execute.  The difference IS the compile cost the fused sweep exists
#: to amortize.
_T_BUCKET_FIRST = obs.timer("dse.bucket.first_call")
_T_BUCKET_WARM = obs.timer("dse.bucket.warm")
#: device→host volume actually realized by the pricing loop (the sweep
#: ships only the per-segment winners, the tests' full-grid reference
#: the full component grids), and the pipeline's shape for the last
#: sweep.
_C_TRANSFER = obs.counter("dse.transfer_bytes")
#: legality bytes handed to the device by the reduced engine: per bucket
#: the class rows, per sweep each design's class index (an int64 row of
#: the design block)
_C_LEGAL_BYTES = obs.counter("dse.legal_bytes")
#: host arrays the reduced engine hands the device: the design block's
#: two per sweep, then each bucket's dispatch (incremented in
#: ``energy``, which does the handing)
_C_H2D = obs.counter("dse.h2d_arrays")
_C_PIPE_BUCKETS = obs.counter("dse.pipeline.buckets")
_G_PIPE_DEPTH = obs.gauge("dse.pipeline.depth")
_G_PIPE_OCC = obs.gauge("dse.pipeline.occupancy")

#: buckets in flight in the sweep's bucket loop: bucket *i*'s device
#: work and finalization overlap bucket *i+1*'s build and dispatch
_PIPELINE_DEPTH = 2


def _shape_key(layer: Layer) -> tuple:
    """Cost-relevant layer signature: loop bounds + precisions, not the
    name.  Layers sharing this key share one lattice slot in the fused
    sweep and one entry in the layer-result cache."""
    return (tuple(sorted(layer.dims.items())), layer.w_prec, layer.i_prec,
            layer.psum_prec)


def _cache_key(layer: Layer, macro: IMCMacro, mem: MemoryModel,
               objective: str, alpha: float | None, schedules) -> tuple:
    """Cost-relevant signature: everything but the layer *name*."""
    return (*_shape_key(layer), macro, mem, objective, alpha,
            _schedule_names(schedules))


#: memoized ``_layer_resident_bytes`` per distinct shape key — the
#: bucket pricing loops would otherwise recompute the element-count sum
#: for every (bucket, layer) visit of the same shape.  Unbounded on
#: purpose: entries are a few machine words, the key space is the
#: distinct-shape space, and ``cache_clear`` empties it with the memos.
_RESIDENT_CACHE: dict[tuple, int] = {}


def _resident_bytes_cached(layer: Layer) -> int:
    key = _shape_key(layer)
    v = _RESIDENT_CACHE.get(key)
    if v is None:
        v = _RESIDENT_CACHE[key] = _layer_resident_bytes(layer)
    return v


def cache_clear() -> None:
    _CACHE.clear()
    _LATTICE_CACHE.clear()
    _RESIDENT_CACHE.clear()
    # counters, bucket timers and any other dse-subsystem metrics reset
    # together so a fresh measurement window starts clean
    obs.reset("dse.")


def cache_info() -> dict[str, int | float]:
    """Layer-result cache stats plus fused-lattice stats:
    ``lattice_slots`` distinct shape slots priced by sweeps (repeated
    shapes share a slot), ``lattice_layers`` eligible layers those
    slots covered, ``padding_waste`` — the fraction of dispatched
    lanes that were quantum-padding filler — and the LRU bookkeeping of
    both memo caches (``size``/``evictions`` for the layer-result
    cache, ``lattice_size``/``lattice_evictions`` for the union-lattice
    memo).

    Compatibility view over the ``dse.*`` metrics of the process-global
    registry (``repro.obs``) — the historical return shape is
    unchanged; the registry snapshot additionally carries the same
    counters plus the per-bucket first-call/warm timing split."""
    lanes = _C_LAT_LANES.value
    waste = (_C_LAT_PAD_LANES.value / lanes) if lanes else 0.0
    return {"size": len(_CACHE),
            "hits": _C_HITS.value,
            "misses": _C_MISSES.value,
            "evictions": _C_EVICTIONS.value,
            "lattice_size": len(_LATTICE_CACHE),
            "lattice_evictions": _C_LAT_EVICTIONS.value,
            "lattice_slots": _C_LAT_SLOTS.value,
            "lattice_layers": _C_LAT_LAYERS.value,
            "padding_waste": waste}


def best_mapping(layer: Layer, macro: IMCMacro, mem: MemoryModel,
                 objective: str = "energy",
                 alpha: float | None = None,
                 engine: str = "batch",
                 schedules=None) -> LayerResult:
    """Search the (mapping x dataflow) space of one layer; return the
    argmin.

    ``engine="batch"`` (default) evaluates all candidates in one
    vectorized pass and memoizes per layer signature; ``"scalar"`` runs
    the uncached reference loop.  Both return bitwise-identical results.
    ``schedules`` selects the temporal dataflows searched
    (``repro.core.schedule.normalize`` forms; default weight-stationary
    only).
    """
    scheds = _normalize_schedules(schedules)
    if engine == "scalar":
        return best_mapping_scalar(layer, macro, mem, objective=objective,
                                   alpha=alpha, schedules=scheds)
    if engine not in _ENGINES:
        raise KeyError(engine)
    key = _cache_key(layer, macro, mem, objective, alpha, scheds)
    hit = _CACHE.get(key)
    if hit is not None:
        _C_HITS.inc()
        _CACHE.move_to_end(key)
        return hit if hit.layer.name == layer.name \
            else dataclasses.replace(hit, layer=layer)
    _C_MISSES.inc()
    res = _ENGINES[engine](layer, macro, mem, objective=objective,
                           alpha=alpha, schedules=scheds)
    while len(_CACHE) >= _CACHE_MAX:
        _CACHE.popitem(last=False)
        _C_EVICTIONS.inc()
    _CACHE[key] = res
    return res


# --------------------------------------------------------------------------- #
# design-space sweep: batch over designs x mappings                            #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Per-design best-mapping network totals over a macro grid.

    All arrays have shape (D,) and are indexed by the design's position
    in ``designs``.  Totals are accumulated in the scalar engine's
    float association, so ``energy_fj[d]`` et al. are bitwise what
    ``map_network(..., designs.macro_at(d))`` reports.
    """

    network: str
    objective: str
    designs: MacroBatch
    energy_fj: np.ndarray                # (D,) total network energy
    cycles: np.ndarray                   # (D,) total network latency
    area_mm2: np.ndarray                 # (D,) macro area
    layer_names: tuple[str, ...]         # IMC-eligible layers, network order
    schedules: tuple[str, ...] = ("ws",)  # dataflow axis searched (names)
    #: survivor mask the sweep was degraded by (None = pristine); see
    #: ``repro.faults`` — winners/totals reflect the masked lattice.
    survivors: SurvivorMask | None = None
    # per distinct layer shape: (layer, grid, best_idx (D,)) — enough to
    # rebuild any design's full scalar-oracle result without re-searching.
    _shapes: tuple = dataclasses.field(repr=False, default=())
    _layer_shape: tuple[int, ...] = dataclasses.field(repr=False, default=())
    _alpha: float | None = dataclasses.field(repr=False, default=None)
    _mem: MemoryModel | None = dataclasses.field(repr=False, default=None)

    def __len__(self) -> int:
        return len(self.energy_fj)

    @property
    def n_shapes(self) -> int:
        """Distinct layer shapes priced (repeated shapes share a
        lattice slot; compare against ``len(layer_names)``)."""
        return len(self._shapes)

    @property
    def edp(self) -> np.ndarray:
        return self.energy_fj * self.cycles

    def best(self, objective: str | None = None) -> int:
        """Index of the best design under ``objective`` (default: the
        sweep objective)."""
        col = {"energy": self.energy_fj, "latency": self.cycles,
               "edp": self.edp}[objective or self.objective]
        return int(np.argmin(col))

    def pareto_mask(self) -> np.ndarray:
        """(D,) bool: design is non-dominated over (energy, latency,
        area) — no other design is <= on all three axes and < on one."""
        return _non_dominated(np.stack(
            [self.energy_fj, self.cycles.astype(np.float64),
             self.area_mm2], axis=1))

    def pareto(self) -> np.ndarray:
        """Indices of the Pareto-frontier designs, sorted by energy."""
        idx = np.flatnonzero(self.pareto_mask())
        return idx[np.argsort(self.energy_fj[idx], kind="stable")]

    def dataflows(self, d: int) -> tuple[str, ...]:
        """Per-layer chosen dataflow names for design ``d``, in
        ``layer_names`` order (the winning ``Schedule.name`` of each
        layer's (mapping x dataflow) argmin)."""
        return tuple(
            self._shapes[si][1].cand.schedule_at(
                int(self._shapes[si][2][d])).name
            for si in self._layer_shape)

    def dataflow_counts(self, d: int) -> dict[str, int]:
        """Histogram of :meth:`dataflows` for design ``d``."""
        return dict(collections.Counter(self.dataflows(d)))

    def network_result(self, d: int) -> NetworkResult:
        """Rebuild design ``d``'s full :class:`NetworkResult` through the
        scalar oracle, from the stored winning (mapping, dataflow) pairs
        (no re-search)."""
        macro = self.designs.macro_at(d)
        mem = self._mem or MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
        shape_results: dict[int, LayerResult] = {}
        results = []
        for name, si in zip(self.layer_names, self._layer_shape):
            if si not in shape_results:
                layer, grid, best_idx = self._shapes[si]
                sm = grid.cand.mapping_at(int(best_idx[d]))
                cost = evaluate(layer, macro, sm, alpha=self._alpha,
                                schedule=grid.cand.schedule_at(
                                    int(best_idx[d])))
                shape_results[si] = LayerResult(
                    layer=layer, cost=cost,
                    memory_energy_fj=mem.traffic_energy_fj(
                        cost, _layer_resident_bytes(layer)))
            r = shape_results[si]
            results.append(r if r.layer.name == name
                           else dataclasses.replace(
                               r, layer=dataclasses.replace(r.layer,
                                                            name=name)))
        return NetworkResult(network=self.network, macro_name=macro.name,
                             layers=tuple(results))


#: finite masked-lane sentinels for the fused argmin.  Illegal and
#: padded lanes never carry inf/NaN: their well-defined finite garbage
#: is replaced by the largest representable value of the objective
#: dtype, which any real candidate cost undercuts — so the argmin stays
#: FMA-safe (no 0*inf / inf-inf patterns for XLA or NumPy to mangle)
#: and tie-breaks are untouched (every (layer, design) pair has at
#: least one legal lane: the all-ones mapping is always legal).
_SENTINEL_F64 = np.float64(np.finfo(np.float64).max)
_SENTINEL_I64 = np.int64(np.iinfo(np.int64).max)

#: lane-axis budget of one fused bucket: D * Ctot is capped at this many
#: lattice points, bounding peak (D, Ctot) array memory (~32 MiB per
#: float64 field at the default).  Shapes never split across buckets.
_BUCKET_ELEMS = 1 << 22


def _grid_for(layer: Layer, designs: MacroBatch, scheds,
              max_candidates: int = 4096):
    """Cached ``mapping.candidate_grid`` (see ``_LATTICE_CACHE``)."""
    from .mapping import candidate_grid, lattice_key
    key = (lattice_key(layer), designs.signature(), _schedule_names(scheds),
           max_candidates)
    grid = _LATTICE_CACHE.get(key)
    if grid is None:
        with obs.span("dse.lattice_build", layer=layer.name,
                      designs=len(designs)) as sp:
            grid = candidate_grid(layer, designs,
                                  max_candidates=max_candidates,
                                  schedules=scheds)
            sp.set(lanes=len(grid))
        while len(_LATTICE_CACHE) >= _LATTICE_CACHE_MAX:
            _LATTICE_CACHE.popitem(last=False)
            _C_LAT_EVICTIONS.inc()
        _LATTICE_CACHE[key] = grid
    else:
        _LATTICE_CACHE.move_to_end(key)
        _C_LAT_HITS.inc()
    return grid


def _with_survivors(net, survivors: SurvivorMask | None):
    """AND a survivor mask's fault legality into one bucket's lattice.

    ``None`` returns ``net`` unchanged (the inertness contract: faults
    off is the identical object, not an equal one).  Otherwise survivors
    differ per design, so the bucket is re-wrapped with one legality
    class per design: ``legal_rows = legal & fault_legal(...)`` (D,
    Ctot) and ``design_class = arange(D)``.  Grids in ``_LATTICE_CACHE``
    stay fault-free (masks are per-sweep, caches per lattice key) and
    both consumers (the reduced kernel's class gather, the reference's
    host ``np.where`` sentinels) see the degraded legality through the
    fields they already consume.  The all-ones mapping
    survives any clamp-to->=1 mask, so every (layer, design) segment
    keeps >= 1 legal lane and sentinels still never win the argmin.
    """
    if survivors is None:
        return net
    legal = net.legal & fault_legal(survivors, net.cand)
    return dataclasses.replace(
        net, legal_rows=legal,
        design_class=np.arange(len(legal), dtype=np.int32))


def _price_buckets(buckets, designs: MacroBatch, objective: str,
                   alpha: float | None, per_bit, buffer_bytes: int,
                   dram: float,
                   survivors: SurvivorMask | None = None) -> list[tuple]:
    """Price fused workload buckets on the host, from full (D, Ctot)
    cost grids; per shape slot return
    ``(grid, best_idx (D,), total (D,), cycles (D,))``.  The body of
    :func:`_price_shapes_reference`.

    Each bucket is one ``mapping.evaluate_network_grid`` pass — a
    single jit dispatch for every (layer, design, candidate) triple it
    holds — followed by the masked per-segment argmin.  All float
    reductions happen here in NumPy with the scalar association (see
    the module docstring's bitwise contract); the masked lanes enter
    the argmin as finite sentinels, never as inf/NaN arithmetic.

    Telemetry: each bucket dispatch is a ``dse.price_bucket`` span and
    one observation of the ``dse.bucket.first_call`` / ``.warm`` timer
    pair — a bucket counts as *first call* when its jit dispatch added
    a kernel shape XLA had not seen this process (the distinct-shape
    delta of ``energy.grid_kernel_info``), so its wall includes
    trace+compile time (or a persistent compile-cache deserialize; the
    span's ``persistent_cache`` attr records whether one was active to
    attribute suspiciously-fast first calls).  Warm buckets are pure
    execute: this reference realizes the kernel's outputs as NumPy
    arrays inside the span, so its wall includes the device's time and
    the copy.  The split is what "compile vs execute" means per bucket.
    """
    from .compilecache import persistent_cache_dir
    from .energy import grid_kernel_info
    from .mapping import evaluate_network_grid
    from .memory import traffic_energy_grid

    out: list[tuple | None] = [None] * sum(
        len(net.shape_indices) for net in buckets)
    for bi, net in enumerate(buckets):
        net = _with_survivors(net, survivors)
        shapes_before = grid_kernel_info()["distinct_shapes"]
        t0 = time.perf_counter()
        with obs.span("dse.price_bucket", bucket=bi, lanes=len(net),
                      layers=len(net.layers), designs=net.n_designs) as sp:
            costs = evaluate_network_grid(net, designs, alpha=alpha)
            new_shapes = (grid_kernel_info()["distinct_shapes"]
                          - shapes_before)
            timer = _T_BUCKET_FIRST if new_shapes else _T_BUCKET_WARM
            timer.observe(time.perf_counter() - t0)
            sp.set(new_kernel_shapes=new_shapes,
                   first_call=bool(new_shapes),
                   persistent_cache=persistent_cache_dir() is not None)
            # device→host accounting: this path realizes the kernel's
            # whole output face — nine (D, Ctot) f64 grids plus the
            # (Ctot,) macs row
            _C_TRANSFER.inc((9 * net.n_designs + 1) * len(net) * 8)
            resident = np.asarray(
                [_resident_bytes_cached(l) for l in net.layers],
                dtype=np.int64)[net.lane_layer]
            mem_fj = traffic_energy_grid(per_bit, costs, resident,
                                         buffer_bytes=buffer_bytes,
                                         dram_fj_per_bit=dram)
            # The scalar association, assembled with in-place adds to
            # keep (D, Ctot) temporaries down: total_fj is
            # (((e_wl + e_bl) + e_logic) + (e_adc + e_tree)) + e_dac
            # + e_ww and the memory side is ((w + i) + o) + p, then
            # macro + mem — each += performs the identical float add
            # the property chain would, so every lane stays bitwise.
            e = costs.macro_energy
            total = e.e_wl + e.e_bl
            total += e.e_logic
            total += e.e_adc + e.e_adder_tree
            total += e.e_dac
            total += e.e_weight_write
            mem_total = mem_fj["weights"]
            mem_total += mem_fj["inputs"]
            mem_total += mem_fj["outputs"]
            mem_total += mem_fj["psums"]
            total += mem_total
            if objective == "energy":
                col = np.where(net.legal, total, _SENTINEL_F64)
            elif objective == "latency":
                col = np.where(net.legal, costs.cycles, _SENTINEL_I64)
            else:                                 # edp
                col = np.where(net.legal, total * costs.cycles,
                               _SENTINEL_F64)
            for row, si in enumerate(net.shape_indices):
                seg = net.segment(row)
                best_idx = np.argmin(col[:, seg], axis=1)
                take = lambda a: np.take_along_axis(
                    a[:, seg], best_idx[:, None], axis=1)[:, 0]
                out[si] = (net.grids[row], best_idx,
                           take(total), take(costs.cycles))
        _C_LAT_LANES.inc(len(net))
        _C_LAT_PAD_LANES.inc(net.pad_lanes)
    return out


def _price_shapes_reference(shape_layers: Sequence[Layer],
                            designs: MacroBatch, objective: str,
                            alpha: float | None, per_bit,
                            buffer_bytes: int, dram: float, scheds,
                            survivors: SurvivorMask | None = None
                            ) -> list[tuple]:
    """Full-grid host pricing of the same shapes as :func:`_price_shapes`,
    with the same return: the tests' bitwise reference for the sweep.

    Every bucket's nine (D, Ctot) float64 cost grids are realized on the
    host and reduced in NumPy (:func:`_price_buckets`).  Nothing in the
    program calls it; tests that call it by name, or put it in
    ``_price_shapes``' place, hold the sweep's winners, totals and cycles
    to it bit for bit.
    """
    from .mapping import PAD_QUANTUM, network_grid
    grids = [_grid_for(l, designs, scheds) for l in shape_layers]
    max_lanes = max((len(g) for g in grids),
                    default=1)
    max_lanes = max(max_lanes, _BUCKET_ELEMS // max(1, len(designs)))
    with obs.span("dse.network_grid_build", shapes=len(shape_layers),
                  designs=len(designs)) as sp:
        buckets = network_grid(shape_layers, designs, schedules=scheds,
                               grids=grids, pad_quantum=PAD_QUANTUM,
                               max_lanes=max_lanes)
        sp.set(buckets=len(buckets),
               lanes=sum(len(b) for b in buckets))
    return _price_buckets(buckets, designs, objective, alpha, per_bit,
                          buffer_bytes, dram, survivors=survivors)


def _bucket_builder(shape_layers, designs, scheds, out_q,
                    stop: threading.Event, parent_span: int):
    """Builder-thread body of :func:`_price_shapes`: greedily assemble
    lane buckets (``_BUCKET_ELEMS`` budget; shapes never split) and fuse
    each into one :class:`NetworkGrid`, feeding the bounded queue so
    lattice construction — pure NumPy, which runs concurrently because
    XLA execution on the consumer side releases the GIL — overlaps
    bucket pricing.

    One accepted divergence from :func:`_price_shapes_reference`'s
    bucketing: the budget is not raised to the largest single lattice,
    so when one shape alone exceeds the budget the *boundaries* between
    buckets may differ.  Results are bitwise identical either way —
    every shape segment is priced independently.

    ``parent_span`` is the starter's open span (the sweep's root), which
    this thread's spans name as their parent.
    """
    from .mapping import PAD_QUANTUM, network_grid

    obs.adopt_parent(parent_span)

    def put(item) -> bool:
        while not stop.is_set():
            try:
                out_q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    try:
        budget = max(1, _BUCKET_ELEMS // max(1, len(designs)))
        members: list[int] = []
        grids: list = []
        lanes = 0

        def flush() -> bool:
            nonlocal members, grids, lanes
            if not members:
                return True
            with obs.span("dse.network_grid_build", shapes=len(members),
                          designs=len(designs)) as sp:
                (net,) = network_grid(
                    [shape_layers[s] for s in members], designs,
                    schedules=scheds, grids=grids, pad_quantum=PAD_QUANTUM,
                    max_lanes=None)
                sp.set(buckets=1, lanes=len(net),
                       lattices=len({id(g) for g in grids}))
            ok = put(("bucket", tuple(members), net))
            members, grids, lanes = [], [], 0
            return ok

        for si, layer in enumerate(shape_layers):
            g = _grid_for(layer, designs, scheds)
            if members and lanes + len(g) > budget:
                if not flush():
                    return
            members.append(si)
            grids.append(g)
            lanes += len(g)
        if flush():
            put(("done",))
    except BaseException as e:                   # pragma: no cover
        put(("error", e))


def _finalize_bucket(entry, out) -> None:
    """Realize one in-flight reduced bucket's (S, D) winners on the host
    and scatter them into the per-shape output table."""
    members, net, red = entry
    with obs.span("dse.finalize_bucket", lanes=len(net),
                  layers=len(net.layers), designs=net.n_designs) as sp:
        # the realizations block until the device has priced the bucket,
        # so this span is the host's wait for the chip plus the copy
        with obs.span("dse.device_wait"):
            best = np.asarray(red.best_idx)
            total = np.asarray(red.total)
            cyc = np.asarray(red.cycles)
        _C_TRANSFER.inc(red.transfer_bytes)
        sp.set(transfer_bytes=red.transfer_bytes)
        for row, si in enumerate(members):
            out[si] = (net.grids[row], best[row], total[row], cyc[row])
    _C_LAT_LANES.inc(len(net))
    _C_LAT_PAD_LANES.inc(net.pad_lanes)


def _next_bucket(out_q: queue.Queue, builder: threading.Thread) -> tuple:
    """The builder's next queue item; raises if the builder thread died
    without posting one."""
    while True:
        try:
            return out_q.get(timeout=0.5)
        except queue.Empty:
            if not builder.is_alive():
                raise RuntimeError(
                    "sweep bucket builder died without a result")


def _price_shapes(shape_layers: Sequence[Layer], designs: MacroBatch,
                  objective: str, alpha: float | None, per_bit,
                  buffer_bytes: int, dram: float, scheds,
                  survivors: SurvivorMask | None = None) -> list[tuple]:
    """Build (cached) per-shape lattices, fuse them into buckets, and
    price everything; one entry per distinct shape, input order.

    Three overlapped stages: a builder thread assembles lattice buckets
    (:func:`_bucket_builder`), the main thread dispatches each bucket's
    reduced evaluation asynchronously (``mapping.reduce_network_grid``:
    the fused grid-and-terms executable, then the argmin one) and keeps
    up to ``_PIPELINE_DEPTH`` buckets in flight before finalizing the
    oldest — so bucket *i*'s device execution and host finalization
    overlap bucket *i+1*'s build and dispatch.  Only the per-segment
    winners (``best_idx`` / ``total`` / ``cycles``, 3·S·D values) ever
    cross the device→host boundary.  Bitwise identical to
    :func:`_price_shapes_reference`.

    Telemetry: the main thread's time is split into consecutive spans —
    ``dse.await_bucket`` (waiting on the builder's queue;
    ``in_flight`` says how many buckets the device still has queued),
    ``dse.price_bucket`` (host dispatch only: jit trace+compile is
    synchronous, so first-call cost lands there, with one
    ``dse.bucket.first_call``/``warm`` observation per bucket; attr
    ``legal_rows`` is the bucket's legality class count,
    ``h2d_arrays`` the host arrays it handed the device) and
    ``dse.finalize_bucket`` holding ``dse.device_wait`` (the
    realization of the winners: the wait for the device plus the copy).
    The builder thread's spans name the caller's open span (the sweep's
    root) as their parent.  Plus ``dse.transfer_bytes``,
    ``dse.legal_bytes`` (legality handed to the device),
    ``dse.h2d_arrays`` and the ``dse.pipeline.*`` depth/occupancy
    gauges.

    The per-design arguments every bucket shares (design constants,
    traffic rates, ``alpha``, legality classes) are put on the device
    once, at the first bucket (``mapping.reduced_design_block``), and
    handed to each dispatch from there; nothing is kept past the sweep.
    """
    from .compilecache import persistent_cache_dir
    from .energy import grid_kernel_info
    from .mapping import reduce_network_grid, reduced_design_block

    depth = _PIPELINE_DEPTH
    _G_PIPE_DEPTH.set(depth)
    out: list[tuple | None] = [None] * len(shape_layers)
    out_q: queue.Queue = queue.Queue(maxsize=max(2, depth + 1))
    stop = threading.Event()
    builder = threading.Thread(
        target=_bucket_builder,
        args=(shape_layers, designs, scheds, out_q, stop,
              obs.current_span_id()),
        name="repro-sweep-builder", daemon=True)

    pending: collections.deque = collections.deque()
    block = None
    busy = 0.0
    busy_start: float | None = None
    t_loop = time.perf_counter()
    bi = 0
    try:
        while True:
            with obs.span("dse.await_bucket", in_flight=len(pending)):
                if builder.ident is None:
                    # started inside the first wait: the new thread takes
                    # the interpreter lock, and getting it back is part
                    # of waiting for the builder
                    builder.start()
                item = _next_bucket(out_q, builder)
            if item[0] == "error":
                raise item[1]
            if item[0] == "done":
                break
            _, members, net = item
            with obs.span("dse.price_bucket", bucket=bi, lanes=len(net),
                          layers=len(net.layers),
                          designs=net.n_designs, reduced=True) as sp:
                net = _with_survivors(net, survivors)
                shapes_before = grid_kernel_info()["distinct_shapes"]
                h2d_before = _C_H2D.value
                t0 = time.perf_counter()
                if busy_start is None:
                    busy_start = t0
                if block is None:
                    block = reduced_design_block(
                        designs, net.design_class, per_bit=per_bit,
                        alpha=alpha, dram_fj_per_bit=dram)
                    _C_LEGAL_BYTES.inc(8 * len(net.design_class))
                resident = np.asarray(
                    [_resident_bytes_cached(l) for l in net.layers],
                    dtype=np.int64)[net.lane_layer]
                red = reduce_network_grid(
                    net, objective=objective, design_block=block,
                    resident_bytes=resident, buffer_bytes=buffer_bytes)
                sp.lap("dispatch")
                _C_LEGAL_BYTES.inc(net.legal_rows.nbytes)
                new_shapes = (grid_kernel_info()["distinct_shapes"]
                              - shapes_before)
                timer = _T_BUCKET_FIRST if new_shapes else _T_BUCKET_WARM
                timer.observe(time.perf_counter() - t0)
                sp.set(new_kernel_shapes=new_shapes,
                       first_call=bool(new_shapes),
                       persistent_cache=persistent_cache_dir()
                       is not None,
                       legal_rows=len(net.legal_rows),
                       h2d_arrays=_C_H2D.value - h2d_before)
            pending.append((members, net, red))
            bi += 1
            _C_PIPE_BUCKETS.inc()
            if len(pending) >= depth:
                _finalize_bucket(pending.popleft(), out)
                if not pending and busy_start is not None:
                    busy += time.perf_counter() - busy_start
                    busy_start = None
        while pending:
            _finalize_bucket(pending.popleft(), out)
        if busy_start is not None:
            busy += time.perf_counter() - busy_start
            busy_start = None
    finally:
        stop.set()
        if builder.ident is not None:
            builder.join(timeout=10.0)
    wall = time.perf_counter() - t_loop
    _G_PIPE_OCC.set(busy / wall if wall > 0 else 0.0)
    return out


def _mem_pricing(designs: MacroBatch, mem: MemoryModel | None):
    from .memory import DRAM_FJ_PER_BIT, sram_fj_per_bit_grid
    if mem is None:
        return (sram_fj_per_bit_grid(designs.tech_nm, designs.vdd),
                MemoryModel.buffer_bytes, DRAM_FJ_PER_BIT)
    return mem.sram_fj_per_bit(), mem.buffer_bytes, mem.dram_fj_per_bit


def _resolve_survivors(faults, designs: MacroBatch) -> SurvivorMask | None:
    """Normalize the public ``faults=`` argument: ``None`` / an inert
    spec -> ``None`` (the pristine path, bit-for-bit), a
    :class:`FaultSpec` -> its seeded draw over ``designs``, a
    pre-drawn :class:`SurvivorMask` -> itself (callers sharing one draw
    across sweeps, e.g. the chaos harness's accuracy leg)."""
    if faults is None:
        return None
    if isinstance(faults, SurvivorMask):
        return faults
    if isinstance(faults, FaultSpec):
        return survivor_mask(faults, designs) if faults.enabled else None
    raise TypeError(f"faults must be FaultSpec | SurvivorMask | None, "
                    f"got {type(faults).__name__}")


def sweep_networks(networks: Sequence[tuple[str, Sequence[Layer]]],
                   designs: MacroBatch, objective: str = "energy",
                   alpha: float | None = None,
                   mem: MemoryModel | None = None,
                   schedules=None,
                   faults: "FaultSpec | SurvivorMask | None" = None
                   ) -> tuple[SweepResult, ...]:
    """Price *several* workloads against a macro grid in one fused pass.

    Layer shapes are deduplicated globally (``_shape_key``) across all
    networks, so e.g. the dense classifier heads the tinyMLPerf nets
    share occupy one lattice slot; the union of distinct shapes is then
    priced through as few fused jit dispatches as the lane budget
    allows (usually one) and each network's :class:`SweepResult` is
    assembled from the shared per-(shape, design) winners.  Every
    returned result is bitwise what :func:`sweep` alone would return
    for that network — same totals, same winners, same tie-breaks.

    ``faults`` degrades every design by its seeded survivor mask
    (``repro.faults``): mappings that no longer fit the surviving
    column groups / macro count drop out of the legality mask before
    the argmin, so one call answers "which design wins at N% failure".
    Costs of surviving lanes are untouched and the oracle is
    :func:`best_mapping_scalar` with the matching ``survivors=`` filter
    — parity stays bitwise.  ``faults=None`` (or an all-zero spec) is
    the identical pristine code path.
    """
    if objective not in OBJECTIVES:
        raise KeyError(objective)
    survivors = _resolve_survivors(faults, designs)
    with obs.span("dse.sweep_networks", networks=len(networks),
                  designs=len(designs), objective=objective,
                  faults=survivors is not None):
        return _sweep_networks_traced(networks, designs, objective, alpha,
                                      mem, schedules, survivors)


def _sweep_networks_traced(networks, designs, objective, alpha, mem,
                           schedules,
                           survivors: SurvivorMask | None = None
                           ) -> tuple[SweepResult, ...]:
    """Body of :func:`sweep_networks`, under its root span — the span
    covers lattice build, every bucket dispatch and result assembly
    (``dse.assemble``), so trace wall-time coverage of a sweep is the
    root span itself."""
    # persist XLA executables across processes (no-op after first call;
    # see core.compilecache)
    from .compilecache import enable_compilation_cache
    enable_compilation_cache()
    scheds = _normalize_schedules(schedules)
    per_bit, buffer_bytes, dram = _mem_pricing(designs, mem)
    n_designs = len(designs)

    shape_layers: list[Layer] = []
    shape_index: dict[tuple, int] = {}
    nets: list[tuple[str, list[Layer], list[int]]] = []
    for network, layers in networks:
        eligible = [l for l in layers if l.imc_eligible]
        if not eligible:
            raise ValueError(f"{network}: no IMC-eligible layers")
        layer_shape: list[int] = []
        for layer in eligible:
            key = _shape_key(layer)
            if key not in shape_index:
                shape_index[key] = len(shape_layers)
                shape_layers.append(layer)
            layer_shape.append(shape_index[key])
        nets.append((network, eligible, layer_shape))

    priced = _price_shapes(shape_layers, designs, objective, alpha,
                           per_bit, buffer_bytes, dram, scheds,
                           survivors=survivors)
    with obs.span("dse.assemble", networks=len(nets)):
        _C_LAT_SLOTS.inc(len(shape_layers))
        _C_LAT_LAYERS.inc(sum(len(n[2]) for n in nets))

        area = designs.area_mm2()
        results = []
        for network, eligible, layer_shape in nets:
            # per-network slot table in first-appearance order, so the
            # stored shapes/_layer_shape match what sweep() alone builds
            local: dict[int, int] = {}
            shapes: list[tuple] = []
            local_shape: list[int] = []
            for layer, si in zip(eligible, layer_shape):
                if si not in local:
                    local[si] = len(shapes)
                    grid, best_idx, total, cyc = priced[si]
                    shapes.append((layer, grid, best_idx, total, cyc))
                local_shape.append(local[si])
            # network totals, accumulated in layer order like NetworkResult
            energy = np.zeros(n_designs, dtype=np.float64)
            cycles = np.zeros(n_designs, dtype=np.int64)
            for si in local_shape:
                energy = energy + shapes[si][3]
                cycles = cycles + shapes[si][4]
            results.append(SweepResult(
                network=network, objective=objective, designs=designs,
                energy_fj=energy, cycles=cycles, area_mm2=area,
                layer_names=tuple(l.name for l in eligible),
                schedules=_schedule_names(scheds),
                survivors=survivors,
                _shapes=tuple((s[0], s[1], s[2]) for s in shapes),
                _layer_shape=tuple(local_shape), _alpha=alpha, _mem=mem))
        return tuple(results)


def sweep(network: str, layers: Sequence[Layer], designs: MacroBatch,
          objective: str = "energy", alpha: float | None = None,
          mem: MemoryModel | None = None,
          schedules=None,
          faults: "FaultSpec | SurvivorMask | None" = None) -> SweepResult:
    """Price a whole macro grid against a workload in one batched pass.

    For every design in ``designs`` (a ``designs.MacroBatch``) and every
    IMC-eligible layer, the full legal (mapping x dataflow) lattice is
    evaluated through the jitted grid engine and the per-layer argmin
    under ``objective`` is kept — the same candidate, bitwise, that
    ``best_mapping`` would pick on that design (the fused lattice's
    masked lane axis preserves the scalar enumeration order per layer
    segment, schedule inner, so even ties break identically).  Repeated
    layer shapes are deduplicated into one lattice slot, like the
    layer-result cache, and *all* distinct shapes are priced together
    through the workload-fused lane axis — one jit dispatch per lane
    bucket (usually one per network) instead of one per layer shape.

    ``mem=None`` (default) gives each design its own
    ``MemoryModel(tech_nm, vdd)``, matching ``map_network``; passing an
    explicit model prices every design against that one memory system.
    ``schedules`` enables the dataflow axis (default: weight-stationary
    only); the chosen-per-layer dataflow is surfaced via
    :meth:`SweepResult.dataflows`.  To amortize the fused dispatch over
    several workloads at once, see :func:`sweep_networks`.
    """
    return sweep_networks(((network, layers),), designs,
                          objective=objective, alpha=alpha, mem=mem,
                          schedules=schedules, faults=faults)[0]


# --------------------------------------------------------------------------- #
# serving operating-point sweep: prefill/decode phases + KV hierarchy          #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ServingPointResult:
    """Per-design serving cost at ONE (prompt_len x batch) operating
    point: the phase-split (prefill + decode) MVM cost from the fused
    lattice plus the KV-cache hierarchy traffic, folded into
    (tokens/s, J/token).  ``sum_phase`` runs over ``point.phases`` in
    order: every layer group of every phase.

    All arrays are (D,), indexed like ``designs``.  The float
    association of every derived column is pinned (and property-tested)
    against the scalar per-design oracle ``serving_point_scalar``:

    * ``energy_fj[d]  = sum_phase sweep.energy_fj[d] * repeats``
    * ``kv_energy_fj[d] = sum_phase kv_traffic_energy(phase, d)``
    * ``total_fj = energy_fj + kv_energy_fj`` (MVM first, KV second)
    * ``cycles[d] = sum_phase float64(sweep.cycles[d]) * repeats``
    * ``time_s = cycles / (f_clk_ghz * 1e9)``;
      ``tokens_per_s = tokens_out / time_s``;
      ``j_per_token = (total_fj * 1e-15) / tokens_out``
    """

    point: ServingPoint
    objective: str
    designs: MacroBatch
    phase_sweeps: tuple[SweepResult, ...]   # aligned with point.phases
    energy_fj: np.ndarray                   # (D,) MVM + operand traffic
    kv_energy_fj: np.ndarray                # (D,) KV hierarchy traffic
    cycles: np.ndarray                      # (D,) float64 request cycles
    tokens_per_s: np.ndarray                # (D,) generated-token rate
    j_per_token: np.ndarray                 # (D,) Joules per generated token

    def __len__(self) -> int:
        return len(self.energy_fj)

    @property
    def total_fj(self) -> np.ndarray:
        return self.energy_fj + self.kv_energy_fj

    def best(self, objective: str | None = None) -> int:
        """Argmin design index: ``"energy"``/``"edp"`` rank by
        J/token (their per-request order), ``"latency"`` by cycles —
        i.e. the per-operating-point winner under the sweep objective."""
        obj = objective or self.objective
        if obj == "latency":
            return int(np.argmin(self.cycles))
        if obj == "edp":
            return int(np.argmin(self.total_fj * self.cycles))
        return int(np.argmin(self.j_per_token))

    def pareto_mask(self) -> np.ndarray:
        """(D,) bool: non-dominated over (tokens/s max, J/token min) —
        the serving frontier the benchmark renders per operating
        point."""
        return _non_dominated(np.stack(
            [-self.tokens_per_s, self.j_per_token], axis=1))

    def pareto(self) -> np.ndarray:
        """Frontier design indices, throughput-descending."""
        idx = np.flatnonzero(self.pareto_mask())
        return idx[np.argsort(-self.tokens_per_s[idx], kind="stable")]

    def to_records(self) -> list[dict]:
        """One JSON-ready row per design (``BENCH_serving.json``)."""
        mask = self.pareto_mask()
        return [{
            "name": self.designs.names[d],
            "analog": bool(self.designs.analog[d]),
            "tokens_per_s": float(self.tokens_per_s[d]),
            "j_per_token": float(self.j_per_token[d]),
            "energy_fj": float(self.energy_fj[d]),
            "kv_energy_fj": float(self.kv_energy_fj[d]),
            "cycles": float(self.cycles[d]),
            "pareto": bool(mask[d]),
        } for d in range(len(self))]


def _f_clk_ghz(designs: MacroBatch) -> np.ndarray:
    """(D,) per-design macro clock — the scalar property per row, so
    grid-side time conversions are trivially bitwise vs the oracle."""
    return np.array([m.f_clk_ghz for m in designs.macros], dtype=np.float64)


def sweep_serving(points: Sequence[ServingPoint], designs: MacroBatch,
                  objective: str = "energy", alpha: float | None = None,
                  mem: MemoryModel | None = None, schedules=None,
                  kv_hier: KVCacheHierarchy = KVCacheHierarchy(),
                  faults: "FaultSpec | SurvivorMask | None" = None
                  ) -> tuple[ServingPointResult, ...]:
    """Price a serving operating-point grid against a macro grid in one
    fused pass — the serving axis of the DSE lattice.

    Every phase group of every point enters :func:`sweep_networks` as
    its own workload (named ``<point>/<PhaseWorkload.tag>``), so the
    whole (point x phase x layer x design x mapping x dataflow) lattice
    shares one lane axis, one set of jit dispatches
    and the usual finite-sentinel masking; the per-(layer, design)
    argmin is therefore taken *per operating point* and is bitwise what
    ``map_network`` on that phase alone would pick.  On top of the MVM
    sweep each phase's KV-cache byte volumes are priced through
    ``memory.kv_traffic_energy_grid`` at the per-design SRAM rate
    (``mem=None``) or the shared memory model's — tier-selected by the
    phase's live working set.  Build ``points`` with
    ``lm_bridge.serving_points``.
    """
    with obs.span("dse.sweep_serving", points=len(points),
                  designs=len(designs)):
        nets = []
        for pt in points:
            for ph in pt.phases:
                nets.append((f"{pt.name}/{ph.tag}", list(ph.layers)))
        sweeps = sweep_networks(nets, designs, objective=objective,
                                alpha=alpha, mem=mem, schedules=schedules,
                                faults=faults)
        with obs.span("dse.assemble", points=len(points)):
            per_bit, _, _ = _mem_pricing(designs, mem)
            f_clk = _f_clk_ghz(designs)
            n_designs = len(designs)

            results = []
            it = iter(sweeps)
            for pt in points:
                if pt.tokens_out <= 0:
                    raise ValueError(f"{pt.name}: no generated tokens "
                                     f"(gen_len must be >= 1)")
                with obs.span("dse.serving_point", point=pt.name,
                              phases=len(pt.phases)):
                    phase_sweeps = tuple(next(it) for _ in pt.phases)
                    energy = np.zeros(n_designs, dtype=np.float64)
                    kv = np.zeros(n_designs, dtype=np.float64)
                    cycles = np.zeros(n_designs, dtype=np.float64)
                    for ph, sw in zip(pt.phases, phase_sweeps):
                        energy = energy + sw.energy_fj * ph.repeats
                        cycles = (cycles + sw.cycles.astype(np.float64)
                                  * ph.repeats)
                        kv = kv + kv_traffic_energy_grid(
                            per_bit, ph.kv_read_bytes, ph.kv_write_bytes,
                            ph.kv_live_bytes, kv_hier)
                    total = energy + kv
                    time_s = cycles / (f_clk * 1e9)
                    results.append(ServingPointResult(
                        point=pt, objective=objective, designs=designs,
                        phase_sweeps=phase_sweeps,
                        energy_fj=energy, kv_energy_fj=kv, cycles=cycles,
                        tokens_per_s=pt.tokens_out / time_s,
                        j_per_token=(total * 1e-15) / pt.tokens_out))
            return tuple(results)


def serving_point_scalar(pt: ServingPoint, macro: IMCMacro,
                         objective: str = "energy",
                         alpha: float | None = None,
                         mem: MemoryModel | None = None, schedules=None,
                         kv_hier: KVCacheHierarchy = KVCacheHierarchy()
                         ) -> dict[str, float]:
    """Reference oracle for ONE (operating point, design) pair: the
    per-phase scalar ``map_network`` loop plus python-float KV pricing,
    combined with exactly the association :func:`sweep_serving`
    documents.  Never vectorized; the fused serving lattice is
    property-tested bitwise against this."""
    m = mem or MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
    per_bit = m.sram_fj_per_bit()
    energy = 0.0
    kv = 0.0
    cycles = 0.0
    for ph in pt.phases:
        net = map_network(f"{pt.name}/{ph.tag}", list(ph.layers), macro,
                          objective=objective, mem=m, alpha=alpha,
                          engine="scalar", schedules=schedules)
        energy = energy + net.total_energy_fj * ph.repeats
        cycles = cycles + float(net.total_cycles) * ph.repeats
        kv = kv + kv_hier.traffic_energy_fj(
            per_bit, ph.kv_read_bytes, ph.kv_write_bytes, ph.kv_live_bytes)
    total = energy + kv
    time_s = cycles / (macro.f_clk_ghz * 1e9)
    return {
        "energy_fj": energy, "kv_energy_fj": kv, "cycles": cycles,
        "tokens_per_s": pt.tokens_out / time_s,
        "j_per_token": (total * 1e-15) / pt.tokens_out,
    }


def _non_dominated(pts: np.ndarray) -> np.ndarray:
    """(D,) bool mask of Pareto-optimal rows of a (D, n_axes) matrix,
    all axes minimized: row i survives iff no row j is <= on every axis
    and < on at least one.  O(D^2) pairwise scan; fine for grids of a
    few thousand points."""
    ge_all = (pts[:, None, :] >= pts[None, :, :]).all(-1)   # [i,j]: j<=i
    gt_any = (pts[:, None, :] > pts[None, :, :]).any(-1)    # [i,j]: j<i
    return ~(ge_all & gt_any).any(axis=1)


# --------------------------------------------------------------------------- #
# joint accuracy x cost frontier                                               #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class JointFrontier:
    """Per-design (accuracy, energy, latency[, area]) over one grid.

    Joins a :class:`SweepResult` (cost axes, minimized) with a
    per-design accuracy column (maximized) — typically
    ``fidelity.evaluate_grid``'s output on the same ``MacroBatch``.
    This is the paper's three-way AIMC/DIMC trade made explicit: the
    designs surviving ``pareto_mask()`` are exactly those where more
    accuracy costs energy or latency.
    """

    sweep: SweepResult
    accuracy: np.ndarray                 # (D,) higher is better
    sqnr_db: np.ndarray | None = None    # (D,) optional companion metric

    def __len__(self) -> int:
        return len(self.accuracy)

    @property
    def designs(self) -> MacroBatch:
        return self.sweep.designs

    @property
    def energy_fj(self) -> np.ndarray:
        return self.sweep.energy_fj

    @property
    def cycles(self) -> np.ndarray:
        return self.sweep.cycles

    @property
    def area_mm2(self) -> np.ndarray:
        return self.sweep.area_mm2

    def pareto_mask(self, include_area: bool = False) -> np.ndarray:
        """(D,) bool: non-dominated over (accuracy max, energy min,
        latency min[, area min]) — the accuracy axis enters the shared
        dominance scan negated."""
        cols = [-self.accuracy, self.energy_fj,
                self.cycles.astype(np.float64)]
        if include_area:
            cols.append(self.area_mm2)
        return _non_dominated(np.stack(cols, axis=1))

    def pareto(self, include_area: bool = False) -> np.ndarray:
        """Frontier design indices, sorted accuracy-descending (ties by
        ascending energy)."""
        idx = np.flatnonzero(self.pareto_mask(include_area))
        order = np.lexsort((self.energy_fj[idx], -self.accuracy[idx]))
        return idx[order]

    def best(self, min_accuracy: float = 0.0,
             objective: str = "energy") -> int:
        """Cheapest design under ``objective`` meeting the accuracy
        floor; falls back to the most accurate design when nothing
        clears the floor."""
        col = {"energy": self.energy_fj, "latency": self.cycles,
               "edp": self.sweep.edp}[objective]
        ok = np.flatnonzero(self.accuracy >= min_accuracy)
        if len(ok) == 0:
            return int(np.argmax(self.accuracy))
        return int(ok[np.argmin(col[ok])])

    def to_records(self) -> list[dict]:
        """One JSON-ready row per design (artifact / rendering format)."""
        mask = self.pareto_mask()
        return [{
            "name": self.designs.names[d],
            "analog": bool(self.designs.analog[d]),
            "accuracy": float(self.accuracy[d]),
            "sqnr_db": (None if self.sqnr_db is None
                        else float(self.sqnr_db[d])),
            "energy_fj": float(self.energy_fj[d]),
            "cycles": int(self.cycles[d]),
            "area_mm2": float(self.area_mm2[d]),
            "pareto": bool(mask[d]),
        } for d in range(len(self))]


def joint_frontier(sweep_result: SweepResult, accuracy) -> JointFrontier:
    """Join cost and accuracy axes computed on the same design grid.

    ``accuracy`` is either a (D,) array or a ``fidelity.FidelityGrid``
    (duck-typed: anything with ``accuracy`` / ``designs`` attributes —
    ``core`` stays import-independent of ``fidelity``); design identity
    is checked by name so mismatched grids fail loudly.
    """
    sqnr = None
    acc = accuracy
    if hasattr(accuracy, "accuracy"):
        grid = getattr(accuracy, "designs", None)
        if grid is not None and grid.names != sweep_result.designs.names:
            raise ValueError(
                "joint_frontier: accuracy grid and sweep were computed on "
                "different designs")
        sqnr = np.asarray(accuracy.sqnr_db) \
            if getattr(accuracy, "sqnr_db", None) is not None else None
        acc = accuracy.accuracy
    acc = np.asarray(acc, dtype=np.float64)
    if acc.shape != sweep_result.energy_fj.shape:
        raise ValueError(
            f"joint_frontier: accuracy shape {acc.shape} != designs "
            f"{sweep_result.energy_fj.shape}")
    return JointFrontier(sweep=sweep_result, accuracy=acc, sqnr_db=sqnr)


def map_network(network: str, layers: Sequence[Layer], macro: IMCMacro,
                objective: str = "energy",
                mem: MemoryModel | None = None,
                alpha: float | None = None,
                engine: str = "batch",
                schedules=None) -> NetworkResult:
    """Map every IMC-eligible layer of a network onto one macro.

    ``engine="batch"`` (default) runs the vectorized per-layer NumPy
    search through the layer-result cache; ``"scalar"`` the uncached
    reference loop; ``"grid"`` prices the whole network through the
    workload-fused jit lattice (one dispatch for all distinct layer
    shapes on a single-design batch — the fastest path when the same
    macro is priced against many layers once, e.g. the benchmark case
    studies).  All three return bitwise-identical results; ``"grid"``
    shares the layer-result cache with ``"batch"``.
    """
    mem = mem or MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
    if engine == "grid":
        return _map_network_grid(network, layers, macro, mem,
                                 objective=objective, alpha=alpha,
                                 schedules=schedules)
    results = tuple(
        best_mapping(l, macro, mem, objective=objective, alpha=alpha,
                     engine=engine, schedules=schedules)
        for l in layers if l.imc_eligible)
    return NetworkResult(network=network, macro_name=macro.name,
                         layers=results)


def _map_network_grid(network: str, layers: Sequence[Layer],
                      macro: IMCMacro, mem: MemoryModel,
                      objective: str = "energy",
                      alpha: float | None = None,
                      schedules=None) -> NetworkResult:
    """Fused-lattice ``map_network``: consult the shared layer-result
    cache, price every missing shape in one single-design
    :func:`sweep`, and rebuild the winners through the scalar oracle
    (so results stay bitwise equal to the other engines).  Cache
    hit/miss accounting matches the per-layer ``best_mapping`` path:
    the first occurrence of a shape is a miss, repeats are hits."""
    scheds = _normalize_schedules(schedules)
    eligible = [l for l in layers if l.imc_eligible]
    pending: dict[tuple, Layer] = {}
    for layer in eligible:
        key = _cache_key(layer, macro, mem, objective, alpha, scheds)
        if key in _CACHE or key in pending:
            _C_HITS.inc()
        else:
            _C_MISSES.inc()
            pending[key] = layer
    if pending:
        res = sweep(network, list(pending.values()),
                    MacroBatch.from_macros([macro]), objective=objective,
                    alpha=alpha, mem=mem, schedules=scheds)
        net0 = res.network_result(0)
        for key, lr in zip(pending, net0.layers):
            _CACHE[key] = lr
    results = []
    for layer in eligible:
        hit = _CACHE[_cache_key(layer, macro, mem, objective, alpha, scheds)]
        results.append(hit if hit.layer.name == layer.name
                       else dataclasses.replace(hit, layer=layer))
    return NetworkResult(network=network, macro_name=macro.name,
                         layers=tuple(results))
