"""Dense macro-grid design-space sweep, AIMC vs DIMC (the follow-up
work arXiv 2405.14978 sweeps thousands of macro configurations per
workload; this reproduces that experiment shape on the paper's cost
model).

One ``dse.sweep`` call prices every (design x mapping-candidate) pair
of each tinyMLPerf workload through the jitted grid engine and reports,
per IMC type, the best design under energy / latency / EDP plus the
(energy, latency, area) Pareto frontier — the macro-level answer to
"which IMC style wins where".  With ``--dataflows`` the sweep also
searches the temporal schedule axis (weight- vs output-stationary) per
layer and reports how often each dataflow wins — the flexibility axis
of the paper's three-way AIMC/DIMC trade.

With ``--networks`` the whole workload suite is priced in ONE
workload-fused pass (``dse.sweep_networks``: every distinct layer
shape of every network shares one padded lane lattice and one jit
compile) and a ``BENCH_sweep.json`` timing artifact is written — cold
and warm wall time, vectorized lattice-build time, kernel
dispatch/compile counters, compilation-cache state and lattice padding
stats — one point of the committed ``BENCH_trajectory.json`` history
(see ``benchmarks.trajectory``).  Timing sections block on the sweep
result before stopping the clock, and the artifact is written
atomically (tmp + rename).

Env knobs
---------
``JAX_COMPILATION_CACHE_DIR``
    Persistent XLA compilation-cache directory (default: the
    git-ignored ``.jax_cache/`` at the repo root;
    ``JAX_ENABLE_COMPILATION_CACHE=false`` disables it).  With a warm
    cache, "cold" sweeps skip their XLA compiles entirely — across
    benchmark runs and CI jobs.
``REPRO_FAULT_RATE`` / ``REPRO_FAULT_SEED``
    Degraded-mode sweep: a non-zero rate builds a seeded
    ``repro.faults.FaultSpec`` (rate applied to both stuck column
    groups and macro dropout, seed pinning the survivor draw) and the
    whole sweep prices only the mappings that survive — the survivor
    mask ANDs into the lattice's ``legal`` plane, so no cost kernel,
    jit graph or compile count changes (the sweep folds the degraded
    mask in on the device).  The artifact records the active
    rate/seed under ``"faults"``; unset/0 is bit-for-bit the pristine
    sweep.  The dedicated fault-rate axis sweep lives in
    ``benchmarks.chaos_sweep``.
``REPRO_TRACE``
    Turn on span tracing (``repro.obs``).  The fused sweep then records
    nested wall-time spans — lattice builds, per-bucket jit dispatch
    with compile-vs-execute attribution, kernel calls — and
    ``--networks`` writes ``design_sweep_trace.json`` (Chrome
    trace-event format, loadable in ``chrome://tracing``/Perfetto) plus
    ``design_sweep_telemetry.jsonl`` next to the artifact.  Tracing is
    inert: sweep outputs are bitwise identical on/off.
``REPRO_TRACE_DIR``
    Directory for the trace files above (default: current directory).

Telemetry artifact schema
-------------------------
``BENCH_sweep.json`` carries a ``"telemetry"`` block
(``repro.obs.telemetry_block``):

* ``trace_enabled`` — whether spans were recorded this run;
* ``metrics`` — full registry snapshot (``dse.cache.*`` layer-result
  cache hits/misses/evictions, ``dse.lattice.*`` slot/lane/eviction
  counters, ``energy.kernel.*`` dispatch/compile-proxy counters,
  ``dse.bucket.first_call``/``dse.bucket.warm`` compile-vs-execute
  timer splits, ``compilecache.*`` persistent-cache gauges);

and the top level carries the reduced-engine headline numbers of the
cold pass: ``transfer_bytes_cold`` — measured device→host bytes
realized by bucket pricing (the quantity the reduced path collapses
from nine (D, Ctot) float64 grids to 3·S·D winners per bucket) —
plus ``pipeline_depth`` and ``pipeline_occupancy`` (in-flight depth
actually used and the fraction of finalizations that never had to
wait, 0/0.0 under the host oracle);
* ``spans`` — per-name ``{count, total_s}`` rollup of recorded spans;
* ``cache`` — headline hit-rate/eviction numbers;
* ``span_coverage_cold`` (tracing only) — fraction of the cold-sweep
  wall covered by the root ``dse.sweep_networks`` span;
* ``trace_files`` (tracing only) — paths of the exported traces.

Run:  PYTHONPATH=src python -m benchmarks.design_sweep \
          [--smoke] [--dataflows] [--networks] [--out BENCH_sweep.json]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import obs
from repro.core import designs, dse, energy, mapping, workloads
from repro.core.compilecache import compilation_cache_info
from repro.faults import FaultSpec

from .common import emit, sync, timed, write_json_atomic


def make_grid(smoke: bool = False) -> designs.MacroBatch:
    """The swept knob ranges: >= 1000 designs (a 2405.14978-scale dense
    grid) in full mode, a handful in smoke mode so CI stays fast."""
    if smoke:
        return designs.macro_grid(
            rows=(64, 256), cols=(256,), adc_bits=(4, 6), dac_bits=(2,),
            m_mux=(1, 16), tech_nm=(22,), vdd=(0.8,))
    return designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))


def run(smoke: bool = False, dataflows: bool = False) -> None:
    grid = make_grid(smoke)
    schedules = ("ws", "os") if dataflows else None
    faults = FaultSpec.from_env()
    nets = (("deep_autoencoder", workloads.deep_autoencoder()),)
    if not smoke:
        nets += (("resnet8", workloads.resnet8()),)

    for net_name, layers in nets:
        def sweep_net() -> str:
            res = dse.sweep(net_name, layers, grid, schedules=schedules,
                            faults=faults)
            aimc = np.flatnonzero(grid.analog)
            dimc = np.flatnonzero(~grid.analog)
            total_macs = sum(l.macs for l in layers if l.imc_eligible)

            def best_of(idx: np.ndarray) -> int:
                return int(idx[np.argmin(res.energy_fj[idx])])

            print(f"# {net_name}: {len(grid)} designs "
                  f"({len(aimc)} AIMC / {len(dimc)} DIMC), "
                  f"objective={res.objective}, "
                  f"dataflows={'+'.join(res.schedules)}")
            print(f"# {'design':44s} {'fJ/MAC':>8s} {'Mcycles':>9s} "
                  f"{'mm2':>7s}")
            for tag, d in (("best AIMC", best_of(aimc)),
                           ("best DIMC", best_of(dimc))):
                line = (f"# {tag}: {grid.names[d]:42s}"
                        f" {res.energy_fj[d] / total_macs:8.2f}"
                        f" {res.cycles[d] / 1e6:9.2f}"
                        f" {res.area_mm2[d]:7.3f}")
                if dataflows:
                    counts = res.dataflow_counts(d)
                    line += " " + ",".join(f"{k}:{v}" for k, v
                                           in sorted(counts.items()))
                print(line)
            front = res.pareto()
            for d in front[:5]:
                print(f"#   pareto {grid.names[d]:42s}"
                      f" {res.energy_fj[d] / total_macs:8.2f}"
                      f" {res.cycles[d] / 1e6:9.2f}"
                      f" {res.area_mm2[d]:7.3f}")
            winner = "AIMC" if bool(grid.analog[res.best()]) else "DIMC"
            derived = (f"designs={len(grid)} pareto={len(front)} "
                       f"energy_winner={winner}")
            if dataflows:
                # how many designs map at least one layer output-stationary
                os_designs = sum(
                    1 for d in range(len(grid))
                    if res.dataflow_counts(d).get("os", 0) > 0)
                derived += f" os_designs={os_designs}"
            return derived

        timed(f"design_sweep_{net_name}", sweep_net)


def run_networks(smoke: bool = False, dataflows: bool = False,
                 out: str = "BENCH_sweep.json") -> dict:
    """Workload-fused multi-network sweep + ``BENCH_sweep.json`` artifact.

    All networks are priced through ``dse.sweep_networks`` — one padded
    lane lattice, typically one jit compile — measured cold (compiles
    and lattice builds included) and warm (best of 3).  The artifact
    records the wall times alongside the fused-kernel dispatch counters
    (``energy.grid_kernel_info``: ``distinct_shapes`` is the XLA
    compile-count proxy) and the lattice slot/padding stats
    (``dse.cache_info``), so CI uploads a comparable timing point per
    commit.
    """
    grid = make_grid(smoke)
    schedules = ("ws", "os") if dataflows else None
    faults = FaultSpec.from_env()
    nets = [("deep_autoencoder", workloads.deep_autoencoder()),
            ("ds_cnn", workloads.ds_cnn())]
    if not smoke:
        nets += [("resnet8", workloads.resnet8()),
                 ("mobilenet_v1_025", workloads.mobilenet_v1_025())]

    dse.cache_clear()
    energy.grid_kernel_reset()
    obs.drain_spans()
    t0 = time.perf_counter()
    results = sync(dse.sweep_networks(nets, grid, schedules=schedules,
                                      faults=faults))
    t_cold = time.perf_counter() - t0
    kernel_cold = energy.grid_kernel_info()
    cache = dse.cache_info()
    # reduced-engine headline of the cold pass (cache_clear above reset
    # the dse.* registry, so these are this sweep's numbers alone)
    pipe_cold = obs.snapshot("dse.")

    t_warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        sync(dse.sweep_networks(nets, grid, schedules=schedules,
                                faults=faults))
        t_warm = min(t_warm, time.perf_counter() - t0)

    # isolated lattice-build wall time (the vectorized candidate_grid
    # path), rebuilt fresh per distinct shape — the component the cold
    # time above amortizes through the lattice memo
    shape_layers: list = []
    seen: set = set()
    for _, layers in nets:
        for l in layers:
            if l.imc_eligible and dse._shape_key(l) not in seen:
                seen.add(dse._shape_key(l))
                shape_layers.append(l)
    t0 = time.perf_counter()
    for l in shape_layers:
        mapping.candidate_grid(l, grid, schedules=schedules)
    t_lattice = time.perf_counter() - t0

    per_network = {}
    for res in results:
        best = res.best()
        per_network[res.network] = {
            "layers": len(res.layer_names),
            "distinct_shapes": res.n_shapes,
            "best_design": grid.names[best],
            "best_energy_fj": float(res.energy_fj[best]),
            "pareto_designs": int(res.pareto_mask().sum()),
        }
        print(f"# {res.network}: best={grid.names[best]} "
              f"energy={res.energy_fj[best]:.3e} fJ "
              f"pareto={per_network[res.network]['pareto_designs']}")

    artifact = {
        "benchmark": "design_sweep_networks",
        "smoke": smoke,
        "designs": len(grid),
        "networks": [n for n, _ in nets],
        "schedules": list(results[0].schedules),
        "cold_s": t_cold,
        "warm_s": t_warm,
        "lattice_build_s": t_lattice,
        "kernel_calls_cold": kernel_cold["calls"],
        "kernel_distinct_shapes_cold": kernel_cold["distinct_shapes"],
        "pipeline_depth": int(pipe_cold.get("dse.pipeline.depth", 0)),
        "pipeline_occupancy": float(
            pipe_cold.get("dse.pipeline.occupancy", 0.0)),
        "transfer_bytes_cold": int(
            pipe_cold.get("dse.transfer_bytes", 0)),
        "faults": {"enabled": faults.enabled,
                   "rate": faults.column_fail_rate, "seed": faults.seed},
        "compilation_cache": compilation_cache_info(),
        "lattice_slots": cache["lattice_slots"],
        "lattice_layers": cache["lattice_layers"],
        "padding_waste": cache["padding_waste"],
        "per_network": per_network,
    }
    tele = obs.telemetry_block()
    if obs.trace_enabled():
        # the root sweep span covers lattice build + every bucket
        # dispatch + assembly; its share of the measured cold wall is
        # the trace-coverage acceptance number
        roots = [s for s in obs.iter_spans()
                 if s["name"] == "dse.sweep_networks"]
        if roots:
            tele["span_coverage_cold"] = min(
                1.0, roots[0]["dur_us"] / 1e6 / max(t_cold, 1e-9))
        tele["trace_files"] = obs.export_all(prefix="design_sweep")
    artifact["telemetry"] = tele
    write_json_atomic(out, artifact)
    print(f"# wrote {out}: cold={t_cold:.3f}s warm={t_warm:.3f}s "
          f"compiles~{kernel_cold['distinct_shapes']} "
          f"(dispatches={kernel_cold['calls']}) "
          f"slots={cache['lattice_slots']} "
          f"waste={cache['padding_waste']:.1%}")
    emit("design_sweep_networks", t_cold * 1e6,
         f"networks={len(nets)} designs={len(grid)} "
         f"slots={cache['lattice_slots']} "
         f"compiles={kernel_cold['distinct_shapes']} "
         f"warm_us={t_warm * 1e6:.1f}")
    return artifact


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + single network so CI can exercise "
                         "the full grid path in seconds")
    ap.add_argument("--dataflows", action="store_true",
                    help="search the temporal dataflow axis (ws+os) per "
                         "layer instead of weight-stationary only")
    ap.add_argument("--networks", action="store_true",
                    help="price the whole workload suite in one "
                         "workload-fused pass and write the timing "
                         "artifact (see --out)")
    ap.add_argument("--out", default="BENCH_sweep.json",
                    help="artifact path for --networks "
                         "(default: BENCH_sweep.json)")
    args = ap.parse_args()
    if args.networks:
        run_networks(smoke=args.smoke, dataflows=args.dataflows,
                     out=args.out)
    else:
        run(smoke=args.smoke, dataflows=args.dataflows)
