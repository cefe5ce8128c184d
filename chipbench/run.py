#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process.  It finds the accelerator (no CPU fallback: without one it
exits non-zero and prints no result), keeps JAX's persistent compilation
cache in ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
names one, makes the cell's inputs from ``--seed`` and warms every shape
the window uses (all of that is ``setup_s``), then drives the cell's
traffic for ``--seconds`` and checks a seeded sample of what the window
produced against the plain reference.

Everything particular to a cell lives in data files and small modules
found by name: the configuration file that ``BENCHMARK.json`` names,
``traffic/<mix>.json`` (whose ``runner`` names ``runners/<runner>.py``)
and one reader per metric in ``metrics/<metric>.py``.  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and the
program's own spans and counters.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, optionally ``breakdown``, and last
``check``: each compared number with its limit); the compared numbers
are also the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
#: switches of the program that select non-default paths; the benchmark
#: measures the default path, so it never passes them on
PROGRAM_SWITCHES = ("REPRO_SWEEP_PIPELINE", "REPRO_SWEEP_SHARDS",
                    "REPRO_TRACE", "REPRO_TRACE_DIR", "REPRO_FAULT_RATE",
                    "REPRO_FAULT_SEED")


class NoAccelerator(RuntimeError):
    pass


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, cell, configuration, traffic) for ``workload``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def applicable(spec: dict, workload: str, per_layer: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports."""
    def has(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if has(m)]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_metrics(metrics: list[dict], ctx: dict) -> dict:
    out = {}
    for m in metrics:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{m['name']}", path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def find_devices(chips: int) -> list:
    """The chips a cell of ``chips`` runs on; never the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform 'cpu')")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str | None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = ROOT / ".jax_cache"
        path.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    from repro.core.compilecache import enable_compilation_cache
    return enable_compilation_cache()


class CompileCounter:
    """Counts lowerings to XLA (every new jit shape lowers, whether the
    persistent cache then has it or not) while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event in self.counts:
            self.counts[event] += 1


def run(args) -> dict:
    for name in PROGRAM_SWITCHES:
        os.environ.pop(name, None)
    spec, cell, config, traffic = load_cell(args.workload)
    sys.path[:0] = [str(ROOT / "src")]
    devices = find_devices(cell["chips"])
    from chipbench import peaks
    peaks.lookup(devices[0].device_kind)
    cache_dir = enable_compile_cache()

    import jax

    from repro import obs
    runner = importlib.import_module(
        f"chipbench.runners.{traffic['runner']}").make(config, traffic,
                                                      args.seed)
    runner.setup()
    counter = CompileCounter()
    trace_dir = None
    if args.trace:
        obs.set_trace_enabled(True)
        obs.drain_spans()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    counter.active = True
    units = steps = 0
    with jax.profiler.TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            units += runner.step(steps)
            steps += 1
            if time.perf_counter() - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
    counter.active = False
    ctx = {"workload": args.workload, "seconds": window_s,
           "setup_s": setup_s, "units": units, "steps": steps,
           "runner": runner.stats(), "spans": None, "trace": None}
    if args.trace:
        jax.profiler.stop_trace()
        ctx["spans"] = obs.span_summary(obs.drain_spans())
        obs.set_trace_enabled(False)
        from chipbench import trace
        try:
            ctx["trace"] = trace.reduce(trace.load_events(
                trace.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    runner.release()
    gc.collect()
    check = runner.check()
    numbers = check["numbers"]
    correct = all(v <= lim for v, lim in numbers.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if ctx["trace"]:
        device.update(busy_s=ctx["trace"]["busy_s"],
                      window_s=ctx["trace"]["window_s"])
    result = {"correct": correct, "attempted": steps, "failed": 0,
              "metrics": read_metrics(applicable(spec, args.workload,
                                                 bool(args.trace)), ctx),
              "device": device}
    if ctx["trace"]:
        result["breakdown"] = {k: ctx["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    result["check"] = {k: {"value": min(v, sys.float_info.max),
                           "limit": lim} for k, (v, lim) in numbers.items()}
    return {"result": result, "compiles": counter.counts,
            "check_detail": check["detail"], "cache_dir": cache_dir,
            "window_s": window_s, "setup_s": setup_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    try:
        out = run(args)
    except (NoAccelerator, FileNotFoundError, ModuleNotFoundError,
            KeyError) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    res = out["result"]
    print(f"compiles_in_window {out['compiles'][CompileCounter.EVENTS[0]]} "
          f"(backend compiles {out['compiles'][CompileCounter.EVENTS[1]]})")
    print(f"setup_s {out['setup_s']} window_s {out['window_s']} "
          f"steps {res['attempted']} compile_cache {out['cache_dir']}")
    print(f"check detail {json.dumps(out['check_detail'])}")
    print(json.dumps(res), flush=True)
    for k, v in res["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
