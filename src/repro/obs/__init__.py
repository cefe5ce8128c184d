"""Unified telemetry layer: metrics registry + span tracer + exporters.

Observability substrate for the fused sweep engine, importable with the
standard library alone (jax is imported lazily, by the first span opened
with tracing on).  Three pieces:

* :mod:`repro.obs.registry` — process-global counters / gauges /
  timers, labeled by subsystem via dotted names, with atomic
  snapshot/reset.  The legacy ad-hoc counters (``dse.cache_info``,
  ``energy.grid_kernel_info``, ``compilecache.compilation_cache_info``)
  are compatibility views over this registry.
* :mod:`repro.obs.tracing` — nestable, thread-safe wall-time spans
  over the hot path (lattice build, per-bucket jit dispatch with
  compile-vs-execute attribution, the wait for the device, result
  assembly, fidelity groups, serving phases, serve-loop steps).  Off by
  default; the ``REPRO_TRACE`` env knob (or :func:`set_trace_enabled`)
  turns recording on.  While on, each span is also a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
  trace shows the spans on the device trace's clock.  Tracing is inert
  by contract: outputs are bitwise identical with tracing on or off
  (``tests/obs/test_inert.py``).
* :mod:`repro.obs.export` — JSONL + Chrome trace-event writers through
  the atomic tmp+rename path, and the structured ``telemetry`` block
  BENCH artifacts embed.  ``REPRO_TRACE_DIR`` picks the output
  directory.  :mod:`repro.obs.validate` schema-checks both formats
  (CI runs it on the smoke traces).

Typical instrumentation::

    from repro import obs

    _BUILDS = obs.counter("mapping.lattice.builds")

    def build(...):
        _BUILDS.inc()
        with obs.span("dse.lattice_build", layer=layer.name) as sp:
            grid = ...
            sp.set(lanes=len(grid))
        return grid

and, in a benchmark::

    artifact["telemetry"] = obs.telemetry_block()
    if obs.trace_enabled():
        artifact["telemetry"]["trace_files"] = obs.export_all(
            out_dir, prefix="design_sweep")
"""

from .export import (export_all, export_chrome, export_jsonl,
                     telemetry_block, write_json_atomic,
                     write_text_atomic)
from .registry import (REGISTRY, Counter, Gauge, MetricsRegistry, Timer,
                       counter, gauge, reset, snapshot, timer)
from .tracing import (Span, adopt_parent, current_span_id, drain_spans,
                      iter_spans, set_trace_enabled, span, span_summary,
                      sync, trace_enabled, traced)

__all__ = [
    # registry
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Timer",
    "counter", "gauge", "timer", "snapshot", "reset",
    # tracing
    "Span", "span", "traced", "trace_enabled", "set_trace_enabled",
    "current_span_id", "adopt_parent", "drain_spans", "iter_spans",
    "span_summary", "sync",
    # export
    "export_all", "export_chrome", "export_jsonl", "telemetry_block",
    "write_json_atomic", "write_text_atomic",
]
