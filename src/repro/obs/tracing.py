"""Span tracer: nestable, thread-safe wall-time spans over the hot path.

``span("dse.price_bucket", lanes=512)`` is a context manager that
records one timed interval into the process-global trace buffer.  Spans
nest through a per-thread stack (the ``parent``/``depth`` fields make
the nesting explicit for the validator and the JSONL export; Chrome's
trace viewer infers it from interval containment per thread).  A worker
thread started on behalf of an open span passes that span's id
(:func:`current_span_id`) to :func:`adopt_parent`, so the spans it opens
name the span that caused them as their parent.

Off by default: tracing is enabled by the ``REPRO_TRACE`` env knob
(``""``/``"0"``/``"off"``/``"false"``/``"none"``/``"disabled"`` mean
off, anything else on), resolved once and overridable in-process via
:func:`set_trace_enabled`.  When disabled, :func:`span` returns a
shared no-op context manager without allocating or touching jax — the
per-call cost is one dict build for the kwargs plus one flag check,
which is what keeps the instrumented sweep within the 2 % overhead
guard (``tests/perf/test_obs_overhead.py``).

One clock with the device: while tracing is on, every span also opens a
``jax.profiler.TraceAnnotation`` under its bare name, so a profiler
trace taken meanwhile holds the program's spans on its host plane (one
line per thread) beside the device's operations.  Attributes stay in
the span record only (JAX would fold them into the event name).
``jax.profiler`` is imported on the first traced span, never at import
time: this module needs nothing beyond the standard library, and
without jax a span records as usual.

A span times host wall clock only.  jax dispatch is asynchronous, so a
span around a jit call measures the enqueue; the device's time shows
in the span that realizes the result on the host (``np.asarray``), or
in the profiler trace itself.

The buffer is bounded (``_MAX_SPANS``); overflow increments the
``obs.spans.dropped`` counter instead of growing without limit.
Tracing is *inert* by contract: no instrumented code path may read a
span or metric to make a decision, and the property test
``tests/obs/test_inert.py`` pins that sweeps with tracing on are
bitwise identical to tracing off.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import threading
import time

from .registry import counter as _counter

__all__ = [
    "span", "traced", "Span", "trace_enabled", "set_trace_enabled",
    "current_span_id", "adopt_parent", "drain_spans", "iter_spans",
    "span_summary", "sync",
]

_DISABLED_VALUES = {"", "0", "off", "false", "none", "disabled"}

#: tri-state: None = resolve from env on next check
_STATE: dict = {"enabled": None}
#: ``jax.profiler.TraceAnnotation`` once the first traced span looked it
#: up (``None`` without jax); absent until then
_ANNOTATION: dict = {}

_MAX_SPANS = 200_000

_LOCK = threading.Lock()
_SPANS: list[dict] = []
_IDS = itertools.count(1)
_TLS = threading.local()

_DROPPED = _counter("obs.spans.dropped")
_RECORDED = _counter("obs.spans.recorded")


def trace_enabled() -> bool:
    """Whether spans are being recorded (env ``REPRO_TRACE``, cached)."""
    e = _STATE["enabled"]
    if e is None:
        e = (os.environ.get("REPRO_TRACE", "").strip().lower()
             not in _DISABLED_VALUES)
        _STATE["enabled"] = e
    return e


def set_trace_enabled(on: bool | None) -> None:
    """Force tracing on/off in-process; ``None`` re-reads the env on
    the next :func:`trace_enabled` call."""
    _STATE["enabled"] = None if on is None else bool(on)


def sync(x):
    """Block until every jax array reachable from ``x`` has a value.

    jax dispatch is asynchronous: stopping a clock without forcing the
    result under-reports wall time by whatever is still in flight.
    Walks containers and dataclasses; NumPy arrays and scalars pass
    through untouched.  Returns ``x`` so it can wrap a call expression
    inline.  (``benchmarks.common.sync`` re-exports it.)
    """
    seen: set[int] = set()

    def walk(v) -> None:
        if id(v) in seen:
            return
        seen.add(id(v))
        ready = getattr(v, "block_until_ready", None)
        if ready is not None:
            ready()
        elif dataclasses.is_dataclass(v) and not isinstance(v, type):
            for f in dataclasses.fields(v):
                walk(getattr(v, f.name))
        elif isinstance(v, dict):
            for item in v.values():
                walk(item)
        elif isinstance(v, (list, tuple)):
            for item in v:
                walk(item)

    walk(x)
    return x


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use; ``None``
    where jax cannot be imported."""
    if "cls" not in _ANNOTATION:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = None
        _ANNOTATION["cls"] = TraceAnnotation
    return _ANNOTATION["cls"]


class _Parent:
    """Base of a thread's span stack standing for a span another thread
    holds open (:func:`adopt_parent`)."""

    __slots__ = ("id",)

    def __init__(self, span_id: int):
        self.id = span_id


class Span:
    """One live span.  Use via ``with span(name, **attrs) as sp:``."""

    __slots__ = ("name", "attrs", "id", "parent", "depth", "tid", "t0",
                 "annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Attach/overwrite attributes on the open span."""
        self.attrs.update(attrs)

    def lap(self, label: str) -> float:
        """Record the elapsed time since span start as attribute
        ``<label>_s`` and return it (e.g. ``sp.lap("restore")`` after
        one phase of a multi-phase span)."""
        dt = (time.perf_counter_ns() - self.t0) / 1e9
        self.attrs[label + "_s"] = dt
        return dt

    def __enter__(self) -> "Span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self.id = next(_IDS)
        self.parent = stack[-1].id if stack else 0
        self.depth = len(stack)
        self.tid = threading.get_ident()
        stack.append(self)
        cls = _trace_annotation()
        self.annotation = None if cls is None else cls(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter_ns()
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        stack = getattr(_TLS, "stack", [])
        # tolerate exception-path teardown out of order
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        rec = {
            "type": "span",
            "id": self.id,
            "parent": self.parent,
            "depth": self.depth,
            "name": self.name,
            "cat": self.name.split(".", 1)[0],
            "tid": self.tid,
            "ts_us": self.t0 / 1e3,
            "dur_us": (t1 - self.t0) / 1e3,
        }
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if self.attrs:
            rec["attrs"] = self.attrs
        with _LOCK:
            if len(_SPANS) < _MAX_SPANS:
                _SPANS.append(rec)
                _RECORDED.inc()
            else:
                _DROPPED.inc()


class _NullSpan:
    """Shared no-op stand-in when tracing is disabled."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def lap(self, label: str) -> float:
        return 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, **attrs) -> Span | _NullSpan:
    """Open a span named ``name`` with initial attributes ``attrs``.
    Returns the shared no-op span when tracing is disabled."""
    if not trace_enabled():
        return _NULL
    return Span(name, attrs)


def current_span_id() -> int:
    """Id of the innermost span open on this thread; 0 when none is
    (always, with tracing off)."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1].id if stack else 0


def adopt_parent(span_id: int) -> None:
    """Make the span ``span_id``, held open by another thread, the base
    of this thread's span stack: the spans this thread opens next name
    it as their parent, one level below it.  Call at the start of a
    worker thread with the id :func:`current_span_id` gave its starter;
    0 (no span open, or tracing off) leaves the stack as it is."""
    if span_id:
        _TLS.stack = [_Parent(span_id)]


def traced(name: str | None = None):
    """Decorator form: time every call of ``fn`` as a span.  The label
    defaults to ``<module tail>.<fn name>``.  When tracing is disabled
    the wrapper is one flag check away from the bare call."""
    def deco(fn):
        label = name or (fn.__module__.rsplit(".", 1)[-1]
                         + "." + fn.__name__)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not trace_enabled():
                return fn(*args, **kwargs)
            with Span(label, {}):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def iter_spans() -> list[dict]:
    """Copy of the finished-span buffer (oldest first)."""
    with _LOCK:
        return list(_SPANS)


def drain_spans() -> list[dict]:
    """Return and clear the finished-span buffer."""
    with _LOCK:
        out = list(_SPANS)
        _SPANS.clear()
        return out


def span_summary(spans: list[dict] | None = None) -> dict:
    """Per-name ``{count, total_s}`` rollup of finished spans — the
    compact form the BENCH telemetry block embeds."""
    if spans is None:
        spans = iter_spans()
    out: dict[str, dict] = {}
    for s in spans:
        agg = out.setdefault(s["name"], {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += s["dur_us"] / 1e6
    return out
