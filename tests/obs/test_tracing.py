"""Span tracer: enable/disable gating, nesting, thread isolation,
buffer bound, lap/set attributes, decorator form."""

import threading

import pytest

from repro import obs
from repro.obs import tracing


@pytest.fixture
def traced_on():
    """Enable tracing with a clean buffer; restore env-driven state and
    drain afterwards so other tests see no leftover spans."""
    obs.set_trace_enabled(True)
    obs.drain_spans()
    yield
    obs.drain_spans()
    obs.set_trace_enabled(None)


@pytest.fixture
def traced_off():
    obs.set_trace_enabled(False)
    obs.drain_spans()
    yield
    obs.set_trace_enabled(None)


def test_disabled_records_nothing_and_shares_null(traced_off):
    s1 = obs.span("t.a", k=1)
    s2 = obs.span("t.b")
    assert s1 is s2 is tracing._NULL       # no per-call allocation
    with s1 as sp:
        sp.set(x=2)
        assert sp.lap("l") == 0.0
    assert obs.iter_spans() == []


def test_enabled_records_span_with_attrs(traced_on):
    with obs.span("t.work", n=3) as sp:
        sp.set(extra="y")
    (rec,) = obs.iter_spans()
    assert rec["name"] == "t.work"
    assert rec["cat"] == "t"
    assert rec["parent"] == 0 and rec["depth"] == 0
    assert rec["dur_us"] >= 0
    assert rec["attrs"] == {"n": 3, "extra": "y"}


def test_nesting_parent_and_depth(traced_on):
    with obs.span("t.outer"):
        with obs.span("t.inner"):
            pass
        with obs.span("t.inner2"):
            pass
    recs = {r["name"]: r for r in obs.iter_spans()}
    outer = recs["t.outer"]
    assert recs["t.inner"]["parent"] == outer["id"]
    assert recs["t.inner2"]["parent"] == outer["id"]
    assert recs["t.inner"]["depth"] == 1
    assert outer["depth"] == 0
    # children close before the parent does
    assert outer["dur_us"] >= recs["t.inner"]["dur_us"]


def test_threads_have_independent_stacks(traced_on):
    done = threading.Event()

    def other():
        with obs.span("t.thread"):
            pass
        done.set()

    with obs.span("t.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert done.wait(5)
    recs = {r["name"]: r for r in obs.iter_spans()}
    # the other thread's span must NOT parent under t.main
    assert recs["t.thread"]["parent"] == 0
    assert recs["t.thread"]["tid"] != recs["t.main"]["tid"]


def test_lap_records_elapsed_attr(traced_on):
    with obs.span("t.lap") as sp:
        dt = sp.lap("phase1")
    (rec,) = obs.iter_spans()
    assert rec["attrs"]["phase1_s"] == dt
    assert 0 <= dt <= rec["dur_us"] / 1e6 + 1e-6


def test_exception_marks_span_and_unwinds_stack(traced_on):
    with pytest.raises(ValueError):
        with obs.span("t.boom"):
            raise ValueError("x")
    (rec,) = obs.iter_spans()
    assert rec["attrs"]["error"] == "ValueError"
    # the stack unwound: a fresh span is top-level again
    with obs.span("t.after"):
        pass
    after = obs.iter_spans()[-1]
    assert after["parent"] == 0


def test_drain_clears_buffer(traced_on):
    with obs.span("t.one"):
        pass
    drained = obs.drain_spans()
    assert [r["name"] for r in drained] == ["t.one"]
    assert obs.iter_spans() == []


def test_traced_decorator(traced_on):
    @obs.traced("t.fn")
    def fn(a, b):
        return a + b

    assert fn(2, 3) == 5
    (rec,) = obs.iter_spans()
    assert rec["name"] == "t.fn"


def test_traced_decorator_default_label(traced_on):
    @obs.traced()
    def helper():
        return 1

    helper()
    (rec,) = obs.iter_spans()
    assert rec["name"].endswith(".helper")


def test_buffer_bound_increments_dropped(traced_on, monkeypatch):
    monkeypatch.setattr(tracing, "_MAX_SPANS", 3)
    obs.reset("obs.spans.")
    for i in range(5):
        with obs.span("t.many", i=i):
            pass
    assert len(obs.iter_spans()) == 3
    snap = obs.snapshot("obs.spans.")
    assert snap["obs.spans.dropped"] == 2
    assert snap["obs.spans.recorded"] == 3


def test_span_summary_rollup(traced_on):
    for _ in range(3):
        with obs.span("t.x"):
            pass
    with obs.span("t.y"):
        pass
    summary = obs.span_summary()
    assert summary["t.x"]["count"] == 3
    assert summary["t.y"]["count"] == 1
    assert summary["t.x"]["total_s"] >= 0


def test_env_knob_resolution(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    obs.set_trace_enabled(None)            # force env re-read
    assert obs.trace_enabled()
    for off in ("", "0", "off", "false", "none", "disabled", "OFF"):
        monkeypatch.setenv("REPRO_TRACE", off)
        obs.set_trace_enabled(None)
        assert not obs.trace_enabled(), repr(off)
    monkeypatch.delenv("REPRO_TRACE")
    obs.set_trace_enabled(None)
    assert not obs.trace_enabled()


def test_sync_walks_containers_and_dataclasses():
    import dataclasses

    class Blockable:
        def __init__(self):
            self.forced = False

        def block_until_ready(self):
            self.forced = True

    @dataclasses.dataclass
    class Box:
        inner: object

    b1, b2, b3 = Blockable(), Blockable(), Blockable()
    out = obs.sync({"a": [b1, (b2,)], "b": Box(b3), "c": 42})
    assert b1.forced and b2.forced and b3.forced
    assert out["c"] == 42
