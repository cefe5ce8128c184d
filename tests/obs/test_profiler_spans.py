"""Program spans on the profiler's clock, and the sweep's main-thread
partition.

With tracing on, every ``obs.span`` is also a
``jax.profiler.TraceAnnotation`` of the same bare name, so a profiler
trace holds the program's spans on a ``/host`` plane beside the device's
operations; with tracing off nothing reaches it and jax is never
touched.  Under each ``dse.sweep_networks`` root the main thread's
children (``dse.await_bucket``, ``dse.price_bucket``,
``dse.finalize_bucket`` holding ``dse.device_wait``, ``dse.assemble``)
do not overlap, and the builder thread's spans name the root as their
parent.
"""

import glob
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import configs, obs
from repro.core import designs, dse, lm_bridge, workloads
from repro.obs import tracing

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench.trace import LABELS  # noqa: E402

MAIN_CHILDREN = ("dse.await_bucket", "dse.price_bucket",
                 "dse.finalize_bucket", "dse.assemble")


@pytest.fixture
def traced_on():
    obs.set_trace_enabled(True)
    obs.drain_spans()
    yield
    obs.drain_spans()
    obs.set_trace_enabled(None)


@pytest.fixture
def fresh_annotation(monkeypatch):
    """Forget the looked-up ``TraceAnnotation`` for the test's span."""
    monkeypatch.setattr(tracing, "_ANNOTATION", {})


def _host_event_names(log_dir) -> set[str]:
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("where", ["main", "second_thread"])
def test_span_lands_on_profiler_host_plane(traced_on, tmp_path, where):
    def work():
        with obs.span("t.profiled", n=1):
            jnp.ones(4).block_until_ready()

    with jax.profiler.trace(str(tmp_path)):
        if where == "main":
            work()
        else:
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    # the bare name: attributes stay in the obs record
    assert "t.profiled" in _host_event_names(tmp_path)
    (rec,) = obs.iter_spans()
    assert rec["attrs"] == {"n": 1}


def test_tracing_off_span_absent_from_profiler(tmp_path):
    obs.set_trace_enabled(False)
    try:
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("t.unprofiled"):
                jnp.ones(4).block_until_ready()
    finally:
        obs.set_trace_enabled(None)
    assert "t.unprofiled" not in _host_event_names(tmp_path)


def test_tracing_off_never_touches_jax(fresh_annotation):
    obs.set_trace_enabled(False)
    try:
        sp = obs.span("t.off", k=1)
        with sp:
            pass
    finally:
        obs.set_trace_enabled(None)
    assert sp is tracing._NULL
    assert tracing._ANNOTATION == {}          # jax.profiler not looked up


def test_span_records_without_jax(traced_on, fresh_annotation,
                                  monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    with obs.span("t.nojax"):
        pass
    assert tracing._ANNOTATION == {"cls": None}
    assert [r["name"] for r in obs.iter_spans()] == ["t.nojax"]


def test_adopted_parent_names_the_other_threads_span(traced_on):
    got = {}

    def worker(parent):
        obs.adopt_parent(parent)
        with obs.span("t.child"):
            got["inner"] = obs.current_span_id()

    assert obs.current_span_id() == 0
    with obs.span("t.root"):
        root_id = obs.current_span_id()
        t = threading.Thread(target=worker, args=(root_id,))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    recs = {r["name"]: r for r in obs.iter_spans()}
    assert recs["t.root"]["id"] == root_id
    assert recs["t.child"]["parent"] == root_id
    assert recs["t.child"]["tid"] != recs["t.root"]["tid"]
    assert got["inner"] == recs["t.child"]["id"]


def _grid():
    return designs.macro_grid(rows=(64, 256), cols=(256,),
                              adc_bits=(4, 6), dac_bits=(2,),
                              m_mux=(1, 16), tech_nm=(22,))


def _nets():
    layers = [workloads.dense(f"l{i}", 1, 24 + 8 * i, 8)
              for i in range(4)]
    return [("net_a", layers[:3]), ("net_b", layers[1:])]


@pytest.fixture
def pipelined_sweeps(traced_on, monkeypatch):
    """Spans of two pipelined sweeps with caches cleared, a bucket per
    shape or two so the main thread awaits, dispatches and finalizes
    several buckets."""
    monkeypatch.setattr(dse, "_BUCKET_ELEMS", 1)
    for _ in range(2):
        dse.cache_clear()
        dse.sweep_networks(_nets(), _grid(), schedules=("ws", "os"))
    return obs.drain_spans()


def _end(rec):
    return rec["ts_us"] + rec["dur_us"]


def test_sweep_main_thread_children_partition_the_root(pipelined_sweeps):
    spans = pipelined_sweeps
    roots = [r for r in spans if r["name"] == "dse.sweep_networks"]
    assert len(roots) == 2
    for root in roots:
        kids = sorted((r for r in spans if r["parent"] == root["id"]
                       and r["tid"] == root["tid"]),
                      key=lambda r: r["ts_us"])
        names = [k["name"] for k in kids]
        assert set(names) == set(MAIN_CHILDREN)
        assert names.count("dse.price_bucket") >= 2
        assert names[-1] == "dse.assemble"
        # one wait per bucket plus the final "done"
        assert (names.count("dse.await_bucket")
                == names.count("dse.price_bucket") + 1)
        for k in kids:
            assert root["ts_us"] <= k["ts_us"] and _end(k) <= _end(root)
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts_us"]
        for fin in (k for k in kids if k["name"] == "dse.finalize_bucket"):
            (wait,) = [r for r in spans if r["parent"] == fin["id"]]
            assert wait["name"] == "dse.device_wait"
            assert fin["ts_us"] <= wait["ts_us"] and _end(wait) <= _end(fin)
        awaits = [k for k in kids if k["name"] == "dse.await_bucket"]
        assert awaits[0]["attrs"]["in_flight"] == 0


def test_builder_spans_name_the_sweep_root(pipelined_sweeps):
    spans = pipelined_sweeps
    roots = {r["id"]: r for r in spans if r["name"] == "dse.sweep_networks"}
    built = [r for r in spans if r["name"] in ("dse.lattice_build",
                                               "dse.network_grid_build")]
    assert {r["name"] for r in built} == {"dse.lattice_build",
                                          "dse.network_grid_build"}
    for r in built:
        root = roots[r["parent"]]
        assert r["tid"] != root["tid"]
        assert root["ts_us"] <= r["ts_us"] and _end(r) <= _end(root)


def test_sweep_spans_avoid_benchmark_labels(pipelined_sweeps):
    points = lm_bridge.serving_points(configs.get("qwen1.5-0.5b"),
                                      [(16, 1)], gen_len=2)
    dse.sweep_serving(points, _grid())
    spans = pipelined_sweeps + obs.drain_spans()
    names = {r["name"] for r in spans}
    assert {"dse.sweep_serving", "dse.device_wait"} <= names
    assert all("." in n and n not in LABELS for n in names)


def test_serving_root_split_into_sweep_and_assembly(traced_on):
    points = lm_bridge.serving_points(configs.get("qwen1.5-0.5b"),
                                      [(16, 1), (64, 2)], gen_len=2)
    dse.sweep_serving(points, _grid())
    spans = obs.drain_spans()
    (root,) = [r for r in spans if r["name"] == "dse.sweep_serving"]
    kids = sorted((r for r in spans if r["parent"] == root["id"]),
                  key=lambda r: r["ts_us"])
    assert [k["name"] for k in kids] == ["dse.sweep_networks",
                                         "dse.assemble"]
    assert _end(kids[0]) <= kids[1]["ts_us"]
    points_in = [r for r in spans if r["name"] == "dse.serving_point"]
    assert len(points_in) == 2
    assert all(r["parent"] == kids[1]["id"] for r in points_in)
