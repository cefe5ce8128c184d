"""The trace reduction on a small trace recorded on a TPU v5e (three
512x512 float32 matmuls, each under a ``sweep`` annotation inside one
``window``, with 10 ms host sleeps between them)."""

from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_reduce_recorded_trace():
    ev = trace.load_events(DATA)
    assert list(ev["devices"]) == ["/device:TPU:0"]
    ops = ev["devices"]["/device:TPU:0"]
    assert len(ops) == 9
    labels = sorted(n for _, _, n in ev["annotations"])
    assert labels == ["sweep"] * 3 + ["window"]
    red = trace.reduce(ev)
    (w0, w1), = [(s, e) for s, e, n in ev["annotations"] if n == "window"]
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    inside = trace._union((max(s, w0), min(e, w1)) for s, e, _ in ops
                          if e > w0 and s < w1)
    assert red["busy_s"] == pytest.approx(
        sum(e - s for s, e in inside) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert red["device_ops"][0][0].startswith("%convolution_reduce_fusion")


def test_reduce_synthetic_events():
    ev = {"devices": {"/device:TPU:0": [(10, 30, "a"), (20, 40, "b"),
                                        (70, 80, "a")],
                      "/device:TPU:1": [(0, 100, "c")]},
          "annotations": [(0, 100, "window"), (40, 60, "cache_clear"),
                          (0, 100, "sweep")]}
    red = trace.reduce(ev)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((40 + 100) / 2 * 1e-9)
    assert dict(red["device_ops"]) == pytest.approx(
        {"a": 15e-9, "b": 10e-9, "c": 50e-9})
    # gaps on chip 0: [0,10] sweep, [40,70] cache_clear-covered midpoint
    # (55), [80,100] sweep; averaged over two chips
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"sweep": 15e-9, "cache_clear": 15e-9})


def test_reduce_refuses_a_trace_without_window_or_device():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"/device:TPU:0": []}, "annotations": []})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "annotations": [(0, 1, "window")]})
