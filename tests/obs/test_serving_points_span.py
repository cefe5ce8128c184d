"""``lm_bridge.serving_points`` records one span per lowering: how many
operating points, layer groups and layer entries it made, and the most
routed experts one MoE layer-step touches."""

import pytest

from repro import configs, obs
from repro.configs import deepseek_v3
from repro.core import lm_bridge

_OPS = [(p, b) for p in (64, 1024, 8192) for b in (1, 8, 64)]


@pytest.fixture
def traced_on():
    obs.set_trace_enabled(True)
    obs.drain_spans()
    yield
    obs.drain_spans()
    obs.set_trace_enabled(None)


@pytest.mark.parametrize("cfg, attrs", [
    (deepseek_v3.config(), {"points": 9, "groups": 54, "entries": 396,
                            "experts_touched": 256}),
    (configs.get("glm4-9b"), {"points": 9, "groups": 18, "entries": 126,
                              "experts_touched": 0}),
    (configs.get("olmoe-1b-7b"), {"points": 1, "groups": 4, "entries": 16,
                                  "experts_touched": 64}),
])
def test_serving_points_span_counts_what_it_lowered(traced_on, cfg, attrs):
    grid = _OPS if attrs["points"] == 9 else [(16, 1)]
    points = lm_bridge.serving_points(cfg, grid, gen_len=64)
    (rec,) = [r for r in obs.drain_spans()
              if r["name"] == "lm_bridge.serving_points"]
    assert rec["attrs"] == attrs
    assert rec["attrs"]["groups"] == sum(len(p.phases) for p in points)
    assert rec["attrs"]["entries"] == sum(len(ph.layers) for p in points
                                          for ph in p.phases)


def test_serving_points_records_nothing_untraced():
    obs.set_trace_enabled(False)
    obs.drain_spans()
    try:
        lm_bridge.serving_points(deepseek_v3.config(), [(64, 1)], gen_len=4)
        assert obs.drain_spans() == []
    finally:
        obs.set_trace_enabled(None)
