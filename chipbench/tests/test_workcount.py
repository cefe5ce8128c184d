"""Work counts are pinned to the configurations' shapes."""

from chipbench import gen, workcount


def _cfg(name):
    return gen.load_json(f"configs/{name}.json")


def test_pairs_per_sweep():
    tiny, glm = _cfg("tinymlperf-1620"), _cfg("glm4-9b")
    assert workcount.sweep_pairs(
        tiny, gen.load_json("traffic/sweep_cold.json")) == 93_960
    assert workcount.sweep_pairs(
        tiny, gen.load_json("traffic/sweep_reuse.json")) == 93_960
    assert workcount.sweep_pairs(
        glm, gen.load_json("traffic/serving_price.json")) == 204_120

