"""The plain reference agrees with the program's scalar oracle bit for
bit on the CPU, and the benchmark's grid walk makes the program's
designs in the program's order."""

import numpy as np
import pytest

from chipbench import gen
from chipbench.reference import imc


@pytest.fixture(scope="module")
def grid():
    return gen.design_grid(gen.load_json("grids/imc-1620.json"),
                           (0.63, 0.87))


def test_design_grid_matches_macro_grid(grid):
    from repro.core import designs
    k = gen.load_json("grids/imc-1620.json")
    batch = designs.macro_grid(
        rows=k["rows"], cols=k["cols"], adc_bits=k["adc_bits"],
        dac_bits=k["dac_bits"], m_mux=k["m_mux"], tech_nm=k["tech_nm"],
        vdd=(0.63, 0.87))
    assert len(grid) == len(batch) == k["designs"]
    assert [d["name"] for d in grid] == list(batch.names)


def _macro(d):
    from repro.core.hardware import IMCMacro, IMCType
    return IMCMacro(name=d["name"], imc_type=IMCType(
        "aimc" if d["analog"] else "dimc"), rows=d["rows"], cols=d["cols"],
        tech_nm=d["tech_nm"], vdd=d["vdd"], bw=d["bw"], bi=d["bi"],
        adc_res=d["adc_res"], dac_res=d["dac_res"], m_mux=d["m_mux"],
        n_macros=d["n_macros"], cols_per_adc=d["cols_per_adc"],
        adc_share=d["adc_share"], booth=d["booth"])


@pytest.mark.parametrize("objective", ["energy", "latency", "edp"])
def test_networks_bitwise_equal_scalar_oracle(grid, objective):
    from repro.core import dse
    from repro.core.memory import MemoryModel
    from repro.core.workloads import Layer
    nets = gen.network_layers(gen.load_json("configs/tinymlperf-1620.json"))
    for d in np.random.default_rng(1).choice(len(grid), 3, replace=False):
        design = grid[int(d)]
        m, ref_m = _macro(design), imc.Macro(design)
        mem = MemoryModel(tech_nm=m.tech_nm, vdd=m.vdd)
        for _, layers in nets[:2]:
            for l in layers[:4]:
                want = dse.best_mapping_scalar(
                    Layer(l["name"], l["type"], l["dims"], l["w_prec"],
                          l["i_prec"], l["psum_prec"]),
                    m, mem, objective=objective, schedules=("ws", "os"))
                got = imc.best_mapping(l, ref_m, objective)
                assert got["energy_fj"] == want.total_energy_fj
                assert got["cycles"] == want.cost.cycles
                assert got["schedule"] == want.cost.schedule.name
                assert got["mapping"] == {
                    "cols": dict(want.cost.mapping.cols),
                    "rows": dict(want.cost.mapping.rows),
                    "macros": dict(want.cost.mapping.macros)}


def test_serving_points_bitwise_equal_scalar_oracle(grid):
    from repro import configs
    from repro.core import dse, lm_bridge
    cfg = gen.load_json("configs/glm4-9b.json")
    ops = [(64, 1), (8192, 64)]
    theirs = lm_bridge.serving_points(configs.get("glm4-9b"), ops,
                                      gen_len=64)
    ours = [imc.serving_point(cfg, p, b, 64) for p, b in ops]
    for t, o in zip(theirs, ours):
        assert t.name == o["name"] and t.tokens_out == o["tokens_out"]
        for tp, op in zip(t.phases, o["phases"]):
            assert (tp.repeats, tp.kv_read_bytes, tp.kv_write_bytes,
                    tp.kv_live_bytes) == (op["repeats"], op["kv_read"],
                                          op["kv_write"], op["kv_live"])
            assert [(l.name, dict(l.dims)) for l in tp.layers] == \
                [(l["name"], l["dims"]) for l in op["layers"]]
    design = grid[1500]
    want = dse.serving_point_scalar(theirs[1], _macro(design),
                                    schedules=("ws", "os"))
    got = imc.serve(ours[1], imc.Macro(design))
    for c in ("energy_fj", "kv_energy_fj", "cycles", "tokens_per_s",
              "j_per_token"):
        assert got[c] == want[c], c
