"""The plain DeepSeek-V3 lowering (``chipbench/reference/deepseek_v3.py``)
is the program's, to the dimension, repeat and KV byte; the reference
prices it bit for bit as the program's scalar oracle does; and a run of
``sweep.deepseek-v3.serving`` whose lowering is broken underneath comes
out not correct:

* ``topk``: routed experts priced as one weight set at B = tokens *
  top_k (the lowering before every touched expert was its own);
* ``no_prologue``: the leading dense layers dropped (every layer MoE).
"""

import dataclasses
import json

import pytest

from chipbench import gen, run
from chipbench.reference import deepseek_v3, imc

CELL = "sweep.deepseek-v3.serving"


@pytest.fixture(scope="module")
def cell():
    spec, _, config, traffic = run.load_cell(CELL)
    return spec, config, traffic


@pytest.fixture(scope="module")
def program_points(cell):
    from chipbench.runners import sweep_moe
    _, config, traffic = cell
    runner = sweep_moe.make(config, traffic, 1)
    return runner, runner._program_workload()


def test_reference_lowering_is_the_programs(cell, program_points):
    from repro.configs import deepseek_v3 as program_cfg
    from repro.core import lm_bridge
    _, config, traffic = cell
    runner, theirs = program_points
    ours = runner.workload
    assert len(theirs) == len(ours) == 9
    for t, o in zip(theirs, ours):
        assert t.name == o["name"] and t.tokens_out == o["tokens_out"]
        assert len(t.phases) == len(o["phases"]) == 6
        for tp, op in zip(t.phases, o["phases"]):
            assert (tp.phase, tp.group, tp.repeats, tp.kv_read_bytes,
                    tp.kv_write_bytes, tp.kv_live_bytes) == (
                op["phase"], op["group"], op["repeats"], op["kv_read"],
                op["kv_write"], op["kv_live"])
            assert [(l.name, dict(l.dims), l.w_prec, l.i_prec, l.psum_prec)
                    for l in tp.layers] == [
                (l["name"], l["dims"], l["w_prec"], l["i_prec"],
                 l["psum_prec"]) for l in op["layers"]]
    # the runner's ModelConfig is the program's registered one
    again = lm_bridge.serving_points(program_cfg.config(),
                                     gen.operating_points(traffic), gen_len=64)
    assert again == theirs
    # 22 entries per phase, 396 per sweep, priced on 1620 designs
    assert runner.pairs == 396 * 1620 == 641_520
    for pt in ours:
        for phase in ("prefill", "decode"):
            assert sum(len(ph["layers"]) for ph in pt["phases"]
                       if ph["phase"] == phase) == 22


def test_routed_classes_touch_every_assignment():
    for tokens, experts, k in [(1, 256, 8), (8, 256, 8), (64, 256, 8),
                               (3, 5, 2), (7, 64, 8), (1, 4, 3)]:
        classes = deepseek_v3.routed_classes(tokens, experts, k)
        assert sum(b * n for b, n in classes) == tokens * k
        assert sum(n for _, n in classes) == min(experts, tokens * k)


def _macro(d):
    from repro.core.hardware import IMCMacro, IMCType
    return IMCMacro(name=d["name"], imc_type=IMCType(
        "aimc" if d["analog"] else "dimc"), rows=d["rows"], cols=d["cols"],
        tech_nm=d["tech_nm"], vdd=d["vdd"], bw=d["bw"], bi=d["bi"],
        adc_res=d["adc_res"], dac_res=d["dac_res"], m_mux=d["m_mux"],
        n_macros=d["n_macros"], cols_per_adc=d["cols_per_adc"],
        adc_share=d["adc_share"], booth=d["booth"])


def test_reference_serve_bitwise_equals_scalar_oracle(program_points):
    from repro.core import dse
    runner, theirs = program_points
    grid = gen.design_grid(gen.load_json("grids/imc-1620.json"),
                           (0.63, 0.87))
    # two AIMC and two DIMC designs over the grid's rows, cols and nodes
    for d, point in zip((7, 777, 1000, 1619), (1, 3, 5, 7)):
        design = grid[d]
        want = dse.serving_point_scalar(theirs[point], _macro(design),
                                        schedules=("ws", "os"))
        got = imc.serve(runner.workload[point], imc.Macro(design))
        for c in ("energy_fj", "kv_energy_fj", "cycles", "tokens_per_s",
                  "j_per_token"):
            assert got[c] == want[c], (d, c)


def _result(capsys, seed=987654321987):
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(cpu_small, capsys):
    res = _result(capsys)
    assert res["correct"] is True
    assert res["check"]["max_rel_dev"]["value"] == 0.0


def _topk(orig):
    """Routed experts back to one weight set of B = tokens * top_k."""
    def lowered(cfg, tokens, phase, **kw):
        out = []
        for group, layers, supers in orig(cfg, tokens, phase, **kw):
            if ".routed." in group:
                layers = tuple(dataclasses.replace(
                    l, dims=dict(l.dims, B=tokens * cfg.moe.top_k))
                    for l in layers)
                supers = cfg.n_super - cfg.moe.first_dense
            out.append((group, layers, supers))
        return out
    return lowered


def _no_prologue(orig):
    """Every layer lowered as a MoE layer."""
    def lowered(cfg, tokens, phase, **kw):
        moe = dataclasses.replace(cfg.moe, first_dense=0)
        return orig(dataclasses.replace(cfg, moe=moe), tokens, phase, **kw)
    return lowered


@pytest.mark.parametrize("fault", [_topk, _no_prologue])
def test_fault_is_not_correct(cpu_small, monkeypatch, capsys, fault):
    from repro.core import lm_bridge
    monkeypatch.setattr(lm_bridge, "phase_groups",
                        fault(lm_bridge.phase_groups))
    res = _result(capsys)
    assert res["correct"] is False, res["check"]
