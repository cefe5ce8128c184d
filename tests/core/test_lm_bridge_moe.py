"""MoE serving lowering in ``core.lm_bridge``: every routed expert a
phase unit touches is its own weight set (balanced routing), beside the
router, shared experts and a dense prologue, each layer group with its
own repeats -- and configs without MoE lower exactly as before."""

import dataclasses
import hashlib

import pytest

from repro import configs
from repro.configs import deepseek_v3
from repro.core import designs, dse, lm_bridge
from repro.core.workloads import PhaseWorkload, ServingPoint
from repro.models.lm import LM

MOE_CONFIGS = {"deepseek-v3": deepseek_v3.config,
               **{a: (lambda a=a: configs.get(a)) for a in configs.ARCH_IDS
                  if configs.get(a).moe is not None}}

_GRID = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(4,),
                           dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,),
                           vdd=(0.8,))
_COLS = ("energy_fj", "kv_energy_fj", "cycles", "tokens_per_s",
         "j_per_token")


def _routed(groups):
    return [(g, layers, supers) for g, layers, supers in groups
            if ".routed." in g]


# --------------------------------------------------------------------------- #
# balanced routing                                                             #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("regime", ["fewer", "equal", "more"])
@pytest.mark.parametrize("arch", sorted(MOE_CONFIGS))
def test_balanced_routing_touches_and_prices_every_assignment(arch, regime):
    """T*k assignments below, at and above the expert count: the touched
    experts, the tokens they take, and routed MACs equal to the top_k
    lowering (one expert's weights at B = T * top_k)."""
    cfg = MOE_CONFIGS[arch]()
    m = cfg.moe
    tokens = {"fewer": 1, "equal": m.n_experts // m.top_k,
              "more": m.n_experts // m.top_k + 3}[regime]
    a = tokens * m.top_k
    assert {"fewer": a < m.n_experts, "equal": a == m.n_experts,
            "more": a > m.n_experts}[regime]
    classes = lm_bridge.balanced_routing(tokens, m.n_experts, m.top_k)
    assert sum(n for _, n in classes) == min(m.n_experts, a)
    assert sum(b * n for b, n in classes) == a
    assert max(b for b, _ in classes) - min(b for b, _ in classes) <= 1

    groups = lm_bridge.phase_groups(cfg, tokens, "decode")
    routed = _routed(groups)
    moe_positions = [p for p in range(len(cfg.pattern))
                     if cfg.layer_is_moe(p)]
    assert len(routed) == len(moe_positions) * len(classes)
    n_moe = cfg.n_super - m.first_dense
    touched = sum(supers for _, _, supers in routed) // n_moe
    assert touched == len(moe_positions) * min(m.n_experts, a)
    got = sum(l.macs * supers for _, layers, supers in routed
              for l in layers)
    top_k = [l for l in lm_bridge.lm_imc_workloads(cfg, tokens)
             if ".moe_" in l.name]
    assert got == n_moe * sum(l.macs for l in top_k)
    # weight writes now count every touched expert, not one
    weights = sum(l.weight_elems * supers for _, layers, supers in routed
                  for l in layers)
    assert weights == touched * n_moe * sum(
        l.weight_elems for l in top_k) // len(moe_positions)


# --------------------------------------------------------------------------- #
# grouping is per-expert pricing; the fused sweep is the scalar oracle         #
# --------------------------------------------------------------------------- #
_SMOKE = deepseek_v3.smoke_config()
#: prefill T = 15 and decode T = 5 split 8 experts into two classes;
#: (8, 1) gives one class each, with T*k < E in decode
_POINTS = [(3, 5), (8, 1)]


def _per_expert(pt: ServingPoint) -> ServingPoint:
    """Every touched expert listed as its own group."""
    phases = []
    for ph in pt.phases:
        if ".routed." not in ph.group:
            phases.append(ph)
            continue
        n_moe = _SMOKE.n_super - _SMOKE.moe.first_dense
        steps = pt.gen_len if ph.phase == "decode" else 1
        count = int(ph.repeats) // (n_moe * steps)
        phases += [PhaseWorkload(phase=ph.phase, layers=ph.layers,
                                 repeats=float(n_moe * steps),
                                 group=f"{ph.group}.e{i}")
                   for i in range(count)]
    return ServingPoint(name=pt.name, prompt_len=pt.prompt_len,
                        batch=pt.batch, gen_len=pt.gen_len,
                        phases=tuple(phases))


def test_grouping_prices_as_every_expert_alone():
    points = lm_bridge.serving_points(_SMOKE, _POINTS, gen_len=4)
    assert {len(_routed([(p.group, p.layers, 0) for p in pt.phases]))
            for pt in points} == {4, 2}
    for pt in points:
        alone = _per_expert(pt)
        assert len(alone.phases) > len(pt.phases)
        for d in range(len(_GRID)):
            m = _GRID.macro_at(d)
            got = dse.serving_point_scalar(pt, m, schedules=("ws", "os"))
            want = dse.serving_point_scalar(alone, m, schedules=("ws", "os"))
            for col in ("energy_fj", "cycles"):
                assert got[col] == pytest.approx(want[col], rel=1e-12)


def test_fused_sweep_matches_scalar_oracle_bitwise():
    points = lm_bridge.serving_points(_SMOKE, _POINTS, gen_len=4)
    results = dse.sweep_serving(points, _GRID, schedules=("ws", "os"))
    for pt, res in zip(points, results):
        assert len(res.phase_sweeps) == len(pt.phases)
        for d in range(len(_GRID)):
            m = _GRID.macro_at(d)
            want = dse.serving_point_scalar(pt, m, schedules=("ws", "os"))
            for col in _COLS:
                assert getattr(res, col)[d] == want[col], (col, d)
            for ph, sw in zip(pt.phases, res.phase_sweeps):
                net = dse.map_network(f"{pt.name}/{ph.tag}", ph.layers, m,
                                      engine="scalar",
                                      schedules=("ws", "os"))
                got = sw.network_result(d)
                assert [(l.cost.mapping, l.cost.schedule.name)
                        for l in got.layers] == [
                    (l.cost.mapping, l.cost.schedule.name)
                    for l in net.layers], (ph.tag, d)


# --------------------------------------------------------------------------- #
# DeepSeek-V3 at published widths                                              #
# --------------------------------------------------------------------------- #
def test_deepseek_v3_lowering_at_published_widths():
    cfg = deepseek_v3.config()
    gen = 64
    (pt,) = lm_bridge.serving_points(cfg, [(1024, 8)], gen_len=gen)
    for phase, steps in (("prefill", 1), ("decode", gen)):
        groups = [p for p in pt.phases if p.phase == phase]
        assert [p.group for p in groups] == [
            "dense", "moe", f"p0.routed.b{256 if phase == 'prefill' else 1}"]
        assert sum(len(p.layers) for p in groups) == 22
        mla = [p for p in groups
               if any(l.name.endswith(".wkv_a") for l in p.layers)]
        assert sum(p.repeats for p in mla) == 61 * steps
        assert groups[0].kv_write_bytes > 0
        assert all(p.kv_read_bytes == p.kv_write_bytes == p.kv_live_bytes
                   == p.tokens_out == 0.0 for p in groups[1:])
    assert lm_bridge.kv_slot_bytes(cfg) * cfg.n_super == 61 * 576 * 2
    prefill = pt.phases[0]
    assert prefill.kv_write_bytes == 8 * 1024 * 61 * 576 * 2
    assert pt.tokens_out == 8.0 * gen
    # decode batch 8: 64 of 256 experts, one token each
    assert pt.phases[-1].repeats == 58 * gen * 64


# --------------------------------------------------------------------------- #
# configs without MoE lower exactly as before                                  #
# --------------------------------------------------------------------------- #
#: sha256 of the serving points of each config without MoE at (64, 1),
#: (1024, 8), (8192, 64), gen 64, as lowered before MoE layer groups
_BEFORE = {
    "qwen1.5-0.5b":
        "2d0b828fa92f5388ce05ca53d7a685a8b41c41a79fb60d2455f445a9762c4519",
    "glm4-9b":
        "39c223862775adc0eeb9390bf23bf901211985a4fe77fdac14e896e49eaf3bac",
    "gemma3-1b":
        "50c351841c03b750a063ded07c8f691295d8a05f6d0d516783b4d12f832c9b92",
    "minicpm3-4b":
        "f36d7ea5283921d3479f5b1ddb8518fa1325e5f6e24cf501f324eab5f87e87cb",
    "paligemma-3b":
        "eb3eb845f667166a11da4629d321b91857d693536c10a012a5f6566ee9765726",
    "musicgen-large":
        "3dd5e5458716a45b50477ed8f17ec128578ffe2404a6f63d9d48e07fa849c12a",
    "rwkv6-7b":
        "11920cb4b0e1deffe4e9f3a002e06820fc687f17d443805d00e56be8c776c42d",
}


@pytest.mark.parametrize("arch", sorted(_BEFORE))
def test_non_moe_lowering_unchanged(arch):
    cfg = configs.get(arch)
    assert cfg.moe is None
    pts = lm_bridge.serving_points(cfg, [(64, 1), (1024, 8), (8192, 64)],
                                   gen_len=64)
    rows = []
    for pt in pts:
        assert [ph.phase for ph in pt.phases] == ["prefill", "decode"]
        assert all(ph.group == "" for ph in pt.phases)
        rows.append((pt.name, pt.prompt_len, pt.batch, pt.gen_len))
        for ph in pt.phases:
            rows.append((ph.phase, float(ph.repeats).hex(),
                         float(ph.kv_read_bytes).hex(),
                         float(ph.kv_write_bytes).hex(),
                         float(ph.kv_live_bytes).hex(),
                         float(ph.tokens_out).hex()))
            rows += [(l.name, sorted(l.dims.items()), l.w_prec, l.i_prec,
                      l.psum_prec, l.imc_eligible) for l in ph.layers]
    digest = hashlib.sha256(repr(rows).encode()).hexdigest()
    assert digest == _BEFORE[arch]


# --------------------------------------------------------------------------- #
# what LM does not run                                                         #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("field", ["n_shared", "first_dense"])
def test_lm_refuses_shared_experts_and_dense_prologue(field):
    cfg = deepseek_v3.smoke_config()
    other = {"n_shared": "first_dense", "first_dense": "n_shared"}[field]
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           **{other: 0}))
    with pytest.raises(NotImplementedError, match=field):
        LM(cfg)
    LM(dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                        **{field: 0})))


def test_dense_prologue_needs_one_position_pattern():
    jamba = configs.get_smoke("jamba-1.5-large-398b")
    with pytest.raises(ValueError, match="one-position pattern"):
        dataclasses.replace(jamba, moe=dataclasses.replace(jamba.moe,
                                                           first_dense=1))
    with pytest.raises(ValueError, match="first_dense"):
        dataclasses.replace(_SMOKE, moe=dataclasses.replace(
            _SMOKE.moe, first_dense=_SMOKE.n_layers))
