"""Serving operating-point sweep (``dse.sweep_serving``): bitwise
parity with the scalar per-point oracle, fusion/masking immunity, and
the regime-dependence golden pin."""

import numpy as np
import pytest

from repro import configs, obs
from repro.configs import deepseek_v3
from repro.core import designs, dse, lm_bridge, mapping, workloads
from repro.core.workloads import PhaseWorkload, ServingPoint
from repro.testing.hypocompat import given, settings, st

_CFG = configs.get("qwen1.5-0.5b")
_GRID = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(4,),
                           dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,),
                           vdd=(0.8,))

_COLS = ("energy_fj", "kv_energy_fj", "cycles", "tokens_per_s",
         "j_per_token")


@settings(max_examples=6, deadline=None)
@given(prompt_len=st.sampled_from((8, 64, 300)),
       batch=st.sampled_from((1, 4)),
       gen=st.sampled_from((1, 7, 32)),
       dataflows=st.booleans())
def test_sweep_serving_matches_scalar_oracle_bitwise(prompt_len, batch,
                                                     gen, dataflows):
    """Every derived column of the fused serving sweep equals the
    scalar per-(point, design) oracle bitwise, and the per-point argmin
    design is the one the scalar loop would pick."""
    schedules = ("ws", "os") if dataflows else None
    points = lm_bridge.serving_points(_CFG, [(prompt_len, batch)],
                                      gen_len=gen)
    (res,) = dse.sweep_serving(points, _GRID, schedules=schedules)
    oracle = [dse.serving_point_scalar(points[0], _GRID.macro_at(d),
                                       schedules=schedules)
              for d in range(len(_GRID))]
    for d, o in enumerate(oracle):
        for col in _COLS:
            assert getattr(res, col)[d] == o[col], (col, d)
    assert res.best() == int(np.argmin([o["j_per_token"] for o in oracle]))


def test_fused_points_are_bitwise_independent():
    """Sweeping many operating points through one shared lattice gives
    exactly the numbers each point gets swept alone — lattice fusion,
    shape dedup, and lane padding leak nothing across points."""
    points = lm_bridge.serving_points(_CFG, [(16, 1), (64, 4), (256, 2)],
                                      gen_len=8)
    fused = dse.sweep_serving(points, _GRID)
    for pt, res in zip(points, fused):
        (alone,) = dse.sweep_serving((pt,), _GRID)
        for col in _COLS:
            assert (getattr(res, col) == getattr(alone, col)).all(), col


def test_serving_lattice_pad_lanes_are_inert():
    """The decode phase's tiny-B layers force pad lanes in the fused
    lattice; scribbling garbage into them changes no priced output —
    the finite-sentinel masking covers the serving path too."""
    (pt,) = lm_bridge.serving_points(_CFG, [(32, 1)], gen_len=4)
    layers = list(pt.phases[1].layers)        # decode: B=1 per step
    per_bit = np.full(len(_GRID), 1.5)

    def price(poison: bool):
        (net,) = mapping.network_grid(layers, _GRID, schedules=("ws", "os"))
        assert net.pad_lanes > 0
        if poison:
            pad = ~net.valid
            for f in ("k_cols", "k_macros", "c_un", "fx_un", "fy_un",
                      "row_un", "mac_un", "dup_macros",
                      "n_spatial_temporal"):
                getattr(net.cand, f)[pad] = 991
        return dse._price_buckets([net], _GRID, "energy", None, per_bit,
                                  1 << 20, 4000.0)

    for (g0, i0, t0, c0), (g1, i1, t1, c1) in zip(price(False), price(True)):
        assert (i0 == i1).all()
        assert (t0 == t1).all()
        assert (c0 == c1).all()


def test_decode_heavy_regime_shifts_aimc_dimc_winner():
    """Golden pin: the AIMC/DIMC winner is regime-dependent.  For this
    design pair a prefill-heavy operating point (long prompts, gen=1)
    picks the DIMC macro, while a decode-heavy one (short prompts, long
    generation) flips to the AIMC macro — decode's tiny per-step
    batches neutralize AIMC's input-proportional bitline cost, while
    prefill's huge token batches make it dominant."""
    a = designs.macro_grid(rows=(128,), cols=(256,), adc_bits=(6,),
                           dac_bits=(1,), m_mux=(1,), tech_nm=(22,),
                           vdd=(0.8,))
    d = designs.macro_grid(rows=(1024,), cols=(256,), adc_bits=(4,),
                           dac_bits=(2,), m_mux=(1,), tech_nm=(22,),
                           vdd=(0.8,))
    pair = designs.MacroBatch.from_macros([
        a.macro_at(int(np.flatnonzero(a.analog)[0])),
        d.macro_at(int(np.flatnonzero(~d.analog)[0]))])
    assert bool(pair.analog[0]) and not bool(pair.analog[1])

    prefill_heavy = lm_bridge.serving_points(_CFG, [(4096, 16)], gen_len=1)
    decode_heavy = lm_bridge.serving_points(_CFG, [(16, 1)], gen_len=512)
    (rp,) = dse.sweep_serving(prefill_heavy, pair)
    (rd,) = dse.sweep_serving(decode_heavy, pair)
    assert not bool(pair.analog[rp.best()])   # prefill-heavy -> DIMC
    assert bool(pair.analog[rd.best()])       # decode-heavy -> AIMC


def test_sweep_serving_rejects_zero_generated_tokens():
    (pt,) = lm_bridge.serving_points(_CFG, [(8, 1)], gen_len=1)
    degenerate = ServingPoint(
        name=pt.name, prompt_len=pt.prompt_len, batch=pt.batch,
        gen_len=pt.gen_len,
        phases=(pt.phases[0],
                PhaseWorkload(phase="decode", layers=pt.phases[1].layers,
                              repeats=pt.phases[1].repeats, tokens_out=0.0)))
    with pytest.raises(ValueError):
        dse.sweep_serving((degenerate,), _GRID)


def test_serving_result_pareto_and_records():
    points = lm_bridge.serving_points(_CFG, [(64, 2)], gen_len=8)
    (res,) = dse.sweep_serving(points, _GRID)
    mask = res.pareto_mask()
    assert mask.any()
    # the extreme designs on either axis are never dominated
    assert mask[int(np.argmax(res.tokens_per_s))]
    assert mask[int(np.argmin(res.j_per_token))]
    front = res.pareto()
    assert (np.diff(res.tokens_per_s[front]) <= 0).all()
    recs = res.to_records()
    assert len(recs) == len(_GRID)
    by_name = {r["name"]: r for r in recs}
    for i, name in enumerate(_GRID.names):
        assert by_name[name]["pareto"] == bool(mask[i])
        assert by_name[name]["tokens_per_s"] == float(res.tokens_per_s[i])


def _shapes_and_keys(layers) -> tuple[int, int]:
    """Distinct result slots (``_shape_key``) and distinct lattices
    (``mapping.lattice_key``) among the IMC-eligible ``layers``."""
    shapes = {dse._shape_key(l): l for l in layers if l.imc_eligible}
    return len(shapes), len({mapping.lattice_key(l)
                             for l in shapes.values()})


def _cold_counts(run) -> tuple[object, int, int]:
    """``run()`` after a cleared memo; its result with the lattice
    builds and memo hits it counted."""
    dse.cache_clear()
    obs.reset("mapping.")
    out = run()
    snap = obs.snapshot()
    return (out, snap.get("mapping.lattice.builds", 0),
            snap.get("dse.lattice.hits", 0))


@pytest.mark.parametrize("cfg", [configs.get_smoke("glm4-9b"),
                                 deepseek_v3.smoke_config()],
                         ids=["glm4-9b", "deepseek-v3"])
def test_serving_sweep_builds_one_lattice_per_key(monkeypatch, cfg):
    """A serving lowering repeats each projection at several token
    counts; a cold sweep builds one lattice per distinct
    ``lattice_key`` and answers the other slots from the memo, with the
    same winners, totals and cycles as the full-grid reference and the
    scalar oracle."""
    points = lm_bridge.serving_points(cfg, [(16, 1), (64, 4), (256, 2)],
                                      gen_len=8)
    n_shapes, n_keys = _shapes_and_keys(
        [l for pt in points for ph in pt.phases for l in ph.layers])
    assert n_keys < n_shapes, "fixture no longer repeats a projection"

    fused, builds, hits = _cold_counts(
        lambda: dse.sweep_serving(points, _GRID, schedules=("ws", "os")))
    assert builds == n_keys
    assert hits == n_shapes - n_keys
    obs.set_trace_enabled(True)
    obs.drain_spans()
    try:
        _cold_counts(lambda: dse.sweep_serving(points, _GRID,
                                               schedules=("ws", "os")))
        (fused_span,) = [s for s in obs.drain_spans()
                         if s["name"] == "dse.network_grid_build"]
    finally:
        obs.set_trace_enabled(None)
    assert fused_span["attrs"]["lattices"] == n_keys

    with monkeypatch.context() as m:
        m.setattr(dse, "_price_shapes", dse._price_shapes_reference)
        ref, _, _ = _cold_counts(
            lambda: dse.sweep_serving(points, _GRID, schedules=("ws", "os")))
    for a, b in zip(ref, fused):
        for col in _COLS:
            assert np.array_equal(getattr(a, col), getattr(b, col)), col
        for sa, sb in zip(a.phase_sweeps, b.phase_sweeps):
            assert np.array_equal(sa.energy_fj, sb.energy_fj)
            assert np.array_equal(sa.cycles, sb.cycles)
            for xa, xb in zip(sa._shapes, sb._shapes):
                assert np.array_equal(xa[2], xb[2])   # winners per design
    for d in (0, len(_GRID) - 1):
        for pt, res in zip(points, fused):
            o = dse.serving_point_scalar(pt, _GRID.macro_at(d),
                                         schedules=("ws", "os"))
            for col in _COLS:
                assert getattr(res, col)[d] == o[col], (pt.name, col, d)
            assert res.phase_sweeps[0].network_result(d) == \
                dse.map_network(f"{pt.name}/{pt.phases[0].tag}",
                                list(pt.phases[0].layers),
                                _GRID.macro_at(d), schedules=("ws", "os"))


def test_cnn_sweep_has_no_lattice_hits():
    """CNN shapes differ in the dims a lattice reads: every slot builds
    its own lattice and the memo answers none of them in a cold sweep."""
    nets = [("ds_cnn", workloads.ds_cnn()),
            ("resnet8", workloads.resnet8()),
            ("dae", workloads.deep_autoencoder())]
    n_shapes, n_keys = _shapes_and_keys([l for _, ls in nets for l in ls])
    assert n_keys == n_shapes
    _, builds, hits = _cold_counts(lambda: dse.sweep_networks(nets, _GRID))
    assert builds == n_shapes
    assert hits == 0
