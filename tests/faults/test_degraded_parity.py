"""The fault axis keeps the sweep engine's two core contracts.

Inertness: ``faults=None`` and an all-zero :class:`FaultSpec` run the
identical pristine path — results bitwise equal to a sweep that never
heard of the kwarg (the ``tests/obs/test_inert.py`` pattern, applied to
faults instead of tracing).

Degraded parity: under random survivor masks the fused engine's
per-(layer, design) winner — totals, argmins *including tie-breaks*,
finite sentinels in dead lanes — is bitwise the scalar oracle
``best_mapping_scalar(..., survivors=...)`` filtered to surviving
mappings; and the sweep's route agrees bitwise with the full-grid host
reference (``dse._price_shapes_reference``) under faults.
"""

import numpy as np

from repro.core import designs, dse, workloads
from repro.core.memory import MemoryModel
from repro.faults import FaultSpec, survivor_mask, survivors_for
from repro.testing.hypocompat import given, settings, st


def _grid():
    return designs.macro_grid(rows=(64, 256), cols=(256,),
                              adc_bits=(4, 6), dac_bits=(2,),
                              m_mux=(1, 16), n_macros=(1, 4),
                              tech_nm=(22,))


def _nets():
    layers = [workloads.dense(f"l{i}", 1, 24 + 8 * i, 8)
              for i in range(3)]
    return [("net_a", layers[:2]), ("net_b", layers[1:])]


def test_faults_off_is_bitwise_inert():
    grid = _grid()
    nets = _nets()
    dse.cache_clear()
    base = dse.sweep_networks(nets, grid, schedules=("ws", "os"))
    for faults in (None, FaultSpec()):
        dse.cache_clear()
        r = dse.sweep_networks(nets, grid, schedules=("ws", "os"),
                               faults=faults)
        for a, b in zip(base, r):
            np.testing.assert_array_equal(a.energy_fj, b.energy_fj)
            np.testing.assert_array_equal(a.cycles, b.cycles)
            assert b.survivors is None
            assert a.network_result(0) == b.network_result(0)


def _check_parity(spec, schedules=None):
    grid = _grid()
    nets = _nets()
    results = dse.sweep_networks(nets, grid, schedules=schedules,
                                 faults=spec)
    mask = survivor_mask(spec, grid)
    for res in results:
        assert res.survivors is not None
        np.testing.assert_array_equal(res.survivors.cols, mask.cols)
        for d in range(len(grid)):
            macro = grid.macro_at(d)
            cols, macros, _ = survivors_for(spec, macro)
            mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
            energy = 0.0
            cycles = 0
            for name, si in zip(res.layer_names, res._layer_shape):
                layer, g, best_idx = res._shapes[si]
                lr = dse.best_mapping_scalar(layer, macro, mem,
                                             schedules=schedules,
                                             survivors=(cols, macros))
                energy = energy + lr.total_energy_fj
                cycles = cycles + lr.cost.cycles
                # same winner, including tie-breaks: the fused argmin
                # lane re-priced through the scalar path must equal the
                # oracle's pick bitwise
                win = int(best_idx[d])
                sm = g.cand.mapping_at(win)
                sched = g.cand.schedule_at(win)
                from repro.core.mapping import evaluate
                cost = evaluate(layer, macro, sm, schedule=sched)
                assert cost == lr.cost, (res.network, name, d)
            assert energy == res.energy_fj[d], (res.network, d)
            assert cycles == res.cycles[d], (res.network, d)


@settings(max_examples=4, deadline=None)
@given(rate=st.sampled_from([0.05, 0.2, 0.5]),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_degraded_parity_pipelined(rate, seed):
    _check_parity(FaultSpec(column_fail_rate=rate, macro_fail_rate=rate,
                            seed=seed))


def test_degraded_parity_host_path():
    """ws+os at rate 0.3 against the scalar reference."""
    spec = FaultSpec(column_fail_rate=0.3, macro_fail_rate=0.3, seed=13)
    _check_parity(spec, schedules=("ws", "os"))


def test_host_and_pipelined_agree_bitwise(monkeypatch):
    grid = _grid()
    nets = _nets()
    spec = FaultSpec(column_fail_rate=0.4, macro_fail_rate=0.4, seed=5)
    piped = dse.sweep_networks(nets, grid, faults=spec)
    monkeypatch.setattr(dse, "_price_shapes", dse._price_shapes_reference)
    host = dse.sweep_networks(nets, grid, faults=spec)
    for a, b in zip(piped, host):
        np.testing.assert_array_equal(a.energy_fj, b.energy_fj)
        np.testing.assert_array_equal(a.cycles, b.cycles)


def test_degradation_never_improves_the_objective():
    grid = _grid()
    nets = _nets()
    base = dse.sweep_networks(nets, grid)
    deg = dse.sweep_networks(
        nets, grid, faults=FaultSpec(column_fail_rate=0.5,
                                     macro_fail_rate=0.5, seed=1))
    for a, b in zip(base, deg):
        # shrinking the legal set can only keep or worsen the argmin
        assert (b.energy_fj >= a.energy_fj).all()


def test_sweep_serving_accepts_faults():
    # the serving lattice shares sweep_networks; a degraded serving
    # sweep must still produce finite, well-formed per-design columns
    from repro.core import lm_bridge
    from repro import configs
    cfg = configs.get_smoke("qwen1.5-0.5b")
    pts = lm_bridge.serving_points(cfg, [(16, 1)], gen_len=4)
    grid = _grid()
    spec = FaultSpec(column_fail_rate=0.3, seed=2)
    base = dse.sweep_serving(pts, grid)
    deg = dse.sweep_serving(pts, grid, faults=spec)
    for a, b in zip(base, deg):
        assert np.isfinite(b.j_per_token).all()
        assert (b.energy_fj >= a.energy_fj).all()
        assert b.phase_sweeps[0].survivors is not None
