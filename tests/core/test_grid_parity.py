"""Design-axis grid engine parity: grid <-> batch <-> scalar oracle.

The grid engine (``designs.macro_grid`` + ``energy.tile_energy_grid`` +
``mapping.candidate_grid`` / ``evaluate_grid`` + ``dse.sweep``) promises
the same bitwise contract over *designs* that PR 1's batch engine
promises over mapping candidates: every legal (design, candidate) entry
carries exactly the floats the scalar oracle computes, candidate order
restricted to one design reproduces the scalar enumeration order (so
argmins tie-break identically), and per-design sweep totals equal
``map_network`` on that design, bitwise.  These property tests draw
random legal (layer, macro-grid) pairs from knob ranges — replacing the
fixed-case-only parity coverage the suite had before — and pin the
acceptance criterion: a >= 1000-point grid whose sampled points match
the scalar oracle exactly.
"""

import numpy as np
import pytest

from repro.testing.hypocompat import (  # real hypothesis when installed
    given, settings, st)

from repro.core import designs, dse, energy, mapping, workloads
from repro.core.hardware import IMCType
from repro.core.memory import MemoryModel

# --------------------------------------------------------------------------- #
# random knob-range / workload strategies                                      #
# --------------------------------------------------------------------------- #
GRID_STRAT = dict(
    rows=st.sampled_from([(64,), (64, 256), (128, 512), (64, 128, 1024)]),
    cols=st.sampled_from([(64,), (256,), (64, 512)]),
    bw=st.sampled_from([(2,), (4,), (2, 8)]),
    bi=st.sampled_from([(2,), (4,), (8,)]),
    adc_bits=st.sampled_from([(4,), (4, 8), (3, 5, 6)]),
    dac_bits=st.sampled_from([(1,), (1, 4), (2,)]),
    m_mux=st.sampled_from([(1,), (1, 4), (1, 16)]),
    n_macros=st.sampled_from([(1,), (1, 4), (12,)]),
    tech_nm=st.sampled_from([(28,), (5, 22), (28, 65)]),
    vdd=st.sampled_from([(0.8,), (0.6, 1.0)]),
    booth=st.sampled_from([(False,), (False, True)]),
    cols_per_adc=st.sampled_from([(1,), (1, 4)]),
    adc_share=st.sampled_from([(8,), (1, 8)]),
)

LAYER_STRAT = dict(
    b=st.sampled_from([1, 4]),
    k=st.integers(1, 96),
    c=st.integers(1, 96),
    ox=st.sampled_from([1, 5, 16]),
    oy=st.sampled_from([1, 7, 16]),
    fx=st.sampled_from([1, 3]),
    fy=st.sampled_from([1, 3]),
)


def _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux, n_macros,
               tech_nm, vdd, booth, cols_per_adc, adc_share
               ) -> designs.MacroBatch:
    return designs.macro_grid(
        rows=rows, cols=cols, bw=bw, bi=bi, adc_bits=adc_bits,
        dac_bits=dac_bits, m_mux=m_mux, n_macros=n_macros, tech_nm=tech_nm,
        vdd=vdd, booth=booth, cols_per_adc=cols_per_adc,
        adc_share=adc_share)


def _make_layer(b, k, c, ox, oy, fx, fy) -> workloads.Layer:
    return workloads.Layer("g-layer", "conv2d",
                           dict(B=b, K=k, C=c, OX=ox, OY=oy, FX=fx, FY=fy))


_ENERGY_FIELDS = ("e_wl", "e_bl", "e_logic", "e_adc", "e_adder_tree",
                  "e_dac", "e_weight_write", "macs")


# --------------------------------------------------------------------------- #
# macro_grid expansion                                                        #
# --------------------------------------------------------------------------- #
@given(**GRID_STRAT)
@settings(max_examples=20, deadline=None)
def test_macro_grid_designs_legal_and_unique(rows, cols, bw, bi, adc_bits,
                                             dac_bits, m_mux, n_macros,
                                             tech_nm, vdd, booth,
                                             cols_per_adc, adc_share):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    assert len(grid) >= 1
    assert len(set(grid.names)) == len(grid)         # unique names
    for d in range(len(grid)):
        m = grid.macro_at(d)                         # __post_init__ validated
        if m.analog:
            assert m.m_mux == 1 and m.adc_res > 0 and m.dac_res > 0
        else:
            assert m.adc_res == 0 and m.dac_res == 0
        # struct-of-arrays rows mirror the scalar macro exactly
        assert int(grid.d1[d]) == m.d1
        assert int(grid.d2[d]) == m.d2
        assert int(grid.cc_bs[d]) == m.cc_bs
        assert bool(grid.analog[d]) == m.analog


def test_macro_grid_rejects_empty():
    with pytest.raises(ValueError):
        designs.macro_grid(imc_type="aimc", rows=(100,), cols=(100,),
                           bw=(8,), m_mux=(3,))       # 100 % 8 != 0


# --------------------------------------------------------------------------- #
# tile_energy_grid vs scalar oracle per (design, tile)                         #
# --------------------------------------------------------------------------- #
@given(**GRID_STRAT)
@settings(max_examples=15, deadline=None)
def test_tile_energy_grid_bitwise(rows, cols, bw, bi, adc_bits, dac_bits,
                                  m_mux, n_macros, tech_nm, vdd, booth,
                                  cols_per_adc, adc_share):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    rng = np.random.default_rng(len(grid))
    n = 9
    n_inputs = rng.integers(1, 5000, n)
    rows_used = rng.integers(1, int(grid.rows.max()) + 1, n)
    cols_used = rng.integers(1, int(grid.d1.max()) + 1, n)
    loads = rng.integers(1, 9, n)
    g = energy.tile_energy_grid(grid, n_inputs=n_inputs, rows_used=rows_used,
                                cols_used=cols_used, weight_loads=loads)
    d_idx = rng.integers(0, len(grid), min(6, len(grid)))
    for d in map(int, d_idx):
        macro = grid.macro_at(d)
        for i in range(n):
            ref = energy.tile_energy(macro, energy.MacroTile(
                n_inputs=int(n_inputs[i]), rows_used=int(rows_used[i]),
                cols_used=int(cols_used[i]), weight_loads=int(loads[i])))
            got = energy.EnergyBreakdown(
                *(float(getattr(g, f)[d, i]) for f in _ENERGY_FIELDS))
            assert got == ref                        # exact float eq


# --------------------------------------------------------------------------- #
# candidate_grid: masked rows == enumerate_mappings, per design               #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=15, deadline=None)
def test_candidate_grid_matches_generator(rows, cols, bw, bi, adc_bits,
                                          dac_bits, m_mux, n_macros, tech_nm,
                                          vdd, booth, cols_per_adc,
                                          adc_share, b, k, c, ox, oy, fx,
                                          fy):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    mg = mapping.candidate_grid(layer, grid)
    assert mg.legal.shape == (len(grid), len(mg))
    rng = np.random.default_rng(k * 11 + c)
    for d in map(int, rng.integers(0, len(grid), min(5, len(grid)))):
        gen = tuple(mapping.enumerate_mappings(layer, grid.macro_at(d)))
        assert mg.mappings_for(d) == gen             # same set, same order


# --------------------------------------------------------------------------- #
# evaluate_grid vs per-design batch engine (bitwise columns)                   #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=10, deadline=None)
def test_evaluate_grid_bitwise_vs_batch(rows, cols, bw, bi, adc_bits,
                                        dac_bits, m_mux, n_macros, tech_nm,
                                        vdd, booth, cols_per_adc, adc_share,
                                        b, k, c, ox, oy, fx, fy):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    mg = mapping.candidate_grid(layer, grid)
    costs = mapping.evaluate_grid(layer, grid, mg)
    rng = np.random.default_rng(k * 13 + ox)
    for d in map(int, rng.integers(0, len(grid), min(4, len(grid)))):
        macro = grid.macro_at(d)
        batch = mapping.candidate_batch(layer, macro)
        ref = mapping.evaluate_batch(layer, macro, batch)
        sel = np.flatnonzero(mg.legal[d])            # grid col -> batch row
        assert len(sel) == len(batch)
        for f in _ENERGY_FIELDS:
            assert (getattr(costs.macro_energy, f)[d, sel]
                    == getattr(ref.macro_energy, f)).all()
        assert (costs.cycles[d, sel] == ref.cycles).all()
        assert (costs.weight_tiles[sel] == ref.weight_tiles).all()
        assert (costs.inputs_per_tile[sel] == ref.inputs_per_tile).all()
        assert (costs.weight_bits[sel] == ref.weight_bits).all()
        assert (costs.input_bits[sel] == ref.input_bits).all()
        assert (costs.output_bits[sel] == ref.output_bits).all()
        assert (costs.psum_bits[sel] == ref.psum_bits).all()


# --------------------------------------------------------------------------- #
# sweep vs per-design engines: totals, argmin identity, full results           #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT,
          "objective": st.sampled_from(["energy", "latency", "edp"])})
@settings(max_examples=10, deadline=None)
def test_sweep_matches_per_design_engines(rows, cols, bw, bi, adc_bits,
                                          dac_bits, m_mux, n_macros, tech_nm,
                                          vdd, booth, cols_per_adc,
                                          adc_share, b, k, c, ox, oy, fx,
                                          fy, objective):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    res = dse.sweep("prop", [layer], grid, objective=objective)
    rng = np.random.default_rng(k * 17 + oy)
    for d in map(int, rng.integers(0, len(grid), min(4, len(grid)))):
        macro = grid.macro_at(d)
        mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
        a = dse.best_mapping_scalar(layer, macro, mem, objective=objective)
        bt = dse.best_mapping_batched(layer, macro, mem, objective=objective)
        assert a == bt
        # bitwise totals + argmin identity (same winning mapping)
        assert float(res.energy_fj[d]) == a.total_energy_fj
        assert int(res.cycles[d]) == a.cost.cycles
        nr = res.network_result(d)
        assert nr.layers[0] == a


# --------------------------------------------------------------------------- #
# dataflow axis: grid <-> batch <-> scalar across (layer, macro, dataflow)      #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT,
          "dataflows": st.sampled_from([("ws",), ("os",), ("ws", "os")]),
          "objective": st.sampled_from(["energy", "latency", "edp"])})
@settings(max_examples=10, deadline=None)
def test_sweep_dataflow_axis_matches_scalar_oracle(rows, cols, bw, bi,
                                                   adc_bits, dac_bits, m_mux,
                                                   n_macros, tech_nm, vdd,
                                                   booth, cols_per_adc,
                                                   adc_share, b, k, c, ox,
                                                   oy, fx, fy, dataflows,
                                                   objective):
    """Random (layer, macro-grid, dataflow-set) triples: the fused
    (design x mapping x dataflow) sweep reproduces the scalar oracle's
    per-design winner — totals, full result, and the chosen (mapping,
    dataflow) pair — bitwise, including argmin tie-breaks (the scalar
    loop is first-wins over mappings outer / schedules inner)."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    res = dse.sweep("prop", [layer], grid, objective=objective,
                    schedules=dataflows)
    assert res.schedules == dataflows
    rng = np.random.default_rng(k * 19 + c + len(dataflows))
    for d in map(int, rng.integers(0, len(grid), min(4, len(grid)))):
        macro = grid.macro_at(d)
        mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
        a = dse.best_mapping_scalar(layer, macro, mem, objective=objective,
                                    schedules=dataflows)
        bt = dse.best_mapping_batched(layer, macro, mem,
                                      objective=objective,
                                      schedules=dataflows)
        assert a == bt
        assert float(res.energy_fj[d]) == a.total_energy_fj
        assert int(res.cycles[d]) == a.cost.cycles
        nr = res.network_result(d)
        assert nr.layers[0] == a
        assert res.dataflows(d) == (a.cost.schedule.name,)


@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=8, deadline=None)
def test_evaluate_grid_dataflow_bitwise_vs_batch(rows, cols, bw, bi,
                                                 adc_bits, dac_bits, m_mux,
                                                 n_macros, tech_nm, vdd,
                                                 booth, cols_per_adc,
                                                 adc_share, b, k, c, ox, oy,
                                                 fx, fy):
    """With both schedules enabled, every legal (design, candidate)
    entry of the grid engine stays bitwise-equal to the per-design
    batch engine, and the candidate axis interleaves schedules
    mapping-outer / schedule-inner."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    scheds = ("ws", "os")
    mg = mapping.candidate_grid(layer, grid, schedules=scheds)
    assert (mg.cand.schedule[0::2] == 0).all()         # ws lanes
    assert (mg.cand.schedule[1::2] == 1).all()         # os lanes
    costs = mapping.evaluate_grid(layer, grid, mg)
    rng = np.random.default_rng(k * 23 + oy)
    for d in map(int, rng.integers(0, len(grid), min(3, len(grid)))):
        macro = grid.macro_at(d)
        batch = mapping.candidate_batch(layer, macro, schedules=scheds)
        ref = mapping.evaluate_batch(layer, macro, batch)
        sel = np.flatnonzero(mg.legal[d])
        assert len(sel) == len(batch)
        assert (mg.cand.schedule[sel] == batch.schedule).all()
        for f in _ENERGY_FIELDS:
            assert (getattr(costs.macro_energy, f)[d, sel]
                    == getattr(ref.macro_energy, f)).all()
        assert (costs.cycles[d, sel] == ref.cycles).all()
        for f in ("weight_bits", "input_bits", "output_bits", "psum_bits"):
            assert (getattr(costs, f)[sel] == getattr(ref, f)).all()


def test_sweep_acceptance_1000_point_grid():
    """Acceptance pin: a >= 1000-point macro grid, >= 50 sampled points
    bitwise-matching the scalar oracle (totals + full network result)."""
    grid = designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))
    assert len(grid) >= 1000
    layer = workloads.dense("probe", 64, 1024, 64)
    res = dse.sweep("probe", [layer], grid)
    rng = np.random.default_rng(0)
    sampled = sorted(set(map(int, rng.integers(0, len(grid), 80))))
    assert len(sampled) >= 50
    for d in sampled:
        macro = grid.macro_at(d)
        ref = dse.map_network("probe", [layer], macro, engine="scalar")
        assert float(res.energy_fj[d]) == ref.total_energy_fj
        assert int(res.cycles[d]) == ref.total_cycles
        assert res.network_result(d) == ref


def test_sweep_repeated_shapes_and_multinet():
    """Repeated layer shapes are priced once but accumulated per layer,
    matching map_network (which caches) bitwise, on a real network."""
    grid = designs.macro_grid(rows=(256, 1024), cols=(256,),
                              adc_bits=(5,), dac_bits=(2,), m_mux=(1, 16),
                              tech_nm=(22,))
    layers = workloads.deep_autoencoder()
    res = dse.sweep("dae", layers, grid)
    # 11 layers, but only 7 distinct shapes were priced
    assert len(res.layer_names) == len(layers)
    assert len(res._shapes) < len(layers)
    dse.cache_clear()
    for d in range(len(grid)):
        ref = dse.map_network("dae", layers, grid.macro_at(d))
        assert float(res.energy_fj[d]) == ref.total_energy_fj
        assert int(res.cycles[d]) == ref.total_cycles
        assert res.network_result(d) == ref


def test_sweep_fixed_memory_model():
    grid = designs.macro_grid(rows=(128, 256), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1,), tech_nm=(22, 65))
    layer = workloads.dense("d", 4, 256, 64)
    mem = MemoryModel(tech_nm=28, vdd=0.8, buffer_bytes=1 << 10)  # force DRAM
    res = dse.sweep("d", [layer], grid, mem=mem)
    for d in range(len(grid)):
        ref = dse.best_mapping_scalar(layer, grid.macro_at(d), mem)
        assert float(res.energy_fj[d]) == ref.total_energy_fj


def test_sweep_pareto_frontier_sound():
    grid = designs.macro_grid(rows=(64, 256, 1024), cols=(128, 256),
                              adc_bits=(4, 6, 8), dac_bits=(1, 4),
                              m_mux=(1, 16), tech_nm=(5, 28))
    layer = workloads.dense("probe", 64, 1024, 64)
    res = dse.sweep("probe", [layer], grid)
    mask = res.pareto_mask()
    front = res.pareto()
    assert mask.any()
    assert set(front) == set(np.flatnonzero(mask))
    pts = np.stack([res.energy_fj, res.cycles.astype(float),
                    res.area_mm2], axis=1)
    # no frontier point dominates another; every dominated point has a
    # dominating frontier witness
    for i in front:
        for j in front:
            if i != j:
                assert not ((pts[j] <= pts[i]).all()
                            and (pts[j] < pts[i]).any())
    for i in np.flatnonzero(~mask):
        assert any((pts[j] <= pts[i]).all() and (pts[j] < pts[i]).any()
                   for j in front)
    # the objective-best design is never dominated
    assert mask[res.best()]


# --------------------------------------------------------------------------- #
# workload-axis fusion: fused network sweep vs per-layer oracles               #
# --------------------------------------------------------------------------- #
def _make_network(b, k, c, ox, oy, fx, fy) -> list[workloads.Layer]:
    """A mixed conv/dense/depthwise network with a repeated conv shape
    (same dims, different name) so the fused sweep exercises slot dedup
    alongside the ragged lane axis."""
    conv = dict(B=b, K=k, C=c, OX=ox, OY=oy, FX=fx, FY=fy)
    return [
        workloads.Layer("c0", "conv2d", conv),
        workloads.Layer("dw1", "depthwise",
                        dict(B=b, G=max(2, c), OX=ox, OY=oy, FX=fx, FY=fy)),
        workloads.dense("fc2", b, max(1, c * fx), max(1, k // 2 + 1)),
        workloads.Layer("c3", "conv2d", conv),            # repeated shape
        workloads.dense("head", b, max(1, k), 10),
    ]


@given(**{**GRID_STRAT, **LAYER_STRAT,
          "dataflows": st.sampled_from([None, ("ws", "os")]),
          "objective": st.sampled_from(["energy", "latency", "edp"])})
@settings(max_examples=8, deadline=None)
def test_fused_network_sweep_matches_scalar_oracle(rows, cols, bw, bi,
                                                   adc_bits, dac_bits, m_mux,
                                                   n_macros, tech_nm, vdd,
                                                   booth, cols_per_adc,
                                                   adc_share, b, k, c, ox, oy,
                                                   fx, fy, dataflows,
                                                   objective):
    """Random multi-layer networks (mixed conv/dense/depthwise shapes):
    the workload-fused sweep — all shapes in one padded lane lattice,
    one jit dispatch — reproduces the per-layer scalar oracle bitwise
    on sampled designs: totals, full network result, and every winning
    (mapping, dataflow) pair including argmin tie-breaks."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layers = _make_network(b, k, c, ox, oy, fx, fy)
    res = dse.sweep("mixed", layers, grid, objective=objective,
                    schedules=dataflows)
    assert len(res._shapes) < len(res.layer_names)        # dedup happened
    rng = np.random.default_rng(k * 29 + ox + len(res))
    for d in map(int, rng.integers(0, len(grid), min(3, len(grid)))):
        ref = dse.map_network("mixed", layers, grid.macro_at(d),
                              objective=objective, engine="scalar",
                              schedules=dataflows)
        assert float(res.energy_fj[d]) == ref.total_energy_fj
        assert int(res.cycles[d]) == ref.total_cycles
        assert res.network_result(d) == ref


@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=6, deadline=None)
def test_sweep_networks_matches_individual_sweeps(rows, cols, bw, bi,
                                                  adc_bits, dac_bits, m_mux,
                                                  n_macros, tech_nm, vdd,
                                                  booth, cols_per_adc,
                                                  adc_share, b, k, c, ox, oy,
                                                  fx, fy):
    """Several networks priced in ONE fused pass return exactly what
    per-network ``sweep`` calls return, even though shapes shared
    across networks occupy one lattice slot."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layers = _make_network(b, k, c, ox, oy, fx, fy)
    nets = [("net_a", layers[:3]), ("net_b", layers[2:])]   # share fc2's shape
    fused = dse.sweep_networks(nets, grid)
    for (name, ls), res in zip(nets, fused):
        alone = dse.sweep(name, ls, grid)
        assert res.network == alone.network == name
        assert (res.energy_fj == alone.energy_fj).all()
        assert (res.cycles == alone.cycles).all()
        assert res.layer_names == alone.layer_names
        assert res.network_result(0) == alone.network_result(0)


@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=6, deadline=None)
def test_evaluate_network_grid_bitwise_vs_per_layer(rows, cols, bw, bi,
                                                    adc_bits, dac_bits,
                                                    m_mux, n_macros, tech_nm,
                                                    vdd, booth, cols_per_adc,
                                                    adc_share, b, k, c, ox,
                                                    oy, fx, fy):
    """Every real lane segment of the fused lattice carries bitwise the
    columns the per-layer grid engine computes for that shape."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd, booth, cols_per_adc,
                      adc_share)
    layers = [l for l in _make_network(b, k, c, ox, oy, fx, fy)[:3]]
    (net,) = mapping.network_grid(layers, grid, schedules=("ws", "os"))
    costs = mapping.evaluate_network_grid(net, grid)
    assert net.legal[:, ~net.valid].sum() == 0             # pads never legal
    for s, layer in enumerate(net.layers):
        seg = net.segment(s)
        mg = net.grids[s]
        ref = mapping.evaluate_grid(layer, grid, mg, alpha=None)
        assert (net.legal[:, seg] == mg.legal).all()
        for f in _ENERGY_FIELDS:
            assert (getattr(costs.macro_energy, f)[:, seg]
                    == getattr(ref.macro_energy, f)).all()
        assert (costs.cycles[:, seg] == ref.cycles).all()
        for f in ("weight_tiles", "inputs_per_tile", "weight_bits",
                  "input_bits", "output_bits", "psum_bits"):
            assert (getattr(costs, f)[seg] == getattr(ref, f)).all()


def test_tile_energy_grid_leading_layer_axis():
    """(L, C) stacked tile arguments produce an (L, D, C) lattice whose
    every row is bitwise the 1-D call on that row alone."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,))
    rng = np.random.default_rng(7)
    L, C = 3, 11
    n_inputs = rng.integers(1, 4000, (L, C))
    rows_used = rng.integers(1, 257, (L, C))
    cols_used = rng.integers(1, 65, (L, C))
    loads = rng.integers(1, 9, (L, C))
    stacked = energy.tile_energy_grid(grid, n_inputs=n_inputs,
                                      rows_used=rows_used,
                                      cols_used=cols_used,
                                      weight_loads=loads)
    assert stacked.e_wl.shape == (L, len(grid), C)
    for l in range(L):
        row = energy.tile_energy_grid(grid, n_inputs=n_inputs[l],
                                      rows_used=rows_used[l],
                                      cols_used=cols_used[l],
                                      weight_loads=loads[l])
        for f in _ENERGY_FIELDS:
            assert (getattr(stacked, f)[l] == getattr(row, f)).all()


def test_padded_lanes_are_inert():
    """Masked-lane immunity pin: quantum-padding filler lanes hold
    benign finite values (no NaN/inf arithmetic anywhere in the fused
    pass), and scribbling garbage into them changes nothing — the
    finite sentinel masking keeps every winner and total bitwise."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(4, 6),
                              dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,))
    layers = [workloads.dense("a", 1, 130, 37), workloads.dense("b", 2, 9, 5)]
    per_bit = np.full(len(grid), 1.5)

    def price(poison: bool):
        (net,) = mapping.network_grid(layers, grid, schedules=("ws", "os"))
        assert net.pad_lanes > 0
        if poison:
            pad = ~net.valid
            for f in ("k_cols", "k_macros", "c_un", "fx_un", "fy_un",
                      "row_un", "mac_un", "dup_macros", "n_spatial_temporal"):
                getattr(net.cand, f)[pad] = 997
        priced = dse._price_buckets([net], grid, "energy", None, per_bit,
                                    1 << 20, 4000.0)
        costs = mapping.evaluate_network_grid(net, grid)
        return priced, costs

    clean, costs_clean = price(poison=False)
    dirty, costs_dirty = price(poison=True)
    # every fused column is finite even on (poisoned) pad lanes
    for costs in (costs_clean, costs_dirty):
        for f in _ENERGY_FIELDS:
            assert np.isfinite(getattr(costs.macro_energy, f)).all()
    for (g0, i0, t0, c0), (g1, i1, t1, c1) in zip(clean, dirty):
        assert (i0 == i1).all()
        assert (t0 == t1).all()
        assert (c0 == c1).all()


def test_cache_info_reports_lattice_stats():
    grid = designs.macro_grid(rows=(64,), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1,), tech_nm=(22,))
    dse.cache_clear()
    layers = workloads.deep_autoencoder()
    dse.sweep("dae", layers, grid)
    info = dse.cache_info()
    # slots count shapes (``_shape_key``), not lattices; the 5 shapes
    # here also differ in C or K, so they are 5 lattices as well
    assert info["lattice_slots"] == 5            # 5 distinct dense shapes
    assert info["lattice_layers"] == len(layers)
    assert 0.0 <= info["padding_waste"] < 1.0
    dse.cache_clear()
    assert dse.cache_info()["lattice_slots"] == 0


def test_sweep_matches_table2_designs():
    """from_macros path: sweeping the hand-built Table II designs equals
    map_network on each, bitwise (no macro_grid involved)."""
    batch = designs.MacroBatch.from_macros(designs.table2_designs())
    layers = workloads.ds_cnn()
    res = dse.sweep("ds_cnn", layers, batch)
    dse.cache_clear()
    for d in range(len(batch)):
        ref = dse.map_network("ds_cnn", layers, batch.macro_at(d))
        assert float(res.energy_fj[d]) == ref.total_energy_fj
        assert res.network_result(d) == ref
