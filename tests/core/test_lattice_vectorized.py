"""Vectorized lattice construction parity + truncation edge pins.

``mapping.candidate_grid`` is now pure array construction (pools +
membership grids + index-arithmetic crossing); the original nested-loop
builder survives verbatim as ``mapping.candidate_grid_loop``, the
enumeration-order oracle.  These property tests pin the tentpole
contract: the two builders agree **bitwise** — every candidate field,
the per-design legality mask, the ``max_candidates`` truncation and the
schedule crossing — across random layer/knob grids, and the fused
``network_grid`` built from either set of per-shape grids is identical.

The truncation/zero-legal pins cover the satellite audit: designs whose
lattice rows are *entirely* masked (``max_candidates=0`` forces this
for every design) must keep finite sentinels through the fused pricing
pass and lose every argmin tie-break (the winner degenerates to lane 0
of each segment), and per-design truncation must interact with the
schedule crossing as ``len(schedules) * min(spatial_legal, cap)`` —
spatial truncation first, schedule expansion second.
"""

import numpy as np
import pytest

from repro.testing.hypocompat import given, settings, st

from repro.core import designs, dse, mapping, workloads

GRID_STRAT = dict(
    rows=st.sampled_from([(64,), (64, 256), (128, 512), (64, 128, 1024)]),
    cols=st.sampled_from([(64,), (256,), (64, 512)]),
    bw=st.sampled_from([(2,), (4,), (2, 8)]),
    bi=st.sampled_from([(2,), (4,), (8,)]),
    adc_bits=st.sampled_from([(4,), (4, 8), (3, 5, 6)]),
    dac_bits=st.sampled_from([(1,), (1, 4), (2,)]),
    m_mux=st.sampled_from([(1,), (1, 4), (1, 16)]),
    n_macros=st.sampled_from([(1,), (1, 4), (12,), (1, 2, 8)]),
    tech_nm=st.sampled_from([(28,), (5, 22)]),
    vdd=st.sampled_from([(0.8,), (0.6, 1.0)]),
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

TRUNC_STRAT = dict(
    max_candidates=st.sampled_from([0, 1, 3, 7, 40, 4096]),
    dataflows=st.sampled_from([None, ("os",), ("ws", "os")]),
)


def _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux, n_macros,
               tech_nm, vdd) -> designs.MacroBatch:
    return designs.macro_grid(
        rows=rows, cols=cols, bw=bw, bi=bi, adc_bits=adc_bits,
        dac_bits=dac_bits, m_mux=m_mux, n_macros=n_macros, tech_nm=tech_nm,
        vdd=vdd)


def _make_layer(b, k, c, ox, oy, fx, fy) -> workloads.Layer:
    return workloads.Layer("v-layer", "conv2d",
                           dict(B=b, K=k, C=c, OX=ox, OY=oy, FX=fx, FY=fy))


def _assert_grids_bitwise(a: mapping.MappingGrid,
                          b: mapping.MappingGrid) -> None:
    assert np.array_equal(a.legal, b.legal)
    assert len(a) == len(b)
    for f in ("k_cols", "k_macros", "c_un", "fx_un", "fy_un", "row_un",
              "mac_dim", "mac_un", "dup_macros", "n_spatial_temporal",
              "schedule"):
        assert np.array_equal(getattr(a.cand, f), getattr(b.cand, f)), f


# --------------------------------------------------------------------------- #
# candidate_grid: loop oracle vs vectorized builder, bitwise                  #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT, **TRUNC_STRAT})
@settings(max_examples=25, deadline=None)
def test_candidate_grid_matches_loop_oracle(rows, cols, bw, bi, adc_bits,
                                            dac_bits, m_mux, n_macros,
                                            tech_nm, vdd, b, k, c, ox, oy,
                                            fx, fy, max_candidates,
                                            dataflows):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    _assert_grids_bitwise(
        mapping.candidate_grid_loop(layer, grid,
                                    max_candidates=max_candidates,
                                    schedules=dataflows),
        mapping.candidate_grid(layer, grid, max_candidates=max_candidates,
                               schedules=dataflows))


def test_candidate_grid_matches_loop_on_tinyml_suite():
    """Fixed-case pin on the benchmark grid: every distinct tinyMLPerf
    layer shape, both schedule sets — the exact lattices the fused
    sweep prices."""
    grid = designs.macro_grid(
        rows=(64, 256, 1024), cols=(128, 512), adc_bits=(4, 8),
        dac_bits=(1, 2), m_mux=(1, 16), tech_nm=(22,), vdd=(0.8,),
        n_macros=(1, 2, 4))
    layers = [l for net in (workloads.deep_autoencoder(),
                            workloads.ds_cnn(),
                            workloads.mobilenet_v1_025())
              for l in net if l.imc_eligible]
    for sch in (None, ("ws", "os")):
        for layer in layers:
            _assert_grids_bitwise(
                mapping.candidate_grid_loop(layer, grid, schedules=sch),
                mapping.candidate_grid(layer, grid, schedules=sch))


# --------------------------------------------------------------------------- #
# network_grid over either builder's per-shape grids                           #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT})
@settings(max_examples=8, deadline=None)
def test_network_grid_matches_loop_oracle(rows, cols, bw, bi, adc_bits,
                                          dac_bits, m_mux, n_macros, tech_nm,
                                          vdd, b, k, c, ox, oy, fx, fy):
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd)
    layers = [_make_layer(b, k, c, ox, oy, fx, fy),
              workloads.dense("fc", b, max(1, c * fx), max(1, k // 2 + 1)),
              workloads.dense("head", b, max(1, k), 10)]
    scheds = ("ws", "os")
    loop_grids = [mapping.candidate_grid_loop(l, grid, schedules=scheds)
                  for l in layers]
    vec_grids = [mapping.candidate_grid(l, grid, schedules=scheds)
                 for l in layers]
    (net_l,) = mapping.network_grid(layers, grid, schedules=scheds,
                                    grids=loop_grids)
    (net_v,) = mapping.network_grid(layers, grid, schedules=scheds,
                                    grids=vec_grids)
    assert np.array_equal(net_l.starts, net_v.starts)
    assert np.array_equal(net_l.lane_layer, net_v.lane_layer)
    assert np.array_equal(net_l.legal, net_v.legal)
    assert np.array_equal(net_l.valid, net_v.valid)
    for f in mapping._CAND_FIELDS:
        assert np.array_equal(getattr(net_l.cand, f),
                              getattr(net_v.cand, f)), f


# --------------------------------------------------------------------------- #
# truncation x schedule crossing, and all-masked (zero-legal) designs          #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT,
          "max_candidates": st.sampled_from([0, 1, 3, 7, 40]),
          "dataflows": st.sampled_from([None, ("ws", "os")])})
@settings(max_examples=15, deadline=None)
def test_truncation_crosses_schedules_spatially(rows, cols, bw, bi,
                                                adc_bits, dac_bits, m_mux,
                                                n_macros, tech_nm, vdd, b, k,
                                                c, ox, oy, fx, fy,
                                                max_candidates, dataflows):
    """``max_candidates`` caps *spatial* candidates per design before
    the schedule axis expands: each design keeps exactly
    ``len(schedules) * min(spatial_legal, cap)`` legal lanes, and the
    truncated mask is the prefix of the untruncated one (repeated along
    the schedule-inner axis) — never a resampling."""
    grid = _make_grid(rows, cols, bw, bi, adc_bits, dac_bits, m_mux,
                      n_macros, tech_nm, vdd)
    layer = _make_layer(b, k, c, ox, oy, fx, fy)
    n_sched = 1 if dataflows is None else len(dataflows)
    spatial = mapping.candidate_grid(layer, grid, max_candidates=1 << 30)
    trunc = mapping.candidate_grid(layer, grid,
                                   max_candidates=max_candidates,
                                   schedules=dataflows)
    spatial_legal = spatial.legal.sum(axis=1)
    kept = np.minimum(spatial_legal, max_candidates)
    assert (trunc.legal.sum(axis=1) == n_sched * kept).all()
    # prefix property: the kept lanes are the FIRST spatial-legal lanes
    # in enumeration order, schedule lanes riding along unchanged
    prefix = spatial.legal & (np.cumsum(spatial.legal, axis=1)
                              <= max_candidates)
    assert np.array_equal(trunc.legal,
                          np.repeat(prefix, n_sched, axis=1))


def test_zero_legal_designs_keep_finite_sentinels_and_lane0():
    """``max_candidates=0`` masks every lane of every design — the
    degenerate case the fused pass must survive: the objective column
    is the finite sentinel everywhere (never inf/NaN), the per-segment
    argmin collapses to lane 0 (all tie-breaks lost, first-wins over an
    all-equal column), and the priced totals stay finite."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(4, 6),
                              dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,))
    layers = [workloads.dense("a", 1, 130, 37), workloads.dense("b", 2, 9, 5)]
    for scheds in (None, ("ws", "os")):
        grids = [mapping.candidate_grid(l, grid, max_candidates=0,
                                        schedules=scheds) for l in layers]
        for g in grids:
            assert g.legal.shape[1] == len(g)
            assert not g.legal.any()
        (net,) = mapping.network_grid(layers, grid, schedules=scheds,
                                      grids=grids)
        assert not net.legal.any()
        per_bit = np.full(len(grid), 1.5)
        priced = dse._price_buckets([net], grid, "energy", None, per_bit,
                                    1 << 20, 4000.0)
        for _g, best_idx, total, cycles in priced:
            assert (best_idx == 0).all()
            assert np.isfinite(total).all()
            assert (cycles < np.iinfo(np.int64).max).all()


def test_zero_legal_matches_loop_oracle():
    """The all-masked lattice is still bitwise the loop builder's."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1,), tech_nm=(22,),
                              n_macros=(1, 4))
    layer = workloads.dense("z", 4, 96, 40)
    for scheds in (None, ("ws", "os")):
        _assert_grids_bitwise(
            mapping.candidate_grid_loop(layer, grid, max_candidates=0,
                                        schedules=scheds),
            mapping.candidate_grid(layer, grid, max_candidates=0,
                                   schedules=scheds))


# --------------------------------------------------------------------------- #
# per-class legality: legal_rows[design_class] is the per-design mask          #
# --------------------------------------------------------------------------- #
def _grid_1620() -> designs.MacroBatch:
    """The 1620-design sweep grid (``benchmarks.design_sweep.make_grid``):
    5 rows x 3 cols, so 15 legality classes."""
    return designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))


def _grid_multi_macro() -> designs.MacroBatch:
    return designs.macro_grid(
        rows=(64, 256, 1024), cols=(128, 512), bw=(2, 8), adc_bits=(4, 8),
        dac_bits=(1, 2), m_mux=(1, 16), tech_nm=(22,), vdd=(0.8,),
        n_macros=(1, 2, 4))


def _n_classes(grid: designs.MacroBatch) -> int:
    return len({(int(a), int(b), int(c)) for a, b, c in
                zip(grid.d1, grid.rows, grid.n_macros)})


_CLASS_LAYERS = (
    workloads.Layer("conv", "conv2d",
                    dict(B=1, K=64, C=32, OX=16, OY=16, FX=3, FY=3)),
    workloads.dense("fc", 1, 640, 128),
    workloads.Layer("dw", "conv2d",
                    dict(B=1, K=1, C=1, OX=25, OY=5, FX=3, FY=3, G=64)),
)


@pytest.mark.parametrize("max_candidates", [4096, 3])
@pytest.mark.parametrize("scheds", [("ws",), ("os",), ("ws", "os")])
@pytest.mark.parametrize("grid_fn", [_grid_1620, _grid_multi_macro])
def test_class_rows_expand_to_per_design_mask(grid_fn, scheds,
                                              max_candidates):
    """The vectorized builder keeps legality on the distinct
    (d1, rows, n_macros) classes; gathered through ``design_class`` it
    is the loop oracle's per-design mask element for element, truncation
    and schedule crossing included, and ``mappings_for`` reads one
    design's row without expanding the rest."""
    grid = grid_fn()
    for layer in _CLASS_LAYERS:
        loop = mapping.candidate_grid_loop(
            layer, grid, max_candidates=max_candidates, schedules=scheds)
        vec = mapping.candidate_grid(
            layer, grid, max_candidates=max_candidates, schedules=scheds)
        assert loop.legal_rows.shape == (len(grid), len(loop))
        assert np.array_equal(loop.design_class, np.arange(len(grid)))
        assert vec.legal_rows.shape == (_n_classes(grid), len(vec))
        assert vec.design_class.shape == (len(grid),)
        assert vec.design_class.dtype == np.int32
        assert vec.n_designs == len(grid)
        assert np.array_equal(vec.legal_rows[vec.design_class],
                              loop.legal_rows)
        if max_candidates == 3:
            assert (vec.legal_rows.sum(axis=1) <= 3 * len(scheds)).all()
        for d in (0, len(grid) // 2, len(grid) - 1):
            assert vec.mappings_for(d) == loop.mappings_for(d)
    if grid_fn is _grid_1620:
        assert _n_classes(grid) == 15


def test_network_grid_shares_design_class_and_pads_false():
    """``network_grid`` concatenates the class rows of every segment:
    one ``design_class`` for the bucket (each segment's own), pad lanes
    False in every class, and each segment's lanes its grid's rows.
    Grids numbering classes differently fall back to one class per
    design with the same expanded mask."""
    grid = _grid_multi_macro()
    scheds = ("ws", "os")
    grids = [mapping.candidate_grid(l, grid, schedules=scheds)
             for l in _CLASS_LAYERS]
    (net,) = mapping.network_grid(_CLASS_LAYERS, grid, schedules=scheds,
                                  grids=grids, pad_quantum=256)
    assert net.pad_lanes > 0, "fixture no longer pads the lane axis"
    assert net.legal_rows.shape == (_n_classes(grid), len(net))
    for s, g in enumerate(grids):
        assert np.array_equal(g.design_class, net.design_class)
        assert np.array_equal(net.legal_rows[:, net.segment(s)],
                              g.legal_rows)
        assert np.array_equal(net.legal[:, net.segment(s)], g.legal)
    assert not net.legal_rows[:, ~net.valid].any()

    mixed = [mapping.candidate_grid_loop(_CLASS_LAYERS[0], grid,
                                         schedules=scheds)] + grids[1:]
    (net_m,) = mapping.network_grid(_CLASS_LAYERS, grid, schedules=scheds,
                                    grids=mixed, pad_quantum=256)
    assert np.array_equal(net_m.design_class, np.arange(len(grid)))
    assert np.array_equal(net_m.legal_rows, net.legal)


# --------------------------------------------------------------------------- #
# lattice_key: what a lattice reads, and nothing else                          #
# --------------------------------------------------------------------------- #
def _assert_same_lattice(a: mapping.MappingGrid,
                         b: mapping.MappingGrid) -> None:
    """Every candidate column, the class rows and the class index."""
    assert len(a) == len(b)
    for f in mapping._CAND_FIELDS:
        assert np.array_equal(getattr(a.cand, f), getattr(b.cand, f)), f
    assert np.array_equal(a.legal_rows, b.legal_rows)
    assert np.array_equal(a.design_class, b.design_class)


@pytest.mark.parametrize("grid_fn", [_grid_1620, _grid_multi_macro])
@given(k=st.integers(1, 96), c=st.integers(1, 96),
       ox=st.sampled_from([1, 5, 16]), oy=st.sampled_from([1, 7, 16]),
       g=st.sampled_from([1, 4]), fx=st.sampled_from([1, 3]),
       fy=st.sampled_from([1, 3]), b=st.sampled_from([(1, 64), (8, 1024)]),
       precs=st.sampled_from([((4, 4, 24), (8, 8, 32)),
                              ((2, 8, 16), (4, 2, 24))]),
       dataflows=st.sampled_from([None, ("ws", "os")]))
@settings(max_examples=4, deadline=None)
def test_layers_sharing_lattice_key_get_identical_grids(
        grid_fn, k, c, ox, oy, g, fx, fy, b, precs, dataflows):
    """Two layers that agree on ``lattice_key`` but differ in ``B``, in
    every precision and in name get bitwise-identical grids from both
    builders: the lattice memo may hand one layer's grid to the other."""
    grid = grid_fn()
    dims = dict(K=k, C=c, OX=ox, OY=oy, G=g, FX=fx, FY=fy)
    (w0, i0, p0), (w1, i1, p1) = precs
    a = workloads.Layer("prefill.q", "dense", dict(dims, B=b[0]),
                        w_prec=w0, i_prec=i0, psum_prec=p0)
    z = workloads.Layer("decode.q", "dense", dict(dims, B=b[1]),
                        w_prec=w1, i_prec=i1, psum_prec=p1)
    assert mapping.lattice_key(a) == mapping.lattice_key(z)
    assert dse._shape_key(a) != dse._shape_key(z)
    _assert_same_lattice(
        mapping.candidate_grid(a, grid, schedules=dataflows),
        mapping.candidate_grid(z, grid, schedules=dataflows))
    _assert_same_lattice(
        mapping.candidate_grid_loop(a, grid, schedules=dataflows),
        mapping.candidate_grid_loop(z, grid, schedules=dataflows))


def test_lattice_key_reads_each_lattice_dim_and_defaults_to_one():
    """A layer's missing dims read 1, as ``Layer.dim`` does; changing
    any of the seven dims the builders read changes the key, and ``B``
    does not."""
    base = dict(K=8, C=16, FX=3, FY=3, OX=5, OY=7, G=2)
    key = mapping.lattice_key(workloads.Layer("x", "conv2d", base))
    assert key == (8, 16, 3, 3, 5, 7, 2)
    assert mapping.lattice_key(workloads.dense("d", 4, 16, 8)) == \
        mapping.lattice_key(workloads.Layer("e", "dense",
                                            dict(K=8, C=16, OX=1, G=1)))
    for d in base:
        bumped = workloads.Layer("x", "conv2d", dict(base, **{d: 11}))
        assert mapping.lattice_key(bumped) != key, d
    assert mapping.lattice_key(
        workloads.Layer("x", "conv2d", dict(base, B=64))) == key
