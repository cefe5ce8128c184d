"""Sweep route parity (device-side reduction) against the full-grid
reference.

The sweep prices buckets through ``mapping.reduce_network_grid`` —
objective assembly and the masked per-segment argmin run inside the jit
graph and only (S, D) winners cross the device→host boundary.  The
contract these tests pin: the route (``dse._price_shapes``) is
**bitwise identical** to the full-grid host reference
(``dse._price_shapes_reference``) — argmins (first-minimum tie-breaks
included), totals, cycles, and masked poison-pad lanes — across random
grids, layers, schedules, objectives and pipeline depths.
"""

import numpy as np
import pytest

from repro.testing.hypocompat import (  # real hypothesis when installed
    given, settings, st)

from repro.core import designs, dse, energy, workloads
from repro.core.schedule import normalize
from repro.faults import FaultSpec

GRID_STRAT = dict(
    rows=st.sampled_from([(64,), (64, 256), (128, 512)]),
    cols=st.sampled_from([(64,), (64, 512)]),
    bw=st.sampled_from([(2,), (2, 8)]),
    adc_bits=st.sampled_from([(4,), (4, 8)]),
    m_mux=st.sampled_from([(1,), (1, 4)]),
    tech_nm=st.sampled_from([(28,), (5, 22)]),
)

LAYER_STRAT = dict(
    k=st.integers(1, 96),
    c=st.integers(1, 96),
    ox=st.sampled_from([1, 5, 16]),
    oy=st.sampled_from([1, 7]),
)


def _grid(rows, cols, bw, adc_bits, m_mux, tech_nm):
    return designs.macro_grid(rows=rows, cols=cols, bw=bw,
                              adc_bits=adc_bits, m_mux=m_mux,
                              tech_nm=tech_nm)


def _layer(k, c, ox, oy, name="r-layer"):
    return workloads.Layer(name, "conv2d",
                           dict(B=1, K=k, C=c, OX=ox, OY=oy, FX=3, FY=3))


def _price_both(shape_layers, grid, objective, scheds, depth=2,
                survivors=None, alpha=None):
    """Price the same shapes through the full-grid reference and the
    sweep's route with ``depth`` buckets in flight; return both
    per-shape result lists."""
    per_bit, buffer_bytes, dram = dse._mem_pricing(grid, None)
    sch = normalize(scheds)
    dse.cache_clear()
    host = dse._price_shapes_reference(shape_layers, grid, objective,
                                       alpha, per_bit, buffer_bytes, dram,
                                       sch, survivors=survivors)
    dse.cache_clear()
    saved, dse._PIPELINE_DEPTH = dse._PIPELINE_DEPTH, depth
    try:
        red = dse._price_shapes(shape_layers, grid, objective, alpha,
                                per_bit, buffer_bytes, dram, sch,
                                survivors=survivors)
    finally:
        dse._PIPELINE_DEPTH = saved
    return host, red


def _sweep_both(monkeypatch, nets, grid, **kw):
    """``dse.sweep_networks`` through the full-grid reference (put in
    ``_price_shapes``' place) and through the route."""
    dse.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(dse, "_price_shapes", dse._price_shapes_reference)
        ref = dse.sweep_networks(nets, grid, **kw)
    dse.cache_clear()
    return ref, dse.sweep_networks(nets, grid, **kw)


def _assert_sweeps_bitwise(ref, out):
    assert [r.network for r in ref] == [r.network for r in out]
    for a, b in zip(ref, out):
        assert np.array_equal(a.energy_fj, b.energy_fj)
        assert np.array_equal(a.cycles, b.cycles)
        assert len(a._shapes) == len(b._shapes)
        for sa, sb in zip(a._shapes, b._shapes):
            assert np.array_equal(sa[2], sb[2])    # winners per design


@pytest.fixture
def legality_seen(monkeypatch):
    """Record the legality pair of every reduced dispatch."""
    seen = []
    real = energy.reduce_objective_grid

    def spy(**kw):
        # any other (D, lanes) bool argument would be a per-design mask
        assert not [k for k, v in kw.items()
                    if k != "legal_rows" and np.ndim(v) == 2
                    and np.asarray(v).dtype == bool]
        seen.append((kw["legal_rows"], kw["design_class"]))
        return real(**kw)

    monkeypatch.setattr(energy, "reduce_objective_grid", spy)
    return seen


def _assert_slots_bitwise(host, red):
    assert len(host) == len(red)
    for (hg, hb, ht, hc), (rg, rb, rt, rc) in zip(host, red):
        assert len(hg) == len(rg)
        assert np.array_equal(hb, rb)          # winners incl. tie-breaks
        assert np.array_equal(ht, rt)          # totals, bitwise
        assert rt.dtype == np.float64
        assert np.array_equal(hc, rc)          # cycles, exact int64
        assert rc.dtype == np.int64


# --------------------------------------------------------------------------- #
# property: random (grid, layers, schedules, objective) parity                 #
# --------------------------------------------------------------------------- #
@given(**{**GRID_STRAT, **LAYER_STRAT,
          "objective": st.sampled_from(["energy", "latency", "edp"]),
          "scheds": st.sampled_from([("ws",), ("ws", "os")]),
          "depth": st.sampled_from([1, 2, 3])})
@settings(max_examples=10, deadline=None)
def test_reduced_matches_host_oracle(rows, cols, bw, adc_bits, m_mux,
                                     tech_nm, k, c, ox, oy, objective,
                                     scheds, depth):
    grid = _grid(rows, cols, bw, adc_bits, m_mux, tech_nm)
    layers = [_layer(k, c, ox, oy),
              _layer(max(1, k // 2), c, ox, oy, name="r-half")]
    host, red = _price_both(layers, grid, objective, scheds, depth=depth)
    _assert_slots_bitwise(host, red)


# --------------------------------------------------------------------------- #
# per-class legality through the reduced kernel, faults off and on             #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("objective", ["energy", "latency", "edp"])
@pytest.mark.parametrize("faults", [False, True], ids=["faults-off",
                                                       "faults-on"])
def test_class_legality_matches_host_oracle(legality_seen, faults,
                                            objective):
    """The reduced kernel gathers ``legal_rows[design_class]`` on the
    device; winners, totals and cycles stay bitwise the full-grid
    reference's.  Fault-free buckets carry one row per (d1, rows, n_macros) class;
    with a survivor mask every design is its own class (U = D)."""
    from repro.faults import survivor_mask
    grid = designs.macro_grid(rows=(64, 256), cols=(64, 512), bw=(2, 8),
                              adc_bits=(4, 8), m_mux=(1, 4), tech_nm=(28,),
                              n_macros=(1, 4))
    layers = [_layer(40, 24, 5, 7), _layer(96, 8, 1, 1, name="r-b"),
              _layer(12, 60, 16, 7, name="r-c")]
    survivors = (survivor_mask(FaultSpec(column_fail_rate=0.4,
                                         macro_fail_rate=0.4, seed=3),
                               grid) if faults else None)
    host, red = _price_both(layers, grid, objective, ("ws", "os"),
                            survivors=survivors)
    _assert_slots_bitwise(host, red)
    n_classes = len(set(zip(grid.d1, grid.rows, grid.n_macros)))
    assert n_classes < len(grid)
    assert legality_seen
    for legal_rows, design_class in legality_seen:
        assert len(legal_rows) == (len(grid) if faults else n_classes)
        assert design_class.shape == (len(grid),)
    if faults:
        clean, _ = _price_both(layers, grid, objective, ("ws", "os"))
        assert any(not np.array_equal(c[1], h[1])
                   for c, h in zip(clean, host)), \
            "fixture's survivor mask no longer moves a winner"


@pytest.mark.parametrize("objective", ["energy", "latency", "edp"])
@pytest.mark.parametrize("faults", [False, True], ids=["faults-off",
                                                       "faults-on"])
def test_packed_dispatch_hands_few_host_arrays(monkeypatch, faults,
                                               objective):
    """Argument packing: a sweep of at least three buckets puts the
    per-design block on the device once, each bucket's dispatch hands
    it at most four host arrays (``dse.h2d_arrays``, and the
    ``h2d_arrays`` attr of every ``dse.price_bucket`` span), and
    winners, totals and int64 cycles stay bitwise the full-grid
    reference's."""
    from repro import obs
    from repro.faults import survivor_mask
    grid = designs.macro_grid(rows=(64, 256), cols=(64, 512), bw=(2, 8),
                              adc_bits=(4, 8), m_mux=(1, 4), tech_nm=(28,),
                              n_macros=(1, 4))
    layers = [_layer(40, 24, 5, 7), _layer(96, 8, 1, 1, name="r-b"),
              _layer(12, 60, 16, 7, name="r-c")]
    survivors = (survivor_mask(FaultSpec(column_fail_rate=0.4,
                                         macro_fail_rate=0.4, seed=3),
                               grid) if faults else None)
    puts = []
    real_put = energy.put_design_block

    def put_spy(*a, **kw):
        puts.append(1)
        return real_put(*a, **kw)

    monkeypatch.setattr(energy, "put_design_block", put_spy)
    monkeypatch.setattr(dse, "_BUCKET_ELEMS", 1)      # a bucket per shape
    obs.set_trace_enabled(True)
    obs.drain_spans()
    try:
        host, red = _price_both(layers, grid, objective, ("ws", "os"),
                                survivors=survivors)
        h2d = obs.snapshot("dse.")["dse.h2d_arrays"]
        spans = [r["attrs"] for r in obs.drain_spans()
                 if r["name"] == "dse.price_bucket"
                 and r["attrs"].get("reduced")]
    finally:
        obs.set_trace_enabled(None)
    _assert_slots_bitwise(host, red)
    assert len(spans) >= 3
    assert len(puts) == 1
    per_bucket = [a["h2d_arrays"] for a in spans]
    assert per_bucket[0] == 2 + 2                 # the block, then a bucket
    assert all(n <= 4 for n in per_bucket)
    assert per_bucket[1:] == [2] * (len(spans) - 1)
    assert h2d == sum(per_bucket) == 2 + 2 * len(spans)


def test_reduce_refuses_a_loose_alpha():
    """The route prices with the design block's ``alpha`` and takes no
    other: a block built at ``alpha=0.3`` prices bitwise like the
    reference at 0.3 (and unlike the default), and
    ``mapping.reduce_network_grid`` has no ``alpha`` to pass beside
    it."""
    from repro.core.mapping import network_grid, reduce_network_grid
    grid = _grid((64, 256), (64,), (2, 8), (4, 8), (1, 4), (28,))
    layers = [_layer(40, 24, 5, 7), _layer(12, 60, 16, 7, name="r-c")]
    host, red = _price_both(layers, grid, "energy", ("ws", "os"),
                            alpha=0.3)
    _assert_slots_bitwise(host, red)
    default, _ = _price_both(layers, grid, "energy", ("ws", "os"))
    assert not np.array_equal(default[0][2], red[0][2])

    sch = normalize(("ws",))
    dse.cache_clear()
    (net,) = network_grid(layers[:1], grid, schedules=sch,
                          grids=[dse._grid_for(layers[0], grid, sch)])
    with pytest.raises(TypeError, match="alpha"):
        reduce_network_grid(net, objective="energy", design_block=None,
                            resident_bytes=None, buffer_bytes=0,
                            alpha=0.3)


def _grid_1620():
    """The 1620-design sweep grid (``benchmarks.design_sweep.make_grid``):
    15 (d1, rows, n_macros) legality classes."""
    return designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))


@pytest.mark.parametrize("faults", [False, True], ids=["faults-off",
                                                       "faults-on"])
def test_sweep_hands_class_legality_to_device(legality_seen, monkeypatch,
                                              faults):
    """Mechanism pin on the 1620-design grid: a fault-free sweep never
    expands per-design legality on the host, every bucket hands the
    device its 15 class rows, and ``dse.legal_bytes`` counts exactly
    U * Ctot per bucket (the ``dse.price_bucket`` span names U) plus
    the designs' int64 class row, put once per sweep.  With faults on
    every design is its own class, U = D."""
    from repro import obs
    from repro.core import mapping
    from repro.faults import FaultSpec
    grid = _grid_1620()
    if not faults:
        def expanded(self):
            raise AssertionError("per-design legality built on the host")
        monkeypatch.setattr(mapping.MappingGrid, "legal",
                            property(expanded))
        monkeypatch.setattr(mapping.NetworkGrid, "legal",
                            property(expanded))
    spec = FaultSpec(column_fail_rate=0.3, seed=1) if faults else None
    monkeypatch.setattr(dse, "_BUCKET_ELEMS", 1)      # a bucket per shape
    dse.cache_clear()
    obs.set_trace_enabled(True)
    obs.drain_spans()
    try:
        dse.sweep_networks([("dae", workloads.deep_autoencoder())], grid,
                           schedules=("ws", "os"), faults=spec)
        legal_bytes = obs.snapshot("dse.")["dse.legal_bytes"]
        spans = [r for r in obs.drain_spans()
                 if r["name"] == "dse.price_bucket"]
    finally:
        obs.set_trace_enabled(None)
    n_classes = len(grid) if faults else 15
    assert len(legality_seen) >= 2
    expect = 8 * len(grid)
    for legal_rows, design_class in legality_seen:
        assert legal_rows.shape[0] == n_classes
        assert design_class.shape == (len(grid),)
        assert design_class.dtype == np.int32
        expect += n_classes * legal_rows.shape[1]
    assert legal_bytes == expect
    assert [r["attrs"]["legal_rows"] for r in spans] == \
        [n_classes] * len(legality_seen)


# --------------------------------------------------------------------------- #
# tie-breaks: first minimum wins on both paths                                 #
# --------------------------------------------------------------------------- #
def test_first_min_tie_break_parity():
    """Latency columns carry massive lane ties (cycles ignore most
    mapping knobs); assert ties genuinely exist, then that the reduced
    argmin picks the same (first) lane as the reference."""
    from repro.core.mapping import evaluate_network_grid, network_grid
    grid = _grid((64, 256), (64,), (2,), (4, 8), (1, 4), (28,))
    layers = [_layer(48, 32, 5, 7, name="tie-layer")]
    sch = normalize(("ws", "os"))

    dse.cache_clear()
    grids = [dse._grid_for(l, grid, sch) for l in layers]
    (net,) = network_grid(layers, grid, schedules=sch, grids=grids)
    costs = evaluate_network_grid(net, grid)
    col = np.where(net.legal, costs.cycles, dse._SENTINEL_I64)
    n_at_min = (col == col.min(axis=1, keepdims=True)).sum(axis=1)
    assert (n_at_min > 1).any(), "fixture no longer produces lane ties"

    host, red = _price_both(layers, grid, "latency", ("ws", "os"))
    _assert_slots_bitwise(host, red)


# --------------------------------------------------------------------------- #
# poison pads: quantum-padding lanes stay masked behind finite sentinels       #
# --------------------------------------------------------------------------- #
def test_pad_lanes_masked_and_winners_legal():
    from repro.core.mapping import network_grid
    grid = _grid((64,), (64,), (2,), (4,), (1,), (28,))
    layers = [_layer(7, 5, 5, 1, name="pad-layer")]
    sch = normalize(("ws",))

    dse.cache_clear()
    grids = [dse._grid_for(l, grid, sch) for l in layers]
    (net,) = network_grid(layers, grid, schedules=sch, grids=grids)
    assert net.pad_lanes > 0, "fixture no longer pads the lane axis"

    host, red = _price_both(layers, grid, "energy", ("ws",))
    _assert_slots_bitwise(host, red)
    # every reduced winner must be a legal (non-pad, non-illegal) lane
    for row, (_, best_idx, _, _) in enumerate(red):
        seg = net.segment(row)
        lanes = np.arange(seg.start, seg.stop)[best_idx]
        assert net.legal[np.arange(net.n_designs), lanes].all()
    assert np.isfinite(red[0][2]).all()


# --------------------------------------------------------------------------- #
# end-to-end: sweep_networks totals through the public entry point             #
# --------------------------------------------------------------------------- #
def test_sweep_networks_end_to_end_parity(monkeypatch):
    grid = _grid((64, 256), (64,), (2, 8), (4, 8), (1, 4), (28,))
    nets = [("resnet8", workloads.resnet8()),
            ("ae", workloads.deep_autoencoder())]
    ref, out = _sweep_both(monkeypatch, nets, grid,
                           schedules=("ws", "os"))
    _assert_sweeps_bitwise(ref, out)


def test_reduced_transfer_accounting(monkeypatch):
    """The route must ship >= 5x less than the full-grid reference (the
    acceptance floor; real grids are orders of magnitude beyond it)."""
    from repro import obs
    grid = _grid((64, 256), (64,), (2, 8), (4, 8), (1, 4), (28,))
    nets = [("resnet8", workloads.resnet8())]
    dse.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(dse, "_price_shapes", dse._price_shapes_reference)
        dse.sweep_networks(nets, grid)
    host_bytes = obs.snapshot("dse.")["dse.transfer_bytes"]
    dse.cache_clear()
    dse.sweep_networks(nets, grid)
    red_bytes = obs.snapshot("dse.")["dse.transfer_bytes"]
    assert host_bytes >= 5 * red_bytes


# --------------------------------------------------------------------------- #
# the route against the reference on a wide grid, at every pipeline depth     #
# --------------------------------------------------------------------------- #
def _wide_grid():
    """Multi-macro, mux-16 and 1024-row designs beside small ones."""
    return designs.macro_grid(
        rows=(64, 256, 1024), cols=(128, 512), adc_bits=(4, 8),
        dac_bits=(1, 2), m_mux=(1, 16), tech_nm=(22,), vdd=(0.8,),
        n_macros=(1, 2, 4))


@pytest.mark.parametrize("objective", ["energy", "latency", "edp"])
@pytest.mark.parametrize("faults", [False, True], ids=["faults-off",
                                                       "faults-on"])
def test_route_matches_reference_on_wide_grid(monkeypatch, faults,
                                              objective):
    """Two networks over the wide grid, ws+os: the route's totals,
    cycles and winners equal the reference's bit for bit, with survivor
    masks off and on (and the mask really moves a total)."""
    grid = _wide_grid()
    nets = [("dae", workloads.deep_autoencoder()),
            ("ds_cnn", workloads.ds_cnn())]
    spec = (FaultSpec(column_fail_rate=0.3, macro_fail_rate=0.3, seed=5)
            if faults else None)
    ref, out = _sweep_both(monkeypatch, nets, grid, objective=objective,
                           schedules=("ws", "os"), faults=spec)
    _assert_sweeps_bitwise(ref, out)
    if faults:
        dse.cache_clear()
        clean = dse.sweep_networks(nets, grid, objective=objective,
                                   schedules=("ws", "os"))
        assert any(not np.array_equal(c.energy_fj, o.energy_fj)
                   for c, o in zip(clean, out)), \
            "fixture's survivor mask no longer moves a total"


@pytest.mark.parametrize("objective", ["energy", "latency", "edp"])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipeline_depth_leaves_winners(monkeypatch, depth, objective):
    """A bucket per shape, so several buckets are in flight: at depth
    1, 2 and 3 the route's winners, totals and cycles equal the
    reference's, and the ``dse.pipeline.*`` telemetry names the depth
    and the buckets."""
    from repro import obs
    monkeypatch.setattr(dse, "_BUCKET_ELEMS", 1)
    grid = _grid((64, 256), (64, 512), (2, 8), (4, 8), (1, 4), (28,))
    layers = [_layer(40, 24, 5, 7), _layer(96, 8, 1, 1, name="r-b"),
              _layer(12, 60, 16, 7, name="r-c"),
              _layer(64, 32, 5, 1, name="r-d")]
    host, red = _price_both(layers, grid, objective, ("ws", "os"),
                            depth=depth)
    _assert_slots_bitwise(host, red)
    snap = obs.snapshot("dse.pipeline.")
    assert snap["dse.pipeline.depth"] == depth
    assert snap["dse.pipeline.buckets"] == len(layers)


@pytest.mark.parametrize("var,value", [("REPRO_SWEEP_PIPELINE", "0"),
                                       ("REPRO_SWEEP_SHARDS", "4")])
def test_sweep_ignores_retired_switches(monkeypatch, var, value):
    """The sweep reads no environment switch: with either retired
    variable set it returns the same winners, ships the same bytes (the
    winners alone: three 8-byte values per shape and design) and
    dispatches the same kernels as without it."""
    from repro import obs
    grid = _grid((64, 256), (64,), (2, 8), (4, 8), (1, 4), (28,))
    nets = [("resnet8", workloads.resnet8())]

    def sweep():
        dse.cache_clear()
        energy.grid_kernel_reset()
        res = dse.sweep_networks(nets, grid, schedules=("ws", "os"))
        return (res, obs.snapshot("dse.")["dse.transfer_bytes"],
                energy.grid_kernel_info())

    monkeypatch.setenv(var, value)
    res, nbytes, info = sweep()
    monkeypatch.delenv(var)
    base, base_bytes, base_info = sweep()
    _assert_sweeps_bitwise(base, res)
    assert nbytes == base_bytes == 3 * 8 * base[0].n_shapes * len(grid)
    assert info == base_info


def test_resident_bytes_memo():
    a = _layer(8, 8, 5, 1, name="m-a")
    b = _layer(8, 8, 5, 1, name="m-b")          # same shape key
    dse.cache_clear()
    va = dse._resident_bytes_cached(a)
    assert va == dse._layer_resident_bytes(a)
    assert len(dse._RESIDENT_CACHE) == 1
    assert dse._resident_bytes_cached(b) == va  # shared slot, no growth
    assert len(dse._RESIDENT_CACHE) == 1
    dse.cache_clear()
    assert len(dse._RESIDENT_CACHE) == 0
