"""Tracing is inert by contract: a fused sweep with tracing enabled is
bitwise identical to the same sweep with tracing disabled, and the
legacy stats accessors keep their historical return shapes (they are
now views over the metrics registry)."""

import numpy as np
import pytest

from repro import obs
from repro.core import designs, dse, energy, workloads
from repro.core.compilecache import compilation_cache_info


@pytest.fixture
def restore_tracing():
    yield
    obs.set_trace_enabled(None)
    obs.drain_spans()


def _grid():
    return designs.macro_grid(rows=(64, 256), cols=(256,),
                              adc_bits=(4, 6), dac_bits=(2,),
                              m_mux=(1, 16), tech_nm=(22,))


def _nets():
    layers = [workloads.dense(f"l{i}", 1, 24 + 8 * i, 8)
              for i in range(3)]
    return [("net_a", layers[:2]), ("net_b", layers[1:])]


def test_sweep_bitwise_identical_tracing_on_off(restore_tracing):
    grid = _grid()
    nets = _nets()

    obs.set_trace_enabled(False)
    dse.cache_clear()
    off = dse.sweep_networks(nets, grid, schedules=("ws", "os"))

    obs.set_trace_enabled(True)
    dse.cache_clear()
    on = dse.sweep_networks(nets, grid, schedules=("ws", "os"))
    assert len(obs.iter_spans()) > 0        # tracing really was on

    for a, b in zip(off, on):
        assert a.network == b.network
        np.testing.assert_array_equal(a.energy_fj, b.energy_fj)
        np.testing.assert_array_equal(a.cycles, b.cycles)
        assert a.layer_names == b.layer_names
        assert a.network_result(0) == b.network_result(0)


def test_disabled_sweep_records_no_spans(restore_tracing):
    obs.set_trace_enabled(False)
    obs.drain_spans()
    dse.cache_clear()
    dse.sweep_networks(_nets(), _grid())
    assert obs.iter_spans() == []


def test_cache_info_keys_unchanged():
    dse.cache_clear()
    info = dse.cache_info()
    assert set(info) == {"size", "hits", "misses", "evictions",
                         "lattice_size", "lattice_evictions",
                         "lattice_slots", "lattice_layers",
                         "padding_waste"}
    assert info["evictions"] == 0           # cache_clear resets counters
    assert info["hits"] == 0 and info["misses"] == 0


def test_grid_kernel_info_keys_unchanged():
    energy.grid_kernel_reset()
    info = energy.grid_kernel_info()
    assert info == {"calls": 0, "distinct_shapes": 0}
    dse.cache_clear()
    dse.sweep_networks(_nets(), _grid())
    info = energy.grid_kernel_info()
    assert info["calls"] >= 1
    assert info["distinct_shapes"] >= 1
    assert set(info) == {"calls", "distinct_shapes"}


def test_compilation_cache_info_keys_unchanged():
    info = compilation_cache_info()
    assert set(info) == {"dir", "entries", "bytes"}
    # the registry gauges mirror the returned figures
    snap = obs.snapshot("compilecache.")
    assert snap["compilecache.entries"] == info["entries"]
    assert snap["compilecache.bytes"] == info["bytes"]


def test_counters_track_sweep_work(restore_tracing):
    obs.set_trace_enabled(False)
    dse.cache_clear()
    energy.grid_kernel_reset()
    obs.reset("mapping.")
    dse.sweep_networks(_nets(), _grid())
    snap = obs.snapshot()
    # one per distinct lattice_key; the 3 shapes differ in C, so one
    # per distinct shape too
    assert snap["mapping.lattice.builds"] >= 3      # one per distinct shape
    assert snap["dse.lattice.slots"] >= 3
    assert snap["energy.kernel.calls"] >= 1
    # a bucket dispatch landed in exactly one of the two timers
    n_timed = (snap["dse.bucket.first_call"]["count"]
               + snap["dse.bucket.warm"]["count"])
    assert n_timed == snap["energy.kernel.calls"]
