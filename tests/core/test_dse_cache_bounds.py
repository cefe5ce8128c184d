"""Bounded memo caches in ``core.dse`` (satellite bugfix regression).

Both module-level memos — the layer-result cache and the per-shape
union-lattice cache — previously grew without bound across sweeps over
differing grids.  They are now LRU-bounded: entry counts stay at their
caps across arbitrarily long sweep sequences, hits refresh recency,
``cache_info()`` reports sizes and eviction counts, and
``cache_clear()`` evicts the lattice memo too (it used to only clear
the layer-result side before PR 3 made it shared)."""

import numpy as np
import pytest

from repro.core import designs, dse, workloads
from repro.core.memory import MemoryModel


@pytest.fixture
def small_caps(monkeypatch):
    monkeypatch.setattr(dse, "_CACHE_MAX", 6)
    monkeypatch.setattr(dse, "_LATTICE_CACHE_MAX", 3)
    dse.cache_clear()
    yield
    dse.cache_clear()


def _grid() -> designs.MacroBatch:
    return designs.macro_grid(rows=(64,), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1,), tech_nm=(22,))


def test_layer_result_cache_bounded(small_caps):
    grid = _grid()
    macro = grid.macro_at(0)
    mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
    layers = [workloads.dense(f"l{i}", 1, 16 + i, 8) for i in range(20)]
    for layer in layers:
        dse.best_mapping(layer, macro, mem)
    info = dse.cache_info()
    assert info["size"] <= 6
    assert info["evictions"] >= 14
    assert len(dse._CACHE) <= 6


def test_layer_result_cache_lru_recency(small_caps):
    """A re-hit entry survives evictions that claim colder ones."""
    grid = _grid()
    macro = grid.macro_at(0)
    mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
    hot = workloads.dense("hot", 1, 100, 8)
    dse.best_mapping(hot, macro, mem)
    for i in range(5):                           # fill to the cap of 6
        dse.best_mapping(workloads.dense(f"c{i}", 1, 16 + i, 8), macro, mem)
    dse.best_mapping(hot, macro, mem)            # refresh recency
    hits_before = dse.cache_info()["hits"]
    for i in range(3):                           # evict the coldest 3
        dse.best_mapping(workloads.dense(f"n{i}", 1, 40 + i, 8), macro, mem)
    dse.best_mapping(hot, macro, mem)
    assert dse.cache_info()["hits"] == hits_before + 1   # still cached


def test_lattice_cache_bounded_across_long_sweep_sequence(small_caps):
    """Regression pin for the unbounded-growth bug: a long sequence of
    sweeps over many distinct shapes holds at most the cap's worth of
    lattice entries, with the overflow reported as evictions.  Each
    layer has its own ``C``, so each is a distinct ``lattice_key`` and
    a lattice entry of its own."""
    grid = _grid()
    for i in range(12):
        layer = workloads.dense(f"s{i}", 1, 24 + i, 8)
        dse.sweep(f"net{i}", [layer], grid)
    info = dse.cache_info()
    assert len(dse._LATTICE_CACHE) <= 3
    assert info["lattice_size"] <= 3
    assert info["lattice_evictions"] >= 9


def test_cache_clear_evicts_lattice_memo(small_caps):
    grid = _grid()
    dse.sweep("dae", workloads.deep_autoencoder(), grid)
    assert len(dse._LATTICE_CACHE) > 0
    dse.cache_clear()
    assert len(dse._LATTICE_CACHE) == 0
    info = dse.cache_info()
    assert info["size"] == 0
    assert info["lattice_size"] == 0
    assert info["evictions"] == 0
    assert info["lattice_evictions"] == 0


def test_eviction_keeps_results_bitwise(small_caps):
    """Cache churn is invisible to results: sweeping the same network
    before and after heavy eviction pressure returns identical
    arrays."""
    grid = _grid()
    layers = workloads.deep_autoencoder()
    ref = dse.sweep("dae", layers, grid)
    for i in range(8):                           # churn the lattice memo
        dse.sweep(f"x{i}", [workloads.dense(f"x{i}", 1, 30 + i, 8)], grid)
    res = dse.sweep("dae", layers, grid)
    assert np.array_equal(ref.energy_fj, res.energy_fj)
    assert np.array_equal(ref.cycles, res.cycles)
