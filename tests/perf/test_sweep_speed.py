"""Micro-benchmark guards: the jitted design-grid sweep must beat a
Python loop over the PR-1 per-design batch engine by >= 10x on a
>= 1000-point macro grid (ISSUE 2 acceptance), enabling the
dataflow axis (ws+os) must stay within 2x the single-dataflow wall
time (ISSUE 4 acceptance) — the schedule lanes ride the same fused
lattice instead of re-running the sweep per dataflow — and the
workload-fused multi-network sweep must beat the pre-fusion per-layer
loop by >= 5x cold (compiles included) while staying within 1.5x of
it warm (ISSUE 5 acceptance).  Same marker scheme as
``test_dse_speed.py``: wall-clock assertions are flaky on shared CI
runners, so CI only runs the sweeps for crash coverage and the ratios
are enforced locally, where a regression means an axis fell back to
per-point Python (or, for the fused sweep, to per-shape compiles).
"""

import os
import time

import numpy as np
import pytest

from repro.core import designs, dse, workloads
from repro.core.memory import MemoryModel


def _grid() -> designs.MacroBatch:
    g = designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))
    assert len(g) >= 1000
    return g


def test_grid_sweep_beats_batch_engine_loop():
    grid = _grid()
    layer = workloads.dense("probe", 64, 1024, 64)

    dse.sweep("probe", [layer], grid)          # warm the jit cache
    # best of 3: the sweep is ~20 ms, so a single trial flakes on a
    # scheduler hiccup when the whole suite loads the machine
    t_sweep = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res = dse.sweep("probe", [layer], grid)
        t_sweep = min(t_sweep, time.perf_counter() - t0)

    n_loop = len(grid) if not os.environ.get("CI") else 64
    t0 = time.perf_counter()
    loop = []
    for d in range(n_loop):
        macro = grid.macro_at(d)
        mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
        loop.append(dse.best_mapping_batched(layer, macro, mem))
    t_loop = (time.perf_counter() - t0) * (len(grid) / n_loop)

    # crash coverage everywhere: the two paths agree where both ran
    for d in range(min(8, n_loop)):
        assert float(res.energy_fj[d]) == loop[d].total_energy_fj

    speedup = t_loop / max(t_sweep, 1e-9)
    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (speedup={speedup:.1f}x)")
    assert speedup >= 10.0, (
        f"grid sweep only {speedup:.1f}x faster than the batch-engine loop "
        f"({t_sweep:.3f}s vs {t_loop:.3f}s for {len(grid)} designs)")


#: subprocess worker for the multi-network guard: a truly cold process
#: (no allocator/jit-cache contamination from the rest of the suite)
#: times one engine — ``fused`` = dse.sweep_networks (one jit compile
#: for all distinct shapes), ``loop`` = the replaced per-layer engine
#: (per-shape lattice + per-shape jit dispatch + argmin, exactly what
#: dse.sweep did before the workload axis fused) — cold then warm
#: (best of 3), and prints JSON.
_NETWORK_GUARD_WORKER = """
import json, time
import numpy as np
from repro.core import designs, dse, mapping, workloads
from repro.core.memory import sram_fj_per_bit_grid, traffic_energy_grid

mode = {mode!r}
grid = designs.macro_grid(
    rows=(64, 128, 256, 512, 1024), cols=(128, 256),
    adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
    tech_nm=(5, 22, 28), vdd=(0.7, 0.8))
assert len(grid) >= 1000
nets = [("deep_autoencoder", workloads.deep_autoencoder()),
        ("ds_cnn", workloads.ds_cnn()),
        ("mobilenet_v1_025", workloads.mobilenet_v1_025())]

def per_layer_loop():
    per_bit = sram_fj_per_bit_grid(grid.tech_nm, grid.vdd)
    sentinel = np.finfo(np.float64).max
    out = {{}}
    for name, layers in nets:
        for l in layers:
            if not l.imc_eligible:
                continue
            key = (name, tuple(sorted(l.dims.items())))
            if key in out:
                continue
            mg = mapping.candidate_grid(l, grid)
            costs = mapping.evaluate_grid(l, grid, mg)
            mem_fj = traffic_energy_grid(per_bit, costs, 0)
            mem_total = ((mem_fj["weights"] + mem_fj["inputs"])
                         + mem_fj["outputs"]) + mem_fj["psums"]
            total = costs.macro_energy.total_fj + mem_total
            col = np.where(mg.legal, total, sentinel)
            best = np.argmin(col, axis=1)
            out[key] = np.take_along_axis(
                total, best[:, None], axis=1)[:, 0]
    return out

run = (lambda: dse.sweep_networks(nets, grid)) if mode == "fused" \\
    else per_layer_loop
# jit-prime the backend so neither engine pays one-off jax runtime init
import repro.core.energy as energy
energy.tile_energy_grid(grid, n_inputs=np.ones(8, np.int64),
                        rows_used=np.ones(8, np.int64),
                        cols_used=np.ones(8, np.int64))
import jax; jax.clear_caches(); dse.cache_clear()
t0 = time.perf_counter(); res = run(); cold = time.perf_counter() - t0
warm = float("inf")
for _ in range(3):
    t0 = time.perf_counter(); run(); warm = min(warm, time.perf_counter() - t0)
totals = (sorted((r.network, float(r.energy_fj.sum())) for r in res)
          if mode == "fused" else None)
print(json.dumps({{"cold": cold, "warm": warm, "totals": totals}}))
"""


def _run_network_guard(mode: str) -> dict:
    import json
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent.parent
    env = {"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
           # pin the CPU backend (same rationale as the launch
           # subprocess tests: an unpinned jax probes for a TPU via the
           # GCP metadata server and hangs for minutes)
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           # this guard measures in-process compile amortization (one
           # fused compile vs one per lattice width), so the persistent
           # XLA cache must not pre-warm either subprocess — a warm
           # compile cache would erase exactly the gap under test
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.update({k: os.environ[k] for k in ("HOME", "TMPDIR")
                if k in os.environ})
    res = subprocess.run(
        [sys.executable, "-c", _NETWORK_GUARD_WORKER.format(mode=mode)],
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_fused_network_sweep_beats_per_layer_loop():
    """ISSUE 5 acceptance: pricing a multi-network tinyMLPerf set
    (29 distinct layer shapes) over a >= 1000-point grid through the
    workload-fused lane lattice — one jit compile instead of one per
    distinct lattice width — is >= 5x faster cold than the pre-fusion
    per-layer loop, and stays within 1.5x of it warm (the fused pass
    adds only bounded quantum-padding waste).  Each engine is measured
    in a fresh subprocess so "cold" really means a cold process, not
    whatever allocator/jit-cache state the suite left behind — best of
    two runs per engine, because the first process to compile after a
    long suite pays a one-off system transient (page-cache/allocator
    warmup) that the engine under test did not cause."""
    fused = min((_run_network_guard("fused") for _ in range(2)),
                key=lambda r: r["cold"])
    loop = min((_run_network_guard("loop") for _ in range(2)),
               key=lambda r: r["cold"])
    # crash coverage everywhere: the fused engine produced sane totals
    # (bitwise parity itself is pinned by tests/core/test_grid_parity.py)
    assert len(fused["totals"]) == 3
    assert all(t > 0 for _, t in fused["totals"])

    speedup = loop["cold"] / max(fused["cold"], 1e-9)
    ratio = fused["warm"] / max(loop["warm"], 1e-9)
    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (cold speedup="
                    f"{speedup:.1f}x, warm ratio={ratio:.2f}x)")
    assert speedup >= 5.0, (
        f"fused network sweep only {speedup:.1f}x faster cold than the "
        f"per-layer loop ({fused['cold']:.3f}s vs {loop['cold']:.3f}s)")
    assert ratio <= 1.5, (
        f"fused network sweep {ratio:.2f}x the per-layer loop warm "
        f"({fused['warm']:.3f}s vs {loop['warm']:.3f}s)")


def test_fused_single_shape_overhead_bounded():
    """A network whose layers all dedup to one shape prices at
    single-layer latency: the workload plumbing (slot dedup, lane
    padding, segment argmin) must not tax the degenerate case."""
    grid = _grid()
    layer = workloads.dense("probe", 64, 1024, 64)
    many = [workloads.dense(f"probe{i}", 64, 1024, 64) for i in range(12)]
    res1 = dse.sweep("one", [layer], grid)
    res12 = dse.sweep("many", many, grid)
    assert res12.n_shapes == 1
    assert np.allclose(res12.energy_fj, 12 * res1.energy_fj)

    def best3(fn):
        t = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    t_one = best3(lambda: dse.sweep("one", [layer], grid))
    t_many = best3(lambda: dse.sweep("many", many, grid))
    ratio = t_many / max(t_one, 1e-9)
    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (ratio={ratio:.2f}x)")
    assert ratio <= 1.5, (
        f"12-layer single-shape sweep {ratio:.2f}x the single-layer "
        f"latency ({t_many:.3f}s vs {t_one:.3f}s)")


def test_dataflow_axis_within_2x_single_dataflow():
    """ISSUE 4 acceptance: the dual-dataflow sweep (ws+os) over a
    >= 1000-point grid stays within 2x the single-dataflow wall time —
    the candidate axis doubles but the union-lattice construction and
    the jit dispatch are shared, so the amortized ratio sits well
    under 2 (typically ~1.7x)."""
    grid = _grid()
    layer = workloads.dense("probe", 64, 1024, 64)

    # warm both jit cache entries
    res1 = dse.sweep("probe", [layer], grid)
    res2 = dse.sweep("probe", [layer], grid, schedules=("ws", "os"))
    # crash coverage everywhere: the superset lattice never prices worse
    assert (res2.energy_fj <= res1.energy_fj).all()

    def best3(fn):
        t = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    t_single = best3(lambda: dse.sweep("probe", [layer], grid))
    t_dual = best3(
        lambda: dse.sweep("probe", [layer], grid, schedules=("ws", "os")))
    ratio = t_dual / max(t_single, 1e-9)
    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (ratio={ratio:.2f}x)")
    assert ratio <= 2.0, (
        f"dual-dataflow sweep {ratio:.2f}x slower than single "
        f"({t_dual:.3f}s vs {t_single:.3f}s for {len(grid)} designs)")
