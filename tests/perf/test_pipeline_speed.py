"""Perf guard for the reduced + pipelined sweep engine (ISSUE 9).

The ``REPRO_SWEEP_PIPELINE`` path must beat the retained full-grid
host oracle by >= 1.3x on a cold multi-network ``sweep_networks`` over
a >= 1000-design grid — the win comes from (a) shipping (S, D) winners
instead of nine (D, Ctot) float64 grids per bucket and (b) overlapping
lattice/NetworkGrid construction with device execution on the builder
thread.  Measured transfer must drop >= 5x (that part is deterministic
accounting, so it is enforced on CI too; the wall-clock ratio follows
the suite's usual local-only marker scheme — see ``test_dse_speed.py``).
"""

import os

import pytest

#: subprocess worker: one cold process per engine mode so neither run
#: inherits jit caches, allocator state, or device buffers from the
#: other (or from the suite).  Prints JSON: cold wall, one warm wall,
#: measured dse.transfer_bytes of the cold pass, pipeline telemetry,
#: and per-network totals for cross-mode crash coverage.
_PIPELINE_GUARD_WORKER = """
import json, time
import numpy as np
from repro import obs
from repro.core import designs, dse, workloads

grid = designs.macro_grid(
    rows=(64, 128, 256, 512, 1024), cols=(128, 256),
    adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
    tech_nm=(5, 22, 28), vdd=(0.7, 0.8))
assert len(grid) >= 1000
# three networks of batch-varying dense layers: every shape shares one
# lattice width, so the fused lane axis packs them into ~9 full
# multi-segment buckets — the regime where avoided grid transfers and
# the fused reduction dominate over one-off compiles
nets = [(f"mlp{j}",
         [workloads.dense(f"fc{j}_{b}", b, 1024, 64)
          for b in range(1 + 134 * j, 1 + 134 * (j + 1))])
        for j in range(3)]

# jit-prime the backend so neither mode pays one-off jax runtime init
import repro.core.energy as energy
energy.tile_energy_grid(grid, n_inputs=np.ones(8, np.int64),
                        rows_used=np.ones(8, np.int64),
                        cols_used=np.ones(8, np.int64))
import jax; jax.clear_caches(); dse.cache_clear()

t0 = time.perf_counter()
res = dse.sweep_networks(nets, grid)
cold = time.perf_counter() - t0
snap = obs.snapshot("dse.")
t0 = time.perf_counter()
dse.sweep_networks(nets, grid)
warm = time.perf_counter() - t0
print(json.dumps({
    "cold": cold, "warm": warm,
    "transfer_bytes": snap["dse.transfer_bytes"],
    "pipeline_depth": snap.get("dse.pipeline.depth", 0),
    "pipeline_occupancy": snap.get("dse.pipeline.occupancy", 0.0),
    "totals": sorted((r.network, float(r.energy_fj.sum()),
                      int(r.cycles.sum())) for r in res)}))
"""


def _run_pipeline_guard(pipeline: str) -> dict:
    import json
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent.parent
    env = {"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
           # pin the CPU backend (an unpinned jax probes for a TPU via
           # the GCP metadata server and hangs for minutes)
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           # cold must mean a cold compile in both modes: a warm
           # persistent XLA cache would shrink exactly the compile wall
           # the pipeline overlaps with builder work
           "JAX_ENABLE_COMPILATION_CACHE": "false",
           "REPRO_SWEEP_PIPELINE": pipeline}
    env.update({k: os.environ[k] for k in ("HOME", "TMPDIR")
                if k in os.environ})
    res = subprocess.run(
        [sys.executable, "-c", _PIPELINE_GUARD_WORKER],
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_pipelined_sweep_beats_host_oracle():
    """ISSUE 9 acceptance: reduced+pipelined cold ``sweep_networks``
    >= 1.3x faster than the pipeline-off host oracle on a three-network
    dense stack over a >= 1000-design grid, with measured device→host
    traffic down >= 5x.  Best of two runs per mode (the first
    subprocess after a long suite pays a one-off system transient
    neither engine caused)."""
    on = min((_run_pipeline_guard("2") for _ in range(2)),
             key=lambda r: r["cold"])
    off = min((_run_pipeline_guard("0") for _ in range(2)),
              key=lambda r: r["cold"])

    # crash + parity coverage everywhere: both modes priced all three
    # networks to identical totals (bitwise parity proper is pinned by
    # tests/core/test_reduced_sweep.py)
    assert on["totals"] == off["totals"]
    assert len(on["totals"]) == 3

    # deterministic accounting — enforced on CI too
    assert on["pipeline_depth"] == 2
    assert 0.0 < on["pipeline_occupancy"] <= 1.0
    assert off["transfer_bytes"] >= 5 * on["transfer_bytes"], (
        f"reduced path shipped {on['transfer_bytes']} B vs host "
        f"{off['transfer_bytes']} B — less than the 5x floor")

    speedup = off["cold"] / max(on["cold"], 1e-9)
    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (cold speedup="
                    f"{speedup:.2f}x, transfer {off['transfer_bytes']}"
                    f" -> {on['transfer_bytes']} B)")
    assert speedup >= 1.3, (
        f"pipelined sweep only {speedup:.2f}x faster cold than the host "
        f"oracle ({on['cold']:.3f}s vs {off['cold']:.3f}s)")
