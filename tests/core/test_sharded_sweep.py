"""Lane-sharded fused sweep: bitwise equivalence and fallbacks.

The shard_map execution path (``energy._sharded_grid_kernel``)
partitions the padded candidate-lane axis of the fused grid kernel
over a 1-D device mesh.  The kernel is purely elementwise, so each
device computes its lane slab with the identical float ops — the
gathered result must be **bitwise** equal to the single-device jit.
The multi-device case runs in a subprocess with
``--xla_force_host_platform_device_count`` (the suite's own process
pins a single CPU device); in-process tests cover the fallbacks: shard
counts above the device count, lane axes that don't divide, and the
shard-aware pad quantum.
"""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import designs, dse, energy, workloads
from repro.core.mapping import PAD_QUANTUM

#: subprocess worker: 4 forced host devices; sweeps the same networks
#: unsharded then sharded (the reduced engine), and through the depth-0
#: host oracle, with faults off and on, and prints exact comparison bits
#: and the legality class counts the sharded dispatches saw as JSON.
_SHARD_WORKER = """
import json
import numpy as np
from repro.core import designs, dse, energy, workloads
from repro.faults import FaultSpec

grid = designs.macro_grid(
    rows=(64, 256, 1024), cols=(128, 512), adc_bits=(4, 8), dac_bits=(1, 2),
    m_mux=(1, 16), tech_nm=(22,), vdd=(0.8,), n_macros=(1, 2, 4))
nets = [("dae", workloads.deep_autoencoder()),
        ("ds_cnn", workloads.ds_cnn())]
faults = FaultSpec(column_fail_rate=0.3, macro_fail_rate=0.3, seed=5)

def sweep(spec=None):
    dse.cache_clear()
    return dse.sweep_networks(nets, grid, schedules=("ws", "os"),
                              faults=spec)

def same(ra, rb):
    return all(a.network == b.network
               and np.array_equal(a.energy_fj, b.energy_fj)
               and np.array_equal(a.cycles, b.cycles)
               for a, b in zip(ra, rb))

energy.set_lane_shards(1)
ref = sweep()
dse.set_sweep_pipeline(0)
host, host_f = sweep(), sweep(faults)
dse.set_sweep_pipeline(None)

seen = []
real = energy.reduce_objective_grid
def spy(designs, **kw):
    seen.append(len(kw["legal_rows"]))
    return real(designs, **kw)
energy.reduce_objective_grid = spy

energy.set_lane_shards(4)
sharded = sweep()
rows_off = sorted(set(seen))
seen.clear()
sharded_f = sweep(faults)
info = energy.grid_kernel_info()

import jax
print(json.dumps({"devices": jax.device_count(),
                  "bitwise": same(ref, sharded),
                  "host_bitwise": same(host, sharded),
                  "faults_bitwise": same(host_f, sharded_f),
                  "faults_differ": not same(host, host_f),
                  "legal_rows_off": rows_off,
                  "legal_rows_on": sorted(set(seen)),
                  "n_classes": len(set(zip(grid.d1, grid.rows,
                                           grid.n_macros))),
                  "n_designs": len(grid),
                  "sharded_calls": info["sharded_calls"]}))
"""


def _run_worker(extra_env: dict) -> dict:
    repo = Path(__file__).resolve().parent.parent.parent
    env = {"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
           # pin the CPU backend (an unpinned jax probes for a TPU via
           # the GCP metadata server and hangs for minutes)
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    env.update({k: os.environ[k] for k in ("HOME", "TMPDIR")
                if k in os.environ})
    env.update(extra_env)
    res = subprocess.run([sys.executable, "-c", _SHARD_WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_sweep_bitwise_equals_unsharded():
    """The shard_map lane path over a 4-device host mesh returns bitwise
    the single-device sweep and the depth-0 host oracle — totals and
    cycles of every network, every design — with the per-class legality
    pair (fault-free: one row per legality class) and with faults on
    (one class per design)."""
    out = _run_worker(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out["devices"] == 4
    assert out["sharded_calls"] > 0            # the shard path really ran
    assert out["bitwise"] is True
    assert out["host_bitwise"] is True
    assert out["faults_bitwise"] is True
    assert out["faults_differ"] is True        # the mask really bites
    assert out["n_classes"] < out["n_designs"]
    assert out["legal_rows_off"] == [out["n_classes"]]
    assert out["legal_rows_on"] == [out["n_designs"]]


@pytest.fixture
def _restore_shards():
    yield
    energy.set_lane_shards(None)


def test_shards_above_device_count_fall_back(_restore_shards):
    """Requesting more shards than jax devices must not crash or change
    results: the dispatch silently uses the single-device jit."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,))
    layer = workloads.dense("probe", 4, 96, 40)
    energy.set_lane_shards(1)
    ref = dse.sweep("probe", [layer], grid)
    import jax

    energy.set_lane_shards(jax.device_count() + 3)
    dse.cache_clear()
    energy.grid_kernel_reset()
    res = dse.sweep("probe", [layer], grid)
    assert energy.grid_kernel_info()["sharded_calls"] == 0
    assert np.array_equal(ref.energy_fj, res.energy_fj)
    assert np.array_equal(ref.cycles, res.cycles)


def test_shard_aware_pad_quantum(_restore_shards):
    """With shards > 1 the fused buckets pad to ``lcm(PAD_QUANTUM,
    shards)`` lanes, so every bucket divides over the mesh — and the
    extra benign pad lanes change nothing (results stay bitwise)."""
    grid = designs.macro_grid(rows=(64, 256), cols=(256,), adc_bits=(5,),
                              dac_bits=(2,), m_mux=(1, 16), tech_nm=(22,))
    layers = workloads.deep_autoencoder()
    energy.set_lane_shards(1)
    ref = dse.sweep("dae", layers, grid)

    energy.set_lane_shards(3)                   # lcm(64, 3) = 192
    dse.cache_clear()
    energy.grid_kernel_reset()
    res = dse.sweep("dae", layers, grid)
    shapes = energy._GRID_KERNEL_SHAPES
    assert all(shape[0][-1] % math.lcm(PAD_QUANTUM, 3) == 0
               for shape in shapes)
    assert np.array_equal(ref.energy_fj, res.energy_fj)
    assert np.array_equal(ref.cycles, res.cycles)


def test_lane_shards_env_resolution(_restore_shards, monkeypatch):
    """``REPRO_SWEEP_SHARDS`` resolution: integers clamp to the device
    count, ``auto`` takes every device, garbage falls back to 1."""
    import jax

    avail = jax.device_count()
    for spec, want in (("auto", avail), ("1", 1),
                       (str(avail + 99), avail), ("bogus", 1)):
        monkeypatch.setenv("REPRO_SWEEP_SHARDS", spec)
        energy.set_lane_shards(None)            # force re-read
        assert energy.lane_shards() == want, spec
