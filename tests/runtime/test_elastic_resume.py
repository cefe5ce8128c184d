"""End-to-end elastic resize-and-restore: a device "dies", the mesh is
replanned via ``best_mesh_shape``, and ``resume_on_new_mesh`` restores
the checkpoint with every leaf device_put onto the *new* sharding —
values intact, placement on the surviving devices only.

The multi-device leg runs in a subprocess with
``--xla_force_host_platform_device_count=4`` (the suite's own process
pins a single CPU device — the
``tests/runtime/test_compressed_psum_subprocess.py`` trick); in-process
tests cover the pure planning math.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.runtime.elastic import best_mesh_shape, plan_resize

#: worker: save a smoke LM sharded over a 4-device (1, 4) mesh, drop to
#: 2 devices, replan, resume — report placement + value equality bits.
_RESUME_WORKER = """
import json
import numpy as np
import jax
from repro import configs, obs
from repro.models.common import Dist
from repro.models.lm import LM
from repro.runtime.checkpoint import Checkpointer, _flatten
from repro.runtime.elastic import (best_mesh_shape,
                                   make_mesh_from_devices,
                                   resume_on_new_mesh)

cfg = configs.get_smoke("qwen1.5-0.5b")
mesh4 = make_mesh_from_devices(jax.devices(), model_axis=4)
lm4 = LM(cfg, Dist(mesh=mesh4))
params4 = lm4.init(jax.random.PRNGKey(0))
ck = Checkpointer(r"%(ckdir)s", async_save=False)
ck.save(3, params4)
ref = {k: np.asarray(jax.device_get(v))
       for k, v in _flatten(params4).items()}

# two devices die; 2 survivors cannot hold the min TP axis of 4, so
# the replan degrades to a pure data-parallel (2, 1) mesh
survivors = 2
planned = best_mesh_shape(survivors, model_axis=4)
mesh2, lm2, step, params2 = resume_on_new_mesh(
    ck, lambda dist: LM(cfg, dist), survivors, model_axis=4)

alive = set(jax.devices()[:survivors])
flat2 = _flatten(params2)
on_new = all(set(v.sharding.device_set) <= alive for v in flat2.values())
values_equal = all(
    np.array_equal(ref[k], np.asarray(jax.device_get(v)))
    for k, v in flat2.items()) and set(ref) == set(flat2)
spans = [s["name"] for s in obs.iter_spans()]

print(json.dumps({
    "devices": jax.device_count(),
    "step": int(step),
    "planned": list(planned),
    "mesh_shape": list(mesh2.devices.shape),
    "old_mesh_shape": list(mesh4.devices.shape),
    "on_new_mesh": on_new,
    "values_equal": values_equal,
    "n_leaves": len(flat2),
    "resume_span": "runtime.elastic.resume" in spans,
}))
"""


def _run_worker(ckdir: str) -> dict:
    repo = Path(__file__).resolve().parent.parent.parent
    env = {"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "REPRO_TRACE": "1",
           # pin the CPU backend (an unpinned jax probes for a TPU via
           # the GCP metadata server and hangs for minutes)
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    env.update({k: os.environ[k] for k in ("HOME", "TMPDIR")
                if k in os.environ})
    res = subprocess.run(
        [sys.executable, "-c", _RESUME_WORKER % {"ckdir": ckdir}],
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_resume_on_new_mesh_after_device_loss(tmp_path):
    out = _run_worker(str(tmp_path / "ckpt"))
    assert out["devices"] == 4
    assert out["step"] == 3
    assert out["old_mesh_shape"] == [1, 4]
    assert out["planned"] == [2, 1]
    assert out["mesh_shape"] == [2, 1]
    assert out["n_leaves"] > 0
    assert out["on_new_mesh"] is True          # only surviving devices
    assert out["values_equal"] is True         # restore is lossless
    assert out["resume_span"] is True          # telemetry really fired


def test_best_mesh_shape_degrades_gracefully():
    assert best_mesh_shape(32, model_axis=16) == (2, 16)
    assert best_mesh_shape(24, model_axis=16) == (3, 8)  # 24 % 16 != 0
    assert best_mesh_shape(6, model_axis=16) == (6, 1)   # below min TP
    assert best_mesh_shape(2, model_axis=2) == (2, 1)


def test_plan_resize_counts_and_preserves_batch():
    from repro import obs
    obs.reset("runtime.elastic.")
    plan = plan_resize(8, 6, global_batch=32, n_hosts=2, model_axis=4)
    assert plan.mesh_shape == (6, 1)
    assert plan.global_batch == 32 and plan.per_host_batch == 16
    assert "6 devices" in plan.describe()
    assert obs.snapshot("runtime.elastic.")["runtime.elastic.resizes"] == 1
