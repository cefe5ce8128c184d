"""Persistent XLA compilation cache plumbing (``core.compilecache``).

The fused sweep enables jax's persistent compilation cache on first
use; a second process pointed at the same directory starts with warm
compiles.  Configuration is process-global and first-call-wins, so the
behavioral tests run in subprocesses with a controlled environment;
the in-process tests only cover the pure helpers.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.core.compilecache import DEFAULT_DIR, compilation_cache_info

REPO = Path(__file__).resolve().parent.parent.parent

_WORKER = """
import json, os
from repro.core import designs, dse, workloads
from repro.core.compilecache import compilation_cache_info

grid = designs.macro_grid(rows=(64,), cols=(256,), adc_bits=(5,),
                          dac_bits=(2,), m_mux=(1,), tech_nm=(22,))
res = dse.sweep("dae", workloads.deep_autoencoder(), grid)
info = compilation_cache_info()
print(json.dumps({"dir": info["dir"], "entries": info["entries"],
                  "bytes": info["bytes"],
                  "energy0": float(res.energy_fj[0])}))
"""


def _run_worker(tmp_path: Path, **cache_env: str) -> dict:
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           # HOME inside tmp so no branch can touch the real user cache
           # from a test
           "HOME": str(tmp_path)}
    if "TMPDIR" in os.environ:
        env["TMPDIR"] = os.environ["TMPDIR"]
    env.update(cache_env)
    res = subprocess.run([sys.executable, "-c", _WORKER],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sweep_populates_cache_dir_and_warm_start(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is honoured: a sweep persists its
    XLA executables there; a fresh process reuses them (entry count does
    not grow) and reproduces identical results."""
    cache = tmp_path / "xla"
    cold = _run_worker(tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert cold["dir"] == str(cache)
    assert cold["entries"] > 0
    assert cold["bytes"] > 0

    warm = _run_worker(tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert warm["entries"] == cold["entries"]    # hits, not re-compiles
    assert warm["energy0"] == cold["energy0"]    # bitwise across processes


def test_cache_disabled_by_env(tmp_path):
    """jax's own ``JAX_ENABLE_COMPILATION_CACHE=false`` disables
    persistence: no directory is configured, the sweep still runs."""
    out = _run_worker(tmp_path, JAX_ENABLE_COMPILATION_CACHE="false")
    assert out["dir"] is None
    assert out["entries"] == 0
    assert not (tmp_path / ".cache").exists()


def test_default_dir_in_checkout(tmp_path):
    """With no env knob the cache lands in the fixed, git-ignored
    ``<repo>/.jax_cache`` and nowhere under HOME."""
    out = _run_worker(tmp_path)
    assert out["dir"] == str(REPO / ".jax_cache") == DEFAULT_DIR
    assert out["entries"] > 0
    assert not (tmp_path / ".cache").exists()


def test_cache_info_tolerates_unconfigured_state():
    info = compilation_cache_info()
    assert set(info) == {"dir", "entries", "bytes"}
    assert info["entries"] >= 0 and info["bytes"] >= 0
