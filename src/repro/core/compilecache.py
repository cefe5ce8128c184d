"""Persistent XLA compilation cache plumbing.

Cold sweep time is dominated by XLA compiles that are identical from
process to process (the fused grid kernel compiles once per distinct
lattice shape).  JAX ships a persistent compilation cache that
serializes compiled executables to a directory keyed by HLO
fingerprint; enabling it makes every process after the first start
warm.

Where the cache lives (resolved at the first
:func:`enable_compilation_cache` call):

* ``JAX_COMPILATION_CACHE_DIR`` set -> jax's own setting is left alone;
* otherwise -> the fixed in-checkout directory ``<repo>/.jax_cache``
  (git-ignored).  The path is part of the cache key, so it never
  carries a temp name, pid or timestamp;
* ``JAX_ENABLE_COMPILATION_CACHE=false`` -> persistence is off and no
  directory is configured or created.

The thresholds ``jax_persistent_cache_min_entry_size_bytes`` and
``jax_persistent_cache_min_compile_time_secs`` are forced to "cache
everything": the sweep kernels compile in fractions of a second each,
below jax's default 1s persistence floor, which would silently skip
exactly the compiles we want to persist.

Entry points (``chip_smoke.py``, ``launch/serve.py:main``,
``benchmarks/run.py``) enable the cache before their first compile; the
kernel builders call it again lazily, which is a no-op after the first
call.
"""

from __future__ import annotations

import os
from pathlib import Path

from .. import obs

#: ``<repo>/.jax_cache``: this file is ``<repo>/src/repro/core/...``
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

#: tri-state: None = not yet configured, "" = disabled, else the dir
_STATE: dict[str, str | None] = {"dir": None}


def enable_compilation_cache() -> str | None:
    """Idempotently enable jax's persistent compilation cache.

    Returns the active cache directory, or ``None`` when persistence is
    disabled through ``JAX_ENABLE_COMPILATION_CACHE``.
    """
    if _STATE["dir"] is not None:
        return _STATE["dir"] or None
    import jax

    if not jax.config.jax_enable_compilation_cache:
        _STATE["dir"] = ""
        return None
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        Path(cache_dir).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # persist every executable: the grid kernels compile fast enough to
    # fall under jax's default floors, which would skip them silently
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _STATE["dir"] = cache_dir
    return cache_dir


def persistent_cache_dir() -> str | None:
    """Active persistent-cache directory, or ``None`` when persistence
    is disabled or not yet configured."""
    return _STATE["dir"] or None


def compilation_cache_info() -> dict:
    """Artifact-friendly snapshot: active dir (or None) and entry
    count/bytes currently on disk.  Also refreshes the registry gauges
    ``compilecache.entries`` / ``compilecache.bytes`` so telemetry
    blocks carry the same figures."""
    d = _STATE["dir"]
    entries = 0
    size = 0
    if d and os.path.isdir(d):
        for p in Path(d).iterdir():
            if p.is_file():
                entries += 1
                size += p.stat().st_size
    obs.gauge("compilecache.entries").set(entries)
    obs.gauge("compilecache.bytes").set(size)
    return {"dir": d or None, "entries": entries, "bytes": size}
