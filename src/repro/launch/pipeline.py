"""Pipeline parallelism over the 'pod' axis: a GPipe schedule in
shard_map.

The production meshes keep `pod` as a data-parallel axis by default
(DESIGN.md §4); this module provides the alternative: treat the pod
axis as `n_stages` pipeline stages, stream `n_micro` microbatches
through a fill-steady-drain schedule, and exchange stage boundaries
with `ppermute` (the collective a TPU pod maps onto its inter-pod
links).  Per-microbatch activations are what crosses pods — for a
transformer stage that is (mb, S, d) once per tick instead of ZeRO
gathers of full parameter shards, which is exactly when PP wins: very
slow inter-pod links + very large models.

``gpipe`` is model-agnostic: ``stage_fn(stage_params, x) -> y`` with
matching x/y shapes; params carry a leading (n_stages, ...) axis
sharded over the pipeline axis.  Bubble overhead is the usual
(n_stages - 1) / (n_micro + n_stages - 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn, stage_params, x, *, mesh: Mesh, axis: str = "pod"):
    """Run ``x: (n_micro, mb, ...)`` through ``n_stages = mesh.shape[axis]``
    stages.  Returns (n_micro, mb, ...) outputs (replicated over the
    pipeline axis).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    def run(params_local, x_all):
        sid = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda t: t[0], params_local)
        h = jnp.zeros_like(x_all[0])
        out = jnp.zeros_like(x_all)
        for t in range(n_ticks):
            # stage 0 consumes microbatch t (while it exists); others
            # consume what arrived from the previous stage last tick
            feed = x_all[min(t, n_micro - 1)]
            x_in = jnp.where(sid == 0, feed, h)
            m = t - sid                         # microbatch at this stage
            valid = (m >= 0) & (m < n_micro)
            y = stage_fn(params_here, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # drain: last stage records its finished microbatch
            is_last = sid == n_stages - 1
            out = out.at[jnp.clip(m, 0, n_micro - 1)].add(
                jnp.where(valid & is_last, y, jnp.zeros_like(y)))
            # fill: boundary activations hop one stage forward
            h = jax.lax.ppermute(y, axis, fwd_perm)
        # replicate the last stage's outputs to every stage
        return jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)),
            axis)

    return run(stage_params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
