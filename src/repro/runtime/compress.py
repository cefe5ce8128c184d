"""Gradient compression for cross-pod reduction: int8 quantization with
error feedback, plus a shard_map-based compressed all-reduce.

Hierarchical DP (DESIGN.md §4): within a pod, gradients reduce over the
'data' axis in full precision (fast ICI); across pods — the slow links —
they are quantized to int8 per-tensor before the all-reduce and the
quantization residual is carried to the next step (error feedback, EF;
1-bit Adam / EF-SGD lineage).  ``compressed_psum`` performs the actual
int8-payload reduction inside ``shard_map``; ``ef_compress_tree`` is
the numerics layer used by the trainer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization; returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress(g: jax.Array, err: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize (g + carried error); return (g_hat, new_error)."""
    target = g.astype(jnp.float32) + err
    q, s = quantize_int8(target)
    g_hat = dequantize_int8(q, s)
    return g_hat.astype(g.dtype), target - g_hat


def ef_compress_tree(grads, err_tree):
    """Error-feedback int8 compression over a gradient pytree."""
    flat_g, td = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_tree)
    out = [ef_compress(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(td, [o[0] for o in out]),
            jax.tree.unflatten(td, [o[1] for o in out]))


def init_error_tree(params):
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """All-reduce with an int8 payload over one mesh axis.

    Each shard quantizes locally; the int8 codes are summed in int32
    (wire format 8 bits/element + one f32 scale) using the max scale
    across the axis so codes are commensurable.
    """
    spec = P()  # x replicated w.r.t. the reduced axis

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=spec,
                       out_specs=spec, check_vma=False)
    def _inner(xl):
        amax_l = jnp.max(jnp.abs(xl))
        amax = jax.lax.pmax(amax_l, axis)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(xl / scale), -127, 127).astype(jnp.int8)
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        return total.astype(jnp.float32) * scale

    return _inner(x)
