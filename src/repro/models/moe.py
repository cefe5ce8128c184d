"""FFN variants: dense (SwiGLU / GELU) and Mixture-of-Experts with
sort-based capacity dispatch and expert parallelism.

MoE dispatch is the sort-based grouped-GEMM formulation: flatten the
top-k (token, expert) assignments, rank tokens within their expert by a
cumulative count, scatter token indices into a dense (E, C) table, and
gather activations into (E, C, d) blocks — one batched einsum then runs
all experts.  With experts sharded over the TP axis ('ep') and tokens
over data, GSPMD lowers the gather/scatter into all-to-alls: the
standard expert-parallel exchange.  Tokens beyond capacity are dropped
(Switch-style), and the Switch load-balancing auxiliary loss is
returned for training.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .common import Dist, ParamSpec


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    every: int = 1               # MoE on layers where (i % every)==every-1
    dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    #: always-on experts of width ``d_ff_expert`` beside the routed ones
    #: (HF ``n_shared_experts``); priced by ``core.lm_bridge`` only
    n_shared: int = 0
    #: leading layers that keep the dense FFN of ``d_ff`` (HF
    #: ``first_k_dense_replace``); priced by ``core.lm_bridge`` only,
    #: and only for a one-position pattern (``ModelConfig`` checks)
    first_dense: int = 0


# --------------------------------------------------------------------------- #
# dense FFN                                                                    #
# --------------------------------------------------------------------------- #
def ffn_specs(d_model: int, d_ff: int, act: str = "swiglu") -> dict[str, Any]:
    s = {
        "w_up": ParamSpec((d_model, d_ff), ("fsdp", "tp")),
        "w_down": ParamSpec((d_ff, d_model), ("tp", "fsdp")),
    }
    if act in ("swiglu", "geglu"):
        s["w_gate"] = ParamSpec((d_model, d_ff), ("fsdp", "tp"))
    return s


def ffn_apply(p, x, *, act: str = "swiglu", dist: Dist) -> jax.Array:
    up = x @ p["w_up"]
    up = dist.shard(up, ("dp", None, "tp"))
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    elif act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    return h @ p["w_down"]


# --------------------------------------------------------------------------- #
# MoE FFN                                                                      #
# --------------------------------------------------------------------------- #
def moe_specs(d_model: int, m: MoEConfig) -> dict[str, Any]:
    e, f = m.n_experts, m.d_ff_expert
    return {
        "router": ParamSpec((d_model, e), (None, None), scale=0.02),
        "w_gate": ParamSpec((e, d_model, f), ("ep", "fsdp", None)),
        "w_up": ParamSpec((e, d_model, f), ("ep", "fsdp", None)),
        "w_down": ParamSpec((e, f, d_model), ("ep", None, "fsdp")),
    }


def _group_dispatch(xf, p_router, m: MoEConfig, capacity: int):
    """Dispatch ONE token group (S, d) -> (E, C) index tables.

    Runs under vmap over groups (batch rows), so every gather/scatter
    is local to the device owning that group — no global-token
    all-gathers; the only cross-device exchange is the (G, E, C, d)
    all-to-all GSPMD inserts for the expert einsum (DESIGN.md §4)."""
    t, _ = xf.shape
    e, k = m.n_experts, m.top_k
    logits = (xf @ p_router).astype(jnp.float32)              # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (S, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch aux loss terms (summed over groups by the caller)
    onehot_top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(jnp.mean(onehot_top1, axis=0)
                       * jnp.mean(probs, axis=0))

    e_flat = expert_idx.reshape(-1)                           # (S*k,)
    g_flat = gate_vals.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(e_flat)                               # stable
    e_sorted = e_flat[order]
    first_of = jnp.searchsorted(e_sorted, jnp.arange(e))      # (E,)
    rank = jnp.arange(t * k) - first_of[e_sorted]
    keep = rank < capacity
    slot = jnp.where(keep, e_sorted * capacity + rank, e * capacity)

    # per-(expert, slot) tables; overflow bucket at the end is sliced off
    dispatch_tok = jnp.zeros(e * capacity + 1, jnp.int32).at[slot].set(
        tok_flat[order].astype(jnp.int32), mode="drop")[:-1]
    filled = jnp.zeros(e * capacity + 1, jnp.bool_).at[slot].set(
        keep, mode="drop")[:-1]
    slot_gate = jnp.zeros(e * capacity + 1, jnp.float32).at[slot].set(
        jnp.where(keep, g_flat[order], 0.0), mode="drop")[:-1]
    return dispatch_tok, filled, slot_gate, aux


def moe_apply(p, x, *, m: MoEConfig, dist: Dist,
              capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  x: (B, S, d); B rows are the dispatch
    groups (GShard-style), so routing state never crosses devices."""
    b, s, d = x.shape
    e = m.n_experts
    if capacity is None:
        capacity = max(1, int(m.capacity_factor * s * m.top_k / e))

    dispatch_tok, filled, slot_gate, aux = jax.vmap(
        lambda xr: _group_dispatch(xr, p["router"], m, capacity))(x)
    aux = jnp.mean(aux) * m.aux_coef

    # local gather: (B, E*C, d) — expressed through vmap so the batch
    # dim is a gather *batch dimension* GSPMD can partition along 'dp'
    # (an indexed gather over a flattened token axis replicates the
    # full (B,S,d) activation on every device — measured 117 GiB/device
    # on arctic-480b before this formulation).
    xg = jax.vmap(lambda xr, tr: xr[tr])(x, dispatch_tok)
    xg = xg * filled[..., None].astype(xg.dtype)
    xg = dist.shard(xg.reshape(b, e, capacity, d),
                    ("dp_moe", "ep", None, None))

    h = jnp.einsum("becd,edf->becf", xg, p["w_up"].astype(xg.dtype))
    g = jnp.einsum("becd,edf->becf", xg, p["w_gate"].astype(xg.dtype))
    h = jax.nn.silu(g) * h
    yo = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(h.dtype))
    yo = dist.shard(yo, ("dp_moe", "ep", None, None)) \
        .reshape(b, e * capacity, d)
    yo = yo * slot_gate[..., None].astype(yo.dtype)

    # local scatter-add back to token positions (vmapped: same batch-dim
    # partitioning argument as the gather above)
    y = jax.vmap(
        lambda yr, tr: jnp.zeros((s, d), yo.dtype).at[tr].add(yr)
    )(yo, dispatch_tok)
    return y, aux
