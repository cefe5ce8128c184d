"""Shared model substrate: declarative parameters with logical sharding
axes, norms, RoPE, blockwise (flash-style) attention and chunked loss.

Parameters are declared once as :class:`ParamSpec` trees — a single
source of truth for shape, initialization AND partitioning.  Partition
specs use *logical* axis names that a :class:`Dist` resolves against
the physical mesh (DESIGN.md §4):

    'dp'    batch / data parallel         -> ('pod', 'data') | ('data',)
    'fsdp'  ZeRO-3 weight shard           -> ('data',) [+ 'pod' if flagged]
    'tp'    tensor parallel               -> ('model',)
    'sp'    sequence shard of residuals   -> ('model',)
    'ep'    expert parallel               -> ('model',)

If a dimension is not divisible by the resolved axis size, the resolver
*drops that dim's sharding* (replicates) — this is how configs with
e.g. 4 or 56 attention heads stay legal on a 16-way TP axis.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxis = str | tuple[str, ...] | None
LogicalSpec = tuple[LogicalAxis, ...]


# --------------------------------------------------------------------------- #
# distribution context                                                         #
# --------------------------------------------------------------------------- #
#: parallelism plans — the pod-level "spatial mappings" the mesh-DSE
#: chooses between (DESIGN.md §2's macro<->pod analogy made executable).
#: "2d"      : batch over data, TP/EP/SP over model, ZeRO-3 over data —
#:             the baseline policy.
#: "ddp"     : pure data parallelism over every axis; params replicated.
#:             Right for small models where TP collectives dominate.
#: "dp_fsdp" : batch over all axes, params ZeRO-3-sharded over data only;
#:             no TP.  Mid-size models that fit 16-way-sharded state.
#: "ep_dp"   : experts over model (EP), attention/dense pure DP+ZeRO-3 —
#:             no TP, so no per-layer residual all-gathers.  For MoE
#:             giants whose non-expert params are small (arctic).
#: "serve_tp": params TP/EP-sharded over model ONLY (no ZeRO — serving
#:             holds no optimizer state, and per-token ZeRO gathers are
#:             the decode bottleneck); batch over data(x pod).
PLANS = ("2d", "ddp", "dp_fsdp", "ep_dp", "serve_tp")


@dataclasses.dataclass(frozen=True)
class Dist:
    """Resolves logical axis names against a physical mesh (or no mesh)."""

    mesh: Mesh | None = None
    fsdp_over_pod: bool = False
    plan: str = "2d"

    def _physical(self, name: str) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        has_pod = "pod" in self.mesh.axis_names
        pod = ("pod",) if has_pod else ()
        if self.plan == "ddp":
            table = {"dp": ("data", "model") + pod, "fsdp": (),
                     "tp": (), "sp": (), "ep": ()}
        elif self.plan == "dp_fsdp":
            table = {"dp": ("data", "model") + pod, "fsdp": ("data",),
                     "tp": (), "sp": (), "ep": ()}
        elif self.plan == "serve_tp":
            table = {"dp": pod + ("data",), "fsdp": (),
                     "tp": ("model",), "sp": ("model",),
                     "ep": ("model",)}
        elif self.plan == "ep_dp":
            # batch over BOTH axes for attention/dense (no idle replicas);
            # inside the MoE, tokens redistribute to a data-only batch
            # axis ('dp_moe') so experts own the model axis — the
            # classic DP-grid -> EP-grid exchange.
            table = {"dp": ("data", "model") + pod,
                     "dp_moe": ("data",),
                     "fsdp": (("pod", "data") if (has_pod
                              and self.fsdp_over_pod) else ("data",)),
                     "tp": (), "sp": (), "ep": ("model",)}
        else:  # "2d"
            table = {
                "dp": pod + ("data",),
                "fsdp": (("pod", "data") if (has_pod and self.fsdp_over_pod)
                         else ("data",)),
                "tp": ("model",),
                "sp": ("model",),
                "ep": ("model",),
            }
        table.setdefault("dp_moe", table["dp"])
        return table[name]

    def _axis_size(self, axes: tuple[str, ...]) -> int:
        assert self.mesh is not None
        return math.prod(self.mesh.shape[a] for a in axes)

    def resolve(self, logical: LogicalSpec,
                shape: tuple[int, ...] | None = None) -> P:
        """Logical spec -> PartitionSpec, dropping non-divisible entries."""
        if self.mesh is None:
            return P()
        out: list[Any] = []
        used: set[str] = set()
        for i, entry in enumerate(logical):
            if entry is None:
                out.append(None)
                continue
            names = (entry,) if isinstance(entry, str) else entry
            phys: list[str] = []
            for n in names:
                for ax in self._physical(n):
                    if ax not in used:
                        phys.append(ax)
            if shape is not None:
                # longest prefix of axes whose product divides the dim
                # (e.g. 4 heads on a 16-way axis -> replicate; batch 256
                # on (data,model,pod)=512 -> shard over (data,model))
                while phys and shape[i] % math.prod(
                        self.mesh.shape[a] for a in phys):
                    phys.pop()
            if not phys:
                out.append(None)
                continue
            used.update(phys)
            out.append(tuple(phys) if len(phys) > 1 else phys[0])
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, logical: LogicalSpec,
                 shape: tuple[int, ...] | None = None) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.resolve(logical, shape))

    def shard(self, x: jax.Array, logical: LogicalSpec) -> jax.Array:
        """with_sharding_constraint under the dist mesh (no-op if none)."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.resolve(logical, tuple(x.shape))))


NO_DIST = Dist(mesh=None)


# --------------------------------------------------------------------------- #
# declarative parameters                                                       #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical: LogicalSpec = ()
    init: str = "normal"          # normal | zeros | ones | embed
    scale: float | None = None    # stddev override (normal), default fan-in
    dtype: Any = None             # defaults to the model's param_dtype

    def stddev(self) -> float:
        if self.scale is not None:
            return self.scale
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        return 1.0 / math.sqrt(max(1, fan_in))


ParamTree = Any  # nested dict of ParamSpec / jax.Array


def _iter_specs(tree: ParamTree, path=()):
    if isinstance(tree, ParamSpec):
        yield path, tree
    elif isinstance(tree, Mapping):
        for k in sorted(tree):
            yield from _iter_specs(tree[k], path + (k,))
    else:
        raise TypeError(f"bad spec node at {path}: {type(tree)}")


def init_params(specs: ParamTree, key: jax.Array, param_dtype=jnp.float32,
                dist: Dist = NO_DIST) -> ParamTree:
    """Materialize a ParamSpec tree (deterministic per-path keys)."""

    def build(path, spec: ParamSpec):
        k = key
        for part in path:
            # crc32, not hash(): str hashes are salted per process, so
            # the weights would not follow from the seed alone
            k = jax.random.fold_in(k, zlib.crc32(part.encode()) & 0x7FFFFFFF)
        dtype = spec.dtype or param_dtype
        std = 0.02 if spec.init == "embed" else spec.stddev()

        def make(k):
            if spec.init == "zeros":
                return jnp.zeros(spec.shape, dtype)
            if spec.init == "ones":
                return jnp.ones(spec.shape, dtype)
            return (jax.random.normal(k, spec.shape, jnp.float32) * std
                    ).astype(dtype)

        sh = dist.sharding(spec.logical, spec.shape)
        if sh is None:
            return make(k)
        # each device draws its own shard: a whole leaf built on one
        # device first may not fit there (glm4-9b's stacked FFN weights
        # alone are 9 GB in f32)
        return jax.jit(make, out_shardings=sh)(k)

    out: dict = {}
    for path, spec in _iter_specs(specs):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = build(path, spec)
    return out


def shape_structs(specs: ParamTree, param_dtype=jnp.float32,
                  dist: Dist = NO_DIST) -> ParamTree:
    """ShapeDtypeStruct tree with shardings — dry-run stand-ins, zero
    allocation (the pattern required by the multi-pod dry-run brief)."""
    out: dict = {}
    for path, spec in _iter_specs(specs):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = jax.ShapeDtypeStruct(
            spec.shape, spec.dtype or param_dtype,
            sharding=dist.sharding(spec.logical, spec.shape))
    return out


def param_shardings(specs: ParamTree, dist: Dist) -> ParamTree:
    out: dict = {}
    for path, spec in _iter_specs(specs):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = dist.sharding(spec.logical, spec.shape)
    return out


def count_params(specs: ParamTree) -> int:
    return sum(math.prod(s.shape) for _, s in _iter_specs(specs))


# --------------------------------------------------------------------------- #
# numerics                                                                     #
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, max_pos: int, theta: float) -> jax.Array:
    """(max_pos, head_dim/2) rotation angles."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    pos = np.arange(max_pos)
    return jnp.asarray(np.outer(pos, inv), jnp.float32)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (..., S, H, D); angles: (S, D/2) or (..., S, D/2)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    if angles.ndim == 2:
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        cos = jnp.cos(angles)[..., None, :]
        sin = jnp.sin(angles)[..., None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


NEG_INF = -1e30


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mask_fn: Callable[[jax.Array, jax.Array], jax.Array],
                        q_chunk: int = 1024, kv_chunk: int = 1024,
                        softmax_scale: float | None = None,
                        causal_blocks: bool = False) -> jax.Array:
    """Memory-bounded attention with online softmax (flash algorithm in
    pure JAX — XLA-fusable, remat-friendly; DESIGN.md §4).

    q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D) with H % Hkv == 0.
    ``mask_fn(q_idx, kv_idx) -> bool (len(q_idx), len(kv_idx))`` — True
    where attention is allowed (causality/windows/prefix live here).

    ``causal_blocks``: statically skip chunk pairs above the diagonal —
    valid whenever the mask is a subset of causal (plain causal, sliding
    windows, prefix-LM with prefix <= q_chunk).  Cuts attention FLOPs
    ~(n-1)/2n (44 % at n=8): EXPERIMENTS.md §Perf.
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    groups = h // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    assert sq % q_chunk == 0 and skv % kv_chunk == 0
    if causal_blocks and sq == skv:
        chunk = min(q_chunk, kv_chunk)
        if sq % chunk == 0 and sq // chunk > 1:
            return _triangular_attention(q, k, v, mask_fn, chunk, scale)

    # (nq, B, qc, HKV, G, D) — grouped query layout for GQA
    qr = q.reshape(b, nq, q_chunk, hkv, groups, d).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(b, nkv, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nkv, kv_chunk, hkv, dv).transpose(1, 0, 2, 3, 4)

    # Both loops are checkpointed: without this, scan-AD saves the
    # (b,h,g,qc,kc) probabilities for EVERY chunk pair — the full S x S
    # attention matrix in f32 (measured 84 GiB/device on minicpm3
    # train_4k).  With remat, backward recomputes one chunk pair at a
    # time: true flash-attention memory at the standard ~2x FLOPs cost.
    @jax.checkpoint
    def q_block(carry, qi_qc):
        qi, qc = qi_qc
        q_idx = qi * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint
        def kv_block(state, ki_kc_vc):
            ki, kc, vc = ki_kc_vc
            m_prev, l_prev, o_prev = state
            kv_idx = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = mask_fn(q_idx, kv_idx)                   # (qc, kc)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            o_new = o_prev * corr[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((b, hkv, groups, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, groups, q_chunk), jnp.float32)
        o0 = jnp.zeros((b, hkv, groups, q_chunk, dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_block, (m0, l0, o0), (jnp.arange(nkv), kr, vr))
        o = o / jnp.maximum(l[..., None], 1e-30)
        # (B, qc, HKV, G, D)
        return carry, o.transpose(0, 3, 1, 2, 4)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qr))
    # outs: (nq, B, qc, HKV, G, Dv) -> (B, Sq, H, Dv)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def _triangular_attention(q, k, v, mask_fn, chunk: int, scale: float):
    """Flash attention over the lower-triangle chunk pairs only.

    Accumulators (m, l, o) for every q chunk are carried through one
    scan over the static (i >= j) pair list; each step contributes kv
    chunk j to q chunk i.
    """
    b, s, h, d = q.shape
    _, _, hkv, dv = v.shape
    g = h // hkv
    n = s // chunk
    qr = q.reshape(b, n, chunk, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(b, n, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, n, chunk, hkv, dv).transpose(1, 0, 2, 3, 4)

    pairs = np.asarray([(i, j) for i in range(n) for j in range(i + 1)],
                       np.int32)

    @jax.checkpoint
    def step(state, ij):
        m_all, l_all, o_all = state
        i, j = ij[0], ij[1]
        qc = jax.lax.dynamic_index_in_dim(qr, i, 0, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(kr, j, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vr, j, 0, keepdims=False)
        q_idx = i * chunk + jnp.arange(chunk)
        kv_idx = j * chunk + jnp.arange(chunk)
        sij = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                         preferred_element_type=jnp.float32) * scale
        mask = mask_fn(q_idx, kv_idx)
        sij = jnp.where(mask[None, None, None], sij, NEG_INF)
        m_prev = jax.lax.dynamic_index_in_dim(m_all, i, 0, keepdims=False)
        l_prev = jax.lax.dynamic_index_in_dim(l_all, i, 0, keepdims=False)
        o_prev = jax.lax.dynamic_index_in_dim(o_all, i, 0, keepdims=False)
        m_new = jnp.maximum(m_prev, jnp.max(sij, axis=-1))
        p = jnp.exp(sij - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        o_new = o_prev * corr[..., None] + pv
        return (jax.lax.dynamic_update_index_in_dim(m_all, m_new, i, 0),
                jax.lax.dynamic_update_index_in_dim(l_all, l_new, i, 0),
                jax.lax.dynamic_update_index_in_dim(o_all, o_new, i, 0)), None

    m0 = jnp.full((n, b, hkv, g, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, b, hkv, g, chunk), jnp.float32)
    o0 = jnp.zeros((n, b, hkv, g, chunk, dv), jnp.float32)
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), jnp.asarray(pairs))
    o = o / jnp.maximum(l[..., None], 1e-30)
    # (n, B, hkv, g, chunk, dv) -> (B, S, H, dv)
    out = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, dv)
    return out.astype(q.dtype)


def causal_mask_fn(q_offset: int = 0):
    def fn(q_idx, kv_idx):
        return (q_idx[:, None] + q_offset) >= kv_idx[None, :]
    return fn


def sliding_mask_fn(window: int, q_offset: int = 0):
    def fn(q_idx, kv_idx):
        qi = q_idx[:, None] + q_offset
        return (qi >= kv_idx[None, :]) & (qi - kv_idx[None, :] < window)
    return fn


def prefix_lm_mask_fn(prefix_len: int):
    """Bidirectional over the first ``prefix_len`` positions (PaliGemma
    image prefix), causal elsewhere."""
    def fn(q_idx, kv_idx):
        causal = q_idx[:, None] >= kv_idx[None, :]
        in_prefix = (q_idx[:, None] < prefix_len) & \
            (kv_idx[None, :] < prefix_len)
        return causal | in_prefix
    return fn


def chunked_softmax_xent(x: jax.Array, head_w: jax.Array,
                         labels: jax.Array, dist: Dist = NO_DIST,
                         chunk: int = 512,
                         vocab_size: int | None = None) -> jax.Array:
    """Cross-entropy over a large vocab, computed in sequence chunks so
    the (B, chunk, V) logits tensor bounds the live memory.

    ``head_w``: (d, V_padded); ``vocab_size``: logical vocab (padding
    columns masked out).  Returns mean NLL over all tokens.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    while s % chunk:          # e.g. s=3840 (paligemma text) -> chunk 256
        chunk //= 2
    n = s // chunk
    xr = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lr = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    v_pad = head_w.shape[-1]

    def body(tot, xs):
        xc, lc = xs
        logits = (xc @ head_w).astype(jnp.float32)
        logits = dist.shard(logits, ("dp", None, "tp"))
        if vocab_size is not None and vocab_size != v_pad:
            pad_mask = jnp.arange(v_pad) >= vocab_size
            logits = jnp.where(pad_mask[None, None], NEG_INF, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0), (xr, lr))
    return total / (b * s)
