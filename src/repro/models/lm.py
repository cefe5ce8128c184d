"""Generic decoder-LM assembly for all assigned architectures.

A model is a stack of *superblocks* scanned with ``jax.lax.scan``: the
superblock is one period of ``cfg.pattern`` (e.g. ``('attn',)`` for a
uniform transformer, 7x mamba + 1x attn for Jamba).  All per-layer
params are stacked along a leading ``n_super`` axis; per-layer scalar
variation (gemma3's local/global flag) is scanned data.  Scan keeps the
HLO size O(superblock) — essential for 62-72 layer configs compiling
on the 512-way SPMD mesh — and ``jax.checkpoint`` on the superblock
bounds train-time activation memory to one residual per layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .attention import (AttnConfig, MLAConfig, gqa_apply, gqa_cache_specs,
                        gqa_decode, gqa_specs, mla_apply, mla_cache_specs,
                        mla_decode, mla_specs)
from .common import (Dist, NO_DIST, ParamSpec, chunked_softmax_xent,
                     count_params, init_params, param_shardings, rms_norm,
                     rope_freqs, shape_structs)
from .moe import MoEConfig, ffn_apply, ffn_specs, moe_apply, moe_specs
from .ssm import (MambaConfig, RWKVConfig, mamba_apply, mamba_cache_specs,
                  mamba_decode, mamba_specs, rwkv6_block_decode,
                  rwkv6_block_specs, rwkv6_cache_specs, rwkv6_channel_mix,
                  rwkv6_time_mix)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    vocab_size: int
    d_ff: int
    ffn_act: str = "swiglu"
    pattern: tuple[str, ...] = ("attn",)     # attn | mla | mamba | rwkv6
    attn: AttnConfig | None = None
    mla: MLAConfig | None = None
    mamba: MambaConfig | None = None
    rwkv: RWKVConfig | None = None
    moe: MoEConfig | None = None
    frontend: str = "tokens"                  # tokens | frames | image_text
    img_tokens: int = 0
    img_dim: int = 0                          # SigLIP feature dim (paligemma)
    frame_dim: int = 0                        # EnCodec latent dim (musicgen)
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    post_norm: bool = False                   # gemma3 sandwich norms
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    moment_dtype: Any = jnp.float32
    cache_dtype: Any = jnp.bfloat16
    fsdp_over_pod: bool = False
    #: remat policy for the layer scan: "none" (recompute everything),
    #: "save_moe" (keep MoE outputs), "save_dots" (keep matmul outputs,
    #: skipping the forward recompute in backward at HBM cost) —
    #: EXPERIMENTS.md §Perf iterations.
    remat_policy: str = "none"
    #: chunkwise-parallel WKV6 (batched einsums instead of the per-step
    #: recurrence; see ssm._wkv_chunk_parallel) — EXPERIMENTS.md §Perf.
    wkv_chunked: bool = False
    #: microbatch count for gradient accumulation in train_step (trades
    #: activation memory for an f32 grad buffer) — the mechanism that
    #: makes jamba-398b train fit a single pod (EXPERIMENTS.md §Perf).
    grad_accum: int = 1
    vocab_pad_multiple: int = 128
    scan_chunk: int = 128                     # SSM time-scan chunk
    q_chunk: int = 512
    kv_chunk: int = 1024

    # ------------------------------------------------------------- derived
    def __post_init__(self):
        assert self.n_layers % len(self.pattern) == 0, \
            f"{self.name}: n_layers {self.n_layers} % pattern " \
            f"{len(self.pattern)} != 0"
        if self.moe is not None:
            assert len(self.pattern) % self.moe.every == 0
            if self.moe.first_dense and len(self.pattern) != 1:
                raise ValueError(
                    f"{self.name}: a dense prologue (first_dense "
                    f"{self.moe.first_dense}) needs a one-position pattern, "
                    f"not {self.pattern}")
            if not 0 <= self.moe.first_dense < self.n_layers:
                raise ValueError(f"{self.name}: first_dense "
                                 f"{self.moe.first_dense} outside "
                                 f"[0, {self.n_layers})")

    @property
    def n_super(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k: any non-attention mixer, or sliding-
        window attention (gemma3's 5:1 local:global)."""
        if any(k in ("mamba", "rwkv6") for k in self.pattern):
            return True
        return bool(self.attn and self.attn.sliding_window > 0)

    @property
    def has_decoder(self) -> bool:
        return True                            # all assigned archs decode

    def layer_is_moe(self, pos: int) -> bool:
        return (self.moe is not None
                and pos % self.moe.every == self.moe.every - 1)

    def layer_is_global_attn(self, layer_idx: int) -> bool:
        a = self.attn
        if a is None or a.sliding_window <= 0:
            return True
        if a.global_every <= 0:
            return False
        return (layer_idx + 1) % a.global_every == 0

    def n_params(self) -> int:
        return count_params(self.param_specs())

    # ------------------------------------------------------------ params
    def _block_specs(self) -> dict[str, Any]:
        """Specs for ONE superblock (unstacked)."""
        s: dict[str, Any] = {}
        for pos, kind in enumerate(self.pattern):
            if kind == "rwkv6":
                # rwkv6 block = time mix + channel mix, own norms
                s[f"p{pos}"] = rwkv6_block_specs(
                    self.d_model, self.d_ff, self.rwkv)
                s[f"p{pos}_n1"] = ParamSpec((self.d_model,), (None,),
                                            init="zeros")
                s[f"p{pos}_n2"] = ParamSpec((self.d_model,), (None,),
                                            init="zeros")
                continue
            if kind == "attn":
                s[f"p{pos}_mix"] = gqa_specs(self.d_model, self.attn)
            elif kind == "mla":
                s[f"p{pos}_mix"] = mla_specs(self.d_model, self.mla)
            elif kind == "mamba":
                s[f"p{pos}_mix"] = mamba_specs(self.d_model, self.mamba)
            else:
                raise ValueError(kind)
            s[f"p{pos}_n1"] = ParamSpec((self.d_model,), (None,),
                                        init="zeros")
            s[f"p{pos}_n2"] = ParamSpec((self.d_model,), (None,),
                                        init="zeros")
            if self.post_norm:
                s[f"p{pos}_pn1"] = ParamSpec((self.d_model,), (None,),
                                             init="zeros")
                s[f"p{pos}_pn2"] = ParamSpec((self.d_model,), (None,),
                                             init="zeros")
            if self.layer_is_moe(pos):
                s[f"p{pos}_moe"] = moe_specs(self.d_model, self.moe)
                if self.moe.dense_residual:
                    s[f"p{pos}_ffn"] = ffn_specs(self.d_model, self.d_ff,
                                                 self.ffn_act)
            else:
                s[f"p{pos}_ffn"] = ffn_specs(self.d_model, self.d_ff,
                                             self.ffn_act)
        return s

    def param_specs(self) -> dict[str, Any]:
        def stack(node):
            if isinstance(node, ParamSpec):
                return ParamSpec((self.n_super,) + node.shape,
                                 (None,) + tuple(node.logical),
                                 init=node.init, scale=node.scale,
                                 dtype=node.dtype)
            return {k: stack(v) for k, v in node.items()}

        specs: dict[str, Any] = {"blocks": stack(self._block_specs())}
        if self.frontend in ("tokens", "image_text"):
            specs["embed"] = ParamSpec((self.padded_vocab, self.d_model),
                                       ("tp", "fsdp"), init="embed")
        if self.frontend == "image_text":
            specs["img_proj"] = ParamSpec((self.img_dim, self.d_model),
                                          ("fsdp", "tp"))
        if self.frontend == "frames":
            specs["frame_proj"] = ParamSpec((self.frame_dim, self.d_model),
                                            ("fsdp", "tp"))
        specs["final_norm"] = ParamSpec((self.d_model,), (None,),
                                        init="zeros")
        if not self.tie_embeddings:
            specs["head"] = ParamSpec((self.d_model, self.padded_vocab),
                                      ("fsdp", "tp"), init="embed")
        return specs

    # ------------------------------------------------------------- flags
    def layer_flags(self) -> dict[str, jax.Array]:
        """Per-(superblock, position) scalars, scanned alongside params."""
        p = len(self.pattern)
        is_global = [[self.layer_is_global_attn(sb * p + pos)
                      for pos in range(p)] for sb in range(self.n_super)]
        return {"is_global": jnp.asarray(is_global, jnp.bool_)}


# --------------------------------------------------------------------------- #
# model functions                                                              #
# --------------------------------------------------------------------------- #
class LM:
    """Functional model handle: config + dist context."""

    def __init__(self, cfg: ModelConfig, dist: Dist = NO_DIST):
        m = cfg.moe
        if m is not None and (m.n_shared or m.first_dense):
            raise NotImplementedError(
                f"{cfg.name}: LM has no shared experts (n_shared "
                f"{m.n_shared}) and no dense prologue (first_dense "
                f"{m.first_dense}); such a config is priced by "
                f"core.lm_bridge only")
        self.cfg = cfg
        self.dist = dataclasses.replace(
            dist, fsdp_over_pod=cfg.fsdp_over_pod)

    # -------------------------------------------------------------- params
    def init(self, key: jax.Array):
        return init_params(self.cfg.param_specs(), key,
                           self.cfg.param_dtype, self.dist)

    def param_structs(self):
        return shape_structs(self.cfg.param_specs(), self.cfg.param_dtype,
                             self.dist)

    def param_shardings(self):
        return param_shardings(self.cfg.param_specs(), self.dist)

    # ------------------------------------------------------------ embedding
    def _embed(self, params, batch) -> jax.Array:
        cfg, dist = self.cfg, self.dist
        cd = cfg.compute_dtype
        if cfg.frontend == "tokens":
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
            return dist.shard(x.astype(cd), ("dp", "sp", None))
        if cfg.frontend == "frames":
            x = batch["frames"].astype(cd) @ params["frame_proj"].astype(cd)
            return dist.shard(x, ("dp", "sp", None))
        if cfg.frontend == "image_text":
            img = batch["images"].astype(cd) @ params["img_proj"].astype(cd)
            txt = jnp.take(params["embed"], batch["tokens"], axis=0)
            x = jnp.concatenate([img, txt.astype(cd)], axis=1)
            return dist.shard(x, ("dp", "sp", None))
        raise ValueError(cfg.frontend)

    def _angles(self, max_pos: int):
        cfg = self.cfg
        if cfg.attn is not None:
            hd = (cfg.attn.head_dim)
            ag = rope_freqs(hd, max_pos, cfg.attn.rope_theta)
            al = (rope_freqs(hd, max_pos, cfg.attn.rope_local_theta)
                  if cfg.attn.rope_local_theta else None)
            return ag, al
        if cfg.mla is not None:
            return rope_freqs(cfg.mla.qk_rope_dim, max_pos,
                              cfg.mla.rope_theta), None
        return None, None

    # ------------------------------------------------------------- forward
    def _ffn_part(self, bp, pos: int, x: jax.Array):
        cfg, dist = self.cfg, self.dist
        aux = jnp.float32(0.0)
        y = jnp.zeros_like(x)
        if cfg.layer_is_moe(pos):
            ym, aux = moe_apply(bp[f"p{pos}_moe"], x, m=cfg.moe, dist=dist)
            ym = checkpoint_name(ym, "moe_out")
            y = y + ym
            if cfg.moe.dense_residual:
                y = y + ffn_apply(bp[f"p{pos}_ffn"], x, act=cfg.ffn_act,
                                  dist=dist)
        else:
            y = ffn_apply(bp[f"p{pos}_ffn"], x, act=cfg.ffn_act, dist=dist)
        return y, aux

    def _cast(self, bp):
        cd = self.cfg.compute_dtype
        return jax.tree.map(
            lambda t: t.astype(cd) if jnp.issubdtype(t.dtype, jnp.floating)
            else t, bp)

    def _superblock(self, x, bp, flags, angles, prefix_len: int):
        """One pattern period, full-sequence."""
        cfg, dist = self.cfg, self.dist
        bp = self._cast(bp)
        ag, al = angles
        aux_total = jnp.float32(0.0)
        for pos, kind in enumerate(cfg.pattern):
            if kind == "rwkv6":
                p = bp[f"p{pos}"]
                xa = rms_norm(x, bp[f"p{pos}_n1"], cfg.norm_eps)
                x = x + rwkv6_time_mix(p, xa, c=cfg.rwkv, dist=dist,
                                       chunk=cfg.scan_chunk,
                                       chunked_wkv=cfg.wkv_chunked)
                xb = rms_norm(x, bp[f"p{pos}_n2"], cfg.norm_eps)
                x = x + rwkv6_channel_mix(p, xb, dist=dist)
                x = dist.shard(x, ("dp", "sp", None))
                continue
            h = rms_norm(x, bp[f"p{pos}_n1"], cfg.norm_eps)
            if kind == "attn":
                h = gqa_apply(bp[f"p{pos}_mix"], h, a=cfg.attn, dist=dist,
                              angles_global=ag, angles_local=al,
                              is_global=flags["is_global"][pos],
                              prefix_len=prefix_len, q_chunk=cfg.q_chunk,
                              kv_chunk=cfg.kv_chunk)
            elif kind == "mla":
                h = mla_apply(bp[f"p{pos}_mix"], h, m=cfg.mla, dist=dist,
                              angles=ag, q_chunk=cfg.q_chunk,
                              kv_chunk=cfg.kv_chunk)
            elif kind == "mamba":
                h = mamba_apply(bp[f"p{pos}_mix"], h, c=cfg.mamba,
                                dist=dist, chunk=cfg.scan_chunk)
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn1"], cfg.norm_eps)
            x = x + h
            h = rms_norm(x, bp[f"p{pos}_n2"], cfg.norm_eps)
            h, aux = self._ffn_part(bp, pos, h)
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn2"], cfg.norm_eps)
            aux_total = aux_total + aux
            x = x + h
            x = dist.shard(x, ("dp", "sp", None))
        return x, aux_total

    def forward(self, params, batch, prefix_len: int = 0):
        """Full-sequence forward -> (hidden (B,S,d), moe_aux)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        angles = self._angles(x.shape[1])
        flags = cfg.layer_flags()

        def body(x, xs):
            bp, fl = xs
            return self._superblock(x, bp, fl, angles, prefix_len)

        policy = {
            "none": None,
            "save_moe": jax.checkpoint_policies.save_only_these_names(
                "moe_out"),
            "save_dots":
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }[cfg.remat_policy]
        x, auxs = jax.lax.scan(jax.checkpoint(body, policy=policy), x,
                               (params["blocks"], flags))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.sum(auxs)

    def _head_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def loss(self, params, batch) -> jax.Array:
        """Token-mean NLL (+ MoE aux)."""
        cfg = self.cfg
        prefix = cfg.img_tokens if cfg.frontend == "image_text" else 0
        x, aux = self.forward(params, batch, prefix_len=prefix)
        if prefix:
            x = x[:, prefix:]
        hw = self._head_weight(params).astype(cfg.compute_dtype)
        nll = chunked_softmax_xent(x, hw, batch["labels"], dist=self.dist,
                                   vocab_size=cfg.vocab_size)
        return nll + aux.astype(jnp.float32)

    def logits_last(self, params, x_last) -> jax.Array:
        cfg = self.cfg
        hw = self._head_weight(params).astype(cfg.compute_dtype)
        logits = (x_last @ hw).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(mask, -1e30, logits)
        return logits

    # ---------------------------------------------------------------- serve
    def cache_specs(self, batch: int, max_seq: int) -> dict[str, Any]:
        """Decode-cache ParamSpec tree (stacked over superblocks)."""
        cfg = self.cfg
        one: dict[str, Any] = {}
        cd = cfg.cache_dtype
        for pos, kind in enumerate(cfg.pattern):
            if kind == "attn":
                one[f"p{pos}"] = gqa_cache_specs(cfg.attn, batch, max_seq,
                                                 dtype=cd)
            elif kind == "mla":
                one[f"p{pos}"] = mla_cache_specs(cfg.mla, batch, max_seq,
                                                 dtype=cd)
            elif kind == "mamba":
                one[f"p{pos}"] = mamba_cache_specs(cfg.d_model, cfg.mamba,
                                                   batch, dtype=cd)
            elif kind == "rwkv6":
                one[f"p{pos}"] = rwkv6_cache_specs(cfg.d_model, cfg.rwkv,
                                                   batch, dtype=cd)
        def stack(node):
            if isinstance(node, ParamSpec):
                return ParamSpec((cfg.n_super,) + node.shape,
                                 (None,) + tuple(node.logical),
                                 init="zeros", dtype=node.dtype)
            return {k: stack(v) for k, v in node.items()}
        return {k: stack(v) for k, v in one.items()}

    def init_cache(self, batch: int, max_seq: int):
        return init_params(self.cache_specs(batch, max_seq),
                           jax.random.PRNGKey(0), self.cfg.cache_dtype,
                           self.dist)

    def cache_structs(self, batch: int, max_seq: int):
        return shape_structs(self.cache_specs(batch, max_seq),
                             self.cfg.cache_dtype, self.dist)

    def _superblock_prefill(self, x, bp, flags, angles, prefix_len: int,
                            max_seq: int):
        """One pattern period, full-sequence, collecting decode caches."""
        cfg, dist = self.cfg, self.dist
        bp = self._cast(bp)
        ag, al = angles
        s = x.shape[1]
        pad = max_seq - s
        cache: dict[str, Any] = {}

        def pad_seq(t):
            if pad == 0:
                return t.astype(cfg.cache_dtype)
            widths = [(0, 0)] * t.ndim
            widths[1] = (0, pad)
            return jnp.pad(t.astype(cfg.cache_dtype), widths)

        for pos, kind in enumerate(cfg.pattern):
            if kind == "rwkv6":
                p = bp[f"p{pos}"]
                xa = rms_norm(x, bp[f"p{pos}_n1"], cfg.norm_eps)
                y, state, last = rwkv6_time_mix(
                    p, xa, c=cfg.rwkv, dist=dist, chunk=cfg.scan_chunk,
                    return_state=True, chunked_wkv=cfg.wkv_chunked)
                x = x + y
                xb = rms_norm(x, bp[f"p{pos}_n2"], cfg.norm_eps)
                y2, last_cm = rwkv6_channel_mix(p, xb, dist=dist,
                                                return_last=True)
                x = x + y2
                cache[f"p{pos}"] = {
                    "state": state, "x_tm": last.astype(cfg.cache_dtype),
                    "x_cm": last_cm.astype(cfg.cache_dtype)}
                continue
            h = rms_norm(x, bp[f"p{pos}_n1"], cfg.norm_eps)
            if kind == "attn":
                h, (k, v) = gqa_apply(
                    bp[f"p{pos}_mix"], h, a=cfg.attn, dist=dist,
                    angles_global=ag, angles_local=al,
                    is_global=flags["is_global"][pos],
                    prefix_len=prefix_len, q_chunk=cfg.q_chunk,
                    kv_chunk=cfg.kv_chunk, return_kv=True)
                cache[f"p{pos}"] = {"k": pad_seq(k), "v": pad_seq(v)}
            elif kind == "mla":
                h, (c_kv, k_rope) = mla_apply(
                    bp[f"p{pos}_mix"], h, m=cfg.mla, dist=dist, angles=ag,
                    q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                    return_latent=True)
                cache[f"p{pos}"] = {"c_kv": pad_seq(c_kv),
                                    "k_rope": pad_seq(k_rope)}
            elif kind == "mamba":
                h, (hst, conv_tail) = mamba_apply(
                    bp[f"p{pos}_mix"], h, c=cfg.mamba, dist=dist,
                    chunk=cfg.scan_chunk, return_state=True)
                cache[f"p{pos}"] = {"h": hst,
                                    "conv": conv_tail.astype(cfg.cache_dtype)}
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn1"], cfg.norm_eps)
            x = x + h
            h = rms_norm(x, bp[f"p{pos}_n2"], cfg.norm_eps)
            h, _ = self._ffn_part(bp, pos, h)
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn2"], cfg.norm_eps)
            x = x + h
            x = dist.shard(x, ("dp", "sp", None))
        return x, cache

    def prefill(self, params, batch, max_seq: int | None = None):
        """Process a prompt; returns (last-token logits, cache, n_pos)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        max_seq = s if max_seq is None else max_seq
        angles = self._angles(max_seq)
        flags = cfg.layer_flags()
        prefix = cfg.img_tokens if cfg.frontend == "image_text" else 0

        def body(x, xs):
            bp, fl = xs
            return self._superblock_prefill(x, bp, fl, angles, prefix,
                                            max_seq)

        x, cache = jax.lax.scan(jax.checkpoint(body), x,
                                (params["blocks"], flags))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.logits_last(params, x[:, -1:])
        return logits, cache, s

    def _superblock_decode(self, x, bp, cache, flags, angles, pos_idx):
        cfg, dist = self.cfg, self.dist
        bp = self._cast(bp)
        ag, al = angles
        new_cache: dict[str, Any] = {}
        for pos, kind in enumerate(cfg.pattern):
            if kind == "rwkv6":
                x, new_cache[f"p{pos}"] = rwkv6_block_decode(
                    bp[f"p{pos}"], x, cache[f"p{pos}"], c=cfg.rwkv,
                    dist=dist, norm1=bp[f"p{pos}_n1"],
                    norm2=bp[f"p{pos}_n2"], eps=cfg.norm_eps)
                continue
            h = rms_norm(x, bp[f"p{pos}_n1"], cfg.norm_eps)
            if kind == "attn":
                h, new_cache[f"p{pos}"] = gqa_decode(
                    bp[f"p{pos}_mix"], h, cache[f"p{pos}"], pos_idx,
                    a=cfg.attn, dist=dist, angles_global=ag,
                    angles_local=al, is_global=flags["is_global"][pos])
            elif kind == "mla":
                h, new_cache[f"p{pos}"] = mla_decode(
                    bp[f"p{pos}_mix"], h, cache[f"p{pos}"], pos_idx,
                    m=cfg.mla, dist=dist, angles=ag)
            elif kind == "mamba":
                h, new_cache[f"p{pos}"] = mamba_decode(
                    bp[f"p{pos}_mix"], h, cache[f"p{pos}"], c=cfg.mamba,
                    dist=dist)
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn1"], cfg.norm_eps)
            x = x + h
            h = rms_norm(x, bp[f"p{pos}_n2"], cfg.norm_eps)
            h, _ = self._ffn_part(bp, pos, h)
            if cfg.post_norm:
                h = rms_norm(h, bp[f"p{pos}_pn2"], cfg.norm_eps)
            x = x + h
        return x, new_cache

    def decode_step(self, params, cache, tokens, pos):
        """One decode step.  tokens: (B,) int32 (or (B, frame_dim) frames
        for the frames frontend); pos: scalar int32.  Returns (logits
        (B, 1, V), new_cache)."""
        cfg = self.cfg
        cd = cfg.compute_dtype
        if cfg.frontend == "frames":
            x = tokens.astype(cd)[:, None] @ params["frame_proj"].astype(cd)
        else:
            x = jnp.take(params["embed"], tokens[:, None],
                         axis=0).astype(cd)
        x = self.dist.shard(x, ("dp", None, None))
        max_seq = 1
        for p, kind in enumerate(cfg.pattern):
            if kind == "attn":
                max_seq = cache[f"p{p}"]["k"].shape[2]     # (n_super,B,S,..)
                break
            if kind == "mla":
                max_seq = cache[f"p{p}"]["c_kv"].shape[2]
                break
        angles = self._angles(max_seq)
        flags = cfg.layer_flags()

        def body(x, xs):
            bp, c, fl = xs
            x, new_c = self._superblock_decode(x, bp, c, fl, angles, pos)
            return x, new_c

        x, new_cache = jax.lax.scan(body, x,
                                    (params["blocks"], cache, flags))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self.logits_last(params, x), new_cache
