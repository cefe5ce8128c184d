"""Lower assigned-LM architectures into IMC MVM workloads (beyond-paper
extension, DESIGN.md §2): every projection of one superblock becomes a
Dense workload with B = tokens, plus an accounting of the non-MVM MACs
(attention score/value products, SSM/WKV recurrences) that are NOT
IMC-mappable — reported as coverage %.

Serving operating points
------------------------
LLM serving splits every request into two phases with very different
cost shapes: **prefill** processes the whole prompt at once (MVMs with
B = batch * prompt_len, one KV-cache write per prompt token) and
**decode** emits one token at a time (MVMs with B = batch, the whole
live KV window read back per step).  :func:`lm_imc_workloads` takes a
``phase`` and a ``ctx_len`` so both regimes lower correctly, and
:func:`serving_points` bundles the two phases of one
(prompt_len x batch x gen_len) operating point — including the
bytes-based KV-cache traffic volumes the memory hierarchy prices
(``memory.KVCacheHierarchy``) — into a ``workloads.ServingPoint`` for
``dse.sweep_serving``.

Mixture-of-experts phases are priced as layer groups
(:func:`phase_groups`): a dense prologue, the MoE block (mixers,
router, shared experts) and, per MoE position, the routed experts a
phase unit touches under balanced routing -- each touched expert a
weight set of its own, so weight writes count every expert while MACs
stay those of ``top_k`` experts per token.
"""

from __future__ import annotations

from typing import Sequence

from repro import obs
from repro.core.workloads import (Layer, LMBlockSpec, PhaseWorkload,
                                  ServingPoint, dense)
from repro.models.lm import ModelConfig


def _ffn_projections(name: str, d: int, width: int, act: str
                     ) -> list[tuple[str, int, int]]:
    """(name, in, out) of one dense FFN: up, down, then the gate of a
    gated activation."""
    projs = [(f"{name}_up", d, width), (f"{name}_down", width, d)]
    if act in ("swiglu", "geglu"):
        projs.append((f"{name}_gate", d, width))
    return projs


def _block_projections(cfg: ModelConfig, dense_ffn: bool = False
                       ) -> list[tuple[int, bool, str, int, int]]:
    """(position, routed, name, in_features, out_features) of one
    superblock, in pricing order (``name`` without its ``p<pos>.``
    tag).  ``routed`` marks a routed expert's projection (one expert's
    weights; ``top_k`` of them per token).  ``dense_ffn`` lowers MoE
    positions to the dense FFN of ``d_ff``, as the leading dense layers
    of a ``first_dense`` config run."""
    d = cfg.d_model
    projs: list[tuple[int, bool, str, int, int]] = []

    def add(pos: int, items, routed: bool = False) -> None:
        projs.extend((pos, routed, *item) for item in items)

    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            a = cfg.attn
            add(pos, [("wq", d, a.q_dim), ("wk", d, a.kv_dim),
                      ("wv", d, a.kv_dim), ("wo", a.q_dim, d)])
        elif kind == "mla":
            m = cfg.mla
            add(pos, [("wq_a", d, m.q_lora_rank),
                      ("wq_b", m.q_lora_rank, m.n_heads * m.qk_dim),
                      ("wkv_a", d, m.kv_lora_rank + m.qk_rope_dim),
                      ("wk_b", m.kv_lora_rank, m.n_heads * m.qk_nope_dim),
                      ("wv_b", m.kv_lora_rank, m.n_heads * m.v_dim),
                      ("wo", m.n_heads * m.v_dim, d)])
        elif kind == "mamba":
            c = cfg.mamba
            di, r = c.d_inner(d), c.rank(d)
            add(pos, [("in_proj", d, 2 * di),
                      ("x_proj", di, r + 2 * c.d_state),
                      ("dt_proj", r, di), ("out_proj", di, d)])
        elif kind == "rwkv6":
            # rwkv6 carries its own channel mix in place of the FFN
            add(pos, [(f"w{n}", d, d) for n in "rkvg"]
                + [("wo", d, d), ("cm_wk", d, cfg.d_ff),
                   ("cm_wv", cfg.d_ff, d), ("cm_wr", d, d)])
            continue
        if cfg.layer_is_moe(pos) and not dense_ffn:
            m = cfg.moe
            f = m.d_ff_expert
            add(pos, [("router", d, m.n_experts)])
            add(pos, [("moe_gate", d, f), ("moe_up", d, f),
                      ("moe_down", f, d)], routed=True)
            if m.n_shared:
                add(pos, _ffn_projections("shared", d, m.n_shared * f,
                                          cfg.ffn_act))
            if m.dense_residual:
                add(pos, _ffn_projections("ffn", d, cfg.d_ff, cfg.ffn_act))
        else:
            add(pos, _ffn_projections("ffn", d, cfg.d_ff, cfg.ffn_act))
    return projs


def _superblock_projections(cfg: ModelConfig) -> list[tuple[str, int, int, int]]:
    """(name, in_features, out_features, calls_per_superblock): a routed
    expert's projection is called ``top_k`` times per token."""
    k = cfg.moe.top_k if cfg.moe is not None else 1
    return [(f"p{pos}.{name}", fi, fo, k if routed else 1)
            for pos, routed, name, fi, fo in _block_projections(cfg)]


def _global_attn_frac(cfg: ModelConfig, pos: int) -> float:
    """Fraction of pattern position ``pos``'s ``n_super`` instances that
    run *global* attention.  ``layer_is_global_attn`` is defined on the
    absolute layer depth (every ``global_every``-th layer), which the
    one-superblock abstraction can't index positionally — averaging
    over the repeats keeps whole-model totals exact (all the uses are
    linear in the span)."""
    a = cfg.attn
    if a is None or not a.sliding_window:
        return 1.0
    if a.global_every <= 0:
        return 0.0
    stride = len(cfg.pattern)
    n_global = sum(1 for r in range(cfg.n_super)
                   if cfg.layer_is_global_attn(r * stride + pos))
    return n_global / cfg.n_super


def _attn_span(cfg: ModelConfig, pos: int, ctx_len: int) -> float:
    """Expected live-context span of an ``attn`` pattern position at
    ``ctx_len``: global instances see the whole context, windowed ones
    clamp at the sliding window."""
    frac = _global_attn_frac(cfg, pos)
    window = cfg.attn.sliding_window or ctx_len
    return frac * ctx_len + (1.0 - frac) * min(window, ctx_len)


def _non_mvm_macs_per_token(cfg: ModelConfig, ctx_len: int) -> float:
    """Score/value products and recurrent updates per token, per
    superblock — compute that cannot sit in an IMC array."""
    d = cfg.d_model
    total = 0.0
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            a = cfg.attn
            span = _attn_span(cfg, pos, ctx_len)
            total += 2.0 * span * a.n_heads * a.head_dim
        elif kind == "mla":
            m = cfg.mla
            total += 2.0 * ctx_len * m.n_heads * (m.qk_dim + m.v_dim) / 2
        elif kind == "mamba":
            c = cfg.mamba
            total += 4.0 * c.d_inner(d) * c.d_state
        elif kind == "rwkv6":
            total += 3.0 * d * (cfg.rwkv.head_dim)
    return total


def lm_block_spec(cfg: ModelConfig, ctx_len: int = 4096) -> LMBlockSpec:
    return LMBlockSpec(
        name=cfg.name, d_model=cfg.d_model, n_layers=cfg.n_layers,
        projections=tuple(_superblock_projections(cfg)),
        non_mvm_macs_per_token=_non_mvm_macs_per_token(cfg, ctx_len))


def lm_imc_workloads(cfg: ModelConfig, tokens: int,
                     w_prec: int = 4, i_prec: int = 4,
                     phase: str | None = None,
                     ctx_len: int = 4096) -> list[Layer]:
    """Dense workloads for ONE superblock (multiply results by
    cfg.n_super for whole-model numbers).

    ``tokens`` is the per-phase token count the MVMs batch over — for a
    serving operating point that is ``batch * prompt_len`` in prefill
    and ``batch`` (one step) in decode, never one flat per-request
    count.  ``ctx_len`` is the attention context the phase runs at; it
    threads through to :func:`lm_block_spec` so the non-MVM accounting
    (sliding-window vs global span) matches the operating point instead
    of a hardcoded 4096.  ``phase`` (``"prefill"`` / ``"decode"``) tags
    the layer names so both phases of one request coexist in a fused
    sweep; ``None`` keeps the historical flat naming.
    """
    spec = lm_block_spec(cfg, ctx_len=ctx_len)
    prefix = f"{phase}." if phase else ""
    return [dense(prefix + name, tokens * calls, fin, fout,
                  w_prec=w_prec, i_prec=i_prec)
            for (name, fin, fout, calls) in spec.projections]


# --------------------------------------------------------------------------- #
# layer groups of one phase unit (dense prologue, MoE block, routed experts)   #
# --------------------------------------------------------------------------- #
def balanced_routing(tokens: int, n_experts: int, top_k: int
                     ) -> tuple[tuple[int, int], ...]:
    """((tokens per expert, experts), ...) of ``tokens * top_k`` routing
    assignments spread as evenly as they go over ``n_experts``: they
    touch ``n = min(n_experts, tokens * top_k)`` experts, ``r`` of which
    get ``q + 1`` tokens and ``n - r`` get ``q`` (``q, r = divmod(A,
    n)``).  Empty classes are left out, the larger class comes first."""
    a = tokens * top_k
    n = min(n_experts, a)
    q, r = divmod(a, n)
    return tuple((b, c) for b, c in ((q + 1, r), (q, n - r)) if c)


def phase_groups(cfg: ModelConfig, tokens: int, phase: str,
                 w_prec: int = 4, i_prec: int = 4
                 ) -> list[tuple[str, tuple[Layer, ...], int]]:
    """(group, layers, superblocks) of ONE phase unit of ``tokens``
    tokens, in pricing order; the group's repeats are its superblock
    count (times ``gen_len`` in decode).

    Without MoE: one unnamed group, :func:`lm_imc_workloads` over
    ``n_super`` superblocks.  With MoE every touched expert is a weight
    set of its own, as in a deployment:

    * ``dense``: the ``first_dense`` leading layers with their dense FFN;
    * ``moe``: every projection of the remaining superblocks but the
      routed experts' (mixers, router, shared experts, dense FFNs of the
      non-MoE positions and a dense residual);
    * ``p<pos>.routed.b<B>``: per MoE position, one class of
      :func:`balanced_routing` -- one expert's gate, up and down at
      ``B`` tokens, repeated over the class's experts and the ``moe``
      group's superblocks.  Its MACs equal ``top_k`` calls per token.
    """
    if cfg.moe is None:
        return [("", tuple(lm_imc_workloads(
            cfg, tokens, w_prec=w_prec, i_prec=i_prec, phase=phase)),
            cfg.n_super)]
    m = cfg.moe

    def lower(group: str, projs, b: int) -> tuple[Layer, ...]:
        return tuple(dense(f"{phase}.{group}.{name}", b, fi, fo,
                           w_prec=w_prec, i_prec=i_prec)
                     for name, fi, fo in projs)

    groups = []
    if m.first_dense:
        groups.append(("dense", lower("dense", [
            (f"p{pos}.{name}", fi, fo) for pos, _, name, fi, fo
            in _block_projections(cfg, dense_ffn=True)], tokens),
            m.first_dense))
    block = _block_projections(cfg)
    n_moe = cfg.n_super - m.first_dense
    groups.append(("moe", lower("moe", [
        (f"p{pos}.{name}", fi, fo) for pos, routed, name, fi, fo in block
        if not routed], tokens), n_moe))
    for pos in range(len(cfg.pattern)):
        routed = [(name, fi, fo) for p, r, name, fi, fo in block
                  if r and p == pos]
        if not routed:
            continue
        for b, count in balanced_routing(tokens, m.n_experts, m.top_k):
            group = f"p{pos}.routed.b{b}"
            groups.append((group, lower(group, routed, b), n_moe * count))
    return groups


# --------------------------------------------------------------------------- #
# KV-cache byte accounting (bytes-based hierarchy, per phase)                  #
# --------------------------------------------------------------------------- #
def _cache_itemsize(cfg: ModelConfig) -> int:
    import jax.numpy as jnp
    return jnp.dtype(cfg.cache_dtype).itemsize


def kv_slot_bytes(cfg: ModelConfig) -> float:
    """Cache bytes appended per token, per superblock: attention K+V
    slots and MLA latents grow with context; pure-SSM blocks contribute
    0 (their state is ctx-independent — see :func:`kv_state_bytes`).
    Matches ``LM.cache_specs`` elementwise (same dims, same
    ``cache_dtype``)."""
    e = _cache_itemsize(cfg)
    total = 0.0
    for kind in cfg.pattern:
        if kind == "attn":
            total += 2.0 * cfg.attn.kv_dim * e
        elif kind == "mla":
            total += float(cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim) * e
    return total


def kv_state_bytes(cfg: ModelConfig) -> float:
    """Ctx-independent recurrent state bytes per sequence, per
    superblock (Mamba ``h`` is f32 + conv tail in ``cache_dtype``,
    RWKV6 ``state`` is f32 + two token shifts — mirrors
    ``ssm.mamba_cache_specs`` / ``rwkv6_cache_specs``)."""
    d, e = cfg.d_model, _cache_itemsize(cfg)
    total = 0.0
    for kind in cfg.pattern:
        if kind == "mamba":
            c = cfg.mamba
            di = c.d_inner(d)
            total += di * c.d_state * 4.0 + (c.d_conv - 1) * di * e
        elif kind == "rwkv6":
            c = cfg.rwkv
            total += (c.n_heads(d) * c.head_dim * c.head_dim * 4.0
                      + 2.0 * d * e)
    return total


def _window_spans(cfg: ModelConfig, ctx_len: int) -> list[float]:
    """Effective live-slot span per attention-family position of one
    superblock at context ``ctx_len`` (sliding-window layers saturate
    at their window, averaged with their ``global_every`` instances;
    MLA and global attention hold the whole context)."""
    spans: list[float] = []
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            spans.append(_attn_span(cfg, pos, ctx_len))
        elif kind == "mla":
            spans.append(float(ctx_len))
    return spans


def _slot_bytes_per_pos(cfg: ModelConfig) -> list[float]:
    """Per-token slot bytes per attention-family position, aligned with
    :func:`_window_spans`."""
    e = _cache_itemsize(cfg)
    out = []
    for kind in cfg.pattern:
        if kind == "attn":
            out.append(2.0 * cfg.attn.kv_dim * e)
        elif kind == "mla":
            out.append(float(cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim) * e)
    return out


def kv_live_bytes(cfg: ModelConfig, ctx_len: int, batch: int = 1) -> float:
    """Live KV working set across the whole model at context ``ctx_len``
    — the quantity the hierarchy's tier selection compares against its
    buffer/HBM capacities.  Sliding-window layers only keep their
    window live; recurrent state is always live."""
    per_super = sum(s * b for s, b in zip(_window_spans(cfg, ctx_len),
                                          _slot_bytes_per_pos(cfg)))
    per_super += kv_state_bytes(cfg)
    return batch * cfg.n_super * per_super


def _span_sum(lo: int, hi: int, window: int) -> float:
    """sum_{t=lo..hi} min(t, window) in closed form (t = live context
    when the t-th token attends; ``window`` clamps sliding layers)."""
    if hi < lo:
        return 0.0
    if window >= hi:                      # never clamped
        return (hi * (hi + 1) - (lo - 1) * lo) / 2.0
    if window <= lo:                      # always clamped
        return float(window) * (hi - lo + 1)
    head = (window * (window + 1) - (lo - 1) * lo) / 2.0
    return head + float(window) * (hi - window)


def kv_phase_traffic(cfg: ModelConfig, phase: str, prompt_len: int,
                     batch: int, gen_len: int = 1) -> tuple[float, float]:
    """Whole-model (read_bytes, write_bytes) KV-cache traffic of one
    serving phase.

    * **prefill**: every prompt token appends its slot once (write =
      prompt cache build); causal attention reads the growing prefix,
      so reads sum ``min(t, window)`` slots over t = 1..prompt_len per
      layer.  Recurrent state is written once per sequence.
    * **decode**: each of the ``gen_len`` steps reads the whole live
      window (context grows prompt_len..prompt_len+gen_len-1) and
      appends one slot; recurrent state is read and fully rewritten
      every step.
    """
    slot_b = _slot_bytes_per_pos(cfg)
    state_b = kv_state_bytes(cfg)
    mix: list[tuple[float, int]] = []   # (global frac, window), per slot pos
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            mix.append((_global_attn_frac(cfg, pos),
                        cfg.attn.sliding_window or 0))
        elif kind == "mla":
            mix.append((1.0, 0))

    def span_reads(lo: int, hi: int) -> float:
        total = 0.0
        for b, (frac, w) in zip(slot_b, mix):
            full = _span_sum(lo, hi, hi)          # never clamped
            clamped = _span_sum(lo, hi, w) if w else full
            total += b * (frac * full + (1.0 - frac) * clamped)
        return total

    if phase == "prefill":
        reads = span_reads(1, prompt_len)
        # every prompt token's slot is written once, window or not (the
        # eviction of old slots is free; only live slots are re-read)
        writes = sum(b * prompt_len for b in slot_b) + state_b
    elif phase == "decode":
        reads = span_reads(prompt_len, prompt_len + gen_len - 1)
        reads += state_b * gen_len
        writes = sum(b * gen_len for b in slot_b) + state_b * gen_len
    else:
        raise ValueError(f"unknown phase {phase!r}")
    return (batch * cfg.n_super * reads, batch * cfg.n_super * writes)


# --------------------------------------------------------------------------- #
# operating-point assembly                                                     #
# --------------------------------------------------------------------------- #
def serving_points(cfg: ModelConfig,
                   grid: Sequence[tuple[int, int]],
                   gen_len: int = 128,
                   w_prec: int = 4, i_prec: int = 4
                   ) -> tuple[ServingPoint, ...]:
    """Build the (prompt_len x batch) operating-point grid of one LM as
    phase-split :class:`~repro.core.workloads.ServingPoint` bundles.

    Each phase is one :class:`PhaseWorkload` per layer group of
    :func:`phase_groups` (one group without MoE): prefill's unit is the
    whole prompt at B = batch * prompt_len, repeated over the group's
    superblocks; decode's is ONE step at B = batch, repeated over the
    group's superblocks times ``gen_len``.  A phase's first group
    carries its whole-phase KV-cache byte volumes at that point's
    context, and decode's first group the generated tokens.  Feed the
    tuple straight to ``dse.sweep_serving``.
    """
    with obs.span("lm_bridge.serving_points", points=len(grid)) as sp:
        points = []
        groups = entries = touched = 0
        for prompt_len, batch in grid:
            ctx = prompt_len + gen_len
            phases = []
            for phase, tokens, steps, live_ctx, out in (
                    ("prefill", batch * prompt_len, 1, prompt_len, 0.0),
                    ("decode", batch, gen_len, ctx,
                     float(batch) * gen_len)):
                kv_r, kv_w = kv_phase_traffic(cfg, phase, prompt_len, batch,
                                              gen_len=steps)
                for i, (group, layers, supers) in enumerate(phase_groups(
                        cfg, tokens, phase, w_prec=w_prec, i_prec=i_prec)):
                    first = i == 0
                    phases.append(PhaseWorkload(
                        phase=phase, layers=layers,
                        repeats=float(supers) * steps, group=group,
                        kv_read_bytes=kv_r if first else 0.0,
                        kv_write_bytes=kv_w if first else 0.0,
                        kv_live_bytes=(kv_live_bytes(cfg, live_ctx, batch)
                                       if first else 0.0),
                        tokens_out=out if first else 0.0))
                    entries += len(layers)
                if cfg.moe is not None:
                    touched = max(touched, min(cfg.moe.n_experts,
                                               tokens * cfg.moe.top_k))
            groups += len(phases)
            points.append(ServingPoint(
                name=f"{cfg.name}/p{prompt_len}xb{batch}",
                prompt_len=prompt_len, batch=batch, gen_len=gen_len,
                phases=tuple(phases)))
        sp.set(groups=groups, entries=entries, experts_touched=touched)
    return tuple(points)
