"""Plain lowering of a DeepSeek-V3 ``config.json`` into the serving
points :func:`chipbench.reference.imc.serve` prices.

It reads the published keys directly and imports nothing of the program.
One phase unit (the whole prompt in prefill, one step in decode) of
``T`` tokens is priced as layer groups, each with its own repeats:

* ``dense``: the ``first_k_dense_replace`` leading layers -- MLA in its
  projection form (``wq_a``, ``wq_b``, ``wkv_a``, ``wk_b``, ``wv_b``,
  ``wo``) and the SwiGLU FFN of ``intermediate_size`` (up, down, gate);
* ``moe``: the other layers' MLA, the router (hidden -> experts) and the
  shared experts as one SwiGLU FFN of ``n_shared_experts *
  moe_intermediate_size``;
* ``p0.routed.b<B>``: routed experts, each a weight set of its own.
  ``T * top_k`` assignments land on ``n = min(experts, T * top_k)``
  experts, ``A mod n`` of them with one token more; each class is one
  expert's gate, up and down at ``B`` tokens, repeated over the class's
  experts and the MoE layers.

A decode group repeats ``gen`` times more.  The phase's KV-cache bytes
(the MLA latent and rope key of every layer) and decode's generated
tokens sit on the phase's first group.
"""

from __future__ import annotations

from chipbench.reference.imc import _span_sum

#: weight and input bits, partial-sum bits, KV-cache bytes per element
W_PREC, I_PREC, PSUM_PREC, KV_ITEMSIZE = 4, 4, 24, 2


def _dense(name: str, tokens: int, fin: int, fout: int) -> dict:
    return {"name": name, "dims": {"B": tokens, "K": fout, "C": fin},
            "w_prec": W_PREC, "i_prec": I_PREC, "psum_prec": PSUM_PREC}


def _mla(c: dict) -> list[tuple[str, int, int]]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return [("wq_a", d, c["q_lora_rank"]),
            ("wq_b", c["q_lora_rank"], h * qk),
            ("wkv_a", d, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            ("wk_b", c["kv_lora_rank"], h * c["qk_nope_head_dim"]),
            ("wv_b", c["kv_lora_rank"], h * c["v_head_dim"]),
            ("wo", h * c["v_head_dim"], d)]


def _swiglu(name: str, d: int, width: int) -> list[tuple[str, int, int]]:
    return [(f"{name}_up", d, width), (f"{name}_down", width, d),
            (f"{name}_gate", d, width)]


def routed_classes(tokens: int, experts: int, top_k: int
                   ) -> list[tuple[int, int]]:
    """(tokens per expert, experts) of an even spread, larger first."""
    assignments = tokens * top_k
    touched = min(experts, assignments)
    per = assignments // touched
    heavy = assignments - per * touched
    out = []
    if heavy:
        out.append((per + 1, heavy))
    out.append((per, touched - heavy))
    return out


def groups(c: dict, tokens: int, phase: str) -> list[tuple[str, list, int]]:
    """(group, layers, layers of the model it stands for) of one unit."""
    d = c["hidden_size"]
    dense_n = c["first_k_dense_replace"]
    moe_n = c["num_hidden_layers"] - dense_n
    f = c["moe_intermediate_size"]
    mla = [(f"p0.{n}", fi, fo) for n, fi, fo in _mla(c)]
    dense = mla + [(f"p0.{n}", fi, fo) for n, fi, fo
                   in _swiglu("ffn", d, c["intermediate_size"])]
    moe = mla + [("p0.router", d, c["n_routed_experts"])] + [
        (f"p0.{n}", fi, fo) for n, fi, fo
        in _swiglu("shared", d, c["n_shared_experts"] * f)]
    expert = [("moe_gate", d, f), ("moe_up", d, f), ("moe_down", f, d)]
    out = []
    if dense_n:
        out.append(("dense", [_dense(f"{phase}.dense.{n}", tokens, fi, fo)
                              for n, fi, fo in dense], dense_n))
    out.append(("moe", [_dense(f"{phase}.moe.{n}", tokens, fi, fo)
                        for n, fi, fo in moe], moe_n))
    for b, count in routed_classes(tokens, c["n_routed_experts"],
                                   c["num_experts_per_tok"]):
        g = f"p0.routed.b{b}"
        out.append((g, [_dense(f"{phase}.{g}.{n}", b, fi, fo)
                        for n, fi, fo in expert], moe_n * count))
    return out


def serving_point(c: dict, prompt_len: int, batch: int, gen: int) -> dict:
    """Both phases of one (prompt, batch, gen) point, one entry of
    ``phases`` per layer group, with the KV-cache byte volumes (whole
    model, whole phase) on each phase's first group."""
    layers = c["num_hidden_layers"]
    slot = float(c["kv_lora_rank"] + c["qk_rope_head_dim"]) * KV_ITEMSIZE
    ctx = prompt_len + gen
    kv = {"prefill": (batch * layers * (slot * _span_sum(1, prompt_len)),
                      batch * layers * (slot * prompt_len),
                      batch * layers * (float(prompt_len) * slot)),
          "decode": (batch * layers * (slot * _span_sum(prompt_len,
                                                        ctx - 1)),
                     batch * layers * (slot * gen),
                     batch * layers * (float(ctx) * slot))}
    phases = []
    for phase, tokens, steps in (("prefill", batch * prompt_len, 1),
                                 ("decode", batch, gen)):
        for i, (g, ls, n) in enumerate(groups(c, tokens, phase)):
            read, write, live = kv[phase] if i == 0 else (0.0, 0.0, 0.0)
            phases.append({"phase": phase, "group": g, "layers": ls,
                           "repeats": float(n * steps), "kv_read": read,
                           "kv_write": write, "kv_live": live})
    return {"name": f"{c['name']}/p{prompt_len}xb{batch}",
            "tokens_out": float(batch * gen), "phases": phases}
