"""deepseek-v3 [moe] — 61L d_model=7168 MLA 128H (q_lora 1536, kv_lora
512, qk 128+64, v 128) vocab=129280; first 3 layers dense (d_ff=18432),
then 58 MoE layers of 256 routed experts (width 2048, top-8) and 1
shared expert.  [hf:deepseek-ai/DeepSeek-V3]

Priced only (``core.lm_bridge.serving_points``): ``LM`` has no shared
expert and no dense prologue and refuses this config, so it is not in
``ARCH_IDS`` (every member there runs through ``LM``).  The MTP module
is not modelled."""

from repro.models.attention import MLAConfig
from repro.models.lm import ModelConfig
from repro.models.moe import MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3",
        d_model=7168, n_layers=61, vocab_size=129280, d_ff=18432,
        ffn_act="swiglu", pattern=("mla",),
        mla=MLAConfig(n_heads=128, q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_dim=128, qk_rope_dim=64, v_dim=128,
                      rope_theta=1e4),
        moe=MoEConfig(n_experts=256, top_k=8, d_ff_expert=2048, every=1,
                      n_shared=1, first_dense=3),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-smoke",
        d_model=64, n_layers=4, vocab_size=512, d_ff=96,
        ffn_act="swiglu", pattern=("mla",),
        mla=MLAConfig(n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                      qk_nope_dim=8, qk_rope_dim=4, v_dim=8,
                      rope_theta=1e4),
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, every=1,
                      n_shared=1, first_dense=1),
        vocab_pad_multiple=16,
    )
