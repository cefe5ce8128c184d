"""Runner of ``"runner": "sweep_moe"`` traffic: the serving sweep of
``runners/sweep.py`` on a DeepSeek-V3-shaped configuration (MLA, routed
and shared experts, leading dense layers).

The program lowers the configuration itself (``lm_bridge.serving_points``
from a ``ModelConfig`` built here out of the published keys) and prices
it with ``dse.sweep_serving``; the workload ``check`` compares with, and
the pairs one sweep prices, come from the plain lowering
``chipbench.reference.deepseek_v3``.  Everything else -- grids, vdd
redraws, cache clearing, the kept sample and its check -- is
``runners/sweep.py``'s.
"""

from __future__ import annotations

from chipbench import gen
from chipbench.reference import deepseek_v3
from chipbench.runners import sweep
from repro.core import lm_bridge
from repro.models.attention import MLAConfig
from repro.models.lm import ModelConfig
from repro.models.moe import MoEConfig


class Runner(sweep.Runner):
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.knobs = gen.load_json(f"grids/{traffic['design_grid']}.json")
        self.schedules = tuple(traffic["schedules"])
        self.objectives = tuple(traffic["objectives"])
        self.kind = "serving"
        gen_len = traffic["operating_points"]["gen"]
        self.workload = [deepseek_v3.serving_point(config, p, b, gen_len)
                         for p, b in gen.operating_points(traffic)]
        self.pairs = len(gen.design_grid(self.knobs)) * sum(
            len(ph["layers"]) for pt in self.workload for ph in pt["phases"])
        self.kept: list[tuple] = []
        self.transfer_bytes = 0
        self.sweeps = 0
        self._grids: dict[int, tuple] = {}

    def _program_workload(self):
        c = self.config
        cfg = ModelConfig(
            name=c["name"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
            d_ff=c["intermediate_size"], ffn_act="swiglu", pattern=("mla",),
            mla=MLAConfig(n_heads=c["num_attention_heads"],
                          q_lora_rank=c["q_lora_rank"],
                          kv_lora_rank=c["kv_lora_rank"],
                          qk_nope_dim=c["qk_nope_head_dim"],
                          qk_rope_dim=c["qk_rope_head_dim"],
                          v_dim=c["v_head_dim"], rope_theta=c["rope_theta"]),
            moe=MoEConfig(n_experts=c["n_routed_experts"],
                          top_k=c["num_experts_per_tok"],
                          d_ff_expert=c["moe_intermediate_size"],
                          every=c["moe_layer_freq"],
                          n_shared=c["n_shared_experts"],
                          first_dense=c["first_k_dense_replace"]))
        return lm_bridge.serving_points(
            cfg, gen.operating_points(self.traffic),
            gen_len=self.traffic["operating_points"]["gen"])


def make(config: dict, traffic: dict, seed: int) -> Runner:
    return Runner(config, traffic, seed)
