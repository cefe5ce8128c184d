"""Runner of ``"runner": "sweep"`` traffic: repeated fused design-space
sweeps of one configuration on one design grid.

A ``networks`` configuration goes through ``dse.sweep_networks``; an
``lm`` configuration is lowered to serving operating points by the
program (``lm_bridge.serving_points``) and goes through
``dse.sweep_serving``.  The traffic file says whether caches are cleared
before each sweep, whether the grid's vdd levels are redrawn per sweep,
and which objectives the sweeps cycle through.

Work is counted in (IMC-eligible layer instance, design) pairs, which
the input fixes (``chipbench.workcount``).  The window keeps a seeded
reservoir sample of its sweeps' answers (no work beyond holding them);
after the window, each kept answer is read at a few designs drawn from
the seed and the sweep's index, the same number of AIMC and DIMC designs
spread over the grid -- the program's totals and its winning (mapping,
dataflow) per layer -- and ``check`` compares them with the plain
reference.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import gen, oracle, workcount
from chipbench.reference import imc
from repro import obs
from repro.core import dse, lm_bridge
from repro.core.designs import MacroBatch
from repro.core.hardware import IMCMacro, IMCType
from repro.core.workloads import Layer
from repro.models.attention import AttnConfig
from repro.models.lm import ModelConfig

#: distinct vdd draws a redrawing traffic cycles through
GRID_POOL = 16
#: designs of each macro type (AIMC, DIMC) at which ``check`` reads a
#: kept sweep
CHECK_DESIGNS_PER_TYPE = 2


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.knobs = gen.load_json(f"grids/{traffic['design_grid']}.json")
        self.schedules = tuple(traffic["schedules"])
        self.objectives = tuple(traffic["objectives"])
        self.kind = "serving" if config["kind"] == "lm" else "networks"
        if self.kind == "serving":
            self.workload = [imc.serving_point(config, p, b,
                                               traffic["operating_points"]
                                               ["gen"])
                             for p, b in gen.operating_points(traffic)]
        else:
            self.workload = gen.network_layers(config)
        self.pairs = workcount.sweep_pairs(config, traffic)
        self.kept: list[tuple] = []
        self.transfer_bytes = 0
        self.sweeps = 0
        self._grids: dict[int, tuple] = {}

    # -- inputs ------------------------------------------------------------ #
    def _grid(self, index: int) -> tuple[list[dict], object]:
        """(design dicts, program MacroBatch) for sweep ``index``: one of
        ``GRID_POOL`` vdd draws in turn (making a grid takes about a
        third of a sweep, so the pool is made in set-up)."""
        key = index % GRID_POOL if self.traffic["redraw_vdd"] else -1
        if key not in self._grids:
            vdd = gen.draw_vdd(self.traffic, self.seed, max(key, 0))
            designs = gen.design_grid(self.knobs, vdd)
            batch = MacroBatch.from_macros([IMCMacro(
                name=d["name"], imc_type=IMCType("aimc" if d["analog"]
                                                 else "dimc"),
                rows=d["rows"], cols=d["cols"], tech_nm=d["tech_nm"],
                vdd=d["vdd"], bw=d["bw"], bi=d["bi"], adc_res=d["adc_res"],
                dac_res=d["dac_res"], m_mux=d["m_mux"],
                n_macros=d["n_macros"], cols_per_adc=d["cols_per_adc"],
                adc_share=d["adc_share"], booth=d["booth"])
                for d in designs])
            self._grids[key] = (designs, batch)
        return self._grids[key]

    def _program_workload(self):
        if self.kind == "networks":
            return [(name, [Layer(l["name"], l["type"], dict(l["dims"]),
                                  w_prec=l["w_prec"], i_prec=l["i_prec"],
                                  psum_prec=l["psum_prec"],
                                  imc_eligible=l["imc_eligible"])
                            for l in layers])
                    for name, layers in self.workload]
        c = self.config
        cfg = ModelConfig(
            name=c["name"], d_model=c["hidden_size"], n_layers=c["num_layers"],
            vocab_size=c["padded_vocab_size"], d_ff=c["ffn_hidden_size"],
            ffn_act="swiglu", pattern=("attn",),
            attn=AttnConfig(n_heads=c["num_attention_heads"],
                            n_kv_heads=c["multi_query_group_num"],
                            head_dim=c["kv_channels"],
                            qkv_bias=c["add_qkv_bias"],
                            rope_theta=c["rope_theta"]))
        return lm_bridge.serving_points(
            cfg, gen.operating_points(self.traffic),
            gen_len=self.traffic["operating_points"]["gen"])

    # -- the program ------------------------------------------------------- #
    def _sweep(self, batch, objective: str):
        if self.kind == "serving":
            return dse.sweep_serving(self.program_workload, batch,
                                     objective=objective,
                                     schedules=self.schedules)
        return dse.sweep_networks(self.program_workload, batch,
                                  objective=objective,
                                  schedules=self.schedules)

    def setup(self) -> None:
        """Make the inputs and run two sweeps per objective: the first
        compiles every kernel shape the window uses (vdd changes no
        shape), the second finds the host path warm."""
        self.program_workload = self._program_workload()
        for i in range(GRID_POOL if self.traffic["redraw_vdd"] else 1):
            self._grid(i)
        _, batch = self._grid(0)
        for obj in self.objectives * 2:
            if self.traffic["clear_caches"]:
                dse.cache_clear()
            self._sweep(batch, obj)

    def step(self, index: int) -> int:
        """One sweep; returns the pairs it priced."""
        designs, batch = self._grid(index)
        objective = self.objectives[index % len(self.objectives)]
        with jax.profiler.TraceAnnotation("cache_clear"):
            if self.traffic["clear_caches"]:
                dse.cache_clear()
            before = obs.snapshot("dse.transfer_bytes").get(
                "dse.transfer_bytes", 0)
        with jax.profiler.TraceAnnotation("sweep"):
            results = self._sweep(batch, objective)
        self.transfer_bytes += obs.snapshot("dse.transfer_bytes").get(
            "dse.transfer_bytes", 0) - before
        entry = (index, results, designs, objective)
        k = self.traffic["check_records"]
        if len(self.kept) < k:
            self.kept.append(entry)
        else:
            j = int(gen.rng(self.seed, 3, index).integers(index + 1))
            if j < k:
                self.kept[j] = entry
        self.sweeps += 1
        return self.pairs

    def records(self) -> list[dict]:
        """The kept answers, each read at ``CHECK_DESIGNS_PER_TYPE``
        seeded designs of each macro type: one from each of as many equal
        slices of that type's designs in grid order, so the sample spans
        the grid's rows, columns and nodes."""
        out = []
        for index, results, designs, objective in self.kept:
            g = gen.rng(self.seed, 2, index)
            for analog in (True, False):
                idx = [i for i, d in enumerate(designs)
                       if d["analog"] == analog]
                for part in np.array_split(idx, CHECK_DESIGNS_PER_TYPE):
                    d = int(part[g.integers(len(part))])
                    out.append(dict(self._record(results, designs[d], d,
                                                 objective), sweep=index))
        return out

    def _record(self, results, design: dict, d: int, objective: str) -> dict:
        def winners(sweep_result) -> list:
            return [({"cols": dict(l.cost.mapping.cols),
                      "rows": dict(l.cost.mapping.rows),
                      "macros": dict(l.cost.mapping.macros)},
                     l.cost.schedule.name)
                    for l in sweep_result.network_result(d).layers]

        if self.kind == "networks":
            return {"kind": "networks", "design": design,
                    "objective": objective,
                    "networks": [{"name": r.network,
                                  "energy_fj": float(r.energy_fj[d]),
                                  "cycles": int(r.cycles[d]),
                                  "winners": winners(r)} for r in results]}
        return {"kind": "serving", "design": design, "objective": objective,
                "points": [{"name": r.point.name,
                            "values": {c: float(getattr(r, c)[d])
                                       for c in oracle.SERVING_COLUMNS},
                            "phases": [winners(s) for s in r.phase_sweeps]}
                           for r in results]}

    def release(self) -> None:
        dse.cache_clear()
        self._grids.clear()
        self.program_workload = None

    # -- correctness ------------------------------------------------------- #
    def check(self) -> dict:
        """``max_rel_dev`` over the kept sample of the window's answers."""
        t0 = time.perf_counter()
        got = [dict(oracle.compare(r, self.workload, self.schedules),
                    sweep=r["sweep"]) for r in self.records()]
        worst = max(got, key=lambda g: g["max_rel_dev"], default=None)
        value = worst["max_rel_dev"] if worst else float("inf")
        return {"numbers": {"max_rel_dev": (value, oracle.MAX_REL_DEV_LIMIT)},
                "detail": {"records": len(got),
                           "pairs": sum(g["pairs"] for g in got),
                           "where": (f"sweep {worst['sweep']}: "
                                     f"{worst['where']}" if worst
                                     else "no sweep completed"),
                           "reference_s": time.perf_counter() - t0}}

    def stats(self) -> dict:
        return {"sweeps": self.sweeps, "transfer_bytes": self.transfer_bytes}


def make(config: dict, traffic: dict, seed: int) -> Runner:
    return Runner(config, traffic, seed)
