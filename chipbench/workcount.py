"""Work counts taken from the configurations' shapes, not from the
program: the pairs one sweep prices."""

from __future__ import annotations

from chipbench import gen
from chipbench.reference import imc


def sweep_pairs(config: dict, traffic: dict) -> int:
    """(IMC-eligible layer instance, design) pairs whose best (mapping,
    dataflow) one sweep finds."""
    knobs = gen.load_json(f"grids/{traffic['design_grid']}.json")
    designs = len(gen.design_grid(knobs))
    if config["kind"] == "lm":
        op = traffic["operating_points"]
        layers = sum(len(ph["layers"])
                     for p, b in gen.operating_points(traffic)
                     for ph in imc.serving_point(config, p, b,
                                                 op["gen"])["phases"])
    else:
        layers = sum(1 for _, ls in gen.network_layers(config)
                     for l in ls if l["imc_eligible"])
    return layers * designs
