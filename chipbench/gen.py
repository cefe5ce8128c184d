"""Input generation from the benchmark's data files and ``--seed``.

Everything the program is asked to price is made here, as plain dicts
the reference reads directly; ``runners`` turn them into the program's
own types.  The design-grid walk copies the order of the program's
``designs.macro_grid`` (imc type outer, then rows, cols, bw, bi,
n_macros, tech_nm, vdd, then the type's own knobs), so design ``d`` is
the same design on both sides.
"""

from __future__ import annotations

import itertools
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Seeded generator for one purpose (``stream``); any whole seed,
    negative or past 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def design_grid(knobs: dict, vdd: tuple[float, ...] | None = None
                ) -> list[dict]:
    """Expand knob ranges into deduplicated design dicts."""
    vdds = tuple(knobs["vdd"]) if vdd is None else tuple(vdd)
    out, seen = [], set()
    for t in knobs["imc_type"]:
        analog = t == "aimc"
        for r, c, w, i, nm, tn, v in itertools.product(
                knobs["rows"], knobs["cols"], knobs["bw"], knobs["bi"],
                knobs["n_macros"], knobs["tech_nm"], vdds):
            if c % w:
                continue
            if analog:
                axes = itertools.product(knobs["adc_bits"], knobs["dac_bits"],
                                         knobs["cols_per_adc"],
                                         knobs["adc_share"])
            else:
                axes = itertools.product(knobs["m_mux"], knobs["booth"])
            for spec in axes:
                if analog:
                    adc, dac, cpa, share = spec
                    m, booth = 1, False
                    if adc <= 0 or dac <= 0:
                        continue
                else:
                    m, booth = spec
                    adc = dac = 0
                    cpa, share = 1, 8
                    if r % m:
                        continue
                key = (t, r, c, w, i, adc, dac, m, nm, cpa, share, booth,
                       tn, v)
                if key in seen:
                    continue
                seen.add(key)
                if analog:
                    tag = f"a{adc}d{dac}" + (f"p{cpa}" if cpa != 1 else "") \
                        + (f"s{share}" if share != 8 else "")
                else:
                    tag = f"m{m}" + ("b" if booth else "")
                out.append({
                    "name": f"grid-{t}-r{r}c{c}w{w}i{i}-{tag}-x{nm}-{tn:g}nm"
                            f"-{v:g}V",
                    "analog": analog, "rows": r, "cols": c, "bw": w, "bi": i,
                    "adc_res": adc, "dac_res": dac, "m_mux": m,
                    "n_macros": nm, "cols_per_adc": cpa, "adc_share": share,
                    "booth": booth, "tech_nm": tn, "vdd": v})
    return out


def draw_vdd(traffic: dict, seed: int, index: int) -> tuple[float, float]:
    """The grid's two supply levels for sweep ``index`` (a low and a high
    one, so the grid keeps its two distinct levels)."""
    g = rng(seed, 1, index)
    return (float(g.uniform(*traffic["vdd_low"])),
            float(g.uniform(*traffic["vdd_high"])))


def network_layers(config: dict) -> list[tuple[str, list[dict]]]:
    """(network, layers) pairs of a ``networks`` configuration."""
    return [(name, [dict(l, imc_eligible=l.get("imc_eligible", True))
                    for l in layers])
            for name, layers in config["networks"].items()]


def operating_points(traffic: dict) -> list[tuple[int, int]]:
    op = traffic["operating_points"]
    return [(p, b) for p in op["prompts"] for b in op["batches"]]
