"""What decides ``correct`` in the sweep cells: the seeded sample of a
window's answers, and its comparison with the plain reference.

One number is compared, ``max_rel_dev``: the largest relative deviation
from the float64 reference over everything sampled --

* each network total (energy and cycles) of a sampled (sweep, design),
  or each serving column (MVM energy, KV energy, cycles, tokens/s,
  J/token) of every operating point on it;
* the regret of every layer's winning (mapping, dataflow): the
  reference's objective at the program's winner against the reference's
  own minimum, so a tie broken the other way reads 0 while a wrong
  winner reads its whole excess.  A winner the reference finds illegal
  reads infinity.

The control is the same reference run one precision lower (float32) in
the program's place (:func:`reference_record`).
"""

from __future__ import annotations

import math

from chipbench.reference import imc

#: limit on ``max_rel_dev``, between the program's readings on the chip
#: (emulated float64) and the float32 control's; PERF.md gives both.
MAX_REL_DEV_LIMIT = 1e-10

SERVING_COLUMNS = ("energy_fj", "kv_energy_fj", "cycles", "tokens_per_s",
                   "j_per_token")


def _rel(got, want) -> float:
    """Relative deviation of ``got`` from ``want``; a ``got`` that is not
    a finite number (NaN included) reads infinity."""
    got, want = float(got), float(want)
    if not math.isfinite(got):
        return math.inf
    if got == want:
        return 0.0
    return abs(got - want) / abs(want) if want else math.inf


def _winners_of(net: dict) -> list:
    return [(w["mapping"], w["schedule"]) for w in net["layers"]]


def reference_record(kind: str, workload, design: dict, objective: str,
                     precision: str = "float64",
                     schedules=imc.SCHEDULES) -> dict:
    """What the reference answers for one (design, objective), in the
    shape the runners record the program's answers."""
    m = imc.Macro(design, imc.PRECISIONS[precision])
    if kind == "networks":
        memo: dict = {}
        nets = []
        for name, layers in workload:
            net = imc.network(layers, m, objective, schedules, memo)
            nets.append({"name": name, "energy_fj": net["energy_fj"],
                         "cycles": net["cycles"], "winners": _winners_of(net)})
        return {"kind": kind, "design": design, "objective": objective,
                "networks": nets}
    points, memo = [], {}
    for pt in workload:
        res = imc.serve(pt, m, objective, schedules, memo)
        points.append({"name": pt["name"],
                       "values": {c: res[c] for c in SERVING_COLUMNS},
                       "phases": [_winners_of(n) for n in res["phases"]]})
    return {"kind": kind, "design": design, "objective": objective,
            "points": points}


def _regret(layers, winners, m: imc.Macro, objective: str, memo: dict,
            schedules) -> tuple[float, int]:
    if len(winners) != len(layers):
        return math.inf, len(layers)
    worst = 0.0
    for layer, (mapping, sched) in zip(layers, winners):
        key = (imc.shape_key(layer), objective)
        if key not in memo:
            memo[key] = imc.best_mapping(layer, m, objective, schedules)
        best = imc.objective_value(memo[key], objective)
        if not imc.is_legal(layer, m, mapping):
            return math.inf, len(layers)
        got = imc.objective_value(imc.evaluate(layer, m, mapping, sched),
                                  objective)
        dev = _rel(got, best)
        if not dev <= worst:
            worst = dev
    return worst, len(layers)


def compare(record: dict, workload, schedules=imc.SCHEDULES) -> dict:
    """``max_rel_dev`` of one recorded answer against the float64
    reference, with where it was worst and how many (layer, design)
    pairs it covered."""
    m = imc.Macro(record["design"], float)
    obj = record["objective"]
    memo: dict = {}
    worst, where, pairs = 0.0, "", 0

    def note(dev: float, at: str):
        nonlocal worst, where
        if not dev <= worst or not where:
            worst, where = dev, at

    answers = record["networks" if record["kind"] == "networks"
                     else "points"]
    if len(answers) != len(workload):
        return {"max_rel_dev": math.inf, "where": "answer count",
                "pairs": 0}
    if record["kind"] == "networks":
        for (name, layers), got in zip(workload, answers):
            eligible = [l for l in layers if l.get("imc_eligible", True)]
            ref = imc.network(eligible, m, obj, schedules, memo)
            note(_rel(got["energy_fj"], ref["energy_fj"]), f"{name}/energy")
            note(_rel(got["cycles"], ref["cycles"]), f"{name}/cycles")
            dev, n = _regret(eligible, got["winners"], m, obj, memo,
                             schedules)
            note(dev, f"{name}/winners")
            pairs += n
    else:
        for pt, got in zip(workload, answers):
            ref = imc.serve(pt, m, obj, schedules, memo)
            for c in SERVING_COLUMNS:
                note(_rel(got["values"][c], ref[c]), f"{pt['name']}/{c}")
            if len(got["phases"]) != len(pt["phases"]):
                note(math.inf, f"{pt['name']}/phases")
            for ph, winners in zip(pt["phases"], got["phases"]):
                dev, n = _regret(ph["layers"], winners, m, obj, memo,
                                 schedules)
                note(dev, f"{pt['name']}/{ph['phase']}/winners")
                pairs += n
    return {"max_rel_dev": worst, "where": where, "pairs": pairs}
