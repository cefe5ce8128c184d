"""Chaos harness: the fused DSE sweep and the serving loop re-run under
injected faults, across a fault-rate axis — how gracefully does each
IMC style degrade, and what does the fleet's availability cost?

Three legs per fault rate (all driven by ``repro.faults``):

* **degraded sweeps** — every tinyMLPerf workload re-priced through
  ``dse.sweep_networks(faults=FaultSpec(rate))``: stuck column groups
  and dead macros shrink each design's legal mapping set (survivor
  masks AND into the lattice's ``legal`` plane; the cost kernels and
  jit graphs are untouched), so the per-network winner and Pareto
  count move only through *mapping pressure*.
* **degraded serving** — one LLM operating point through
  ``dse.sweep_serving(faults=...)``; winner, tokens/s and J/token per
  rate.
* **resilient-serve episode** — a model-free ``ServeLoop`` driven by a
  seeded :class:`repro.faults.NodeFailureTrace` at the same rate:
  transients retry with backoff, sticky node losses escalate through
  ``plan_resize`` recovery; availability, MTTR and goodput land in the
  artifact via the ``repro.obs`` registry.

The headline is the *flip report*: for every (workload | operating
point), the rate at which the energy winner first changes vs the
pristine baseline — and whether the change crosses the AIMC/DIMC
style boundary (the paper's comparison inverting under damage).
``tests/faults/test_chaos_golden.py`` pins the smoke-grid flip
behaviour so it moves only with the cost model, never with run order.

Env knobs
---------
``REPRO_FAULT_RATE`` / ``REPRO_FAULT_SEED``
    ``FaultSpec.from_env()`` — the seed knob pins every survivor draw
    and the node-failure trace; the rate knob (when set) *prepends* its
    value to the swept rate axis so a CI lane can pin one extra
    degraded point without editing the benchmark.
``REPRO_TRACE`` / ``REPRO_TRACE_DIR``
    Span tracing; the run exports ``chaos_sweep_trace.json`` +
    ``chaos_sweep_telemetry.jsonl`` and records their paths under
    ``telemetry.trace_files``.

``BENCH_chaos.json`` schema
---------------------------
``{"benchmark": "chaos_sweep", "smoke": bool, "designs": int,
"seed": int, "rates": [..], "networks": [..], "serving_arch": str,
"wall_s": float, "points": [{"rate": r, "survival_mean": f,
"networks": {name: {"best_design", "best_analog", "best_energy_fj",
"pareto_designs"}}, "serving": {"point", "best_design", "best_analog",
"best_tokens_per_s", "best_j_per_token"}, "episode": {"trace_events",
"faults", "retries", "recoveries", "nodes_lost", "availability",
"goodput_tok_per_s", "mttr_s", "downtime_s"}}, ...], "headline":
{"worst_case_goodput", "worst_case_availability",
"frontier_flip_rate", "flips": [{"workload", "rate", "from", "to",
"style_flip"}]}, "telemetry": {...}}`` — written atomically
(tmp + fsync + rename, bounded retry on transient OSError).

Run:  PYTHONPATH=src python -m benchmarks.chaos_sweep \
          [--smoke] [--rates 0.0,0.05,0.2] [--seed 0] \
          [--out BENCH_chaos.json]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import configs, obs
from repro.core import dse, lm_bridge, workloads
from repro.faults import (FaultInjector, FaultSpec, NodeFailureTrace,
                          survivor_mask)
from repro.launch import serve
from repro.runtime.elastic import plan_resize

from .common import emit, write_json_atomic
from .design_sweep import make_grid

_SMOKE_RATES = (0.0, 0.05)
_FULL_RATES = (0.0, 0.01, 0.05, 0.2)


def _parse_rates(s: str) -> tuple[float, ...]:
    return tuple(float(x) for x in s.split(",") if x)


def _nets(smoke: bool):
    nets = [("deep_autoencoder", workloads.deep_autoencoder()),
            ("ds_cnn", workloads.ds_cnn())]
    if not smoke:
        nets += [("resnet8", workloads.resnet8()),
                 ("mobilenet_v1_025", workloads.mobilenet_v1_025())]
    return nets


def chaos_episode(rate: float, *, seed: int = 0, n_nodes: int = 8,
                  n_gen: int = 12, batch: int = 2) -> dict:
    """One resilient-serve episode against a seeded failure trace.

    Model-free: the loop's prefill/decode are stubs (constant logits;
    the real ``sample`` still draws tokens), so the episode measures
    the *dispatch wrapper* — retry/backoff, recovery escalation, the
    availability/MTTR accounting — not XLA.  Node losses recover
    through the elastic path's :func:`plan_resize` on the permanently
    shrunken fleet.
    """
    loop = serve.ServeLoop.__new__(serve.ServeLoop)
    loop.batch = batch
    logits = np.zeros((batch, 1, 64), np.float32)
    loop._prefill = lambda params, b: (logits, {"cache": 0}, 0)
    loop._decode = lambda params, cache, tok, pos: (logits, cache)

    trace = NodeFailureTrace.generate(n_nodes, n_gen + 1, rate=rate,
                                      seed=seed)
    inj = FaultInjector(trace)
    lost: set[int] = set()

    def recover(err):
        lost.add(err.node)
        n_new = trace.n_nodes - len(lost)
        plan_resize(n_new + 1, n_new, global_batch=batch)
        inj.restore(err.node)

    prompts = np.zeros((batch, 8), np.int32)
    tokens, stats = loop.generate_resilient(
        None, prompts, n_gen, injector=inj, recover=recover,
        backoff_s=1e-4)
    assert tokens.shape == (batch, n_gen)
    return {
        "trace_events": len(trace.events),
        "faults": stats["faults"],
        "retries": stats["retries"],
        "recoveries": stats["recoveries"],
        "nodes_lost": len(lost),
        "availability": stats["availability"],
        "goodput_tok_per_s": stats["goodput_tok_per_s"],
        "mttr_s": stats["mttr_s"],
        "downtime_s": stats["downtime_s"],
    }


def run(smoke: bool = False, rates: tuple[float, ...] | None = None,
        seed: int | None = None, arch: str = "qwen1.5-0.5b",
        out: str = "BENCH_chaos.json") -> dict:
    """Sweep the fault-rate axis over every leg; write ``out``."""
    env_spec = FaultSpec.from_env()
    if seed is None:
        seed = env_spec.seed
    if rates is None:
        rates = _SMOKE_RATES if smoke else _FULL_RATES
        if env_spec.enabled and env_spec.column_fail_rate not in rates:
            rates = (env_spec.column_fail_rate,) + rates
    rates = tuple(sorted(set(rates)))
    if not rates or rates[0] != 0.0:
        rates = (0.0,) + rates          # the flip report needs a baseline

    grid = make_grid(smoke)
    nets = _nets(smoke)
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    pt_grid = [(64, 1)]
    points_lm = lm_bridge.serving_points(cfg, pt_grid, gen_len=16)
    totals_cols = np.asarray(grid.d1)
    totals_macros = np.asarray(grid.n_macros)

    # warm the sampler's jit before the clocked episodes: otherwise the
    # rate-0 baseline absorbs the compile and "worst-case goodput"
    # reports XLA warmup instead of fault cost
    chaos_episode(0.0, seed=seed, n_gen=2)

    obs.drain_spans()
    obs.reset("faults.")
    obs.reset("runtime.")
    t0 = time.perf_counter()
    points = []
    baseline: dict[str, tuple[str, bool]] = {}
    flips: list[dict] = []
    print(f"# chaos_sweep: {len(grid)} designs, {len(nets)} networks, "
          f"rates={list(rates)}, seed={seed}")
    print(f"# {'rate':>5s} {'surv':>6s} {'workload':24s} "
          f"{'winner':44s} {'avail':>6s} {'goodput':>9s}")
    for rate in rates:
        spec = FaultSpec(column_fail_rate=rate, macro_fail_rate=rate,
                         seed=seed)
        with obs.span("chaos.rate", rate=rate):
            results = dse.sweep_networks(nets, grid, faults=spec)
            sres = dse.sweep_serving(points_lm, grid, faults=spec)[0]
            episode = chaos_episode(rate, seed=seed)

        if spec.enabled:
            mask = survivor_mask(spec, grid)
            surv = float(np.mean(mask.survival(totals_cols,
                                               totals_macros)))
        else:
            surv = 1.0

        def note_winner(workload: str, name: str, analog: bool) -> None:
            if rate == 0.0:
                baseline[workload] = (name, analog)
            elif baseline[workload][0] != name:
                flips.append({"workload": workload, "rate": rate,
                              "from": baseline[workload][0], "to": name,
                              "style_flip":
                                  baseline[workload][1] != analog})

        per_net = {}
        for res in results:
            b = res.best()
            per_net[res.network] = {
                "best_design": grid.names[b],
                "best_analog": bool(grid.analog[b]),
                "best_energy_fj": float(res.energy_fj[b]),
                "pareto_designs": int(res.pareto_mask().sum()),
            }
            note_winner(res.network, grid.names[b],
                        bool(grid.analog[b]))
            print(f"# {rate:5.2f} {surv:6.1%} {res.network:24s} "
                  f"{grid.names[b]:44s} {episode['availability']:6.1%} "
                  f"{episode['goodput_tok_per_s']:9.1f}")
        sb = sres.best()
        serving_row = {
            "point": points_lm[0].name,
            "best_design": grid.names[sb],
            "best_analog": bool(grid.analog[sb]),
            "best_tokens_per_s": float(sres.tokens_per_s[sb]),
            "best_j_per_token": float(sres.j_per_token[sb]),
        }
        note_winner(points_lm[0].name, grid.names[sb],
                    bool(grid.analog[sb]))
        points.append({"rate": rate, "survival_mean": surv,
                       "networks": per_net, "serving": serving_row,
                       "episode": episode})
    wall = time.perf_counter() - t0

    n_workloads = len(nets) + 1
    n_degraded = sum(1 for r in rates if r > 0.0)
    headline = {
        "worst_case_goodput": min(p["episode"]["goodput_tok_per_s"]
                                  for p in points),
        "worst_case_availability": min(p["episode"]["availability"]
                                       for p in points),
        "frontier_flip_rate": (len(flips)
                               / max(1, n_workloads * n_degraded)),
        "flips": flips,
    }
    artifact = {
        "benchmark": "chaos_sweep",
        "smoke": smoke,
        "designs": len(grid),
        "seed": seed,
        "rates": list(rates),
        "networks": [n for n, _ in nets],
        "serving_arch": arch,
        "wall_s": wall,
        "points": points,
        "headline": headline,
    }
    tele = obs.telemetry_block()
    if obs.trace_enabled():
        tele["trace_files"] = obs.export_all(prefix="chaos_sweep")
    artifact["telemetry"] = tele
    write_json_atomic(out, artifact)
    print(f"# wrote {out}: {len(points)} fault points, "
          f"{len(flips)} winner flips "
          f"(style flips={sum(1 for f in flips if f['style_flip'])}), "
          f"worst availability="
          f"{headline['worst_case_availability']:.1%}")
    emit("chaos_sweep", wall * 1e6,
         f"rates={len(points)} designs={len(grid)} flips={len(flips)} "
         f"avail={headline['worst_case_availability']:.3f} "
         f"goodput={headline['worst_case_goodput']:.1f}")
    return artifact


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + fewer rates/networks, for CI")
    ap.add_argument("--rates", type=_parse_rates, default=None,
                    help="comma list of fault rates (0.0 baseline is "
                         "always included)")
    ap.add_argument("--seed", type=int, default=None,
                    help="fault draw seed (default: REPRO_FAULT_SEED)")
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--out", default="BENCH_chaos.json")
    args = ap.parse_args()
    run(smoke=args.smoke, rates=args.rates, seed=args.seed,
        arch=args.arch, out=args.out)
