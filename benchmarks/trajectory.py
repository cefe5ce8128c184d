"""Tracked perf trajectory: fold ``BENCH_sweep.json`` /
``BENCH_serving.json`` / ``BENCH_chaos.json`` points into the
committed ``BENCH_trajectory.json`` history.

Each entry is one commit's headline numbers for one benchmark — the
fused-sweep timing point (cold/warm wall, lattice-build time,
compile-count proxy, padding waste, shard count), the serving-sweep
summary (operating points, best tokens/s and J/token, oracle verdict),
or the chaos-sweep summary (fault points, worst-case goodput and
availability, frontier flip rate)
— so perf regressions show up as a diff in review instead of vanishing
with the CI artifact.  Appending is idempotent per (commit, benchmark):
re-running on the same SHA replaces that benchmark's entry in place, so
the sweep and serving points of one commit coexist.  The file is
written atomically (tmp + rename).

Run:  PYTHONPATH=src python -m benchmarks.trajectory \
          [--artifact BENCH_sweep.json] [--traj BENCH_trajectory.json] \
          [--commit SHA] [--date ISO8601]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess

from .common import write_json_atomic

#: artifact fields carried into the trajectory (per_network and other
#: bulky detail stays in the per-commit artifact upload)
_FIELDS = ("benchmark", "smoke", "designs", "networks", "schedules",
           "cold_s", "warm_s", "lattice_build_s", "kernel_calls_cold",
           "kernel_distinct_shapes_cold", "lattice_slots", "padding_waste",
           # reduced-engine headline (device->host traffic + pipeline)
           "transfer_bytes_cold", "pipeline_depth", "pipeline_occupancy",
           # serving_sweep headline fields
           "gen_len", "wall_s")


def _serving_headline(artifact: dict) -> dict:
    """Headline columns of a ``BENCH_serving.json`` artifact: point
    count, the best (tokens/s, J/token) across every model's operating
    points, and the bitwise-oracle verdict."""
    pts = [p for m in artifact.get("models", {}).values()
           for p in m["points"]]
    out: dict = {"operating_points": len(pts)}
    if pts:
        out["best_tokens_per_s"] = max(p["best_tokens_per_s"] for p in pts)
        out["best_j_per_token"] = min(p["best_j_per_token"] for p in pts)
    oracle = artifact.get("oracle") or {}
    out["oracle_ok"] = bool(oracle.get("bitwise_equal", False))
    return out


def _chaos_headline(artifact: dict) -> dict:
    """Headline columns of a ``BENCH_chaos.json`` artifact: the fault
    points swept, worst-case goodput/availability across the episodes,
    and how often the energy winner flipped vs the pristine baseline."""
    head = artifact.get("headline") or {}
    return {
        "fault_points": len(artifact.get("points", [])),
        "worst_case_goodput": head.get("worst_case_goodput", 0.0),
        "availability": head.get("worst_case_availability", 0.0),
        "frontier_flip_rate": head.get("frontier_flip_rate", 0.0),
        "style_flips": sum(1 for f in head.get("flips", [])
                           if f.get("style_flip")),
    }


def _head_commit() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=30)
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return "unknown"


def append(artifact_path: str = "BENCH_sweep.json",
           traj_path: str = "BENCH_trajectory.json",
           commit: str | None = None,
           date: str | None = None) -> dict:
    """Fold one artifact into the trajectory; return the new entry."""
    with open(artifact_path) as f:
        artifact = json.load(f)
    entry = {"commit": commit or _head_commit()}
    if date:
        entry["date"] = date
    entry.update({k: artifact[k] for k in _FIELDS if k in artifact})
    if artifact.get("benchmark") == "serving_sweep":
        entry.update(_serving_headline(artifact))
    elif artifact.get("benchmark") == "chaos_sweep":
        entry.update(_chaos_headline(artifact))
    else:
        cc = artifact.get("compilation_cache") or {}
        entry["compile_cache_entries"] = cc.get("entries", 0)
    tele = artifact.get("telemetry")
    if tele:
        # telemetry headline: cache thrash + tracing state travel with
        # the history; the full metrics snapshot stays in the artifact
        cache = tele.get("cache") or {}
        entry["cache_hit_rate"] = cache.get("hit_rate", 0.0)
        entry["cache_evictions"] = cache.get("evictions", 0)
        entry["lattice_evictions"] = cache.get("lattice_evictions", 0)
        entry["trace_enabled"] = bool(tele.get("trace_enabled", False))

    history: list[dict] = []
    if os.path.exists(traj_path):
        with open(traj_path) as f:
            history = json.load(f)["entries"]
    # idempotent per (commit, benchmark); legacy entries without a
    # benchmark field are treated as the fused design sweep's
    bench = entry.get("benchmark", "design_sweep_networks")
    history = [e for e in history
               if not (e.get("commit") == entry["commit"]
                       and e.get("benchmark",
                                 "design_sweep_networks") == bench)]
    history.append(entry)
    write_json_atomic(traj_path, {
        "doc": "benchmark perf history, one entry per (commit, benchmark) "
               "(benchmarks/trajectory.py appends, CI keeps it current)",
        "entries": history,
    })
    wall = entry.get("cold_s", entry.get("wall_s", 0))
    print(f"# trajectory: {len(history)} entries -> {traj_path} "
          f"(latest {entry['commit'][:12]} {bench} wall={wall:.3f}s)")
    return entry


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", default="BENCH_sweep.json")
    ap.add_argument("--traj", default="BENCH_trajectory.json")
    ap.add_argument("--commit", default=None,
                    help="commit SHA for the entry (default: git HEAD)")
    ap.add_argument("--date", default=None,
                    help="ISO8601 timestamp recorded with the entry")
    args = ap.parse_args()
    append(artifact_path=args.artifact, traj_path=args.traj,
           commit=args.commit, date=args.date)
