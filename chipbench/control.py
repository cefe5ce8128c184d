#!/usr/bin/env python3
"""Readings that set the limit of a sweep cell's ``max_rel_dev``.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, in one process: the cell's inputs at its own size, its
set-up, as many sweeps as a run checks, and then two readings on the
same seeded sample of records --

* ``program``: the program's answers against the float64 reference (the
  lower reading: the largest over a dozen seeds or more);
* ``control``: the float32 reference put in the program's place (the
  upper reading: the smallest over three seeds or more).

The benchmark's own runs never run this.  Prints one JSON line per seed
and a last line with the largest program reading and the smallest
control reading.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int) -> dict:
    from chipbench import gen, oracle, run
    spec, cell, config, traffic = run.load_cell(workload)
    run.find_devices(cell["chips"])
    run.enable_compile_cache()
    runner = importlib.import_module(
        f"chipbench.runners.{traffic['runner']}").make(config, traffic, seed)
    runner.setup()
    for i in range(traffic["check_records"]):
        runner.step(i)
    runner.release()
    program = runner.check()
    records = runner.records()
    control = 0.0 if records else float("inf")
    for rec in records:
        low = oracle.reference_record(rec["kind"], runner.workload,
                                      rec["design"], rec["objective"],
                                      "float32", runner.schedules)
        got = oracle.compare(low, runner.workload, runner.schedules)
        control = max(control, got["max_rel_dev"])
    return {"seed": seed,
            "program": program["numbers"]["max_rel_dev"][0],
            "program_where": program["detail"]["where"],
            "control": control, "records": len(records)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    rows = []
    for seed in args.seeds:
        rows.append(readings(args.workload, seed))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
