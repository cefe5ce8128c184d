"""``BENCHMARK.json`` is self-consistent, and every metric it names has a
reader that reads a traced run's context."""

import json
import re
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_files_and_references():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json") \
            .is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", [])) <= cells
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for w in cells:
        assert "setup_s" in {m["name"] for m in run.applicable(SPEC, w,
                                                               False)}
        assert len(run.applicable(SPEC, w, False)) >= 2
        assert run.applicable(SPEC, w, True)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_readers_read_a_traced_context(workload):
    ctx = {"workload": workload, "seconds": 10.0, "setup_s": 11.0,
           "units": 9_396_000, "steps": 100,
           "runner": {"sweeps": 100, "transfer_bytes": 147_744_000},
           "spans": {"dse.lattice_build": {"count": 3800, "total_s": 8.0},
                     "dse.network_grid_build": {"count": 100,
                                                "total_s": 1.0}},
           "trace": {"busy_s": 1.9, "window_s": 10.0}}
    for per_layer in (False, True):
        got = run.read_metrics(run.applicable(SPEC, workload, per_layer),
                               ctx)
        assert got and all(isinstance(v["value"], float)
                           for v in got.values())
