"""The benchmark's readers of the sweep's main-thread spans
(``chipbench/metrics/sweep.{builder_wait,device_wait,assemble}_ms.py``):
ms per sweep from the span rollup of a traced run, and nothing when the
program records no such span (tracing off, or a program without it)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench import run  # noqa: E402

READS = {"sweep.builder_wait_ms": "dse.await_bucket",
         "sweep.device_wait_ms": "dse.device_wait",
         "sweep.assemble_ms": "dse.assemble"}


def _ctx(spans):
    return {"workload": "sweep.tinymlperf.cold", "seconds": 20.0,
            "setup_s": 12.0, "units": 18_792_000, "steps": 200,
            "runner": {"sweeps": 200, "transfer_bytes": 0},
            "spans": spans, "trace": None}


@pytest.mark.parametrize("metric", sorted(READS))
def test_span_reader_ms_per_sweep(metric):
    spec = [m for m in run.load_cell("sweep.tinymlperf.cold")[0]["per_layer"]
            if m["name"] == metric]
    assert spec and spec[0]["source"] == "program_span"
    others = {n: {"count": 600, "total_s": 9.0} for n in READS.values()
              if n != READS[metric]}
    spans = dict(others, **{READS[metric]: {"count": 600, "total_s": 3.0}})
    got = run.read_metrics(spec, _ctx(spans))
    assert got == {metric: {"value": pytest.approx(15.0), "unit": "ms"}}
    # absent span, and an untraced run: the reader reports nothing
    assert run.read_metrics(spec, _ctx(others)) == {}
    assert run.read_metrics(spec, _ctx(None)) == {}
