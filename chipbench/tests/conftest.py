import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture
def cpu_small(monkeypatch):
    """Drive the harness on the CPU: its look for a chip and for the
    chip's peaks pass, and every cell prices the small ``imc-smoke``
    grid."""
    import jax

    from chipbench import peaks, run
    orig = run.load_cell

    def small(workload):
        spec, cell, config, traffic = orig(workload)
        return spec, cell, config, dict(traffic, design_grid="imc-smoke")
    monkeypatch.setattr(run, "load_cell", small)
    monkeypatch.setattr(run, "find_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "lookup", lambda kind: {})
