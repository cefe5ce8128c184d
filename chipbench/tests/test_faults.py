"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run.main`` past its look for a chip, on the small
``imc-smoke`` grid, with ``dse.sweep_networks`` (which
``dse.sweep_serving`` calls too) replaced by a faulty wrapper:

* ``stale``: every sweep returns the first sweep's answer (state left
  unchanged);
* ``half``: only the first half of each network's layers is priced and
  the totals are doubled (half the batch left out, scaled up from the
  rest);
* ``altered``: every energy total is off by one part in 1e9 where the
  sweep produces it;
* ``dimc``: the same, on the DIMC designs alone (one macro type);
* ``nan``: every energy total is NaN;
* ``winner``: one layer's winning candidate is moved to its neighbour.

The exchange between chips cannot be left out: every sweep cell runs on
one chip.
"""

import dataclasses
import json

import numpy as np
import pytest

from chipbench import run

CELLS = ("sweep.tinymlperf.cold", "sweep.glm4-9b.serving",
         "sweep.tinymlperf.reuse")


def _result(capsys, workload, seed=987654321987):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fault(kind, orig):
    first = []

    def faulty(networks, designs, **kw):
        if kind == "stale":
            if not first:
                first.append(orig(networks, designs, **kw))
            return first[0]
        if kind == "half":
            networks = [(n, list(ls)[:max(1, len(ls) // 2)])
                        for n, ls in networks]
        out = orig(networks, designs, **kw)
        if kind == "half":
            return tuple(dataclasses.replace(r, energy_fj=r.energy_fj * 2,
                                             cycles=r.cycles * 2)
                         for r in out)
        scale = {"altered": 1 + 1e-9, "nan": np.nan,
                 "dimc": np.where(designs.analog, 1.0, 1 + 1e-9)}.get(kind)
        if scale is not None:
            return tuple(dataclasses.replace(r, energy_fj=r.energy_fj * scale)
                         for r in out)
        layer, grid, best = out[0]._shapes[0]
        moved = np.where(best > 0, best - 1, best + 1)
        return (dataclasses.replace(
            out[0], _shapes=((layer, grid, moved),) + out[0]._shapes[1:]),
        ) + out[1:]
    return faulty


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(cpu_small, capsys, workload):
    res = _result(capsys, workload)
    assert res["correct"] is True
    assert res["check"]["max_rel_dev"]["value"] == 0.0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("kind", ["stale", "half", "altered", "dimc", "nan",
                                  "winner"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(cpu_small, monkeypatch, capsys, workload,
                              kind):
    from repro.core import dse
    monkeypatch.setattr(dse, "sweep_networks",
                        _fault(kind, dse.sweep_networks))
    res = _result(capsys, workload)
    assert res["correct"] is False, res["check"]
