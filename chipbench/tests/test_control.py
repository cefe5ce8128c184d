"""The float32 control fails the limit; the program, on the CPU, reads 0."""

import pytest

from chipbench import control, oracle


@pytest.mark.parametrize("workload", ["sweep.tinymlperf.cold",
                                      "sweep.glm4-9b.serving",
                                      "sweep.tinymlperf.reuse"])
def test_control_fails_program_passes(cpu_small, workload):
    got = control.readings(workload, 31337)
    assert got["program"] == 0.0
    assert got["control"] > 3 * oracle.MAX_REL_DEV_LIMIT
