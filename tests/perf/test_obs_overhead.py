"""Telemetry overhead guards (ISSUE 8 acceptance): with tracing
*disabled* the instrumented sweep stays within 2% of a no-telemetry
baseline (the instrumentation cost is one flag check + kwargs dict per
span site), and with tracing *enabled* it stays within 10% on a
>= 1000-design grid.  Same marker scheme as the other perf guards:
wall-clock ratios are flaky on shared CI runners, so CI gets crash
coverage only and the ratios are enforced locally.

The no-telemetry baseline monkeypatches ``obs.span`` (as imported by
the instrumented modules) to a zero-cost null factory, so the measured
delta isolates exactly what the telemetry layer adds to the hot path.
"""

import contextlib
import os
import time

import pytest

from repro import obs
from repro.core import designs, dse, workloads


def _grid() -> designs.MacroBatch:
    g = designs.macro_grid(
        rows=(64, 128, 256, 512, 1024), cols=(128, 256, 512),
        adc_bits=(4, 5, 6, 7, 8), dac_bits=(1, 2, 4), m_mux=(1, 4, 16),
        tech_nm=(5, 22, 28), vdd=(0.7, 0.8))
    assert len(g) >= 1000
    return g


def _nets():
    return [("deep_autoencoder", workloads.deep_autoencoder()),
            ("ds_cnn", workloads.ds_cnn())]


class _RawNull:
    """Bare-minimum context manager standing in for obs.span in the
    no-telemetry baseline: attribute-compatible, zero bookkeeping."""

    def set(self, **attrs):
        pass

    def lap(self, label):
        return 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_RAW = _RawNull()


def _best_of(fn, n=5):
    t = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        t = min(t, time.perf_counter() - t0)
    return t


def _best_of_interleaved(fn_a, fn_b, n=7):
    """Best-of walls for two variants, samples interleaved A/B/A/B so
    slow machine drift (thermal, page cache, a background process
    winding down) hits both variants alike instead of biasing whichever
    was measured second."""
    t_a = t_b = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn_a()
        t_a = min(t_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        t_b = min(t_b, time.perf_counter() - t0)
    return t_a, t_b


def test_overhead_disabled_within_2pct():
    grid = _grid()
    nets = _nets()
    run = lambda: dse.sweep_networks(nets, grid)

    obs.set_trace_enabled(False)
    run()                                    # warm jit + lattice caches

    # no-telemetry baseline: null out every span site the sweep hits
    # (repro.core.{dse,mapping,energy} all call through repro.obs.span)
    real_span = obs.span
    raw_span = lambda name, **attrs: _RAW

    def run_instr():
        obs.span = real_span
        run()

    def run_base():
        obs.span = raw_span
        run()

    # a 2% bound on a ~0.1s wall sits near timer jitter: take the best
    # ratio over a couple of measurement rounds so one scheduler hiccup
    # on the instrumented side can't fail the guard
    ratio = float("inf")
    try:
        for _ in range(3):
            t_instr, t_base = _best_of_interleaved(run_instr, run_base)
            ratio = min(ratio, t_instr / max(t_base, 1e-9))
            if ratio <= 1.02:
                break
    finally:
        obs.span = real_span
    obs.set_trace_enabled(None)

    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (disabled-tracing "
                    f"ratio={ratio:.3f}x)")
    assert ratio <= 1.02, (
        f"disabled tracing costs {(ratio - 1) * 100:.1f}% over the "
        f"no-telemetry baseline")


def test_overhead_enabled_within_10pct():
    grid = _grid()
    nets = _nets()
    run = lambda: dse.sweep_networks(nets, grid)

    obs.set_trace_enabled(False)
    run()                                    # warm jit + lattice caches

    def run_off():
        obs.set_trace_enabled(False)
        run()

    def run_on():
        obs.set_trace_enabled(True)
        run()

    obs.drain_spans()
    ratio = float("inf")
    try:
        for _ in range(3):
            t_off, t_on = _best_of_interleaved(run_off, run_on)
            ratio = min(ratio, t_on / max(t_off, 1e-9))
            if ratio <= 1.10:
                break
    finally:
        obs.set_trace_enabled(None)
    n_spans = len(obs.drain_spans())
    assert n_spans > 0                       # tracing really recorded

    if os.environ.get("CI"):
        pytest.skip(f"timing guard skipped on CI (enabled-tracing "
                    f"ratio={ratio:.3f}x)")
    assert ratio <= 1.10, (
        f"enabled tracing costs {(ratio - 1) * 100:.1f}% over the "
        f"tracing-off wall")
