"""Benchmark harness: one entry per paper table/figure (+ the
beyond-paper LM case study, the roofline table from dry-run artifacts,
and the Pallas kernel checks).  Prints ``name,us_per_call,derived``
CSV rows; `#`-prefixed lines are human-readable detail.

Run:  PYTHONPATH=src python -m benchmarks.run [--list] [name ...]

``--list`` prints the registered benchmark names; positional names run
a subset (default: all, in registry order).
"""

from __future__ import annotations

import argparse

from repro.core.compilecache import enable_compilation_cache

from . import (accuracy_sweep, chaos_sweep, common, design_sweep,
               fig4_survey, fig5_validation, fig6_tech, fig7_casestudy,
               kernel_bench, lm_imc_casestudy, roofline_table,
               serving_sweep)

#: registered benchmarks, in the order the full harness runs them.
#: Variant entries (e.g. the dataflow-axis sweep CI smokes) share a
#: module but pin different flags.
BENCHMARKS: dict[str, object] = {
    "fig4_survey": fig4_survey.run,
    "fig5_validation": fig5_validation.run,
    "fig6_tech": fig6_tech.run,
    "fig7_casestudy": fig7_casestudy.run,
    "lm_imc_casestudy": lm_imc_casestudy.run,
    "design_sweep": design_sweep.run,
    "design_sweep_dataflows": lambda: design_sweep.run(smoke=True,
                                                       dataflows=True),
    "design_sweep_networks": lambda: design_sweep.run_networks(smoke=True),
    "accuracy_sweep": lambda: accuracy_sweep.run(smoke=True),
    "serving_sweep": lambda: serving_sweep.run(smoke=True),
    "chaos_sweep": lambda: chaos_sweep.run(smoke=True),
    "roofline_table": roofline_table.run,
    "kernel_bench": kernel_bench.run,
}

#: the default full run skips variants that duplicate a base benchmark
#: on a smaller grid (they exist for `--list`/CI selection).
DEFAULT_RUN = tuple(n for n in BENCHMARKS
                    if n not in ("design_sweep_dataflows",
                                 "design_sweep_networks"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true", dest="list_names",
                    help="print the registered benchmark names and exit")
    ap.add_argument("names", nargs="*", metavar="name",
                    help="benchmarks to run (default: the full suite)")
    args = ap.parse_args(argv)

    if args.list_names:
        for name in BENCHMARKS:
            print(name)
        return

    names = args.names or list(DEFAULT_RUN)
    unknown = [n for n in names if n not in BENCHMARKS]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; see --list")

    enable_compilation_cache()
    common.header()
    for name in names:
        BENCHMARKS[name]()
    print(f"# total benchmarks: {len(common.ROWS)}")


if __name__ == "__main__":
    main()
