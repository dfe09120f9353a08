"""Benchmark orchestrator: one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows.  A section that raises
prints a ``<name>/ERROR`` row, the remaining sections still run, and the
process exits 1.
"""
from __future__ import annotations

import argparse
import sys
import traceback
import warnings


def main(argv: list[str] | None = None) -> int:
    # Benchmarks must run on the RuntimeSpec/InferenceSession API, not
    # the deprecated per-call kwargs: promote the shim warning to an
    # error here (pytest.ini does the same for the test suite) so every
    # CI leg that drives a benchmark enforces the migration.
    from repro.impact import SpecDeprecationWarning
    warnings.simplefilter("error", SpecDeprecationWarning)
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names to run")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compilation_cache
    use_compilation_cache()
    from . import (fig7_8_variability, fig13_tuning_sweep, impact_throughput,
                   roofline, table4_energy, table5_datasets,
                   table6_comparison)
    sections = {
        "table4": table4_energy.main,
        "table5": table5_datasets.main,
        "table6": table6_comparison.main,
        "fig7_8": fig7_8_variability.main,
        "fig13": fig13_tuning_sweep.main,
        "roofline": roofline.main,
        "impact_throughput": impact_throughput.main,
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    print("name,us_per_call,derived")
    failed = []
    for name in chosen:
        try:
            sections[name]()
        except Exception as e:
            # Keep running the other sections, but a failed section
            # fails the run: the exit code is what automation reads.
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{str(e)[:120]}")
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
    if failed:
        print(f"failed sections: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
