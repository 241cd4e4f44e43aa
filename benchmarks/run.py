"""Benchmark harness — one entry per paper table/figure plus the roofline
report. Prints ``name,us_per_call,derived`` CSV lines.

PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (ablations, fedsim_bench, fig1_gap, fig5_neighbors,
                        fig6_selection, fig8_em_weights, kernels_bench,
                        lint_smoke, roofline, table2_accuracy,
                        table3_accuracy)
from repro import compat

ALL = {
    "fig1_gap": fig1_gap.main,
    "fig5_neighbors": fig5_neighbors.main,
    "fig6_selection": fig6_selection.main,
    "fig8_em_weights": fig8_em_weights.main,
    "table2_accuracy": table2_accuracy.main,
    "table3_accuracy": table3_accuracy.main,
    "kernels": kernels_bench.main,
    "roofline": roofline.main,
    "ablations": ablations.main,
    "fedsim_sharded_smoke": fedsim_bench.sharded_smoke,
    "obs_smoke": fedsim_bench.obs_smoke,
    "lint_smoke": lint_smoke.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by registry name")
    ap.add_argument("--list", action="store_true",
                    help="print the available benchmark names and exit")
    args = ap.parse_args()
    if args.list:
        print("\n".join(ALL))
        return
    if args.only is not None and args.only not in ALL:
        raise SystemExit(
            f"unknown benchmark {args.only!r}; available: "
            + ", ".join(sorted(ALL)))
    names = [args.only] if args.only else list(ALL)
    compat.enable_compilation_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            ALL[name]()
        except Exception as e:
            failed.append(name)
            print(f"{name},nan,ERROR:{type(e).__name__}:{str(e)[:120]}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
