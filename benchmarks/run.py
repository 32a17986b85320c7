"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]
    BENCH_ROUNDS=60 PYTHONPATH=src python -m benchmarks.run --quick
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (fig3_privacy_level, fig456_async_efficiency,
                        fig7_distributiveness, fig8_robust_convergence,
                        roofline_table, table1_prediction,
                        table23_privacy_budget, table4_byzantine,
                        theorem1_convergence)
from repro.compile_cache import enable_compile_cache

SUITES = {
    "table1": table1_prediction.main,
    "table23": table23_privacy_budget.main,
    "fig3": fig3_privacy_level.main,
    "fig456": fig456_async_efficiency.main,
    "table4": table4_byzantine.main,
    "fig7": fig7_distributiveness.main,
    "fig8": fig8_robust_convergence.main,
    "theorem1": theorem1_convergence.main,
    "roofline": roofline_table.main,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced method/dataset grid")
    ap.add_argument("--only", default="",
                    help="comma-separated suite names")
    ap.add_argument("--rounds", type=int,
                    default=int(os.environ.get("BENCH_ROUNDS", "150")))
    args = ap.parse_args()

    enable_compile_cache()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or \
        list(SUITES)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            for row in SUITES[name](rounds=args.rounds, quick=args.quick):
                print(row, flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,0.0,failed", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.0f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
