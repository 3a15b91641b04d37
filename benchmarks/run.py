# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper figure/table plus live
microbenchmarks and the TPU roofline table.

  PYTHONPATH=src python -m benchmarks.run [--only fig4,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    args = ap.parse_args()

    from benchmarks import (decode_microbench, fig4_throughput,
                            fig5_op_breakdown, fig6_matmul_breakdown,
                            fig8_scheduler_versions, roofline_table)
    modules = [fig4_throughput, fig5_op_breakdown, fig6_matmul_breakdown,
               fig8_scheduler_versions, decode_microbench, roofline_table]
    if args.only:
        keys = args.only.split(",")
        modules = [m for m in modules
                   if any(k in m.__name__ for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    for mod in modules:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},nan,ERROR", file=sys.stdout)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
