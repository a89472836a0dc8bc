"""Paper-figure driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Heavy results are cached under
benchmarks/artifacts/ (pass --force to regenerate; --only to filter).
"""
import argparse
import time

from . import (table1_hw, table2_accuracy, fig5_bitwidth, fig6_rmse,
               fig7_taskspecific, latency_throughput)
from .common import cached

SUITES = [
    ("table1_hw", table1_hw),
    ("latency_throughput", latency_throughput),
    ("fig6_rmse", fig6_rmse),
    ("fig7_taskspecific", fig7_taskspecific),
    ("table2_accuracy", table2_accuracy),
    ("fig5_bitwidth", fig5_bitwidth),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            res = cached(name, mod.run, force=args.force)
            for line in mod.csv_lines(res):
                print(line)
            print(f"{name}_wall_s,{(time.time()-t0)*1e6:.0f},"
                  f"{time.time()-t0:.1f}", flush=True)
        except Exception as e:  # keep the suite going; record the failure
            print(f"{name}_ERROR,0,{e!r}", flush=True)


if __name__ == "__main__":
    main()
