"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall
microseconds per simulated dataplane tick / engine step; derived = the
paper metric being reproduced).  JSON artifacts land in
benchmarks/results/.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro import compile_cache

MODULES = [
    "sim_perf",                  # engine compile-cache / batching speed
    "fleet_slo",                 # fleet-scale batched control plane
    "placement",                 # fleet admission placement policies
    "churn",                     # tenant-lifecycle churn timelines
    "contention",                # multi-resource vector admission
    "adaptive",                  # closed-loop shaping vs static registers
    "scenarios",                 # production-shaped workload scenarios
    "table2_shaping_accuracy",   # Table 2
    "fig3_provisioning",         # Fig. 3 / Table 1
    "fig6_throughput_cdf",       # Fig. 6 + Sec 5.2 latency
    "table3_deviation",          # Table 3
    "fig7_heterogeneity",        # Fig. 7
    "fig8_large_messages",       # Fig. 8 (use case 1)
    "fig9_bursty_tiny",          # Fig. 9 (use case 2)
    "fig11_end_to_end",          # Fig. 11 + Table 4
    "serving_slo",               # TPU-serving adaptation
    "roofline",                  # §Roofline (reads dry-run artifacts)
    "perf_variants",             # §Perf baseline-vs-optimized comparison
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter sims (CI-scale)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    compile_cache.configure()

    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row in mod.run(quick=args.quick):
                print(row.csv(), flush=True)
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
