"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage:

    PYTHONPATH=src python -m benchmarks.run [--only fig4,table3]
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro.runtime import use_compile_cache

MODULES = [
    "entropy_integrity",
    "fig1_single_vs_multi",
    "fig4_query_heuristics",
    "fig5_step_size",
    "fig6_table1_seasonality",
    "fig7_9_10_t3_characteristics",
    "fig11_12_scoring_effectiveness",
    "fig13_16_sensitivity",
    "table2_3_fig17_pool",
    "fig18_19_recommendation",
    "serve_throughput",
    "pool_scan_scaling",
    "scoring_scaling",
    "ingest_throughput",
    "archive_memory",
    "shard_scaling",
    "latency_slo",
    "operator_replay",
    "multiregion_compare",
    "kernels_micro",
    "roofline",
    "spotlint_gate",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of module names")
    args = ap.parse_args()
    use_compile_cache()
    selected = MODULES
    if args.only:
        keys = args.only.split(",")
        selected = [m for m in MODULES if any(k in m for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for line in mod.run():
                print(line)
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
