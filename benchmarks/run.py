"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only latency,memory]

Prints ``name,us_per_call,derived`` CSV (stdout), one row per measurement.
Mapping to the paper (DESIGN.md §7):
    Table 4  -> solver_runtime      Table 7 -> latency_e2e
    Table 8  -> memory_e2e          Fig 2/4 -> load_capacity
    Fig 6    -> multi_model         Fig 7   -> ablation
    §4.4 online -> bursty_arrivals (scheduler × eviction A/B)
    §4.4 SLO    -> slo_overload (fifo vs slo vs static under overload)
    §4.4 prio   -> priority_overload (weighted EDF × batch cap under overload)
    §4.4 mix    -> mix_shift (joint vs uniform budget split; re-planning)
    §4.4 fleet  -> replica_fleet (affinity vs round-robin; breaker A/B)
    §4.4 kv     -> kv_budget (weights-only vs unified weights+KV+arena pool)
    §4.4 cost   -> learned_cost (RLS calibration vs EWMA; proactive replan)
    Fig 8    -> tradeoff            Fig 9   -> naive_overlap
    §Roofline-> roofline_report     kernels -> kernels_bench
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

SUITES = [
    "solver_runtime",
    "load_capacity",
    "latency_e2e",
    "memory_e2e",
    "multi_model",
    "bursty_arrivals",
    "slo_overload",
    "priority_overload",
    "mix_shift",
    "replica_fleet",
    "kv_budget",
    "trace_scale",
    "learned_cost",
    "ablation",
    "tradeoff",
    "naive_overlap",
    "kernels_bench",
    "streaming_economics",
    "roofline_report",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite substrings")
    args = ap.parse_args()
    want = [s for s in args.only.split(",") if s]
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for suite in SUITES:
        if want and not any(w in suite for w in want):
            continue
        try:
            mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
            for row in mod.run():
                print(row.csv(), flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{suite},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
