#!/usr/bin/env python3
"""Readings for the correctness limits: run one cell's window on many
seeds in one process, and on each compare the program, and the
reference in lower precision put in its place (the control), with the
reference at the configuration's precision. Prints one JSON line per seed.

    python3 benchmarks/chip/calibrate.py --workload neo13-resident \
        --seeds 1,2,3 --seconds 6 --controls bf16
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--controls", default="bf16")
    args = ap.parse_args(argv)
    import gc

    import jax
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        harness.log("FAIL: no TPU")
        return 2
    cell = harness.load_cell(args.workload)
    also = [c for c in args.controls.split(",") if c]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, t0, also=also)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "readings": r["readings"],
                          "run_s": time.perf_counter() - t0}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
