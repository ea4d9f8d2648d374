#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains: build the
cell once, then run its traffic mix at each rate for a short window and
print one JSON line per rate (offered and completed rate, latency
quantiles, and the late-window mean latency over the early one, which
grows with a backlog).

    python3 benchmarks/chip/sweep.py --workload neo13-resident --seed 7 \
        --seconds 10 --rates 5,10,20
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--drain", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    import harness
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.clock import MonotonicClock

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        harness.log("FAIL: no TPU")
        return 2
    base = harness.load_cell(args.workload)
    dep = harness.build(base, args.seed)
    harness.warm_up(dep, base, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.deepcopy(base)
        cell.traffic["rate_per_s"] = rate
        cell.traffic["drain_s"] = args.drain
        clock = MonotonicClock()
        win = harness.run_open(dep, cell, args.seed, args.seconds, clock)
        win.t_end = clock.now()
        lat = harness.latencies(win)
        ok = harness.answered(win)
        ordered = lat                  # in due order: req_id follows it
        third = max(1, len(ordered) // 3)
        last = max((r.finish_s for r in ok.values()), default=win.t_end)
        print(json.dumps({
            "rate": rate, "sent": len(win.sent), "answered": len(ok),
            "completed_per_s": len(ok) / max(last - win.t0, 1e-9),
            "p50_s": harness.nearest_rank(lat, .5),
            "p90_s": harness.nearest_rank(lat, .9),
            "p95_s": harness.nearest_rank(lat, .95),
            "late_over_early": float(np.mean(ordered[-third:])
                                     / max(np.mean(ordered[:third]), 1e-9)),
            "batch_size_mean": float(np.mean([b.size for b in
                                              harness.batches(win)]))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
