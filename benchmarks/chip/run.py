#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix; ``--seed`` makes the weights and the requests. Set-up
builds the models, plans the pool and warms every shape the traffic
uses; the window then drives the serving engine's online loop for
``--seconds``, drains, and compares a seeded sample of what was served
with the plain float32 reference. ``--trace 1`` records a profiler trace
of the window and reports the cell's per-layer metrics instead of its
end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit). The last lines of
stderr repeat the checks. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
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


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("--seed must be >= 0", file=sys.stderr)
        return 2
    import jax
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the op programs compile in well under a second each: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    info = harness.device_info(dev)
    harness.log(f"platform {info['platform']}")
    harness.log(f"device_kind {info['kind']}")
    harness.log(f"device_count {info['count']}")
    if info["platform"] != "tpu":
        harness.log(f"FAIL: no TPU (platform {info['platform']}); the "
                    "benchmark runs only on the chip")
        return 2
    if info["count"] < cell.chips:
        harness.log(f"FAIL: {args.workload} needs {cell.chips} chips, "
                    f"found {info['count']}")
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
