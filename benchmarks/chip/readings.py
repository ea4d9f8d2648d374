"""The arithmetic behind the per-layer metrics. Each metric's file in
``metrics/`` is a reader that calls one of these on the run; a reading
that has nothing to read returns None, and the harness leaves the metric
out of the result line. Shares are percentages.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import counts
import trace_reduce

MiB = 1 << 20


def _ok(run):
    return [r for r in run.win.responses
            if r.req_id in run.win.sent and r.status == "ok"]


def queue_ms_mean(run) -> Optional[float]:
    """Mean wait from due time to the start of the request's batch."""
    q = [r.queue_s for r in _ok(run)]
    return float(np.mean(q)) * 1e3 if q else None


def batch_size_mean(run) -> Optional[float]:
    sizes = [b.size for b in run.batches]
    return float(np.mean(sizes)) if sizes else None


def h2d_mib_per_batch(run) -> Optional[float]:
    """Bytes the executor moved host -> device per batch: whole weights
    preloaded plus chunks the loader streamed."""
    st = run.win.stats
    if not st:
        return None
    return float(np.mean([(s.preloaded_bytes + s.streamed_bytes) / MiB
                          for s in st]))


def stall_events_per_batch(run) -> Optional[float]:
    st = run.win.stats
    return float(np.mean([s.stall_events for s in st])) if st else None


def step_mfu(run) -> Optional[float]:
    """Model operations of the real (unpadded) prompt tokens answered,
    over the executed batches' time (preload + op loop) at the chip's
    peak."""
    if run.peaks is None or not run.win.stats:
        return None
    dims = {m.name: m.dims for m in run.dep.models}
    sent = run.win.sent
    flops = sum(run.dep.family.model_flops(dims[r.model],
                                           sent[r.req_id].length)
                for r in _ok(run))
    t = sum(s.init_s + s.exec_s for s in run.win.stats)
    return 100.0 * flops / (t * run.peaks["flops"]) if t > 0 else None


def roofline(run, program: str) -> Optional[float]:
    """Least time of every call of ``program`` in the window's batches
    over the device time its executions took in the trace."""
    if run.trace is None or run.peaks is None or not run.batches:
        return None
    t_dev, n = trace_reduce.program_time(run.trace, f"jit_{program}")
    if n == 0 or t_dev <= 0:
        return None
    dims = {m.name: m.dims for m in run.dep.models}
    calls = run.dep.family.batch_calls
    least = sum(counts.least_time(f, b, run.peaks)
                for bt in run.batches
                for f, b in calls(dims[bt.model], bt.size,
                                  bt.padded)[program])
    return 100.0 * least / t_dev


def idle_in_steps(run) -> Optional[float]:
    """Share of the time the host spent in engine steps in which no
    operation ran on the device."""
    if run.trace is None or not run.trace.devices:
        return None
    sp = trace_reduce.union(trace_reduce.steps(run.trace))
    total = sum(e - s for s, e in sp)
    if total <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_within(run.trace, sp) / total)


def idle_in_window(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device."""
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace, lo, hi)
                    / (hi - lo))
