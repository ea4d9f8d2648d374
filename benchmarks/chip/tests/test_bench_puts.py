"""``h2d_puts_per_batch``: the loader's device_put count as a per-layer
metric. It reads the per-batch mean of ``RunStats.h2d_puts``, nothing on a
program whose RunStats lacks the counter, and appears in the traced
rehearsal of its own cell only."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import harness

from repro.core.streaming import RunStats

SEED = 2**33 + 31
NAME = "h2d_puts_per_batch"


def _read(stats):
    return harness.reader(NAME)(SimpleNamespace(win=SimpleNamespace(
        stats=stats)))


def test_reads_the_per_batch_mean():
    assert _read([RunStats(h2d_puts=500),
                  RunStats(h2d_puts=561)]) == pytest.approx(530.5)
    assert _read([RunStats()]) == 0.0


@pytest.mark.parametrize("stats", [
    # the phase counters without the put count, as before h2d_puts
    [SimpleNamespace(**{k: v for k, v in vars(RunStats()).items()
                        if k != "h2d_puts"})],
    # counts and bytes only, as before the phase counters
    [SimpleNamespace(stall_events=108, streamed_bytes=10**10,
                     preloaded_bytes=0, init_s=0.1, exec_s=3.0)] * 2,
    [],
], ids=["no_put_count", "no_phase_counters", "no_batches"])
def test_is_silent_without_the_counter(stats):
    assert _read(stats) is None


@pytest.mark.parametrize("workload,reported", [
    ("neo27-offload-batch", True), ("neo13-resident", False)])
def test_traced_rehearsal_reports_it_in_its_cell_only(tiny, workload,
                                                      reported):
    r = harness.run_cell(tiny(workload), SEED, 1.5, True,
                         time.perf_counter())
    assert r["correct"] is True
    assert (NAME in r["metrics"]) is reported
    if reported:
        v = r["metrics"][NAME]["value"]
        assert np.isfinite(v) and v > 0
