"""The executor's phase counters as per-layer metrics: the readers'
arithmetic, their silence on a program without the counters, the traced
CPU rehearsal reporting each in its own cell, and the trace reduction
left as it was by the program's own ``flashmem.*`` spans."""
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import harness
import trace_reduce as tr
from test_bench_trace import _plane

from repro.core.streaming import RunStats

SEED = 2**33 + 29
NEW = {"neo13-resident": {"dispatch_us_per_op.open"},
       "neo27-offload-batch": {"stall_ms_per_batch", "assemble_ms_per_batch",
                               "h2d_gbps.batch"}}


def _run(stats):
    return SimpleNamespace(win=SimpleNamespace(stats=stats))


def _read(name, run):
    return harness.reader(name)(run)


def test_readers_sum_the_window_batches():
    st = [RunStats(stall_s=2.0, assemble_s=0.1, dispatch_s=0.029, ops_run=290,
                   put_s=3.0, streamed_bytes=9 * 10**9),
          RunStats(stall_s=2.4, assemble_s=0.3, dispatch_s=0.029, ops_run=290,
                   put_s=1.0, streamed_bytes=3 * 10**9)]
    run = _run(st)
    assert _read("stall_ms_per_batch", run) == pytest.approx(2200.0)
    assert _read("assemble_ms_per_batch", run) == pytest.approx(200.0)
    assert _read("dispatch_us_per_op.open", run) == pytest.approx(100.0)
    assert _read("h2d_gbps.batch", run) == pytest.approx(3.0)


def test_readers_are_silent_without_the_counters():
    # a RunStats from before the phase counters: counts and bytes only
    old = SimpleNamespace(stall_events=108, streamed_bytes=10**10,
                          preloaded_bytes=0, init_s=0.1, exec_s=3.0)
    resident = RunStats(ops_run=290, dispatch_s=0.03)      # nothing streamed
    for name in set().union(*NEW.values()):
        assert _read(name, _run([old, old])) is None
        assert _read(name, _run([])) is None
    assert _read("h2d_gbps.batch", _run([resident])) is None
    assert _read("dispatch_us_per_op.open", _run([RunStats()])) is None


def test_traced_rehearsal_reports_each_counter_in_its_cell(tiny):
    for workload, want in NEW.items():
        cell = tiny(workload)
        r = harness.run_cell(cell, SEED, 1.5, True, time.perf_counter())
        assert r["correct"] is True
        got = set(r["metrics"])
        assert want <= got, workload
        assert not (set().union(*NEW.values()) - want) & got
        for name in want:
            assert np.isfinite(r["metrics"][name]["value"])
            assert r["metrics"][name]["value"] > 0


def _trace(host):
    modules = [(1, 3, "jit_f_matmul(11)"), (4, 5, "jit_f_attn(12)")]
    ops = [(1, 3, "fusion.1"), (4, 5, "fusion.3")]
    txt = (_plane(1, "/device:TPU:0", [("XLA Modules", modules),
                                      ("XLA Ops", ops)])
           + _plane(2, "/host:CPU", host))
    t = tr.from_profile(jax.profiler.ProfileData.from_text_proto(txt))
    tr.rename_spans(t, "bench.step", ["batch", "idle"])
    return t


def test_program_spans_leave_the_reduction_unchanged():
    bench = [(0, 6, "bench.step"), (6, 8, "bench.wait_arrival"),
             (8, 10, "bench.step")]
    program = [(0.1, 0.9, "flashmem.engine.schedule"),
               (0.9, 1.0, "flashmem.exec.begin"),
               (1.0, 5.5, "flashmem.exec.ops"),
               (3.0, 4.0, "flashmem.exec.wait_weight"),
               (5.0, 5.2, "flashmem.exec.sync"),
               (5.5, 5.9, "flashmem.engine.respond"),
               (8.1, 8.2, "flashmem.engine.schedule")]
    loader = [(1.0, 2.0, "flashmem.loader.task"),
              (2.0, 4.5, "flashmem.loader.gate")]
    old = _trace([("python", bench)])
    new = _trace([("python", bench + program), ("python", loader)])
    assert new.spans == old.spans
    assert tr.window(new) == tr.window(old)
    assert tr.steps(new) == tr.steps(old)
    lo, hi = tr.window(old)
    assert tr.busy_s(new, lo, hi) == tr.busy_s(old, lo, hi)
    assert tr.breakdown(new, lo, hi) == tr.breakdown(old, lo, hi)
