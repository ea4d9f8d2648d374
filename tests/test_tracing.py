"""Host spans and counters inside the engine, executor and loader: the
``RunStats`` phase counters add up to the op loop's time, and one batch
served under the profiler leaves ``flashmem.*`` spans nested by phase on
the serving thread, loader spans on their own thread, all tagged with the
batch's id."""
import glob
import os
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.configs.gptneo import GPTNEO_S
from repro.core import (HostModel, StreamingExecutor, plan_always_next,
                        plan_preload_all)
from repro.core.capacity import HWSpec
from repro.serving.engine import Request, ServingEngine
from repro.serving.stream import RequestStream

CFG = replace(GPTNEO_S, num_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
              d_ff=512, vocab=512, name="gptneo-trace")
SEQ = 32
CHUNK = 64 << 10            # every projection is several chunks
DISK_BW = 50e6              # slow storage: the loader falls behind compute
HW = HWSpec(peak_flops=5e10, hbm_bw=2e10, stream_bw=1e10)
EPS = 1e-6


@pytest.fixture(scope="module")
def model():
    m = HostModel.build(CFG, seq=SEQ, batch=1)
    StreamingExecutor(m, plan_preload_all(m.graph, CHUNK)).run(_tokens())
    return m


def _tokens():
    return np.random.default_rng(0).integers(0, CFG.vocab, (1, SEQ),
                                             dtype=np.int32)


def _phases(st):
    return st.dispatch_s + st.stall_s + st.assemble_s + st.sync_s


def test_streamed_run_counts_its_phases(model):
    plan = plan_always_next(model.graph, CHUNK)
    st = StreamingExecutor(model, plan, disk_bw=DISK_BW).run(_tokens())
    assert st.stall_events > 0 and st.stall_s > 0
    assert st.put_s > 0 and st.streamed_bytes > 0
    assert st.assemble_s > 0 and st.sync_s > 0 and st.dispatch_s > 0
    assert st.ops_run == len(model.graph.ops)
    assert _phases(st) <= st.exec_s + EPS
    assert _phases(st) == pytest.approx(st.exec_s, rel=1e-6)


def test_preloaded_run_never_waits_or_streams(model):
    plan = plan_preload_all(model.graph, CHUNK)
    st = StreamingExecutor(model, plan, disk_bw=DISK_BW).run(_tokens())
    assert st.stall_s == st.put_s == st.assemble_s == 0
    assert st.stall_events == st.streamed_bytes == 0
    assert st.preloaded_bytes > 0
    assert _phases(st) <= st.exec_s + EPS


def test_preempted_run_sums_its_segments(model):
    plan = plan_always_next(model.graph, CHUNK)
    ex = StreamingExecutor(model, plan, disk_bw=DISK_BW)
    state = ex.begin(_tokens(), batch=7)
    half = len(model.graph.ops) // 2
    assert not ex.advance(state, lambda i: i >= half)
    assert state.stats.ops_run == half
    assert ex.advance(state)
    st = state.stats
    assert st.ops_run == len(model.graph.ops)
    assert _phases(st) == pytest.approx(st.exec_s, rel=1e-6)


def _host_lines(log_dir):
    """{line index: [(start_ns, end_ns, name, stats)]} of the host plane's
    ``flashmem.*`` events, one line per thread."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                   for e in line.events if e.name.startswith("flashmem.")]
            if evs:
                out[i] = evs
    return out


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_one_served_batch_leaves_nested_spans_with_one_batch_id(model,
                                                                tmp_path):
    eng = ServingEngine(policy="stream", chunk_bytes=CHUNK, hw=HW,
                        disk_bw=DISK_BW)
    eng.register("m", model)
    eng.plans["m"] = plan_always_next(model.graph, CHUNK)
    req = Request(model="m", tokens=_tokens(), arrival_s=0.0, req_id=0)
    with jax.profiler.trace(str(tmp_path)):
        out = eng.serve(RequestStream.from_trace([req]))
    assert [r.status for r in out] == ["ok"]
    lines = _host_lines(str(tmp_path))

    serving = [i for i, evs in lines.items()
               if any(n == "flashmem.exec.ops" for _, _, n, _ in evs)]
    assert len(serving) == 1
    evs = lines[serving[0]]
    names = {n for _, _, n, _ in evs}
    assert {"flashmem.engine.schedule", "flashmem.exec.begin",
            "flashmem.exec.ops", "flashmem.exec.wait_weight",
            "flashmem.exec.assemble", "flashmem.exec.sync",
            "flashmem.engine.respond"} <= names
    ops, = [e for e in evs if e[2] == "flashmem.exec.ops"]
    for child in ("wait_weight", "assemble", "sync"):
        spans = [e for e in evs if e[2] == f"flashmem.exec.{child}"]
        assert spans and all(_inside(e, ops) for e in spans)

    loader = [i for i, evs in lines.items()
              if any(n == "flashmem.loader.task" for _, _, n, _ in evs)]
    assert loader and serving[0] not in loader
    assert all(n.startswith("flashmem.loader.")
               for i in loader for _, _, n, _ in lines[i])

    every = [e for line in lines.values() for e in line]
    tagged = [e for e in every if "batch" in e[3]]
    assert {e[3]["batch"] for e in tagged} == {eng.batch_log.total - 1}
    assert {e[3]["model"] for e in tagged} == {"m"}
    # only a pass that schedules no batch goes untagged
    assert {e[2] for e in every if "batch" not in e[3]} <= {
        "flashmem.engine.schedule"}
    # the counters of the same batch match its spans
    st = eng.stats_log[-1]
    waits = [e for e in evs if e[2] == "flashmem.exec.wait_weight"]
    assert st.stall_events == len(waits)
    assert st.stall_s == pytest.approx(
        sum(e[1] - e[0] for e in waits) * 1e-9, rel=0.2, abs=1e-3)


def test_prefetch_span_carries_the_running_batch_id(model, tmp_path):
    eng = ServingEngine(policy="stream", chunk_bytes=CHUNK, hw=HW,
                        budget_bytes=4 * sum(
                            a.nbytes for a in model.host_weights.values()))
    eng.register("a", model)
    eng.register("b", HostModel.build(CFG, seq=SEQ, batch=1, seed=1))
    reqs = [Request(model=m, tokens=_tokens(), arrival_s=0.0, req_id=i)
            for i, m in enumerate("abab")]
    with jax.profiler.trace(str(tmp_path)):
        out = eng.serve(RequestStream.from_trace(reqs))
    assert [r.status for r in out] == ["ok"] * 4
    assert eng.prefetch_log
    every = [e for line in _host_lines(str(tmp_path)).values() for e in line]
    pre = [e for e in every if e[2] == "flashmem.prefetch"]
    assert len(pre) == len(eng.prefetch_log)
    running = {e[3]["batch"]: e[3]["model"] for e in every
               if e[2] == "flashmem.exec.ops"}
    for e in pre:
        # prefetching the other model while this batch runs
        assert e[3]["model"] != running[e[3]["batch"]]


def test_a_failing_batch_leaves_no_engine_span_open(model, monkeypatch):
    import repro.serving.engine as engine_mod

    def boom(batch, result):
        raise RuntimeError("de-batching failed")

    monkeypatch.setattr(engine_mod, "split_batch_result", boom)
    eng = ServingEngine(policy="stream", chunk_bytes=CHUNK, hw=HW)
    eng.register("m", model)
    req = Request(model="m", tokens=_tokens(), arrival_s=0.0, req_id=0)
    ses = eng.serve_session(RequestStream.from_trace([req]))
    with pytest.raises(RuntimeError, match="de-batching"):
        while ses.step()[0] != "done":
            pass
    assert ses.phase._span is None
