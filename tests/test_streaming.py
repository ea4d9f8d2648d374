"""Streaming-executor system tests: plan->execution equivalence, memory
bounds, baseline schedulers, serving engine (deliverables a/b/c)."""
import jax
import numpy as np
import pytest
from dataclasses import replace

from repro.configs.gptneo import GPTNEO_S
from repro.core import (HostModel, OPGProblem, OverlapPlan, PreloadExecutor,
                        StreamingExecutor, build_lm_graph, capacities,
                        plan_always_next, plan_preload_all, plan_same_op_type,
                        simulate, solve, streaming)
from repro.core.capacity import HWSpec
from repro.serving.weight_cache import WeightCache

CFG = replace(GPTNEO_S, num_layers=4, d_model=256, n_heads=4, n_kv_heads=4,
              d_ff=1024, vocab=1024, name="gptneo-tiny")
SEQ = 64


@pytest.fixture(scope="module")
def setup():
    graph = build_lm_graph(CFG, seq=SEQ, batch=1, dtype_bytes=4)
    hw = HWSpec.cpu_calibrated()
    chunk = 256 << 10
    prob = OPGProblem(graph, chunk, m_peak=8 << 20,
                      capacity=capacities(graph, chunk, hw))
    sol = solve(prob)
    plan = OverlapPlan.from_solution(prob, sol)
    model = HostModel.build(CFG, seq=SEQ, batch=1)
    tokens = np.random.default_rng(0).integers(0, CFG.vocab, (1, SEQ),
                                               dtype=np.int32)
    PreloadExecutor(model).run(tokens)   # warm kernels
    return graph, prob, sol, plan, model, tokens


def test_streaming_matches_preload_numerics(setup):
    graph, prob, sol, plan, model, tokens = setup
    st = StreamingExecutor(model, plan).run(tokens)
    pe = PreloadExecutor(model).run(tokens)
    np.testing.assert_allclose(np.asarray(st.result), np.asarray(pe.result),
                               atol=1e-5)


def test_streaming_reduces_memory(setup):
    graph, prob, sol, plan, model, tokens = setup
    st = StreamingExecutor(model, plan).run(tokens)
    total = sum(a.nbytes for a in model.host_weights.values())
    assert st.peak_bytes < 0.8 * total
    assert st.avg_bytes < 0.5 * total


def test_naive_plans_execute_correctly(setup):
    graph, prob, sol, plan, model, tokens = setup
    pe = PreloadExecutor(model).run(tokens)
    for build in (plan_always_next, plan_same_op_type):
        p = build(graph, prob.chunk_bytes)
        st = StreamingExecutor(model, p).run(tokens)
        np.testing.assert_allclose(np.asarray(st.result),
                                   np.asarray(pe.result), atol=1e-5)


def test_plan_serialization_roundtrip(setup):
    graph, prob, sol, plan, model, tokens = setup
    p2 = OverlapPlan.from_json(plan.to_json())
    assert p2.preload == plan.preload
    assert p2.chunk_bytes == plan.chunk_bytes
    assert {l: [(t.weight, t.chunk_lo, t.chunk_hi) for t in ts]
            for l, ts in p2.loads.items()} == \
           {l: [(t.weight, t.chunk_lo, t.chunk_hi) for t in ts]
            for l, ts in plan.loads.items()}


def test_simulator_monotone_in_m_peak(setup):
    """More memory headroom never increases simulated residency violations;
    preload-all always has max residency."""
    graph, prob, sol, plan, model, tokens = setup
    sim = simulate(plan, graph)
    pre = simulate(plan_preload_all(graph, prob.chunk_bytes), graph)
    assert sim.peak_bytes <= pre.peak_bytes
    assert sim.avg_bytes <= pre.avg_bytes


def test_plan_covers_all_weights(setup):
    graph, prob, sol, plan, model, tokens = setup
    streamed = {t.weight for ts in plan.loads.values() for t in ts}
    assert streamed | set(plan.preload) == set(graph.weights)


def test_serving_engine_stream_vs_preload():
    from repro.serving.engine import Request, ServingEngine
    rng = np.random.default_rng(0)
    results = {}
    for policy in ("stream", "preload"):
        eng = ServingEngine(policy=policy, m_peak=8 << 20)
        for i, name in enumerate(("a", "b")):
            eng.register(name, HostModel.build(CFG, seq=SEQ, seed=i))
        for r in range(4):
            name = ("a", "b")[r % 2]
            eng.submit(Request(model=name, tokens=rng.integers(
                0, CFG.vocab, (1, SEQ), dtype=np.int32)))
        eng.run_all()          # warm
        eng.timeline.clear()
        for r in range(4):
            name = ("a", "b")[r % 2]
            eng.submit(Request(model=name, tokens=rng.integers(
                0, CFG.vocab, (1, SEQ), dtype=np.int32)))
        eng.run_all()
        results[policy] = (eng.peak_memory(), eng.avg_memory())
    assert results["stream"][0] < results["preload"][0]
    assert results["stream"][1] < results["preload"][1]


def test_batcher_coalesces():
    from repro.serving.batcher import BatcherConfig, batch_requests
    from repro.serving.engine import Request
    reqs = [Request(model="a", tokens=np.zeros((1, 8), np.int32),
                    arrival_s=0.0) for _ in range(3)]
    reqs += [Request(model="b", tokens=np.zeros((1, 8), np.int32),
                     arrival_s=0.0)]
    out = batch_requests(reqs, BatcherConfig(max_batch=4, max_wait_s=1.0))
    assert len(out) == 2
    assert out[0].tokens.shape[0] == 3


def test_quantized_streaming_close_and_fewer_disk_bytes(setup):
    """Beyond-paper: int8 chunk streaming (4x fewer wire bytes) stays within
    quantization tolerance of the fp preload reference."""
    graph, prob, sol, plan, model, tokens = setup
    pe = PreloadExecutor(model).run(tokens)
    sq = StreamingExecutor(model, plan, quantize_stream=True).run(tokens)
    ref = np.asarray(pe.result)
    err = float(np.max(np.abs(np.asarray(sq.result) - ref)))
    assert err < 0.1 * float(np.std(ref)) + 0.05


# -- transfer unit: one device_put per contiguous run of missing chunks -----

SLAB_CFG = replace(CFG, num_layers=2, d_model=256, d_ff=1024, vocab=512,
                   name="gptneo-slab")
SLAB_CHUNK = 64 << 10       # projections of 4 and 16 chunks, norms of one


@pytest.fixture(scope="module")
def slab_model():
    m = HostModel.build(SLAB_CFG, seq=32, batch=1)
    toks = np.random.default_rng(1).integers(0, SLAB_CFG.vocab, (1, 32),
                                             dtype=np.int32)
    return m, toks, np.asarray(PreloadExecutor(m).run(toks).result)


def _views(n=4):
    a = np.arange(n * 6, dtype=np.float32).reshape(n * 2, 3)
    return [a[i:i + 2] for i in range(0, n * 2, 2)]


def _with_quantized(chunks, i):
    chunks[i] = streaming.quantize_chunk(chunks[i])
    return chunks


@pytest.mark.parametrize("chunks, lo, hi, want", [
    (_views(), 0, 4, [(0, 4)]),
    (_views(), 1, 3, [(1, 3)]),
    (_views()[::-1], 0, 4, [(0, 1), (1, 2), (2, 3), (3, 4)]),
    ([c.copy() for c in _views()], 0, 4, [(0, 1), (1, 2), (2, 3), (3, 4)]),
    (_with_quantized(_views(), 2), 0, 4, [(0, 2), (2, 3), (3, 4)]),
    (_views(), 2, 2, []),
])
def test_stretches_join_only_adjacent_row_views(chunks, lo, hi, want):
    assert list(streaming._stretches(chunks, lo, hi)) == want
    for a, b in want:
        if b - a > 1:
            slab = streaming._slab(chunks[a:b])
            np.testing.assert_array_equal(slab, np.concatenate(chunks[a:b]))
            assert np.shares_memory(slab, chunks[a])


@pytest.mark.parametrize("case", ["contiguous", "pool_hit", "quantized",
                                  "copies", "preempted", "capped"])
def test_loader_puts_one_slab_per_run_of_missing_chunks(slab_model,
                                                        monkeypatch, case):
    """Each plan task's run of missing plain chunks moves in one put of
    one host view, or in equal puts of at most ``SLAB_BYTES``; a pool hit
    splits the run, and quantised chunks or chunks that are not adjacent
    row views move one put each. Hit and
    miss counts stay per chunk, the result is the preload's, and the pool
    keeps no chunk or slab entry after the run."""
    m, toks, ref = slab_model
    plan = plan_always_next(m.graph, SLAB_CHUNK)
    tasks = [t for l in sorted(plan.loads) for t in plan.loads[l]]
    chunks = sum(t.chunk_hi - t.chunk_lo for t in tasks)
    assert any(t.chunk_hi - t.chunk_lo >= 16 for t in tasks)
    cache = WeightCache(64 << 20)
    hits = 0
    if case == "pool_hit":
        t = next(t for t in tasks if t.chunk_hi - t.chunk_lo >= 3)
        host = streaming.chunk_rows(m.host_weights[t.weight], SLAB_CHUNK)[1]
        cache.put(("m", t.weight, 1), jax.device_put(host), host.nbytes)
        hits = 1
    if case == "capped":
        monkeypatch.setattr(streaming, "SLAB_BYTES", 4 * SLAB_CHUNK)
    if case == "copies":
        real = streaming.chunk_rows
        monkeypatch.setattr(streaming, "chunk_rows",
                            lambda a, n: [c.copy() for c in real(a, n)])
    puts = []
    real_put = streaming.put_chunk

    def counted(chunk, device):
        puts.append(chunk)
        return real_put(chunk, device)
    monkeypatch.setattr(streaming, "put_chunk", counted)
    ex = StreamingExecutor(m, plan, cache=cache, cache_key="m",
                           quantize_stream=case == "quantized")
    if case == "preempted":
        state = ex.begin(toks)
        half = len(m.graph.ops) // 2
        assert not ex.advance(state, lambda i: i >= half)
        assert ex.advance(state)
        st = state.stats
    else:
        st = ex.run(toks)

    want = {"contiguous": len(tasks), "pool_hit": len(tasks) + 1,
            "quantized": chunks, "copies": chunks, "preempted": len(tasks),
            "capped": sum(-(-(t.chunk_hi - t.chunk_lo) // 4) for t in tasks)}
    assert st.h2d_puts == len(puts) == want[case]
    assert st.streamed_chunks == chunks - hits
    assert st.cache_hits == hits
    assert st.cache_misses == len(plan.preload) + len(tasks) + chunks - hits
    if case == "quantized":
        assert all(isinstance(p, tuple) or p.nbytes <= 4096 for p in puts)
        err = float(np.max(np.abs(np.asarray(st.result) - ref)))
        assert err < 0.1 * float(np.std(ref)) + 0.05
    else:
        np.testing.assert_array_equal(np.asarray(st.result), ref)
    assert cache.ledger_balanced()
    assert cache.pinned_bytes() == 0
    assert {k[2] for k, _ in cache.items()} == {"w"}
