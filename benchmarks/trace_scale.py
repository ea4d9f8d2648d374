"""Trace-scale serving replay: 10^5 requests through the full online
loop with a synthetic executor, asserting the PR-8 scalability budgets
and reporting scheduler-quality metrics per trace family.

The point is to exercise every HOT serving-loop path — arrival polling,
weighted-EDF admission/queueing, deadline-aware batching, the
event-driven idle stepping, ring-buffered logs — at a request count
where any quadratic path or unbounded log is unmissable, WITHOUT paying
for real model execution: each engine's executors are replaced by a
synthetic one that returns a constant-shape ``RunStats`` (no result
tensor), and a ``SimClock`` charges the usual deterministic virtual
``EXEC_S * (1 + growth*(b-1))`` per batch. Scheduling behaviour
(admission, ordering, batching, deadlines) is bit-identical to a real
run with those charges; only the tensor math is skipped.

Asserted budgets (the ISSUE's acceptance criteria), on the big diurnal
replay in both full and ``--smoke`` mode:

  * wall-clock per event    < ``PER_EVENT_BUDGET_US`` (generous — a
    quadratic queue path blows it by orders of magnitude at 10^5);
  * tracemalloc peak        < ``MEM_BUDGET_BYTES`` over the serve call
    (the O(n) trace/response arrays dominate; unbounded logs roughly
    double it, rings keep it flat);
  * session steps           <= ``STEP_FACTOR`` * requests + slack (the
    event-driven loop costs O(1) steps per event, never per poll tick);
  * every log's retained length <= ``LOG_CAP`` while the lifetime
    ``.total`` counters keep exact counts.

Trace families (serving/traces.py), each replayed under "fifo" and
"slo" scheduling on identical seeded traffic:

  * ``diurnal``      — sinusoidal day/night load (thinned Poisson), the
                       scale cell;
  * ``flash_crowd``  — x20 rate spike on one model mid-trace;
  * ``multi_tenant`` — three tenants with per-tenant SLOs/priorities;
                       reports per-tenant goodput and Jain fairness;
  * ``session``      — correlated successive-model chains (the paper's
                       multi-DNN pipeline); reports the model-switch
                       fraction that makes it hard on caching.

Run: ``PYTHONPATH=src python -m benchmarks.run --only trace_scale``
CI artifact: ``PYTHONPATH=src python -m benchmarks.trace_scale --smoke
--out BENCH_trace_scale.json``
"""
from __future__ import annotations

import argparse
import json
import time
import tracemalloc
from dataclasses import replace

import numpy as np

from benchmarks.common import Row
from repro.configs.gptneo import GPTNEO_S
from repro.core.latency_model import BatchLatencyEstimator
from repro.core.streaming import HostModel, RunStats
from repro.serving.batcher import BatcherConfig
from repro.serving.clock import SimClock
from repro.serving.config import ServeConfig
from repro.serving.engine import ServingEngine
from repro.serving.stream import RequestStream
from repro.serving.traces import (TenantSpec, diurnal_trace,
                                  flash_crowd_trace, jain_fairness,
                                  multi_tenant_trace, session_trace)
from repro.serving.types import Request, SLOConfig, prediction_error

SEQ = 8
VOCAB = 64
EXEC_S = 0.004         # virtual seconds per size-1 batch
BATCH_GROWTH = 0.15
MAX_BATCH = 4          # full-batch capacity ~690 req/s — peaks exceed it
SLO_S = 0.08
LOG_CAP = 256          # small on purpose: totals must exceed it at scale

# asserted budgets — generous absolute bounds; the failure mode they
# guard (a re-quadratic queue path / unbounded log) overshoots by 10x+
PER_EVENT_BUDGET_US = 2500.0
MEM_BUDGET_BYTES = 1 << 30
STEP_FACTOR = 3.0      # steps <= 3*requests + slack (batch+idle per event)

SCHEDULERS = ("fifo", "slo")


class _SyntheticExecutor:
    """Stand-in for Preload/StreamingExecutor: constant-shape stats, no
    tensor math, no result. Not a StreamingExecutor, so the serve loop
    takes the non-preemptible ``run()`` path and the SimClock charges
    the deterministic per-batch time."""

    def __init__(self, name: str):
        self.name = name

    def run(self, tokens, batch: int = -1) -> RunStats:
        return RunStats(init_s=0.0, exec_s=EXEC_S, peak_bytes=1 << 20,
                        avg_bytes=float(1 << 20), residency=[1 << 20],
                        model=self.name, result=None)


def _models():
    tiny = replace(GPTNEO_S, d_model=32, n_heads=2, n_kv_heads=2,
                   d_ff=64, vocab=VOCAB, num_layers=1)
    return {n: HostModel.build(replace(tiny, name=n), seq=SEQ, seed=i)
            for i, n in enumerate(("a", "b", "c"))}


def _engine(models) -> ServingEngine:
    eng = ServingEngine(policy="preload", budget_bytes=None,
                        log_cap=LOG_CAP)
    for n, m in models.items():
        eng.register(n, m)
    # swap in synthetic executors AFTER registration (register
    # invalidates the executor cache)
    for n in models:
        eng._executors[n] = _SyntheticExecutor(n)
    return eng


def _replay(models, trace, scheduler: str, *, measure_mem: bool = False,
            result_mode: str = "object"):
    """One full replay; returns (engine, session, responses, wall_s,
    tracemalloc_peak_bytes_or_None)."""
    eng = _engine(models)
    sess = eng.serve_session(
        RequestStream.from_trace(list(trace)),
        clock=SimClock(exec_time=EXEC_S, batch_growth=BATCH_GROWTH),
        config=ServeConfig(
            scheduler=scheduler, slo=SLOConfig(default_slo_s=SLO_S),
            batcher=BatcherConfig(max_batch=MAX_BATCH, max_wait_s=0.01),
            cost_model=BatchLatencyEstimator(
                priors={n: EXEC_S for n in models}, growth=BATCH_GROWTH),
            result_mode=result_mode))
    peak = None
    if measure_mem:
        tracemalloc.start()
    t0 = time.perf_counter()
    responses = sess.run()
    wall = time.perf_counter() - t0
    if measure_mem:
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
    assert len(responses) == len(trace), \
        (scheduler, len(responses), len(trace))
    return eng, sess, responses, wall, peak


def _assert_budgets(eng, sess, n_requests: int, wall_s: float, peak,
                    *, at_scale: bool):
    per_event_us = wall_s / max(n_requests, 1) * 1e6
    assert per_event_us < PER_EVENT_BUDGET_US, \
        f"per-event wall {per_event_us:.0f}us > {PER_EVENT_BUDGET_US}us"
    if peak is not None:
        assert peak < MEM_BUDGET_BYTES, \
            f"tracemalloc peak {peak / 1e6:.0f}MB > budget"
    assert sess.steps <= STEP_FACTOR * n_requests + 64, \
        f"{sess.steps} steps for {n_requests} requests — not O(events)"
    for log_name in ("timeline", "stats_log", "batch_log", "idle_log",
                     "admission_log", "defer_log", "prefetch_log",
                     "preempt_log", "kv_log", "replan_log", "rejected"):
        log = getattr(eng, log_name)
        assert len(log) <= LOG_CAP, (log_name, len(log))
    if at_scale:
        # the rings really truncated: lifetime counts exceed retention
        assert eng.batch_log.total > LOG_CAP, eng.batch_log.total


def _cell(eng, sess, responses, wall_s, peak=None) -> dict:
    rep = eng.slo_report(responses)
    n = len(responses)
    cell = {
        "requests": rep["requests"], "served": rep["served"],
        "miss_rate": rep["miss_rate"],
        "rejection_rate": rep["rejection_rate"],
        "batches": eng.batch_log.total, "steps": sess.steps,
        "deferred_joins": rep["deferred_joins"],
        "per_event_us": wall_s / max(n, 1) * 1e6,
        "wall_s": wall_s,
    }
    if peak is not None:
        cell["peak_tracemalloc_mb"] = peak / 1e6
    return cell


# -- trace families ---------------------------------------------------------

def _diurnal(models, n: int):
    base = {m: 133.0 for m in models}          # ~400 req/s aggregate;
    duration = n / sum(base.values())          # peak 640 strains capacity
    return diurnal_trace(base, duration, period_s=max(duration / 4, 1.0),
                         depth=0.6, vocab=VOCAB, seq=SEQ, seed=7)


def _flash(models, n: int):
    base = {m: 40.0 for m in models}           # 120 req/s + 760 in-window
    duration = n / 196.0
    return flash_crowd_trace(base, duration, crowd_model="a",
                             start_s=0.4 * duration,
                             span_s=0.1 * duration, factor=20.0,
                             vocab=VOCAB, seq=SEQ, seed=11)


def _bulk_trace(models, n: int, *, rate: float = 400.0, seed: int = 17):
    """``n``-request constant-rate Poisson trace built the columnar way:
    vectorized numpy arrivals and model picks, ONE shared tokens array
    across every request (the synthetic executor never reads tokens), and
    stamped ``req_id``s. At 10^6 requests the per-request token arrays a
    normal generator allocates would dominate memory before the serve
    loop even starts."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    names = tuple(models)
    which = rng.integers(0, len(names), size=n)
    tokens = rng.integers(0, VOCAB, (1, SEQ)).astype(np.int32)
    return [Request(model=names[w], tokens=tokens, arrival_s=t, req_id=i)
            for i, (w, t) in enumerate(zip(which.tolist(),
                                           arrivals.tolist()))]


TENANTS = {
    "interactive": TenantSpec(models=("a", "b"), rate=240.0,
                              slo_s=0.06, priority=2.0),
    "standard": TenantSpec(models=("b", "c"), rate=240.0,
                           slo_s=0.15, priority=1.0),
    "batch": TenantSpec(models=("a", "b", "c"), rate=240.0,
                        slo_s=0.5, priority=0.5),
}


def _tenant_metrics(responses, tenant_of) -> dict:
    per = {}
    for name in TENANTS:
        rs = [r for r in responses if tenant_of.get(r.req_id) == name]
        ok = [r for r in rs if r.status == "ok" and r.deadline_met]
        per[name] = {"requests": len(rs),
                     "ontime_frac": len(ok) / len(rs) if rs else 0.0}
    return {"per_tenant": per,
            "jain_frac": jain_fairness(
                [per[n]["ontime_frac"] for n in sorted(per)])}


def _scale_family(models, *, n_equiv: int, n_big: int,
                  smoke: bool) -> dict:
    """The PR-10 columnar cell: (1) replay the same trace in object and
    columnar storage and assert the reducers agree bit-for-bit — the two
    modes feed one vectorized kernel, and with synthetic executors every
    response field is deterministic, so the full row round-trip must be
    exact too; (2) push the columnar path to ``n_big`` requests (10^6 in
    full mode) under the standard wall/step/log budgets, with tracemalloc
    peak PER REQUEST strictly below the object mode's — the object path's
    per-request dataclass allocations are what the struct-of-arrays
    layout removes."""
    trace = _bulk_trace(models, n_equiv)
    eng_o, sess_o, resp_o, wall_o, peak_o = _replay(
        models, trace, "slo", measure_mem=True)
    eng_c, sess_c, resp_c, wall_c, peak_c = _replay(
        models, trace, "slo", measure_mem=True, result_mode="columnar")
    assert eng_o.slo_report(resp_o) == eng_c.slo_report(resp_c), \
        "object vs columnar slo_report diverged"
    assert prediction_error(resp_o) == prediction_error(resp_c), \
        "object vs columnar prediction_error diverged"
    assert resp_o == resp_c.to_responses(), \
        "object vs columnar row round-trip diverged"
    assert peak_c < peak_o, \
        f"columnar peak {peak_c} not below object peak {peak_o} " \
        f"at n={n_equiv}"

    big = _bulk_trace(models, n_big)
    eng_b, sess_b, resp_b, wall_b, peak_b = _replay(
        models, big, "slo", measure_mem=True, result_mode="columnar")
    _assert_budgets(eng_b, sess_b, n_big, wall_b, peak_b,
                    at_scale=not smoke)
    assert peak_b / n_big < peak_o / n_equiv, \
        f"columnar per-request peak {peak_b / n_big:.1f}B not below " \
        f"object mode's {peak_o / n_equiv:.1f}B"
    return {
        "requests": n_big,
        "object": _cell(eng_o, sess_o, resp_o, wall_o, peak_o),
        "columnar": _cell(eng_c, sess_c, resp_c, wall_c, peak_c),
        "columnar_big": _cell(eng_b, sess_b, resp_b, wall_b, peak_b),
    }


def sweep(*, smoke: bool = False) -> dict:
    models = _models()
    sizes = ({"diurnal": 2000, "flash": 1500, "mt": 1500, "session": 600,
              "scale_equiv": 5_000, "scale_big": 50_000}
             if smoke else
             {"diurnal": 100_000, "flash": 20_000, "mt": 20_000,
              "session": 5_000,
              "scale_equiv": 100_000, "scale_big": 1_000_000})
    result = {"bench": "trace_scale", "exec_s": EXEC_S,
              "batch_growth": BATCH_GROWTH, "max_batch": MAX_BATCH,
              "slo_s": SLO_S, "log_cap": LOG_CAP, "families": {}}

    # -- diurnal: THE scale cell — budgets asserted here -------------------
    trace = _diurnal(models, sizes["diurnal"])
    fam = {"requests": len(trace)}
    for sched in SCHEDULERS:
        eng, sess, responses, wall, peak = _replay(
            models, trace, sched, measure_mem=True)
        _assert_budgets(eng, sess, len(trace), wall, peak,
                        at_scale=not smoke)
        fam[sched] = _cell(eng, sess, responses, wall, peak)
    result["families"]["diurnal"] = fam

    # -- flash crowd -------------------------------------------------------
    trace = _flash(models, sizes["flash"])
    fam = {"requests": len(trace)}
    for sched in SCHEDULERS:
        eng, sess, responses, wall, _ = _replay(models, trace, sched)
        fam[sched] = _cell(eng, sess, responses, wall)
    result["families"]["flash_crowd"] = fam

    # -- multi-tenant ------------------------------------------------------
    duration = sizes["mt"] / sum(t.rate for t in TENANTS.values())
    trace, tenant_of = multi_tenant_trace(TENANTS, duration,
                                          vocab=VOCAB, seq=SEQ, seed=23)
    fam = {"requests": len(trace)}
    for sched in SCHEDULERS:
        eng, sess, responses, wall, _ = _replay(models, trace, sched)
        cell = _cell(eng, sess, responses, wall)
        cell.update(_tenant_metrics(responses, tenant_of))
        fam[sched] = cell
    result["families"]["multi_tenant"] = fam

    # -- correlated sessions ----------------------------------------------
    trace = session_trace(tuple(models), 20.0, sizes["session"] / 60.0,
                          chain_len=3, think_s=0.05, vocab=VOCAB,
                          seq=SEQ, seed=31)
    fam = {"requests": len(trace)}
    for sched in SCHEDULERS:
        eng, sess, responses, wall, _ = _replay(models, trace, sched)
        cell = _cell(eng, sess, responses, wall)
        batches = [m for _, m, _ in eng.batch_log]
        switches = sum(1 for x, y in zip(batches, batches[1:]) if x != y)
        cell["switch_frac"] = switches / max(len(batches) - 1, 1)
        fam[sched] = cell
    result["families"]["session"] = fam

    # -- scale: columnar response path (PR 10) -----------------------------
    result["families"]["scale"] = _scale_family(
        models, n_equiv=sizes["scale_equiv"], n_big=sizes["scale_big"],
        smoke=smoke)
    return result


def run():
    result = sweep(smoke=True)
    rows = []
    for fam, cells in result["families"].items():
        for key, m in cells.items():
            if not isinstance(m, dict):
                continue            # the family-level "requests" count
            extra = ""
            if "jain_frac" in m:
                extra = f" jain={m['jain_frac']:.2f}"
            if "switch_frac" in m:
                extra = f" switch={m['switch_frac']:.2f}"
            rows.append(Row(
                f"trace_scale/{fam}/{key}", m["per_event_us"],
                f"n={m['requests']} served={m['served']} "
                f"miss={m['miss_rate']:.2f} "
                f"rej={m['rejection_rate']:.2f} "
                f"batches={m['batches']} steps={m['steps']}" + extra))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-n sweep (same budgets asserted) for CI")
    ap.add_argument("--out", default="",
                    help="write the sweep dict as JSON (BENCH_*.json)")
    args = ap.parse_args(argv)
    result = sweep(smoke=args.smoke)
    result["smoke"] = bool(args.smoke)
    payload = json.dumps(result, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
    print(payload)
    return result


if __name__ == "__main__":
    main()
