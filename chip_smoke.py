#!/usr/bin/env python3
"""Chip smoke: serve two full-width GPT-Neo-1.3B models from one streaming
pool on a TPU, and check what comes out.

    python chip_smoke.py              # one chip (the default phase)
    python chip_smoke.py --chips 4    # four replicas behind the Router

Default phase. Two ``gptneo-1.3b`` models at published widths (24 layers,
d_model 2048, 16 heads, d_ff 8192, vocab 50257), seq 1024, share one
``ServingEngine`` with ``policy="stream"`` and a 6144 MiB ``WeightCache``,
the batch path of ``python -m repro.launch.serve``. Host RAM is the real
source of the weights, so no storage stage is simulated (``disk_bw=0``).
Four requests alternate between the models, so every switch evicts and
re-streams. The run fails unless the plan fits the budget, the loader
streamed chunks, the pool ledger's peak stays within the budget, every
response is bit-for-bit equal to a ``PreloadExecutor`` run of the same
model and tokens on the chip, and one response agrees with a run on the
CPU backend within ``CPU_RTOL``.

``--chips 4``. Four ``Replica``s, one per device, behind the ``Router``
serve a short trace in which every replica serves both models, so each
evicts and re-streams on its own chip. Fails unless every pool entry of
replica r lives on ``jax.devices()[r]``, every replica evicted, every chip
reports bytes in use, and every response is bit-for-bit equal to the
one-chip preload result.

Both models are one ``HostModel`` registered under two names (the pool's
keys are per name); its weights are random, from a seed. The times printed
are smoke timings, not a benchmark. The last line of stdout is a JSON
object that says ``"ok": true`` only when every check passed; without a
TPU the script exits non-zero before serving anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from importlib.metadata import PackageNotFoundError, version
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.streaming import HostModel, PreloadExecutor  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.serving.replica import Replica  # noqa: E402
from repro.serving.router import Router  # noqa: E402
from repro.serving.weight_cache import WeightCache  # noqa: E402

MODEL = "gptneo-1.3b"
SEQ = 1024
BUDGET_BYTES = 6144 << 20
N_REQUESTS = 4
N_REPLICAS = 4
REPLICA_REQUESTS = 8
# The chip computes f32 matmuls at default precision: operands are rounded
# to bf16 (8-bit mantissa, ~2^-9 relative error each) and accumulated in
# f32. On a v5e the 24 layers differ from the CPU's full-f32 result by a
# relative L2 error of 2.7e-3, and a CPU emulation that rounds every matmul
# operand to bf16 differs from full f32 by 2.8e-3; the limit leaves about
# 3.5x room over both. A wrong computation (a wrong weight, chunks out of
# order) on these random weights differs by a relative error of order 1.
CPU_RTOL = 1e-2            # relative L2 error, ||tpu - cpu|| / ||cpu||
MiB = 1 << 20


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(msg, flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)
    log(f"PASS {what}")


def device_report() -> dict:
    dev = jax.devices()[0]
    rep = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices())}
    log(f"platform {rep['platform']}")
    log(f"device_kind {rep['kind']}")
    log(f"device_count {rep['count']}")
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            log(f"{pkg} {version(pkg)}")
        except PackageNotFoundError:
            log(f"{pkg} not installed")
    return rep


def build_model(layers: int = 0, seq: int = SEQ) -> HostModel:
    """``gptneo-1.3b`` at published widths; ``layers`` > 0 cuts depth."""
    cfg = get_arch(MODEL).model
    if layers:
        cfg = replace(cfg, num_layers=layers)
    t0 = time.perf_counter()
    m = HostModel.build(cfg, seq=seq, seed=0)
    nbytes = sum(a.nbytes for a in m.host_weights.values())
    log(f"model {cfg.name}: layers {cfg.num_layers} d_model {cfg.d_model} "
        f"heads {cfg.n_heads} d_ff {cfg.d_ff} vocab {cfg.vocab} seq {seq} "
        f"f32 weights {nbytes / MiB:.1f} MiB "
        f"(host build {time.perf_counter() - t0:.1f}s)")
    return m


def request_tokens(model: HostModel, n: int) -> list:
    rng = np.random.default_rng(0)
    return [rng.integers(0, model.cfg.vocab, (1, model.seq), dtype=np.int32)
            for _ in range(n)]


def model_names(model: HostModel) -> list:
    return [f"{model.cfg.name}#{i}" for i in range(2)]


def _bytes_in_use(dev, key: str = "bytes_in_use"):
    st = dev.memory_stats()
    return None if st is None else int(st.get(key, 0))


def serve_phase(model: HostModel, tokens: list, budget_bytes: int, *,
                hw=None) -> dict:
    """Serve ``tokens`` alternately on the two names of ``model`` through
    one streaming engine with a shared pool. Returns the responses (in
    request order), per-batch ``RunStats``, the plan, the pool ledger peak
    and the device's peak bytes."""
    names = model_names(model)
    engine = ServingEngine(policy="stream", chunk_bytes=MiB,
                           budget_bytes=budget_bytes, disk_bw=0.0, hw=hw)
    for n in names:
        engine.register(n, model)
    t0 = time.perf_counter()
    engine._ensure_planned()
    mp = engine.multi_plan
    log(f"planner hw {engine.hw}")
    log(f"plan ({time.perf_counter() - t0:.1f}s): budget "
        f"{budget_bytes / MiB:.0f} MiB fits_budget {mp.fits_budget()}")
    for n, plan in mp.plans.items():
        chunks = sum(t.chunk_hi - t.chunk_lo
                     for ts in plan.loads.values() for t in ts)
        log(f"  {n}: planned peak {mp.peaks[n] / MiB:.1f} MiB preload "
            f"{plan.preload_bytes(model.graph) / MiB:.1f} MiB streamed "
            f"{chunks} chunks")
    for i, t in enumerate(tokens):
        engine.submit(Request(model=names[i % 2], tokens=t, req_id=i))
    responses = sorted(engine.run_all(), key=lambda r: r.req_id)
    stats = list(engine.stats_log)
    out = {"plan": mp, "responses": responses, "stats": stats,
           "ledger_peak": engine.peak_memory(),
           "pool": engine.cache.stats.as_dict(),
           "device_peak": _bytes_in_use(jax.devices()[0],
                                        "peak_bytes_in_use")}
    engine.cache.clear()
    return out


def preload_reference(model: HostModel, tokens: list, device=None) -> list:
    """``PreloadExecutor`` results for ``tokens`` on ``device``, through a
    private pool that holds every weight so they are loaded once."""
    total = sum(a.nbytes for a in model.host_weights.values())
    pool = WeightCache(total + 64 * MiB)
    ex = PreloadExecutor(model, cache=pool, cache_key="reference",
                         device=device)
    out = [np.asarray(ex.run(t).result) for t in tokens]
    pool.clear()
    return out


def cpu_rel_error(model: HostModel, tokens, result) -> float:
    """Relative L2 error of ``result`` against a run on the CPU backend."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = PreloadExecutor(model, device=cpu).run(tokens).result
    ref = np.asarray(ref, np.float64)
    got = np.asarray(result, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_serving(res: dict, refs: list, budget_bytes: int):
    """The default phase's checks on what ``serve_phase`` returned."""
    for st, r in zip(res["stats"], res["responses"]):
        log(f"request {r.req_id} {r.model}: smoke timing {r.latency_s:.3f}s "
            f"(init {r.init_s:.3f} exec {r.exec_s:.3f})"
            + (" first request, compile included" if r.req_id == 0 else "")
            + f" preloaded {st.preloaded_bytes / MiB:.1f} MiB streamed "
            f"{st.streamed_chunks} chunks {st.streamed_bytes / MiB:.1f} MiB "
            f"hits {st.cache_hits} misses {st.cache_misses} "
            f"stall_events {st.stall_events} peak {st.peak_bytes / MiB:.1f} MiB")
    pool = res["pool"]
    log(f"pool: evictions {pool['evictions']} evicted "
        f"{pool['evicted_bytes'] / MiB:.1f} MiB hits {pool['hits']} "
        f"misses {pool['misses']}")
    dp = res["device_peak"]
    log("device peak_bytes_in_use "
        + ("not reported" if dp is None else f"{dp / MiB:.1f} MiB"))
    check(res["plan"].fits_budget(), "plan fits the budget")
    streamed = sum(st.streamed_chunks for st in res["stats"])
    check(streamed > 0, f"loader streamed {streamed} chunks (> 0)")
    check(res["ledger_peak"] <= budget_bytes,
          f"pool ledger peak {res['ledger_peak'] / MiB:.1f} MiB <= budget "
          f"{budget_bytes / MiB:.0f} MiB")
    same = [np.array_equal(np.asarray(r.result), ref)
            for r, ref in zip(res["responses"], refs)]
    check(len(same) == len(res["responses"]) and all(same),
          f"{len(same)} streamed results bit-equal to preload")


def replica_phase(model: HostModel, tokens: list, budget_bytes: int, *,
                  hw=None) -> dict:
    """Serve ``tokens`` through ``N_REPLICAS`` replicas, one per device,
    behind a round-robin Router. The first ``N_REPLICAS`` requests go to
    one name and the next to the other, so every replica serves both and
    re-streams across a switch. Returns the responses in request order,
    the set of devices each replica's pool entries live on, each pool's
    evictions, and each device's bytes in use."""
    devs = jax.devices()
    if len(devs) < N_REPLICAS:
        raise SmokeFailure(f"{N_REPLICAS} replicas need {N_REPLICAS} "
                           f"devices, found {len(devs)}")
    names = model_names(model)
    fleet = []
    for rid in range(N_REPLICAS):
        rep = Replica(rid, policy="stream", chunk_bytes=MiB,
                      budget_bytes=budget_bytes, disk_bw=0.0, hw=hw)
        for n in names:
            rep.register(n, model)
        fleet.append(rep.start())
    trace = [Request(model=names[(i // N_REPLICAS) % 2], tokens=t,
                     arrival_s=0.0, req_id=i)
             for i, t in enumerate(tokens)]
    # every request arrives at once, so round robin gives each replica
    # its share; the timeout (virtual seconds) outlasts any real batch
    router = Router(fleet, routing="round_robin", timeout_s=600.0)
    t0 = time.perf_counter()
    responses = router.serve(trace)
    log(f"fleet served {len(responses)} requests in "
        f"{time.perf_counter() - t0:.1f}s (smoke timing)")
    placement, evictions = {}, {}
    for rep in fleet:
        on = set()
        for _, v in rep.engine.cache.items():
            for a in (v if isinstance(v, tuple) else (v,)):
                if isinstance(a, jax.Array):
                    on |= {d.id for d in a.devices()}
        placement[rep.rid] = on
        pool = rep.engine.cache.stats
        evictions[rep.rid] = pool.evictions
        log(f"replica {rep.rid} on device {rep.device.id}: pool "
            f"{rep.engine.cache.used_bytes() / MiB:.1f} MiB on devices "
            f"{sorted(on)} evictions {pool.evictions} evicted "
            f"{pool.evicted_bytes / MiB:.1f} MiB")
    in_use = [_bytes_in_use(d) for d in devs[:N_REPLICAS]]
    log("bytes_in_use per device "
        + " ".join("not reported" if b is None else f"{b / MiB:.1f}MiB"
                   for b in in_use))
    return {"responses": responses, "placement": placement,
            "evictions": evictions,
            "devices": [d.id for d in devs[:N_REPLICAS]],
            "bytes_in_use": in_use}


def check_replicas(res: dict, refs: list):
    devs = res["devices"]
    check(all(res["placement"][r] == {devs[r]} for r in range(len(devs))),
          "every pool entry of replica r lives on jax.devices()[r]")
    check(all(res["evictions"][r] > 0 for r in range(len(devs))),
          "every replica evicted to switch models")
    check(all(b is not None and b > 0 for b in res["bytes_in_use"]),
          f"bytes in use on all {len(devs)} chips")
    ok = [r.status == "ok" and np.array_equal(np.asarray(r.result), ref)
          for r, ref in zip(res["responses"], refs)]
    check(len(ok) == len(refs) and all(ok),
          f"{len(ok)} replica results bit-equal to the one-chip preload")


def run_one_chip():
    model = build_model()
    tokens = request_tokens(model, N_REQUESTS)
    res = serve_phase(model, tokens, BUDGET_BYTES)
    t0 = time.perf_counter()
    refs = preload_reference(model, tokens)
    log(f"preload reference: {len(refs)} runs in "
        f"{time.perf_counter() - t0:.1f}s (smoke timing)")
    check_serving(res, refs, BUDGET_BYTES)
    t0 = time.perf_counter()
    err = cpu_rel_error(model, tokens[0], res["responses"][0].result)
    log(f"cpu cross-check {time.perf_counter() - t0:.1f}s: relative L2 "
        f"error {err!r}")
    check(err <= CPU_RTOL, f"chip agrees with the CPU backend within "
          f"relative L2 {CPU_RTOL} (default-precision f32 matmuls)")


def run_four_chips():
    model = build_model()
    tokens = request_tokens(model, REPLICA_REQUESTS)
    res = replica_phase(model, tokens, BUDGET_BYTES)
    refs = preload_reference(model, tokens, device=jax.devices()[0])
    check_replicas(res, refs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    # the CPU backend runs beside the chip for the cross-check
    plat = jax.config.jax_platforms
    if plat and "cpu" not in plat.split(","):
        jax.config.update("jax_platforms", plat + ",cpu")
    enable_compile_cache()
    rep = device_report()
    if rep["platform"] != "tpu":
        print(f"FAIL: no TPU (platform {rep['platform']}); this smoke "
              "runs only on the chip", file=sys.stderr)
        return 2
    if rep["count"] < args.chips:
        print(f"FAIL: --chips {args.chips} but {rep['count']} devices",
              file=sys.stderr)
        return 2
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": rep}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
