"""Streaming executors — FlashMem's runtime (paper §4.4 + §5 baselines).

``HostModel`` holds weights host-side (numpy — the paper's "disk/UM") and a
register-machine program whose op sequence is *exactly* the planning graph
(core/graph.build_lm_graph), so plans map 1:1 onto execution.

Executors:
  * StreamingExecutor  — FlashMem: issues async device_put of the chunk
    tasks scheduled at each op (JAX's async dispatch = the independent DMA
    queue), assembles weights at first use, frees them after last use.
  * PreloadExecutor    — SmartMem/MNN-style: move+transform ALL weights,
    then run (init/exec split reporting).
  * Plans from plan_always_next / plan_same_op_type run through the same
    StreamingExecutor for the Fig 9 comparison.

The optional layout "transformation" applies the 2.5D->MXU tiling pack
(kernels/ref.layout_pack_ref) on device, mirroring the UM->TM transform the
paper optimizes; matmuls consume packed weights via the matching unpack.

Both executors can additionally be bound to a shared ``WeightCache``
(serving/weight_cache.py): chunks and assembled weights are then checked
in/out of one budgeted device pool, so repeated requests and interleaved
multi-model workloads hit device-resident weights instead of re-streaming
them from host/disk. Cache keys are ``(cache_key, weight, chunk_index)``
for in-flight chunks and ``(cache_key, weight, "w")`` for assembled
weights; the executor that assembles a weight consumes its chunk entries.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.graph import ModelGraph, build_lm_graph
from repro.core.plan import OverlapPlan
from repro.serving.weight_cache import WeightCache


# ---------------------------------------------------------------------------
# host model: weights + register program aligned with the planning graph
# ---------------------------------------------------------------------------

def _np_init(rng: np.random.Generator, shape, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (rng.standard_normal(shape) * scale).astype(np.float32)


@dataclass
class HostModel:
    cfg: ModelConfig
    seq: int
    batch: int
    graph: ModelGraph
    host_weights: Dict[str, np.ndarray]
    programs: Dict[str, Callable]       # op name -> fn(regs, w) -> regs

    @staticmethod
    def build(cfg: ModelConfig, *, seq: int = 128, batch: int = 1,
              seed: int = 0) -> "HostModel":
        assert cfg.family == "dense", "HostModel covers the LM families the " \
            "paper benchmarks (GPT-Neo/ViT-style dense stacks)"
        rng = np.random.default_rng(seed)
        graph = build_lm_graph(cfg, seq=seq, batch=batch, dtype_bytes=4)
        w: Dict[str, np.ndarray] = {}
        d, hd = cfg.d_model, cfg.resolved_head_dim
        nq, nkv = cfg.n_heads, cfg.n_kv_heads

        w["embed.w"] = _np_init(rng, (cfg.vocab, d), 0.02)
        for i in range(cfg.num_layers):
            w[f"L{i}.norm1.w"] = np.ones((2, d), np.float32)
            w[f"L{i}.norm2.w"] = np.ones((2, d), np.float32)
            w[f"L{i}.wq.w"] = _np_init(rng, (d, nq * hd))
            w[f"L{i}.wk.w"] = _np_init(rng, (d, nkv * hd))
            w[f"L{i}.wv.w"] = _np_init(rng, (d, nkv * hd))
            w[f"L{i}.wo.w"] = _np_init(rng, (nq * hd, d))
            w[f"L{i}.ffn_in.w"] = _np_init(rng, (d, cfg.d_ff))
            if cfg.glu:
                w[f"L{i}.ffn_gate.w"] = _np_init(rng, (d, cfg.d_ff))
            w[f"L{i}.ffn_out.w"] = _np_init(rng, (cfg.d_ff, d))
        w[f"L{cfg.num_layers}.final_norm.w"] = np.ones((2, d), np.float32)
        if not cfg.tie_embeddings:
            w[f"L{cfg.num_layers}.lm_head.w"] = _np_init(rng, (d, cfg.vocab))

        programs = _build_programs(cfg)
        return HostModel(cfg, seq, batch, graph, w, programs)

    def weight_rows(self, name: str) -> int:
        return self.host_weights[name].shape[0]


def _build_programs(cfg: ModelConfig) -> Dict[str, Callable]:
    """Jitted per-op-kind closures over a register dict."""
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads

    @jax.jit
    def f_embed(tokens, w):
        return w[tokens]

    @jax.jit
    def f_norm(x, w):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        if cfg.norm == "layernorm":
            return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w[0] + w[1]
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6) * w[0]

    @jax.jit
    def f_matmul(x, w):
        return x @ w

    @jax.jit
    def f_attn(q, k, v):
        b, s = q.shape[:2]
        qh = q.reshape(b, s, nq, hd)
        kh = k.reshape(b, s, nkv, hd)
        vh = v.reshape(b, s, nkv, hd)
        if nq != nkv:
            kh = jnp.repeat(kh, nq // nkv, 2)
            vh = jnp.repeat(vh, nq // nkv, 2)
        sc = jnp.einsum("bqhd,bphd->bhqp", qh, kh) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqp,bphd->bqhd", p, vh)
        return o.reshape(b, s, nq * hd)

    @jax.jit
    def f_act(x):
        return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)

    @jax.jit
    def f_gate(g, u):
        return (jax.nn.gelu(g) if cfg.act == "gelu" else jax.nn.silu(g)) * u

    @jax.jit
    def f_add(a, b):
        return a + b

    def step(tag):
        def run(regs, w):
            if tag == "embed":
                regs["x"] = f_embed(regs["tokens"], w)
            elif tag in ("norm1", "norm2", "final_norm"):
                regs["h"] = f_norm(regs["x"], w)
            elif tag == "wq":
                regs["q"] = f_matmul(regs["h"], w)
            elif tag == "wk":
                regs["k"] = f_matmul(regs["h"], w)
            elif tag == "wv":
                regs["v"] = f_matmul(regs["h"], w)
            elif tag == "attn":
                regs["a"] = f_attn(regs["q"], regs["k"], regs["v"])
            elif tag == "wo":
                regs["a"] = f_matmul(regs["a"], w)
            elif tag == "res1":
                regs["x"] = f_add(regs["x"], regs["a"])
            elif tag == "ffn_in":
                regs["u"] = f_matmul(regs["h"], w)
            elif tag == "ffn_gate":
                regs["g"] = f_matmul(regs["h"], w)
            elif tag == "act":
                regs["u"] = f_gate(regs["g"], regs["u"]) if "g" in regs \
                    and self_glu else f_act(regs["u"])
            elif tag == "ffn_out":
                regs["u"] = f_matmul(regs["u"], w)
            elif tag == "res2":
                regs["x"] = f_add(regs["x"], regs["u"])
            elif tag == "lm_head":
                regs["x"] = f_matmul(regs["h"], w)
            elif tag == "rope":
                pass  # positions baked into attention for this benchmark LM
            else:
                raise KeyError(tag)
            return regs
        return run

    self_glu = cfg.glu
    tags = ["embed", "norm1", "norm2", "final_norm", "wq", "wk", "wv", "attn",
            "wo", "res1", "ffn_in", "ffn_gate", "act", "ffn_out", "res2",
            "lm_head", "rope"]
    return {t: step(t) for t in tags}


def op_tag(op_name: str) -> str:
    return op_name.split(".")[-1]


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------

@dataclass
class RunStats:
    init_s: float = 0.0
    exec_s: float = 0.0
    peak_bytes: int = 0
    avg_bytes: float = 0.0
    residency: List[int] = field(default_factory=list)
    stall_events: int = 0
    model: str = ""
    requests: int = 1            # user requests this run served (batch size)
    cache_hits: int = 0          # weight-pool probes served device-resident
    cache_misses: int = 0        # probes that had to stream from host/disk
    preloaded_bytes: int = 0     # whole weights device_put before the op loop
    streamed_chunks: int = 0     # chunks the loader device_put during it
    streamed_bytes: int = 0
    result: Any = None

    @property
    def integrated_s(self) -> float:
        return self.init_s + self.exec_s

    @property
    def cache_hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0


def chunk_rows(arr: np.ndarray, chunk_bytes: int):
    """Split along rows into exactly T(w) = ceil(bytes/S) pieces (or fewer if
    the array has fewer rows) so executor chunk indices match the plan's."""
    t = max(1, math.ceil(arr.nbytes / max(chunk_bytes, 1)))
    rows_total = arr.shape[0] if arr.ndim else 1
    rows = max(1, math.ceil(rows_total / t))
    return [arr[i: i + rows] for i in range(0, rows_total, rows)]


def quantize_chunk(arr: np.ndarray):
    """Symmetric per-chunk int8 quantization (beyond-paper: halves/quarters
    streamed bytes vs f32/bf16; dequantized on device at assembly)."""
    absmax = float(np.max(np.abs(arr))) + 1e-12
    scale = absmax / 127.0
    q = np.clip(np.round(arr / scale), -127, 127).astype(np.int8)
    return q, np.float32(scale)


def put_chunk(chunk, device):
    """device_put one host chunk (array or (int8, scale)) onto ``device``
    (None = JAX's default device)."""
    if isinstance(chunk, tuple):
        return jax.device_put(chunk[0], device), float(chunk[1])
    return jax.device_put(chunk, device)


class _Loader(threading.Thread):
    """Dedicated load queue: walks the plan's chunk tasks in op order,
    emulating the storage stage at `disk_bw` (0 = RAM speed), device_puts
    each chunk (JAX async dispatch = the independent DMA queue) and flags
    weights whose chunks have all arrived. With `quantized` host chunks
    ((int8, scale) tuples) the wire/storage bytes are the int8 payload.

    When bound to a WeightCache, every chunk is probed in the pool first —
    prefetched or previously-streamed chunks skip the storage stage and the
    device_put entirely — and freshly-loaded chunks are checked in pinned
    so LRU pressure cannot drop bytes that are about to be consumed.

    An exception on this thread (e.g. HBM exhausted in a device_put) is
    kept in ``error`` and re-raised by ``check`` on the compute thread."""

    def __init__(self, plan: OverlapPlan, host_chunks: Dict[str, list],
                 disk_bw: float, cache: Optional[WeightCache] = None,
                 cache_key: str = "", device=None):
        super().__init__(daemon=True)
        self.plan = plan
        self.host_chunks = host_chunks
        self.disk_bw = disk_bw
        self.cache = cache
        self.cache_key = cache_key
        self.device = device
        self.error: Optional[Exception] = None
        # op at whose load tasks the plan completes each weight; a weight
        # absent here, or due after its consumer, is a plan miss
        self.due: Dict[str, int] = {}
        planned: Dict[str, int] = {}
        for l in sorted(plan.loads):
            for t in plan.loads[l]:
                n = len(host_chunks[t.weight])
                planned[t.weight] = planned.get(t.weight, 0) + \
                    max(0, min(t.chunk_hi, n) - t.chunk_lo)
                if planned[t.weight] >= n:
                    self.due.setdefault(t.weight, l)
        self.arrived: Dict[str, list] = {}
        self.assembled: Dict[str, jax.Array] = {}   # whole-weight pool hits
        self.uncached_bytes: Dict[str, int] = {}    # pool-rejected transients
        self.ready: Dict[str, threading.Event] = {
            w: threading.Event() for w in host_chunks}
        self.gate: Dict[int, threading.Event] = {}
        self.hits = 0                                # loader-thread-local
        self.misses = 0
        self.streamed_chunks = 0
        self.streamed_bytes = 0
        self.lock = threading.Lock()

    def allow_through(self, op_index: int):
        ev = self.gate.get(op_index)
        if ev is not None:
            ev.set()

    def _load_chunk(self, w: str, ci: int, chunk):
        """Pool probe -> storage sleep -> device_put -> pinned check-in."""
        if isinstance(chunk, tuple):                   # (int8, scale) host
            nbytes = chunk[0].nbytes
        else:
            nbytes = chunk.nbytes
        if self.cache is not None:
            cached = self.cache.acquire((self.cache_key, w, ci))
            if cached is not None:
                self.hits += 1
                return cached
            self.misses += 1
        if self.disk_bw > 0:
            time.sleep(nbytes / self.disk_bw)
        arr = put_chunk(chunk, self.device)
        self.streamed_chunks += 1
        self.streamed_bytes += int(nbytes)
        if self.cache is not None:
            if not self.cache.put((self.cache_key, w, ci), arr, nbytes,
                                  pin=True):
                with self.lock:
                    self.uncached_bytes[w] = \
                        self.uncached_bytes.get(w, 0) + int(nbytes)
        return arr

    def run(self):
        try:
            self._load_all()
        except Exception as e:  # noqa: BLE001 — re-raised by check
            self.error = e
        finally:
            for ev in self.ready.values():      # wake every waiter
                ev.set()

    def check(self):
        """Raise on the calling thread if the loader failed."""
        if self.error is not None:
            raise RuntimeError(f"weight loader for {self.cache_key!r} "
                               "failed") from self.error

    def wait_ready(self, w: str, op_index: int):
        """Block until the chunks the plan loads for ``w`` by compute op
        ``op_index`` have arrived. A weight the plan does not complete by
        then is a plan miss: return at once, and the caller completes it
        synchronously. Raises if the loader failed."""
        if self.due.get(w, op_index + 1) <= op_index:
            self.ready[w].wait()
        self.check()

    def _load_all(self):
        for l in sorted(self.plan.loads):
            # the load queue may run at most one op "ahead window" — tasks
            # for op l are issued once compute reaches op l (the plan already
            # encodes lookahead via which op the task is assigned to)
            ev = self.gate.get(l)
            if ev is not None:
                ev.wait()
            for task in self.plan.loads[l]:
                w = task.weight
                if w in self.assembled or self.ready[w].is_set():
                    continue
                if self.cache is not None and w not in self.arrived:
                    full = self.cache.acquire((self.cache_key, w, "w"))
                    if full is not None:               # assembled on device
                        self.hits += 1
                        self.assembled[w] = full
                        self.ready[w].set()
                        continue
                    self.misses += 1
                hcs = self.host_chunks[w]
                for ci in range(task.chunk_lo, min(task.chunk_hi, len(hcs))):
                    arr = self._load_chunk(w, ci, hcs[ci])
                    with self.lock:
                        self.arrived.setdefault(w, []).append(arr)
                if len(self.arrived.get(w, ())) >= len(hcs):
                    self.ready[w].set()


@dataclass
class ExecState:
    """A paused or in-flight streaming run — everything ``advance`` needs
    to pick up where the op loop left off. Holding one of these across a
    preemption keeps the loader thread, its arrived chunks, and the pinned
    cache entries alive, so resuming never re-streams resident bytes."""
    tokens: Any
    stats: RunStats
    host_chunks: Dict[str, list]
    dev: Dict[str, Any]
    transient: Dict[str, int]
    loader: "_Loader"
    regs: Dict[str, Any]
    op_idx: int = 0
    done: bool = False


class StreamingExecutor:
    """Runs a HostModel under an OverlapPlan with a real loader thread."""

    def __init__(self, model: HostModel, plan: OverlapPlan,
                 disk_bw: float = 0.0, gate_loads: bool = True,
                 quantize_stream: bool = False,
                 cache: Optional[WeightCache] = None,
                 cache_key: Optional[str] = None, device=None):
        # gate_loads paces the loader by compute progress: a task assigned
        # to op l is issued when compute reaches op l (the plan's lookahead
        # IS the overlap); ungated, a fast loader front-runs the plan and
        # residency converges to preload-all.
        # quantize_stream ships int8 chunks + per-chunk scale and
        # dequantizes at assembly (beyond-paper: 4x fewer streamed bytes).
        # cache binds the run to a shared budgeted device pool: weights are
        # checked out of / into the pool, survive the run unpinned for
        # future requests, and residency reports the pool's global usage.
        # device is where every weight, chunk and input is placed (None =
        # JAX's default device).
        self.model = model
        self.device = device
        self.plan = plan
        self.disk_bw = disk_bw
        self.gate_loads = gate_loads
        self.quantize_stream = quantize_stream
        self.cache = cache
        self.cache_key = cache_key or model.graph.name
        self.last_use = {w.name: w.consumer
                         for w in model.graph.weights.values()}

    def _residency(self, dev, loader, transient) -> int:
        if self.cache is not None:
            with loader.lock:
                uncached = sum(loader.uncached_bytes.values())
            return self.cache.used_bytes() + sum(transient.values()) + uncached
        with loader.lock:
            inflight = sum(
                int(c[0].nbytes if isinstance(c, tuple) else c.nbytes)
                for lst in loader.arrived.values() for c in lst)
        return sum(int(v.nbytes) for v in dev.values()) + inflight

    def begin(self, tokens: np.ndarray) -> ExecState:
        """Preload phase + loader start: everything up to the op loop.
        Returns the resumable run state ``advance`` consumes."""
        m, plan, cache, key = self.model, self.plan, self.cache, self.cache_key
        stats = RunStats(model=key)
        host_chunks = {w: chunk_rows(m.host_weights[w], plan.chunk_bytes)
                       for w in m.graph.weights}
        if self.quantize_stream:
            host_chunks = {
                w: [quantize_chunk(c) if c.nbytes > 4096 else c for c in lst]
                for w, lst in host_chunks.items()}

        dev: Dict[str, jax.Array] = {}
        transient: Dict[str, int] = {}    # on-device but pool-rejected bytes
        t0 = time.perf_counter()
        for w in plan.preload:
            arr = None
            if cache is not None:
                arr = cache.acquire((key, w, "w"))
                if arr is not None:
                    stats.cache_hits += 1
                else:
                    stats.cache_misses += 1
            if arr is None:
                nbytes = m.host_weights[w].nbytes
                if self.disk_bw > 0:
                    time.sleep(nbytes / self.disk_bw)
                arr = jax.device_put(m.host_weights[w], self.device)
                stats.preloaded_bytes += int(nbytes)
                if cache is not None and not cache.put((key, w, "w"), arr,
                                                       nbytes, pin=True):
                    transient[w] = int(nbytes)
            dev[w] = arr
        for v in dev.values():
            v.block_until_ready()
        stats.init_s = time.perf_counter() - t0

        loader = _Loader(plan, host_chunks, self.disk_bw, cache=cache,
                         cache_key=key, device=self.device)
        if self.gate_loads:
            loader.gate = {l: threading.Event() for l in plan.loads}
        loader.start()

        regs = {"tokens": jax.device_put(tokens, self.device)}
        return ExecState(tokens=tokens, stats=stats, host_chunks=host_chunks,
                         dev=dev, transient=transient, loader=loader,
                         regs=regs)

    def advance(self, st: ExecState,
                should_yield: Optional[Callable[[int], bool]] = None) -> bool:
        """Run ops from ``st.op_idx`` until the program completes (returns
        True, ``st.done`` set, ``st.stats`` finalized) or ``should_yield``
        fires at an op boundary (returns False; the run is PAUSED — the
        loader thread stays parked at its gate, arrived chunks stay on
        device, cache pins stay held, so a later ``advance`` resumes
        without re-streaming anything already resident).

        ``should_yield(op_idx)`` is consulted before each op except the
        first of this call — every ``advance`` makes progress, so a
        persistently-true callback cannot livelock the engine."""
        m, cache, key = self.model, self.cache, self.cache_key
        stats, dev, transient = st.stats, st.dev, st.transient
        loader, host_chunks = st.loader, st.host_chunks
        ops = m.graph.ops
        entry_idx = st.op_idx
        t1 = time.perf_counter()
        try:
            while st.op_idx < len(ops):
                if (should_yield is not None and st.op_idx > entry_idx
                        and should_yield(st.op_idx)):
                    return False
                op = ops[st.op_idx]
                loader.allow_through(op.index)
                warr = None
                if op.weights:
                    wname = op.weights[0]
                    if wname not in dev:
                        full = loader.assembled.get(wname) \
                            if cache is not None else None
                        if full is None:
                            if not loader.ready[wname].is_set():
                                stats.stall_events += 1
                            loader.wait_ready(wname, op.index)
                            full = loader.assembled.get(wname) \
                                if cache is not None else None
                        if full is None:
                            with loader.lock:
                                got = loader.arrived.pop(wname, [])
                            if len(got) < len(host_chunks[wname]):  # plan miss
                                for c in host_chunks[wname][len(got):]:
                                    got.append(put_chunk(c, self.device))
                            got = [g[0].astype(jnp.float32) * g[1]
                                   if isinstance(g, tuple) else g for g in got]
                            full = got[0] if len(got) == 1 else \
                                jnp.concatenate(got, axis=0)
                            if cache is not None:
                                # chunk entries are consumed into the
                                # assembled weight; re-key so future runs
                                # hit it whole
                                for ci in range(len(host_chunks[wname])):
                                    cache.remove((key, wname, ci))
                                with loader.lock:
                                    loader.uncached_bytes.pop(wname, None)
                                if not cache.put((key, wname, "w"), full,
                                                 int(full.nbytes), pin=True):
                                    transient[wname] = int(full.nbytes)
                        dev[wname] = full
                    warr = dev[wname]
                st.regs = m.programs[op_tag(op.name)](st.regs, warr)
                for wname in op.weights:
                    if self.last_use[wname] <= op.index:
                        dev.pop(wname, None)
                        if cache is not None:
                            cache.release((key, wname, "w"))
                            transient.pop(wname, None)
                stats.residency.append(
                    self._residency(dev, loader, transient))
                st.op_idx += 1
            # final segment: the device sync belongs in the timed region —
            # the op loop largely enqueues async work, so exec_s must cover
            # actual execution, not just dispatch (pre-refactor semantics)
            jax.tree.map(lambda x: x.block_until_ready()
                         if hasattr(x, "block_until_ready") else x, st.regs)
        finally:
            stats.exec_s += time.perf_counter() - t1
        loader.join(timeout=10.0)
        loader.check()
        stats.cache_hits += loader.hits
        stats.cache_misses += loader.misses
        stats.streamed_chunks += loader.streamed_chunks
        stats.streamed_bytes += loader.streamed_bytes
        stats.peak_bytes = max(stats.residency, default=0)
        stats.avg_bytes = float(np.mean(stats.residency)) \
            if stats.residency else 0
        stats.result = st.regs.get("h", st.regs.get("x"))
        st.done = True
        return True

    def run(self, tokens: np.ndarray) -> RunStats:
        """One-shot, non-preemptible execution (the pre-PR entry point)."""
        st = self.begin(tokens)
        self.advance(st)
        return st.stats


class PreloadExecutor:
    """Baseline: load + transform everything, then execute (MNN/SmartMem).

    With a shared WeightCache, already-resident weights skip the storage
    stage and device_put; everything it loads is checked into the pool and
    unpinned after the run, so a later streaming run of the same model hits
    device-resident weights."""

    def __init__(self, model: HostModel, disk_bw: float = 0.0,
                 cache: Optional[WeightCache] = None,
                 cache_key: Optional[str] = None, device=None):
        self.model = model
        self.device = device
        self.disk_bw = disk_bw
        self.cache = cache
        self.cache_key = cache_key or model.graph.name

    def run(self, tokens: np.ndarray) -> RunStats:
        m, cache, key = self.model, self.cache, self.cache_key
        stats = RunStats(model=key)
        dev: Dict[str, jax.Array] = {}
        transient = 0                      # on-device but pool-rejected bytes
        t0 = time.perf_counter()
        missing = []
        for w, arr in m.host_weights.items():
            cached = cache.acquire((key, w, "w")) if cache is not None else None
            if cached is not None:
                stats.cache_hits += 1
                dev[w] = cached
            else:
                if cache is not None:
                    stats.cache_misses += 1
                missing.append(w)
        if self.disk_bw > 0 and missing:
            time.sleep(sum(m.host_weights[w].nbytes for w in missing)
                       / self.disk_bw)
        for w in missing:
            dev[w] = jax.device_put(m.host_weights[w], self.device)
            stats.preloaded_bytes += int(m.host_weights[w].nbytes)
            if cache is not None and not cache.put(
                    (key, w, "w"), dev[w], m.host_weights[w].nbytes, pin=True):
                transient += int(m.host_weights[w].nbytes)
        for v in dev.values():
            v.block_until_ready()
        stats.init_s = time.perf_counter() - t0

        regs = {"tokens": jax.device_put(tokens, self.device)}
        t1 = time.perf_counter()
        for op in m.graph.ops:
            warr = dev[op.weights[0]] if op.weights else None
            regs = m.programs[op_tag(op.name)](regs, warr)
        jax.tree.map(lambda x: x.block_until_ready()
                     if hasattr(x, "block_until_ready") else x, regs)
        stats.exec_s = time.perf_counter() - t1
        if cache is not None:
            resident = cache.used_bytes() + transient
            for w in m.host_weights:
                cache.release((key, w, "w"))
        else:
            resident = sum(a.nbytes for a in m.host_weights.values())
        stats.residency = [resident] * len(m.graph.ops)
        stats.peak_bytes = resident
        stats.avg_bytes = float(resident)
        stats.result = regs.get("h", regs.get("x"))
        return stats
