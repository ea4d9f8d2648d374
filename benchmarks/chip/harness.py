"""One run of one cell: build the deployment from its configuration file
and ``--seed``, warm it up, drive its traffic mix through the serving
engine's online loop for the window, drain, check what was served against
the plain reference, and reduce the run to the cell's metrics.

Everything that belongs to one configuration, model family, traffic mix
or per-layer metric is a file found by name (``configs/``, ``families/``,
``traffic/``, ``metrics/``); this module reads them and holds nothing of
its own about any of them.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import arrivals
import counts
import trace_reduce

from repro.core.streaming import op_tag
from repro.serving.batcher import BatcherConfig
from repro.serving.clock import MonotonicClock
from repro.serving.config import ServeConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.stream import RequestStream

ROOT = Path(__file__).resolve().parent
MiB = 1 << 20


class CellError(Exception):
    """The cell cannot be run as its files describe it."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, as BENCHMARK.json and its files describe it
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    family: ModuleType          # families/<config's family>.py


def bench_file(root: Path) -> Path:
    """``BENCHMARK.json`` sits at the root of the checkout, two levels
    above this directory."""
    return root.parents[1] / "BENCHMARK.json"


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads(bench_file(root).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root.parents[1] / configs[w["config"]]["file"]
    config = json.loads(cfg_path.read_text())
    if "family" not in config:
        raise CellError(f"{cfg_path.name} names no model family")
    fam = family(config["family"], root)
    traffic = arrivals.load(root / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                fam)


def _load(path: Path, module: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return _load(root / "metrics" / f"{name}.py", f"metric_{name}").read


def family(name: str, root: Path = ROOT) -> ModuleType:
    """Model family ``name``: the module ``families/<name>.py``, which
    holds everything the harness needs of one architecture (``dims``,
    ``instance_key``, ``host_model``, ``forward``, ``batch_calls``,
    ``model_flops``, ``cpu_arch``, ``PUBLISHED``)."""
    path = root / "families" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no model family {name!r}: {path} is missing")
    return _load(path, f"family_{name}")


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

@dataclass
class Model:
    name: str
    index: int
    dims: dict
    key: object                 # the family's instance key of the seed


@dataclass
class Deployment:
    engine: ServingEngine
    models: List[Model]
    serve: ServeConfig
    max_batch: int
    family: ModuleType


def build(cell: Cell, seed: int) -> Deployment:
    """The cell's models, made from the seed, registered in configuration
    order on one streaming engine with a shared pool."""
    cfg, fam = cell.config, cell.family
    eng = cfg["engine"]
    engine = ServingEngine(
        policy=eng["policy"], chunk_bytes=int(eng["chunk_bytes"]),
        budget_bytes=int(eng["budget_mib"]) * MiB,
        disk_bw=float(eng["disk_bw"]), eviction=eng["eviction"],
        prefetch=bool(eng["prefetch"]))
    programs: Dict[str, object] = {}
    models = []
    for i, m in enumerate(cfg["models"]):
        arch = cfg["archs"][m["arch"]]
        model = Model(m["name"], i, fam.dims(arch), fam.instance_key(seed, i))
        t0 = time.perf_counter()
        hm = fam.host_model(m["arch"], arch, model.dims, model.key,
                            seq=int(cfg["built_seq"]),
                            batch=int(cfg["built_batch"]),
                            programs=programs.get(m["arch"]))
        programs[m["arch"]] = hm.programs
        engine.register(m["name"], hm)
        mib = sum(a.nbytes for a in hm.host_weights.values()) / MiB
        log(f"model {m['name']}: {m['arch']} layers {model.dims['layers']} "
            f"d {model.dims['d']} {mib:.1f} MiB made in "
            f"{time.perf_counter() - t0:.2f}s")
        models.append(model)
    sv = cfg["serve"]
    serve = ServeConfig(scheduler=sv["scheduler"], batcher=BatcherConfig(
        max_batch=int(sv["max_batch"]), max_wait_s=float(sv["max_wait_s"])))
    return Deployment(engine, models, serve, int(sv["max_batch"]), fam)


def shapes(cell: Cell, max_batch: int) -> List[tuple]:
    """Every (rows, padded length) the cell's traffic can form."""
    tr = cell.traffic
    lens = sorted(int(v) for v, p in zip(tr["prompt_len"]["values"],
                                         tr["prompt_len"]["probs"]) if p > 0)
    if tr["loop"] == "closed" and int(tr["clients"]) % max_batch == 0 \
            and len(lens) == 1:
        return [(max_batch, lens[0])]
    return [(b, L) for L in lens for b in range(1, max_batch + 1)]


def warm_programs(dep: Deployment, cell: Cell):
    """Compile every op program at every shape the traffic forms: each
    configuration's op loop (the executors' own) replayed once per shape
    on zero weights of the served shapes, outside the pool."""
    done = set()
    for m in dep.models:
        hm = dep.engine.models[m.name]
        if id(hm.programs) in done:
            continue
        done.add(id(hm.programs))
        zeros: Dict[tuple, jax.Array] = {}
        for a in hm.host_weights.values():
            if a.shape not in zeros:
                zeros[a.shape] = jnp.zeros(a.shape, a.dtype)
        for rows, L in shapes(cell, dep.max_batch):
            t0 = time.perf_counter()
            regs = {"tokens": jnp.zeros((rows, L), jnp.int32)}
            for op in hm.graph.ops:
                w = zeros[hm.host_weights[op.weights[0]].shape] \
                    if op.weights else None
                regs = hm.programs[op_tag(op.name)](regs, w)
            jax.block_until_ready(regs)
            log(f"warm-up programs {m.name} {rows}x{L}: "
                f"{time.perf_counter() - t0:.3f}s")


def warm_up(dep: Deployment, cell: Cell, seed: int):
    """Compile every shape the window uses (``warm_programs``), then run
    one batch of each model through the served path, models in reverse
    configuration (popularity) order, one batch per session so no prefetch
    runs: the executor's own programs (chunk assembly) are compiled and
    the pool holds the same bytes at the start of every run's window."""
    warm_programs(dep, cell)
    eng = dep.engine
    rows, L = max(shapes(cell, dep.max_batch))
    for m in reversed(dep.models):
        clock = MonotonicClock()
        now = clock.now()
        reqs = [Request(model=m.name, arrival_s=now, req_id=-1 - r,
                        tokens=arrivals.prompt(seed, (9, m.index, r),
                                               m.dims["vocab"], L))
                for r in range(rows)]
        t0 = time.perf_counter()
        out = eng.serve_session(RequestStream.from_trace(reqs),
                                config=dep.serve, clock=clock).run()
        if len(out) != rows or any(o.status != "ok" for o in out):
            raise CellError(f"warm-up of {m.name} {rows}x{L} failed")
        log(f"warm-up served {m.name} {rows}x{L}: "
            f"{time.perf_counter() - t0:.3f}s")
    mp = eng.multi_plan
    if mp is not None and not mp.fits_budget():
        raise CellError("the plan does not fit the pool budget: "
                        + ", ".join(f"{n} {p / MiB:.0f} MiB"
                                    for n, p in mp.peaks.items()))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Sent:
    req_id: int
    model: str
    tokens: np.ndarray
    due: float

    @property
    def length(self) -> int:
        return int(self.tokens.shape[1])


@dataclass
class Window:
    t0: float
    t_close: float
    t_end: float = 0.0
    sent: Dict[int, Sent] = field(default_factory=dict)
    responses: list = field(default_factory=list)
    steps: List[tuple] = field(default_factory=list)   # (start, end, kind)
    lateness: List[float] = field(default_factory=list)
    stats: list = field(default_factory=list)
    compiles: int = 0


def _step(ses, win: Window, clock):
    t = clock.now()
    with jax.profiler.TraceAnnotation("bench.step"):
        kind, payload = ses.step()
    win.steps.append((t, clock.now(), kind))
    return kind, payload


def run_open(dep: Deployment, cell: Cell, seed: int, seconds: float,
             clock) -> Window:
    """Replay the cell's schedule on the serving clock. Requests are due
    at their ``arrival_s``; the loop sleeps to the next due time when idle
    and stops at the drain limit past the window's close."""
    tr = cell.traffic
    sched = arrivals.open_schedule(tr, seconds, seed, len(dep.models))
    t0 = clock.now() + 0.05
    win = Window(t0=t0, t_close=t0 + seconds)
    reqs = []
    for i, (t, mi, L) in enumerate(sched):
        m = dep.models[mi]
        s = Sent(i, m.name, arrivals.prompt(seed, (i,), m.dims["vocab"], L),
                 t0 + t)
        win.sent[i] = s
        reqs.append(Request(model=s.model, tokens=s.tokens, arrival_s=s.due,
                            req_id=i))
    ses = dep.engine.serve_session(RequestStream.from_trace(reqs),
                                   config=dep.serve, clock=clock)
    limit = win.t_close + float(tr["drain_s"])
    while clock.now() < limit:
        kind, payload = _step(ses, win, clock)
        if kind == "done":
            break
        if kind == "idle" and payload is not None:
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                clock.sleep(min(payload, limit) - clock.now())
            win.lateness.append(clock.now() - payload)
    win.responses = list(ses.responses)
    return win


def run_closed(dep: Deployment, cell: Cell, seed: int, seconds: float,
               clock) -> Window:
    """``clients`` callers, each sending its next prompt the moment its
    answer comes back, until the window closes; then the stream closes and
    what was sent drains."""
    tr = cell.traffic
    clients = arrivals.ClosedClients(tr, seed, len(dep.models))
    stream = RequestStream()
    t0 = clock.now()
    win = Window(t0=t0, t_close=t0 + seconds)
    owner: Dict[int, int] = {}

    def send(c: int, now: float):
        mi, L, k = clients.next(c)
        m = dep.models[mi]
        rid = len(win.sent)
        s = Sent(rid, m.name, arrivals.prompt(seed, (c, k), m.dims["vocab"],
                                               L), now)
        win.sent[rid], owner[rid] = s, c
        stream.push(Request(model=s.model, tokens=s.tokens, arrival_s=now,
                            req_id=rid))

    for c in range(clients.clients):
        send(c, t0)
    ses = dep.engine.serve_session(stream, config=dep.serve, clock=clock)
    seen = 0
    limit = win.t_close + float(tr["drain_s"])
    while clock.now() < limit:
        kind, _ = _step(ses, win, clock)
        if kind == "done":
            break
        now = clock.now()
        fresh, seen = ses.responses[seen:], len(ses.responses)
        if now < win.t_close:
            for r in fresh:
                send(owner[r.req_id], now)
        elif not stream.closed:
            stream.close()
        if kind == "idle" and not stream.closed:
            raise CellError("closed loop idle with every client waiting")
    win.responses = list(ses.responses)
    return win


# ---------------------------------------------------------------------------
# what the window served: end-to-end metrics
# ---------------------------------------------------------------------------

def nearest_rank(values: List[float], q: float) -> float:
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def answered(win: Window) -> Dict[int, object]:
    return {r.req_id: r for r in win.responses
            if r.req_id in win.sent and r.status == "ok"
            and r.result is not None}


def latencies(win: Window) -> List[float]:
    """Due-to-completion time of every request due in the window; one
    never answered counts as waiting until the run stopped."""
    ok = answered(win)
    return [ok[i].latency_s if i in ok else win.t_end - s.due
            for i, s in win.sent.items()]


@dataclass
class Batch:
    model: str
    size: int
    padded: int                 # the longest member's prompt length


def batches(win: Window) -> List[Batch]:
    """The executed batches of the window, rebuilt from their members'
    responses (members of one batch share model and completion time)."""
    groups: Dict[tuple, list] = {}
    for r in win.responses:
        if r.req_id in win.sent and r.status == "ok":
            groups.setdefault((r.model, round(r.finish_s, 6)), []).append(r)
    out = []
    for (model, _), rs in sorted(groups.items(), key=lambda kv: kv[0][1]):
        lens = [win.sent[r.req_id].length for r in rs]
        out.append(Batch(model, len(rs), max(lens)))
    return out


def prompt_tokens_per_s(win: Window) -> float:
    ok = answered(win)
    toks = sum(s.length for i, s in win.sent.items() if i in ok)
    last = max((ok[i].finish_s for i in ok), default=win.t_end)
    return toks / max(last - win.t0, 1e-9)


# ---------------------------------------------------------------------------
# correctness: served outputs against the plain reference
# ---------------------------------------------------------------------------

def sample(win: Window, dep: Deployment, k: int, seed: int) -> List[int]:
    """``k`` answered requests drawn from the seed, with the longest
    prompt of each model among them."""
    ok = answered(win)
    ids = sorted(ok)
    order = [ids[i] for i in np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])).permutation(len(ids))]
    chosen: List[int] = []
    for m in dep.models:
        mine = [i for i in order if win.sent[i].model == m.name]
        if mine:
            chosen.append(max(mine, key=lambda i: win.sent[i].length))
    for i in order:
        if len(chosen) >= k:
            break
        if i not in chosen:
            chosen.append(i)
    return chosen


def row_errors(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-position relative L2 error of a served (L, d) output."""
    s = np.asarray(served, np.float64).reshape(ref.shape)
    r = np.asarray(ref, np.float64)
    return np.linalg.norm(s - r, axis=-1) / np.linalg.norm(r, axis=-1)


def reference_outputs(dep: Deployment, win: Window, ids: List[int],
                      precision: str, mode: str = "f32"
                      ) -> Dict[int, np.ndarray]:
    out = {}
    for m in dep.models:
        mine = [i for i in ids if win.sent[i].model == m.name]
        if not mine:
            continue
        refs = dep.family.forward(m.key, m.dims,
                                  [win.sent[i].tokens[0] for i in mine],
                                  precision, mode)
        out.update(zip(mine, refs))
    return out


def check(win: Window, ids: List[int], ref: Dict[int, np.ndarray],
          limits: dict, produced: Optional[Dict[int, np.ndarray]] = None
          ) -> dict:
    """The numbers compared, each with its limit. ``produced`` stands in
    for the served outputs (the control). ``row_err_median`` is the median
    over every position of the sample, ``row_err_max`` the widest."""
    ok = answered(win)
    errs = [row_errors(produced[i] if produced is not None
                       else ok[i].result, ref[i]) for i in ids]
    e = np.concatenate(errs) if errs else np.full(1, math.inf)
    unanswered = sum(1 for i in win.sent if i not in ok)
    return {"row_err_median": {"value": float(np.median(e)),
                               "limit": float(limits["row_err_median"])},
            "row_err_max": {"value": float(np.max(e)),
                            "limit": float(limits["row_err_max"])},
            "unanswered": {"value": unanswered, "limit": 0}}


def profile(win: Window, ids: List[int], ref: Dict[int, np.ndarray],
            produced: Optional[Dict[int, np.ndarray]] = None) -> dict:
    """Where the errors lie: quantiles of the per-position errors and the
    position of the worst (for setting limits, not compared)."""
    ok = answered(win)
    errs, worst = [], (0.0, -1, -1)
    for i in ids:
        got = produced[i] if produced is not None else ok[i].result
        e = row_errors(got, ref[i])
        errs.append(e)
        j = int(np.argmax(e))
        if e[j] > worst[0]:
            worst = (float(e[j]), i, j)
    e = np.concatenate(errs) if errs else np.zeros(1)
    return {"median": float(np.median(e)), "p99": float(np.quantile(e, .99)),
            "worst": worst[0], "worst_req": worst[1], "worst_pos": worst[2],
            "rows": int(e.size)}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclass
class RunView:
    """What a per-layer metric reader reads."""
    cell: Cell
    dep: Deployment
    win: Window
    batches: List[Batch]
    peaks: Optional[dict]
    trace: Optional[trace_reduce.Trace] = None


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while on. One
    per process: JAX's listeners cannot be removed."""
    _one: Optional["CompileCounter"] = None

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.on, cls._one.n = False, 0
        return cls._one

    def _dur(self, name, *_a, **_k):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _evt(self, name, *_a, **_k):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.n += 1


def device_info(dev) -> dict:
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_bytes(dev) -> Optional[int]:
    st = dev.memory_stats()
    return None if st is None else int(st.get("peak_bytes_in_use", 0))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: Optional[str] = None,
             also: Sequence[str] = ()) -> dict:
    """One run. Returns the result line's object (``checks`` last).
    ``control`` puts that precision's reference in the program's place in
    ``checks``; ``also`` adds their readings under ``readings``."""
    dev = jax.devices()[0]
    counter = CompileCounter.get()
    dep = build(cell, seed)
    warm_up(dep, cell, seed)
    clock = MonotonicClock()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    stats_log = dep.engine.stats_log
    n0 = stats_log.total
    counter.on = True
    drive = run_open if cell.traffic["loop"] == "open" else run_closed
    win = drive(dep, cell, seed, seconds, clock)
    win.t_end = clock.now()
    counter.on = False
    k = stats_log.total - n0
    win.stats = list(stats_log)[-k:] if k else []
    win.compiles = counter.n
    if trace:
        jax.profiler.stop_trace()
    setup_s = win.t0 - t_start
    peak = peak_bytes(dev)
    log(f"window: {len(win.sent)} sent, {len(answered(win))} answered, "
        f"{len(win.steps)} steps, closed {win.t_close - win.t0:.3f}s, "
        f"ended {win.t_end - win.t0:.3f}s after start")
    log(f"compiles inside the window: {win.compiles}")
    batch_s = sorted(b - a for a, b, k in win.steps if k == "batch")
    if batch_s:
        log(f"batch steps: {len(batch_s)}, min {batch_s[0]:.3f}s median "
            f"{batch_s[len(batch_s) // 2]:.3f}s max {batch_s[-1]:.3f}s")
    if win.lateness:
        log(f"generator lateness: max {max(win.lateness) * 1e3:.3f} ms "
            f"mean {np.mean(win.lateness) * 1e3:.3f} ms over "
            f"{len(win.lateness)} waits")
    bl = batches(win)
    view = RunView(cell, dep, win, bl,
                   counts.peaks(dev.device_kind) if dev.platform != "cpu"
                   else None)
    # free the pool before the reference runs
    dep.engine.cache.clear()
    t_ref = time.perf_counter()
    ids = sample(win, dep, int(cell.config["check"]["sample"]), seed)
    precision = cell.config["matmul_precision"]
    ref = reference_outputs(dep, win, ids, precision)
    limits = cell.config["check"]
    readings = {"program": check(win, ids, ref, limits)}
    profiles = {"program": profile(win, ids, ref)} if also else {}
    for mode in sorted({control, *also} - {None}):
        out = reference_outputs(dep, win, ids, precision, mode)
        readings[mode] = check(win, ids, ref, limits, out)
        if also:
            profiles[mode] = profile(win, ids, ref, out)
    checks = readings[control or "program"]
    log(f"reference: {len(ids)} requests in "
        f"{time.perf_counter() - t_ref:.2f}s")
    metrics: Dict[str, dict] = {}
    result = {"correct": passed(checks), "attempted": len(win.sent),
              "failed": checks["unanswered"]["value"], "metrics": metrics,
              "device": dict(device_info(dev), memory_peak_bytes=peak)}
    if trace:
        try:
            view.trace = trace_reduce.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        kinds = [k for _, _, k in win.steps]
        trace_reduce.rename_spans(view.trace, "bench.step", kinds)
        lo, hi = trace_reduce.window(view.trace)
        result["device"]["busy_s"] = trace_reduce.busy_s(view.trace, lo, hi)
        result["device"]["window_s"] = hi - lo
        for m in cell.per_layer:
            v = reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = trace_reduce.breakdown(view.trace, lo, hi)
    else:
        e2e = end_to_end(win, setup_s, peak)
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if also:
        result["readings"] = {m: dict(readings[m], profile=profiles[m])
                              for m in readings}
    result["checks"] = checks
    return result


def end_to_end(win: Window, setup_s: float, peak: Optional[int]) -> dict:
    lat = latencies(win)
    return {"setup_s": setup_s,
            "latency_p50_s": nearest_rank(lat, 0.50) if lat else None,
            "prompt_tokens_per_s": prompt_tokens_per_s(win),
            "peak_hbm_mib": peak / MiB if peak is not None else None}
