"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time (the union of the intervals in which
an operation ran), idle share, device time per jitted program, the
longest idle gaps with the host span they fell in, and the breakdown.

Device planes are those named ``/device:<kind>:<n>`` other than the CPU.
On each, the ``XLA Ops`` line gives busy time and the ``XLA Modules``
line one event per program execution, named after the jitted function
(``jit_f_matmul(...)``). Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, whose names start ``bench.``.
All times are in seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[float, float, str]            # (start_s, end_s, name)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
OTHER = "host.other"


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)   # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)            # host

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def from_profile(pd) -> Trace:
    """A ``Trace`` from a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                dest = {OPS_LINE: tr.ops, MODULES_LINE: tr.modules}.get(
                    line.name)
                if dest is None:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    for d in (tr.ops, tr.modules):
        for evs in d.values():
            evs.sort()
    tr.spans.sort()
    return tr


def load(log_dir: str) -> Trace:
    """The trace ``jax.profiler.stop_trace`` wrote under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(paths[-1]))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(tr: Trace, device: str) -> List[Interval]:
    """Merged intervals in which an operation ran on ``device``."""
    evs = tr.ops.get(device) or tr.modules.get(device, [])
    return union([(s, e) for s, e, _ in evs])


def window(tr: Trace) -> Interval:
    """From the first to the last benchmark span."""
    if not tr.spans:
        raise ValueError("trace holds no bench.* host spans")
    return tr.spans[0][0], max(e for _, e, _ in tr.spans)


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the devices traced."""
    devs = tr.devices
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in clip(busy(tr, d), lo, hi))
               for d in devs) / len(devs)


def busy_within(tr: Trace, spans: Sequence[Interval]) -> float:
    """Busy seconds inside the union of ``spans``, averaged over devices."""
    devs = tr.devices
    sp = union(spans)
    return sum(overlap(busy(tr, d), sp) for d in devs) / max(len(devs), 1)


def program_time(tr: Trace, prefix: str) -> Tuple[float, int]:
    """(device seconds, executions) of the programs whose module name
    starts with ``prefix``, summed over devices."""
    t, n = 0.0, 0
    for evs in tr.modules.values():
        for s, e, name in evs:
            if name.startswith(prefix):
                t += e - s
                n += 1
    return t, n


def module_name(name: str) -> str:
    """``jit_f_matmul(1234)`` -> ``jit_f_matmul``."""
    return name.split("(", 1)[0]


def gaps(busy_iv: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(busy_iv, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(gap: Interval, spans: Sequence[Event]) -> str:
    """The host span that covers most of ``gap`` (``host.other`` when
    the host was in no benchmark span)."""
    best, name = 0.0, OTHER
    for s, e, n in spans:
        if s >= gap[1]:
            break
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device programs that took most time and the longest idle gaps,
    each gap named by what the host was doing (first device traced)."""
    per: Dict[str, float] = {}
    for evs in tr.modules.values():
        for s, e, name in clip_events(evs, lo, hi):
            per[module_name(name)] = per.get(module_name(name), 0.0) + e - s
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle: List[list] = []
    if tr.devices:
        gs = sorted(gaps(busy(tr, tr.devices[0]), lo, hi),
                    key=lambda g: -(g[1] - g[0]))[:top]
        idle = [[span_at(g, tr.spans), g[1] - g[0]] for g in gs]
    return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": idle}


def clip_events(evs: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in evs
            if e > lo and s < hi]


def rename_spans(tr: Trace, name: str, kinds: Sequence[str]) -> None:
    """Give the i-th host span called ``name`` the i-th of ``kinds`` as a
    suffix (``bench.step`` -> ``bench.step.batch``); the kind of a step is
    known only once it has returned. Left as it is if the counts differ."""
    idx = [i for i, (_, _, n) in enumerate(tr.spans) if n == name]
    if len(idx) != len(kinds):
        return
    for i, k in zip(idx, kinds):
        s, e, n = tr.spans[i]
        tr.spans[i] = (s, e, f"{n}.{k}")


def steps(tr: Trace, prefix: str = "bench.step") -> List[Interval]:
    return [(s, e) for s, e, n in tr.spans if n.startswith(prefix)]
