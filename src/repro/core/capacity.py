"""Per-op load capacity C_l (paper §4.2, Table 5).

Class thresholds (max tolerated latency inflation from concurrent loading):
  hierarchical 0%   — never overlap (softmax/layernorm/attention/router)
  reusable     20%  — matmul/conv: high tolerance, slow relative growth
  elemental    300% — elementwise: tiny baseline latency, large tolerance

Two modes:
  * analytic — C_bytes = threshold x t_op x stream_bw, with t_op the
    max(compute, memory) roofline time of the op on the target chip. Used
    for planning at dry-run scale.
  * model-calibrated — invert the GBT latency model by binary search
    (profile-driven; used in the benchmarks, mirrors the paper's XGBoost).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from repro.core.graph import ELEMENTAL, HIERARCHICAL, REUSABLE, ModelGraph, Op
from repro.core.latency_model import GBTRegressor, features

THRESHOLDS = {HIERARCHICAL: 0.0, REUSABLE: 0.20, ELEMENTAL: 3.00}


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float        # dense bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # chip-to-chip bytes/s per link
    host_bw: float      # host->HBM bytes/s
    source: str


TPU_V5E = "TPU v5 lite"
# One table of device peaks, keyed by ``jax.Device.device_kind``. A kind
# that is not here is an error, never a default (``HWSpec.for_device``).
PEAKS = {
    TPU_V5E: ChipPeaks(
        flops=197e12, hbm_bw=819e9,
        ici_bw=1600e9 / 8 / 4,       # 1,600 Gbit/s over the chip's 4 links
        host_bw=25e9,                # ASSUMED (PCIe-class), not published
        source="Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI; host->HBM is assumed"),
}


@dataclass(frozen=True)
class HWSpec:
    peak_flops: float = PEAKS[TPU_V5E].flops
    hbm_bw: float = PEAKS[TPU_V5E].hbm_bw
    stream_bw: float = PEAKS[TPU_V5E].host_bw   # host->HBM streaming path
    disk_bw: float = 0.0             # storage->host stage (0 = not modeled)

    def op_time(self, op: Op) -> float:
        return max(op.flops / self.peak_flops, op.act_bytes / self.hbm_bw,
                   1e-9)

    @staticmethod
    def cpu_calibrated() -> "HWSpec":
        """Measure this machine (benchmark executors run on CPU)."""
        import time

        import numpy as np
        a = np.random.rand(768, 768).astype(np.float32)
        t0 = time.perf_counter()
        for _ in range(8):
            a = a @ a * 1e-3
        tf = (time.perf_counter() - t0) / 8
        flops = 2 * 768 ** 3 / max(tf, 1e-9)
        src = np.ones(32 << 20, np.uint8)
        dst = np.empty_like(src)
        np.copyto(dst, src)  # warm pages
        t0 = time.perf_counter()
        for _ in range(4):
            np.copyto(dst, src)
        bw = 4 * src.nbytes / max(time.perf_counter() - t0, 1e-9)
        return HWSpec(peak_flops=flops, hbm_bw=bw, stream_bw=bw / 2,
                      disk_bw=0.5e9)

    @staticmethod
    def for_device(dev) -> "HWSpec":
        """Planner constants for the device that will execute: the peaks
        table row for an accelerator, a measurement of this host for the
        CPU backend. An accelerator kind missing from ``PEAKS`` raises."""
        if dev.platform == "cpu":
            return HWSpec.cpu_calibrated()
        row = PEAKS.get(dev.device_kind)
        if row is None:
            raise ValueError(f"no peaks for device kind {dev.device_kind!r} "
                             f"(platform {dev.platform}); add a row to "
                             "repro.core.capacity.PEAKS")
        return HWSpec(peak_flops=row.flops, hbm_bw=row.hbm_bw,
                      stream_bw=row.host_bw)


def analytic_capacity_bytes(op: Op, hw: HWSpec,
                            thresholds=None) -> int:
    """TPU-adapted C_l (DESIGN.md §2): on a chip with an independent DMA
    engine, interference is HBM-bandwidth contention, not a shared texture
    bus. A compute-bound op leaves (t_c - t_m) x hbm_bw of free HBM slack;
    the class threshold tolerates th x t_op of extra memory time on top.
    The link itself bounds what can physically move during the op."""
    th = (thresholds or THRESHOLDS)[op.op_class]
    if th <= 0.0:
        return 0
    t_c = op.flops / hw.peak_flops
    t_m = op.act_bytes / hw.hbm_bw
    t_op = max(t_c, t_m, 1e-9)
    slack = max(0.0, t_c - t_m) * hw.hbm_bw
    tolerated = th * t_op * hw.hbm_bw
    link_cap = (1.0 + th) * t_op * hw.stream_bw
    return int(min(slack + tolerated, link_cap))


def capacities(graph: ModelGraph, chunk_bytes: int, hw: Optional[HWSpec] = None,
               model: Optional[GBTRegressor] = None,
               thresholds=None) -> List[int]:
    """C_l per op, in chunks."""
    hw = hw or HWSpec()
    out = []
    for op in graph.ops:
        if model is not None:
            b = model_capacity_bytes(op, model, hw, thresholds)
        else:
            b = analytic_capacity_bytes(op, hw, thresholds)
        out.append(b // max(chunk_bytes, 1))
    return out


def model_capacity_bytes(op: Op, model: GBTRegressor, hw: HWSpec,
                         thresholds=None) -> int:
    """Largest extra bytes with predicted slowdown <= class threshold."""
    th = (thresholds or THRESHOLDS)[op.op_class]
    if th <= 0.0:
        return 0
    base = float(model.predict(features(op.op_class, op.flops,
                                        op.act_bytes, 0.0))[0])
    limit = base * (1.0 + th)
    lo, hi = 0.0, max(op.act_bytes * 64.0, 1 << 24)
    for _ in range(40):
        mid = (lo + hi) / 2
        t = float(model.predict(features(op.op_class, op.flops,
                                         op.act_bytes, mid))[0])
        if t <= limit:
            lo = mid
        else:
            hi = mid
    return int(lo)


def classify_report(graph: ModelGraph) -> dict:
    counts = {}
    for op in graph.ops:
        counts[op.op_class] = counts.get(op.op_class, 0) + 1
    return counts
