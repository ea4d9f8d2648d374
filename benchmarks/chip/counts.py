"""Operations and bytes each served program needs, from its shapes alone,
and the chip's peaks they are measured against.

The counts are the work the algorithm needs, whatever implements it:
a matmul reads both operands and writes its result once; attention does
the causal half of the score and value products (query ``i`` meets keys
``0..i``) and moves q, k, v and the output once. A kernel that computes
masked blocks or materialises the scores does more work than is counted
here, so its roofline share shows that waste and can never exceed 100%.
What one model's batch calls is its family's to say
(``families/<family>.py``: ``batch_calls``, ``model_flops``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def matmul(rows: int, k: int, n: int, itemsize: int = F32
           ) -> Tuple[float, float]:
    """(flops, bytes) of a (rows, k) @ (k, n) product."""
    return 2.0 * rows * k * n, float(itemsize) * (rows * k + k * n + rows * n)


def attention(batch: int, seq: int, d: int, itemsize: int = F32
              ) -> Tuple[float, float]:
    """(flops, bytes) of causal multi-head attention over ``batch``
    sequences of ``seq`` positions and model width ``d`` (all heads):
    q k^T and p v each take 2 * head_dim * (i + 1) flops per head at
    query ``i``."""
    flops = 2.0 * batch * d * seq * (seq + 1)
    return flops, float(itemsize) * 4 * batch * seq * d


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    """The roofline's least time: compute or memory, whichever binds."""
    return max(flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"])

