"""Readings of the executor's phase counters, the ``RunStats`` fields that
time where a batch's op loop and its loader spent their time (``stall_s``,
``assemble_s``, ``dispatch_s`` over ``ops_run``, ``put_s``), summed over
the window's batches. A program whose ``RunStats`` lacks a counter reads
None, and the harness leaves the metric out of the result line.
"""
from __future__ import annotations

from typing import Optional


def total(run, field: str) -> Optional[float]:
    """``field`` summed over the window's batches."""
    st = run.win.stats
    if not st or not all(hasattr(s, field) for s in st):
        return None
    return float(sum(getattr(s, field) for s in st))


def per_batch(run, field: str) -> Optional[float]:
    t = total(run, field)
    return None if t is None else t / len(run.win.stats)


def ratio(run, num: str, den: str) -> Optional[float]:
    """Σ``num`` over Σ``den``; None where either is missing or Σ``den``
    is 0."""
    a, b = total(run, num), total(run, den)
    return a / b if a is not None and b else None
