"""The one traffic generator. A traffic mix is a JSON file of parameters
(``traffic/<name>.json``); this module turns it and ``--seed`` into the
requests a cell sends. Nothing here depends on which cell reads it.

Open loop (``"loop": "open"``, ``"arrivals": "poisson"``): a schedule of
``(t, model, prompt_len)`` fixed before the window starts. Arrivals are a
Poisson process of ``rate_per_s`` held to its expected count:
``round(rate * seconds)`` arrival times drawn independently and uniformly
over the window. Given its count, a Poisson process's arrival times are
exactly that (``serving/stream.poisson_trace`` draws exponential gaps
until the window ends, which leaves the count free as well), so bursts
and lulls are Poisson's: a stretch expected to hold 20 arrivals holds
20 +- 4.4. Holding the count keeps the offered load, the one thing the
seed would otherwise change about the work, the same in every run.
Models (``popularity``) and prompt lengths are exact shares of the count,
shuffled by the seed: every seed sends the same set of sizes in another
order.

Closed loop (``"loop": "closed"``): ``clients`` callers, each sending its
next prompt when the previous answer comes back; each caller's models
and lengths are independent draws from its own seeded stream.

Prompts are uniform token ids from their own seeded stream, so contents
change with the seed and sizes do not.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Sequence, Tuple

import numpy as np

Arrival = Tuple[float, int, int]        # (offset_s, model index, prompt_len)


def load(path: Path) -> dict:
    tr = json.loads(Path(path).read_text())
    if tr.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    if tr["loop"] == "open" and tr.get("arrivals") != "poisson":
        raise ValueError(f"{path}: open-loop arrivals must be poisson")
    pl = tr["prompt_len"]
    if len(pl["values"]) != len(pl["probs"]):
        raise ValueError(f"{path}: prompt_len values and probs differ")
    return tr


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         *stream]))


def _probs(p: Sequence[float]) -> np.ndarray:
    p = np.asarray(p, float)
    return p / p.sum()


def shares(values: Sequence, probs: Sequence[float], n: int,
           rng: np.random.Generator) -> list:
    """``n`` draws of ``values`` in exact (largest-remainder) shares,
    shuffled."""
    raw = _probs(probs) * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    out = [v for v, c in zip(values, counts) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def _popularity(tr: dict, n_models: int) -> list:
    pop = tr.get("popularity", [1.0])
    if len(pop) != n_models:
        raise ValueError(f"traffic popularity has {len(pop)} entries, the "
                         f"configuration {n_models} models")
    return pop


def open_schedule(tr: dict, seconds: float, seed: int,
                  n_models: int) -> List[Arrival]:
    pop = _popularity(tr, n_models)
    pl = tr["prompt_len"]
    n = max(1, int(round(float(tr["rate_per_s"]) * seconds)))
    times = np.sort(_rng(seed, 0).uniform(0.0, seconds, n))
    models = shares(list(range(n_models)), pop, n, _rng(seed, 1))
    lens = shares(pl["values"], pl["probs"], n, _rng(seed, 2))
    return [(float(t), int(m), int(L))
            for t, m, L in zip(times, models, lens)]


class ClosedClients:
    """Per-client prompt sequences of a closed loop: client ``c``'s
    ``k``-th request is the same for a given seed whatever the timing."""

    def __init__(self, tr: dict, seed: int, n_models: int):
        self.clients = int(tr["clients"])
        self._pop = _probs(_popularity(tr, n_models))
        pl = tr["prompt_len"]
        self._lens, self._p_len = list(pl["values"]), _probs(pl["probs"])
        self._rng = {c: _rng(seed, 3, c) for c in range(self.clients)}
        self._n = {c: 0 for c in range(self.clients)}

    def next(self, c: int) -> Tuple[int, int, int]:
        """(model index, prompt_len, k) of client ``c``'s next request."""
        k = self._n[c]
        self._n[c] += 1
        rng = self._rng[c]
        m = int(rng.choice(len(self._pop), p=self._pop))
        L = int(self._lens[int(rng.choice(len(self._lens),
                                          p=self._p_len))])
        return m, L, k


def prompt(seed: int, key: Sequence[int], vocab: int,
           length: int) -> np.ndarray:
    """A (1, length) int32 prompt of uniform token ids."""
    return _rng(seed, 5, *key).integers(0, vocab, (1, length),
                                        dtype=np.int32)
