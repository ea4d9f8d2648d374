"""Make the benchmark's modules importable as the CLI sees them (its own
directory and the repository's ``src`` first on the path), and shrink a
cell to a size the CPU runs in seconds."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
for p in (HERE.parents[1] / "src", HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def shrink(cell, *, d=128, layers=2, heads=4, vocab=1024):
    """Cut a cell's models to a CPU size by their family's ``cpu_arch``:
    width, depth, heads and vocabulary; prompts of 16/32/64 tokens, a 1
    MiB pool that holds about half of each model, and small chunks, so
    the streaming path runs."""
    archs = cell.config["archs"]
    per_model = 0
    for name, a in archs.items():
        archs[name], nbytes = cell.family.cpu_arch(
            a, d=d, layers=layers, heads=heads, vocab=vocab)
        per_model = max(per_model, nbytes)
    cell.config["engine"]["budget_mib"] = 1
    cell.config["engine"]["chunk_bytes"] = max(4096, per_model // 64)
    pl = cell.traffic["prompt_len"]
    pl["values"] = [16, 32, 64][-len(pl["values"]):]
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 20.0
    cell.traffic["drain_s"] = 20.0
    cell.config["check"]["sample"] = 6
    return cell


def trio_cell():
    """The three-model configuration the benchmark keeps for a later cell
    (``configs/neo-trio.json``) in a closed loop of 8 callers, each
    request drawing its model by the Zipf popularity, with the
    closed-loop offload cell's metrics."""
    import json

    import harness
    cell = harness.load_cell("neo27-offload-batch")
    cell.name = "neo-trio"
    cell.config = json.loads((HERE / "configs" / "neo-trio.json").read_text())
    cell.family = harness.family(cell.config["family"])
    cell.traffic = {"loop": "closed", "clients": 8,
                    "popularity": [6, 3, 2],
                    "prompt_len": {"values": [256, 512, 1024],
                                   "probs": [0.3, 0.4, 0.3]},
                    "drain_s": 60}
    return cell


@pytest.fixture
def tiny():
    import harness

    def make(workload, root=harness.ROOT, **kw):
        cell = trio_cell() if workload == "neo-trio" \
            else harness.load_cell(workload, root)
        return shrink(cell, **kw)
    return make
