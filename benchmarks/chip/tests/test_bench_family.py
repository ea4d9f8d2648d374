"""A cell's model family is a file found by name: a new family needs only
new files, an unknown one fails when the cell is loaded, and the GPT-Neo
family reads what the harness read before families were files
(``family_parity.json``, recorded from that harness at the same sizes)."""
import hashlib
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import arrivals
import harness
import readings
import trace_reduce
from repro.serving.clock import MonotonicClock
from repro.serving.engine import Request
from repro.serving.stream import RequestStream

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
PARITY = Path(__file__).with_name("family_parity.json")
SEED = 2**33 + 29


def _copy(tmp_path: Path) -> Path:
    """The benchmark as a checkout holds it, under ``tmp_path``; returns
    the copy's harness root."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return root


def _set_family(root: Path, config: str, fam):
    path = root / "configs" / f"{config}.json"
    cfg = json.loads(path.read_text())
    if fam is None:
        del cfg["family"]
    else:
        cfg["family"] = fam
    path.write_text(json.dumps(cfg))


@pytest.mark.parametrize("fam", ["nosuch", None])
def test_a_config_naming_no_known_family_fails_at_load_cell(tmp_path, fam):
    root = _copy(tmp_path)
    _set_family(root, "neo13", fam)
    with pytest.raises(harness.CellError,
                       match="nosuch.py" if fam else "names no model"):
        harness.load_cell("neo13-resident", root)


def test_a_new_family_needs_only_new_files(tmp_path, tiny):
    root = _copy(tmp_path)
    (root / "families" / "toy.py").write_text(
        '"""GPT-Neo under another name."""\n'
        "from families.gptneo import *  # noqa: F401,F403\n")
    _set_family(root, "neo13", "toy")
    cell = tiny("neo13-resident", root=root)
    assert Path(cell.family.__file__) == root / "families" / "toy.py"
    r = harness.run_cell(cell, SEED, 1.5, False, time.perf_counter())
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def _sha(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else repr(p).encode())
    return h.hexdigest()


def served_readings(cell, seed: int) -> dict:
    """What one cell's timed path and readers give at the cell's size on
    a fixed set of requests: the served models (config, graph, weights),
    every served output, the checks against the reference and the
    control, the batches, and ``step_mfu`` and both rooflines at fixed
    peaks and times, which read nothing but the family's counts."""
    dep = harness.build(cell, seed)
    harness.warm_up(dep, cell, seed)
    models = {}
    for m in dep.models:
        hm = dep.engine.models[m.name]
        w = hm.host_weights
        models[m.name] = _sha(hm.cfg, hm.graph.ops,
                              sorted(hm.graph.weights.items()),
                              *[(n, w[n].dtype.str, w[n].shape,
                                 w[n].tobytes()) for n in sorted(w)])
    clock = MonotonicClock()
    now = clock.now()
    win = harness.Window(t0=now, t_close=now + 1.0)
    reqs = []
    lens = sorted(set(cell.traffic["prompt_len"]["values"]))
    for m in dep.models:
        for r in range(dep.max_batch + 1):
            rid = len(win.sent)
            tokens = arrivals.prompt(seed, (5, m.index, r), m.dims["vocab"],
                                    lens[r % len(lens)])
            win.sent[rid] = harness.Sent(rid, m.name, tokens, now)
            reqs.append(Request(model=m.name, tokens=tokens, arrival_s=now,
                                req_id=rid))
    win.responses = dep.engine.serve_session(
        RequestStream.from_trace(reqs), config=dep.serve, clock=clock).run()
    ok = harness.answered(win)
    ids = harness.sample(win, dep, int(cell.config["check"]["sample"]), seed)
    prec = cell.config["matmul_precision"]
    ref = harness.reference_outputs(dep, win, ids, prec)
    ctl = harness.reference_outputs(dep, win, ids, prec, "bf16")
    limits = cell.config["check"]
    bl = harness.batches(win)
    win.stats = [SimpleNamespace(init_s=0.25, exec_s=0.75)]
    trace = trace_reduce.Trace(modules={"/device:TPU:0": [
        (0.0, 1.0, "jit_f_matmul"), (1.0, 1.5, "jit_f_attn")]})
    view = harness.RunView(cell, dep, win, bl,
                           {"flops": 1e12, "hbm_bytes_per_s": 1e11}, trace)
    return {"models": models,
            "outputs": {str(i): _sha(np.asarray(ok[i].result).tobytes())
                        for i in sorted(ok)},
            "checks": harness.check(win, ids, ref, limits),
            "control": harness.check(win, ids, ref, limits, ctl),
            "batches": [[b.model, b.size, b.padded] for b in bl],
            "step_mfu": readings.step_mfu(view),
            "matmul_roofline": readings.roofline(view, "f_matmul"),
            "attention_roofline": readings.roofline(view, "f_attn")}


@pytest.mark.parametrize("workload", ["neo13-resident",
                                      "neo27-offload-batch", "neo-trio"])
def test_gptneo_family_reads_as_the_harness_did_before(tiny, workload):
    want = json.loads(PARITY.read_text())[workload]
    got = json.loads(json.dumps(served_readings(tiny(workload), SEED)))
    assert got == want
