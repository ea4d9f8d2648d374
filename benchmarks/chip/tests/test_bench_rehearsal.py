"""CPU rehearsal: every cell's traffic and configuration path driven
through the harness at a reduced size, and the result line's schema. The
CLI itself refuses to run without a TPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import arrivals
import harness

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**33 + 17           # seeds may be wider than 32 bits


def _schema(result, cell, trace):
    keys = list(result)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    assert set(result["metrics"]) <= want
    for v in result["metrics"].values():
        assert set(v) == {"value", "unit"} and np.isfinite(v["value"])
    json.dumps(result)


@pytest.mark.parametrize("workload", CELLS + ["neo-trio"])
def test_cell_runs_end_to_end_at_cpu_size(workload, tiny):
    cell = tiny(workload)
    r = harness.run_cell(cell, SEED, 1.5, False, time.perf_counter())
    _schema(r, cell, False)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # every end-to-end metric but the device's memory (CPU reports none)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end} - {
        "peak_hbm_mib"}


def test_traced_run_reports_layers_and_breakdown(tiny):
    for workload, want in (
            ("neo27-offload-batch", {"h2d_mib_per_batch",
                                     "stall_events_per_batch"}),
            ("neo13-resident", {"queue_ms_mean", "batch_size_mean"})):
        cell = tiny(workload)
        r = harness.run_cell(cell, SEED, 1.5, True, time.perf_counter())
        _schema(r, cell, True)
        assert r["correct"] is True
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        # counters and spans read on the CPU; device shares need the chip
        assert want <= set(r["metrics"])


def test_cli_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_poisson_schedule_holds_its_count_and_poisson_bursts():
    tr = dict(arrivals.load(HERE / "traffic" / "mixlen-poisson.json"),
              popularity=[6, 3, 2], rate_per_s=24.0)
    a = arrivals.open_schedule(tr, 51.0, 1, 3)
    b = arrivals.open_schedule(tr, 51.0, SEED, 3)
    assert a != b and len(a) == len(b) == 1224
    assert arrivals.open_schedule(tr, 51.0, SEED, 3) == b
    # the same sizes and models in another order
    for k in (1, 2):
        assert sorted(x[k] for x in a) == sorted(x[k] for x in b)
    assert [x[2] for x in a].count(512) == round(0.4 * 1224)
    # counts per stretch expected to hold 20 arrivals spread as
    # Poisson's do (sd 4.4), not as a smoothed schedule's
    sds = []
    for seed in range(20):
        t = [x[0] for x in arrivals.open_schedule(tr, 51.0, seed, 3)]
        sds.append(np.std(np.histogram(t, bins=61, range=(0, 51))[0]))
    assert np.mean(sds) == pytest.approx(20 ** 0.5, rel=0.1)


def test_open_loop_needs_poisson_arrivals(tmp_path):
    tr = json.loads((HERE / "traffic" / "mixlen-poisson.json").read_text())
    p = tmp_path / "t.json"
    p.write_text(json.dumps(dict(tr, arrivals="stratified")))
    with pytest.raises(ValueError):
        arrivals.load(p)


def test_closed_clients_repeat_per_seed():
    tr = dict(arrivals.load(HERE / "traffic" / "closed16-1024.json"),
              popularity=[6, 3, 2])
    a = arrivals.ClosedClients(tr, SEED, 3)
    b = arrivals.ClosedClients(tr, SEED, 3)
    assert [a.next(3) for _ in range(50)] == [b.next(3) for _ in range(50)]
    models = [a.next(5)[0] for _ in range(600)]
    assert np.bincount(models) / 600 == pytest.approx([6 / 11, 3 / 11,
                                                       2 / 11], abs=0.06)
    assert arrivals.prompt(SEED, (3, 0), 50257, 8).shape == (1, 8)
