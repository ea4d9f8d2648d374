"""CPU checks of the chip bring-up: ``HWSpec.for_device``, loader and
prefetch failures surfacing on the serving thread, and ``chip_smoke.py``'s
phase functions at a cut depth (its platform check stays in ``main()``)."""
import os
import shutil
import subprocess
import sys
import textwrap
import threading
import time
from dataclasses import replace
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.gptneo import GPTNEO_S
from repro.core import streaming
from repro.core.capacity import PEAKS, TPU_V5E, HWSpec, capacities
from repro.core.opg import OPGProblem
from repro.core.plan import OverlapPlan
from repro.core.solver import solve
from repro.serving.engine import ServingEngine, _Prefetcher

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

FIXED_HW = HWSpec(peak_flops=5e10, hbm_bw=2e10, stream_bw=1e10)


def _tiny_model(seq=64):
    cfg = replace(GPTNEO_S, name="tiny", num_layers=2, d_model=256,
                  n_heads=4, n_kv_heads=4, d_ff=1024, vocab=1024)
    return streaming.HostModel.build(cfg, seq=seq, seed=0)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), **extra)
    return env


# -- HWSpec.for_device -------------------------------------------------------

def test_for_device_cpu_is_calibrated_measurement():
    hw = HWSpec.for_device(jax.devices("cpu")[0])
    assert hw.peak_flops > 0 and hw.hbm_bw > 0
    assert hw.disk_bw == HWSpec.cpu_calibrated().disk_bw


def test_for_device_known_tpu_kind_is_table_row():
    hw = HWSpec.for_device(SimpleNamespace(platform="tpu",
                                           device_kind=TPU_V5E))
    row = PEAKS[TPU_V5E]
    assert (hw.peak_flops, hw.hbm_bw, hw.stream_bw) == \
        (row.flops, row.hbm_bw, row.host_bw)
    assert hw == HWSpec()


def test_for_device_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="TPU v99"):
        HWSpec.for_device(SimpleNamespace(platform="tpu",
                                          device_kind="TPU v99"))


# -- thread failures reach the serving thread --------------------------------

def test_loader_device_put_failure_raises_promptly(monkeypatch):
    m = _tiny_model()
    prob = OPGProblem(m.graph, 64 << 10, m_peak=1 << 20,
                      capacity=capacities(m.graph, 64 << 10, FIXED_HW))
    plan = OverlapPlan.from_solution(prob, solve(prob))
    assert plan.loads, "plan must stream for the loader to run"

    def boom(chunk, device):
        raise MemoryError("RESOURCE_EXHAUSTED: HBM")

    monkeypatch.setattr(streaming, "put_chunk", boom)
    ex = streaming.StreamingExecutor(m, plan)
    toks = np.zeros((1, 64), np.int32)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="weight loader") as ei:
        ex.run(toks)
    assert isinstance(ei.value.__cause__, MemoryError)
    assert time.perf_counter() - t0 < 10.0


@pytest.mark.parametrize("miss", ["late", "absent"])
def test_plan_miss_completes_synchronously(miss):
    """A weight the plan loads after its consumer (or never) is completed
    on the compute thread without waiting on the gated loader."""
    m = _tiny_model()
    prob = OPGProblem(m.graph, 64 << 10, m_peak=1 << 20,
                      capacity=capacities(m.graph, 64 << 10, FIXED_HW))
    plan = OverlapPlan.from_solution(prob, solve(prob))
    w = plan.loads[min(plan.loads)][0].weight
    assert m.graph.weights[w].consumer < len(m.graph.ops) - 1
    tasks = [t for ts in plan.loads.values() for t in ts if t.weight == w]
    plan.loads = {l: [t for t in ts if t.weight != w]
                  for l, ts in plan.loads.items()}
    if miss == "late":
        plan.loads.setdefault(len(m.graph.ops) - 1, []).extend(tasks)
    toks = np.arange(64, dtype=np.int32)[None] % m.cfg.vocab
    t0 = time.perf_counter()
    got = streaming.StreamingExecutor(m, plan).run(toks).result
    assert time.perf_counter() - t0 < 10.0
    ref = streaming.PreloadExecutor(m).run(toks).result
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_prefetch_failure_reraised_on_stop():
    eng = ServingEngine(policy="stream", hw=FIXED_HW)
    th = _Prefetcher(lambda: 1 / 0, ())
    th.start()
    with pytest.raises(RuntimeError, match="prefetch") as ei:
        eng._stop_prefetch(th, threading.Event())
    assert isinstance(ei.value.__cause__, ZeroDivisionError)


# -- chip_smoke.py phases at 2 layers, seq 64 --------------------------------

def test_smoke_one_chip_phases_on_cpu():
    model = chip_smoke.build_model(layers=2, seq=64)
    assert (model.cfg.d_model, model.cfg.d_ff, model.cfg.vocab) == \
        (2048, 8192, 50257)
    tokens = chip_smoke.request_tokens(model, chip_smoke.N_REQUESTS)
    total = sum(a.nbytes for a in model.host_weights.values())
    budget = int(0.8 * total)              # two models, room for one
    res = chip_smoke.serve_phase(model, tokens, budget, hw=FIXED_HW)
    refs = chip_smoke.preload_reference(model, tokens)
    chip_smoke.check_serving(res, refs, budget)   # raises on failure
    assert res["pool"]["evictions"] > 0
    err = chip_smoke.cpu_rel_error(model, tokens[0],
                                   res["responses"][0].result)
    assert err == 0.0                      # same backend, same programs


def test_smoke_check_rejects_plan_over_budget():
    res = {"plan": SimpleNamespace(fits_budget=lambda: False),
           "stats": [], "responses": [], "ledger_peak": 0, "device_peak": None,
           "pool": {"evictions": 0, "evicted_bytes": 0, "hits": 0,
                    "misses": 0}}
    with pytest.raises(chip_smoke.SmokeFailure, match="fits"):
        chip_smoke.check_serving(res, [], 1 << 30)


def test_smoke_main_refuses_cpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "platform cpu" in out.stdout


def test_smoke_alone_without_repo_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


REPLICA_SCRIPT = textwrap.dedent("""
    import sys
    from dataclasses import replace
    sys.path.insert(0, sys.argv[1])
    import jax, numpy as np
    import chip_smoke
    from repro.configs.gptneo import GPTNEO_S
    from repro.core.capacity import HWSpec
    from repro.core.streaming import HostModel
    FIXED_HW = HWSpec(peak_flops=5e10, hbm_bw=2e10, stream_bw=1e10)
    assert len(jax.devices()) == 4, jax.devices()
    model = HostModel.build(replace(
        GPTNEO_S, name="tiny", num_layers=2, d_model=256, n_heads=4,
        n_kv_heads=4, d_ff=1024, vocab=1024), seq=64, seed=0)
    tokens = chip_smoke.request_tokens(model, 8)
    total = sum(a.nbytes for a in model.host_weights.values())
    res = chip_smoke.replica_phase(model, tokens, int(1.5 * total),
                                   hw=FIXED_HW)
    devs = res["devices"]
    assert devs == [0, 1, 2, 3], devs
    for r in range(4):
        assert res["placement"][r] == {devs[r]}, res["placement"]
        assert res["evictions"][r] > 0, res["evictions"]
    refs = chip_smoke.preload_reference(model, tokens,
                                        device=jax.devices()[0])
    for resp, ref in zip(res["responses"], refs):
        assert resp.status == "ok", resp
        assert np.array_equal(np.asarray(resp.result), ref)
    print("REPLICAS_OK")
""")


def test_four_replicas_place_pools_on_own_devices():
    out = subprocess.run(
        [sys.executable, "-c", REPLICA_SCRIPT, str(ROOT)],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "REPLICAS_OK" in out.stdout
