"""The comparison that decides ``correct`` fails when the timed path is
broken underneath it: a run driven through the harness (its look for a
chip skipped) with one fault planted in the serving system, and with the
control, the reference in bfloat16 in the program's place."""
import time

import numpy as np
import pytest

import harness
import repro.core.streaming as streaming
import repro.serving.engine as engine

SEED = 2**31 + 3


def _run(cell, **kw):
    cell.config["check"]["sample"] = 10**6      # compare every answer
    return harness.run_cell(cell, SEED, 1.5, False, time.perf_counter(),
                            **kw)


@pytest.fixture
def busy_trio(tiny):
    """Three models, two of one size, in a closed loop: batches hold
    several requests and a model can be served another's weights."""
    return tiny("neo-trio")


def test_sound_run_is_correct(busy_trio):
    r = _run(busy_trio)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("workload", ["neo13-resident",
                                      "neo27-offload-batch"])
def test_control_bf16_reference_fails(tiny, workload):
    r = _run(tiny(workload), control="bf16")
    assert r["correct"] is False
    # the control fails the number set to separate it, not only the widest
    assert r["checks"]["row_err_median"]["value"] > \
        r["checks"]["row_err_median"]["limit"]


def test_an_answer_altered_where_it_is_produced(busy_trio, monkeypatch):
    real = engine.split_batch_result

    def altered(batch, result):
        out = real(batch, result)
        out[0] = out[0].copy()
        out[0][0, -1] += 1.0                  # one position of one answer
        return out
    monkeypatch.setattr(engine, "split_batch_result", altered)
    r = _run(busy_trio)
    assert r["correct"] is False


def test_batch_members_given_another_members_rows(busy_trio, monkeypatch):
    real = engine.split_batch_result

    def first_only(batch, result):
        out = real(batch, result)
        arr = np.asarray(result)
        return [arr[:1, :sl] for sl in batch.seq_lens] if len(out) > 1 \
            else out
    monkeypatch.setattr(engine, "split_batch_result", first_only)
    r = _run(busy_trio)
    assert r["correct"] is False


def test_a_prompt_token_altered(busy_trio, monkeypatch):
    real = engine.make_batch

    def altered(group, cfg, **kw):
        b = real(group, cfg, **kw)
        b.tokens[0, 0] = (b.tokens[0, 0] + 1) % 7
        return b
    monkeypatch.setattr(engine, "make_batch", altered)
    r = _run(busy_trio)
    assert r["correct"] is False


def test_streamed_chunks_assembled_out_of_order(busy_trio, monkeypatch):
    real = streaming.chunk_rows
    monkeypatch.setattr(streaming, "chunk_rows",
                        lambda arr, n: real(arr, n)[::-1])
    r = _run(busy_trio)
    assert r["correct"] is False


def test_a_request_served_by_another_models_weights(busy_trio, monkeypatch):
    real = harness.build

    def swapped(cell, seed):
        dep = real(cell, seed)
        eng = dep.engine
        a, b = dep.models[0].name, dep.models[2].name  # the two 1.3b
        ma, mb = eng.models[a], eng.models[b]
        eng.register(a, mb)
        eng.register(b, ma)
        return dep
    monkeypatch.setattr(harness, "build", swapped)
    r = _run(busy_trio)
    assert r["correct"] is False


def test_a_request_never_answered(busy_trio, monkeypatch):
    real = engine.split_batch_result
    dropped = []

    def drop_one(batch, result):
        out = real(batch, result)
        if not dropped and batch.requests[0].req_id >= 0:
            dropped.append(batch.requests[0].req_id)
            out[0] = None
        return out
    monkeypatch.setattr(engine, "split_batch_result", drop_one)
    r = _run(busy_trio)
    assert r["correct"] is False
    assert r["checks"]["unanswered"]["value"] >= 1
