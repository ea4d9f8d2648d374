"""The operation and byte counts against hand counts at one shape of each
served program, and the peaks table."""
import pytest

import counts
import harness

gptneo = harness.family("gptneo")


def test_matmul_hand_count():
    # (2, 3) @ (3, 4): 2*3*4 multiply-adds; 6 + 12 + 8 float32 values
    assert counts.matmul(2, 3, 4) == (48.0, 104.0)


def test_attention_hand_count():
    # one sequence of 2 positions, width 4 (all heads): query 0 meets key
    # 0, query 1 keys 0 and 1 -> 3 pairs; q.k and p.v each 2 flops per
    # pair per width element: 2 * 2 * 3 * 4 = 48. Bytes: q, k, v, out.
    assert counts.attention(1, 2, 4) == (48.0, 4 * 4 * 2 * 4)


def test_batch_calls_cover_every_served_matmul_and_attention():
    dm = {"d": 8, "dff": 32, "layers": 3}
    calls = gptneo.batch_calls(dm, 2, 5)
    assert len(calls["f_matmul"]) == 6 * 3
    assert len(calls["f_attn"]) == 3
    rows = 2 * 5
    assert calls["f_matmul"][0] == counts.matmul(rows, 8, 8)
    assert calls["f_matmul"][4] == counts.matmul(rows, 8, 32)
    assert calls["f_matmul"][5] == counts.matmul(rows, 32, 8)


def test_model_flops_is_matmuls_plus_causal_attention():
    dm = {"d": 8, "dff": 32, "layers": 2}
    tokens = 5
    mm = 2 * tokens * (4 * 64 + 2 * 8 * 32)
    att = counts.attention(1, tokens, 8)[0]
    assert gptneo.model_flops(dm, tokens) == 2 * (mm + att)


def test_least_time_takes_the_binding_roof():
    pk = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000.0, 50.0, pk) == 10.0
    assert counts.least_time(100.0, 50.0, pk) == 5.0


def test_peaks_table_knows_v5e_and_refuses_unknown_kinds():
    pk = counts.peaks("TPU v5 lite")
    assert pk["flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")
