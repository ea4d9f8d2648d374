"""The trace reduction on a small synthetic profiler trace (XSpace text
proto): busy union, idle share, device time per program, idle gaps named
by the host span they fell in, and the breakdown."""
import jax
import pytest

import trace_reduce as tr

MS = 1_000_000_000  # picoseconds per millisecond


def _events(meta, evs):
    return "".join(
        f"events {{ metadata_id: {meta[n]} offset_ps: {int(s * MS)} "
        f"duration_ps: {int((e - s) * MS)} }}\n" for s, e, n in evs)


def _plane(pid, name, lines):
    names = sorted({n for _, evs in lines for _, _, n in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = "".join(
        f"lines {{ id: {j + 1} name: \"{ln}\" timestamp_ns: 0\n"
        f"{_events(meta, evs)}}}\n" for j, (ln, evs) in enumerate(lines))
    md = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                 f"\"{n}\" }} }}\n" for n, i in meta.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}{md}}}\n"


@pytest.fixture(scope="module")
def trace():
    modules = [(1, 3, "jit_f_matmul(11)"), (4, 5, "jit_f_attn(12)"),
               (8.5, 9, "jit_f_add(13)")]
    ops = [(1, 2, "fusion.1"), (1.5, 3, "fusion.2"), (4, 5, "fusion.3"),
           (8.5, 9, "add.1")]
    host = [(0, 6, "bench.step"), (6, 8, "bench.wait_arrival"),
            (8, 10, "bench.step"), (2, 2.5, "PjitFunction(f)")]
    txt = (_plane(1, "/device:TPU:0", [("XLA Modules", modules),
                                      ("XLA Ops", ops)])
           + _plane(2, "/host:CPU", [("python", host)]))
    t = tr.from_profile(jax.profiler.ProfileData.from_text_proto(txt))
    tr.rename_spans(t, "bench.step", ["batch", "idle"])
    return t


def test_spans_and_window(trace):
    assert [n for _, _, n in trace.spans] == [
        "bench.step.batch", "bench.wait_arrival", "bench.step.idle"]
    lo, hi = tr.window(trace)
    assert (lo, hi) == pytest.approx((0.0, 10e-3))


def test_busy_is_the_union_of_ops(trace):
    assert tr.busy_s(trace, 0.0, 10e-3) == pytest.approx(3.5e-3)
    assert tr.busy_s(trace, 1.5e-3, 4.5e-3) == pytest.approx(2.0e-3)


def test_busy_within_steps(trace):
    steps = tr.steps(trace)
    assert tr.busy_within(trace, steps) == pytest.approx(3.5e-3)
    assert sum(e - s for s, e in tr.union(steps)) == pytest.approx(8e-3)


def test_program_time_sums_module_events(trace):
    assert tr.program_time(trace, "jit_f_matmul") == (pytest.approx(2e-3), 1)
    assert tr.program_time(trace, "jit_f_nothing") == (0.0, 0)


def test_breakdown_names_gaps_by_host_span(trace):
    b = tr.breakdown(trace, 0.0, 10e-3)
    assert b["device_ops"][0] == ["jit_f_matmul", pytest.approx(2e-3)]
    assert [n for n, _ in b["device_ops"]] == [
        "jit_f_matmul", "jit_f_attn", "jit_f_add"]
    # gaps: [5, 8.5] (mostly waiting for an arrival), then [0, 1],
    # [3, 4] and [9, 10] inside steps
    assert b["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(3.5e-3)]
    assert sorted(n for n, _ in b["idle_gaps"][1:]) == [
        "bench.step.batch", "bench.step.batch", "bench.step.idle"]


def test_union_and_overlap():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.overlap([(0, 2), (3, 4)], [(1, 3.5)]) == pytest.approx(1.5)
    assert tr.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_rename_leaves_spans_when_counts_differ(trace):
    t = tr.Trace(spans=[(0, 1, "bench.step")])
    tr.rename_spans(t, "bench.step", ["batch", "idle"])
    assert t.spans[0][2] == "bench.step"
