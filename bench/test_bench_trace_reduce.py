"""The reduction from a profiler trace to busy time, program times and
labelled idle gaps: on synthetic spans, whose answers are known, and on
a trace recorded on a TPU v5e (``testdata/decode-b1.xplane.pb``: a short
traced window of the ``qwen05b-decode-b1`` cell around one decode step,
the device drained before the profiler started and stopped; recorded
from a traced run of the harness with ``trace_seconds`` 0.012 of a 1 s
window, seed 5, the profiler's output directory kept); and the
harness's traced window, which lasts its length from its start."""
import shutil
import time
from pathlib import Path

import pytest

from bench import harness, trace_reduce as tr
from bench.trace_reduce import Span

TRACE = Path(__file__).resolve().parent / "testdata" / "decode-b1.xplane.pb"


def test_union_counts_overlap_once():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (8, 9)]) == \
        [(0, 3), (5, 7), (8, 9)]
    assert tr.length(tr.union([(0, 2), (1, 3), (0.5, 1)])) == 3


def test_complement_and_clip():
    busy = tr.union(tr.clip([(-1, 1), (2, 3), (9, 12)], 0, 10))
    assert busy == [(0, 1), (2, 3), (9, 10)]
    assert tr.complement(busy, 0, 10) == [(1, 2), (3, 9)]


def _device(ops, programs=()):
    return {"ops": [Span(f"%op{i} = f32[] add(%a, %b)", a, b)
                    for i, (a, b) in enumerate(ops)],
            "programs": [Span(n, a, b) for n, a, b in programs]}


def test_busy_and_idle_share_from_union():
    dev = _device([(0.0, 0.4), (0.2, 0.5), (0.7, 0.8)])
    out = tr.reduce([dev], [], (0.0, 1.0))
    assert out["window_s"] == 1.0
    assert out["busy_s"] == pytest.approx(0.6)


def test_busy_is_averaged_over_chips():
    out = tr.reduce([_device([(0, 0.5)]), _device([(0, 0.1)])], [], (0, 1))
    assert out["busy_s"] == pytest.approx(0.3)


def test_program_times_count_whole_runs_inside_window():
    dev = _device([], [("jit__decode_paged(7)", 0.1, 0.3),
                       ("jit__decode_paged(7)", 0.5, 0.6),
                       ("jit__decode_paged(7)", 0.9, 1.2),   # cut by window
                       ("jit__extend_paged(3)", 0.3, 0.45)])
    out = tr.reduce([dev], [], (0.0, 1.0))
    dec = out["programs"]["jit__decode_paged"]
    assert dec["count"] == 2
    assert dec["mean_s"] == pytest.approx(0.15)
    assert out["programs"]["jit__extend_paged"]["total_s"] == \
        pytest.approx(0.15)


def test_gaps_go_to_the_inner_annotation_covering_most():
    dev = _device([(0.0, 0.2), (0.5, 0.6), (0.9, 1.0)])
    ann = [Span("bench.scheduler_run", 0.0, 0.95),
           Span("bench.decode_batch", 0.2, 0.25),
           Span("bench.token_emit", 0.25, 0.5),      # covers most of gap 1
           Span("bench.window", 0.0, 1.0)]
    out = tr.reduce([dev], ann, (0.0, 1.0))
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.token_emit"] == pytest.approx(0.3)
    assert gaps["bench.scheduler_run"] == pytest.approx(0.3)   # 0.6-0.9
    assert "bench.window" not in gaps
    assert sum(gaps.values()) == pytest.approx(1.0 - out["busy_s"])


def test_gap_outside_every_annotation():
    out = tr.reduce([_device([(0.0, 0.5)])], [], (0.0, 1.0))
    assert dict(out["idle_gaps"]) == {tr.NONE: pytest.approx(0.5)}


def test_op_labels_drop_layouts_and_containers():
    label, opcode = tr.op_label(
        "%fusion.88 = (f32[4864]{0:T(1024)S(1)}, f32[4864]{0:T(1024)S(1)}) "
        "fusion(f32[896]{0:T(1024)S(1)} %fusion.87), kind=kLoop")
    assert (label, opcode) == ("fusion.88 (f32[4864], f32[4864]) fusion",
                               "fusion")
    dev = {"ops": [Span("%while.1 = (s32[]) while(%t), body=%b", 0, 1),
                   Span("%dot.2 = f32[8] dot(%x, %y)", 0.1, 0.4)],
           "programs": [Span("jit__decode_paged(1)", 0, 1)]}
    ops = dict(tr.reduce([dev], [], (0, 1))["device_ops"])
    assert ops == {"jit__decode_paged/dot.2 f32[8] dot": pytest.approx(0.3)}


@pytest.fixture(scope="module")
def recorded():
    return tr.read_xplane(str(TRACE))


def test_recorded_trace_has_the_window_and_programs(recorded):
    devices, ann, window = recorded
    assert len(devices) == 1 and window is not None
    assert {s.name for s in ann} >= {"bench.window", "bench.decode_batch",
                                     "bench.token_emit"}
    out = tr.reduce(devices, ann, window)
    assert 0 < out["busy_s"] <= out["window_s"]
    step = out["programs"]["jit__decode_paged"]
    assert step["count"] == 1
    # a whole Qwen2.5-0.5B decode step reads 0.99 GB: over a millisecond
    assert 1e-3 < step["mean_s"] < 10e-3
    assert out["device_ops"][0][0].startswith("jit__decode_paged/")


def test_recorded_busy_time_is_the_union_of_ops(recorded):
    devices, ann, (lo, hi) = recorded
    ops = sorted((max(s.start, lo), min(s.end, hi))
                 for s in devices[0]["ops"] if s.end > lo and s.start < hi)
    busy, end = 0.0, lo
    for a, b in ops:                   # a plain sweep, independent of union
        if b > end:
            busy += b - max(a, end)
            end = b
    out = tr.reduce(devices, ann, (lo, hi))
    assert out["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert sum(v for _, v in out["idle_gaps"]) == \
        pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)


def test_recorded_gaps_are_put_down_to_bench_annotations(recorded):
    out = tr.reduce(*recorded)
    labels = {k for k, _ in out["idle_gaps"]}
    assert labels and all(k.startswith("bench.") or k == tr.NONE
                          for k in labels)
    assert "bench.decode_batch" in labels


def test_a_late_start_still_traces_its_full_length():
    """A host stall past ``start_at`` delays the profiler's start: the
    trace then lasts its length from there, and does not stop at the
    next poll with a step or two in it."""
    t = harness.Tracing(0.5, sync=lambda: None)
    t.arm(time.perf_counter() - 10.0, 1.0)     # start_at and stop_at passed
    try:
        t.poll()
        assert t.t_start is not None
        t.poll()
        assert t.t_stop is None and t.stop_at == t.t_start + 0.5
    finally:
        t.stop()
        shutil.rmtree(t.dir, ignore_errors=True)
