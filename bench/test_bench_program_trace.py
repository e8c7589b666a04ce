"""The program's spans and scopes over a profiler trace
(``bench.program_trace``): on synthetic spans, whose answers are known;
on a trace recorded on a TPU v5e with the program's own spans
(``testdata/decode-b1-spans.*``); and the reduction ``trace_reduce``
already had, pinned on the trace recorded for it
(``testdata/decode-b1.xplane.pb``), so that reading the program's spans
beside it changes none of its numbers.

``decode-b1-spans`` is one traced run of the ``qwen05b-decode-b1`` cell
(seed 2147484321, a 2 s window, ``trace_seconds`` 0.012) through the
harness with a live ``repro.obs.Tracer`` on the scheduler: the profiler's
``.xplane.pb``, the scope map of the programs it ran
(``op_scopes`` over ``ModelBackend.compiled_programs()``) as
``.scopes.json``, and as ``.host.json`` the tracer's ring-buffer spans
of the last 0.2 s before the traced window closed, with the window's end
and the decode rows' context lengths."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import costs
from bench import program_trace as pt
from bench import trace_reduce as tr
from bench.families import family
from bench.trace_reduce import Span

from repro.obs import SpanEvent

DATA = Path(__file__).resolve().parent / "testdata"
DECODE = "jit__decode_paged"


def _op(inst, a, b, opcode="fusion"):
    return Span(f"%{inst} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", a, b)


def _device(ops, programs):
    return {"ops": ops, "programs": [Span(n, a, b) for n, a, b in programs]}


# -- synthetic -----------------------------------------------------------------

def test_idle_inside_program_spans():
    dev = _device([_op("a", 0.0, 0.2), _op("b", 0.5, 0.6),
                   _op("c", 0.9, 1.0)], [])
    spans = [Span("repro.dispatch:decode_batch", 0.15, 0.55),
             Span("repro.dispatch:decode_batch", 0.3, 0.4),   # nested
             Span("repro.readback", 0.6, 0.7)]
    idle = pt.idle_within([dev], spans, (0.0, 1.0))
    assert idle["repro.dispatch:decode_batch"] == pytest.approx(0.3)
    assert idle["repro.readback"] == pytest.approx(0.1)
    two = pt.idle_within([dev, _device([_op("a", 0.0, 1.0)], [])], spans,
                         (0.0, 1.0))
    assert two["repro.dispatch:decode_batch"] == pytest.approx(0.15)


def test_device_seconds_by_scope():
    dev = _device([_op("fusion.1", 0.1, 0.3), _op("copy.2", 0.3, 0.35,
                                                  "copy"),
                   _op("while.3", 0.1, 0.4, "while"),      # a container
                   _op("add.4", 0.35, 0.4, "add"),         # no scope
                   _op("fusion.1", 0.9, 1.1)],             # run cut off
                  [(f"{DECODE}(7)", 0.1, 0.4), (f"{DECODE}(7)", 0.9, 1.1)])
    scopes = {DECODE: {"fusion.1": "mlp", "copy.2": "kv_gather"}}
    out = pt.scope_seconds([dev], scopes, (0.0, 1.0))
    assert out == {DECODE: {"mlp": pytest.approx(0.2),
                            "kv_gather": pytest.approx(0.05),
                            pt.UNSCOPED: pytest.approx(0.05)}}


def test_gaps_go_to_the_innermost_program_span_covering_most():
    dev = _device([_op("a", 0.0, 0.2), _op("b", 0.5, 0.6),
                   _op("c", 0.9, 0.95)], [])
    spans = [Span("repro.decode_cycle", 0.1, 0.92),
             Span("repro.dispatch:decode_batch", 0.15, 0.55),
             Span("repro.enqueue", 0.2, 0.5),           # gap 1 exactly
             Span("repro.readback", 0.6, 0.7)]          # a third of gap 2
    ann = [Span("bench.scheduler_run", 0.0, 1.0),
           Span("bench.decode_batch", 0.15, 0.55),
           Span("bench.window", 0.0, 1.0)]
    out = pt.reduce([dev], ann, (0.0, 1.0), spans, {})
    gaps = dict(out["idle_gaps"])
    assert gaps["repro.enqueue"] == pytest.approx(0.3)
    assert gaps["repro.decode_cycle"] == pytest.approx(0.3)
    # 0.95-1.0 touches no program span: the benchmark's label stays
    assert gaps["bench.scheduler_run"] == pytest.approx(0.05)
    assert sum(gaps.values()) == pytest.approx(1.0 - 0.2 - 0.1 - 0.05)


def _ev(name, ts, dur):
    return SpanEvent(name, "phase", "scheduler", ts, dur, "X", 0, None)


def test_cycle_host_time_leaves_out_dispatch_and_readback():
    events = [_ev("decode_cycle", 1.0, 0.010),
              _ev("dispatch:decode_batch", 1.001, 0.004),
              _ev("enqueue", 1.002, 0.003),            # inside the dispatch
              _ev("readback", 1.006, 0.002),
              _ev("decode_cycle", 1.020, 0.006),
              _ev("dispatch:decode_batch", 1.021, 0.001),
              _ev("decode_cycle", 0.5, 0.004),         # before the window
              _ev("sample_emit", 1.021, 0.002)]
    rec = SimpleNamespace(t_start=0.9, t_stop=1.1, decode=[[10]])
    got = pt.cycle_host_us({"spans": events, "tracing": rec})
    assert got == pytest.approx(1e6 * (0.004 + 0.005) / 2)
    assert pt.cycle_host_us({"spans": [], "tracing": rec}) is None


CFG = {"hidden_size": 256, "intermediate_size": 512,
       "num_attention_heads": 4, "num_hidden_layers": 4,
       "num_key_value_heads": 2, "vocab_size": 2048,
       "model_type": "qwen2"}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_paging_time_and_mlp_roofline_per_decode_run():
    ctx = {"trace": {"programs": {DECODE: {"count": 4}}},
           "program_trace": {"scopes": {DECODE: {
               "kv_gather": 0.004, "kv_write": 0.002, "mlp": 0.01,
               "attn": 0.02}}, "idle_in": {
               "repro.dispatch:decode_batch": 0.0008}},
           "tracing": SimpleNamespace(decode=[[100], [101]]),
           "cfg": CFG, "peak": PEAK}
    assert pt.kv_paging_ms(ctx) == pytest.approx(1.5)
    assert pt.dispatch_idle_us(ctx) == pytest.approx(200.0)
    weights = 3 * 256 * 512 * 4
    assert family(CFG).mlp_step(CFG, 1) == (2 * weights, 2 * weights)
    # bytes bound: 2 * weights at 1e9 B/s over 2.5 ms of MLP per run
    assert pt.mlp_roofline(ctx) == pytest.approx(
        100 * (2 * weights / 1e9) / 0.0025)
    assert pt.mlp_roofline(dict(ctx, peak=None)) is None
    assert pt.kv_paging_ms(dict(ctx, program_trace=None)) is None


# -- the recorded trace: trace_reduce's numbers stay as they were ------------

def test_trace_reduce_of_the_recorded_trace_is_unchanged():
    out = tr.reduce(*tr.read_xplane(str(DATA / "decode-b1.xplane.pb")))
    assert set(out) == {"window_s", "busy_s", "programs", "device_ops",
                        "idle_gaps"}
    assert out["window_s"] == pytest.approx(0.004493199999999996, rel=1e-12)
    assert out["busy_s"] == pytest.approx(0.0023719200000000135, rel=1e-12)
    assert out["programs"][DECODE] == {
        "count": 1, "total_s": pytest.approx(0.0023714400000000024,
                                             rel=1e-12),
        "mean_s": pytest.approx(0.0023714400000000024, rel=1e-12)}
    assert set(out["programs"]) == {DECODE, "jit__threefry_split",
                                    "jit__unstack"}
    ops = [
        ("jit__decode_paged/fusion.88 (f32[4864], f32[4864]) fusion",
         0.0005938900000000205),
        ("jit__decode_paged/multiply_reduce_fusion.2 f32[151936] fusion",
         0.0003608380000000022),
        ("jit__decode_paged/fusion.90 bf16[896] fusion",
         0.0003034350000000047),
        ("jit__decode_paged/copy.42 bf16[481,24,16,2,64] copy",
         0.00022562899999999803),
        ("jit__decode_paged/copy.41 bf16[481,24,16,2,64] copy",
         0.00013195500000000304),
        ("jit__decode_paged/fusion.86 bf16[896] fusion",
         7.55029999999976e-05),
        ("jit__decode_paged/fusion.81 f32[896] fusion", 7.5458999999993e-05),
        ("jit__decode_paged/copy.44 bf16[24,1,2560,2,64] copy",
         7.17859999999973e-05),
        ("jit__decode_paged/fusion.1 bf16[160,24,16,2,64] fusion",
         7.066299999999859e-05),
        ("jit__decode_paged/fusion bf16[160,24,16,2,64] fusion",
         4.921900000000312e-05)]
    assert [k for k, _ in out["device_ops"]] == [k for k, _ in ops]
    for (_, got), (_, want) in zip(out["device_ops"], ops):
        assert got == pytest.approx(want, rel=1e-12)
    gaps = dict(out["idle_gaps"])
    assert set(gaps) == {"bench.token_emit", "bench.decode_batch", tr.NONE}
    assert gaps["bench.token_emit"] == pytest.approx(0.0018044179999999951,
                                                     rel=1e-12)
    assert gaps["bench.decode_batch"] == pytest.approx(
        0.00031103600000000065, rel=1e-12)


def test_a_trace_without_program_spans_keeps_its_bench_labels():
    devices, ann, window = tr.read_xplane(str(DATA / "decode-b1.xplane.pb"))
    spans = pt.read_spans(str(DATA / "decode-b1.xplane.pb"))
    assert spans == []
    out = pt.reduce(devices, ann, window, spans, {})
    assert dict(out["idle_gaps"]) == pytest.approx(
        dict(tr.reduce(devices, ann, window)["idle_gaps"]), rel=1e-12)
    assert set(out["scopes"][DECODE]) == {pt.UNSCOPED}


# -- recorded on the chip with the program's spans ---------------------------

@pytest.fixture(scope="module")
def spans_ctx():
    xplane = str(DATA / "decode-b1-spans.xplane.pb")
    devices, ann, window = tr.read_xplane(xplane)
    spans = pt.read_spans(xplane)
    scopes = json.loads((DATA / "decode-b1-spans.scopes.json").read_text())
    host = json.loads((DATA / "decode-b1-spans.host.json").read_text())
    cfg = json.loads((DATA.parent / "configs" / "qwen2.5-0.5b.json")
                     .read_text())["config"]
    events = [SpanEvent(n, "phase", "scheduler", ts, dur, "X", 0, None)
              for n, ts, dur in host["spans"]]
    tracing = SimpleNamespace(t_start=host["t_stop"] - 0.2,
                              t_stop=host["t_stop"], decode=host["decode"])
    return {"trace": tr.reduce(devices, ann, window),
            "program_trace": pt.reduce(devices, ann, window, spans, scopes),
            "spans": events, "tracing": tracing, "cfg": cfg,
            "peak": costs.peaks("TPU v5 lite"), "xplane_spans": spans}


def test_recorded_program_spans_and_scopes(spans_ctx):
    names = {s.name for s in spans_ctx["xplane_spans"]}
    assert {"repro.dispatch:decode_batch", "repro.prepare", "repro.upload",
            "repro.enqueue", "repro.adopt", "repro.readback",
            "repro.sample_emit"} <= names
    scopes = spans_ctx["program_trace"]["scopes"][DECODE]
    # every op of the decode step falls in a named scope
    assert set(scopes) == {"kv_gather", "attn", "mlp", "unembed",
                           "kv_write"}
    step = spans_ctx["trace"]["programs"][DECODE]
    assert step["count"] == 1
    assert sum(scopes.values()) <= step["total_s"]
    assert sum(scopes.values()) > 0.98 * step["total_s"]


def test_recorded_idle_gaps_name_program_spans(spans_ctx):
    gaps = dict(spans_ctx["program_trace"]["idle_gaps"])
    assert gaps["repro.dispatch:decode_batch"] == pytest.approx(
        0.0012851320000000013, rel=1e-9)
    assert "bench.decode_batch" not in gaps
    assert "bench.decode_batch" in dict(spans_ctx["trace"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        spans_ctx["trace"]["window_s"] - spans_ctx["trace"]["busy_s"],
        rel=1e-6)


def test_the_four_readers_on_the_recorded_trace(spans_ctx):
    assert pt.dispatch_idle_us(spans_ctx) == pytest.approx(
        1264.603000000003, rel=1e-9)
    assert pt.cycle_host_us(spans_ctx) == pytest.approx(
        1627.1181818245334, rel=1e-9)
    assert pt.kv_paging_ms(spans_ctx) == pytest.approx(
        0.6111379999999902, rel=1e-9)
    assert pt.mlp_roofline(spans_ctx) == pytest.approx(
        83.6419230634865, rel=1e-9)
