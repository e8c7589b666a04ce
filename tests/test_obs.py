"""Observability subsystem: tracer ring buffer + span nesting, Perfetto
export schema, metrics quantiles, scheduler phase spans for the paged /
speculative / graph-backend paths, the trace↔dispatch_stats consistency
invariant, overhead attribution, and the disabled-tracer cost bound."""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.obs import (MetricsRegistry, Tracer, measure_overhead, percentile,
                       to_trace_events, validate_trace)
from repro.obs.tracer import NULL_SPAN, NULL_TRACER
from repro.serving import (InferenceSession, Scheduler, ServeRequest,
                           create_backend)
from repro.serving.engine import GenerationEngine
from repro.serving.session import SchedulerStats


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n, plen=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(1, plen)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_depth():
    tr = Tracer()
    with tr.span("outer", track="t"):
        with tr.span("inner", track="t"):
            pass
        with tr.span("inner2", track="t"):
            pass
    ev = {e.name: e for e in tr.events()}
    assert ev["outer"].depth == 0
    assert ev["inner"].depth == 1 and ev["inner2"].depth == 1
    # children close before the parent, so they are recorded first
    names = [e.name for e in tr.events()]
    assert names == ["inner", "inner2", "outer"]
    # nested spans sit inside the parent's interval
    assert ev["inner"].ts >= ev["outer"].ts
    assert ev["inner"].ts + ev["inner"].dur <= ev["outer"].ts + \
        ev["outer"].dur + 1e-9


def test_ring_buffer_wraparound():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    # oldest-first order with the oldest 6 overwritten
    assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_disabled_tracer_is_noop_singleton():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", track="x", foo=1)
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1:
        pass
    tr.instant("i")
    tr.counter("c", 1.0)
    assert tr.open_span("main") is None
    assert len(tr) == 0
    assert len(NULL_TRACER) == 0 and not NULL_TRACER.enabled


def test_dispatch_total_sums_dispatch_lane_args():
    tr = Tracer()
    with tr.span("dispatch:decode", cat="dispatch", op="decode") as sp:
        with tr.span("enqueue"):
            # counts land on the innermost open span of the asked category
            assert tr.open_span("main", cat="dispatch") is sp
            assert tr.open_span("main").name == "enqueue"
        sp.tally(dispatches=1)
        sp.tally(dispatches=2)
    with tr.span("dispatch:prefill", cat="dispatch"):
        tr.open_span("main", cat="dispatch").tally(dispatches=2)
    with tr.span("phase", dispatches=99):
        pass
    assert tr.open_span("main") is None
    assert tr.dispatch_total() == 5
    ev = {e.name: e for e in tr.events()}
    assert ev["dispatch:decode"].args == {"op": "decode", "dispatches": 3}


# ---------------------------------------------------------------------------
# perfetto export
# ---------------------------------------------------------------------------

def test_perfetto_export_schema():
    tr = Tracer()
    with tr.span("cycle", track="scheduler", n=1):
        pass
    tr.instant("hit", track="paging")
    tr.counter("occupancy", 2.0, track="scheduler")
    with tr.span("dispatch:decode", cat="dispatch", track="backend:model",
                 dispatches=1):
        pass
    doc = to_trace_events(tr)
    validate_trace(doc)                    # raises on violation
    evs = doc["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"scheduler", "paging", "backend:model"} <= names
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    assert json.dumps(doc)                 # serializable end to end
    # track ordering: scheduler thread sorts before the dispatch lane
    tids = {e["args"]["name"]: e["tid"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids["scheduler"] < tids["backend:model"]


def test_validate_trace_rejects_bad_docs():
    with pytest.raises(ValueError):
        validate_trace({"nope": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"name": "x", "ph": "X",
                                         "pid": 1, "tid": 1, "ts": -5}]})
    with pytest.raises(ValueError):        # X without dur
        validate_trace({"traceEvents": [{"name": "x", "ph": "X",
                                         "pid": 1, "tid": 1, "ts": 0}]})


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    rng = np.random.default_rng(42)
    xs = rng.exponential(10.0, size=500)
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in xs:
        h.observe(v)
    for q in (0, 25, 50, 90, 99, 100):
        assert h.quantile(q) == pytest.approx(np.percentile(xs, q))
        assert percentile(list(xs), q) == pytest.approx(np.percentile(xs, q))
    d = reg.to_dict()
    assert d["histograms"]["lat"]["count"] == 500
    assert d["histograms"]["lat"]["p50"] == pytest.approx(
        np.percentile(xs, 50))


def test_histogram_reservoir_bounds_memory():
    h = MetricsRegistry().histogram("x", max_samples=64)
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000
    assert len(h._samples) == 64
    # quantiles stay inside the observed range
    assert 0.0 <= h.quantile(50) <= 999.0


def test_counter_and_gauge():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.counter("c").inc()
    reg.gauge("g").set(2.5)
    assert reg.to_dict()["counters"]["c"] == 4.0
    assert reg.to_dict()["gauges"]["g"] == 2.5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


def test_scheduler_stats_percentiles_round_trip():
    st = SchedulerStats(ttfts_s=[0.010, 0.020, 0.030, 0.040],
                        tpots_s=[0.001, 0.002, 0.003],
                        queue_waits_s=[0.0, 0.1])
    assert st.ttft_p50_ms == pytest.approx(
        1e3 * np.percentile(st.ttfts_s, 50))
    assert st.ttft_p99_ms == pytest.approx(
        1e3 * np.percentile(st.ttfts_s, 99))
    assert st.tpot_p50_ms == pytest.approx(
        1e3 * np.percentile(st.tpots_s, 50))
    d = st.to_dict()
    for k in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms",
              "queue_wait_p50_ms", "queue_wait_p99_ms"):
        assert k in d and k in st.row()
    rt = SchedulerStats.from_dict(d)
    assert rt.to_dict() == d               # lossless round-trip
    assert SchedulerStats().ttft_p50_ms == 0.0   # empty is defined


# ---------------------------------------------------------------------------
# traced serving runs: phase spans + the consistency invariant
# ---------------------------------------------------------------------------

def _traced_paged_run(model, params, cfg, *, speculative=None, mode="model",
                      n_req=3, tokens=6):
    backend = create_backend(mode, model, params, batch=1, max_len=64)
    tr = Tracer()
    reg = MetricsRegistry()
    sched = Scheduler(InferenceSession(backend), num_slots=2,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      speculative=speculative, tracer=tr, metrics=reg)
    d0 = backend.dispatch_stats().dispatches
    for i, p in enumerate(_prompts(cfg, n_req, seed=3)):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=tokens))
    sched.run()
    delta = backend.dispatch_stats().dispatches - d0
    return backend, tr, reg, sched.last_stats, delta


def test_paged_run_spans_and_consistency(setup):
    cfg, model, params = setup
    backend, tr, reg, st, delta = _traced_paged_run(model, params, cfg)
    names = {e.name for e in tr.events()}
    # every scheduler phase from the span list shows up
    for phase in ("admit", "prefill_chunk", "decode_cycle", "readback",
                  "sample_emit", "release"):
        assert phase in names, f"missing {phase} span"
    # dispatch lanes carry the backend name
    tracks = {e.track for e in tr.events()}
    assert f"backend:{backend.capabilities.name}" in tracks
    assert "scheduler" in tracks
    # THE invariant: trace-derived totals == the stats the backend kept
    assert tr.dispatch_total() == delta == st.dispatches
    assert tr.count("decode_cycle") == st.cycles
    # metrics got fed from the same run
    d = reg.to_dict()
    assert d["counters"]["serving.dispatches"] == delta
    assert d["counters"]["serving.tokens"] == st.tokens
    assert d["histograms"]["serving.ttft_s"]["count"] == st.completed
    # export is valid end to end
    validate_trace(to_trace_events(tr))
    # latency samples landed on the stats object too
    assert len(st.ttfts_s) == st.completed
    assert st.ttft_p99_ms >= st.ttft_p50_ms > 0


def test_speculative_run_draft_verify_spans(setup):
    cfg, model, params = setup
    backend, tr, reg, st, delta = _traced_paged_run(
        model, params, cfg, speculative="ngram")
    names = {e.name for e in tr.events()}
    assert "draft" in names and "verify" in names
    assert tr.count("verify") == st.spec_cycles
    assert "dispatch:verify" in names      # the backend's verify lane
    assert tr.dispatch_total() == delta == st.dispatches


def test_graph_backend_dispatch_lane(setup):
    cfg, model, params = setup
    backend, tr, reg, st, delta = _traced_paged_run(
        model, params, cfg, mode="F3", n_req=2, tokens=4)
    assert tr.dispatch_total() == delta == st.dispatches
    lane = [e for e in tr.events() if e.track == "backend:F3"
            and e.cat == "dispatch"]
    assert lane, "graph backend emitted no dispatch-lane spans"
    # per-op graph execution: decode cycles carry many dispatches each
    decode = [e for e in lane if e.args and e.args.get("op") == "decode_batch"]
    assert decode and all(e.args["dispatches"] > 1 for e in decode)


def test_paging_instants_recorded(setup):
    """COW forks and radix hits surface as paging-track instants, after
    one instant naming the arena layout that ran."""
    cfg, model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    tr = Tracer()
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      tracer=tr)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, size=12)    # not block-aligned
    for i in range(2):
        p = np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=4)])
        sched.submit(ServeRequest(prompt=p.astype(np.int32).reshape(1, -1),
                                  max_new_tokens=4))
        sched.run()
    assert sched.last_stats.prefix_hits >= 1
    names = {e.name for e in tr.events()}
    assert "radix_hit" in names
    assert "cow_fork" in names             # mid-block boundary fork
    assert all(e.track == "paging" for e in tr.events()
               if e.name in ("radix_hit", "cow_fork", "arena_layout"))
    layout = [e for e in tr.events() if e.name == "arena_layout"]
    pool = sched._bstate["paged"].pool
    assert len(layout) == 1                 # two runs, one attach
    assert layout[0].args == {"layout": "stacked",
                              "shape": list(pool.arena_k.shape)}


# ---------------------------------------------------------------------------
# overhead attribution + engine shim accounting
# ---------------------------------------------------------------------------

def test_measure_overhead_decomposition(setup):
    cfg, model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    r = measure_overhead(backend, _prompts(cfg, 1, plen=6)[0], n_steps=6)
    assert r.backend == "model"
    assert r.dispatches_per_step == 1      # one fused executable per step
    assert r.submit_us > 0 and r.naive_per_op_us > 0
    assert r.amortized_per_op_us > 0
    # the decomposition accounts for the naive loop's wall time
    assert r.host_python_us + r.submit_us + r.device_us == pytest.approx(
        r.naive_per_op_us, rel=0.01)
    row = r.row()
    assert set(row) >= {"backend", "dispatches_per_step", "submit_us",
                        "amortization_ratio"}


def test_measure_overhead_graph_backend_counts_per_op(setup):
    cfg, model, params = setup
    backend = create_backend("F3", model, params, batch=1, max_len=64)
    r = measure_overhead(backend, _prompts(cfg, 1, plen=6)[0], n_steps=4)
    assert r.dispatches_per_step > 1       # per-op dispatch stream


def test_generation_engine_single_accounting_source(setup):
    """Regression: the shim must report MEASURED dispatches through the
    same dispatch_stats() path the tracer observes, and its static
    dispatches_per_token must track the backend capability live."""
    cfg, model, params = setup
    eng = GenerationEngine(model, params, mode="model", batch=1, max_len=32)
    assert eng.dispatches_per_token == \
        eng.backend.capabilities.dispatches_per_token
    d0 = eng.dispatch_stats().dispatches
    out = eng.generate(np.array([[3, 1, 4, 1]], np.int32), 6)
    assert out.dispatches == eng.dispatch_stats().dispatches - d0
    assert out.dispatches == out.n_new     # 1 fused dispatch per token
    eng.reset_stats()
    assert eng.dispatch_stats().dispatches == 0


def test_disabled_tracer_overhead_under_budget(setup):
    """The no-op path must cost well under 2% of a decode cycle (the CI
    bound, asserted with generous slack for shared runners)."""
    import time

    cfg, model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    sched = Scheduler(InferenceSession(backend), num_slots=2,
                      kv_layout="paged", prefill_chunk=8, block_size=8)
    for p in _prompts(cfg, 2, seed=9):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=8))
    sched.run()
    st = sched.last_stats
    cycle_s = st.wall_s / max(st.cycles, 1)

    tr = NULL_TRACER
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("decode_cycle", track="scheduler", cycle=0):
            pass
        tr.instant("x")
        with tr.span("enqueue", track="backend:model"):
            pass
    per_iter = (time.perf_counter() - t0) / n
    # ~8 tracer touch points per scheduler cycle; must stay under 2%
    overhead_frac = 8 * per_iter / cycle_s
    assert overhead_frac < 0.02, (
        f"disabled tracer costs {100 * overhead_frac:.3f}% of a "
        f"{1e3 * cycle_s:.2f} ms decode cycle")


# ---------------------------------------------------------------------------
# spans on the profiler's clock; named scopes of the paged step
# ---------------------------------------------------------------------------

def _xplane_spans(path):
    """(name, start_ns, end_ns) of every ``repro.*`` host event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith("repro.")]
    return out


def test_live_spans_reach_the_profiler(setup, tmp_path):
    """Under ``jax.profiler.trace`` every live span is also a profiler
    event: a decode cycle holds its dispatch, which holds the jitted
    call, and the trace counts exactly the cycles the scheduler ran."""
    cfg, model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    tr = Tracer()
    sched = Scheduler(InferenceSession(backend), num_slots=1,
                      kv_layout="paged", prefill_chunk=8, block_size=8,
                      tracer=tr)
    prompts = _prompts(cfg, 2, seed=11)
    sched.submit(ServeRequest(prompt=prompts[0], max_new_tokens=4))
    sched.run()                            # compile outside the trace
    tr.clear()
    with jax.profiler.trace(str(tmp_path)):
        sched.submit(ServeRequest(prompt=prompts[1], max_new_tokens=6))
        sched.run()
    st = sched.last_stats
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = _xplane_spans(path)
    cycles = [s for s in spans if s[0] == "repro.decode_cycle"]
    assert len(cycles) == tr.count("decode_cycle") == st.cycles > 0

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]
    dispatches = [s for s in spans if s[0] == "repro.dispatch:decode_batch"]
    enqueues = [s for s in spans if s[0] == "repro.enqueue"]
    assert len(dispatches) == st.cycles
    for d in dispatches:
        assert any(inside(d, c) for c in cycles)
        assert any(inside(e, d) for e in enqueues)
    names = {s[0] for s in spans}
    assert {"repro.prepare", "repro.upload", "repro.adopt",
            "repro.readback", "repro.sample_emit", "repro.admit",
            "repro.prefill_chunk",
            "repro.dispatch:prefill_chunk"} <= names
    # the ring buffer still holds the same spans for the Perfetto export
    assert tr.dispatch_total() == st.dispatches
    validate_trace(to_trace_events(tr))


def test_null_tracer_constructs_no_trace_annotation(monkeypatch):
    import repro.obs.tracer as tracer_mod
    made = []

    class Counting(tracer_mod.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Counting)
    for _ in range(10):
        with NULL_TRACER.span("decode_cycle", track="scheduler", cycle=0):
            pass
    assert made == []
    tr = Tracer()
    with tr.span("decode_cycle", track="scheduler"):
        with tr.span("dispatch:decode_batch", cat="dispatch"):
            pass
    assert made == ["repro.decode_cycle", "repro.dispatch:decode_batch"]


def test_record_outside_a_dispatch_span_is_refused(setup):
    """A live tracer makes a dispatch recorded outside any dispatch span
    an error instead of a silently lost count."""
    from repro.core.engine import RunStats
    cfg, model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    backend.tracer = Tracer()
    rs = RunStats(wall_s=0.0, dispatches=1, shape_ops=0, sync_mode="none")
    with pytest.raises(RuntimeError, match="outside a dispatch span"):
        backend._record(rs, op="decode")
    with backend._dispatch_span("decode"):
        backend._record(rs, op="decode")
    assert backend.tracer.dispatch_total() == 1
