"""Beyond-paper: batch>1 validation of the crossover model (App. F), plus
the continuous-batching amortization curve the CI bench gate asserts.

The paper measured batch=1 only and flagged batch scaling as its
"highest-priority future work": the B* model predicts per-operation
overhead amortizes with batch while kernel time grows, so tokens/s should
scale super-linearly in the overhead-bound regime and saturate once
compute-bound.  We sweep batch at fixed fusion level and compare the
measured aggregate-token throughput curve against the overhead-amortization
prediction  t(B) ≈ t_overhead + B·t_compute(1).

``run_serving`` measures the SERVING-side amortizer: N overlapping
requests through the continuous slot ``Scheduler`` (one batched decode
dispatch stream per cycle) against the same N requests decoded
sequentially — aggregate tok/s vs. concurrent requests and
dispatches/token vs. occupancy, emitted as ``BENCH_serving.json``.  The
CI ``bench`` job fails if 4-slot continuous throughput drops below the
1-slot sequential baseline (``--gate 1.0``).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import print_table, save_results
from repro.configs.bench import BENCH_05B, BENCH_15B
from repro.core.graphs import LEVELS, build_decode_graph
from repro.models import build_model
from repro.serving import (InferenceSession, ModelDrafter, Scheduler,
                           SchedulerConfig, ServeRequest, SpeculativeConfig,
                           create_backend)
from repro.serving.backends.graph import GRAPH_MODES

BATCHES = (1, 2, 4, 8)
SLOT_SWEEP = (1, 2, 4, 8)
GATE_SLOTS = 4       # the CI gate compares this occupancy vs 1-slot seq
DECODE_HORIZON = 8   # multi-step capture: decode cycles per host super-step


def run(quick: bool = False, tokens: int = 20) -> List[Dict]:
    n_runs, warmup = (3, 1) if quick else (8, 2)
    if quick:
        tokens = 8
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    rows = []
    base_step_s = None
    for b in BATCHES:
        prompt = rng.integers(0, BENCH_05B.vocab_size, size=(b, 5)).astype(np.int32)
        session = InferenceSession(create_backend(
            "F3", model, params, batch=b, max_len=5 + tokens + 4))
        rep = session.benchmark(prompt, tokens, n_runs=n_runs, warmup=warmup)
        step_s = 1.0 / rep.tok_per_s.mean          # seconds per decode step
        if base_step_s is None:
            base_step_s = step_s
        agg = rep.tok_per_s.mean * b
        rows.append({
            "batch": b,
            "step_ms": round(1e3 * step_s, 3),
            "aggregate_tok_s": round(agg, 1),
            "tok_s_scaling_vs_b1": round(agg / (BATCHES[0] / base_step_s), 2),
            "step_slowdown_vs_b1": round(step_s / base_step_s, 2),
            "cv_pct": round(100 * rep.tok_per_s.cv, 1),
        })
    # overhead-amortization read-out: if step time grows far slower than B,
    # the op stream is overhead-bound at B=1 (the paper's claim)
    s1, s8 = rows[0]["step_ms"], rows[-1]["step_ms"]
    verdict = ("overhead-bound at B=1 (step time grew "
               f"{s8/s1:.2f}× for {BATCHES[-1]}× the work)"
               if s8 / s1 < BATCHES[-1] / 2 else
               "compute-bound at B=1 on this host")
    print_table("App. F validation (beyond paper): batch sweep, F3 fusion",
                rows, ["batch", "step_ms", "aggregate_tok_s",
                       "step_slowdown_vs_b1", "cv_pct"])
    print(f"  → {verdict}")
    save_results("batch", {"rows": rows, "verdict": verdict})
    run_serving(quick=quick)
    return rows


# ---------------------------------------------------------------------------
# continuous-batching amortization curve (BENCH_serving.json + CI gate)
# ---------------------------------------------------------------------------

def _schedule(session, prompts, tokens: int, num_slots: int,
              continuous: bool, horizon: int = 1):
    """One scheduler pass over ``prompts``; returns (results, stats)."""
    sched = Scheduler(session, config=SchedulerConfig(
        num_slots=num_slots, continuous=continuous,
        decode_horizon=horizon))
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=tokens,
                                     request_id=f"s{num_slots}-h{horizon}"
                                                f"-r{i}"))
           for i, p in enumerate(prompts)]
    results = sched.run()
    return [results[rid] for rid in ids], sched.last_stats


def run_serving(quick: bool = False, tokens: int = 16,
                modes=("F3", "model"), gate: float = 0.0,
                gate_multistep: bool = False) -> Dict:
    """tok/s vs. concurrent requests, dispatches/token vs. occupancy.

    For each slot count S the same S overlapping requests run twice: the
    continuous scheduler (one batched decode dispatch stream per cycle)
    and the 1-slot sequential baseline (S back-to-back runs).  The
    speedup ratio at each S is the serving amortization curve; ``gate``
    > 0 asserts the S=4 continuous/sequential ratio on the dispatch-bound
    F3 regime (the CI continuous-batching smoke gate) and exits nonzero
    below it.
    """
    if quick:
        tokens = 6
    sweep = tuple(s for s in SLOT_SWEEP if s <= GATE_SLOTS) if quick \
        else SLOT_SWEEP
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    plen = 5
    max_len = plen + tokens + 4

    rows: List[Dict] = []
    gate_ratios: Dict[str, float] = {}
    for mode in modes:
        backend = create_backend(mode, model, params, batch=1,
                                 max_len=max_len)
        session = InferenceSession(backend)
        prompts = [rng.integers(0, BENCH_05B.vocab_size, size=(1, plen))
                   .astype(np.int32) for _ in range(max(sweep))]
        # independent greedy references (also compiles the sequential path,
        # so the timed passes below exclude XLA compilation)
        refs = [session.run(ServeRequest(prompt=p, max_new_tokens=tokens))
                .tokens for p in prompts]
        for s in sweep:
            # warmup: each slot count lowers its own batched decode graph
            _schedule(session, prompts[:s], tokens, s, True)
            res_c, st_c = _schedule(session, prompts[:s], tokens, s, True)
            res_q, st_q = _schedule(session, prompts[:s], tokens, 1, False)
            for r, ref in zip(res_c, refs[:s]):
                np.testing.assert_array_equal(r.tokens, ref)
            for r, ref in zip(res_q, refs[:s]):
                np.testing.assert_array_equal(r.tokens, ref)
            ratio = (st_c.aggregate_tok_per_s
                     / max(st_q.aggregate_tok_per_s, 1e-12))
            if mode == modes[0] and s == GATE_SLOTS:
                gate_ratios[mode] = ratio
            rows.append({
                "mode": mode,
                "concurrent": s,
                "tok_s_continuous": round(st_c.aggregate_tok_per_s, 2),
                "tok_s_sequential": round(st_q.aggregate_tok_per_s, 2),
                "speedup": round(ratio, 2),
                "disp_per_tok_continuous": round(
                    st_c.dispatches_per_token, 2),
                "disp_per_tok_sequential": round(
                    st_q.dispatches_per_token, 2),
                "mean_occupancy": round(st_c.mean_occupancy, 2),
                "ttft_p50_ms": round(st_c.ttft_p50_ms, 2),
                "ttft_p99_ms": round(st_c.ttft_p99_ms, 2),
                "tpot_p50_ms": round(st_c.tpot_p50_ms, 2),
                "tpot_p99_ms": round(st_c.tpot_p99_ms, 2),
            })
    print_table("Continuous batching: amortization curve (bench-0.5b, "
                "greedy parity asserted)",
                rows, ["mode", "concurrent", "tok_s_continuous",
                       "tok_s_sequential", "speedup",
                       "disp_per_tok_continuous", "disp_per_tok_sequential",
                       "mean_occupancy", "ttft_p50_ms", "ttft_p99_ms",
                       "tpot_p50_ms"])
    # -- multi-step decode capture: N cycles per host submission ---------
    # Same prompts through the gate mode at GATE_SLOTS occupancy, horizon
    # 1 vs DECODE_HORIZON.  Token budget = 1 + 2×horizon so every
    # super-step runs the full horizon; a separate max_new=1 pass
    # measures the prefill dispatch share so the decode-stream
    # amortization can be gated exactly (prefill is identical either
    # way and would otherwise dilute the N× claim).
    ms_tokens = 1 + 2 * DECODE_HORIZON
    ms_mode = modes[0]
    backend = create_backend(ms_mode, model, params, batch=1,
                             max_len=plen + ms_tokens + 4)
    session = InferenceSession(backend)
    ms_prompts = [rng.integers(0, BENCH_05B.vocab_size, size=(1, plen))
                  .astype(np.int32) for _ in range(GATE_SLOTS)]
    ms_refs = [session.run(ServeRequest(prompt=p,
                                        max_new_tokens=ms_tokens)).tokens
               for p in ms_prompts]
    _schedule(session, ms_prompts, ms_tokens, GATE_SLOTS, True,
              horizon=DECODE_HORIZON)          # warmup: lowers the capture
    _, st_p = _schedule(session, ms_prompts, 1, GATE_SLOTS, True)
    res_1, st_1 = _schedule(session, ms_prompts, ms_tokens, GATE_SLOTS, True)
    res_n, st_n = _schedule(session, ms_prompts, ms_tokens, GATE_SLOTS, True,
                            horizon=DECODE_HORIZON)
    ms_parity = True
    for r, ref in zip(res_1, ms_refs):
        np.testing.assert_array_equal(r.tokens, ref)
    for r, ref in zip(res_n, ms_refs):
        np.testing.assert_array_equal(r.tokens, ref)

    def _decode_per_tok(st):
        return ((st.dispatches - st_p.dispatches)
                / max(st.tokens - st_p.tokens, 1))

    multistep = {
        "mode": ms_mode,
        "slots": GATE_SLOTS,
        "horizon": DECODE_HORIZON,
        "tokens_per_request": ms_tokens,
        "disp_per_tok_single": round(st_1.dispatches_per_token, 2),
        "disp_per_tok_multi": round(st_n.dispatches_per_token, 2),
        "decode_disp_per_tok_single": round(_decode_per_tok(st_1), 2),
        "decode_disp_per_tok_multi": round(_decode_per_tok(st_n), 2),
        "multi_cycles": st_n.multi_cycles,
        "multi_tokens": st_n.multi_tokens,
        "parity": "exact" if ms_parity else "BROKEN",
    }
    print_table("Multi-step decode capture: one host submission per "
                f"{DECODE_HORIZON} cycles ({ms_mode}, greedy parity "
                "asserted)",
                [multistep], ["mode", "slots", "horizon",
                              "disp_per_tok_single", "disp_per_tok_multi",
                              "decode_disp_per_tok_single",
                              "decode_disp_per_tok_multi", "multi_cycles",
                              "parity"])

    payload = {
        "quick": quick,
        "rows": rows,
        "gate_slots": GATE_SLOTS,
        "gate_mode": modes[0],
        "gate_ratio_measured": gate_ratios.get(modes[0]),
        "gate_ratio_required": gate,
        "multistep": multistep,
        "parity": "exact",
    }
    save_results("serving", payload)
    if gate_multistep:
        need = multistep["decode_disp_per_tok_single"] / DECODE_HORIZON * 1.2
        got = multistep["decode_disp_per_tok_multi"]
        ok = got <= need and ms_parity
        print(f"  → multi-step gate [{ms_mode} @ horizon {DECODE_HORIZON}]: "
              f"decode disp/tok {got:.2f} "
              f"(required ≤ single-step/{DECODE_HORIZON} × 1.2 = "
              f"{need:.2f}), parity exact — {'PASS' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(
                f"multi-step capture gate failed: {got:.2f} > {need:.2f} "
                f"or parity broken")
    if gate > 0:
        r = gate_ratios.get(modes[0], 0.0)
        ok = r >= gate
        print(f"  → bench gate [{modes[0]} @ {GATE_SLOTS} slots]: "
              f"{r:.2f}× vs 1-slot sequential "
              f"(required ≥ {gate:.2f}×) — {'PASS' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(
                f"continuous-batching gate failed: {r:.2f} < {gate:.2f}")
    return payload


# ---------------------------------------------------------------------------
# prefix-reuse curve: radix cache hit rate, prefill dispatches saved, TTFT
# (BENCH_paging.json + CI gate)
# ---------------------------------------------------------------------------

def run_prefix_reuse(quick: bool = False, gate: bool = False,
                     backend_name: str = "model") -> Dict:
    """N requests sharing a long system prompt through the paged scheduler.

    Protocol: serve the same request sequence twice through one-slot paged
    schedulers — once with the radix prefix cache OFF (every prompt pays
    full prefill: the cold baseline) and once ON (request 1 cold, the rest
    warm).  Greedy parity against the plain dense session is asserted for
    every request.  Reported per run: prefix hit rate, prefill chunk
    dispatches, TTFT, COW forks, and the dense-vs-paged KV memory table.

    ``backend_name`` selects the serving backend: ``model`` (the
    single-executable path, ``BENCH_paging.json``) or a dispatch-graph
    level like ``F3`` (the dispatch-MEASURED path,
    ``BENCH_paging_graph.json``) — for graph backends the payload also
    records the paged-vs-``slot_pos`` decode dispatch counts, which
    ``gate`` asserts are IDENTICAL (paging must be free in the per-op
    accounting).

    ``gate`` additionally asserts the paper-level claims CI rides on: a
    warm hit performs ZERO prefill dispatches for the shared span (warm
    chunks == suffix-only chunks) and warm TTFT ≤ cold TTFT.
    """
    n_req = 4 if quick else 8
    tokens = 4 if quick else 8
    sys_len = 28 if quick else 60       # NOT block-aligned → COW path runs
    suffix_len = 6
    block, chunk = 8, 8
    plen = sys_len + suffix_len
    max_len = plen + tokens + 4
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    system = rng.integers(0, BENCH_05B.vocab_size, size=sys_len)
    prompts = [np.concatenate(
        [system, rng.integers(0, BENCH_05B.vocab_size, size=suffix_len)]
    ).astype(np.int32).reshape(1, -1) for _ in range(n_req)]

    backend = create_backend(backend_name, model, params, batch=1,
                             max_len=max_len)
    if not backend.capabilities.paged_kv:
        raise SystemExit(f"backend {backend_name!r} has no paged-KV support")
    session = InferenceSession(backend)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=tokens))
            .tokens for p in prompts]

    def serve_all(prefix_cache: bool):
        sched = Scheduler(session, num_slots=1, kv_layout="paged",
                          prefill_chunk=chunk, block_size=block,
                          prefix_cache=prefix_cache)
        per_req = []
        for i, p in enumerate(prompts):
            rid = sched.submit(ServeRequest(prompt=p, max_new_tokens=tokens,
                                            request_id=f"pc{prefix_cache}-{i}"))
            res = sched.run()[rid]
            np.testing.assert_array_equal(res.tokens, refs[i])
            st = sched.last_stats
            per_req.append({
                "ttft_ms": 1e3 * res.ttft_s,
                "prefill_chunks": st.prefill_chunks,
                "hit_tokens": st.prefix_hit_tokens,
                "cow_copies": st.cow_copies,
            })
        return per_req, sched.last_stats

    # warmup: ONE request compiles the extend + decode executables
    wsched = Scheduler(session, num_slots=1, kv_layout="paged",
                       prefill_chunk=chunk, block_size=block,
                       prefix_cache=False)
    wsched.submit(ServeRequest(prompt=prompts[0], max_new_tokens=tokens))
    wsched.run()
    cold, _ = serve_all(prefix_cache=False)
    warm_all, st_warm = serve_all(prefix_cache=True)
    warm = warm_all[1:]                 # request 0 populates the cache

    cold_chunks = -(-plen // chunk)
    warm_chunks_expected = -(-(plen - sys_len) // chunk)
    ttft_cold = float(np.mean([r["ttft_ms"] for r in cold]))
    ttft_warm = float(np.mean([r["ttft_ms"] for r in warm]))
    rows = [{
        "mode": "cold (no prefix cache)",
        "requests": len(cold),
        "ttft_ms": round(ttft_cold, 2),
        "prefill_chunks_per_req": cold[0]["prefill_chunks"],
        "hit_tokens": 0,
    }, {
        "mode": "warm (radix hit)",
        "requests": len(warm),
        "ttft_ms": round(ttft_warm, 2),
        "prefill_chunks_per_req": warm[0]["prefill_chunks"],
        "hit_tokens": warm[0]["hit_tokens"],
    }]
    print_table(f"Prefix reuse: radix cache vs cold prefill ({backend_name} "
                f"backend, bench-0.5b, shared {sys_len}-token system "
                "prompt, parity asserted)",
                rows, ["mode", "requests", "ttft_ms",
                       "prefill_chunks_per_req", "hit_tokens"])
    saved = cold_chunks - warm[0]["prefill_chunks"]
    print(f"  → shared span {sys_len} tokens: {saved} prefill dispatches "
          f"saved per warm request ({warm[0]['prefill_chunks']} vs "
          f"{cold_chunks}), TTFT {ttft_cold:.1f} → {ttft_warm:.1f} ms")

    # dense-vs-paged KV memory utilization, one table (bytes_allocated /
    # bytes_live are now uniform across both layouts)
    sched_d = Scheduler(session, num_slots=1)
    for i, p in enumerate(prompts):
        sched_d.submit(ServeRequest(prompt=p, max_new_tokens=tokens,
                                    request_id=f"kvd{i}"))
    sched_d.run()
    st_dense = sched_d.last_stats
    kv_rows = [
        {"layout": lay, "kv_bytes_allocated": st.kv_bytes_allocated,
         "kv_bytes_live_peak": st.kv_bytes_live_peak,
         "utilization": round(st.kv_utilization, 3)}
        for lay, st in (("dense", st_dense), ("paged", st_warm))]
    print_table("KV memory utilization: dense rows vs paged blocks "
                "(1 slot, same workload)", kv_rows,
                ["layout", "kv_bytes_allocated", "kv_bytes_live_peak",
                 "utilization"])
    payload = {
        "backend": backend_name,
        "quick": quick,
        "rows": rows,
        "system_prompt_tokens": sys_len,
        "prompt_tokens": plen,
        "block_size": block,
        "prefill_chunk": chunk,
        "prefix_hit_tokens_warm": warm[0]["hit_tokens"],
        "prefill_dispatches_saved_per_warm_req": saved,
        "warm_chunks_expected_suffix_only": warm_chunks_expected,
        "ttft_cold_ms": round(ttft_cold, 2),
        "ttft_warm_ms": round(ttft_warm, 2),
        "cow_copies_warm": sum(r["cow_copies"] for r in warm_all),
        "kv_bytes_allocated": st_warm.kv_bytes_allocated,
        "kv_bytes_live_peak": st_warm.kv_bytes_live_peak,
        "kv_table": kv_rows,
        "parity": "exact",
        "gate_zero_shared_span_prefill":
            warm[0]["prefill_chunks"] == warm_chunks_expected,
        "gate_warm_ttft_le_cold": ttft_warm <= ttft_cold,
    }
    ok_flat = True
    if backend_name in GRAPH_MODES:
        # the dispatch-measured regime: the paged decode graph must spend
        # exactly the dispatches of the dense slot_pos graph — this is the
        # paper's per-operation accounting, so paging has to be free here
        fusion = LEVELS["F0" if backend_name == "FULL" else backend_name]
        g_dense = build_decode_graph(params, BENCH_05B, batch=1,
                                     max_len=max_len, fusion=fusion,
                                     slot_pos=True)
        g_paged = build_decode_graph(params, BENCH_05B, batch=1,
                                     max_len=max_len, fusion=fusion,
                                     paged=True, block_size=block)
        payload["decode_dispatches_per_token_slot_pos"] = \
            g_dense.num_dispatches()
        payload["decode_dispatches_per_token_paged"] = \
            g_paged.num_dispatches()
        ok_flat = g_paged.num_dispatches() == g_dense.num_dispatches()
        payload["gate_dispatches_per_token_flat"] = ok_flat
        print(f"  → paged decode dispatches/token [{backend_name}]: "
              f"{g_paged.num_dispatches()} paged vs "
              f"{g_dense.num_dispatches()} dense slot_pos — "
              f"{'FLAT' if ok_flat else 'REGRESSED'}")
    # one trajectory file per backend family: model → BENCH_paging.json,
    # graph levels → BENCH_paging_graph.json, anything else (e.g. dist)
    # its own name — never clobber another backend's committed baseline
    if backend_name == "model":
        bench_name = "paging"
    elif backend_name in GRAPH_MODES:
        bench_name = "paging_graph"
    else:
        bench_name = f"paging_{backend_name}"
    save_results(bench_name, payload)
    if gate:
        ok_disp = payload["gate_zero_shared_span_prefill"]
        ok_ttft = payload["gate_warm_ttft_le_cold"]
        print(f"  → paging gate [{backend_name}]: shared-span prefill "
              f"dispatches {'ZERO' if ok_disp else 'NONZERO'}; warm TTFT "
              f"{ttft_warm:.1f} ms vs cold {ttft_cold:.1f} ms — "
              f"{'PASS' if ok_disp and ok_ttft and ok_flat else 'FAIL'}")
        if not (ok_disp and ok_ttft and ok_flat):
            raise SystemExit(
                f"prefix-reuse gate failed: chunks "
                f"{warm[0]['prefill_chunks']} (expected "
                f"{warm_chunks_expected}), ttft warm {ttft_warm:.2f} "
                f"vs cold {ttft_cold:.2f}, dispatches/token flat: "
                f"{ok_flat}")
    return payload


# ---------------------------------------------------------------------------
# speculative decoding: dispatches per ACCEPTED token vs autoregressive
# (BENCH_spec.json + CI gate)
# ---------------------------------------------------------------------------

def run_speculative(quick: bool = False, gate: bool = False) -> Dict:
    """Draft-K/verify-once speculation vs plain autoregressive decode.

    The same greedy requests run twice through a one-slot paged
    scheduler — autoregressive (one dispatch per token) and speculative
    (one verify dispatch per CYCLE, each cycle emitting 1 + accepted
    tokens) — with byte-identical output asserted, so every reported
    delta is pure dispatch accounting.  Two drafters are reported: the
    zero-dispatch n-gram prompt-lookup drafter on bench-0.5b (the gated
    row) and the paper's small-model pair, bench-0.5b drafting for
    bench-1.5b (reported only — draft dispatches are real dispatches
    and are broken out separately).

    ``gate`` asserts the headline claim CI rides on: speculative
    dispatches per accepted token strictly below the autoregressive
    dispatches per token (deterministic — pure counter arithmetic), and
    speculative tok/s at or above autoregressive on the gated row.
    """
    tokens = 12 if quick else 24
    k = 4
    n_req = 2 if quick else 4
    block, chunk = 8, 8
    rng = np.random.default_rng(7)
    # periodic prompt body + unique suffix: the workload the paper's
    # serving traces motivate (replayed context), where prompt-lookup
    # drafting accepts well
    motif = rng.integers(0, BENCH_05B.vocab_size, size=6)
    prompts = [np.concatenate(
        [np.tile(motif, 3),
         rng.integers(0, BENCH_05B.vocab_size, size=4)]
    ).astype(np.int32).reshape(1, -1) for _ in range(n_req)]
    plen = prompts[0].shape[1]
    max_len = plen + tokens + 4

    def serve(session, prompts, refs, speculative, label):
        sched = Scheduler(session, num_slots=1, kv_layout="paged",
                          prefill_chunk=chunk, block_size=block,
                          prefix_cache=False, speculative=speculative)
        ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=tokens,
                                         request_id=f"{label}-{i}"))
               for i, p in enumerate(prompts)]
        results = sched.run()
        for rid, ref in zip(ids, refs):
            np.testing.assert_array_equal(results[rid].tokens, ref)
        return sched.last_stats

    rows: List[Dict] = []

    def measure(session, prompts, refs, speculative, name):
        # warmup compiles the prefill/decode/verify executables so the
        # timed passes compare dispatch streams, not XLA compilation
        serve(session, prompts[:1], refs[:1], None, f"w-ar-{name}")
        serve(session, prompts[:1], refs[:1], speculative, f"w-sp-{name}")
        st_ar = serve(session, prompts, refs, None, f"ar-{name}")
        st_sp = serve(session, prompts, refs, speculative, f"sp-{name}")
        rows.append({
            "drafter": name,
            "k": k,
            "acceptance_rate": round(st_sp.acceptance_rate, 3),
            "disp_per_accepted_tok": round(
                st_sp.dispatches_per_accepted_token, 3),
            "disp_per_tok_ar": round(st_ar.dispatches_per_token, 3),
            "draft_dispatches": st_sp.draft_dispatches,
            "tok_s_spec": round(st_sp.aggregate_tok_per_s, 2),
            "tok_s_ar": round(st_ar.aggregate_tok_per_s, 2),
            "speedup": round(st_sp.aggregate_tok_per_s
                             / max(st_ar.aggregate_tok_per_s, 1e-12), 2),
        })
        return st_ar, st_sp

    # gated row: n-gram prompt-lookup on bench-0.5b (zero draft dispatches)
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    session = InferenceSession(create_backend("model", model, params,
                                              batch=1, max_len=max_len))
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=tokens))
            .tokens for p in prompts]
    st_ar, st_sp = measure(session, prompts, refs,
                           SpeculativeConfig(drafter="ngram", k=k),
                           "ngram@0.5b")

    # reported row: the paper's model pair — bench-0.5b drafts, bench-1.5b
    # verifies (drafter dispatches are real and reported, not hidden)
    pair_prompts = prompts[:2]
    target = build_model(BENCH_15B)
    tparams = target.init_params(jax.random.PRNGKey(1))
    tsession = InferenceSession(create_backend("model", target, tparams,
                                               batch=1, max_len=max_len))
    trefs = [tsession.run(ServeRequest(prompt=p, max_new_tokens=tokens))
             .tokens for p in pair_prompts]
    drafter = ModelDrafter(create_backend("model", model, params, batch=1,
                                          max_len=max_len + k + 2))
    measure(tsession, pair_prompts, trefs,
            SpeculativeConfig(drafter=drafter, k=k), "0.5b→1.5b")

    print_table("Speculative decoding: draft K, verify in one dispatch "
                "(1 slot, paged, greedy parity asserted)",
                rows, ["drafter", "k", "acceptance_rate",
                       "disp_per_accepted_tok", "disp_per_tok_ar",
                       "draft_dispatches", "tok_s_spec", "tok_s_ar",
                       "speedup"])
    g = rows[0]
    print(f"  → [{g['drafter']}] acceptance {g['acceptance_rate']:.2f}, "
          f"target dispatches/accepted token "
          f"{g['disp_per_accepted_tok']:.3f} vs autoregressive "
          f"{g['disp_per_tok_ar']:.3f}")
    payload = {
        "quick": quick,
        "backend": "model",
        "drafter": g["drafter"],
        "k": k,
        "rows": rows,
        "acceptance_rate": g["acceptance_rate"],
        "dispatches_per_accepted_token": g["disp_per_accepted_tok"],
        "dispatches_per_token_ar": g["disp_per_tok_ar"],
        "spec_cycles": st_sp.spec_cycles,
        "verify_dispatches": st_sp.verify_dispatches,
        "cow_copies_spec": st_sp.cow_copies,
        "tok_s_spec": g["tok_s_spec"],
        "tok_s_ar": g["tok_s_ar"],
        "speedup": g["speedup"],
        "parity": "exact",
        "gate_fewer_dispatches_per_token":
            g["disp_per_accepted_tok"] < g["disp_per_tok_ar"],
        "gate_tok_s_ge_autoregressive": g["tok_s_spec"] >= g["tok_s_ar"],
    }
    save_results("spec", payload)
    if gate:
        ok_disp = payload["gate_fewer_dispatches_per_token"]
        ok_tps = payload["gate_tok_s_ge_autoregressive"]
        print(f"  → spec gate [{g['drafter']}]: dispatches/accepted token "
              f"{g['disp_per_accepted_tok']:.3f} "
              f"{'<' if ok_disp else '>='} AR {g['disp_per_tok_ar']:.3f}; "
              f"tok/s {g['tok_s_spec']:.1f} vs AR {g['tok_s_ar']:.1f} — "
              f"{'PASS' if ok_disp and ok_tps else 'FAIL'}")
        if not (ok_disp and ok_tps):
            raise SystemExit(
                f"speculative gate failed: dispatches/accepted token "
                f"{g['disp_per_accepted_tok']} vs AR "
                f"{g['disp_per_tok_ar']}, tok/s {g['tok_s_spec']} vs AR "
                f"{g['tok_s_ar']}")
    return payload


# ---------------------------------------------------------------------------
# observability: traced serving run + per-backend overhead attribution
# (BENCH_obs.json, trace_obs.json + CI self-consistency gate)
# ---------------------------------------------------------------------------

def run_obs(quick: bool = False, gate: bool = False,
            profile_dir: str = "") -> Dict:
    """Traced paged serving run + the paper's §7.2 overhead decomposition.

    Serves a small paged workload with ``repro.obs`` tracing enabled and
    writes three artifacts: the Perfetto trace-event JSON
    (``benchmarks/results/trace_obs.json``), the serving metrics registry
    (``metrics_obs.json``), and ``BENCH_obs.json`` — per-backend
    ``OverheadReport`` rows splitting per-op cost into {host Python,
    dispatch submit, device compute} for the model backend (1 fused
    dispatch/step) vs the F3 dispatch graph (per-op dispatch stream).

    ``gate`` asserts the tracer's self-consistency invariant CI rides on:
    the trace-derived dispatch total equals the backend's
    ``dispatch_stats()`` delta EXACTLY (both flow through the one
    ``_record`` choke point), and the traced decode-cycle span count
    equals ``SchedulerStats.cycles``.

    ``profile_dir`` additionally wraps the serving run in
    ``jax.profiler`` so the XLA-level trace lands next to the obs trace
    (uploaded together as CI artifacts).
    """
    import os

    from benchmarks.common import RESULTS_DIR
    from repro.obs import (MetricsRegistry, Tracer, measure_overhead,
                           overhead_table, validate_trace, write_metrics,
                           write_trace)

    tokens = 8 if quick else 16
    n_req = 4 if quick else 6
    num_slots = 2
    plen = 12
    max_len = plen + tokens + 8
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, BENCH_05B.vocab_size, size=(1, plen))
               .astype(np.int32) for _ in range(n_req)]

    backend = create_backend("model", model, params, batch=1,
                             max_len=max_len)
    session = InferenceSession(backend)
    # warmup compiles the extend/decode executables so the traced pass
    # records steady-state dispatches, not XLA compilation
    wsched = Scheduler(session, num_slots=num_slots, kv_layout="paged",
                       prefill_chunk=8, prefix_cache=False)
    for p in prompts[:num_slots]:
        wsched.submit(ServeRequest(prompt=p, max_new_tokens=tokens))
    wsched.run()

    tracer = Tracer()
    metrics = MetricsRegistry()
    sched = Scheduler(session, num_slots=num_slots, kv_layout="paged",
                      prefill_chunk=8, prefix_cache=False,
                      tracer=tracer, metrics=metrics)
    for i, p in enumerate(prompts):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=tokens,
                                  request_id=f"obs-{i}"))
    d0 = backend.dispatch_stats().dispatches
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    try:
        sched.run()
    finally:
        if profile_dir:
            jax.profiler.stop_trace()
    st = sched.last_stats
    delta = backend.dispatch_stats().dispatches - d0

    os.makedirs(RESULTS_DIR, exist_ok=True)
    trace_path = write_trace(tracer, os.path.join(RESULTS_DIR,
                                                  "trace_obs.json"))
    metrics_path = write_metrics(metrics, os.path.join(RESULTS_DIR,
                                                       "metrics_obs.json"))
    import json
    with open(trace_path) as f:
        validate_trace(json.load(f))

    trace_total = tracer.dispatch_total()
    decode_spans = tracer.count("decode_cycle")
    print(f"\n== Observability: traced paged serving run "
          f"({n_req} req × {tokens} tok, model backend) ==")
    print(f"  trace events {len(tracer)} (dropped {tracer.dropped}); "
          f"dispatch total {trace_total} vs dispatch_stats delta {delta}; "
          f"decode spans {decode_spans} vs cycles {st.cycles}")
    print(f"  artifacts: {trace_path}, {metrics_path}"
          + (f", {profile_dir}/" if profile_dir else ""))

    # per-backend §7.2 decomposition: 1-dispatch model vs per-op F3 graph
    rng2 = np.random.default_rng(12)
    oh_prompt = rng2.integers(0, BENCH_05B.vocab_size, (1, 8))
    n_steps = 8 if quick else 32
    reports = []
    for mode in ("model", "F3"):
        b = create_backend(mode, model, params, batch=1,
                           max_len=8 + 2 + 3 * n_steps + 4)
        reports.append(measure_overhead(b, oh_prompt, n_steps=n_steps))
    oh_rows = overhead_table(reports)
    print_table("Overhead attribution: naive vs sequential-dispatch "
                "timing (µs/op)", oh_rows,
                ["backend", "dispatches_per_step", "host_python_us",
                 "submit_us", "device_us", "naive_per_op_us",
                 "amortized_per_op_us", "amortization_ratio"])

    ok_total = trace_total == delta
    ok_decode = decode_spans == st.cycles
    payload = {
        "quick": quick,
        "backend": "model",
        "requests": n_req,
        "tokens_per_request": tokens,
        "trace_events": len(tracer),
        "trace_dropped": tracer.dropped,
        "trace_dispatch_total": trace_total,
        "dispatch_stats_delta": delta,
        "decode_cycle_spans": decode_spans,
        "scheduler_cycles": st.cycles,
        "serving": {
            "dispatches_per_token": round(st.dispatches_per_token, 3),
            "ttft_p50_ms": round(st.ttft_p50_ms, 2),
            "ttft_p99_ms": round(st.ttft_p99_ms, 2),
            "tpot_p50_ms": round(st.tpot_p50_ms, 2),
            "tpot_p99_ms": round(st.tpot_p99_ms, 2),
        },
        "overhead": oh_rows,
        "gate_trace_matches_stats": ok_total,
        "gate_decode_spans_match_cycles": ok_decode,
    }
    save_results("obs", payload)
    if gate:
        print(f"  → obs gate: trace dispatch total "
              f"{'==' if ok_total else '!='} stats delta; decode spans "
              f"{'==' if ok_decode else '!='} cycles — "
              f"{'PASS' if ok_total and ok_decode else 'FAIL'}")
        if not (ok_total and ok_decode):
            raise SystemExit(
                f"obs self-consistency gate failed: trace {trace_total} vs "
                f"stats {delta}; decode spans {decode_spans} vs cycles "
                f"{st.cycles}")
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--serving-only", action="store_true",
                    help="skip the App. F batch sweep")
    ap.add_argument("--gate", type=float, default=0.0,
                    help="fail unless 4-slot continuous tok/s ≥ GATE × "
                         "1-slot sequential (CI regression gate)")
    ap.add_argument("--gate-multistep", action="store_true",
                    help="fail unless horizon-N decode dispatches/token ≤ "
                         "single-step/N × 1.2 with byte-exact greedy "
                         "parity (multi-step capture CI gate)")
    ap.add_argument("--prefix-reuse", action="store_true",
                    help="run the radix prefix-cache reuse benchmark "
                         "(BENCH_paging.json / BENCH_paging_graph.json)")
    ap.add_argument("--gate-paging", action="store_true",
                    help="fail unless a warm radix hit skips the shared "
                         "span's prefill dispatches, warm TTFT ≤ cold, and "
                         "(graph backends) paged decode dispatches/token "
                         "== dense slot_pos")
    ap.add_argument("--backend", default="model",
                    help="prefix-reuse backend: model | F0..F4 | FULL | "
                         "dist (graph levels emit BENCH_paging_graph.json "
                         "with the dispatch-count gate)")
    ap.add_argument("--speculative", action="store_true",
                    help="run the speculative-decoding benchmark "
                         "(BENCH_spec.json: n-gram + model-pair drafters)")
    ap.add_argument("--gate-spec", action="store_true",
                    help="fail unless speculative dispatches per accepted "
                         "token < autoregressive dispatches/token and "
                         "speculative tok/s >= autoregressive")
    ap.add_argument("--obs", action="store_true",
                    help="run the traced serving + overhead-attribution "
                         "benchmark (BENCH_obs.json, trace_obs.json)")
    ap.add_argument("--gate-obs", action="store_true",
                    help="fail unless the trace-derived dispatch total "
                         "equals the backend dispatch_stats() delta and "
                         "decode-cycle spans equal scheduler cycles")
    ap.add_argument("--profile-dir", default="",
                    help="also capture a jax.profiler trace of the obs "
                         "serving run into this directory")
    args = ap.parse_args()
    if args.obs or args.gate_obs:
        run_obs(quick=args.quick, gate=args.gate_obs,
                profile_dir=args.profile_dir)
    elif args.speculative or args.gate_spec:
        run_speculative(quick=args.quick, gate=args.gate_spec)
    elif args.prefix_reuse or args.gate_paging:
        run_prefix_reuse(quick=args.quick, gate=args.gate_paging,
                         backend_name=args.backend)
    elif args.serving_only or args.gate > 0 or args.gate_multistep:
        run_serving(quick=args.quick, gate=args.gate,
                    gate_multistep=args.gate_multistep)
    else:
        run(quick=args.quick)
