"""Traffic harness + SLO-aware preemption: arrival-generator determinism
and rate, workload synthesis invariants, swap-out/swap-in block-chain
integrity (refcounts, radix nodes, byte-exact arena restore), and greedy
parity across preempt→swap/recompute→resume cycles on the paged model
backend — the oversubscription machinery ``bench_traffic.py`` rides on."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import (InferenceSession, PoissonArrivals, ReplayArrivals,
                           Scheduler, ServeRequest, create_backend,
                           synthesize_workload)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _prompts(model, n, lens=(12, 9, 15, 7, 11, 6)):
    rng = np.random.default_rng(11)
    return [rng.integers(0, model.cfg.vocab_size,
                         size=(1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# arrival generators: determinism + empirical rate
# ---------------------------------------------------------------------------

def test_poisson_arrivals_seed_reproducible():
    a = PoissonArrivals(20.0, seed=3).times(50)
    b = PoissonArrivals(20.0, seed=3).times(50)
    c = PoissonArrivals(20.0, seed=4).times(50)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0)          # strictly increasing offsets


def test_poisson_arrivals_empirical_rate():
    # 4000 samples: the empirical rate of a seeded draw sits well within
    # 10% of the target (deterministic, but the tolerance keeps the test
    # honest across RNG implementations)
    rate = 50.0
    t = PoissonArrivals(rate, seed=0).times(4000)
    empirical = len(t) / t[-1]
    assert abs(empirical - rate) / rate < 0.10


def test_poisson_arrivals_validation():
    with pytest.raises(ValueError, match="rate_rps"):
        PoissonArrivals(0.0)


def test_replay_arrivals_scale_and_bounds():
    base = [0.0, 0.1, 0.3, 0.7]
    r = ReplayArrivals(base, scale=0.5)           # 2× the recorded rate
    np.testing.assert_allclose(r.times(4), [0.0, 0.05, 0.15, 0.35])
    np.testing.assert_allclose(r.times(2), [0.0, 0.05])
    with pytest.raises(ValueError, match="4 arrivals"):
        r.times(5)
    with pytest.raises(ValueError, match="non-decreasing"):
        ReplayArrivals([0.0, 0.2, 0.1])
    with pytest.raises(ValueError, match="scale"):
        ReplayArrivals(base, scale=0.0)


def test_synthesize_workload_deterministic_and_shaped():
    kw = dict(vocab_size=1000, prompt_lens=(12, 20), output_lens=(4, 9),
              num_tenants=3, shared_prefix_len=8,
              priorities=((0, 0.7), (1, 0.3)), slo_ttft_ms=50.0, seed=2)
    w1 = synthesize_workload(30, PoissonArrivals(10.0, seed=1), **kw)
    w2 = synthesize_workload(30, PoissonArrivals(10.0, seed=1), **kw)
    assert len(w1) == 30
    prefixes = {}
    for a, b in zip(w1, w2):
        assert a.at_s == b.at_s and a.tenant == b.tenant
        np.testing.assert_array_equal(a.request.prompt, b.request.prompt)
        assert a.request.priority == b.request.priority
        assert 12 <= a.request.prompt.shape[1] <= 20
        assert 4 <= a.request.max_new_tokens <= 9
        assert a.request.slo_ttft_ms == 50.0
        # every request opens with its tenant's shared prefix
        head = a.request.prompt[0, :8].tobytes()
        assert prefixes.setdefault(a.tenant, head) == head
    assert len(prefixes) > 1                       # multi-tenant mix
    assert {tr.request.priority for tr in w1} == {0, 1}


# ---------------------------------------------------------------------------
# swap-out / swap-in: refcounts, radix nodes, byte-exact arena restore
# ---------------------------------------------------------------------------

def _prefill_all(backend, bs, slot):
    out = None
    while out is None:
        out = backend.prefill_paged_chunk(bs, slot)
    return out


def test_swap_roundtrip_block_chain_integrity(setup):
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=96)
    assert backend.capabilities.preemption
    bs = backend.alloc_slots_paged(3, block_size=8, prefill_chunk=16)
    pg, pool, radix = bs["paged"], bs["paged"].pool, bs["radix"]
    rng = np.random.default_rng(0)
    shared = rng.integers(0, model.cfg.vocab_size, size=24)
    p0 = np.concatenate([shared, rng.integers(0, model.cfg.vocab_size,
                                              size=9)]).astype(np.int32)
    p1 = np.concatenate([shared, rng.integers(0, model.cfg.vocab_size,
                                              size=5)]).astype(np.int32)
    backend.admit_paged(bs, 0, p0)
    _prefill_all(backend, bs, 0)              # inserts p0's prefix in radix
    info = backend.admit_paged(bs, 1, p1)
    assert info.cached >= 24                  # slot 1 adopts shared blocks
    _prefill_all(backend, bs, 1)

    pos0 = int(pg.pos[0])
    chain0 = pg.chain(0, pos0)
    ref_counts = {b: pool.refcount[b] for b in chain0}
    ref_k = np.asarray(pool.arena_k)[chain0].copy()
    ref_v = np.asarray(pool.arena_v)[chain0].copy()
    free0 = pool.num_free

    swap = backend.swap_out_paged(bs, 0)
    chain = swap["chain"]
    assert chain.pos == pos0
    assert len(chain.retained) + len(chain.host) == len(chain0)
    # shared blocks park by REFERENCE: refcount unchanged, zero host bytes
    for bid in chain.retained.values():
        assert pool.refcount[bid] == ref_counts[bid]
    # exclusive blocks were freed — that is the capacity preemption buys
    # (≥: the never-read chunk-slack block past ``pos`` frees too)
    assert len(chain.host) > 0
    assert pool.num_free >= free0 + len(chain.host)
    free_swapped = pool.num_free
    assert chain.host_bytes > 0
    # slot 1 (the radix sharer) is untouched and still decodable
    assert int(pg.pos[1]) > 0

    slot = backend.swap_in_paged(bs, swap, 0)
    assert slot == 0 and int(pg.pos[0]) == pos0
    new_chain = pg.chain(0, pos0)
    np.testing.assert_array_equal(
        ref_k, np.asarray(pool.arena_k)[new_chain])
    np.testing.assert_array_equal(
        ref_v, np.asarray(pool.arena_v)[new_chain])
    for bid in (set(new_chain) & set(ref_counts)):
        assert pool.refcount[bid] == ref_counts[bid]
    # restore claims exactly one fresh block per host-copied block
    assert pool.num_free == free_swapped - len(chain.host)
    assert bs["meta"][0]["prompt"] is not None   # meta restored with slot


def test_drop_swap_releases_retained_references(setup):
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=96)
    bs = backend.alloc_slots_paged(2, block_size=8, prefill_chunk=16)
    pool = bs["paged"].pool
    rng = np.random.default_rng(1)
    shared = rng.integers(0, model.cfg.vocab_size, size=16)
    p = np.concatenate([shared, rng.integers(0, model.cfg.vocab_size,
                                             size=7)]).astype(np.int32)
    backend.admit_paged(bs, 0, p)
    _prefill_all(backend, bs, 0)
    backend.admit_paged(bs, 1, p)             # radix hit → shared refs
    _prefill_all(backend, bs, 1)
    free0 = pool.num_free
    swap = backend.swap_out_paged(bs, 0)
    free_swapped = pool.num_free
    assert free_swapped > free0               # exclusive + slack blocks freed
    counts = {b: pool.refcount[b] for b in swap["chain"].retained.values()}
    bs["paged"].drop_swap(swap["chain"])      # request cancelled mid-swap
    # retained references drop at drop_swap; the radix tree keeps those
    # blocks live (refcount decremented, not freed), host copies are gone
    assert pool.num_free == free_swapped + sum(
        1 for b, c in counts.items() if c == 1)
    for b, c in counts.items():
        if c > 1:
            assert pool.refcount[b] == c - 1
    assert not swap["chain"].retained and not swap["chain"].host


def test_graph_layout_swap_unsupported(setup):
    model, params = setup
    backend = create_backend("F3", model, params, batch=1, max_len=64)
    assert not backend.capabilities.preemption
    bs = backend.alloc_slots_paged(1, block_size=8)
    with pytest.raises(NotImplementedError, match="preemption"):
        backend.swap_out_paged(bs, 0)


# ---------------------------------------------------------------------------
# scheduler: preempt → swap/recompute → resume greedy parity + accounting
# ---------------------------------------------------------------------------

def _traffic_reqs(prompts, tokens, hi_idx):
    reqs = []
    for i, p in enumerate(prompts):
        reqs.append(ServeRequest(
            prompt=p, max_new_tokens=tokens, seed=i, request_id=f"t{i}",
            priority=2 if i == hi_idx else 0, slo_ttft_ms=5000.0))
    return reqs


@pytest.mark.parametrize("mode", ["swap", "recompute", "auto"])
def test_preemption_parity_and_counters(setup, mode):
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=128)
    session = InferenceSession(backend)
    prompts = _prompts(model, 4)
    tokens = 10
    ref = {}
    for i, p in enumerate(prompts):
        ref[f"t{i}"] = session.run(
            ServeRequest(prompt=p, max_new_tokens=tokens)).tokens

    sched = Scheduler(session, num_slots=2, kv_layout="paged",
                      prefill_chunk=8, block_size=8, preemption=mode)
    reqs = _traffic_reqs(prompts, tokens, hi_idx=3)

    def arrive(i, _toks):
        # the high-priority request lands while both slots decode
        # low-priority work: t1's second token comes from a decode step,
        # and t0 was admitted and prefilled before it
        if i == 1:
            sched.submit(reqs[3])
    reqs[1] = dataclasses.replace(reqs[1], stream=arrive)
    for r in reqs[:3]:
        sched.submit(r)
    results = sched.run()
    st = sched.last_stats

    assert len(results) == 4
    for rid, tokens_ref in ref.items():
        np.testing.assert_array_equal(results[rid].tokens, tokens_ref)
    assert st.preemptions >= 1
    assert st.preemptions == st.preempt_swaps + st.preempt_recomputes
    if mode == "swap":
        assert st.preempt_swaps == st.preemptions
        assert st.swap_ins == st.preempt_swaps
    if mode == "recompute":
        assert st.preempt_recomputes == st.preemptions
        assert st.swap_ins == 0
    # SLO accounting: every request declared a (generous) TTFT objective
    assert st.slo_requests == 4
    assert st.slo_met == 4
    assert st.goodput_tokens == st.tokens
    assert st.slo_attainment == 1.0


def test_preemption_requires_paged_layout(setup):
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    with pytest.raises(ValueError, match="paged"):
        Scheduler(InferenceSession(backend), num_slots=1, preemption="auto")
    with pytest.raises(ValueError, match="unknown preemption"):
        Scheduler(InferenceSession(backend), num_slots=1,
                  kv_layout="paged", preemption="yes")


def test_priority_admission_order(setup):
    """Queued high-priority requests admit before earlier low-priority
    ones; FIFO within a class (asserted through completion identity —
    with one slot and no preemption, admission order IS service order)."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    session = InferenceSession(backend)
    prompts = _prompts(model, 3)
    order = []
    sched = Scheduler(session, num_slots=1, kv_layout="paged",
                      prefill_chunk=8, block_size=8)
    for i, pri in enumerate((0, 0, 5)):
        sched.submit(ServeRequest(
            prompt=prompts[i], max_new_tokens=3, request_id=f"o{i}",
            priority=pri,
            stream=lambda step, toks, i=i: order.append(i)
            if step == 0 else None))
    sched.run()
    assert order == [2, 0, 1]


def test_submit_at_open_loop_queue_wait(setup):
    """Open-loop arrivals enter at their scheduled instant; queue_wait is
    charged from the SCHEDULED arrival, not the submit_at call."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    session = InferenceSession(backend)
    prompts = _prompts(model, 2)
    # warmup: compile the paged prefill/decode executables so the timed
    # open-loop pass below measures scheduling, not XLA compilation
    warm = Scheduler(session, num_slots=1, kv_layout="paged",
                     prefill_chunk=8, block_size=8)
    for p in prompts:
        warm.submit(ServeRequest(prompt=p, max_new_tokens=2))
    warm.run()
    sched = Scheduler(session, num_slots=1, kv_layout="paged",
                      prefill_chunk=8, block_size=8)
    t0 = time.perf_counter()
    sched.submit_at(ServeRequest(prompt=prompts[0], max_new_tokens=2,
                                 request_id="a0"), t0 + 0.02)
    sched.submit_at(ServeRequest(prompt=prompts[1], max_new_tokens=2,
                                 request_id="a1"), t0 + 0.06)
    results = sched.run()
    assert len(results) == 2
    st = sched.last_stats
    assert st.admitted == 2
    # an idle 1-slot server admits each arrival promptly: the wait charged
    # from the scheduled instant stays far below the 40 ms arrival gap
    assert all(w < 0.04 for w in st.queue_waits_s)
    assert time.perf_counter() - t0 >= 0.06     # really waited for arrival 2
