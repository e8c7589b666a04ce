"""Speculative decoding over COW block forks: drafter units, greedy
acceptance, zero-copy fork commit/rollback, scheduler parity with the
autoregressive paged path (stop tokens and mixed samplers included),
self-draft full acceptance, the rejected-draft radix guard, and
``SchedulerStats`` serialization round-trips."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import (InferenceSession, ModelDrafter, NgramDrafter,
                           PagedKVCache, SamplerConfig, Scheduler,
                           SchedulerStats, ServeRequest, SpeculativeConfig,
                           create_backend)
from repro.serving.spec import greedy_accept


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=3)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _prompts(model, n, lens=(9, 4, 13, 6, 7, 5)):
    rng = np.random.default_rng(11)
    return [rng.integers(0, model.cfg.vocab_size,
                         size=(1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


def _run_sched(model, params, reqs, *, num_slots=3, speculative=None,
               max_len=96):
    be = create_backend("model", model, params, batch=1, max_len=max_len)
    sched = Scheduler(InferenceSession(be), num_slots=num_slots,
                      kv_layout="paged", prefill_chunk=8,
                      speculative=speculative)
    ids = [sched.submit(r) for r in reqs]
    res = sched.run()
    return [res[i] for i in ids], sched.last_stats


# ---------------------------------------------------------------------------
# drafters + acceptance rule
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_n=3, min_n=1)
    # ... 5 6 7 8 ... 5 6 7 -> the 3-gram repeats; propose what followed
    seq = np.array([1, 2, 5, 6, 7, 8, 9, 3, 5, 6, 7], np.int32)
    np.testing.assert_array_equal(d.propose(0, seq, 2), [8, 9])
    # k caps the proposal length
    np.testing.assert_array_equal(d.propose(0, seq, 4), [8, 9, 3, 5])
    # most RECENT earlier occurrence wins
    seq2 = np.array([4, 1, 4, 2, 4], np.int32)
    np.testing.assert_array_equal(d.propose(0, seq2, 1), [2])
    # no repeated suffix -> empty proposal (cycle degrades to plain decode)
    assert d.propose(0, np.array([1, 2, 3, 4], np.int32), 4).size == 0
    # single-token sequence has no earlier context at all
    assert d.propose(0, np.array([7], np.int32), 4).size == 0


def test_greedy_accept_prefix_rule():
    assert greedy_accept([5, 6, 7], [5, 6, 7]) == 3
    assert greedy_accept([5, 6, 7], [5, 6, 9]) == 2
    assert greedy_accept([5, 6, 7], [1, 6, 7]) == 0
    assert greedy_accept([], [4]) == 0


def test_speculative_config_validation():
    with pytest.raises(ValueError, match="k must be"):
        SpeculativeConfig(k=0)
    with pytest.raises(ValueError, match="min_n"):
        SpeculativeConfig(min_n=3, max_n=2)
    with pytest.raises(ValueError, match="unknown drafter"):
        SpeculativeConfig(drafter="oracle")


# ---------------------------------------------------------------------------
# COW fork commit / rollback: zero KV copies
# ---------------------------------------------------------------------------

def test_fork_commit_and_rollback_zero_copies(setup):
    model, _ = setup
    pg = PagedKVCache(model.cfg, num_slots=1, max_len=32, block_size=4,
                      num_blocks=12)
    s = pg.allocate()
    pg.ensure_writable(s, 0, 6)          # "prefilled" through position 5
    pg.pos[s] = 6
    owned0 = list(pg._owned[s])
    forks0, free0 = pg.pool.cow_forks, pg.pool.num_free

    # speculate 5 tokens across a block boundary, then reject everything
    f = pg.fork_slot(s)
    pg.ensure_writable(s, 6, 11)          # claims block 2 for positions 8..11
    assert len(pg._owned[s]) == len(owned0) + 1
    pg.drop_fork(s, f)
    assert int(pg.pos[s]) == 6
    assert pg._owned[s] == owned0         # fork block returned
    assert pg.pool.num_free == free0
    assert pg.pool.cow_forks == forks0    # rollback made ZERO KV copies

    # speculate again, accept 3 of 5: pos jumps, needed block is kept
    f = pg.fork_slot(s)
    pg.ensure_writable(s, 6, 11)
    pg.commit_fork(s, f, 9)
    assert int(pg.pos[s]) == 9
    assert len(pg._owned[s]) == len(owned0) + 1   # block 2 covers pos 8
    assert pg.pool.cow_forks == forks0    # commit made ZERO KV copies too

    # accept only 2 more: the speculative block past pos is trimmed
    f = pg.fork_slot(s)
    pg.ensure_writable(s, 9, 14)          # claims block 3
    pg.commit_fork(s, f, 11)              # keep through block 2 only
    assert len(pg._owned[s]) == len(owned0) + 1
    assert pg.pool.cow_forks == forks0

    pg.free(s)
    assert pg.pool.num_live == 1          # only the trash block


def test_fork_rollback_keeps_cow_replacements(setup):
    """A COW fork triggered mid-speculation replaces a SHARED block with a
    private copy; rollback keeps the copy (content-identical) and never
    un-forks it."""
    model, _ = setup
    pg = PagedKVCache(model.cfg, num_slots=2, max_len=16, block_size=4,
                      num_blocks=12)
    a = pg.allocate()
    pg.ensure_writable(a, 0, 4)
    pg.pos[a] = 4
    # share slot a's block with slot b (radix-adoption stand-in)
    b = pg.allocate()
    shared = int(pg.table[a, 0])
    pg.adopt_prefix(b, 3, [shared])       # partial: COW immediately
    f = pg.fork_slot(b)
    copies = pg.ensure_writable(b, 3, 6)  # tail block private already; next fresh
    pg.drop_fork(b, f)
    assert int(pg.pos[b]) == 3
    assert pg.pool.refcount[shared] == 1  # b holds only its private copy
    assert copies == 0
    pg.free(a), pg.free(b)
    assert pg.pool.num_live == 1


def test_fork_validation(setup):
    model, _ = setup
    pg = PagedKVCache(model.cfg, num_slots=2, max_len=16, block_size=4)
    s = pg.allocate()
    f = pg.fork_slot(s)
    with pytest.raises(RuntimeError, match="belongs to slot"):
        pg.commit_fork(1 - s, f, 0)
    with pytest.raises(RuntimeError, match="rewinds past"):
        pg.pos[s] = 4
        pg.commit_fork(s, pg.fork_slot(s), 2)
    with pytest.raises(RuntimeError, match="unallocated"):
        pg.fork_slot(1 - s)


# ---------------------------------------------------------------------------
# scheduler integration: exact greedy parity + amortization
# ---------------------------------------------------------------------------

def test_speculative_greedy_parity_and_fewer_dispatches(setup):
    model, params = setup
    def reqs():
        return [ServeRequest(prompt=p, max_new_tokens=24)
                for p in _prompts(model, 3)]
    ref, st_ar = _run_sched(model, params, reqs())
    out, st_sp = _run_sched(model, params, reqs(), speculative="ngram")
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(r.tokens, o.tokens)
    assert st_sp.speculative == "ngram"
    assert st_sp.spec_cycles == st_sp.verify_dispatches > 0
    assert st_sp.draft_tokens_accepted > 0
    assert 0.0 < st_sp.acceptance_rate <= 1.0
    # the tentpole claim: more tokens per target dispatch than AR decode
    assert st_sp.dispatches_per_accepted_token < st_ar.dispatches_per_token
    # verify cycles emit at least 1 token each, so cycles shrank too
    assert st_sp.cycles < st_ar.cycles


def test_speculative_rollback_never_copies_blocks(setup):
    """Rejected speculative branches are dropped by pure bookkeeping: the
    run's COW copy counters stay exactly where normal decode would put
    them (zero here — no prefix sharing in play)."""
    model, params = setup
    out, st = _run_sched(
        model, params,
        [ServeRequest(prompt=p, max_new_tokens=20)
         for p in _prompts(model, 2)],
        num_slots=2, speculative=SpeculativeConfig(drafter="ngram", k=3))
    assert st.draft_tokens_proposed > st.draft_tokens_accepted  # rejections
    assert st.cow_copies == 0


def test_speculative_stop_token_truncates_span(setup):
    """A stop token accepted mid-span ends the request at exactly the same
    token as the autoregressive path — later accepted drafts and the
    bonus token are discarded."""
    model, params = setup
    p = _prompts(model, 1)[0]
    ref, _ = _run_sched(model, params,
                        [ServeRequest(prompt=p, max_new_tokens=24)])
    stop = int(ref[0].tokens[0, 10])      # a token AR emits mid-stream
    def req():
        return [ServeRequest(prompt=p, max_new_tokens=24,
                             stop_tokens=(stop,))]
    r_ar, _ = _run_sched(model, params, req())
    r_sp, _ = _run_sched(model, params, req(), speculative="ngram")
    assert r_ar[0].finish_reason == "stop"
    assert r_sp[0].finish_reason == "stop"
    assert r_sp[0].n_new == r_ar[0].n_new
    np.testing.assert_array_equal(r_ar[0].tokens, r_sp[0].tokens)


def test_speculative_mixed_sampler_batch(setup):
    """Non-greedy slots ride the verify dispatch as plain decodes (column
    0 logits are bit-identical to decode logits), so a temperature slot's
    stream matches the non-speculative run seed-for-seed."""
    model, params = setup
    ps = _prompts(model, 2)
    def reqs():
        return [ServeRequest(prompt=ps[0], max_new_tokens=16),
                ServeRequest(prompt=ps[1], max_new_tokens=16, seed=3,
                             sampler=SamplerConfig(kind="temperature",
                                                   temperature=0.8))]
    ref, _ = _run_sched(model, params, reqs(), num_slots=2)
    out, st = _run_sched(model, params, reqs(), num_slots=2,
                         speculative="ngram")
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(r.tokens, o.tokens)
    assert st.spec_cycles > 0


def test_model_drafter_self_draft_accepts_everything(setup):
    """Draft model == target model ⇒ every draft is the target's own
    argmax ⇒ acceptance rate exactly 1.0 and max-width spans."""
    model, params = setup
    drafter = ModelDrafter(create_backend("model", model, params, batch=1,
                                          max_len=128))
    ref, _ = _run_sched(model, params,
                        [ServeRequest(prompt=p, max_new_tokens=16)
                         for p in _prompts(model, 2)], num_slots=2)
    out, st = _run_sched(model, params,
                         [ServeRequest(prompt=p, max_new_tokens=16)
                          for p in _prompts(model, 2)], num_slots=2,
                         speculative=SpeculativeConfig(drafter=drafter, k=4))
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(r.tokens, o.tokens)
    assert st.acceptance_rate == 1.0
    assert st.draft_dispatches > 0        # drafter work is accounted
    assert st.speculative == "ModelDrafter"


def test_speculative_requires_paged_and_capability(setup):
    model, params = setup
    be = create_backend("model", model, params, batch=1, max_len=64)
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        Scheduler(InferenceSession(be), speculative="ngram")
    with pytest.raises(ValueError, match="drafter name"):
        Scheduler(InferenceSession(be), kv_layout="paged", speculative=3.5)
    # graph backends serve paged but have no batched verify executable
    gbe = create_backend("F3", model, params, batch=1, max_len=64)
    sched = Scheduler(InferenceSession(gbe), kv_layout="paged",
                      speculative="ngram")
    sched.submit(ServeRequest(prompt=_prompts(model, 1)[0],
                              max_new_tokens=4))
    with pytest.raises(ValueError, match="no speculative verify"):
        sched.run()


# ---------------------------------------------------------------------------
# rejected drafts never reach the radix cache (release-time guard)
# ---------------------------------------------------------------------------

def test_rejected_draft_tokens_never_radix_cached(setup):
    model, params = setup
    be = create_backend("model", model, params, batch=1, max_len=96)
    sched = Scheduler(InferenceSession(be), num_slots=3, kv_layout="paged",
                      prefill_chunk=8, block_size=4, speculative="ngram")
    ps = _prompts(model, 3)
    rids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=24))
            for p in ps]
    res = sched.run()
    st = sched.last_stats
    assert st.draft_tokens_proposed > st.draft_tokens_accepted  # rejections
    radix = sched._bstate["radix"]
    bs = sched.config.block_size
    for p, rid in zip(ps, rids):
        realized = np.concatenate([p[0],
                                   res[rid].tokens[0]]).astype(np.int32)
        # the realized chain is cached (minus the sampling-boundary token)...
        matched, _ = radix.match(realized)
        assert matched == (len(realized) - 1) // bs * bs
        # ...but extending it with any non-realized continuation (as every
        # rejected draft is) matches NOTHING past the realized span:
        # rejected drafts are not keys in the trie
        for fake in (7, 13, 1001):
            poisoned = np.concatenate(
                [realized[:-1], [fake] * bs]).astype(np.int32)
            m2, _ = radix.match(poisoned)
            assert m2 <= matched


def test_release_guard_caps_at_realized_length(setup):
    """Direct unit for the `_release_paged` guard: a slot whose pos sits
    PAST the realized stream (an open speculative fork at release time)
    only ever caches realized tokens."""
    model, params = setup
    be = create_backend("model", model, params, batch=1, max_len=64)
    bstate = be.alloc_slots_paged(1, block_size=4, spec_slack=5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, model.cfg.vocab_size, size=(1, 8)).astype(np.int32)
    be.admit_paged(bstate, 0, prompt)
    while be.prefill_paged_chunk(bstate, 0) is None:
        pass
    pg = bstate["paged"]
    pg.ensure_writable(0, 8, 12)
    pg.pos[0] = 12                        # 4 unverified speculative writes
    be.release_slot(bstate, 0, tokens=prompt[0])
    matched, _ = bstate["radix"].match(
        np.concatenate([prompt[0], [9, 9, 9, 9]]).astype(np.int32))
    assert matched <= 8                   # nothing past the realized prompt


# ---------------------------------------------------------------------------
# SchedulerStats serialization (satellite)
# ---------------------------------------------------------------------------

def test_scheduler_stats_roundtrip_and_zero_edges():
    st = SchedulerStats()
    # zero-token edges: every derived metric defined, no ZeroDivisionError
    assert st.dispatches_per_token == 0.0
    assert st.acceptance_rate == 0.0
    assert st.dispatches_per_accepted_token == 0.0
    assert st.prefix_hit_rate == 0.0

    st = SchedulerStats(num_slots=3, kv_layout="paged", cycles=10,
                        admitted=4, completed=4, tokens=40, dispatches=12,
                        occupancy_sum=25, wall_s=0.5,
                        queue_waits_s=[0.01, 0.02], prefill_chunks=6,
                        prefix_hits=1, prefix_hit_tokens=8, prompt_tokens=30,
                        cow_copies=2, evictions=1, speculative="ngram",
                        spec_cycles=9, verify_dispatches=9,
                        draft_dispatches=0, draft_tokens_proposed=20,
                        draft_tokens_accepted=15, bonus_tokens=9,
                        spec_tokens=36)
    d = st.to_dict()
    # every dataclass field serialized, derived metrics included
    for f in dataclasses.fields(SchedulerStats):
        assert f.name in d
    assert d["acceptance_rate"] == st.acceptance_rate == 0.75
    assert d["dispatches_per_accepted_token"] == 9 / 36
    assert d["dispatches_per_token"] == st.dispatches_per_token
    back = SchedulerStats.from_dict(d)
    assert back == st                     # derived keys ignored, fields exact
    assert back.to_dict() == d
