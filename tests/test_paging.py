"""Paged KV-cache subsystem: BlockPool alloc/free/refcount/COW invariants,
radix insert/match/evict (partial-block prefix splits included), paged-vs-
dense greedy parity, chunked-prefill parity, prefix-cache hits skipping the
shared span, eviction under pressure, memory accounting, async readback,
and the paged decode-graph variant."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.bench import BENCH_05B
from repro.core.graphs import LEVELS, build_decode_graph
from repro.core.opgraph import run_graph_pure
from repro.models import build_model, transformer
from repro.serving import (BlockPool, InferenceSession, PagedKVCache,
                           RadixPrefixCache, Scheduler, ServeRequest,
                           SlotKVCache, create_backend)
from repro.serving.backends.base import device_snapshot
from repro.serving.paging import (arena_shape, decode_step_paged,
                                  extend_step_paged, verify_step_paged)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=3)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def bench_setup():
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _prompts(model, n, lens=(9, 4, 13, 6, 7, 5)):
    rng = np.random.default_rng(11)
    return [rng.integers(0, model.cfg.vocab_size,
                         size=(1, lens[i % len(lens)])).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# BlockPool: alloc / free / refcount / COW
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_refcount(setup):
    model, _ = setup
    pool = BlockPool(model.cfg, 4, block_size=4)
    b0, b1 = pool.alloc(), pool.alloc()
    assert (b0, b1) == (0, 1) and pool.num_free == 2
    pool.incref(b0)
    assert not pool.decref(b0)           # still referenced
    assert pool.decref(b0)               # now freed
    assert pool.num_free == 3
    with pytest.raises(RuntimeError, match="decref on free"):
        pool.decref(b0)
    with pytest.raises(RuntimeError, match="incref on free"):
        pool.incref(b0)
    assert pool.alloc() == b0            # lowest free id reused
    pool.alloc(), pool.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc()
    assert pool.bytes_allocated == 4 * pool.block_bytes
    assert pool.bytes_live == 4 * pool.block_bytes


def test_block_pool_cow_forks_shared_blocks(setup):
    model, _ = setup
    pool = BlockPool(model.cfg, 4, block_size=4)
    bid = pool.alloc()
    pool.arena_k = pool.arena_k.at[bid].set(7.0)
    pool.arena_v = pool.arena_v.at[bid].set(9.0)
    # exclusive block: cow is a no-op
    same, copied = pool.cow(bid)
    assert same == bid and not copied
    # shared block: cow forks, content matches, source untouched
    pool.incref(bid)
    nb, copied = pool.cow(bid)
    assert copied and nb != bid and pool.cow_forks == 1
    np.testing.assert_array_equal(np.asarray(pool.arena_k[nb]),
                                  np.asarray(pool.arena_k[bid]))
    np.testing.assert_array_equal(np.asarray(pool.arena_v[nb]),
                                  np.asarray(pool.arena_v[bid]))
    assert pool.refcount[nb] == 1 and pool.refcount[bid] == 2


# ---------------------------------------------------------------------------
# RadixPrefixCache: insert / match / split / evict
# ---------------------------------------------------------------------------

def _pool_with_blocks(model, n, bs=4):
    pool = BlockPool(model.cfg, n, block_size=bs)
    return pool, [pool.alloc() for _ in range(n)]


def test_radix_insert_match_shared_prefix(setup):
    model, _ = setup
    pool, bids = _pool_with_blocks(model, 8)
    radix = RadixPrefixCache(pool, block_size=4)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32)       # blocks 0,1
    b = np.array([1, 2, 3, 4, 9, 9, 9, 9], np.int32)       # shares block 0
    radix.insert(a, bids[:2])
    radix.insert(b, [bids[0], bids[2]])
    m, chain = radix.match(a)
    assert m == 8 and chain == bids[:2]
    m, chain = radix.match(b)
    assert m == 8 and chain == [bids[0], bids[2]]
    m, chain = radix.match([1, 2, 3, 4, 5, 5])             # diverges at 4
    assert m == 5 and chain == bids[:2]                    # partial block 1
    m, chain = radix.match([2, 2, 2])
    assert m == 0 and chain == []
    # each new node holds a ref per chain block: block 0 is in 3 chains
    # (split parent + two leaves), block 1 and 2 in one leaf each
    assert pool.refcount[bids[0]] == 1 + 3
    assert pool.refcount[bids[1]] == 1 + 1


def test_radix_partial_block_split_and_cow_adoption(setup):
    """Prompts diverging mid-block: the match is token-granular, full
    blocks are shared by reference, and the boundary block is COW-forked
    into the adopting slot."""
    model, _ = setup
    pg = PagedKVCache(model.cfg, 2, max_len=16, block_size=4, num_blocks=12)
    radix = RadixPrefixCache(pg.pool, block_size=4)
    pg.radix = radix
    s0 = pg.allocate()
    pg.ensure_writable(s0, 0, 8)
    donor = pg.chain(s0, 8)
    pg.pool.arena_k = pg.pool.arena_k.at[donor[1]].set(3.25)
    radix.insert(np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32), donor)

    s1 = pg.allocate()
    matched, chain = radix.match(np.array([1, 2, 3, 4, 5, 6, 9], np.int32))
    assert matched == 6                 # mid-block 1
    copies = pg.adopt_prefix(s1, matched, chain)
    assert copies == 1 and pg.cow_copies == 1
    assert pg.pos[s1] == 6
    t1 = pg.chain(s1, 8)
    assert t1[0] == donor[0]            # full block shared by reference
    assert t1[1] != donor[1]            # boundary block privately forked
    np.testing.assert_array_equal(
        np.asarray(pg.pool.arena_k[t1[1]]),
        np.asarray(pg.pool.arena_k[donor[1]]))
    # writing through s1's fork never touches the donor
    pg.ensure_writable(s1, 6, 8)
    assert pg.chain(s1, 8)[1] == t1[1]  # already exclusive — no new fork


def test_radix_lru_eviction_frees_leaf_chains_only(setup):
    model, _ = setup
    pool, bids = _pool_with_blocks(model, 6, bs=4)
    radix = RadixPrefixCache(pool, block_size=4)
    radix.insert(np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32), bids[:2])
    radix.insert(np.array([1, 2, 3, 4, 6, 6, 6, 6], np.int32),
                 [bids[0], bids[2]])
    radix.match(np.array([1, 2, 3, 4, 6, 6, 6, 6], np.int32))  # touch 2nd
    for b in bids:                       # drop OUR refs; cache refs remain
        pool.decref(b)
    free0 = pool.num_free
    assert radix.evict_one()             # LRU leaf = the FIRST insert
    assert pool.num_free == free0 + 1    # block 1 freed; block 0 shared
    assert pool.refcount[bids[0]] > 0
    m, _ = radix.match(np.array([1, 2, 3, 4, 6, 6, 6, 6], np.int32))
    assert m == 8                        # survivor chain intact
    while radix.evict_one():
        pass
    assert pool.num_free == pool.num_blocks
    assert radix.num_nodes == 0


# ---------------------------------------------------------------------------
# end-to-end: paged vs dense greedy parity, chunked prefill, prefix hits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["model", "ondevice"])
def test_paged_matches_dense_greedy(setup, mode):
    """Paged + chunked-prefill + radix scheduling produces byte-identical
    greedy streams to independent dense runs, including slot reuse."""
    model, params = setup
    backend = create_backend(mode, model, params, batch=1, max_len=32)
    session = InferenceSession(backend)
    prompts = _prompts(model, 6)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=5)).tokens
            for p in prompts]
    sched = Scheduler(session, num_slots=2, kv_layout="paged",
                      prefill_chunk=4, block_size=4)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=5,
                                     request_id=f"pg{i}"))
           for i, p in enumerate(prompts)]
    results = sched.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(results[rid].tokens, refs[i])
    st = sched.last_stats
    assert st.admitted == 6 and st.completed == 6
    assert st.kv_layout == "paged"
    assert st.prefill_chunks >= 6        # chunked: ≥1 extend per admission
    assert st.mean_occupancy > 1.0       # decode genuinely overlapped


def test_chunked_prefill_matches_whole_prompt(bench_setup):
    """Chunk-by-chunk prefill (chunk ∤ prompt included) emits the same
    stream as whole-prompt prefill on the bench config."""
    model, params = bench_setup
    backend = create_backend("model", model, params, batch=1, max_len=40)
    session = InferenceSession(backend)
    prompt = np.arange(1, 14, dtype=np.int32).reshape(1, -1)  # plen=13
    ref = session.run(ServeRequest(prompt=prompt, max_new_tokens=6)).tokens
    for chunk in (3, 5, None):           # None = single extend call
        sched = Scheduler(session, num_slots=1, kv_layout="paged",
                          prefill_chunk=chunk, block_size=8,
                          prefix_cache=False)
        rid = sched.submit(ServeRequest(prompt=prompt, max_new_tokens=6))
        res = sched.run()[rid]
        np.testing.assert_array_equal(res.tokens, ref)
        expected = -(-13 // chunk) if chunk else 1
        assert sched.last_stats.prefill_chunks == expected


def test_prefix_cache_hit_skips_shared_span(setup):
    """A warm radix hit performs zero prefill work for the shared span:
    only the unique suffix (plus the mandatory final token) is extended."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    session = InferenceSession(backend)
    rng = np.random.default_rng(5)
    system = rng.integers(0, model.cfg.vocab_size, size=10)
    p1 = np.concatenate([system, [7, 8]]).astype(np.int32).reshape(1, -1)
    p2 = np.concatenate([system, [9, 3]]).astype(np.int32).reshape(1, -1)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=4)).tokens
            for p in (p1, p2)]
    sched = Scheduler(session, num_slots=1, kv_layout="paged",
                      prefill_chunk=4, block_size=4)
    for i, (p, ref) in enumerate(zip((p1, p2), refs)):
        rid = sched.submit(ServeRequest(prompt=p, max_new_tokens=4,
                                        request_id=f"hit{i}"))
        res = sched.run()[rid]
        np.testing.assert_array_equal(res.tokens, ref)
    st = sched.last_stats                # the WARM request's run
    assert st.prefix_hits == 1
    assert st.prefix_hit_tokens == 10    # the whole shared system prompt
    assert st.prefill_chunks == 1        # suffix-only: 2 tokens, 1 chunk
    # identical prompt again: match caps at plen-1, still one chunk
    rid = sched.submit(ServeRequest(prompt=p1, max_new_tokens=4,
                                    request_id="hit-full"))
    res = sched.run()[rid]
    np.testing.assert_array_equal(res.tokens, refs[0])
    assert sched.last_stats.prefix_hit_tokens == p1.shape[1] - 1


def test_eviction_under_pressure_preserves_active_slots(setup):
    """A pool too small to cache everything evicts LRU chains to admit new
    requests — while an ACTIVE slot mid-decode keeps its blocks and its
    exact token stream."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=24)
    session = InferenceSession(backend)
    prompts = _prompts(model, 5, lens=(11, 12, 10, 13, 9))
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=6)).tokens
            for p in prompts]
    # 2 slots × width 6 + 1 trash + 1 spare: caching every distinct prompt
    # chain is impossible, so admissions must evict
    sched = Scheduler(session, num_slots=2, kv_layout="paged",
                      prefill_chunk=4, block_size=4, num_blocks=13)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=6,
                                     request_id=f"ev{i}"))
           for i, p in enumerate(prompts)]
    results = sched.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(results[rid].tokens, refs[i])
    assert sched.last_stats.evictions > 0
    pg = sched._bstate["paged"]
    assert pg.occupancy == 0             # every slot released cleanly


def test_paged_requires_capability_and_continuous(setup):
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=16)
    # every dense-family backend now advertises paged_kv, so simulate a
    # backend without it (e.g. a non-batchable model family)
    backend.capabilities = dataclasses.replace(backend.capabilities,
                                               paged_kv=False)
    session = InferenceSession(backend)
    with pytest.raises(ValueError, match="paged KV requires"):
        Scheduler(session, kv_layout="paged", continuous=False)
    sched = Scheduler(session, kv_layout="paged")
    sched.submit(ServeRequest(prompt=np.array([[1, 2]], np.int32),
                              max_new_tokens=2))
    with pytest.raises(ValueError, match="no paged-KV support"):
        sched.run()


# ---------------------------------------------------------------------------
# memory accounting + async readback
# ---------------------------------------------------------------------------

def test_kv_bytes_accounting_both_layouts(setup):
    model, _ = setup
    cfg = model.cfg
    dense = SlotKVCache.for_model(cfg, 2, 16)
    assert dense.bytes_live == 0
    s = dense.allocate()
    dense.pos[s] = 8
    assert dense.bytes_live * 4 == dense.bytes_allocated  # 8 of 2×16 tokens
    paged = PagedKVCache(cfg, 2, max_len=16, block_size=4, num_blocks=8)
    base = paged.bytes_live              # the reserved trash block
    slot = paged.allocate()
    paged.ensure_writable(slot, 0, 8)    # two 4-token blocks
    assert paged.bytes_live - base == 2 * paged.pool.block_bytes
    assert paged.bytes_allocated == 9 * paged.pool.block_bytes
    paged.free(slot)
    assert paged.bytes_live == base      # blocks returned on release


def test_async_readback_parity_and_overlap(setup):
    """Deferred (double-buffered) readback changes timing only: identical
    streams, overlap cycles recorded; sync mode records none."""
    model, params = setup
    prompts = _prompts(model, 3)
    outs = {}
    for flag in (True, False):
        backend = create_backend("model", model, params, batch=1, max_len=32)
        sched = Scheduler(InferenceSession(backend), num_slots=3,
                          async_readback=flag)
        ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=8,
                                         request_id=f"as{flag}{i}"))
               for i, p in enumerate(prompts)]
        results = sched.run()
        outs[flag] = [results[rid].tokens for rid in ids]
        st = sched.last_stats
        if flag:
            assert st.overlap_cycles > 0
        else:
            assert st.overlap_cycles == 0
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_async_readback_defers_to_sync_on_stop_tokens(setup):
    """Stop tokens need every cycle's tokens before the next issue — the
    async path must stand down and stops must still bind exactly."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    session = InferenceSession(backend)
    p = _prompts(model, 1)[0]
    full = session.run(ServeRequest(prompt=p, max_new_tokens=8)).tokens
    stop = int(full[0, 3])
    first = int(np.argmax(full[0] == stop))   # tiny models repeat tokens
    sched = Scheduler(session, num_slots=2, async_readback=True)
    rid = sched.submit(ServeRequest(prompt=p, max_new_tokens=8,
                                    stop_tokens=(stop,)))
    res = sched.run()[rid]
    assert sched.last_stats.overlap_cycles == 0
    assert res.finish_reason == "stop"
    np.testing.assert_array_equal(res.tokens[0], full[0, :first + 1])


# ---------------------------------------------------------------------------
# paged decode graph (build_decode_graph(paged=True))
# ---------------------------------------------------------------------------

def test_paged_decode_graph_parity_and_dispatch_count(setup):
    """The block-table decode graph matches the dense slot-position graph
    op-for-op: same dispatch count, same next token, same cache writes."""
    model, params = setup
    cfg = model.cfg
    batch, max_len, bs = 2, 16, 4
    width = max_len // bs
    dense_g = build_decode_graph(params, cfg, batch=batch, max_len=max_len,
                                 slot_pos=True)
    paged_g = build_decode_graph(params, cfg, batch=batch, max_len=max_len,
                                 paged=True, block_size=bs)
    assert paged_g.meta["paged"] and paged_g.num_dispatches() == \
        dense_g.num_dispatches()

    rng = np.random.default_rng(0)
    pos = np.array([5, 9], np.int32)
    tokens = np.array([[3], [4]], np.int32)
    num_blocks = batch * width + 1
    dense_in = {"tokens": jnp.asarray(tokens), "pos": jnp.asarray(pos)}
    paged_in = dict(dense_in)
    # row b uses blocks [1+b*width, ...); block 0 is the trash block
    table = np.zeros((batch, width), np.int32)
    for b in range(batch):
        table[b] = 1 + b * width + np.arange(width)
    paged_in["block_table"] = jnp.asarray(table)
    for i in range(cfg.num_layers):
        hd = cfg.resolved_head_dim
        kc = rng.normal(size=(batch, max_len, cfg.num_kv_heads, hd)) \
            .astype(np.float32)
        vc = rng.normal(size=(batch, max_len, cfg.num_kv_heads, hd)) \
            .astype(np.float32)
        dense_in[f"k_cache_{i}"] = jnp.asarray(kc)
        dense_in[f"v_cache_{i}"] = jnp.asarray(vc)
        ka = np.zeros((num_blocks, bs, cfg.num_kv_heads, hd), np.float32)
        va = np.zeros_like(ka)
        for b in range(batch):
            ka[table[b]] = kc[b].reshape(width, bs, cfg.num_kv_heads, hd)
            va[table[b]] = vc[b].reshape(width, bs, cfg.num_kv_heads, hd)
        paged_in[f"k_arena_{i}"] = jnp.asarray(ka)
        paged_in[f"v_arena_{i}"] = jnp.asarray(va)

    out_d = run_graph_pure(dense_g, dense_in)
    out_p = run_graph_pure(paged_g, paged_in)
    np.testing.assert_array_equal(np.asarray(out_d["next_token"]),
                                  np.asarray(out_p["next_token"]))
    # the new token's K/V landed at the same logical position
    for i in range(cfg.num_layers):
        kd = np.asarray(out_d[f"k_cache_{i}"])
        ka = np.asarray(out_p[f"k_arena_{i}"])
        for b in range(batch):
            logical = ka[table[b]].reshape(max_len, cfg.num_kv_heads, -1)
            np.testing.assert_allclose(logical[pos[b]], kd[b, pos[b]],
                                       rtol=1e-6, atol=1e-6)


def test_paged_graph_dispatch_count_flat_at_every_fusion_level(setup):
    """Paging must be free in the per-operation accounting at EVERY fusion
    level: the paged decode graph's dispatch count equals the dense
    slot-position graph's, F0 through F4."""
    model, params = setup
    cfg = model.cfg
    for level, fusion in LEVELS.items():
        dense_g = build_decode_graph(params, cfg, batch=2, max_len=16,
                                     fusion=fusion, slot_pos=True)
        paged_g = build_decode_graph(params, cfg, batch=2, max_len=16,
                                     fusion=fusion, paged=True, block_size=4)
        assert paged_g.num_dispatches() == dense_g.num_dispatches(), level


# ---------------------------------------------------------------------------
# graph + dist backends: paged serving end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["F3", "FULL", "dist"])
def test_graph_and_dist_backends_paged_match_dense(setup, mode):
    """Every ExecutionBackend family now serves paged: the paged scheduler
    on graph-dispatch (F3), whole-graph-capture (FULL) and pipeline (dist)
    backends emits byte-identical greedy streams to independent dense runs,
    and a repeated prompt hits the radix cache."""
    model, params = setup
    backend = create_backend(mode, model, params, batch=1, max_len=32)
    assert backend.capabilities.paged_kv
    session = InferenceSession(backend)
    prompts = _prompts(model, 3)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=5)).tokens
            for p in prompts]
    sched = Scheduler(session, num_slots=2, kv_layout="paged",
                      prefill_chunk=4, block_size=4)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=5,
                                     request_id=f"{mode}-{i}"))
           for i, p in enumerate(prompts)]
    results = sched.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(results[rid].tokens, refs[i])
    assert sched.last_stats.prefill_chunks >= 3
    # warm pass: the SAME prompt again must reuse the cached span
    rid = sched.submit(ServeRequest(prompt=prompts[0], max_new_tokens=5,
                                    request_id=f"{mode}-warm"))
    res = sched.run()[rid]
    np.testing.assert_array_equal(res.tokens, refs[0])
    assert sched.last_stats.prefix_hit_tokens > 0


def test_graph_backend_paged_decode_same_dispatches_as_dense(setup):
    """The F3 paged cycle engine runs the SAME dispatch stream as the dense
    slot_pos cycle — measured through the backend's own dispatch
    accounting, not just the static graph property."""
    model, params = setup
    backend = create_backend("F3", model, params, batch=1, max_len=32)
    session = InferenceSession(backend)
    p = _prompts(model, 1)[0]
    ref = session.run(ServeRequest(prompt=p, max_new_tokens=6)).tokens

    def decode_disp_per_cycle(kv_layout):
        sched = Scheduler(session, num_slots=2, kv_layout=kv_layout,
                          prefill_chunk=None, prefix_cache=False,
                          block_size=4, async_readback=False)
        rid = sched.submit(ServeRequest(prompt=p, max_new_tokens=6,
                                        request_id=f"disp-{kv_layout}"))
        backend.reset_stats()
        res = sched.run()[rid]
        np.testing.assert_array_equal(res.tokens, ref)
        st = sched.last_stats
        # subtract the admission dispatches (dense prefill graph / one
        # whole-prompt extend), leaving pure decode cycles
        d_total = backend.dispatch_stats().dispatches
        if kv_layout == "paged":
            pg = sched._bstate["paged"]
            eng = backend._paged_extend_engines[
                (p.shape[1], pg.block_size, pg.pool.num_blocks, pg.width)]
            d_admit = eng.graph.num_dispatches()
        else:
            d_admit = backend._prefill_engine(p.shape[1]) \
                .graph.num_dispatches()
        return (d_total - d_admit) / st.cycles

    assert decode_disp_per_cycle("paged") == decode_disp_per_cycle("dense")


def test_multi_turn_generated_tokens_reused(setup):
    """Turn 2 of a conversation (prompt + completion + follow-up) must hit
    the radix cache over the prompt AND the generated span — zero prefill
    dispatches for the shared tokens, exact greedy parity."""
    model, params = setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    session = InferenceSession(backend)
    rng = np.random.default_rng(9)
    block, chunk, n_gen = 4, 4, 8
    p1 = rng.integers(0, model.cfg.vocab_size, size=(1, 12)).astype(np.int32)
    r1 = session.run(ServeRequest(prompt=p1, max_new_tokens=n_gen))
    follow = rng.integers(0, model.cfg.vocab_size, size=3).astype(np.int32)
    p2 = np.concatenate([p1[0], r1.tokens[0], follow]).reshape(1, -1)
    ref2 = session.run(ServeRequest(prompt=p2, max_new_tokens=4)).tokens

    sched = Scheduler(session, num_slots=1, kv_layout="paged",
                      prefill_chunk=chunk, block_size=block)
    rid = sched.submit(ServeRequest(prompt=p1, max_new_tokens=n_gen,
                                    request_id="turn1"))
    np.testing.assert_array_equal(sched.run()[rid].tokens, r1.tokens)
    rid = sched.submit(ServeRequest(prompt=p2, max_new_tokens=4,
                                    request_id="turn2"))
    res2 = sched.run()[rid]
    np.testing.assert_array_equal(res2.tokens, ref2)
    st = sched.last_stats
    # KV cached through turn 1 covers prompt + generated[:-1] (the final
    # sampled token is the sampling boundary — never fed back, never
    # cached); the radix insert keeps whole blocks of that span
    covered = (p1.shape[1] + n_gen - 1) // block * block
    assert st.prefix_hit_tokens == covered
    assert covered > p1.shape[1], "generated tokens were not reused"
    # zero prefill dispatches over the shared span: only the unshared
    # suffix is chunked
    assert st.prefill_chunks == -(-(p2.shape[1] - covered) // chunk)


def test_dist_paged_release_and_memory_accounting(setup):
    """Dist paged slots release cleanly (blocks back to the pool, radix
    chains surviving) and report the same memory accounting surface."""
    model, params = setup
    backend = create_backend("dist", model, params, batch=1, max_len=32)
    session = InferenceSession(backend)
    p = _prompts(model, 1)[0]
    sched = Scheduler(session, num_slots=2, kv_layout="paged",
                      prefill_chunk=4, block_size=4)
    rid = sched.submit(ServeRequest(prompt=p, max_new_tokens=4,
                                    request_id="dm"))
    sched.run()
    pg = sched._bstate["paged"]
    assert pg.occupancy == 0
    assert sched.last_stats.kv_bytes_allocated > 0
    assert sched.last_stats.kv_bytes_live_peak > 0
    # the released request's chain stays cached for the next warm hit
    assert sched._bstate["radix"].num_nodes > 0


# ---------------------------------------------------------------------------
# fork accounting under churn (speculative rollback-heavy traffic)
# ---------------------------------------------------------------------------

def _apply_fork_churn(cfg, ops):
    """Interpret a fuzz op stream against a fresh PagedKVCache and assert
    the pool invariants after every op: free+live partitions the arena,
    refcounts and the free list agree, owned blocks are live, and a full
    teardown leaks nothing.  Opcodes: 0 allocate, 1 write+advance,
    2 adopt-share into the other slot, 3 open a speculative fork,
    4 partial commit, 5 drop (rollback), 6 free slot."""
    from repro.serving.paging.allocator import _ceildiv

    pg = PagedKVCache(cfg, num_slots=2, max_len=24, block_size=4,
                      num_blocks=14)
    pool, bs = pg.pool, pg.block_size
    cap = pg.width * bs
    forks = {}

    def check():
        assert pool.num_free + pool.num_live == pool.num_blocks
        free = set(pool._free)
        for b in range(pool.num_blocks):
            assert (pool.refcount[b] == 0) == (b in free)
            assert pool.refcount[b] >= 0
        for own in pg._owned.values():
            for b in own:
                assert pool.refcount[b] >= 1 and b not in free
        for s in pg._live:
            # table entries covering [0, pos) are real owned-or-shared
            # blocks, never recycled ones
            for i in range(_ceildiv(int(pg.pos[s]), bs)):
                assert pool.refcount[int(pg.table[s, i])] >= 1

    for code, arg in ops:
        s = arg % 2
        pos = int(pg.pos[s]) if s in pg._live else 0
        if code == 0 and s not in pg._live:
            pg.allocate(s)
        elif code == 1 and s in pg._live and pos < cap:
            pg.ensure_writable(s, pos, pos + 1)
            pg.pos[s] = pos + 1
        elif code == 2 and s in pg._live and (1 - s) not in pg._live \
                and pos >= 1:
            take = (arg // 2) % pos + 1
            pg.allocate(1 - s)
            pg.adopt_prefix(1 - s, take, pg.chain(s, take))
        elif code == 3 and s in pg._live and s not in forks:
            span = (arg // 2) % 5 + 1
            if pos + span <= cap:
                forks[s] = (pg.fork_slot(s), span)
                pg.ensure_writable(s, pos, pos + span)
        elif code == 4 and s in forks:
            f, span = forks.pop(s)
            pg.commit_fork(s, f, f.pos0 + (arg // 2) % (span + 1))
        elif code == 5 and s in forks:
            pg.drop_fork(s, forks.pop(s)[0])
        elif code == 6 and s in pg._live:
            forks.pop(s, None)
            pg.free(s)
        check()

    for s, (f, _) in list(forks.items()):
        pg.drop_fork(s, f)
    for s in list(pg._live):
        pg.free(s)
    assert pool.num_live == 1            # trash block only: zero leaks
    assert pool.num_free == pool.num_blocks - 1


def test_fork_churn_randomized(setup):
    """Deterministic 400-op churn over cow/adopt/fork/commit/drop/free —
    always runs (no hypothesis needed)."""
    model, _ = setup
    rng = np.random.default_rng(1234)
    ops = [(int(rng.integers(0, 7)), int(rng.integers(0, 64)))
           for _ in range(400)]
    _apply_fork_churn(model.cfg, ops)


def test_fork_churn_property(setup):
    """Hypothesis-guarded version: shrinks any violating interleaving to
    a minimal op sequence."""
    pytest.importorskip("hypothesis", reason="property tests need the "
                        "hypothesis dev extra")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    model, _ = setup

    @given(st.lists(st.tuples(st.integers(0, 6), st.integers(0, 63)),
                    max_size=60))
    @settings(max_examples=25, deadline=None)
    def prop(ops):
        _apply_fork_churn(model.cfg, ops)

    prop()


# ---------------------------------------------------------------------------
# arena layout rule: heads merged where head_dim is not lane-aligned
# ---------------------------------------------------------------------------

def _drive_paged(params, cfg, pg, seq, layout):
    """Prefill ``seq[:, :13]`` in chunks of 8, decode three teacher-forced
    tokens, then verify three more in one call, all through ``pg``'s block
    tables; the arenas are reshaped to ``layout`` before the first step.
    Tables and positions go up as snapshots, since the host mutates them
    while a step may still run.  Returns the logits of every call, the
    slot and its KV readout."""
    pool = pg.pool
    pool.set_arena(pool.arena_k.reshape(layout),
                   pool.arena_v.reshape(layout))
    extend = jax.jit(functools.partial(extend_step_paged, params, cfg))
    decode = jax.jit(functools.partial(decode_step_paged, params, cfg))
    verify = jax.jit(functools.partial(verify_step_paged, params, cfg))
    slot = pg.allocate()
    out = []
    for pos0, valid in ((0, 8), (8, 5)):
        pg.ensure_writable(slot, pos0, pos0 + 8)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :valid] = seq[0, pos0:pos0 + valid]
        ak, av, logits, _ = extend(pool.arena_k, pool.arena_v,
                                   device_snapshot(pg.table[slot:slot + 1]),
                                   jnp.int32(pos0), jnp.int32(valid),
                                   jnp.asarray(chunk))
        pool.set_arena(ak, av)
        pg.pos[slot] = pos0 + valid
        out.append(("extend", pos0 + valid - 1, logits[0, 0]))
    for _ in range(3):
        p = int(pg.pos[slot])
        pg.ensure_writable(slot, p, p + 1)
        toks = np.zeros((pg.num_slots, 1), np.int32)
        toks[slot, 0] = seq[0, p]
        ak, av, logits, _ = decode(pool.arena_k, pool.arena_v,
                                   device_snapshot(pg.table),
                                   device_snapshot(pg.pos), jnp.asarray(toks))
        pool.set_arena(ak, av)
        pg.advance([slot])
        out.append(("decode", p, logits[slot, 0]))
    p = int(pg.pos[slot])
    pg.ensure_writable(slot, p, p + 3)
    toks = np.zeros((pg.num_slots, 3), np.int32)
    toks[slot] = seq[0, p:p + 3]
    ak, av, logits, _ = verify(pool.arena_k, pool.arena_v,
                               device_snapshot(pg.table),
                               device_snapshot(pg.pos), jnp.asarray(toks))
    pool.set_arena(ak, av)
    out += [("verify", p + j, logits[slot, j]) for j in range(3)]
    return out, slot, pg.gather(slot)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_arena_layout_follows_head_dim(head_dim):
    """The stacked arena stores (KV, hd) merged exactly when hd is not a
    multiple of 128 lanes, and nothing else changes: paged extend, decode
    and verify match the dense forward's tokens and logits and are
    bit-identical to the same steps on the split layout; the KV readout,
    a COW fork and a swap round trip are exact."""
    cfg = get_smoke_config("qwen2.5-0.5b", d_model=4 * head_dim, heads=4,
                           kv_heads=2)
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    L, KV, bs = cfg.num_layers, 2, 4
    seq = np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=(1, 19)).astype(np.int32)
    ref = np.asarray(transformer.forward(params, cfg, jnp.asarray(seq))[0][0])

    def cache():
        return PagedKVCache(cfg, 2, max_len=24, block_size=bs, table_slack=8)

    pg = cache()
    n = pg.pool.num_blocks
    split = (n, L, bs, KV, head_dim)
    stored = split if head_dim % 128 == 0 else (n, L, bs, KV * head_dim)
    assert pg.pool.arena_k.shape == stored == pg.pool.arena_v.shape
    assert arena_shape(cfg, n, bs) == stored

    out, slot, kv = _drive_paged(params, cfg, pg, seq, stored)
    out_split, _, kv_split = _drive_paged(params, cfg, cache(), seq, split)
    for (kind, p, got), (_, _, want) in zip(out, out_split):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{kind} at {p}")
        assert int(np.argmax(got)) == int(np.argmax(ref[p])), (kind, p)
        np.testing.assert_allclose(np.asarray(got), ref[p], rtol=1e-4,
                                   atol=1e-4, err_msg=f"{kind} at {p}")
    npos = int(pg.pos[slot])
    assert kv["k"].shape == (L, npos, KV, head_dim) == kv["v"].shape
    for c in ("k", "v"):
        np.testing.assert_array_equal(kv[c], kv_split[c])

    pool = pg.pool
    bid = int(pg.table[slot, 0])
    pool.incref(bid)                       # shared: the next write forks
    nb, forked = pool.cow(bid)
    assert forked
    for a in (pool.arena_k, pool.arena_v):
        np.testing.assert_array_equal(np.asarray(a[nb]), np.asarray(a[bid]))
    pool.decref(nb)
    pool.decref(bid)

    chain = pg.swap_out(slot)
    assert chain.host
    slot, uploads = pg.swap_in(chain)
    assert uploads == len(chain.host) and int(pg.pos[slot]) == npos
    back = pg.gather(slot)
    for c in ("k", "v"):
        np.testing.assert_array_equal(back[c], kv[c])
