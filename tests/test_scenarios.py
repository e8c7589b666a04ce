"""Heterogeneous-family serving: Mamba2 / RG-LRU behind the one scheduler.

The state-cache protocol (`repro.serving.statecache`) puts constant-size
recurrent state slots behind the same continuous-batching contract as
transformer KV.  These tests pin the contract: scheduled-vs-raw greedy
parity per family, slot reuse without state leakage, ring-buffer
window-KV wraparound, honest capability errors, and the arbitrary-tree
memory accounting the scenarios bench reports.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import (InferenceSession, RecurrentStateCache, Scheduler,
                           ServeRequest, SlotKVCache, create_backend)

FAMILIES = {
    "mamba2": ("mamba2-1.3b", {}),
    "rglru": ("recurrentgemma-9b", {"layers": 3}),  # full (R, R, A) pattern
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def fam_setup(request):
    arch, kw = FAMILIES[request.param]
    cfg = get_smoke_config(arch, **kw)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return request.param, model, params


def _prompts(model, n, lens=(4, 6, 5, 3, 7, 4, 5, 6)):
    rng = np.random.default_rng(11)
    return [rng.integers(0, model.cfg.vocab_size, size=(1, lens[i % len(lens)]))
            .astype(np.int32) for i in range(n)]


def _raw_greedy(model, params, prompt, n_new, max_len=64):
    """The family's own prefill + decode loop — the parity oracle."""
    cache, logits = model.prefill(params, {"tokens": jnp.asarray(prompt)},
                                  max_len)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        cache, logits = model.decode_step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return np.asarray(toks, np.int32)


# ---------------------------------------------------------------------------
# scheduled-vs-raw greedy parity (the tentpole acceptance criterion)
# ---------------------------------------------------------------------------

def test_scheduled_matches_raw_decode_loop(fam_setup):
    """Continuous batching over RecurrentStateCache is byte-exact against
    the family's raw batch-1 prefill+decode loop — slots at different
    positions share one dispatch without perturbing each other."""
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    assert backend.capabilities.state_kind == "recurrent"
    assert backend.capabilities.decode_batch
    prompts = _prompts(model, 5)
    lens = [9, 3, 7, 4, 5]  # staggered finishes → staggered admissions
    refs = [_raw_greedy(model, params, p, n) for p, n in zip(prompts, lens)]
    sched = Scheduler(InferenceSession(backend), num_slots=3, continuous=True)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=n,
                                     request_id=f"{fam}{i}"))
           for i, (p, n) in enumerate(zip(prompts, lens))]
    results = sched.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens).ravel(), ref)
    st = sched.last_stats
    assert st.mean_occupancy > 1.0          # decode genuinely overlapped
    assert st.cycles < sum(lens)            # fewer cycles than total steps
    # recurrent state: constant footprint, live == occupancy × per-slot
    assert st.kv_bytes_allocated > 0
    assert st.kv_bytes_live_peak <= st.kv_bytes_allocated


def test_slot_reuse_no_state_leakage(fam_setup):
    """More requests than slots: a reused RecurrentStateCache slot cannot
    leak the previous occupant's conv/SSM/ring state."""
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    session = InferenceSession(backend)
    prompts = _prompts(model, 6)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=5)).tokens
            for p in prompts]
    sched = Scheduler(session, num_slots=2, continuous=True)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=5))
           for p in prompts]
    results = sched.run()
    assert sched.last_stats.admitted == 6          # every slot reused ≥ once
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(results[rid].tokens, ref)


def test_scheduler_fallback_loop_matches(fam_setup):
    """Per-slot-loop fallback (decode_batch=False) serves recurrent
    families through the same contract with identical tokens."""
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    backend.capabilities = dataclasses.replace(backend.capabilities,
                                               decode_batch=False)
    session = InferenceSession(backend)
    prompts = _prompts(model, 3)
    refs = [session.run(ServeRequest(prompt=p, max_new_tokens=5)).tokens
            for p in prompts]
    sched = Scheduler(session, num_slots=2, continuous=True)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=5))
           for p in prompts]
    results = sched.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(results[rid].tokens, ref)


# ---------------------------------------------------------------------------
# rglru ring-buffer window KV: wraparound past attention_window
# ---------------------------------------------------------------------------

def test_rglru_ring_buffer_wraparound():
    """Decode far past attention_window: each generated token must match
    the full-sequence forward (windowed causal attention, NO ring buffer)
    teacher-forced over the same stream — so ring writes land in the
    right slots and attention masks the right window after wraparound."""
    cfg = get_smoke_config("recurrentgemma-9b", layers=3)
    cfg = dataclasses.replace(
        cfg, rglru=dataclasses.replace(cfg.rglru, attention_window=8))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size, size=(1, 5)).astype(np.int32)
    n_new = 20                                   # 5 + 20 ≫ window of 8
    toks = _raw_greedy(model, params, prompt, n_new)
    # teacher-force the whole stream through forward(): logits at position
    # len(prompt)-1+i must argmax to toks[i] for every i, including all
    # positions past the window boundary
    stream = np.concatenate([prompt[0], toks[:-1]])[None, :]
    logits, _ = model.forward(params, {"tokens": jnp.asarray(stream)})
    want = np.argmax(np.asarray(logits[0, prompt.shape[1] - 1:]), axis=-1)
    np.testing.assert_array_equal(toks, want.astype(np.int32))


def test_rglru_ring_wraparound_through_scheduler():
    """The same wraparound regime, but scheduled: pooled per-row ring
    writes stay byte-exact vs the raw loop beyond the window."""
    cfg = get_smoke_config("recurrentgemma-9b", layers=3)
    cfg = dataclasses.replace(
        cfg, rglru=dataclasses.replace(cfg.rglru, attention_window=8))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    backend = create_backend("model", model, params, batch=1, max_len=64)
    prompts = _prompts(model, 3, lens=(4, 6, 5))
    refs = [_raw_greedy(model, params, p, 16) for p in prompts]
    sched = Scheduler(InferenceSession(backend), num_slots=3, continuous=True)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=16))
           for p in prompts]
    results = sched.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(
            np.asarray(results[rid].tokens).ravel(), ref)


# ---------------------------------------------------------------------------
# capability honesty: unsupported paths raise, naming the capability
# ---------------------------------------------------------------------------

def test_recurrent_capabilities_are_honest(fam_setup):
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    caps = backend.capabilities
    assert caps.state_kind == "recurrent"
    assert caps.decode_batch
    assert not caps.paged_kv and not caps.speculative and not caps.preemption


def test_paged_layout_raises_for_recurrent(fam_setup):
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    sched = Scheduler(InferenceSession(backend), num_slots=2,
                      kv_layout="paged")
    sched.submit(ServeRequest(prompt=_prompts(model, 1)[0], max_new_tokens=2))
    with pytest.raises(ValueError, match="no paged-KV.*recurrent"):
        sched.run()


def test_alloc_slots_paged_raises_for_recurrent(fam_setup):
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    with pytest.raises(NotImplementedError, match="no paged-KV"):
        backend.alloc_slots_paged(2)


def test_serve_cli_names_missing_capability(monkeypatch):
    """launch/serve.py fails loudly (naming the capability and the
    state_kind) instead of silently skipping the scheduler run."""
    from repro.launch import serve
    monkeypatch.setattr("sys.argv", [
        "serve", "--config", "mamba2-1.3b", "--smoke", "--modes", "model",
        "--tokens", "2", "--runs", "1", "--warmup", "0",
        "--num-slots", "2", "--kv-layout", "paged"])
    with pytest.raises(SystemExit, match="paged_kv=False.*recurrent"):
        serve.main()


# ---------------------------------------------------------------------------
# RecurrentStateCache unit behavior
# ---------------------------------------------------------------------------

def test_recurrent_cache_lifecycle_and_isolation(fam_setup):
    fam, model, params = fam_setup
    rs = RecurrentStateCache(model, num_slots=2, max_len=32)
    assert rs.state_kind == "recurrent"
    cache, _ = model.prefill(
        params, {"tokens": jnp.asarray(_prompts(model, 1)[0])}, 32)
    s0 = rs.allocate()
    rs.write(s0, cache)
    back = rs.gather(s0)
    for a, b in zip(jax.tree.leaves({k: v for k, v in cache.items()
                                     if k != "pos"}),
                    jax.tree.leaves({k: v for k, v in back.items()
                                     if k != "pos"})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(back["pos"]) == int(cache["pos"])
    # the neighbouring slot stays zero
    other = rs.gather(1 - s0)
    assert all(float(np.abs(np.asarray(leaf)).max()) == 0.0
               for leaf in jax.tree.leaves({k: v for k, v in other.items()
                                            if k != "pos"}))
    with pytest.raises(RuntimeError, match="unallocated"):
        rs.write(1 - s0, cache)
    rs.allocate()
    with pytest.raises(RuntimeError, match="full"):
        rs.allocate()
    rs.free(s0)
    assert rs.pos[s0] == 0 and rs.num_free == 1


def test_recurrent_cache_fork_restore(fam_setup):
    """O(1) snapshot: fork a slot, mutate the pool, restore byte-exactly."""
    fam, model, params = fam_setup
    rs = RecurrentStateCache(model, num_slots=2, max_len=32)
    cache, _ = model.prefill(
        params, {"tokens": jnp.asarray(_prompts(model, 1)[0])}, 32)
    s0 = rs.allocate()
    rs.write(s0, cache)
    snap = rs.fork(s0)
    rs.free(s0)
    s1 = rs.restore(snap)
    back = rs.gather(s1)
    for a, b in zip(jax.tree.leaves({k: v for k, v in snap.items()
                                     if k != "pos"}),
                    jax.tree.leaves({k: v for k, v in back.items()
                                     if k != "pos"})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(back["pos"]) == int(snap["pos"])


def test_recurrent_state_bytes_constant_in_max_len(fam_setup):
    """THE memory claim: per-slot state bytes do not grow with max_len."""
    fam, model, params = fam_setup
    small = RecurrentStateCache(model, num_slots=2, max_len=32)
    large = RecurrentStateCache(model, num_slots=2, max_len=256)
    assert small.bytes_per_slot == large.bytes_per_slot
    assert small.bytes_allocated == large.bytes_allocated
    # bytes_live tracks occupancy, not decoded length
    cache, _ = model.prefill(
        params, {"tokens": jnp.asarray(_prompts(model, 1)[0])}, 32)
    s = small.allocate()
    small.write(s, cache)
    live0 = small.bytes_live
    assert live0 == small.bytes_per_slot
    small.advance([s])
    small.advance([s])
    assert small.bytes_live == live0       # advancing never grows state


def test_recurrent_cache_rejects_unknown_layout():
    """Families whose cache is not a pos-keyed dict are refused, not
    silently mis-scattered."""

    class FakeModel:
        class cfg:
            family = "weird"

        @staticmethod
        def init_cache(batch, max_len):
            return [jnp.zeros((batch, 4))]

        @staticmethod
        def cache_spec(batch, max_len):
            return [jax.ShapeDtypeStruct((batch, 4), jnp.float32)]

    with pytest.raises(ValueError, match="pos"):
        RecurrentStateCache(FakeModel(), num_slots=2, max_len=8)


# ---------------------------------------------------------------------------
# SlotKVCache memory accounting over arbitrary trees (satellite fix)
# ---------------------------------------------------------------------------

def test_slotkv_bytes_over_heterogeneous_tree():
    """bytes_allocated/bytes_live sum per leaf — mixed dtypes, mixed
    shapes, mixed max_len — instead of assuming uniform KV leaves."""
    tree = {
        "a": jnp.zeros((2, 8, 4), jnp.float32),     # slot axis 0, max_len 8
        "b": jnp.zeros((2, 8, 2, 3), jnp.bfloat16),
    }
    kv = SlotKVCache(tree, num_slots=2, slot_axis=0)
    want_alloc = 2 * 8 * 4 * 4 + 2 * 8 * 2 * 3 * 2
    assert kv.bytes_allocated == want_alloc
    s = kv.allocate()
    kv.pos[s] = 3
    per_tok = 4 * 4 + 2 * 3 * 2                     # per-leaf, per token
    assert kv.bytes_live == 3 * per_tok


# ---------------------------------------------------------------------------
# obs: recurrent decode dispatches flow through the one _record choke point
# ---------------------------------------------------------------------------

def test_recurrent_dispatches_traced_exactly(fam_setup):
    """Trace-derived dispatch totals equal the backend's dispatch_stats for
    recurrent families, and the decode lane is labelled decode_recurrent —
    the CI trace↔stats exact-consistency gate covers the new cache class."""
    from repro.obs import Tracer
    fam, model, params = fam_setup
    backend = create_backend("model", model, params, batch=1, max_len=64)
    tr = Tracer()
    sched = Scheduler(InferenceSession(backend), num_slots=2, tracer=tr)
    d0 = backend.dispatch_stats().dispatches
    for p in _prompts(model, 3):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=4))
    sched.run()
    st = sched.last_stats
    delta = backend.dispatch_stats().dispatches - d0
    assert tr.dispatch_total() == delta == st.dispatches
    lane = [e for e in tr.events()
            if e.track == f"backend:{backend.capabilities.name}"
            and e.cat == "dispatch"]
    ops = {e.args.get("op") for e in lane if e.args}
    assert "decode_recurrent" in ops
    assert "decode_batch" not in ops       # the KV lane never fired
