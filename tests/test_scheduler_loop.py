"""The scheduler's cycle structure, pinned: one fixed seeded mix served
through every kind of cycle (single-step sync and async, multi-step,
speculative, recompute preemption, open-loop arrivals) on both KV
layouts and two backend families, asserting the token streams and the
count fields of ``last_stats``.  The counts were recorded by running
this body on the scheduler that had one loop per KV layout; the single
loop must reproduce them exactly.

Also the contract ``bench/harness.py`` relies on: ``Tracing.wrap``
replaces ``decode_batch`` and ``prefill_paged_chunk`` on the backend
INSTANCE after warm-up and reads ``sched._bstate``, so the scheduler must
look both methods up at every call and keep its batch state there."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import (InferenceSession, Scheduler, SchedulerConfig,
                           ServeRequest, create_backend)

MAX_LEN = 48
LENS = (12, 7, 15, 9, 11)
NEW = (9, 1, 12, 6, 10)          # request 1 is done at its first token
STOP_AT = 3                      # request 3 stops on its 4th token
COUNTS = ("cycles", "tokens", "overlap_cycles", "multi_cycles",
          "spec_cycles", "prefill_chunks", "dispatches", "preemptions")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(18)
    prompts = [rng.integers(0, cfg.vocab_size, size=(1, n)).astype(np.int32)
               for n in LENS]
    backends = {}

    def backend(mode):
        if mode not in backends:
            backends[mode] = create_backend(mode, model, params, batch=1,
                                            max_len=MAX_LEN)
        return backends[mode]
    return prompts, backend


def _requests(session, prompts):
    """The mix: five greedy requests, one done at its first token and one
    with a stop token it meets mid-stream (found from its own stream), and
    each request's reference tokens served alone through ``session.run``."""
    reqs, refs = [], {}
    for i, (p, n) in enumerate(zip(prompts, NEW)):
        req = ServeRequest(prompt=p, max_new_tokens=n, request_id=f"r{i}")
        if i == 3:
            alone = session.run(dataclasses.replace(req, request_id=""))
            req = dataclasses.replace(
                req, stop_tokens=(int(alone.tokens[0, STOP_AT]),))
        reqs.append(req)
        refs[req.request_id] = session.run(
            dataclasses.replace(req, request_id="")).tokens
    return reqs, refs


# (backend, scheduler fields, how requests arrive): "closed" submits the
# mix up front; "preempt" adds a priority-2 request when r0 emits its
# second token; "open" schedules the mix 5 ms apart with submit_at
CASES = {
    "model-dense-sync": ("model", dict(async_readback=False), "closed"),
    "model-dense-async": ("model", dict(), "closed"),
    "model-paged-sync": ("model", dict(kv_layout="paged",
                                       async_readback=False), "closed"),
    "model-paged-async": ("model", dict(kv_layout="paged"), "closed"),
    "F3-dense-sync": ("F3", dict(async_readback=False), "closed"),
    "F3-dense-async": ("F3", dict(), "closed"),
    "F3-paged-sync": ("F3", dict(kv_layout="paged", async_readback=False),
                      "closed"),
    "F3-paged-async": ("F3", dict(kv_layout="paged"), "closed"),
    "F3-dense-h4": ("F3", dict(decode_horizon=4), "closed"),
    "F3-paged-h4": ("F3", dict(kv_layout="paged", decode_horizon=4),
                    "closed"),
    "model-paged-ngram": ("model", dict(kv_layout="paged",
                                        speculative="ngram"), "closed"),
    "model-paged-recompute": ("model", dict(kv_layout="paged",
                                            preemption="recompute"),
                              "preempt"),
    "model-dense-open": ("model", dict(), "open"),
    "model-paged-open": ("model", dict(kv_layout="paged"), "open"),
}

# cycles, tokens, overlap_cycles, multi_cycles, spec_cycles,
# prefill_chunks, dispatches, preemptions
EXPECTED = {
    "model-dense-sync": (20, 36, 0, 0, 0, 0, 25, 0),
    "model-dense-async": (20, 36, 15, 0, 0, 0, 25, 0),
    "model-paged-sync": (23, 36, 0, 0, 0, 15, 38, 0),
    "model-paged-async": (23, 36, 12, 0, 0, 15, 38, 0),
    "F3-dense-sync": (20, 36, 0, 0, 0, 0, 1360, 0),
    "F3-dense-async": (20, 36, 15, 0, 0, 0, 1360, 0),
    "F3-paged-sync": (23, 36, 0, 0, 0, 15, 2150, 0),
    "F3-paged-async": (23, 36, 12, 0, 0, 15, 2150, 0),
    "F3-dense-h4": (6, 36, 2, 5, 0, 0, 590, 0),
    "F3-paged-h4": (11, 36, 1, 4, 0, 15, 1490, 0),
    "model-paged-ngram": (22, 36, 0, 0, 22, 15, 37, 0),
    "model-paged-recompute": (23, 41, 6, 0, 0, 18, 41, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_cycle_structure(setup, case):
    prompts, backend_of = setup
    mode, fields, arrival = CASES[case]
    session = InferenceSession(backend_of(mode))
    reqs, refs = _requests(session, prompts)
    sched = Scheduler(session, config=SchedulerConfig(
        num_slots=2, prefill_chunk=4, block_size=4, **fields))
    if arrival == "preempt":
        extra = ServeRequest(prompt=prompts[2][:, :6], max_new_tokens=5,
                             request_id="hi", priority=2)
        refs["hi"] = session.run(
            dataclasses.replace(extra, request_id="")).tokens

        def arrive(i, _toks):
            if i == 1:
                sched.submit(extra)
        reqs[0] = dataclasses.replace(reqs[0], stream=arrive)
    if arrival == "open":
        t0 = time.perf_counter()
        for i, r in enumerate(reqs):
            sched.submit_at(r, t0 + 0.005 * i)
    else:
        for r in reqs:
            sched.submit(r)
    results = sched.run()
    assert set(results) == set(refs)
    for rid, ref in refs.items():
        np.testing.assert_array_equal(results[rid].tokens, ref, err_msg=rid)
    st = sched.last_stats
    if arrival == "open":
        # its cycles follow the clock: only the tokens are fixed
        assert st.tokens == sum(r.shape[1] for r in refs.values())
        return
    assert {f: getattr(st, f) for f in COUNTS} == dict(zip(COUNTS,
                                                           EXPECTED[case]))


def test_scheduler_calls_the_backend_methods_the_harness_wraps(setup):
    """Warm a paged scheduler up, then replace ``decode_batch`` and
    ``prefill_paged_chunk`` on the backend instance with counting
    wrappers, as ``bench/harness.py`` ``Tracing.wrap`` does: every decode
    cycle and prefill chunk of the next run goes through them, and
    ``sched._bstate`` holds the live paged state they are handed."""
    prompts, _ = setup
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    backend = create_backend("model", model,
                             model.init_params(jax.random.PRNGKey(0)),
                             batch=1, max_len=MAX_LEN)
    sched = Scheduler(InferenceSession(backend), config=SchedulerConfig(
        continuous=True, async_readback=True, num_slots=1,
        kv_layout="paged", prefill_chunk=4, block_size=4))
    sched.submit(ServeRequest(prompt=prompts[0], max_new_tokens=3))
    sched.run()

    decode, prefill = backend.decode_batch, backend.prefill_paged_chunk
    calls = {"decode": 0, "prefill": 0}

    def decode_batch(bstate, tokens, slots):
        assert bstate["paged"] is sched._bstate["paged"]
        assert all(bstate["paged"].pos[s] > 0 for s in slots)
        calls["decode"] += 1
        return decode(bstate, tokens, slots)

    def prefill_paged_chunk(bstate, slot):
        assert bstate["paged"] is sched._bstate["paged"]
        meta = bstate["meta"][slot]
        assert meta["cursor"] < len(meta["prompt"])
        assert bstate["chunk"] == 4
        calls["prefill"] += 1
        return prefill(bstate, slot)

    backend.decode_batch = decode_batch
    backend.prefill_paged_chunk = prefill_paged_chunk
    for p, n in zip(prompts, NEW):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=n))
    sched.run()
    st = sched.last_stats
    assert st.overlap_cycles > 0
    assert calls == {"decode": st.cycles, "prefill": st.prefill_chunks}
    assert sched._bstate["paged"] is not None
