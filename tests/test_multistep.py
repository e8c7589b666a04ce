"""Multi-step decode capture: byte-exact greedy parity vs the per-cycle
path on every graph level, stop-token mid-horizon reconciliation,
fallback behavior for ineligible request mixes, trace↔stats exactness
for the ``decode_multi`` lane, and the ``SchedulerConfig`` /
``CapabilityError`` consolidation surface."""
import numpy as np
import pytest

import jax

from repro.configs.bench import BENCH_05B
from repro.models import build_model
from repro.obs import Tracer
from repro.serving import (CapabilityError, InferenceSession, Scheduler,
                           SchedulerConfig, ServeRequest, create_backend)
from repro.serving.sampler import SamplerConfig

TOK = 12
PLEN = 5


@pytest.fixture(scope="module")
def setup():
    model = build_model(BENCH_05B)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, BENCH_05B.vocab_size, size=(1, PLEN))
               .astype(np.int32) for _ in range(4)]
    return model, params, prompts


def _run(model, params, prompts, mode="F3", horizon=1, num_slots=2,
         reqkw=None, tok=TOK, **schedkw):
    backend = create_backend(mode, model, params, batch=1,
                             max_len=PLEN + tok + 4)
    sched = Scheduler(InferenceSession(backend), num_slots=num_slots,
                      decode_horizon=horizon, **schedkw)
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=tok,
                                     request_id=f"m{i}", **(reqkw or {})))
           for i, p in enumerate(prompts)]
    results = sched.run()
    return [results[rid].tokens for rid in ids], sched.last_stats, backend


# ---------------------------------------------------------------------------
# byte-exact greedy parity, per graph level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["F0", "F1", "F2", "F3", "F4", "FULL"])
def test_multi_step_greedy_parity(setup, mode):
    model, params, prompts = setup
    ref, st1, _ = _run(model, params, prompts, mode=mode, horizon=1)
    got, st8, _ = _run(model, params, prompts, mode=mode, horizon=8)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles > 0
    assert st8.multi_tokens > 0
    # one super-step records the captured stream ONCE for up to N tokens
    assert st8.dispatches_per_token < st1.dispatches_per_token
    assert st8.cycles < st1.cycles


def test_multi_step_dispatch_drop_factor(setup):
    """The acceptance bar: horizon-8 super-steps cut F3 dispatches/token
    by ≥ 4× (8 captured cycles per submission; 17 tokens = first token +
    two full horizons, so the capture dominates the constant prefill
    cost)."""
    model, params, prompts = setup
    _, st1, _ = _run(model, params, prompts, mode="F3", horizon=1, tok=17)
    _, st8, _ = _run(model, params, prompts, mode="F3", horizon=8, tok=17)
    assert st1.dispatches_per_token / st8.dispatches_per_token >= 4.0


def test_multi_step_paged_parity(setup):
    model, params, prompts = setup
    ref, _, _ = _run(model, params, prompts, mode="F3", horizon=1,
                     kv_layout="paged")
    got, st8, _ = _run(model, params, prompts, mode="F3", horizon=8,
                       kv_layout="paged")
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles > 0


# ---------------------------------------------------------------------------
# stop tokens: on-device stop table + retire-time reconciliation
# ---------------------------------------------------------------------------

def test_multi_step_stop_mid_horizon(setup):
    """A stop token hit mid-horizon truncates exactly where the
    single-step path stops — nothing past the stop is ever emitted."""
    model, params, prompts = setup
    ref, _, _ = _run(model, params, prompts, horizon=1)
    stop = int(ref[0][0, 5])                  # mid-stream token of req 0
    ref_s, st1, _ = _run(model, params, prompts, horizon=1,
                         reqkw={"stop_tokens": (stop,)})
    got_s, st8, _ = _run(model, params, prompts, horizon=8,
                         reqkw={"stop_tokens": (stop,)})
    for a, b in zip(ref_s, got_s):
        np.testing.assert_array_equal(a, b)
    assert st8.tokens == st1.tokens           # reconciliation emitted no extra
    assert st8.multi_cycles > 0               # stops did NOT disable capture


def test_multi_step_stop_paged_radix_safe(setup):
    """Paged + stop tokens: a slot finishing mid-horizon publishes only
    its sampling-boundary coverage, so later prefix-cache adopters of the
    released chain still see exact tokens."""
    model, params, prompts = setup
    ref, _, _ = _run(model, params, prompts, horizon=1)
    stop = int(ref[0][0, 5])
    ref_s, _, _ = _run(model, params, prompts, horizon=1, kv_layout="paged",
                       reqkw={"stop_tokens": (stop,)})
    got_s, st8, _ = _run(model, params, prompts, horizon=8,
                         kv_layout="paged", reqkw={"stop_tokens": (stop,)})
    for a, b in zip(ref_s, got_s):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles > 0


# ---------------------------------------------------------------------------
# fallback: ineligible mixes take the per-cycle path, same tokens
# ---------------------------------------------------------------------------

def test_multi_step_fallback_non_greedy(setup):
    model, params, prompts = setup
    kw = {"sampler": SamplerConfig("temperature", temperature=0.8),
          "seed": 3}
    ref, _, _ = _run(model, params, prompts, horizon=1, reqkw=kw)
    got, st8, _ = _run(model, params, prompts, horizon=8, reqkw=kw)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles == 0              # fell back, never captured


def test_multi_step_fallback_streaming(setup):
    model, params, prompts = setup
    seen = []
    _, st8, _ = _run(model, params, prompts[:2], horizon=8,
                     reqkw={"stream": lambda i, t: seen.append(i)})
    assert st8.multi_cycles == 0
    assert seen                               # stream callbacks still fired


def test_multi_step_fallback_logits_readback(setup):
    model, params, prompts = setup
    ref, _, _ = _run(model, params, prompts[:2], horizon=1,
                     reqkw={"readback": "logits"})
    got, st8, _ = _run(model, params, prompts[:2], horizon=8,
                       reqkw={"readback": "logits"})
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles == 0


def test_multi_step_fallback_backend_without_capability(setup):
    """Backends that never advertise decode_multi (the jitted model path)
    silently keep the per-cycle stream under decode_horizon > 1."""
    model, params, prompts = setup
    ref, _, _ = _run(model, params, prompts, mode="model", horizon=1)
    got, st8, backend = _run(model, params, prompts, mode="model",
                             horizon=8)
    assert not backend.capabilities.decode_multi
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st8.multi_cycles == 0


# ---------------------------------------------------------------------------
# trace ↔ stats exactness for the decode_multi lane
# ---------------------------------------------------------------------------

def test_multi_step_trace_stats_exact(setup):
    model, params, prompts = setup
    tr = Tracer()
    backend = create_backend("F3", model, params, batch=1,
                             max_len=PLEN + TOK + 4)
    sched = Scheduler(InferenceSession(backend), num_slots=2,
                      decode_horizon=8, tracer=tr)
    d0 = backend.dispatch_stats().dispatches
    for i, p in enumerate(prompts):
        sched.submit(ServeRequest(prompt=p, max_new_tokens=TOK))
    sched.run()
    st = sched.last_stats
    delta = backend.dispatch_stats().dispatches - d0
    # THE obs invariant survives capture: trace totals == stats delta,
    # decode_cycle spans == cycles (one span per super-step)
    assert tr.dispatch_total() == delta == st.dispatches
    assert tr.count("decode_cycle") == st.cycles
    lane = [e for e in tr.events() if e.name == "dispatch:decode_multi"]
    assert len(lane) == st.multi_cycles
    assert all(e.args["dispatches"] > 1 for e in lane)


# ---------------------------------------------------------------------------
# SchedulerConfig consolidation + CapabilityError surface
# ---------------------------------------------------------------------------

def test_scheduler_config_equivalent_to_kwargs(setup):
    model, params, prompts = setup
    backend = create_backend("F3", model, params, batch=1,
                             max_len=PLEN + TOK + 4)
    session = InferenceSession(backend)
    cfg = SchedulerConfig(num_slots=2, decode_horizon=4)
    s1 = Scheduler(session, config=cfg)
    s2 = Scheduler(session, num_slots=2, decode_horizon=4)
    assert s1.config.num_slots == s2.config.num_slots == 2
    assert s1.config.decode_horizon == s2.config.decode_horizon == 4
    assert s1.config == s2.config


def test_scheduler_config_rejects_mixing():
    with pytest.raises(ValueError, match="not both"):
        Scheduler(None, 2, config=SchedulerConfig())
    with pytest.raises(ValueError, match="not both"):
        Scheduler(None, config=SchedulerConfig(), kv_layout="paged")


def test_scheduler_config_validation_messages():
    with pytest.raises(ValueError, match="num_slots"):
        SchedulerConfig(num_slots=0)
    with pytest.raises(ValueError, match="decode_horizon"):
        SchedulerConfig(decode_horizon=0)
    with pytest.raises(ValueError, match="unknown kv_layout"):
        SchedulerConfig(kv_layout="sparse")
    with pytest.raises(ValueError, match="unknown preemption"):
        SchedulerConfig(preemption="maybe")
    with pytest.raises(ValueError, match="paged"):
        SchedulerConfig(speculative="ngram")


def test_capability_error_uniform_type_and_message(setup):
    model, params, _ = setup
    backend = create_backend("model", model, params, batch=1, max_len=32)
    with pytest.raises(CapabilityError, match="no multi-step decode"):
        backend.decode_multi({}, None, (0,), horizon=4)
    # the dual inheritance keeps every historical except-clause working
    assert issubclass(CapabilityError, NotImplementedError)
    assert issubclass(CapabilityError, ValueError)
    with pytest.raises(CapabilityError, match=r"capabilities\.decode_multi"):
        backend.capabilities.require("decode_multi")
