"""Retiring a decode token.  A greedy token taken from the device argmax
is retired with host numpy alone: no key is made or split and no logits
row is sliced.  A sampled request draws exactly the keys it drew when
every token split one (``PRNGKey(seed)``, one split for the first token
and one per step), so its tokens are bit-identical."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import (InferenceSession, Scheduler, ServeRequest,
                           create_backend)
from repro.serving.sampler import SamplerConfig, sample

N_NEW = 9


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b", layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    # a repeated prompt, so the n-gram drafter finds drafts to verify
    base = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    prompts = [np.tile(base, 3)[None], rng.integers(
        0, cfg.vocab_size, size=(1, 7)).astype(np.int32)]
    return model, params, prompts


def _sched(model, params, session_cls=InferenceSession, mode="model",
           **kw):
    backend = create_backend(mode, model, params, batch=1, max_len=64)
    return Scheduler(session_cls(backend), num_slots=2, **kw), backend


def _serve(sched, prompts, samplers, **reqkw):
    ids = [sched.submit(ServeRequest(prompt=p, max_new_tokens=N_NEW,
                                     sampler=smp, seed=1000 + i,
                                     request_id=f"r{i}", **reqkw))
           for i, (p, smp) in enumerate(zip(prompts, samplers))]
    results = sched.run()
    return [np.asarray(results[i].tokens).reshape(-1) for i in ids]


# -- a greedy retire makes no JAX call ----------------------------------------

class _Unread:
    """Stands in for a batched step's logits: reading any part fails."""

    def __getitem__(self, index):
        raise AssertionError("a greedy retire indexed a logits row")

    def __array__(self, *args, **kwargs):
        raise AssertionError("a greedy retire read the logits")


def _unread_logits(method):
    def call(*args, **kwargs):
        bstate, out = method(*args, **kwargs)
        return bstate, out._replace(logits=_Unread())
    return call


def _refuse(*args, **kwargs):
    raise AssertionError("a greedy request touched the sampler's RNG")


RETIRES = {
    "paged-async": dict(kv_layout="paged", prefill_chunk=8),
    "paged-sync": dict(kv_layout="paged", prefill_chunk=8,
                       async_readback=False),
    "dense-async": dict(kv_layout="dense"),
    "dense-sync": dict(kv_layout="dense", async_readback=False),
    "multi-step": dict(mode="F3", decode_horizon=4),
    "speculative": dict(kv_layout="paged", prefill_chunk=8,
                        speculative="ngram"),
}


@pytest.mark.parametrize("case", sorted(RETIRES))
def test_greedy_retire_makes_no_jax_call(setup, monkeypatch, case):
    model, params, prompts = setup
    sched, backend = _sched(model, params, **RETIRES[case])
    for name in ("decode_batch", "verify_paged"):
        if hasattr(backend, name):
            monkeypatch.setattr(backend, name,
                                _unread_logits(getattr(backend, name)))
    for name in ("PRNGKey", "key", "split"):
        monkeypatch.setattr(jax.random, name, _refuse)
    toks = _serve(sched, prompts, [SamplerConfig()] * 2)
    monkeypatch.undo()
    assert [len(t) for t in toks] == [N_NEW, N_NEW]
    st = sched.last_stats
    engaged = {"paged-async": st.overlap_cycles,
               "dense-async": st.overlap_cycles,
               "multi-step": st.multi_tokens,
               "speculative": st.spec_cycles}
    assert engaged.get(case, st.cycles) > 0
    if case.endswith("-sync"):
        assert st.overlap_cycles == 0


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_greedy_tokens_match_the_logits_path(setup, kv_layout):
    """Host-only greedy tokens equal those chosen from each row's logits
    on the host (``readback="logits"``), the path that still slices every
    row as every greedy row once was."""
    model, params, prompts = setup
    kw = dict(kv_layout=kv_layout)
    if kv_layout == "paged":
        kw["prefill_chunk"] = 8
    got = _serve(_sched(model, params, **kw)[0], prompts,
                 [SamplerConfig()] * 2)
    want = _serve(_sched(model, params, **kw)[0], prompts,
                  [SamplerConfig()] * 2, readback="logits")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# -- sampled tokens keep their keys -------------------------------------------

class _Recording(InferenceSession):
    """The program's session, keeping the logits each token came from."""

    def __init__(self, backend) -> None:
        super().__init__(backend)
        self.logits = collections.defaultdict(list)

    def first(self, a, out):
        self.logits[a.req.request_id].append(np.asarray(out.logits))
        super().first(a, out)

    def step_row(self, a, out):
        self.logits[a.req.request_id].append(np.asarray(out.logits))
        return super().step_row(a, out)


def _drawn_the_old_way(logits, sampler, seed):
    """One split of ``PRNGKey(seed)`` per token, the first included."""
    key = jax.random.PRNGKey(seed)
    toks = []
    for row in logits:
        key, sub = jax.random.split(key)
        toks.append(int(np.asarray(sample(jnp.asarray(row), sampler, sub))
                        .reshape(-1)[0]))
    return np.asarray(toks, np.int32)


TEMP = SamplerConfig("temperature", temperature=1.0)
TOPK = SamplerConfig("topk", temperature=1.0, top_k=5)
GREEDY = SamplerConfig()
SAMPLED = {
    "temperature-paged": ((TEMP, TEMP), dict(kv_layout="paged",
                                             prefill_chunk=8), {}),
    "topk-paged": ((TOPK, TOPK), dict(kv_layout="paged", prefill_chunk=8),
                   {}),
    "mixed-paged": ((GREEDY, TEMP), dict(kv_layout="paged",
                                         prefill_chunk=8), {}),
    "mixed-dense": ((TOPK, GREEDY), dict(kv_layout="dense"), {}),
    "mixed-speculative": ((GREEDY, TEMP), dict(
        kv_layout="paged", prefill_chunk=8, speculative="ngram"), {}),
    "sequential": ((TEMP, TOPK), dict(continuous=False), {}),
    "logits-readback": ((TEMP, GREEDY), dict(kv_layout="paged",
                                             prefill_chunk=8),
                        dict(readback="logits")),
}


@pytest.mark.parametrize("case", sorted(SAMPLED))
def test_sampled_tokens_keep_their_key_sequence(setup, case):
    model, params, prompts = setup
    samplers, schedkw, reqkw = SAMPLED[case]
    sched, _ = _sched(model, params, _Recording, **schedkw)
    toks = _serve(sched, prompts, samplers, **reqkw)
    session = sched.session
    for i, (got, smp) in enumerate(zip(toks, samplers)):
        logits = session.logits[f"r{i}"]
        assert len(logits) == len(got) == N_NEW
        if smp.kind == "greedy":
            want = np.asarray([int(np.argmax(np.asarray(r).reshape(-1)))
                               for r in logits], np.int32)
        else:
            want = _drawn_the_old_way(logits, smp, 1000 + i)
            # the draw is not the argmax: the keys decide these tokens
            assert any(t != np.argmax(np.asarray(r).reshape(-1))
                       for t, r in zip(got, logits))
        np.testing.assert_array_equal(got, want)


# -- the chip smoke's logit tap rides the same hook ---------------------------

def _chip_smoke():
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_logit_tap_keeps_every_row(setup):
    """``chip_smoke.LogitTap`` overrides ``_select_token``; through the
    scheduler it keeps one logit row per token of a greedy and of a
    sampled request, and each token is the one those rows give."""
    model, params, prompts = setup
    sched, _ = _sched(model, params, _chip_smoke().LogitTap,
                      kv_layout="paged", prefill_chunk=8)
    samplers = (GREEDY, TEMP)
    toks = _serve(sched, prompts, samplers)
    for i, (got, smp) in enumerate(zip(toks, samplers)):
        rows = sched.session.logits(f"r{i}")
        assert rows.shape == (N_NEW, model.cfg.vocab_size)
        want = (np.argmax(rows, -1).astype(np.int32)
                if smp.kind == "greedy"
                else _drawn_the_old_way(rows, smp, 1000 + i))
        np.testing.assert_array_equal(got, want)
