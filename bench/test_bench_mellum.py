"""Mellum 2 at a small size on the CPU, on the benchmark's seeded weights:
served through ``Scheduler`` → ``InferenceSession`` → the paged ``model``
backend, its logits agree with the plain float32 reference
(``bench/reference/mellum.py``) over chunked prefill and decode, past the
sliding window; a prefill chunk that sends every token to the same
experts drops none; and a decode step that leaves out an expert, or
routes to one expert fewer, comes out not correct.

The program runs in float32 here, so that rounding stays far below what
a fault moves: float32 products on the CPU against the reference's
``HIGHEST`` products agree to about 1e-6 of the logits' spread (read:
3.6e-5 of 15.7), and each tolerance below is written against that.
"""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs, harness, program_trace as pt, weights
from bench.families import family, mellum
from bench.reference import mellum as reference

import repro.models.moe as moe
from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig, RopeConfig
from repro.models import build_model
from repro.models import layers as L
from repro.serving import (InferenceSession, Scheduler, ServeRequest,
                           create_backend)

ROOT = Path(__file__).resolve().parent
WINDOW = 16
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def small(experts: int, top_k: int):
    """A 4-layer Mellum (one period) as a configuration dict and as the
    program's config, float32."""
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
           "layer_types": KINDS, "mlp_layer_types": ["sparse"] * 4,
           "num_experts": experts, "num_experts_per_tok": top_k,
           "moe_intermediate_size": 32, "norm_topk_prob": True,
           "vocab_size": 512, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": False, "attention_bias": False,
           "sliding_window": WINDOW, "rope_parameters": ROPE,
           "model_type": "mellum"}
    mcfg = ModelConfig(
        name="small-mellum", family="moe", num_layers=4, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
        rope_theta=500000.0, sliding_window=WINDOW,
        layer_types=("sliding",) * 3 + ("full",),
        rope_by_kind=(("full", RopeConfig(500000.0, 16.0, 8192, 32.0, 1.0,
                                          1.2772588722239782)),
                      ("sliding", RopeConfig(500000.0))),
        tie_embeddings=False, moe=MoEConfig(experts, top_k, 32),
        dtype="float32")
    return cfg, mcfg


CFG, MCFG = small(8, 2)
# logits agree to 2e-4 of their largest magnitude: 100x the float32
# agreement read above, and under any fault below
ATOL = 2e-4


class LogitSession(InferenceSession):
    """The program's session, keeping every served token's logits."""

    def __init__(self, backend) -> None:
        super().__init__(backend)
        self.logits = []

    def first(self, a, out):
        self.logits.append(np.asarray(out.logits, np.float64).reshape(-1))
        super().first(a, out)

    def step_row(self, a, out):
        self.logits.append(np.asarray(out.logits, np.float64).reshape(-1))
        return super().step_row(a, out)


def serve(cfg, mcfg, w, prompt, n_new):
    """One request through the paged scheduler: chunks of 16, blocks of
    8.  → (served tokens, their logits (n_new, V))."""
    params = harness.program_params(cfg, w, build_model(mcfg),
                                    jax.random.PRNGKey(0))
    backend = create_backend("model", build_model(mcfg), params, batch=1,
                             max_len=128)
    session = LogitSession(backend)
    sched = Scheduler(session, num_slots=1, kv_layout="paged",
                      prefill_chunk=16, block_size=8)
    sched.submit(ServeRequest(prompt=prompt.reshape(1, -1),
                              max_new_tokens=n_new, request_id="r"))
    out = sched.run()["r"]
    return np.asarray(out.tokens[0]).reshape(-1), np.stack(session.logits)


@pytest.fixture(scope="module")
def served():
    harness.check_program_config(MCFG, CFG)
    w = weights.make_weights(CFG, 2**31 + 11, "float32")
    prompt = np.random.default_rng(1).integers(0, 512, 40).astype(np.int32)
    toks, got = serve(CFG, MCFG, w, prompt, 12)
    seq = np.concatenate([prompt, toks[:-1]])
    return w, prompt, toks, seq, got


def _reference(w, cfg, seq, start):
    return np.asarray(reference.logits(w, cfg, seq), np.float64)[start:]


def test_chunked_prefill_and_decode_match_the_reference(served):
    """Three prefill chunks (40 tokens, chunk 16) then 11 decode steps, to
    a context of 51: past the window of 16, so the sliding layers mask
    what the full layer still sees."""
    w, prompt, toks, seq, got = served
    want = _reference(w, CFG, seq, len(prompt) - 1)
    assert len(seq) > 3 * WINDOW
    assert np.abs(want).max() > 5.0                 # logits of a real spread
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=ATOL * np.abs(want).max())
    gaps = reference.token_gaps(w, CFG, prompt, toks)
    assert gaps.max() < ATOL * np.abs(want).max()


@pytest.mark.parametrize("change", ["no_window", "no_yarn"])
def test_window_and_yarn_move_the_logits(served, change):
    """The served logits would not match a reference that let the sliding
    layers see everything, or rotated the full layer without YaRN: both
    mechanisms are exercised, by more than the tolerance."""
    w, prompt, _, seq, got = served
    cfg = json.loads(json.dumps(CFG))
    if change == "no_window":
        cfg["sliding_window"] = 10**6
    else:
        cfg["rope_parameters"]["full_attention"] = ROPE["sliding_attention"]
    other = _reference(w, cfg, seq, len(prompt) - 1)
    assert np.abs(other - got).max() > 20 * ATOL * np.abs(got).max()


def test_reference_yarn_table_is_the_published_ramp():
    """The reference's own YaRN frequencies at head_dim 16, θ 5e5, factor
    16 over 8192, β 32/1, against the hand-written table of
    ``tests/test_layers.py``: ramp ends floor(2.26)=2 and ceil(4.37)=5."""
    want = [1.0, 0.19392275, 0.03760603, 0.0050137066, 5.3033000e-4,
            1.7140511e-05, 3.3239353e-06, 6.4458658e-07]
    got = reference.inv_freq(16, ROPE["full_attention"])
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_a_chunk_that_picks_the_same_experts_drops_no_token():
    """A router of zeros ties every expert, so every token of a 40-token
    prompt takes experts 0 and 1 (top-k breaks ties by index): 40 tokens
    for one expert, where the training path's capacity holds 16 of a
    16-token chunk's.  Served logits still match the reference, and the
    capacity path would have given other numbers for the same chunk."""
    w = dict(weights.make_weights(CFG, 5, "float32"))
    w["router"] = jnp.zeros_like(w["router"])
    prompt = np.random.default_rng(2).integers(0, 512, 40).astype(np.int32)
    toks, got = serve(CFG, MCFG, w, prompt, 4)
    seq = np.concatenate([prompt, toks[:-1]])
    want = _reference(w, CFG, seq, len(prompt) - 1)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=ATOL * np.abs(want).max())

    p = harness.program_params(CFG, w, build_model(MCFG),
                               jax.random.PRNGKey(0))
    ffn = jax.tree.map(lambda a: a[0], p["blocks"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 64))
    dropless, ids = moe.moe_serve(ffn, MCFG, x)
    assert (np.asarray(ids)[..., :2] == [0, 1]).all()
    assert moe.capacity(16, 8, 2) < 16              # expert 0 overflows
    capped, _ = moe.moe_ffn(ffn, MCFG, x)
    assert np.abs(np.asarray(capped - dropless)).max() > 1e-2


# -- a broken decode step is not correct -------------------------------------
# k = 8 of 16 experts, as the published top-8; the window and traffic of
# ``test_bench_faults.py``'s tiny-b1, float32
FAULT_CFG, FAULT_MCFG = small(16, 8)
# the float32 program's widest gap is rounding: it read 0.0 here (seed 7,
# 2 s window), the same request set with each decode step's first expert
# left out 2.42, routed top-7 1.63, the int8 control 0.50; 0.01 lies far
# from all three
LIMIT = 0.01
TRAFFIC = json.loads((ROOT / "testdata" / "tiny-b1.json").read_text())
ITL = [m for m in json.loads((ROOT.parent / "BENCHMARK.json").read_text())[
    "end_to_end"] if m["name"] == "itl_p95_ms"]


def _drop_first(orig):
    """Decode steps leave out each row's first expert (its largest)."""
    def step(x, top_i, top_p, *w, **kw):
        return orig(x, top_i, top_p.at[:, 0].set(0.0), *w, **kw)
    return step


def _top_seven(orig):
    """Decode steps route each row to its first k-1 experts, renormalised."""
    def step(x, top_i, top_p, *w, **kw):
        p = top_p[:, :-1]
        return orig(x, top_i[:, :-1], p / p.sum(-1, keepdims=True), *w,
                    **kw)
    return step


@pytest.mark.parametrize("fault", [None, _drop_first, _top_seven],
                         ids=["sound", "expert_left_out", "top_seven"])
def test_a_decode_step_missing_an_expert_is_not_correct(monkeypatch, fault):
    """The routed-expert kernel is the decode path (1 row · 8 experts ≤
    16 held); a prefill chunk of 32 tokens takes the grouped path, so the
    fault touches decode steps only."""
    if fault is not None:
        monkeypatch.setattr(moe, "routed_experts", fault(moe.routed_experts))
    out = harness.run(cfg=FAULT_CFG, traffic=TRAFFIC, mcfg=FAULT_MCFG,
                      limit=LIMIT, seed=7, seconds=2.0, trace=False,
                      metrics=ITL, t_process=time.perf_counter(),
                      require_tpu=False, dtype="float32")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] == (fault is None), out["checks"]


def test_the_control_is_not_correct():
    s = harness.serve(cfg=FAULT_CFG, traffic=TRAFFIC, mcfg=FAULT_MCFG,
                      seed=7, seconds=2.0, trace=False, metrics=ITL,
                      t_process=time.perf_counter(), require_tpu=False,
                      dtype="float32")
    control = harness.judge(s, cfg=FAULT_CFG, traffic=TRAFFIC, seed=7,
                            dtype="float32", limit=LIMIT, quant="int8")
    assert not control["correct"]


# -- the configuration and its costs ------------------------------------------

def _config():
    return json.loads((ROOT / "configs" / "mellum2-12b-a2.5b.json")
                      .read_text())


def test_configuration_is_the_program_and_the_cut():
    conf = _config()
    cfg = conf["config"]
    harness.check_program_config(get_config(conf["registry"]), cfg)
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "mlp_layer_types"]
    assert cfg["num_hidden_layers"] == 8 and len(cfg["layer_types"]) == 8
    assert mellum.layer_kinds(cfg) == ("sliding",) * 3 + ("full",) \
        + ("sliding",) * 3 + ("full",)
    assert family(cfg).param_count(cfg) == 3_794_966_784
    assert costs.kv_bytes_per_token(cfg) == 16 * 1024


def test_the_top_level_states_the_configuration_that_runs():
    """The file states the published config's keys at its top level, as
    the catalog entry has them, and ``config`` (what the harness serves)
    holds the same keys and values: the two copies cannot drift apart."""
    conf = _config()
    cfg = conf["config"]
    assert {k: conf.get(k) for k in cfg} == cfg
    assert conf["head_dim"] == 128 and conf["num_experts"] == 64


def test_a_decode_step_reads_only_the_routed_experts():
    """At one row a step reads 8 of 64 experts a layer: 1.59 GB in all,
    of which the experts are 0.79 GB (half); all 64 would be 7.14 GB."""
    cfg = _config()["config"]
    n = mellum.dims(cfg)
    expert = 3 * n["d"] * n["F"] * 2                     # bytes, bf16
    flops, nbytes = family(cfg).decode_step(cfg, [1])
    m_flops, m_bytes = mellum.moe_step(cfg, 1)
    assert m_bytes == n["L"] * 8 * expert
    assert m_flops == 2 * n["L"] * 8 * expert // 2
    assert nbytes == 1_587_905_536
    assert 0.49 < m_bytes / nbytes < 0.51
    whole = nbytes + n["L"] * 56 * expert
    assert round(whole / 1e9, 2) == 7.14
    # rows that could touch every expert read each once
    assert mellum.moe_step(cfg, 16)[1] == n["L"] * 64 * expert


def test_the_routing_code_decides_every_layers_experts():
    """Each embedding row codes k of the first E coordinates, which no
    layer writes to; each layer's router reads them through a
    permutation.  So in bf16, on residuals far larger than the code, the
    program routes each token to the coded experts, every layer: no
    rounding can swap the 8th expert for the 9th."""
    w = weights.make_weights(FAULT_CFG, 2**33 + 5, "bfloat16")
    n = mellum.dims(FAULT_CFG)
    e, k = n["E"], n["k"]
    code = np.asarray(w["embed"][:, :e], np.float32)
    assert ((code > 0).sum(1) == k).all() and (code >= 0).all()
    assert not np.asarray(w["wo"][..., :e]).any()
    assert not np.asarray(w["w_down"][..., :e]).any()
    router = np.asarray(w["router"], np.float32)
    assert (router[:, e:] == 0).all()
    perm = router[:, :e].argmax(-1)                      # (L, E)
    assert all(sorted(p) == list(range(e)) for p in perm)
    tokens = np.arange(0, 512, 7)
    noise = 8.0 * jax.random.normal(jax.random.PRNGKey(1),
                                    (len(tokens), n["d"]))
    x = (w["embed"][tokens] + noise.at[:, :e].set(0.0)).astype(jnp.bfloat16)
    for layer in range(n["L"]):
        xn = L.rmsnorm(x, w["mlp_norm"][layer], 1e-6)
        _, ids = moe.route(w["router"][layer], FAULT_MCFG, xn)
        want = [set(perm[layer][np.flatnonzero(c)]) for c in code[tokens]]
        assert [set(r) for r in np.asarray(ids).tolist()] == want


def test_mlp_roofline_of_a_recorded_moe_step_is_a_share():
    """In an MoE layer the ``mlp`` scope holds only ``ffn_norm`` and the
    residual add (the router and the experts are in its inner scopes),
    and the family's ``mlp_step`` counts that, so ``mlp_roofline`` reads
    a share of at most 100%.  Scope times per decode run are those of a
    traced 8-layer step on a TPU v5e (PERF.md, section 5)."""
    cfg = _config()["config"]
    runs = 500
    per_run = {"mlp": 0.007e-3, "moe_route": 0.041e-3,
               "moe_experts": 1.127e-3, "attn": 0.555e-3,
               "unembed": 0.605e-3, "kv_gather": 0.136e-3,
               "kv_write": 0.004e-3}
    ctx = {"trace": {"programs": {pt.DECODE: {"count": runs}}},
           "program_trace": {"scopes": {pt.DECODE: {
               k: v * runs for k, v in per_run.items()}}},
           "tracing": SimpleNamespace(decode=[[300]] * runs), "cfg": cfg,
           "peak": costs.peaks("TPU v5 lite")}
    assert 0 < pt.mlp_roofline(ctx) <= 100
    flops, nbytes = mellum.mlp_step(cfg, 1)
    assert nbytes == 8 * 2304 * 2                 # ffn_norm's weights


def test_program_config_refuses_what_the_program_does_not_do():
    cfg = dict(CFG, norm_topk_prob=False)
    with pytest.raises(ValueError):
        mellum.program_config(cfg)
    with pytest.raises(ValueError):
        reference.consts(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError):
        mellum.layer_kinds(dict(CFG, mlp_layer_types=["dense"] * 4))
