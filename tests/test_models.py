"""Per-architecture smoke tests: REDUCED config of the same family, one
forward + one train step on CPU, asserting shapes + no NaNs — plus the
prefill/decode ≡ teacher-forced-forward consistency invariant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_smoke_config
from repro.models import build_model
from repro.train.optimizer import AdamWConfig, adamw
from repro.train.trainer import init_state, make_train_step

# hybrid needs ≥3 layers to exercise the full (rec, rec, attn) pattern
_SMOKE_KW = {"recurrentgemma-9b": {"layers": 3}}


def _batch(model, rng, b=2, s=12):
    cfg = model.cfg
    ks = jax.random.split(rng, 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size, jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            ks[2], (b, cfg.encoder.num_positions, cfg.encoder.d_model),
            jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[3], (b, cfg.encoder.num_positions, cfg.encoder.d_model),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_smoke_config(arch, **_SMOKE_KW.get(arch, {}))
    model = build_model(cfg)
    params = model.init_params(rng)
    batch = _batch(model, rng)
    b, s = batch["tokens"].shape

    logits, aux = model.forward(params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), "NaN in forward logits"

    opt = adamw(AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10))
    step = make_train_step(model, opt)
    state = init_state(model, rng, opt)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state["step"]) == 1
    # params actually moved
    moved = jax.tree.reduce(
        lambda a, leaf: a + float(jnp.sum(jnp.abs(leaf))), state["params"], 0.0)
    assert np.isfinite(moved)


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_decode_consistency(arch, rng):
    """prefill(S−1) + decode_step(last) ≡ forward(S)[-1] — exercises every
    cache implementation (dense KV, MoE, SSD state, RG-LRU ring buffer,
    whisper cross-attention)."""
    cfg = get_smoke_config(arch, **_SMOKE_KW.get(arch, {}))
    model = build_model(cfg)
    params = model.init_params(rng)
    batch = _batch(model, rng, b=2, s=8)
    logits, _ = model.forward(params, batch)

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :-1]
    cache, _ = model.prefill(params, pre, 8)
    _, dec_logits = model.decode_step(params, cache, batch["tokens"][:, -1:])
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(logits[:, -1]), atol=2e-4)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "recurrentgemma-9b"])
def test_subquadratic_state_is_o1_in_max_len(arch, rng):
    """The long_500k designation: cache size must not grow with max_len."""
    cfg = get_smoke_config(arch, **_SMOKE_KW.get(arch, {}))
    model = build_model(cfg)
    c1 = model.init_cache(2, 64)
    c2 = model.init_cache(2, 65536)
    sz = lambda c: sum(x.size for x in jax.tree.leaves(c))
    assert sz(c1) == sz(c2)


def test_dense_cache_grows_with_max_len(rng):
    cfg = get_smoke_config("qwen2-1.5b")
    model = build_model(cfg)
    sz = lambda c: sum(x.size for x in jax.tree.leaves(c))
    assert sz(model.init_cache(2, 128)) > sz(model.init_cache(2, 64))


def test_input_specs_cover_all_cells():
    """Every (arch × applicable shape) yields well-formed abstract inputs."""
    from repro.configs import dryrun_cells
    for cfg, shape in dryrun_cells():
        model = build_model(cfg)
        specs = model.input_specs(shape)
        assert "tokens" in specs
        if shape.kind == "decode":
            assert "cache" in specs
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_moe_balance_aux_positive(rng):
    cfg = get_smoke_config("granite-moe-1b-a400m")
    model = build_model(cfg)
    params = model.init_params(rng)
    batch = _batch(model, rng)
    _, aux = model.forward(params, batch)
    assert float(aux) > 0.0


def test_moe_capacity_properties():
    pytest.importorskip("hypothesis", reason="property tests need the "
                        "hypothesis dev extra")
    from repro.models.moe import capacity
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @given(st.integers(8, 4096), st.integers(2, 128), st.integers(1, 8))
    @settings(max_examples=50, deadline=None)
    def prop(t, e, k):
        c = capacity(t, e, k)
        assert c % 8 == 0 and c >= 8
        assert c * e >= t * k  # capacity_factor ≥ 1 ⇒ no forced drops

    prop()


# -- per-layer attention kinds ------------------------------------------------

def test_layer_types_are_checked_and_kept_by_reduction():
    from repro.configs import get_config
    cfg = get_config("mellum2-12b-a2.5b")
    assert not cfg.uniform_layers
    assert [cfg.layer_window(i) for i in range(4)] == [1024] * 3 + [None]
    assert cfg.rope_for("full").factor == 16.0
    assert cfg.rope_for("sliding") == (500000.0,) + (None,) * 5
    # a smoke size keeps one whole period of the pattern, and top-k of more
    small = get_smoke_config("mellum2-12b-a2.5b", layers=2)
    assert small.layer_types == ("sliding",) * 3 + ("full",)
    assert small.moe.top_k < small.moe.num_experts
    for bad in (dict(layer_types=("sliding",) * 7),
                dict(layer_types=("local",) * 8),
                dict(sliding_window=None)):
        with pytest.raises(ValueError):
            cfg.replace(**bad)
    # every registered config without the fields is uniform
    assert all(c.uniform_layers for c in ASSIGNED.values())


def test_mixed_layers_serve_with_the_dropless_layer_and_decode_consistently(
        rng, monkeypatch):
    """prefill(S−1) + decode_step(last) ≡ forward(S)[-1] for the mixed
    sliding/full YaRN MoE config, past the window (smoke window 32), with
    the training forward's MoE swapped for the serving layer it is
    compared with (capacity would drop tokens the serving path keeps)."""
    from repro.models import moe, transformer
    cfg = get_smoke_config("mellum2-12b-a2.5b")
    model = build_model(cfg)
    params = model.init_params(rng)
    toks = jax.random.randint(rng, (1, 48), 0, cfg.vocab_size, jnp.int32)
    cache, _ = model.prefill(params, {"tokens": toks[:, :-1]}, 64)
    _, dec = model.decode_step(params, cache, toks[:, -1:])
    monkeypatch.setattr(moe, "moe_ffn", lambda p, c, x: (
        moe.moe_serve(p, c, x)[0], jnp.float32(0)))
    full, _ = transformer.forward(params, cfg, toks)
    unrolled, _ = transformer.forward(params, cfg, toks, scan_layers=False)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-4)
    np.testing.assert_allclose(np.asarray(unrolled), np.asarray(full),
                               atol=2e-4)
