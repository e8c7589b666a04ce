"""Fast in-process coverage for ``repro.dist`` (1 device, no subprocess).

The multi-device behaviour is exercised under ``-m slow`` in test_dist.py;
these tests pin down the pure math (quantization, error feedback, bubble
accounting) and the degenerate 1-device paths so the subsystem stays in
the tier-1 loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.compression import (compress_gradients, dequantize_int8,
                                    quantize_int8)
from repro.dist.pipeline import (PipelineStats, bubble_fraction,
                                 pipeline_apply, pipeline_stats)


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_quantize_dequantize_round_trip(rng):
    x = jax.random.normal(rng, (16, 64)) * 3.0
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    assert scale.shape == (16, 1)
    deq = dequantize_int8(q, scale)
    # symmetric round-to-nearest: error ≤ half a quantization step per elem
    step = np.asarray(scale)
    assert np.max(np.abs(np.asarray(deq - x)) / step) <= 0.5 + 1e-6
    rel = float(jnp.max(jnp.abs(deq - x)) / jnp.max(jnp.abs(x)))
    assert rel < 1 / 127 + 1e-6


def test_quantize_handles_zero_rows_and_scalars():
    q, s = quantize_int8(jnp.zeros((4, 8)))
    assert not np.any(np.asarray(q))
    q0, s0 = quantize_int8(jnp.float32(2.5))
    assert float(dequantize_int8(q0, s0)) == pytest.approx(2.5, rel=1e-6)


def test_error_feedback_residual_bound(rng):
    grads = {"w": jax.random.normal(rng, (8, 32)),
             "b": jax.random.normal(jax.random.fold_in(rng, 1), (32,))}
    err = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
    for _ in range(3):
        prev = err
        comp, err = compress_gradients(grads, err)
        for g, c, e0, e1 in zip(jax.tree.leaves(grads), jax.tree.leaves(comp),
                                jax.tree.leaves(prev), jax.tree.leaves(err)):
            # residual is exactly what quantization dropped …
            np.testing.assert_allclose(np.asarray(e1),
                                       np.asarray(g + e0) - np.asarray(c),
                                       atol=1e-6)
            # … and stays below one quantization step of the fed-back signal
            _, scale = quantize_int8(g + e0)
            assert float(jnp.max(jnp.abs(e1))) <= float(jnp.max(scale))
            assert float(jnp.max(jnp.abs(e1))) < float(jnp.max(jnp.abs(g)))


def test_compressed_update_tracks_exact_mean(rng):
    """Accumulated compressed gradients converge on the exact sum (the
    error-feedback guarantee), even though each step is lossy."""
    g = jax.random.normal(rng, (4, 64))
    err = jnp.zeros((4, 64))
    acc = jnp.zeros((4, 64))
    n = 8
    for _ in range(n):
        comp, err = compress_gradients(g, err)
        acc = acc + comp
    exact = g * n
    rel = float(jnp.max(jnp.abs(acc - exact)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.01


# ---------------------------------------------------------------------------
# pipeline schedule math
# ---------------------------------------------------------------------------

def test_bubble_fraction_math():
    assert bubble_fraction(1, 1) == 0.0
    assert bubble_fraction(1, 64) == 0.0          # no pipeline → no bubble
    assert bubble_fraction(8, 16) == pytest.approx(7 / 23)
    assert bubble_fraction(8, 1) == pytest.approx(7 / 8)   # serving decode
    # more microbatches amortize the fill/drain cost monotonically
    fracs = [bubble_fraction(8, m) for m in (1, 2, 8, 32, 128)]
    assert fracs == sorted(fracs, reverse=True)
    with pytest.raises(ValueError):
        bubble_fraction(0, 4)


def test_pipeline_stats_row():
    st = pipeline_stats(n_layers=24, n_stages=8, n_micro=16)
    assert st == PipelineStats(8, 3, 16)
    assert st.ticks == 23
    assert st.row() == {"stages": 8, "layers_per_stage": 3, "n_micro": 16,
                        "ticks": 23, "bubble_pct": 30.4}
    with pytest.raises(ValueError):
        pipeline_stats(n_layers=10, n_stages=4, n_micro=2)


def test_pipeline_apply_single_stage_matches_sequential(rng):
    """On the 1-device ("stage",) mesh the same shard_map/ppermute code
    path runs a 1-stage pipeline and must equal the sequential program."""
    mesh = jax.make_mesh((1,), ("stage",))
    n_layers, n_micro, b, d = 3, 4, 2, 16
    w = jax.random.normal(rng, (n_layers, d, d)) / np.sqrt(d)
    x = jax.random.normal(jax.random.fold_in(rng, 7), (n_micro, b, d))
    stage_fn = lambda wi, h: jnp.tanh(h @ wi)
    out = pipeline_apply(w, x, mesh=mesh, stage_fn=stage_fn)
    ref = x
    for i in range(n_layers):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_apply_validates_divisibility(rng):
    mesh = jax.make_mesh((1,), ("stage",))
    w = jax.random.normal(rng, (2, 4, 4))
    x = jax.random.normal(rng, (2, 2, 4))
    with pytest.raises(ValueError):
        pipeline_apply({}, x, mesh=mesh, stage_fn=lambda wi, h: h)
    # 1 stage always divides; a bad leading-axis mix must not
    w_bad = {"a": w, "b": jax.random.normal(rng, (3, 4, 4))}
    with pytest.raises(ValueError):
        pipeline_apply(w_bad, x, mesh=mesh, stage_fn=lambda wi, h: h)


# ---------------------------------------------------------------------------
# elastic shardings on the 1-device mesh
# ---------------------------------------------------------------------------

def test_state_shardings_for_single_device_mesh():
    from jax.sharding import NamedSharding

    from repro.configs import get_smoke_config
    from repro.dist.elastic import state_shardings_for
    from repro.models import build_model

    cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                           d_ff=128, vocab=256)
    model = build_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shapes, sh = state_shardings_for(model, mesh)
    assert set(sh) == {"params", "opt", "step"}
    assert jax.tree.structure(shapes["params"]) == \
        jax.tree.structure(sh["params"])
    for leaf in jax.tree.leaves(sh):
        assert isinstance(leaf, NamedSharding)
    # with the compression hook on, the residual pytree follows params
    shapes_c, sh_c = state_shardings_for(model, mesh, compression=True)
    assert "grad_err" in sh_c and "grad_err" in shapes_c


def test_checkpoint_restore_onto_shardings(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.train import checkpoint as ckpt

    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "step": np.int32(7)}
    ckpt.save(str(tmp_path / "ck"), 5, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, PartitionSpec(None, None)),
          "step": NamedSharding(mesh, PartitionSpec())}
    step, restored = ckpt.restore(str(tmp_path / "ck"), shardings=sh)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    assert restored["w"].sharding == sh["w"]


# ---------------------------------------------------------------------------
# the "dist" serving backend (degenerate 1-stage pipeline in-process)
# ---------------------------------------------------------------------------

def test_dist_backend_registry_and_greedy_parity():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import (InferenceSession, ServeRequest,
                               available_backends, create_backend)
    from repro.serving.backends import get_backend
    from repro.serving.backends.dist import DistBackend

    assert "dist" in available_backends()
    assert get_backend("dist") is DistBackend

    cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                           d_ff=128, vocab=256)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = np.array([[11, 23, 37, 41]], np.int32)
    streams = {}
    for mode in ("model", "dist"):
        backend = create_backend(mode, model, params, batch=1, max_len=16)
        r = InferenceSession(backend).run(
            ServeRequest(prompt=prompt, max_new_tokens=5))
        streams[mode] = r.tokens
        assert backend.capabilities.dispatches_per_token == 1
    np.testing.assert_array_equal(streams["model"], streams["dist"])
    b = create_backend("dist", model, params, batch=1, max_len=16)
    assert b.pipeline_stats().row()["stages"] == len(jax.devices())


def test_train_step_compression_hook(rng):
    """The config opt-in: compressed steps carry the residual in state and
    track the exact-gradient loss trajectory closely."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig, adamw
    from repro.train.trainer import init_state, make_train_step

    cfg = get_smoke_config("qwen2-1.5b", layers=2, d_model=64, heads=4,
                           d_ff=128, vocab=256)
    model = build_model(cfg)
    opt = adamw(AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10))
    batch = {"tokens": jax.random.randint(rng, (4, 16), 0, 256, jnp.int32),
             "labels": jax.random.randint(rng, (4, 16), 0, 256, jnp.int32)}
    losses = {}
    for comp in (False, True):
        state = init_state(model, rng, opt, compression=comp)
        assert ("grad_err" in state) == comp
        fn = jax.jit(make_train_step(model, opt, compression=comp))
        hist = []
        for _ in range(4):
            state, m = fn(state, batch)
            hist.append(float(m["loss"]))
        losses[comp] = hist
        if comp:
            err_max = max(float(jnp.max(jnp.abs(e)))
                          for e in jax.tree.leaves(state["grad_err"]))
            assert 0 < err_max  # residual is live, not dropped
    assert losses[True][-1] < losses[True][0]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-3)


def test_dist_backend_rejects_unsupported_family():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import create_backend

    cfg = get_smoke_config("mamba2-1.3b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dense"):
        create_backend("dist", model, params, batch=1, max_len=8)


def test_dist_backend_refuses_mixed_attention_layers():
    """The stage scans apply one window and RoPE base to every layer, so
    a config that mixes sliding and full layers is refused by name."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving.backends.dist import DistBackend
    cfg = get_smoke_config("qwen2-1.5b").replace(
        layer_types=("sliding", "full"), sliding_window=8)
    with pytest.raises(ValueError, match="every layer alike"):
        DistBackend(build_model(cfg), None)
