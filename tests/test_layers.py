"""Layer-level correctness: attention equivalences + hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need the hypothesis dev extra (pip install -r requirements.txt + dev extra)")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import layers as L


def _qkv(key, b, s, h, kv, d):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, s, kv, d), jnp.float32),
            jax.random.normal(ks[2], (b, s, kv, d), jnp.float32))


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (8, 1)])
def test_chunked_equals_plain(rng, h, kv):
    q, k, v = _qkv(rng, 2, 75, h, kv, 16)
    ref = L.causal_attention(q, k, v)
    out = L.chunked_causal_attention(q, k, v, q_chunk=16, k_chunk=32)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_chunked_windowed(rng):
    q, k, v = _qkv(rng, 1, 100, 4, 2, 8)
    ref = L.causal_attention(q, k, v, window=13)
    out = L.chunked_causal_attention(q, k, v, q_chunk=32, k_chunk=16, window=13)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_decode_matches_last_row_of_causal(rng):
    q, k, v = _qkv(rng, 2, 33, 6, 2, 16)
    full = L.causal_attention(q, k, v)
    out = L.decode_attention(q[:, -1:], k, v, 33)
    np.testing.assert_allclose(out[:, 0], full[:, -1], atol=2e-5)


def test_decode_attention_permutation_invariant(rng):
    """Softmax attention is permutation-invariant over (valid) KV entries —
    the property the hybrid ring-buffer cache relies on."""
    q, k, v = _qkv(rng, 1, 24, 4, 2, 8)
    out = L.decode_attention(q[:, -1:], k, v, 24)
    perm = jax.random.permutation(jax.random.PRNGKey(7), 24)
    out_p = L.decode_attention(q[:, -1:], k[:, perm], v[:, perm], 24)
    np.testing.assert_allclose(out, out_p, atol=2e-5)


def test_rope_preserves_norm(rng):
    x = jax.random.normal(rng, (2, 9, 4, 32), jnp.float32)
    y = L.apply_rope(x, jnp.arange(9), 10000.0)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_rope_relative_property(rng):
    """q·k after RoPE depends only on relative distance."""
    d = 32
    q = jax.random.normal(rng, (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))
    def dot_at(p_q, p_k):
        qr = L.apply_rope(q, jnp.array([p_q]), 10000.0)
        kr = L.apply_rope(k, jnp.array([p_k]), 10000.0)
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(5, 3) - dot_at(105, 103)) < 1e-3


def test_causal_conv1d_causality(rng):
    b, s, c, k = 2, 16, 4, 4
    x = jax.random.normal(rng, (b, s, c), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (c, k), jnp.float32)
    y1 = L.causal_conv1d(x, w)
    x2 = x.at[:, 10:].set(99.0)  # poison the future
    y2 = L.causal_conv1d(x2, w)
    np.testing.assert_allclose(y1[:, :10], y2[:, :10], atol=1e-6)


@given(st.integers(1, 4), st.integers(2, 24), st.integers(8, 64))
@settings(max_examples=20, deadline=None)
def test_rmsnorm_scale_invariance(b, s, d):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(b, s, d)),
                    jnp.float32)
    w = jnp.ones((d,))
    y1 = L.rmsnorm(x, w, eps=0.0)
    y2 = L.rmsnorm(3.7 * x, w, eps=0.0)
    np.testing.assert_allclose(y1, y2, atol=1e-4)


def test_cross_entropy_uniform_is_log_v(rng):
    b, s, v = 2, 3, 17
    logits = jnp.zeros((b, s, v))
    labels = jax.random.randint(rng, (b, s), 0, v, jnp.int32)
    loss = L.cross_entropy_loss(logits, labels)
    assert abs(float(loss) - np.log(v)) < 1e-5


def test_cross_entropy_mask(rng):
    b, s, v = 1, 4, 11
    logits = jax.random.normal(rng, (b, s, v))
    labels = jnp.zeros((b, s), jnp.int32)
    m = jnp.array([[1, 1, 0, 0]], jnp.float32)
    full = L.cross_entropy_loss(logits[:, :2], labels[:, :2])
    masked = L.cross_entropy_loss(logits, labels, m)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)


# -- per-layer rotary tables and windows ------------------------------------

def test_yarn_freqs_match_a_hand_table():
    """YaRN at head_dim 16, θ 5e5, factor 16 over 8192, β 32/1.  The ramp
    ends are floor(16·ln(8192/(32·2π)) / (2·ln 5e5)) = floor(2.26) = 2 and
    ceil(16·ln(8192/2π) / (2·ln 5e5)) = ceil(4.37) = 5: dims 0-2 keep
    5e5^(-i/8), dims 5-7 take it over 16, dims 3 and 4 mix the two 2:1
    and 1:2."""
    from repro.configs.base import RopeConfig
    plain = [1.0, 0.19392275, 0.037606031, 7.2926647e-3, 1.4142136e-3,
             2.7424818e-4, 5.3182959e-5, 1.0313385e-5]
    want = plain[:3] + [2 / 3 * plain[3] + 1 / 3 * plain[3] / 16,
                        1 / 3 * plain[4] + 2 / 3 * plain[4] / 16] \
        + [f / 16 for f in plain[5:]]
    rope = RopeConfig(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    inv, scale = L.rope_table(16, rope)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert scale == 1.2772588722239782
    # without an attention factor YaRN's own: 0.1·ln(16) + 1
    _, scale = L.rope_table(16, rope._replace(attention_factor=None))
    assert scale == pytest.approx(1.2772588722239782)


def test_plain_rope_table_is_the_theta_path(rng):
    from repro.configs.base import RopeConfig
    inv, scale = L.rope_table(16, RopeConfig(1e6))
    np.testing.assert_allclose(inv, L.rope_freqs(16, 1e6), rtol=1e-7)
    x = jax.random.normal(rng, (2, 5, 3, 16))
    pos = jnp.arange(5) + 7
    np.testing.assert_allclose(
        L.apply_rope_freqs(x, pos, jnp.asarray(inv), scale),
        L.apply_rope(x, pos, 1e6), atol=1e-6)
    # the scale multiplies the rotated vector
    np.testing.assert_allclose(
        L.apply_rope_freqs(x, pos, jnp.asarray(inv), 2.0),
        2.0 * L.apply_rope(x, pos, 1e6), atol=1e-5)


def test_a_traced_window_masks_as_a_static_one(rng):
    """The layer scan hands each layer its window as an array: the same
    mask as the static int, and a window past every position is none."""
    q, k, v = _qkv(rng, 1, 40, 4, 2, 8)
    np.testing.assert_allclose(
        L.causal_attention(q, k, v, window=jnp.int32(13)),
        L.causal_attention(q, k, v, window=13), atol=1e-6)
    np.testing.assert_allclose(
        L.causal_attention(q, k, v, window=jnp.int32(1 << 30)),
        L.causal_attention(q, k, v), atol=1e-6)
    np.testing.assert_allclose(
        L.decode_attention(q[:, -1:], k, v, 40, window=jnp.int32(13)),
        L.decode_attention(q[:, -1:], k, v, 40, window=13), atol=1e-6)
