"""Per-kernel allclose sweeps vs the pure-jnp oracles, across shapes and
dtypes (interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (decode_attention, fused_kv_proj, fused_mlp,
                           fused_rmsnorm, fused_softmax, tiled_matmul)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.fused_kv_proj.ref import kv_proj_ref
from repro.kernels.fused_mlp.ref import fused_mlp_ref
from repro.kernels.fused_rmsnorm.ref import rmsnorm_ref
from repro.kernels.fused_softmax.ref import softmax_ref
from repro.kernels.routed_experts.ops import expert_slots, routed_experts
from repro.kernels.routed_experts.ref import routed_experts_ref
from repro.kernels.tiled_matmul.ref import matmul_ref

_TOL = {jnp.float32: dict(atol=2e-3, rtol=2e-3),
        jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


def _cmp(out, ref, dtype):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 200, 60),
                                   (128, 128, 128), (257, 129, 65)])
def test_tiled_matmul(rng, m, k, n, dtype):
    x = jax.random.normal(rng, (m, k), jnp.float32).astype(dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32).astype(dtype)
    _cmp(tiled_matmul(x, y), matmul_ref(x, y), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d", [(1, 64), (7, 128), (32, 896), (100, 200)])
def test_fused_rmsnorm(rng, rows, d, dtype):
    x = jax.random.normal(rng, (rows, d), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32).astype(dtype)
    _cmp(fused_rmsnorm(x, w), rmsnorm_ref(x, w), dtype)


def test_fused_rmsnorm_nd(rng):
    x = jax.random.normal(rng, (2, 5, 3, 64), jnp.float32)
    w = jnp.ones((64,))
    _cmp(fused_rmsnorm(x, w), rmsnorm_ref(x, w), jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,d,f", [(8, 64, 32), (100, 200, 96), (128, 896, 512)])
def test_fused_mlp(rng, m, d, f, dtype):
    x = jax.random.normal(rng, (m, d), jnp.float32).astype(dtype)
    wg = jax.random.normal(jax.random.PRNGKey(1), (d, f), jnp.float32).astype(dtype)
    wu = jax.random.normal(jax.random.PRNGKey(2), (d, f), jnp.float32).astype(dtype)
    _cmp(fused_mlp(x, wg, wu), fused_mlp_ref(x, wg, wu), dtype)


@pytest.mark.parametrize("m,d,n", [(4, 96, 64), (64, 128, 128)])
def test_fused_kv_proj(rng, m, d, n):
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (m, d), jnp.float32)
    wk = jax.random.normal(ks[1], (d, n), jnp.float32)
    wv = jax.random.normal(ks[2], (d, n), jnp.float32)
    bk = jax.random.normal(ks[3], (n,), jnp.float32)
    bv = jax.random.normal(ks[4], (n,), jnp.float32)
    _cmp(fused_kv_proj(x, wk, wv, bk, bv), kv_proj_ref(x, wk, wv, bk, bv),
         jnp.float32)
    # bias-free path (the F4 QKV merge uses it)
    out = fused_kv_proj(x, wk, wv)
    ref = kv_proj_ref(x, wk, wv, jnp.zeros(n), jnp.zeros(n))
    _cmp(out, ref, jnp.float32)


@pytest.mark.parametrize("rows,d", [(1, 16), (9, 151), (64, 2048)])
def test_fused_softmax(rng, rows, d):
    x = jax.random.normal(rng, (rows, d), jnp.float32) * 5
    _cmp(fused_softmax(x), softmax_ref(x), jnp.float32)
    s = jnp.sum(fused_softmax(x), axis=-1)
    np.testing.assert_allclose(np.asarray(s), 1.0, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv,d,s,length", [
    (4, 2, 32, 64, 1), (4, 2, 32, 64, 40), (8, 1, 64, 300, 300),
    (4, 4, 16, 150, 97),
])
def test_decode_attention_kernel(rng, h, kv, d, s, length, dtype):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 1, h, d), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (2, s, kv, d), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (2, s, kv, d), jnp.float32).astype(dtype)
    out = decode_attention(q, kc, vc, length)
    ref = decode_attention_ref(q, kc, vc, length)
    _cmp(out, ref, dtype)


def test_decode_attention_ignores_entries_beyond_length(rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 1, 4, 16), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 80, 2, 16), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 80, 2, 16), jnp.float32)
    out1 = decode_attention(q, kc, vc, 37)
    kc2 = kc.at[:, 37:].set(1e4)  # garbage beyond the valid length
    vc2 = vc.at[:, 37:].set(-1e4)
    out2 = decode_attention(q, kc2, vc2, 37)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


def test_kernels_fuse_identically_to_model_layers(rng):
    """The fused kernels must be drop-in for the unfused model math — the
    paper's 'same kernels, fewer dispatches' controlled-experiment design."""
    from repro.models import layers as L
    x = jax.random.normal(rng, (4, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128,), jnp.float32)
    np.testing.assert_allclose(np.asarray(fused_rmsnorm(x, w)),
                               np.asarray(L.rmsnorm(x, w)), atol=2e-5)


def test_use_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is the CPU's alone: a TPU compiles the kernels, and
    any other backend is refused rather than silently interpreted."""
    from repro.kernels import common
    try:
        for backend, want in (("cpu", True), ("tpu", False)):
            common.use_interpret.cache_clear()
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert common.use_interpret() is want
        common.use_interpret.cache_clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            common.use_interpret()
    finally:
        common.use_interpret.cache_clear()


def _routing(key, rows, k, n_experts):
    """Each row's k distinct experts (rows 0 and 1 share two) and weights
    that sum to 1."""
    ks = jax.random.split(key, rows + 1)
    ids = jnp.stack([jax.random.permutation(ks[r], n_experts)[:k]
                     for r in range(rows)]).astype(jnp.int32)
    if rows > 1:
        ids = ids.at[1, :2].set(ids[0, :2])
    p = jax.nn.softmax(jax.random.normal(ks[-1], (rows, k)), -1)
    return ids, p


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,held", [(1, None), (3, None), (4, (8, 24))])
def test_routed_experts(rng, rows, held, dtype):
    """The kernel against the gather oracle: one row, rows sharing
    experts, and a held share of the experts (the others' assignments
    left out)."""
    n_e, k, d, f = 32, 4, 128, 256
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (rows, d)).astype(dtype)
    w = [(jax.random.normal(kk, shape) / np.sqrt(shape[1])).astype(dtype)
         for kk, shape in zip(ks[1:4], [(n_e, d, f), (n_e, d, f),
                                        (n_e, f, d)])]
    ids, p = _routing(ks[4], rows, k, n_e)
    lo, hi = held or (0, n_e)
    w = [a[lo:hi] for a in w]
    got = routed_experts(x, ids, p, *w, lo=lo)
    want = routed_experts_ref(x, ids, p, *w, lo=lo)
    assert got.dtype == jnp.float32
    _cmp(got, want, dtype)


def test_expert_slots_read_each_touched_expert_once():
    ids = jnp.array([[5, 2], [2, 9], [40, 5]], jnp.int32)
    p = jnp.array([[0.75, 0.25], [0.5, 0.5], [0.875, 0.125]])
    slots, count, gates = expert_slots(ids, p, n_held=16, lo=0, n_slots=6)
    assert int(count[0]) == 3                       # 40 is not held
    assert slots.tolist() == [2, 5, 9, 9, 9, 9]     # pads repeat the last
    np.testing.assert_allclose(gates, [[0.25, 0.5, 0], [0.75, 0, 0.125],
                                       [0, 0.5, 0], [0, 0, 0], [0, 0, 0],
                                       [0, 0, 0]])
