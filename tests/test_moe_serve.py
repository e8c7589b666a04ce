"""The serving expert layer (``models.moe.moe_serve``): its two paths
give the same layer, no token is dropped, and a layer told which experts
it holds returns their share, the shares adding up to the whole layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.kernels.routed_experts.ref import routed_experts_ref
from repro.models import moe

E, K, D, F = 64, 8, 32, 16
CFG = ModelConfig(name="moe-layer", family="moe", num_layers=1, d_model=D,
                  num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=64,
                  moe=MoEConfig(E, K, F), dtype="float32")
SHARES = [(0, 16), (16, 32), (32, 48), (48, 64)]


@pytest.fixture(scope="module")
def layer():
    p = moe.init_moe_ffn(jax.random.PRNGKey(0), CFG)
    return dict(p, router=p["router"] * 8.0)   # spread the routing


def _x(rows):
    return jax.random.normal(jax.random.PRNGKey(rows), (1, rows, D))


def _share(p, lo, hi):
    return dict(p, **{n: p[n][lo:hi] for n in ("w_gate", "w_up", "w_down")})


def _oracle(p, x):
    """Every row through its own k experts, gathered: the plain layer."""
    w, ids = moe.route(p["router"], CFG, x.reshape(-1, D))
    return routed_experts_ref(x.reshape(-1, D), ids, w, p["w_gate"],
                              p["w_up"], p["w_down"]).reshape(x.shape)


@pytest.mark.parametrize("rows", [1, 8, 24])
def test_serving_layer_is_the_plain_layer(layer, rows):
    """1 and 8 rows take the routed-expert kernel (rows · 8 ≤ 64), 24 the
    grouped products; float32, so they agree to rounding."""
    y, ids = moe.moe_serve(layer, CFG, _x(rows))
    assert ids.shape == (1, rows, K)
    np.testing.assert_allclose(y, _oracle(layer, _x(rows)), atol=1e-5)


@pytest.mark.parametrize("rows", [1, 24])
def test_shares_of_the_held_experts_sum_to_the_layer(layer, rows):
    """Four chips' shares of 16 experts each: the router spans all 64
    on every share, each share adds only its own experts' part, and the
    parts add up to the uncut layer.  One row takes the kernel on each
    share (8 ≤ 16), 24 rows the grouped products."""
    x = _x(rows)
    whole, _ = moe.moe_serve(layer, CFG, x)
    parts = [moe.moe_serve(_share(layer, lo, hi), CFG, x, held=(lo, hi))[0]
             for lo, hi in SHARES]
    assert all(np.abs(np.asarray(p)).max() > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)


def test_a_share_with_the_wrong_weights_is_refused(layer):
    with pytest.raises(ValueError):
        moe.moe_serve(layer, CFG, _x(1), held=(0, 16))


def test_no_token_is_dropped_where_capacity_would(layer):
    """Every token of a 64-token chunk picks experts 0-7 (a router that
    prefers them by far): 64 tokens an expert, where capacity holds
    ``capacity(64, 64, 8)`` = 16.  The serving layer computes them all."""
    p = dict(layer, router=jnp.zeros_like(layer["router"])
             .at[:, :K].set(1.0))
    x = jnp.abs(_x(64))                       # every logit of 0-7 positive
    y, ids = moe.moe_serve(p, CFG, x)
    assert set(np.unique(np.asarray(ids))) == set(range(K))
    assert moe.capacity(64, E, K) < 64
    np.testing.assert_allclose(y, _oracle(p, x), atol=1e-5)
    capped, _ = moe.moe_ffn(p, CFG, x)
    assert np.abs(np.asarray(capped - y)).max() > 1e-2
