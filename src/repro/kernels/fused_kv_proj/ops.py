"""Public fused K+V projection: concatenates weights, pads, jits."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_dim, round_up, use_interpret
from repro.kernels.fused_kv_proj.kernel import kv_proj_pallas


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def fused_kv_proj(x: jax.Array, wk: jax.Array, wv: jax.Array,
                  bk: jax.Array | None = None, bv: jax.Array | None = None, *,
                  block_m: int = 128, block_n: int = 128,
                  block_k: int = 128) -> jax.Array:
    """Returns concat([x·Wk+bk, x·Wv+bv], -1); split is a (free) shape op."""
    wkv = jnp.concatenate([wk, wv], axis=-1)
    if bk is None:
        bkv = jnp.zeros((wkv.shape[-1],), x.dtype)
    else:
        bkv = jnp.concatenate([bk, bv])
    return kv_proj(x, wkv, bkv, block_m=block_m, block_n=block_n,
                   block_k=block_k)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def kv_proj(x: jax.Array, wkv: jax.Array, bkv: jax.Array, *,
            block_m: int = 128, block_n: int = 128,
            block_k: int = 128) -> jax.Array:
    """x (..., D) @ wkv (D, N) + bkv (N,) on the kernel, padded to blocks."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    n = wkv.shape[-1]
    x2 = x.reshape(rows, d)
    mp, kp, np_ = round_up(rows, block_m), round_up(d, block_k), round_up(n, block_n)
    out = kv_proj_pallas(
        pad_dim(pad_dim(x2, 0, mp), 1, kp),
        pad_dim(pad_dim(wkv, 0, kp), 1, np_),
        pad_dim(bkv, 0, np_),
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=use_interpret())
    return out[:rows, :n].reshape(*shape[:-1], n)
