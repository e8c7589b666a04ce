"""Public decode attention: GQA regrouping, cache padding, jit."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_dim, round_up, use_interpret
from repro.kernels.decode_attention.kernel import decode_attention_pallas


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length, *, block_s: int = 128) -> jax.Array:
    """q (B, 1, H, D); k/v (B, S, KV, D); length = valid entries → (B, 1, H, D)."""
    b, one, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, d) if one == 1 else None
    assert qg is not None, "decode attention is single-token"
    sp = round_up(s, block_s)
    # head-major (B, KV, S, D): the kernel's K/V blocks end in (block_s, D)
    kh = pad_dim(jnp.swapaxes(k_cache, 1, 2), 2, sp)
    vh = pad_dim(jnp.swapaxes(v_cache, 1, 2), 2, sp)
    ln = jnp.asarray(length, jnp.int32).reshape(1)
    out = decode_attention_pallas(qg, kh, vh, ln,
                                  block_s=block_s, interpret=use_interpret())
    return out.reshape(b, 1, h, d)
