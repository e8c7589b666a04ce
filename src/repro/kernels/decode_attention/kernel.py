"""Single-token GQA decode attention Pallas kernel (flash-style).

The batch-1 decode hot loop the paper characterizes: one new query token
attends to a long KV cache.  WGSL cannot express an online-softmax pipeline
across workgroups (no cross-workgroup sync — the paper's mega-kernel
failure, App. C); on TPU the whole reduction is ONE kernel: the grid's
innermost ("arbitrary") axis streams KV blocks through VMEM while running
(max, denom, acc) state lives in VMEM scratch — the classic Flash-Attention
recurrence, MXU-batched over the G = H/KV query heads that share a KV head.

Length masking makes the same kernel serve any cache fill level (decode at
position p attends to p+1 entries of a max_len cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, n_s: int, block_s: int,
                        scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                     # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bs, D)

    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    valid = pos < length                                     # (1, bs)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (G, bs)
    scores = jnp.where(valid, scores, NEG_INF)

    m_old = m_ref[...]                                       # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)                              # (G, bs)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_old - m_new)                            # (G, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            length: jax.Array, *, block_s: int = 128,
                            interpret: bool = False) -> jax.Array:
    """q (B, KV, G, D); k/v (B, KV, S, D); length (1,) int32 → (B, KV, G, D).

    K/V are head-major so each block's last two dimensions are
    ``(block_s, D)``, the (8, 128)-tiled layout Mosaic requires; the
    length is scalar-prefetched into SMEM.
    """
    b, kv, g, d = q.shape
    s = k.shape[2]
    n_s = s // block_s
    scale = 1.0 / (d ** 0.5)
    kv_spec = pl.BlockSpec((1, 1, block_s, d),
                           lambda bb, hh, ss, ln: (bb, hh, ss, 0))
    qo_spec = pl.BlockSpec((1, 1, g, d), lambda bb, hh, ss, ln: (bb, hh, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, n_s=n_s, block_s=block_s,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, n_s),
            in_specs=[qo_spec, kv_spec, kv_spec],
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),    # running max
                pltpu.VMEM((g, 1), jnp.float32),    # running denom
                pltpu.VMEM((g, d), jnp.float32),    # running numerator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, q, k, v)
