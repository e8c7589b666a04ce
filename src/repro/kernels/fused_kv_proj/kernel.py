"""Fused K+V projection Pallas kernel — the paper's GQA K+V merge (Table 5).

GQA gives K and V identical projection dims, so both are computed by ONE
tiled matmul against the column-concatenated weight [Wk | Wv] with a bias
epilogue.  Removes a dispatch and reads the activation block from HBM once
instead of twice.  The same kernel implements the beyond-paper QKV merge
(F4): just concatenate three weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kv_proj_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def kv_proj_pallas(x: jax.Array, wkv: jax.Array, bkv: jax.Array, *,
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: bool = False) -> jax.Array:
    """x (M, D) @ wkv (D, 2·Nkv) + bkv (2·Nkv,) → (M, 2·Nkv).

    The bias rides as a ``(1, N)`` row so its block ``(1, block_n)`` meets
    Mosaic's tiling for any dtype (a rank-1 bf16 block must span 256).
    """
    m, d = x.shape
    _, n = wkv.shape
    n_k = d // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_kv_proj_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wkv, bkv.reshape(1, n))
