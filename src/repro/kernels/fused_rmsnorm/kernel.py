"""Fused RMSNorm Pallas kernel — the paper's flagship fusion (Table 5/7).

WebGPU decomposed RMSNorm into 6 dispatches (pow, mean, add ε, rsqrt,
mul x, mul w); fusing them bought +44% end-to-end on Vulkan.  On TPU the
whole chain is one VMEM-resident pass: a (rows × d) block is loaded once,
the mean-of-squares reduction runs on the VPU in float32, and the scaled
output is written back — one HBM round trip instead of six.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)          # (block_rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                   block_rows: int = 8, interpret: bool = False) -> jax.Array:
    """x (rows, d), w (d,) → (rows, d).  rows must divide by block_rows."""
    rows, d = x.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
