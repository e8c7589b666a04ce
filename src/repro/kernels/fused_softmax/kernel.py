"""One-pass row softmax Pallas kernel.

The paper's biggest isolated kernel win (84×, §5.1/Table 16): the naive
WGSL softmax made three HBM passes (max, exp-sum, normalize); the shared-
memory rewrite did one.  TPU analogue: the whole row block sits in VMEM,
max/sum reductions run on the VPU in float32, one HBM round trip — and the
paper's conclusion transfers: after this fix, dispatch overhead (not the
kernel) dominates the decode loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[...] = (e / s).astype(o_ref.dtype)


def softmax_pallas(x: jax.Array, *, block_rows: int = 8,
                   interpret: bool = False) -> jax.Array:
    rows, d = x.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _softmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
