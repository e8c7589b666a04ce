"""Shared kernel utilities: interpret-mode selection and padding helpers.

TPU is the target.  On a TPU backend every ``pallas_call`` lowers to
Mosaic.  On the CPU backend (the test suite runs there with
``JAX_PLATFORMS=cpu``) kernels execute under ``interpret=True``, the
kernel body running as JAX ops, for correctness validation only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.lru_cache(None)
def use_interpret() -> bool:
    """True on the CPU backend, False on a TPU; any other backend is an
    error, so a kernel never silently interprets on an accelerator."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on the {backend!r} "
                           "backend; use a TPU, or the CPU in interpret mode")
    return backend == "cpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_dim(x: jax.Array, axis: int, target: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
