"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
sets ``xla_force_host_platform_device_count`` before first jax init, while
smoke tests must keep seeing 1 device.

Production topology (TPU v5e pods):
* single-pod: (data=16, model=16)            = 256 chips
* multi-pod:  (pod=2, data=16, model=16)     = 512 chips
The ``pod`` axis extends data parallelism across the inter-pod (DCN-ish)
boundary; gradients reduce over ("pod", "data").
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Build a mesh on the first prod(shape) available devices."""
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before any jax import")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def small_test_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Optional[Mesh]:
    """A (2, n//2) mesh when >1 devices are available (subprocess tests)."""
    n = len(jax.devices())
    if n < 2:
        return None
    return make_mesh((2, n // 2), axes)
