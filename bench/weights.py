"""Seeded random weights for a configuration's model family, made on the
device.

The benchmark owns its weights: ``make_weights`` draws every leaf of the
family's layout (``bench.families``: ``shapes``, and the scale rules in
``draw``) from ``--seed`` in ONE jitted call, in the dtype the model is
served in, and both the program (through ``harness.program_params``) and
the plain reference (``bench/reference``) are handed the same values,
each by calling this function.  Leaves are drawn in the order of their
names, leaf ``i`` from ``fold_in(key, i)``.
"""
from __future__ import annotations

import functools
import json
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.families import family


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jnp.asarray([seed >> 32 & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


@functools.lru_cache(maxsize=None)
def _maker(draw: Callable, cfg_json: str, shape_items: tuple, dtype: str):
    cfg = json.loads(cfg_json)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shape_items):
            k = jax.random.fold_in(key, i)
            out[name] = draw(k, name, shape, cfg).astype(dtype)
        return out
    return jax.jit(make)


def make_weights(cfg: Dict, seed: int, dtype: str = "bfloat16"
                 ) -> Dict[str, jax.Array]:
    """Every weight, drawn on the default device from ``seed``.  The seed
    is an argument of the compiled call, so one program serves every seed."""
    fam = family(cfg)
    items = tuple(sorted(fam.shapes(cfg).items()))
    return _maker(fam.draw, json.dumps(cfg, sort_keys=True), items,
                  dtype)(seed_key(seed))
