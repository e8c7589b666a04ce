"""Seeded random weights for a Qwen2-style decoder, made on the device.

The benchmark owns its weights: ``make_weights`` draws every leaf from
``--seed`` in ONE jitted call, in the dtype the model is served in, and
both the program (through ``harness.program_params``) and the plain
reference (``bench/reference``) are handed the same values, each by
calling this function.  The layout is the plain one, stacked over layers:

    embed (V, d)            tied: the unembedding is ``embed.T``
    attn_norm, mlp_norm     (L, d)
    wq (L, d, H*hd)  bq (L, H*hd)   wk, wv (L, d, KV*hd)  bk, bv (L, KV*hd)
    wo (L, H*hd, d)
    w_gate, w_up (L, d, F)  w_down (L, F, d)
    final_norm (d,)

Scales (random weights, so chosen, not published):
  * matrices ~ N(0, 1/fan_in), except wq and wk at 1.5x that, so an
    attention score has a spread of about 2 and a query attends to a few
    positions rather than averaging all of them; a wrong or stale KV
    block then moves the output instead of vanishing into a mean;
  * biases ~ N(0, 0.1) and norm weights ~ U(0.8, 1.2), so both paths carry
    numbers that matter;
  * embed ~ N(0, (4 / sqrt(d))^2): logits spread by about 4 after the
    final norm, and the token identity survives the first layer.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

QK_GAIN = 1.5
BIAS_STD = 0.1
NORM_RANGE = (0.8, 1.2)
LOGIT_STD = 4.0


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the weights need, from a configuration's ``config`` dict
    (Hugging Face key names)."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": h,
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def shapes(cfg: Dict) -> Dict[str, tuple]:
    n = dims(cfg)
    L, d, F = n["L"], n["d"], n["F"]
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return {"embed": (n["V"], d), "attn_norm": (L, d), "wq": (L, d, q),
            "bq": (L, q), "wk": (L, d, kv), "bk": (L, kv), "wv": (L, d, kv),
            "bv": (L, kv), "wo": (L, q, d), "mlp_norm": (L, d),
            "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
            "final_norm": (d,)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jnp.asarray([seed >> 32 & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def _draw(key, name: str, shape: tuple, d: int):
    if name.endswith("norm"):
        lo, hi = NORM_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name.startswith("b"):
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return LOGIT_STD / np.sqrt(d) * jax.random.normal(key, shape,
                                                          jnp.float32)
    fan_in = shape[-2]
    gain = QK_GAIN if name in ("wq", "wk") else 1.0
    return gain / np.sqrt(fan_in) * jax.random.normal(key, shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _maker(shape_items: tuple, d: int, dtype: str):
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shape_items):
            k = jax.random.fold_in(key, i)
            out[name] = _draw(k, name, shape, d).astype(dtype)
        return out
    return jax.jit(make)


def make_weights(cfg: Dict, seed: int, dtype: str = "bfloat16"
                 ) -> Dict[str, jax.Array]:
    """Every weight, drawn on the default device from ``seed``.  The seed
    is an argument of the compiled call, so one program serves every seed."""
    items = tuple(sorted(shapes(cfg).items()))
    return _maker(items, dims(cfg)["d"], dtype)(seed_key(seed))


def param_count(cfg: Dict) -> int:
    return int(sum(np.prod(s) for s in shapes(cfg).values()))
