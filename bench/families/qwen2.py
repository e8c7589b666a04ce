"""Qwen2 / Qwen2.5 (``model_type`` "qwen2"): a dense grouped-query
decoder with a bias on q, k and v, SwiGLU MLP and a tied unembedding.

The weight layout is the plain one, stacked over layers:

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

Costs: a decode step reads every weight once, reads each active row's
live KV once and writes the new token's K/V; K and V are full-width in
every layer.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

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


def draw(key, name: str, shape: tuple, cfg: Dict):
    """One leaf's values, float32, by the scale rules above."""
    if name.endswith("norm"):
        lo, hi = NORM_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name.startswith("b"):
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        d = dims(cfg)["d"]
        return LOGIT_STD / np.sqrt(d) * jax.random.normal(key, shape,
                                                          jnp.float32)
    fan_in = shape[-2]
    gain = QK_GAIN if name in ("wq", "wk") else 1.0
    return gain / np.sqrt(fan_in) * jax.random.normal(key, shape, jnp.float32)


def param_count(cfg: Dict) -> int:
    return int(sum(np.prod(s) for s in shapes(cfg).values()))


def program_config(cfg: Dict) -> Dict:
    """The program's ``ModelConfig`` fields that this configuration fixes."""
    n = dims(cfg)
    return {"num_layers": n["L"], "d_model": n["d"], "num_heads": n["H"],
            "num_kv_heads": n["KV"], "resolved_head_dim": n["hd"],
            "d_ff": n["F"], "vocab_size": n["V"],
            "rope_theta": float(cfg["rope_theta"]),
            "rms_eps": float(cfg["rms_norm_eps"]),
            "tie_embeddings": bool(cfg["tie_word_embeddings"]),
            "qkv_bias": True, "qk_norm": False, "sliding_window": None}


def program_params(w: Dict) -> Dict:
    """The weights in the program's parameter tree (``transformer``)."""
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "blocks": {"attn_norm": w["attn_norm"], "ffn_norm": w["mlp_norm"],
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                                  "bq", "bk", "bv")},
                       "ffn": {k: w[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}}


def _layer_matmul_params(cfg: Dict) -> int:
    """Weights of one layer that multiply every token (q, k, v, o, MLP)."""
    n = dims(cfg)
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return n["d"] * (q + 2 * kv) + q * n["d"] + 3 * n["d"] * n["F"]


def kv_bytes_per_token(cfg: Dict, itemsize: int = 2) -> int:
    """K and V of one position over every layer."""
    n = dims(cfg)
    return n["L"] * 2 * n["KV"] * n["hd"] * itemsize


def _attn_flops(cfg: Dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, every layer."""
    n = dims(cfg)
    return n["L"] * 4 * n["H"] * n["hd"] * keys


def decode_step(cfg: Dict, ctx: Iterable[int], itemsize: int = 2
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one batched decode step; ``ctx[r]`` is the number
    of positions row r attends over, its new token included."""
    ctx = [int(c) for c in ctx]
    n = dims(cfg)
    per_row = 2 * n["L"] * _layer_matmul_params(cfg) + 2 * n["d"] * n["V"]
    flops = len(ctx) * per_row + sum(_attn_flops(cfg, c) for c in ctx)
    nbytes = param_count(cfg) * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * sum(ctx)
    return flops, nbytes


def prefill_chunk(cfg: Dict, pos0: int, valid: int, itemsize: int = 2
                  ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill chunk of ``valid`` tokens starting at
    position ``pos0``: causal attention over the prefix, logits of the
    last position only."""
    n = dims(cfg)
    keys = valid * pos0 + valid * (valid + 1) // 2
    flops = (2 * n["L"] * _layer_matmul_params(cfg) * valid
             + _attn_flops(cfg, keys) + 2 * n["d"] * n["V"])
    nbytes = param_count(cfg) * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * (pos0 + valid)
    return flops, nbytes


def mlp_step(cfg: Dict, rows: float, itemsize: int = 2
             ) -> Tuple[float, int]:
    """(FLOPs, bytes) of one decode step's MLP over ``rows`` rows: the
    gate, up and down weights (3·d·F per layer) read once."""
    n = dims(cfg)
    w = 3 * n["d"] * n["F"] * n["L"]
    return 2 * w * rows, w * itemsize
