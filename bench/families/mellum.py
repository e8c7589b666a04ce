"""Mellum 2 (``model_type`` "mellum"): a grouped-query decoder whose every
layer is a sparse MLP of SwiGLU experts, with sliding-window and full
attention layers in a fixed pattern, YaRN RoPE on the full layers and an
untied unembedding.

The weight layout, stacked over layers, the experts over a second axis:

    embed (V, d)     lm_head (d, V)     final_norm (d,)
    attn_norm, mlp_norm (L, d)
    wq (L, d, H*hd)  wk, wv (L, d, KV*hd)  wo (L, H*hd, d)
    router (L, d, E)
    w_gate, w_up (L, E, d, F)  w_down (L, E, F, d)   (F: moe_intermediate_size)

Scales (random weights, so chosen, not published):
  * matrices ~ N(0, 1/fan_in), except wq and wk at 1.5x that (as in
    ``qwen2.py``: a query attends to a few positions, so a wrong or stale
    KV block moves the output);
  * norm weights ~ U(0.8, 1.2);
  * embed and lm_head ~ N(0, σ²), σ = 4 / sqrt(d): logits spread by
    about 4 after the final norm, as in ``qwen2.py``;
  * the routing, drawn with a margin (``route_code``).  A router of
    random N(0, 1/d) columns puts the 8th and 9th expert of some tokens
    in a near tie whatever its scale, since its logits and the bf16
    rounding of the residual that feeds them grow together: the program
    then swaps the two for 2-5% of positions, a swap moves the token's
    output by 10-14%, attention spreads it, and over 8 layers the
    widest logit gap of a served window comes near the int8 control's.
    So each token carries a routing code, as a hash layer routes by
    token (Roller et al., arXiv:2106.04426): the first E coordinates of
    its embedding row hold CODE_LEVEL·σ·U(0.5, 1.5) at k of them, drawn
    per token, and 0 at the rest; every layer's router reads only those
    coordinates, coordinate c feeding expert perm_l(c) (a permutation
    drawn per layer) with weight ROUTER_GAIN; wo and w_down write 0
    there, so the code stays the embedding's through every layer.  A
    token's k coded experts then have logits above 0 in every layer and
    the others exactly 0: no rounding swaps them, while which experts a
    step reads still depends on the token.  Their renormalised weights
    are near equal, 0.10-0.15 each (coded logits under 0.5: the
    residual outgrows the code from the first attention on; read at half
    width, 8 layers), so an expert left out, or a top-7 step, moves a
    token's output by about an eighth of the layer's.

Costs: a decode step reads the attention weights, the router, the
experts its rows are routed to (``min(E, rows·k)`` a layer: exact at one
row, whose k experts are distinct; an upper bound for more), the
unembedding and one embedding row per row, each once; every layer holds
its whole context in KV (the arena does not drop what a sliding layer no
longer sees).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QK_GAIN = 1.5
CODE_LEVEL = 2.0
CODE_RANGE = (0.5, 1.5)
ROUTER_GAIN = 0.5
NORM_RANGE = (0.8, 1.2)
LOGIT_STD = 4.0
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the weights need, from a configuration's ``config`` dict
    (Hugging Face key names)."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": h,
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "E": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
            "F": int(cfg["moe_intermediate_size"]),
            "V": int(cfg["vocab_size"])}


def layer_kinds(cfg: Dict) -> Tuple[str, ...]:
    """Each layer's attention kind, "sliding" or "full"."""
    kinds = tuple(KINDS[t] for t in cfg["layer_types"])
    if len(kinds) != dims(cfg)["L"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{dims(cfg)['L']} layers")
    if any(t != "sparse" for t in cfg["mlp_layer_types"]):
        raise ValueError("every layer of this family is a sparse MLP")
    return kinds


def shapes(cfg: Dict) -> Dict[str, tuple]:
    n = dims(cfg)
    L, d, E, F, V = n["L"], n["d"], n["E"], n["F"], n["V"]
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return {"embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
            "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
            "router": (L, d, E), "w_gate": (L, E, d, F),
            "w_up": (L, E, d, F), "w_down": (L, E, F, d)}


def route_code(key, rows: int, experts: int, k: int):
    """(rows, experts) float32: each row's k coded coordinates at
    U(CODE_RANGE), drawn per row, the others 0."""
    pick_key, level_key = jax.random.split(key)
    picked = jax.lax.top_k(jax.random.uniform(pick_key, (rows, experts)),
                           k)[1]
    lo, hi = CODE_RANGE
    level = jax.random.uniform(level_key, (rows, k), jnp.float32, lo, hi)
    return jnp.zeros((rows, experts), jnp.float32).at[
        jnp.arange(rows)[:, None], picked].set(level)


def draw(key, name: str, shape: tuple, cfg: Dict):
    """One leaf's values, float32, by the scale rules above."""
    if name.endswith("norm"):
        lo, hi = NORM_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    n = dims(cfg)
    d, e = n["d"], n["E"]
    sigma = LOGIT_STD / np.sqrt(d)
    if name == "router":
        perm = jnp.argsort(jax.random.uniform(key, (shape[0], e)), axis=-1)
        return jnp.zeros(shape, jnp.float32).at[
            jnp.arange(shape[0])[:, None], jnp.arange(e), perm].set(
                ROUTER_GAIN)
    if name in ("embed", "lm_head"):
        code_key, key = jax.random.split(key)
        w = sigma * jax.random.normal(key, shape, jnp.float32)
        if name == "embed":
            w = w.at[:, :e].set(CODE_LEVEL * sigma * route_code(
                code_key, shape[0], e, n["k"]))
        return w
    fan_in = shape[-2]
    gain = QK_GAIN if name in ("wq", "wk") else 1.0
    w = gain / np.sqrt(fan_in) * jax.random.normal(key, shape, jnp.float32)
    if name in ("wo", "w_down"):
        w = w.at[..., :e].set(0.0)
    return w


def param_count(cfg: Dict) -> int:
    return int(sum(np.prod(s) for s in shapes(cfg).values()))


def _rope(params: Dict) -> tuple:
    """A Hugging Face ``rope_parameters`` entry as the program's
    ``RopeConfig`` fields (theta, factor, original length, beta_fast,
    beta_slow, attention_factor)."""
    if params.get("rope_type", "default") == "default":
        return (float(params["rope_theta"]),) + (None,) * 5
    if params["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    return (float(params["rope_theta"]), float(params["factor"]),
            int(params["original_max_position_embeddings"]),
            float(params["beta_fast"]), float(params["beta_slow"]),
            float(params["attention_factor"]))


def program_config(cfg: Dict) -> Dict:
    """The program's ``ModelConfig`` fields that this configuration fixes
    (the expert shapes are checked leaf by leaf by ``program_params``)."""
    n = dims(cfg)
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k weights")
    rope = cfg["rope_parameters"]
    return {"num_layers": n["L"], "d_model": n["d"], "num_heads": n["H"],
            "num_kv_heads": n["KV"], "resolved_head_dim": n["hd"],
            "vocab_size": n["V"], "rms_eps": float(cfg["rms_norm_eps"]),
            "tie_embeddings": bool(cfg["tie_word_embeddings"]),
            "qkv_bias": bool(cfg["attention_bias"]), "qk_norm": False,
            "sliding_window": int(cfg["sliding_window"]),
            "layer_types": layer_kinds(cfg),
            "rope_by_kind": tuple(sorted(
                (KINDS[kind], _rope(p)) for kind, p in rope.items())),
            "experts_per_token": n["k"]}


def program_params(w: Dict) -> Dict:
    """The weights in the program's parameter tree (``transformer`` with
    ``moe`` FFNs)."""
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": w["final_norm"],
            "blocks": {"attn_norm": w["attn_norm"], "ffn_norm": w["mlp_norm"],
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "ffn": {k: w[k] for k in ("router", "w_gate", "w_up",
                                                 "w_down")}}}


def _attn_params(n: Dict[str, int]) -> int:
    """q, k, v and o of one layer."""
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return n["d"] * (q + 2 * kv) + q * n["d"]


def _expert_params(n: Dict[str, int]) -> int:
    return 3 * n["d"] * n["F"]


def kv_bytes_per_token(cfg: Dict, itemsize: int = 2) -> int:
    """K and V of one position over every layer."""
    n = dims(cfg)
    return n["L"] * 2 * n["KV"] * n["hd"] * itemsize


def _attn_flops(cfg: Dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` positions, every layer."""
    n = dims(cfg)
    return n["L"] * 4 * n["H"] * n["hd"] * keys


def _dense_bytes(n: Dict[str, int], rows: int, itemsize: int) -> int:
    """What a step reads whatever the routing: attention, router and
    norms of every layer, the unembedding, the final norm and one
    embedding row per row."""
    per_layer = _attn_params(n) + n["d"] * n["E"] + 2 * n["d"]
    return (n["L"] * per_layer + n["d"] * n["V"] + n["d"]
            + rows * n["d"]) * itemsize


def moe_step(cfg: Dict, rows: float, itemsize: int = 2
             ) -> Tuple[float, int]:
    """(FLOPs, bytes) of one decode step's routed experts over ``rows``
    rows: each row's k expert SwiGLUs, and the weights of
    ``min(E, rows·k)`` experts a layer read once (the share of the
    ``moe_experts`` scope's time; exact at one row)."""
    n = dims(cfg)
    w = _expert_params(n)
    touched = min(n["E"], int(np.ceil(rows * n["k"])))
    return 2 * w * rows * n["k"] * n["L"], w * touched * n["L"] * itemsize


def mlp_step(cfg: Dict, rows: float, itemsize: int = 2
             ) -> Tuple[float, int]:
    """(FLOPs, bytes) of what one decode step's ``mlp`` scope holds
    itself over ``rows`` rows: in an MoE layer only ``ffn_norm`` and the
    residual add (about 5 FLOPs a feature) and the norm's weights, since
    the router and the experts sit in the scopes ``moe_route`` and
    ``moe_experts`` inside it (``repro.obs.scopes`` gives an op its
    innermost scope).  The experts' costs are ``moe_step``."""
    n = dims(cfg)
    return 5 * n["d"] * rows * n["L"], n["d"] * n["L"] * itemsize


def decode_step(cfg: Dict, ctx: Iterable[int], itemsize: int = 2
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one batched decode step; ``ctx[r]`` is the number
    of positions row r attends over, its new token included."""
    ctx = [int(c) for c in ctx]
    n = dims(cfg)
    rows = len(ctx)
    m_flops, m_bytes = moe_step(cfg, rows, itemsize)
    flops = (rows * (2 * n["L"] * (_attn_params(n) + n["d"] * n["E"])
                     + 2 * n["d"] * n["V"])
             + m_flops + sum(_attn_flops(cfg, c) for c in ctx))
    nbytes = (_dense_bytes(n, rows, itemsize) + m_bytes
              + kv_bytes_per_token(cfg, itemsize) * sum(ctx))
    return flops, nbytes


def prefill_chunk(cfg: Dict, pos0: int, valid: int, itemsize: int = 2
                  ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill chunk of ``valid`` tokens starting at
    position ``pos0``: causal attention over the prefix, each token's k
    experts, the experts the chunk can touch (``min(E, valid·k)`` a layer)
    read once, logits of the last position only."""
    n = dims(cfg)
    keys = valid * pos0 + valid * (valid + 1) // 2
    m_flops, m_bytes = moe_step(cfg, valid, itemsize)
    flops = (2 * n["L"] * (_attn_params(n) + n["d"] * n["E"]) * valid
             + m_flops
             + _attn_flops(cfg, keys) + 2 * n["d"] * n["V"])
    nbytes = (_dense_bytes(n, valid, itemsize) + m_bytes
              + kv_bytes_per_token(cfg, itemsize) * (pos0 + valid))
    return flops, nbytes
