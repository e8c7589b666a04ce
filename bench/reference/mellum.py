"""Plain float32 Mellum 2 forward pass, the benchmark's reference.

Written from the published configuration (Hugging Face
``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``) in
straightforward ``jax.numpy``, every matrix product at
``Precision.HIGHEST``: RMSNorm before attention and MLP; grouped-query
attention without biases; rotary embeddings (half rotation) on q and k,
plain at ``rope_theta`` on the sliding layers and YaRN on the full ones
(arXiv:2309.00071, as Hugging Face's ``rope_type: "yarn"`` computes it:
inverse frequencies ramped between extrapolation and interpolation by
``factor``, cos and sin scaled by ``attention_factor``); causal softmax
attention, where a sliding layer's query at position p sees keys j with
p - window < j <= p; a sparse MLP in every layer: float32 router logits,
softmax over every expert, the top k renormalised to sum to 1, each
chosen SwiGLU expert weighted by its share; final RMSNorm and an untied
unembedding.  No cache, no batching, no kernels: one sequence, every
position at once, and every expert computed on every position (the
weights of the experts a token did not choose are 0).  It imports
nothing of the program.

Where it departs from the published model: the multi-token-prediction
head is left out (serving reads the next-token logits only); no q/k norm
(the configuration has no key for one); the routing is a softmax (the
configuration names no other scoring function); the weights are random.

Weights are the layout of ``bench/families/mellum.py``; they may be
stored in a narrower type and are widened to float32 layer by layer (the
8-layer cut's float32 weights would be 15.2 GB, one layer is 1.7 GB).

``quant="int8"`` computes the linear products in int8, for the control
that the comparison must fail: both operands of every matrix product with
a weight (q, k, v, o, the router, each expert's gate, up and down, and
the unembedding) are rounded to int8, symmetric with 127 levels, the
weight with one scale per output channel and the activation with one
per token; the embedding rows are the rounded weight's.  Products are
accumulated, and attention, norms, softmaxes and residuals computed, in
float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# each matrix and its input axis, the one its per-output-channel scale
# spans
MATRICES = {"wq": 0, "wk": 0, "wv": 0, "wo": 0, "router": 0,
            "w_gate": 1, "w_up": 1, "w_down": 1}
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def quantize(w: jax.Array, kind: Optional[str], axis: int) -> jax.Array:
    """Round ``w`` (float32) to ``kind``, one scale for each slice along
    ``axis``: the axis that each scale's largest magnitude is taken over
    (a weight's input axis, an activation's features)."""
    if kind is None:
        return w
    if kind != "int8":
        raise ValueError(f"unknown quant {kind!r}")
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(hd: int, rope: Dict) -> np.ndarray:
    """Inverse frequencies (hd/2,) of one ``rope_parameters`` entry."""
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freqs
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    orig = rope["original_max_position_embeddings"]

    def turns_dim(turns):          # the dimension that turns `turns` times
        return hd * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(
            base))

    low = max(math.floor(turns_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rope["beta_slow"])), hd - 1)
    high = high + 0.001 if high == low else high
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp                # 1: the original frequency
    return keep / pos_freqs + (1 - keep) / (rope["factor"] * pos_freqs)


def rope(x, positions, inv, scale):
    """x (S, heads, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def experts(xn, p, c, act):
    """The sparse MLP over x (S, d): route, then every expert on every
    position weighted by its renormalised share (0 if not chosen)."""
    s = xn.shape[0]
    probs = jax.nn.softmax(jnp.dot(xn, p["router"], precision=HI), -1)
    top_p, top_i = jax.lax.top_k(probs, c["k"])
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    share = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], top_i].set(
        top_p)                                             # (S, E)
    g = jnp.einsum("sd,edf->sef", xn, p["w_gate"], precision=HI)
    u = jnp.einsum("sd,edf->sef", xn, p["w_up"], precision=HI)
    a = quantize(jax.nn.silu(g) * u, act, -1)
    return jnp.einsum("sef,efd->sd", share[..., None] * a, p["w_down"],
                      precision=HI)


def layer(x, p, c, positions, la, act=None):
    """One decoder layer over a whole sequence x (S, d), float32; ``la``
    is the layer's window and rotary table; ``act`` is the kind each
    product's activation is rounded to, if any."""
    s = x.shape[0]
    h, kvh, hd = c["H"], c["KV"], c["hd"]
    xn = quantize(rmsnorm(x, p["attn_norm"], c["eps"]), act, -1)
    q = jnp.dot(xn, p["wq"], precision=HI).reshape(s, h, hd)
    k = jnp.dot(xn, p["wk"], precision=HI).reshape(s, kvh, hd)
    v = jnp.dot(xn, p["wv"], precision=HI).reshape(s, kvh, hd)
    q = rope(q, positions, la["inv"], la["scale"])
    k = rope(k, positions, la["inv"], la["scale"])
    k = jnp.repeat(k, h // kvh, axis=1)           # query head i reads kv
    v = jnp.repeat(v, h // kvh, axis=1)           # head i // (h // kvh)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    seen = ((positions[None, :] <= positions[:, None])
            & (positions[None, :] > positions[:, None] - la["window"]))
    scores = jnp.where(seen[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                   precision=HI)
    o = quantize(o.reshape(s, h * hd), act, -1)
    x = x + jnp.dot(o, p["wo"], precision=HI)
    xn = quantize(rmsnorm(x, p["mlp_norm"], c["eps"]), act, -1)
    return x + experts(xn, p, c, act)


def per_layer(c: Dict) -> Dict:
    """Each layer's window (sequence-long for a full layer) and rotary
    table, stacked over layers."""
    ropes = dict(c["rope"])
    windows, invs, scales = [], [], []
    for kind in c["kinds"]:
        r = dict(ropes[kind])
        windows.append(c["window"] if kind == "sliding" else 2**30)
        invs.append(inv_freq(c["hd"], r))
        scales.append(r.get("attention_factor", 1.0))
    return {"window": jnp.asarray(windows, jnp.int32),
            "inv": jnp.asarray(np.stack(invs), jnp.float32),
            "scale": jnp.asarray(scales, jnp.float32)}


def hidden(w: Dict, c: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """Final normed hidden states (S, d) float32 of one token sequence,
    rounded as ``quant`` rounds a product's activation, and the (possibly
    rounded) float32 unembedding (d, V)."""
    embed = quantize(w["embed"].astype(jnp.float32), quant, axis=1)
    x = embed[tokens]
    positions = jnp.arange(tokens.shape[0])
    names = [n for n in w if n not in ("embed", "lm_head", "final_norm")]

    def body(x, xs):
        lp, la = xs
        lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
        for n, axis in MATRICES.items():
            lp[n] = quantize(lp[n], quant, axis=axis)
        return layer(x, lp, c, positions, la, quant), None

    x, _ = jax.lax.scan(body, x, ({n: w[n] for n in names}, per_layer(c)))
    h = rmsnorm(x, w["final_norm"].astype(jnp.float32), c["eps"])
    head = quantize(w["lm_head"].astype(jnp.float32), quant, axis=0)
    return quantize(h, quant, -1), head


def consts(cfg: Dict) -> Dict:
    """The numbers the forward pass reads, from a configuration's
    ``config`` dict (Hugging Face key names); hashable."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this reference implements the untied unembedding")
    if cfg.get("attention_bias", False):
        raise ValueError("this reference has no attention biases")
    if not cfg["norm_topk_prob"]:
        raise ValueError("this reference renormalises the top-k weights")
    rope = tuple(sorted((KINDS[kind], tuple(sorted(p.items())))
                        for kind, p in cfg["rope_parameters"].items()))
    return {"H": h, "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "eps": float(cfg["rms_norm_eps"]),
            "k": int(cfg["num_experts_per_tok"]),
            "window": int(cfg["sliding_window"]),
            "kinds": tuple(KINDS[t] for t in cfg["layer_types"]),
            "rope": rope}


def logits(w: Dict, cfg: Dict, tokens, quant: Optional[str] = None):
    """Every position's logits (S, V) float32; for small sizes."""
    h, head = hidden(w, consts(cfg), jnp.asarray(tokens, jnp.int32), quant)
    return jnp.dot(h, head, precision=HI)


ROW_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("c_items", "quant"))
def _gaps(w, tokens, targets, *, c_items, quant):
    c = dict(c_items)
    h, head = hidden(w, c, tokens)
    hc, head_c = (h, head) if quant is None else hidden(w, c, tokens, quant)
    s, d = h.shape
    nb = s // ROW_BLOCK

    def block(args):
        hb, hcb, tb = args
        ref = jnp.dot(hb, head, precision=HI)              # (rows, V)
        best = jnp.max(ref, -1)
        if quant is None:
            pick = tb
        else:
            pick = jnp.argmax(jnp.dot(hcb, head_c, precision=HI), -1)
        return best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]

    out = jax.lax.map(block, (h.reshape(nb, ROW_BLOCK, d),
                              hc.reshape(nb, ROW_BLOCK, d),
                              targets.reshape(nb, ROW_BLOCK)))
    return out.reshape(s)


def bucket(n: int) -> int:
    """Padded length for a sequence of n tokens: the next power of two,
    at least one row block, so a few compiled programs serve every run."""
    return max(ROW_BLOCK, 1 << max(0, int(n - 1).bit_length()))


def token_gaps(w: Dict, cfg: Dict, prompt: np.ndarray, served: np.ndarray,
               quant: Optional[str] = None) -> np.ndarray:
    """How far below the reference's best logit each served token lies.

    ``served[i]`` was produced at position ``len(prompt) - 1 + i`` of the
    sequence ``prompt + served[:-1]``; the result has one entry per served
    token, 0 where it is the reference's own choice.  With ``quant`` the
    served tokens are ignored: each position reads the gap of the token
    that the lower-precision forward puts first.
    """
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    seq = np.concatenate([prompt, served[:-1]])
    n = bucket(len(seq))
    toks = np.zeros(n, np.int32)
    toks[:len(seq)] = seq
    tgt = np.zeros(n, np.int32)
    start = len(prompt) - 1
    tgt[start:start + len(served)] = served
    g = _gaps(w, jnp.asarray(toks), jnp.asarray(tgt),
              c_items=tuple(sorted(consts(cfg).items())), quant=quant)
    return np.asarray(g)[start:start + len(served)]
