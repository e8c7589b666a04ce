"""Plain float32 Qwen2 / Qwen2.5 forward pass, the benchmark's reference.

Written from the published architecture (arXiv:2407.10671, 2412.15115),
in straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: RMSNorm before attention and MLP, grouped-query
attention with a bias on q, k and v, rotary embeddings (half rotation,
base ``rope_theta``) on q and k, causal softmax attention, SwiGLU MLP,
final RMSNorm and a tied unembedding.  No cache, no batching, no kernels:
one sequence, every position at once.  It imports nothing of the program.

Weights are the layout of ``bench/weights.py``; they may be stored in a
narrower type and are widened to float32 layer by layer.

``quant="int8"`` computes the linear products in int8, for the control
that the comparison must fail: both operands of every matrix product with
a weight (q, k, v, o, gate, up, down and the unembedding) are rounded to
int8, symmetric with 127 levels, the weight with one scale per output
channel and the activation with one per token; the embedding rows are the
rounded weight's.  Products are accumulated, and attention, norms and
residuals computed, in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


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


def rope(x, positions, theta):
    """x (S, heads, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, c, positions, act=None):
    """One decoder layer over a whole sequence x (S, d), float32; ``act``
    is the kind each product's activation is rounded to, if any."""
    s = x.shape[0]
    h, kvh, hd = c["H"], c["KV"], c["hd"]
    xn = quantize(rmsnorm(x, p["attn_norm"], c["eps"]), act, -1)
    q = (jnp.dot(xn, p["wq"], precision=HI) + p["bq"]).reshape(s, h, hd)
    k = (jnp.dot(xn, p["wk"], precision=HI) + p["bk"]).reshape(s, kvh, hd)
    v = (jnp.dot(xn, p["wv"], precision=HI) + p["bv"]).reshape(s, kvh, hd)
    q, k = rope(q, positions, c["theta"]), rope(k, positions, c["theta"])
    k = jnp.repeat(k, h // kvh, axis=1)           # query head i reads kv
    v = jnp.repeat(v, h // kvh, axis=1)           # head i // (h // kvh)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    causal = positions[None, :] <= positions[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                   precision=HI)
    o = quantize(o.reshape(s, h * hd), act, -1)
    x = x + jnp.dot(o, p["wo"], precision=HI)
    xn = quantize(rmsnorm(x, p["mlp_norm"], c["eps"]), act, -1)
    g = jnp.dot(xn, p["w_gate"], precision=HI)
    u = jnp.dot(xn, p["w_up"], precision=HI)
    a = quantize(jax.nn.silu(g) * u, act, -1)
    return x + jnp.dot(a, p["w_down"], precision=HI)


def hidden(w: Dict, c: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """Final normed hidden states (S, d) float32 of one token sequence,
    rounded as ``quant`` rounds a product's activation, and the (possibly
    rounded) float32 embedding that unembeds them."""
    embed = quantize(w["embed"].astype(jnp.float32), quant, axis=1)
    x = embed[tokens]
    positions = jnp.arange(tokens.shape[0])
    names = [n for n in w if n not in ("embed", "final_norm")]

    def body(x, lp):
        lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
        for n in MATRICES:
            lp[n] = quantize(lp[n], quant, axis=0)
        return layer(x, lp, c, positions, quant), None

    x, _ = jax.lax.scan(body, x, {n: w[n] for n in names})
    h = rmsnorm(x, w["final_norm"].astype(jnp.float32), c["eps"])
    return quantize(h, quant, -1), embed


def consts(cfg: Dict) -> Dict:
    """The numbers the forward pass reads, from a configuration's
    ``config`` dict (Hugging Face key names)."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("this reference implements the tied unembedding")
    return {"H": h, "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def logits(w: Dict, cfg: Dict, tokens, quant: Optional[str] = None):
    """Every position's logits (S, V) float32; for small sizes."""
    h, embed = hidden(w, consts(cfg), jnp.asarray(tokens, jnp.int32), quant)
    return jnp.dot(h, embed.T, precision=HI)


ROW_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("c_items", "quant"))
def _gaps(w, tokens, targets, *, c_items, quant):
    c = dict(c_items)
    h, embed = hidden(w, c, tokens)
    hc, embed_c = (h, embed) if quant is None else hidden(w, c, tokens, quant)
    s, d = h.shape
    nb = s // ROW_BLOCK

    def block(args):
        hb, hcb, tb = args
        ref = jnp.dot(hb, embed.T, precision=HI)           # (rows, V)
        best = jnp.max(ref, -1)
        if quant is None:
            pick = tb
        else:
            pick = jnp.argmax(jnp.dot(hcb, embed_c.T, precision=HI), -1)
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
