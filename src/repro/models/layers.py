"""Shared neural-net layers used by every architecture family.

Pure-functional JAX: parameters are plain dicts of arrays, every layer is a
function.  Conventions:

* activations:  ``(batch, seq, d_model)``
* attention:    ``(batch, seq, heads, head_dim)``
* KV caches:    ``(batch, max_len, kv_heads, head_dim)`` (per layer; model
                 code stacks a leading layer axis)
* norms/softmax run in float32 and cast back; matmuls accumulate in f32 via
  ``preferred_element_type``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, dtype) -> jax.Array:
    """Truncated-normal fan-in init (matches common LM practice)."""
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.truncated_normal(rng, -2.0, 2.0, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d_model: int, dtype) -> jax.Array:
    return (jax.random.normal(rng, (vocab, d_model)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm (Zhang & Sennrich 2019) — the paper's 6-dispatch decomposition
    (pow, mean, add eps, rsqrt, mul x, mul weight), here fused by XLA."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim//2,) float32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, rope) -> np.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071, as Hugging Face's
    ``rope_type: "yarn"`` computes them), shape (head_dim//2,) float32.

    ``rope`` is a ``configs.base.RopeConfig`` with every YaRN field set.
    Dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency (extrapolation), those that turn fewer
    than ``beta_slow`` times are divided by ``factor`` (interpolation),
    and a linear ramp between the two (ends floored and ceiled) mixes
    them."""
    base, dim = float(rope.theta), head_dim

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(rope.original_max_len
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolate = 1 - ramp
    inv = ((1.0 / (rope.factor * pos_freqs)) * (1 - extrapolate)
           + (1.0 / pos_freqs) * extrapolate)
    return inv.astype(np.float32)


def rope_table(head_dim: int, rope) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim//2,) float32, cos/sin scale) of a
    ``configs.base.RopeConfig``: plain RoPE at ``theta``, or YaRN where
    ``factor`` is set."""
    if rope.factor is None:
        inv = 1.0 / (float(rope.theta) ** (
            np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
        return inv.astype(np.float32), 1.0
    scale = rope.attention_factor
    if scale is None:
        scale = 0.1 * math.log(rope.factor) + 1.0
    return yarn_freqs(head_dim, rope), float(scale)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate ``x (B, S, H, D)`` by position.  ``positions`` is (B, S) or (S,).

    Uses the half-rotation convention (x1,x2 split at D/2) like Llama/Qwen.
    """
    return apply_rope_freqs(x, positions, rope_freqs(x.shape[-1], theta))


def apply_rope_freqs(x: jax.Array, positions: jax.Array, inv: jax.Array,
                     scale=None) -> jax.Array:
    """``apply_rope`` at inverse frequencies ``inv`` (D/2,), cos and sin
    multiplied by ``scale`` where given (YaRN's attention factor)."""
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[:, :, None] * inv[None, None, :]          # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :]                   # (B, S, 1, d/2)
    sin = jnp.sin(ang)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, kv, D) -> (B, S, kv*n_rep, D) for grouped-query attention."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     q_offset: int = 0,
                     window: Optional[int] = None) -> jax.Array:
    """Plain O(S²) causal attention.  q (B,Sq,H,D), k/v (B,Sk,KV,D).

    GQA via *grouped einsum* — the KV head dim stays factored
    (B,Sq,KV,G,D) so no repeated-KV tensor is ever materialized (saves HBM
    traffic and keeps GSPMD shardings propagating cleanly).

    ``q_offset``: absolute position of q[0] relative to k[0] (decode = Sk-1).
    Scalar, or (B,) when every batch row sits at its own offset — the
    speculative-verify regime where each scheduler slot scores its drafted
    span against its own cache length in one dispatch.
    ``window``: optional sliding-window width (local attention).
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    per_row = jnp.ndim(q_offset) == 1
    off = q_offset[:, None] if per_row else q_offset
    qpos = jnp.arange(sq) + off                  # (Sq,) or (B, Sq)
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None, :] <= qpos[..., :, None]   # (Sq, Sk) or (B, Sq, Sk)
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[..., :, None] - window)
    if per_row:
        mask = mask[:, None, None]               # (B, 1, 1, Sq, Sk)
    else:
        mask = mask[None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Bidirectional attention (encoder / cross-attention)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d).astype(jnp.float32)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                        k.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             q_chunk: int = 1024, k_chunk: int = 1024,
                             q_offset: int = 0,
                             window: Optional[int] = None) -> jax.Array:
    """Flash-style online-softmax causal attention with O(q_chunk·k_chunk)
    live memory — the long-sequence prefill path (32k cells).

    Mathematically identical to :func:`causal_attention`; memory-bounded by
    construction.  Scans over K blocks with a running (max, denom, acc) carry.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    sk = k.shape[1]
    n_rep = h // kvh
    scale = 1.0 / np.sqrt(d)
    # pad q/k to chunk multiples
    pq = (-sq) % q_chunk
    pk = (-sk) % k_chunk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_chunk, kp.shape[1] // k_chunk

    g = n_rep
    # q-chunks are a REAL tensor dim (not a lax.map loop) so GSPMD can
    # shard the sequence across chips (context parallelism for prefill);
    # only the KV stream is a sequential scan.  Fully-masked (q,k) block
    # pairs cost dead compute (~2× attention FLOPs) — the price of a
    # spatially shardable q axis.
    qb = qp.reshape(b, nq, q_chunk, kvh, g, d)
    kb = kp.reshape(b, nk, k_chunk, kvh, d)
    vb = vp.reshape(b, nk, k_chunk, kvh, d)

    qpos = (jnp.arange(nq * q_chunk) + q_offset).reshape(nq, q_chunk)
    kpos = jnp.arange(nk * k_chunk).reshape(nk, k_chunk)

    def kv_step(carry, xs):
        m, l, acc = carry                           # (B,nq,KV,G,qc) ...
        kblk, vblk, kpb = xs                        # (B,kc,KV,D), (kc,)
        s = jnp.einsum("bnqhgd,bkhd->bnhgqk", qb, kblk,
                       preferred_element_type=jnp.float32) * scale
        msk = kpb[None, None, :] <= qpos[:, :, None]     # (nq,qc,kc)
        if window is not None:
            msk = msk & (kpb[None, None, :] > qpos[:, :, None] - window)
        s = jnp.where(msk[None, :, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bnhgqk,bkhd->bnhgqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nq, kvh, g, q_chunk), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, nq, kvh, g, q_chunk), jnp.float32)
    a0 = jnp.zeros((b, nq, kvh, g, q_chunk, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        kv_step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kpos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,nq,KV,G,qc,D)
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5))         # (B,nq,qc,KV,G,D)
    out = out.reshape(b, nq * q_chunk, h, d)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, *,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token attention against a KV cache.

    q (B, 1, H, D);  k/v cache (B, max_len, KV, D);  ``length`` = number of
    valid cache entries (the new token's k/v already written).
    """
    b, _, h, d = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    scale = 1.0 / np.sqrt(d)
    # native-dtype operands + f32 accumulation: collectives and HBM reads
    # move bf16, the MXU still accumulates f32 (§Perf iteration 1)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale  # (B,KV,G,max)
    kpos = jnp.arange(k_cache.shape[1])
    # length may be scalar (uniform decode) or (B,) per-row — the
    # continuous-batching regime where every slot sits at its own position
    valid = kpos[None, :] < length if jnp.ndim(length) == 0 else kpos[None, :] < length[:, None]
    if window is not None:
        lo = length - window
        valid = valid & (kpos[None, :] >= (lo if jnp.ndim(lo) == 0
                                           else lo[:, None]))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP (Shazeer 2020): down( silu(x·Wg) ⊙ (x·Wu) )."""
    g = jnp.einsum("...d,df->...f", x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("...d,df->...f", x, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, w_down,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
             w_out: jax.Array, b_out: jax.Array) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, w_in,
                   preferred_element_type=jnp.float32) + b_in.astype(jnp.float32)
    h = jax.nn.gelu(h).astype(x.dtype)
    return (jnp.einsum("...f,fd->...d", h, w_out,
                       preferred_element_type=jnp.float32)
            + b_out.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Fixed sinusoidal table (n, d) float32 — Whisper-style."""
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    tbl = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(tbl, jnp.float32)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-level CE.  logits (B,S,V) any float dtype; labels (B,S) int."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def causal_conv1d(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv.  x (B, S, C), w (C, K).  Output (B, S, C)."""
    b, s, c = x.shape
    k = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # windows: y[t, c] = sum_j x[t-k+1+j, c] * w[c, j]
    idx = jnp.arange(s)[:, None] + jnp.arange(k)[None, :]      # (S, K)
    win = xp[:, idx, :]                                        # (B, S, K, C)
    return jnp.einsum("bskc,ck->bsc", win.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)
