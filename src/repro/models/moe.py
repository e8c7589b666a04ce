"""Mixture-of-experts FFN: sort-based capacity dispatch for training,
and a dropless expert layer for serving.

``moe_ffn`` (training) dispatches by capacity:

TPU-friendly formulation: no ragged shapes, no (T, E, C) one-hot tensor.
Tokens are grouped by expert with a stable argsort, truncated to a static
per-expert capacity, gathered into a dense ``(E, C, d)`` block, pushed
through a batched-einsum SwiGLU, and scatter-added back with their router
weights.  Experts shard over the "model" mesh axis (expert parallelism);
the dispatch gather/scatter lower to collectives GSPMD schedules.

``moe_serve`` (every serving step: prefill, extend, decode, verify)
drops no token.  It routes in float32 over every expert, then takes one
of two paths by the step's static shape:

* few rows (``rows · k`` at most the experts held, as in decode): the
  routed-expert Pallas kernel (``kernels/routed_experts``) reads each
  expert the rows picked once, in place, and nothing else;
* many rows (a prefill chunk): the ``rows · k`` assignments sorted by
  expert, grouped with ``jax.lax.ragged_dot`` and no capacity, so an
  expert that many tokens pick computes them all.

The layer is told which experts it holds (``held``): the router keeps
its full width, and the layer returns what its own experts add, as one
share of an expert-parallel layer does before the exchange.

Covers qwen3-moe-235b-a22b (128e top-8), granite-moe-1b-a400m (32e top-8)
and mellum2-12b-a2.5b (64e top-8).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.routed_experts.ops import routed_experts
from repro.sharding.activation import constrain_moe_block

Params = Dict[str, Any]

# an MoE FFN's expert weights, each (E, ...) a layer
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")

CAPACITY_FACTOR = 1.25


def init_moe_ffn(rng, cfg: ModelConfig) -> Params:
    assert cfg.moe is not None
    dt = jnp.dtype(cfg.dtype)
    e = cfg.moe
    d, f = cfg.d_model, e.expert_d_ff
    ks = jax.random.split(rng, 4)
    scale = 1.0 / math.sqrt(d)
    return {
        "router": (jax.random.normal(ks[0], (d, e.num_experts)) * scale).astype(dt),
        "w_gate": (jax.random.normal(ks[1], (e.num_experts, d, f)) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e.num_experts, d, f)) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e.num_experts, f, d)) * (1.0 / math.sqrt(f))).astype(dt),
    }


def capacity(num_tokens: int, num_experts: int, top_k: int,
             factor: float = CAPACITY_FACTOR) -> int:
    c = math.ceil(num_tokens * top_k / num_experts * factor)
    return max(8, ((c + 7) // 8) * 8)  # lane-aligned, never zero


# token-group size for chunked dispatch: routing/sort stay chunk-local so
# the chunk axis shards over "data" and cross-chip token movement lowers to
# the canonical MoE all-to-all instead of a global sort (§Perf iteration 5)
CHUNK_TOKENS = 16384


def _n_chunks(t: int) -> int:
    n = max(1, t // CHUNK_TOKENS)
    # power of two → divides typical data-axis sizes (8, 16, 32)
    while n & (n - 1):
        n &= n - 1
    return n


def moe_ffn(p: Params, cfg: ModelConfig, x: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) → (y (B, S, d), load-balance aux loss).

    Chunked sort-based dispatch: tokens are split into chunks (a real,
    shardable tensor dim); each chunk routes/sorts locally to a per-chunk
    capacity, experts run one grouped einsum over (chunk, expert) blocks.
    """
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = e.top_k
    n_e = e.num_experts
    nc = _n_chunks(t)
    tc = t // nc                                              # tokens/chunk
    cap = capacity(tc, n_e, k)
    xf = x.reshape(nc, tc, d)

    # --- routing (float32 for numerics) ---------------------------------
    logits = jnp.einsum("ntd,de->nte", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (nc, tc, E)
    top_p, top_i = jax.lax.top_k(probs, k)                    # (nc, tc, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalize

    # --- chunk-local sort-based slot assignment --------------------------
    flat_e = top_i.reshape(nc, tc * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    group_start = jax.vmap(
        lambda se: jnp.searchsorted(se, se, side="left"))(sorted_e)
    pos_in_e = jnp.arange(tc * k)[None, :] - group_start
    keep = pos_in_e < cap
    safe_pos = jnp.where(keep, pos_in_e, cap)                 # cap = OOB → drop

    slot_token = order // k                                   # (nc, tc*k)
    slot_gate = jnp.take_along_axis(top_p.reshape(nc, tc * k), order, axis=-1)

    zt = jnp.zeros((nc, n_e, cap), jnp.int32)
    zg = jnp.zeros((nc, n_e, cap), jnp.float32)
    cidx = jnp.broadcast_to(jnp.arange(nc)[:, None], sorted_e.shape)
    dispatch_tok = zt.at[cidx, sorted_e, safe_pos].set(slot_token, mode="drop")
    dispatch_gate = zg.at[cidx, sorted_e, safe_pos].set(slot_gate, mode="drop")

    # --- expert compute (grouped over chunk × expert) ---------------------
    # (nc, E, C, d): the (chunk ↔ expert) exchange is the MoE all-to-all
    xe = jax.vmap(lambda xc, tok: xc[tok])(xf, dispatch_tok)
    xe = constrain_moe_block(xe)
    g = jnp.einsum("necd,edf->necf", xe, p["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("necd,edf->necf", xe, p["w_up"],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    ye = jnp.einsum("necf,efd->necd", h, p["w_down"],
                    preferred_element_type=jnp.float32)       # (nc, E, C, d)
    ye = constrain_moe_block(ye)

    # --- combine ----------------------------------------------------------
    contrib = (ye * dispatch_gate[..., None]).astype(x.dtype)
    y = jax.vmap(lambda tok, c: jnp.zeros((tc, d), x.dtype).at[tok].add(c))(
        dispatch_tok, contrib)
    y = y.reshape(b, s, d)

    # --- Switch-style load-balance aux loss -------------------------------
    # fraction of routed slots per expert × mean router prob per expert
    frac = jnp.mean(jax.nn.one_hot(top_i, n_e, dtype=jnp.float32),
                    axis=(0, 1, 2))
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = n_e * jnp.sum(frac * mean_p) * e.router_aux_weight
    return y, aux


# -- serving --------------------------------------------------------------

def route(router: jax.Array, cfg: ModelConfig, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """x (T, d) → (weights (T, k) float32, expert ids (T, k) int32): float32
    logits over every expert, softmax, top-k, renormalised to sum to 1."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.moe.top_k)
        return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_i


def _grouped(x: jax.Array, top_p: jax.Array, top_i: jax.Array,
             w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
             lo: int, layer=None) -> jax.Array:
    """Every (token, expert) assignment to a held expert, sorted by expert
    and grouped with ``ragged_dot``: no capacity, so no token is dropped.
    → (T, d) float32.

    With ``layer`` the weights are stacks over layers, (L, E_held, ...),
    of which this is layer ``layer``: the products run over the stacks
    flattened to (L·E_held, ...), every group outside this layer's empty,
    so the weights are read in place and no layer's slice is copied."""
    t, k = top_i.shape
    w = [w_gate, w_up, w_down]
    if layer is None:
        w, layer = [a[None] for a in w], 0
    n_layers, n_held = w[0].shape[:2]
    groups = n_layers * n_held
    w = [a.reshape((groups,) + a.shape[2:]) for a in w]
    local = (top_i - lo).reshape(-1)
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local + layer * n_held, groups)   # others last
    order = jnp.argsort(key, stable=True)
    rows = order // k
    sizes = jnp.bincount(key, length=groups + 1)[:groups].astype(jnp.int32)
    xs = x[rows]                                  # (T·k, d)
    g = jax.lax.ragged_dot(xs, w[0], sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, w[1], sizes,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jax.lax.ragged_dot(h, w[2], sizes,
                           preferred_element_type=jnp.float32)
    p = top_p.reshape(-1)[order]
    y = jnp.where(held[order][:, None], y * p[:, None], 0.0)
    return jnp.zeros((t, x.shape[-1]), jnp.float32).at[rows].add(y)


def moe_serve(p: Params, cfg: ModelConfig, x: jax.Array, *,
              held: Optional[Tuple[int, int]] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """The serving expert layer: x (..., d) → (y (..., d), expert ids
    (..., k)).  No token is dropped.

    ``held=(lo, hi)``: the layer holds experts ``[lo, hi)``, whose weights
    are ``p["w_gate"]`` etc. (``hi - lo`` of them); ``p["router"]`` spans
    every expert.  ``y`` is what the held experts add (all of the layer's
    output where they are all held).  The ids are global.

    Where ``p["layer"]`` is set (a paged step's layer scan,
    ``transformer.split_experts``), the expert weights are the stacks of
    every layer and this is layer ``p["layer"]``."""
    e = cfg.moe
    lo, hi = held if held is not None else (0, e.num_experts)
    w = [p[n] for n in EXPERT_WEIGHTS]
    if w[0].shape[-3] != hi - lo:
        raise ValueError(f"held experts [{lo}, {hi}) but weights for "
                         f"{w[0].shape[-3]}")
    layer = p.get("layer")
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    top_p, top_i = route(p["router"], cfg, xf)
    with jax.named_scope("moe_experts"):
        if xf.shape[0] * e.top_k <= hi - lo:
            y = routed_experts(xf, top_i, top_p, *w, lo=lo, layer=layer)
        else:
            y = _grouped(xf, top_p, top_i, *w, lo, layer)
        y = y.astype(x.dtype).reshape(shape)
    return y, top_i.reshape(*shape[:-1], e.top_k)
