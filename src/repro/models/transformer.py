"""Decoder-only GQA transformer — the dense/MoE/VLM LM backbone.

Covers: qwen1.5-110b, phi3-medium-14b, qwen3-14b, qwen2-1.5b, the paper's
qwen2.5-0.5b/1.5b, the LM backbone of internvl2-1b, and (with ``moe.py``'s
FFN) the two MoE architectures.

Layer parameters are stacked along a leading layer axis and consumed with
``jax.lax.scan`` so the lowered HLO is O(1) in depth — essential for the
94-layer MoE dry-run cells.  ``scan_layers=False`` unrolls (used by the
dispatch-engine reproduction experiments, which need op-level granularity).

Layers of different attention kinds (``ModelConfig.layer_types``: sliding
and full, each with its own rotary settings) stay in the one scan: the
scan carries each layer's window and RoPE table beside its weights
(``attention_layers``).  A config whose layers are all alike carries
nothing and keeps its static window and RoPE base, so its programs are
what they were.  Serving steps run MoE layers through the dropless
``moe.moe_serve``; training keeps ``moe.moe_ffn``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.sharding.activation import constrain_hidden

Params = Dict[str, Any]

# threshold above which prefill switches to the memory-bounded chunked path
CHUNKED_ATTENTION_MIN_SEQ = 8192

# the window of a layer that attends to every position, where windows
# are scanned per layer
NO_WINDOW = 1 << 30


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    d, h = cfg.d_model, cfg.resolved_head_dim
    n_q, n_kv = cfg.num_heads * h, cfg.num_kv_heads * h
    ks = jax.random.split(rng, 4)
    p = {
        "wq": L.dense_init(ks[0], d, n_q, dt),
        "wk": L.dense_init(ks[1], d, n_kv, dt),
        "wv": L.dense_init(ks[2], d, n_kv, dt),
        "wo": L.dense_init(ks[3], n_q, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_q,), dt)
        p["bk"] = jnp.zeros((n_kv,), dt)
        p["bv"] = jnp.zeros((n_kv,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((h,), dt)
        p["k_norm"] = jnp.ones((h,), dt)
    return p


def init_ffn(rng, cfg: ModelConfig) -> Params:
    if cfg.moe is not None:
        return moe_mod.init_moe_ffn(rng, cfg)
    dt = _dtype(cfg)
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": L.dense_init(ks[0], cfg.d_model, cfg.d_ff, dt),
        "w_up": L.dense_init(ks[1], cfg.d_model, cfg.d_ff, dt),
        "w_down": L.dense_init(ks[2], cfg.d_ff, cfg.d_model, dt),
    }


def init_block(rng, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": jnp.ones((cfg.d_model,), dt),
        "attn": init_attention(k1, cfg),
        "ffn_norm": jnp.ones((cfg.d_model,), dt),
        "ffn": init_ffn(k2, cfg),
    }


def init_params(rng, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    k_emb, k_blocks, k_head = jax.random.split(rng, 3)
    block_keys = jax.random.split(k_blocks, cfg.num_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    params: Params = {
        "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dt),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dt)
    return params


# ---------------------------------------------------------------------------
# per-layer attention settings
# ---------------------------------------------------------------------------

def attention_layers(cfg: ModelConfig) -> Optional[Params]:
    """Each layer's attention settings as arrays with a leading layer axis,
    to scan beside ``params["blocks"]``: ``window`` (L,) int32
    (``NO_WINDOW`` for a full layer), ``inv_freq`` (L, head_dim/2) and
    ``rope_scale`` (L,) float32.  None where every layer is alike: the
    layers then use ``cfg.sliding_window`` and ``cfg.rope_theta``
    statically."""
    if cfg.uniform_layers:
        return None
    hd = cfg.resolved_head_dim
    tables = [L.rope_table(hd, cfg.rope_for(cfg.layer_kind(i)))
              for i in range(cfg.num_layers)]
    windows = [cfg.layer_window(i) for i in range(cfg.num_layers)]
    return {"window": jnp.asarray([NO_WINDOW if w is None else w
                                   for w in windows], jnp.int32),
            "inv_freq": jnp.asarray(np.stack([t[0] for t in tables])),
            "rope_scale": jnp.asarray([t[1] for t in tables], jnp.float32)}


def _rope(cfg: ModelConfig, la: Optional[Params], x: jax.Array,
          positions: jax.Array) -> jax.Array:
    """RoPE of one layer: its scanned table ``la``, or ``cfg.rope_theta``."""
    if la is None:
        return L.apply_rope(x, positions, cfg.rope_theta)
    return L.apply_rope_freqs(x, positions, la["inv_freq"], la["rope_scale"])


def _window(cfg: ModelConfig, la: Optional[Params]):
    return cfg.sliding_window if la is None else la["window"]


def split_experts(cfg: ModelConfig, blocks: Params
                  ) -> Tuple[Params, Optional[Params]]:
    """The stacked layer weights as a paged step's layer scan takes them:
    ``(what the scan slices per layer, the MoE expert stacks)``.  The
    expert stacks are not sliced: a slice of a stack that the
    routed-expert kernel reads would be copied first (every expert of the
    layer, 0.79 GB a layer at Mellum2's widths), so the scan slices the
    layer's index (``ffn["layer"]``) in their place and ``join_experts``
    hands the kernel the whole stacks.  A dense config: ``(blocks,
    None)``, unchanged."""
    if cfg.moe is None:
        return blocks, None
    ffn = dict(blocks["ffn"])
    experts = {n: ffn.pop(n) for n in moe_mod.EXPERT_WEIGHTS}
    ffn["layer"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    return dict(blocks, ffn=ffn), experts


def join_experts(p: Params, experts: Optional[Params]) -> Params:
    """One layer's scanned weights with the whole expert stacks back in."""
    if experts is None:
        return p
    return dict(p, ffn=dict(p["ffn"], **experts))


def _layer(params: Params, i: int, la: Optional[Params]):
    """Layer ``i``'s weights and attention settings (unrolled paths)."""
    pick = functools.partial(jax.tree.map, lambda a: a[i])
    return pick(params["blocks"]), None if la is None else pick(la)


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------

def _project_qkv(p: Params, cfg: ModelConfig, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    h = cfg.resolved_head_dim
    q = L.linear(x, p["wq"], p.get("bq"))
    k = L.linear(x, p["wk"], p.get("bk"))
    v = L.linear(x, p["wv"], p.get("bv"))
    q = q.reshape(b, s, cfg.num_heads, h)
    k = k.reshape(b, s, cfg.num_kv_heads, h)
    v = v.reshape(b, s, cfg.num_kv_heads, h)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


def attention_block(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, *, chunked: bool,
                    la: Optional[Params] = None) -> jax.Array:
    """Full-sequence causal self-attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    q = _rope(cfg, la, q, positions)
    k = _rope(cfg, la, k, positions)
    if chunked:
        o = L.chunked_causal_attention(q, k, v, window=_window(cfg, la))
    else:
        o = L.causal_attention(q, k, v, window=_window(cfg, la))
    return L.linear(o.reshape(b, s, -1), p["wo"])


def ffn_block(p: Params, cfg: ModelConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss) — aux is the MoE load-balance loss (0 dense)."""
    if cfg.moe is not None:
        return moe_mod.moe_ffn(p, cfg, x)
    return L.swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), jnp.float32(0.0)


def mlp_residual(p: Params, cfg: ModelConfig, x: jax.Array
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``(x + FFN(ffn_norm(x)), ids)`` of a serving step, under the
    ``mlp`` named scope so the profiler can tell the MLP's ops from the
    rest of the step; the residual add sits inside, so a fusion rooted at
    it still counts to the MLP.  An MoE FFN is the dropless
    ``moe.moe_serve`` and ``ids`` its expert ids (..., k); None for a
    dense FFN."""
    with jax.named_scope("mlp"):
        xn = L.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
        if cfg.moe is None:
            return x + ffn_block(p["ffn"], cfg, xn)[0], None
        f, ids = moe_mod.moe_serve(p["ffn"], cfg, xn)
        return x + f, ids


def block_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, *, chunked: bool,
                  la: Optional[Params] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    h = x + attention_block(p["attn"], cfg,
                            L.rmsnorm(x, p["attn_norm"], cfg.rms_eps),
                            positions, chunked=chunked, la=la)
    h = constrain_hidden(h)  # sequence-parallel boundary (no-op by default)
    f, aux = ffn_block(p["ffn"], cfg, L.rmsnorm(h, p["ffn_norm"], cfg.rms_eps))
    return constrain_hidden(h + f), aux


def unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x, w, preferred_element_type=jnp.float32)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            scan_layers: bool = True, remat: bool = False,
            extra_embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced forward.  tokens (B, S) int32 → (logits (B,S,V), aux).

    ``extra_embeds`` (B, P, d_model): a prefix of precomputed embeddings
    (VLM patch embeddings); logits are returned for the token part only.
    """
    x = params["embed"][tokens]
    prefix = 0
    if extra_embeds is not None:
        prefix = extra_embeds.shape[1]
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    chunked = s >= CHUNKED_ATTENTION_MIN_SEQ

    body = functools.partial(block_forward, cfg=cfg, positions=positions,
                             chunked=chunked)
    per_layer = attention_layers(cfg)
    if scan_layers:
        def scan_body(carry, xs):
            layer_params, la = xs
            fn = body
            if remat:
                fn = jax.checkpoint(
                    lambda p_, x_, la_: body(p_, x=x_, la=la_),
                    policy=jax.checkpoint_policies.nothing_saveable)
                y, aux = fn(layer_params, carry, la)
            else:
                y, aux = fn(layer_params, x=carry, la=la)
            return y, aux
        x, auxs = jax.lax.scan(scan_body, x, (params["blocks"], per_layer))
        aux = jnp.sum(auxs)
    else:
        aux = jnp.float32(0.0)
        n = cfg.num_layers
        for i in range(n):
            layer_params, la = _layer(params, i, per_layer)
            x, a = body(layer_params, x=x, la=la)
            aux = aux + a
    logits = unembed(params, cfg, x)
    if prefix:
        logits = logits[:, prefix:]
    return logits, aux


# ---------------------------------------------------------------------------
# KV cache serving path
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = _dtype(cfg)
    h = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, h)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """ShapeDtypeStruct cache (no allocation) for dry-run lowering."""
    dt = _dtype(cfg)
    h = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, h)
    return {
        "k": jax.ShapeDtypeStruct(shape, dt),
        "v": jax.ShapeDtypeStruct(shape, dt),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def prefill_block(p: Params, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, max_len: int, *,
                  chunked: bool, la: Optional[Params] = None
                  ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer of the cached prefill path → (x', (k_cache, v_cache)).

    Shared by the depth ``lax.scan`` in ``prefill`` and by the
    pipeline-parallel dist backend, which scans it over a per-stage layer
    chunk inside ``shard_map``.
    """
    b, s, _ = x.shape
    h = cfg.resolved_head_dim
    xn = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _project_qkv(p["attn"], cfg, xn)
    q = _rope(cfg, la, q, positions)
    k = _rope(cfg, la, k, positions)
    if chunked:
        o = L.chunked_causal_attention(q, k, v, window=_window(cfg, la))
    else:
        o = L.causal_attention(q, k, v, window=_window(cfg, la))
    x = constrain_hidden(x + L.linear(o.reshape(b, s, -1), p["attn"]["wo"]))
    x = constrain_hidden(mlp_residual(p, cfg, x)[0])
    kc = jnp.zeros((b, max_len, cfg.num_kv_heads, h), k.dtype)
    vc = jnp.zeros_like(kc)
    kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
    return x, (kc, vc)


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            max_len: int, *, extra_embeds: Optional[jax.Array] = None
            ) -> Tuple[Params, jax.Array]:
    """Run the prompt, build the cache.  Returns (cache, last-token logits)."""
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    chunked = s >= CHUNKED_ATTENTION_MIN_SEQ

    def scan_body(carry, xs):
        layer_params, la = xs
        return prefill_block(layer_params, cfg, carry, positions, max_len,
                             chunked=chunked, la=la)

    x, (kcache, vcache) = jax.lax.scan(
        scan_body, x, (params["blocks"], attention_layers(cfg)))
    logits = unembed(params, cfg, x[:, -1:, :])
    cache = {"k": kcache, "v": vcache, "pos": jnp.int32(s)}
    return cache, logits


def decode_block(p: Params, cfg: ModelConfig, x: jax.Array, kc: jax.Array,
                 vc: jax.Array, pos: jax.Array, positions: jax.Array,
                 la: Optional[Params] = None
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer of the single-token decode path → (x', (kc', vc')).

    Shared by the depth ``lax.scan`` in ``decode_step`` and by the
    pipeline-parallel dist backend (per-stage layer chunks under
    ``shard_map``).
    """
    b = x.shape[0]
    xn = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _project_qkv(p["attn"], cfg, xn)
    q = _rope(cfg, la, q, positions)
    k = _rope(cfg, la, k, positions)
    kc = jax.lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
    o = L.decode_attention(q, kc, vc, pos + 1, window=_window(cfg, la))
    x = x + L.linear(o.reshape(b, 1, -1), p["attn"]["wo"])
    return mlp_residual(p, cfg, x)[0], (kc, vc)


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jax.Array) -> Tuple[Params, jax.Array]:
    """One autoregressive step.  tokens (B, 1) → (cache', logits (B,1,V))."""
    x = params["embed"][tokens]
    b = x.shape[0]
    pos = cache["pos"]
    positions = jnp.full((b, 1), pos, jnp.int32)

    def scan_body(carry, scan_in):
        p, kc, vc, la = scan_in
        return decode_block(p, cfg, carry, kc, vc, pos, positions, la)

    x, (kcache, vcache) = jax.lax.scan(
        scan_body, x, (params["blocks"], cache["k"], cache["v"],
                       attention_layers(cfg)))
    logits = unembed(params, cfg, x)
    return {"k": kcache, "v": vcache, "pos": pos + 1}, logits


def decode_core_rows(p: Params, cfg: ModelConfig, x: jax.Array,
                     kc: jax.Array, vc: jax.Array, pos: jax.Array, *,
                     emit_cache: bool = True, la: Optional[Params] = None):
    """Shared per-layer math for PER-ROW-position decode.

    Identical math to :func:`decode_block`, except every batch row carries
    its own cache position ``pos (B,)`` — the continuous-batching regime
    where each scheduler slot sits at a different sequence offset.  The KV
    write is a per-row scatter instead of a shared dynamic slice, and the
    attention mask is per-row (``decode_attention`` takes vector lengths).

    ``emit_cache=True`` returns the updated dense caches (the slot-major
    pool carries them forward); ``emit_cache=False`` returns just the new
    token's (k, v) rows — the paged path scatters those into its block
    arena instead of materializing a dense cache copy.

    ``la``: this layer's scanned attention settings (``attention_layers``),
    None where every layer is alike.  An MoE layer's expert ids (B, 1,
    k) come back as a third element of the cache tuple.
    """
    b = x.shape[0]
    positions = pos[:, None]                         # (B, 1)
    with jax.named_scope("attn"):
        xn = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
        q, k, v = _project_qkv(p["attn"], cfg, xn)
        q = _rope(cfg, la, q, positions)
        k = _rope(cfg, la, k, positions)
        rows = jnp.arange(b)
        kc = kc.at[rows, pos].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[rows, pos].set(v[:, 0].astype(vc.dtype))
        o = L.decode_attention(q, kc, vc, pos + 1, window=_window(cfg, la))
        x = x + L.linear(o.reshape(b, 1, -1), p["attn"]["wo"])
    out = (kc, vc) if emit_cache else (k[:, 0], v[:, 0])
    x, ids = mlp_residual(p, cfg, x)
    return x, out if ids is None else out + (ids,)


def decode_block_rows(p: Params, cfg: ModelConfig, x: jax.Array,
                      kc: jax.Array, vc: jax.Array, pos: jax.Array,
                      la: Optional[Params] = None
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer of the per-row-position decode path → (x', (kc', vc'))."""
    x, out = decode_core_rows(p, cfg, x, kc, vc, pos, emit_cache=True,
                              la=la)
    return x, out[:2]


def extend_block(p: Params, cfg: ModelConfig, x: jax.Array, kc: jax.Array,
                 vc: jax.Array, pos0: jax.Array, positions: jax.Array,
                 la: Optional[Params] = None
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer of the MULTI-token extend path (chunked prefill).

    ``x`` (B, C, d) is a chunk of C prompt tokens starting at absolute
    position ``pos0`` against a cache ``kc``/``vc`` (B, T, KV, hd) already
    holding the first ``pos0`` positions.  The chunk's K/V is written at
    [pos0, pos0+C) and the chunk attends causally over the whole valid
    prefix (``q_offset`` masks everything past each query's own position,
    so trailing cache garbage — padded chunk tail included — is
    unreachable).  Returns (x', (k_chunk, v_chunk)); the caller persists
    the chunk K/V into its cache layout.  With C == prompt length this IS
    whole-prompt prefill, which is the chunking parity argument.
    """
    b, s, _ = x.shape
    with jax.named_scope("attn"):
        xn = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
        q, k, v = _project_qkv(p["attn"], cfg, xn)
        q = _rope(cfg, la, q, positions)
        k = _rope(cfg, la, k, positions)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, pos0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, pos0, 0, 0))
        o = L.causal_attention(q, kc, vc, q_offset=pos0,
                               window=_window(cfg, la))
        x = x + L.linear(o.reshape(b, s, -1), p["attn"]["wo"])
    return mlp_residual(p, cfg, x)[0], (k, v)


def verify_block(p: Params, cfg: ModelConfig, x: jax.Array, kc: jax.Array,
                 vc: jax.Array, pos: jax.Array, positions: jax.Array,
                 la: Optional[Params] = None
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer of the PER-ROW multi-token verify path (speculative decode).

    The hybrid of :func:`decode_core_rows` (every row at its own cache
    position ``pos (B,)``) and :func:`extend_block` (C tokens scored
    causally in one pass).  ``x`` (B, C, d) holds each slot's candidate
    span — its pending last token followed by drafted continuations —
    written at per-row absolute positions ``positions (B, C)`` =
    ``pos[:, None] + arange(C)``.  The per-row ``q_offset`` mask means row
    ``b``'s query ``j`` sees exactly cache[:pos[b]+j+1]: identical math to
    running C sequential decode steps, so greedy verify output matches the
    autoregressive path bit-for-bit.  Returns (x', (k_chunk, v_chunk));
    the caller scatters the chunk K/V into its block arena — rejected
    positions land past the committed ``pos`` and are simply overwritten.
    """
    b, c, _ = x.shape
    with jax.named_scope("attn"):
        xn = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
        q, k, v = _project_qkv(p["attn"], cfg, xn)
        q = _rope(cfg, la, q, positions)
        k = _rope(cfg, la, k, positions)
        rows = jnp.arange(b)
        kc = kc.at[rows[:, None], positions].set(k.astype(kc.dtype))
        vc = vc.at[rows[:, None], positions].set(v.astype(vc.dtype))
        o = L.causal_attention(q, kc, vc, q_offset=pos,
                               window=_window(cfg, la))
        x = x + L.linear(o.reshape(b, c, -1), p["attn"]["wo"])
    return mlp_residual(p, cfg, x)[0], (k, v)


def decode_step_rows(params: Params, cfg: ModelConfig, cache: Params,
                     tokens: jax.Array) -> Tuple[Params, jax.Array]:
    """One batched decode step with per-row positions (continuous batching).

    ``cache["pos"]`` is (B,) int32 — each row its own valid length.  One
    call decodes every scheduler slot in ONE dispatch, so the per-step
    dispatch overhead the paper measures is paid once per cycle instead of
    once per request.  tokens (B, 1) → (cache', logits (B, 1, V)).
    """
    x = params["embed"][tokens]
    pos = cache["pos"]

    def scan_body(carry, scan_in):
        p, kc, vc, la = scan_in
        return decode_block_rows(p, cfg, carry, kc, vc, pos, la)

    x, (kcache, vcache) = jax.lax.scan(
        scan_body, x, (params["blocks"], cache["k"], cache["v"],
                       attention_layers(cfg)))
    logits = unembed(params, cfg, x)
    return {"k": kcache, "v": vcache, "pos": pos + 1}, logits
