"""Block-table-aware attention: paged decode and chunked-prefill extend.

Both entry points keep the dispatch economics of the dense continuous-
batching path — ONE jitted executable per decode cycle / prefill chunk —
while reading and writing K/V through per-slot block tables instead of
contiguous ``max_len`` rows:

* ``decode_step_paged`` — the paged twin of
  ``transformer.decode_step_rows``: every scheduler slot advances one
  token at its own position in the same dispatch, gathering its cache
  through ``block_table`` and scattering the new token's K/V back into its
  current (always privately-owned) block.
* ``verify_step_paged`` — one speculative-verify cycle: every slot scores
  its pending token plus K drafted continuations at per-row positions in
  the same dispatch, the per-row causal offset keeping each candidate's
  view identical to sequential decode (exact greedy parity).
* ``extend_step_paged`` — one chunked-prefill step: run ``chunk`` prompt
  tokens of one slot against everything already cached for it (shared
  prefix blocks included), append the chunk's K/V into its blocks, and
  return last-valid-position logits.  Chunks are padded to a fixed width
  so every chunk reuses one compiled executable; padded positions write
  into blocks the very next chunk (or decode) overwrites, and the causal
  mask keeps them unreadable meanwhile.

Each step is one ``lax.scan`` over the layers; where layers differ in
attention kind (sliding or full window, plain or YaRN RoPE) the scan
carries each layer's settings (``transformer.attention_layers``) beside
its weights and gathered KV, so the step stays one dispatch.  An MoE
layer's expert stacks are not sliced by the scan, which carries the
layer's index instead (``transformer.split_experts``): the routed-expert
kernel reads the stacks in place.

The gathered dense view is position-identical to the dense cache layout
(table entry ``i`` covers logical tokens ``[i*block_size, (i+1)*block_size)``),
so the math — and the greedy token stream — matches the dense path
exactly; trailing garbage is masked the same way dense ``max_len`` padding
is.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer


def gather_blocks(arena: jax.Array, table: jax.Array, kv_heads: int
                  ) -> jax.Array:
    """Arena as ``allocator.arena_shape`` stores it, (N, L, Bs, KV, hd) or
    (N, L, Bs, KV·hd), + (S, W) block table → (L, S, W·Bs, KV, hd) dense
    per-layer view, position-compatible with the dense cache."""
    with jax.named_scope("kv_gather"):
        g = arena[table]                           # (S, W, L, Bs, ...)
        s, w, nl, bs = g.shape[:4]
        g = jnp.moveaxis(g, 2, 0)                  # (L, S, W, Bs, ...)
        return g.reshape(nl, s, w * bs, kv_heads, -1)


def _unembed_argmax(params, cfg: ModelConfig, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Final norm, logits and greedy token, under the ``unembed`` scope."""
    with jax.named_scope("unembed"):
        logits = transformer.unembed(params, cfg, x)
        return logits, jnp.argmax(logits, -1).astype(jnp.int32)


def write_kv(arena_k: jax.Array, arena_v: jax.Array, table: jax.Array,
             positions: jax.Array, knew: jax.Array, vnew: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """Scatter the new K/V into the arena blocks that ``table`` maps
    ``positions`` to, under the ``kv_write`` scope.

    ``table`` is indexed by ``(rows, positions // block_size)``;
    ``knew``/``vnew`` are (L, *positions.shape, KV, hd), layer first as
    the layer scan stacks them.  (KV, hd) take the arena's own minor
    shape (``allocator.arena_shape``), and each (block, layer, offset)
    triple indexes one stored row, so the scatter writes whole rows and
    needs no relayout of the arena around it."""
    with jax.named_scope("kv_write"):
        nl, bs = arena_k.shape[1:3]
        rows = jnp.arange(table.shape[0]).reshape(
            (-1,) + (1,) * (positions.ndim - 1))
        bids = table[rows, positions // bs]
        offs = positions % bs
        layers = jnp.arange(nl).reshape((nl,) + (1,) * positions.ndim)

        def put(arena, new):
            new = new.reshape(*new.shape[:-2], *arena.shape[3:])
            return arena.at[bids, layers, offs].set(new.astype(arena.dtype))
        return put(arena_k, knew), put(arena_v, vnew)


def decode_step_paged(params, cfg: ModelConfig, arena_k: jax.Array,
                      arena_v: jax.Array, table: jax.Array, pos: jax.Array,
                      tokens: jax.Array):
    """One batched decode cycle through per-slot block tables.

    ``table`` (S, W) int32, ``pos`` (S,) int32, ``tokens`` (S, 1) int32 →
    (arena_k', arena_v', logits (S, 1, V), next_token (S, 1)).  Same single
    dispatch as the dense rows path; only the cache plumbing differs.
    An MoE config's step has a fifth output, every layer's expert ids
    (L, S, k) int32, which a live tracer reads back with the tokens.
    """
    x = params["embed"][tokens]
    kd = gather_blocks(arena_k, table, cfg.num_kv_heads)
    vd = gather_blocks(arena_v, table, cfg.num_kv_heads)
    blocks, experts = transformer.split_experts(cfg, params["blocks"])

    def body(carry, xs):
        p, kc, vc, la = xs
        return transformer.decode_core_rows(
            transformer.join_experts(p, experts), cfg, carry, kc, vc, pos,
            emit_cache=False, la=la)

    x, new = jax.lax.scan(body, x, (blocks, kd, vd,
                                    transformer.attention_layers(cfg)))
    logits, nxt = _unembed_argmax(params, cfg, x)
    # each slot's new token lands in its current block, which
    # ensure_writable made exclusively ours
    arena_k, arena_v = write_kv(arena_k, arena_v, table, pos, new[0], new[1])
    if cfg.moe is not None:
        return arena_k, arena_v, logits, nxt, new[2][:, :, 0]
    return arena_k, arena_v, logits, nxt


def verify_step_paged(params, cfg: ModelConfig, arena_k: jax.Array,
                      arena_v: jax.Array, table: jax.Array, pos: jax.Array,
                      tokens: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One speculative-verify cycle: score C candidate tokens per slot in
    ONE dispatch through the block tables.

    ``tokens`` (S, C) int32 — column 0 is each slot's pending last token
    (so position 0 IS an ordinary decode step), columns 1.. are drafted
    continuations (zero-padded for non-speculating slots).  Returns
    (arena_k', arena_v', logits (S, C, V), next_token (S, C)) where
    ``next_token[s, j]`` is the target model's greedy choice after
    consuming ``tokens[s, :j+1]`` — the reference stream the drafts are
    accepted against.  K/V for all C positions is scattered at
    [pos, pos+C); positions past the accepted span stay beyond the
    committed ``pos`` and are overwritten by the next cycle, with the
    causal mask keeping them unreadable meanwhile (same argument as chunk
    padding in ``extend_step_paged``).
    """
    x = params["embed"][tokens]
    kd = gather_blocks(arena_k, table, cfg.num_kv_heads)
    vd = gather_blocks(arena_v, table, cfg.num_kv_heads)
    c = tokens.shape[1]
    positions = pos[:, None] + jnp.arange(c)       # (S, C)

    blocks, experts = transformer.split_experts(cfg, params["blocks"])

    def body(carry, xs):
        p, kc, vc, la = xs
        return transformer.verify_block(transformer.join_experts(p, experts),
                                        cfg, carry, kc, vc, pos, positions,
                                        la)

    x, (kch, vch) = jax.lax.scan(body, x, (blocks, kd, vd,
                                           transformer.attention_layers(cfg)))
    logits, nxt = _unembed_argmax(params, cfg, x)
    # the same scatter decode_step_paged makes, batched over the span axis
    arena_k, arena_v = write_kv(arena_k, arena_v, table, positions, kch,
                                vch)
    return arena_k, arena_v, logits, nxt


def extend_step_paged(params, cfg: ModelConfig, arena_k: jax.Array,
                      arena_v: jax.Array, table_row: jax.Array,
                      pos0: jax.Array, valid: jax.Array, tokens: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One prefill chunk for one slot, against its paged cache.

    ``table_row`` (1, W); ``tokens`` (1, C) padded to the chunk width with
    ``valid`` real tokens starting at absolute position ``pos0``.  Returns
    (arena_k', arena_v', logits (1, 1, V) at the last VALID position,
    next_token (1, 1)) — the final chunk's logits seed generation, earlier
    chunks' are ignored.  A radix-cache hit means ``pos0`` starts past the
    shared span: those positions are never recomputed (zero prefill
    dispatches for the shared prefix).
    """
    x = params["embed"][tokens]
    kd = gather_blocks(arena_k, table_row, cfg.num_kv_heads)
    vd = gather_blocks(arena_v, table_row, cfg.num_kv_heads)
    c = tokens.shape[1]
    positions = pos0 + jnp.arange(c)

    blocks, experts = transformer.split_experts(cfg, params["blocks"])

    def body(carry, xs):
        p, kc, vc, la = xs
        return transformer.extend_block(transformer.join_experts(p, experts),
                                        cfg, carry, kc, vc, pos0, positions,
                                        la)

    x, (kch, vch) = jax.lax.scan(body, x, (blocks, kd, vd,
                                           transformer.attention_layers(cfg)))
    x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
    logits, nxt = _unembed_argmax(params, cfg, x_last)
    # padded positions land in writable blocks and are overwritten before
    # anything can attend them
    arena_k, arena_v = write_kv(arena_k, arena_v, table_row, positions,
                                kch[:, 0], vch[:, 0])
    return arena_k, arena_v, logits, nxt
