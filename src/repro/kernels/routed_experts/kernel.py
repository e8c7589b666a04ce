"""Routed-expert SwiGLU Pallas kernel: each routed expert's weights read
once from HBM, in place, chosen by scalar-prefetched expert ids.

A decode step routes each of its R rows to k experts, so it touches at
most ``R·k`` of the layer's experts.  The step's routing hands this kernel
the sorted distinct experts it touched (``ids``, of which the first
``count`` are real) and a gate matrix ``gates (slots, R)`` holding each
row's renormalised router weight for that slot's expert (0 for rows that
did not pick it).  The weights are the model's stacks over layers and
experts, passed whole with the layer's index: a slice of a stack that a
kernel reads is copied first, so the layer scan does not slice them.
Grid step ``s`` brings in the gate, up and down weights of expert
``ids[s]`` of that layer whole: the ``index_map`` reads both indices,
so no weight is gathered or copied first, and rows that share an expert
share its one read; the pipeline fetches expert ``s + 1`` while step
``s`` computes.  The step computes ``gates[s] · down(silu(x·Wg) ⊙
(x·Wu))`` and adds it into one float32 output that stays in VMEM for
the whole grid: the weighted combine happens in the kernel.

Slots past ``count`` repeat the last real id, so the pipeline finds the
block it already holds and fetches nothing, and they compute nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# room in VMEM for two buffers of one expert's three matrices at Mellum2's
# width (3 · 2304 · 896 · 2 bytes, doubled) and the rows
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(layer_ref, ids_ref, count_ref, x_ref, g_ref, wg_ref, wu_ref,
            wd_ref, o_ref):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < count_ref[0])
    def _expert():
        x = x_ref[...]
        a = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        b = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(a) * b).astype(x.dtype)
        y = jnp.dot(h, wd_ref[0, 0], preferred_element_type=jnp.float32)
        o_ref[...] += g_ref[0] * y


def routed_experts_pallas(layer: jax.Array, ids: jax.Array,
                          count: jax.Array, x: jax.Array, gates: jax.Array,
                          w_gate: jax.Array, w_up: jax.Array,
                          w_down: jax.Array, *, interpret: bool = False
                          ) -> jax.Array:
    """layer (1,) int32, ids (S,) int32 expert per slot, count (1,) int32
    real slots, x (R, d), gates (S, R, 1) float32, w_gate/w_up
    (L, E, d, F), w_down (L, E, F, d) → (R, d) float32:
    ``Σ_s gates[s] · expert_ids[s](x)`` of layer ``layer``."""
    rows, d = x.shape
    f = w_gate.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ids.shape[0],),
            in_specs=[
                pl.BlockSpec((rows, d), lambda s, l, ids, n: (0, 0)),
                pl.BlockSpec((1, rows, 1), lambda s, l, ids, n: (s, 0, 0)),
                pl.BlockSpec((1, 1, d, f),
                             lambda s, l, ids, n: (l[0], ids[s], 0, 0)),
                pl.BlockSpec((1, 1, d, f),
                             lambda s, l, ids, n: (l[0], ids[s], 0, 0)),
                pl.BlockSpec((1, 1, f, d),
                             lambda s, l, ids, n: (l[0], ids[s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda s, l, ids, n: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="routed_experts",
    )(layer, ids, count, x, gates, w_gate, w_up, w_down)
