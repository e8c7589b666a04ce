"""Public routed-expert entry point: from a step's routing to the kernel's
slots, then the kernel (interpret mode on the CPU)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_dim, round_up, use_interpret
from repro.kernels.routed_experts.kernel import routed_experts_pallas


def expert_slots(top_i: jax.Array, top_p: jax.Array, n_held: int, lo: int,
                 n_slots: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The distinct held experts that ``top_i (R, k)`` routes to, sorted,
    as ``(ids (n_slots,), count (1,), gates (n_slots, R))``: the first
    ``count`` ids are real (local to the held range ``[lo, lo+n_held)``),
    the rest repeat the last of them; ``gates[s, r]`` is row r's weight
    for expert ``ids[s]`` (0 where r did not pick it, and on pad slots).
    Experts outside the held range are left out."""
    local = top_i - lo
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    uniq = jnp.unique(key.reshape(-1), size=n_slots, fill_value=n_held)
    count = jnp.sum(uniq < n_held).astype(jnp.int32)
    last = uniq[jnp.maximum(count - 1, 0)]
    real = jnp.arange(n_slots) < count
    ids = jnp.minimum(jnp.where(real, uniq, last), n_held - 1)
    hit = (key[None, :, :] == ids[:, None, None]) & real[:, None, None]
    gates = jnp.sum(jnp.where(hit, top_p[None].astype(jnp.float32), 0.0),
                    axis=-1)
    return ids.astype(jnp.int32), count.reshape(1), gates


def routed_experts(x: jax.Array, top_i: jax.Array, top_p: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   *, lo: int = 0, layer=None) -> jax.Array:
    """x (R, d), routing ``top_i``/``top_p`` (R, k) over global expert ids,
    held expert weights ``w_gate``/``w_up`` (E_held, d, F) and ``w_down``
    (E_held, F, d) of experts ``[lo, lo + E_held)`` → (R, d) float32, the
    rows' weighted sum over the held experts they picked.  Each touched
    expert is read once, whatever number of rows picked it.

    ``layer``: the weights are stacks over layers, (L, E_held, d, F) and
    (L, E_held, F, d), of which layer ``layer`` (a traced index) is read,
    in place."""
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    rows, k = top_i.shape
    n_held = w_gate.shape[1]
    n_slots = min(n_held, rows * k)
    ids, count, gates = expert_slots(top_i, top_p, n_held, lo, n_slots)
    rp = round_up(rows, 8)
    out = routed_experts_pallas(
        jnp.asarray(layer, jnp.int32).reshape(1), ids, count,
        pad_dim(x, 0, rp), pad_dim(gates, 1, rp)[..., None],
        w_gate, w_up, w_down, interpret=use_interpret())
    return out[:rows]
