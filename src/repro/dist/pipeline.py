"""GPipe-style microbatched pipeline parallelism over a ``("stage",)`` axis.

``pipeline_apply`` runs ``n_micro`` microbatches through ``n_layers``
stacked layers laid out across the mesh's ``stage`` axis: each device owns
a contiguous chunk of ``n_layers / n_stages`` layers, activations rotate
stage→stage+1 via ``lax.ppermute`` after every tick, and the loop follows
the classic fill/drain schedule — ``n_micro + n_stages − 1`` ticks, of
which only ``n_micro`` per device carry useful work.  The idle remainder
is the pipeline *bubble*; ``bubble_fraction`` / ``pipeline_stats`` report
it in the Table-20 style the serving layer uses for dispatch accounting,
because the bubble is exactly the dispatch-amortization trade the paper
quantifies: more microbatches → larger scheduled units per dispatch →
smaller per-op overhead share.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def ring_perm(n: int) -> List[Tuple[int, int]]:
    """The stage→stage+1 rotation (last stage wraps to 0, feeding drain)."""
    return [(i, (i + 1) % n) for i in range(n)]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the fill/drain schedule: (S−1) / (M + S − 1)."""
    if n_stages < 1 or n_micro < 1:
        raise ValueError("need n_stages >= 1 and n_micro >= 1")
    return (n_stages - 1) / (n_micro + n_stages - 1)


@dataclasses.dataclass(frozen=True)
class PipelineStats:
    """Static schedule accounting for one pipeline execution."""
    n_stages: int
    layers_per_stage: int
    n_micro: int

    @property
    def ticks(self) -> int:
        return self.n_micro + self.n_stages - 1

    @property
    def bubble(self) -> float:
        return bubble_fraction(self.n_stages, self.n_micro)

    def row(self) -> Dict[str, Any]:
        """Uniform reporting row (Table-20 style, like DispatchStats.row)."""
        return {
            "stages": self.n_stages,
            "layers_per_stage": self.layers_per_stage,
            "n_micro": self.n_micro,
            "ticks": self.ticks,
            "bubble_pct": round(100 * self.bubble, 1),
        }


def pipeline_stats(n_layers: int, n_stages: int, n_micro: int) -> PipelineStats:
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers do not divide into "
                         f"{n_stages} stages")
    return PipelineStats(n_stages, n_layers // n_stages, n_micro)


def _apply_local(stage_fn: Callable, w_local: Any, h: jax.Array) -> jax.Array:
    """Apply this stage's layer chunk sequentially (leading-axis scan)."""

    def step(carry, wi):
        return stage_fn(wi, carry), None

    h, _ = lax.scan(step, h, w_local)
    return h


@functools.lru_cache(maxsize=64)
def _compiled_pipeline(mesh: Mesh, axis: str, stage_fn: Callable,
                       w_treedef, n_micro: int, n_stages: int):
    """One jitted pipeline executable per (mesh, stage_fn, schedule) —
    repeat calls with the same shapes reuse jit's compilation cache
    instead of retracing a fresh closure every time."""
    perm = ring_perm(n_stages)
    last = n_stages - 1

    def body(w_local, xs):
        stage = lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])
        out = jnp.zeros_like(xs)
        for t in range(n_micro + n_stages - 1):
            # fill: stage 0 ingests microbatch t (clamped feeds past the
            # last microbatch are garbage that drains before reaching the
            # final stage inside the tick budget)
            feed = xs[min(t, n_micro - 1)]
            state = jnp.where(stage == 0, feed, state)
            h = _apply_local(stage_fn, w_local, state)
            # drain: the last stage emits microbatch t − (S−1)
            m = t - last
            if m >= 0:
                out = jnp.where(stage == last, out.at[m].set(h), out)
            # rotate activations one stage forward for the next tick
            if n_stages > 1:
                state = lax.ppermute(h, axis, perm)
        # only the last stage holds real outputs; broadcast them
        return lax.psum(jnp.where(stage == last, out, 0), axis)

    in_specs = (jax.tree_util.tree_unflatten(
        w_treedef, [P(axis)] * w_treedef.num_leaves), P())
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False))


def pipeline_apply(w: Any, x: jax.Array, *, mesh: Mesh,
                   stage_fn: Callable[[Any, jax.Array], jax.Array],
                   axis: str = "stage") -> jax.Array:
    """Run microbatches through layer-sharded weights on a pipeline.

    ``w``        — pytree whose leaves carry a leading ``n_layers`` axis
                   (``n_layers`` must divide by the mesh's ``axis`` size);
                   each stage owns a contiguous chunk of layers.
    ``x``        — (n_micro, *microbatch_shape) stacked microbatches.
    ``stage_fn`` — ``stage_fn(w_i, h) → h'``: ONE layer applied to one
                   microbatch's activations.  Must be a stable callable
                   (module-level fn / stored lambda) for the compilation
                   cache to hit across calls.

    Returns outputs shaped like ``x``, numerically equal to applying all
    layers sequentially to every microbatch.
    """
    n_stages = mesh.shape[axis]
    leaves, treedef = jax.tree_util.tree_flatten(w)
    if not leaves:
        raise ValueError("empty weight pytree")
    n_layers = leaves[0].shape[0]
    for leaf in leaves:
        if leaf.shape[0] != n_layers:
            raise ValueError("all weight leaves must share the leading "
                             "layer axis")
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers do not divide over "
                         f"{n_stages} pipeline stages")
    fn = _compiled_pipeline(mesh, axis, stage_fn, treedef, x.shape[0],
                            n_stages)
    return fn(w, x)
