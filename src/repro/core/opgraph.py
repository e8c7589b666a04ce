"""Op-level graph IR — the FX-graph analogue (paper §2.2, Appendix B).

torch-webgpu translated ``torch.compile`` FX graphs into one WebGPU dispatch
per compute node.  Here the same role is played by an ``OpGraph``: each
compute node becomes one *separately jitted XLA executable*, so executing a
graph node-by-node reproduces the paper's dispatch-per-operation regime
(level F0), and fusion passes that collapse node patterns reproduce the
paper's fusion levels (Table 5).  Shape nodes (reshape/transpose/split) cost
no dispatch — exactly the paper's "shape operations (241) don't require
them" observation.

Node taxonomy mirrors Table 10: matmul / mul / add / sdpa / silu / rmsnorm
components (pow, mean, rsqrt) / concat (cache + rotary) / other.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# op registry: canonical callables, one per op name
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, length):
    """Decode attention against a cache — one dispatch, like the paper's SDPA."""
    from repro.models import layers as L
    return L.decode_attention(q, k, v, length)


def _sdpa_prefill(q, k, v):
    from repro.models import layers as L
    return L.causal_attention(q, k, v)


def _sdpa_paged(q, k_arena, v_arena, table, length):
    """Decode attention through a per-row block table (paged KV).

    The block gather is folded INTO the attention op, so the paged decode
    graph keeps the exact dispatch count of the dense slot-position graph —
    the layout change is free in the paper's per-operation accounting.
    """
    from repro.models import layers as L
    kd = k_arena[table]                       # (B, W, Bs, KV, hd)
    b, w, bs = kd.shape[:3]
    kd = kd.reshape(b, w * bs, *kd.shape[3:])
    vd = v_arena[table].reshape(b, w * bs, *kd.shape[2:])
    return L.decode_attention(q, kd, vd, length)


def _cache_update_paged(arena, val, table, pos, *, block_size):
    """Per-row scatter of one new token's K/V into its current block."""
    rows = jnp.arange(table.shape[0])
    bids = table[rows, pos // block_size]
    return arena.at[bids, pos % block_size].set(
        val[:, 0].astype(arena.dtype))


def _sdpa_extend_paged(q, k_arena, v_arena, table, pos0):
    """Chunked-prefill attention through one slot's block table.

    ``q`` (1, C, H, hd) is a chunk starting at absolute position ``pos0``;
    the arenas already hold the chunk's K/V (scattered by
    ``cache_update_span_paged`` just before).  Like ``sdpa_paged``, the
    block gather folds into the attention op so the paged extend graph
    spends exactly one dispatch where the dense prefill graph spends one.
    """
    from repro.models import layers as L
    kd = k_arena[table]                       # (1, W, Bs, KV, hd)
    b, w, bs = kd.shape[:3]
    kd = kd.reshape(b, w * bs, *kd.shape[3:])
    vd = v_arena[table].reshape(b, w * bs, *kd.shape[2:])
    return L.causal_attention(q, kd, vd, q_offset=pos0)


def _cache_update_span_paged(arena, val, table, pos0, *, block_size):
    """Scatter one chunk's K/V (1, C, KV, hd) into its slot's blocks at
    absolute positions [pos0, pos0+C).  Padded chunk-tail positions land in
    writable blocks and are overwritten before anything can attend them —
    the same don't-care contract as the jitted ``extend_step_paged``."""
    c = val.shape[1]
    idx = pos0 + jnp.arange(c)
    bids = table[0, idx // block_size]
    return arena.at[bids, idx % block_size].set(val[0].astype(arena.dtype))


# Fused-op backend: "xla" (jnp bodies fused by XLA — the wall-clock path on
# the CPU host) or "pallas" (the hand-written TPU kernels from
# repro.kernels — the production TPU path; interpret-mode on CPU, so used
# for correctness, not speed, in this container).
_FUSED_BACKEND = "xla"


def set_fused_backend(name: str) -> None:
    """Select the fused-op bodies for every graph traced from now on.

    The choice is read while tracing, and jit keeps one trace per function
    and argument shapes across all its wrappers, so a switch also drops
    the traces made under the other backend."""
    global _FUSED_BACKEND
    assert name in ("xla", "pallas"), name
    if name != _FUSED_BACKEND:
        jax.clear_caches()
    _FUSED_BACKEND = name


def get_fused_backend() -> str:
    return _FUSED_BACKEND


def _fused_rmsnorm(x, w, *, eps):
    if _FUSED_BACKEND == "pallas":
        from repro.kernels import fused_rmsnorm as k_rmsnorm
        return k_rmsnorm(x, w, eps=eps)
    from repro.models import layers as L
    return L.rmsnorm(x, w, eps)


def _fused_mlp(x, wg, wu):
    if _FUSED_BACKEND == "pallas":
        from repro.kernels import fused_mlp as k_mlp
        return k_mlp(x, wg, wu)
    g = jnp.einsum("...d,df->...f", x, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("...d,df->...f", x, wu, preferred_element_type=jnp.float32)
    return (jax.nn.silu(g) * u).astype(x.dtype)


def _fused_kv(x, wkv, bkv):
    if _FUSED_BACKEND == "pallas":
        # the kernel consumes the concatenated [Wk|Wv] directly
        from repro.kernels.fused_kv_proj.ops import kv_proj
        return kv_proj(x, jnp.asarray(wkv), jnp.asarray(bkv))
    y = jnp.einsum("...d,df->...f", x, wkv, preferred_element_type=jnp.float32)
    return (y + bkv.astype(jnp.float32)).astype(x.dtype)


def _fused_kv_nobias(x, wkv):
    return jnp.einsum("...d,df->...f", x, wkv,
                      preferred_element_type=jnp.float32).astype(x.dtype)


OPS: Dict[str, Callable] = {
    # --- Table 10 categories -------------------------------------------
    "matmul": lambda x, w: jnp.einsum(
        "...d,df->...f", x, w, preferred_element_type=jnp.float32).astype(x.dtype),
    "add": lambda a, b: a + b,
    "mul": lambda a, b: a * b,
    "pow": lambda x: jnp.square(x.astype(jnp.float32)),
    "mean": lambda x: jnp.mean(x, axis=-1, keepdims=True),
    "add_eps": lambda x, *, eps: x + eps,
    "rsqrt": jax.lax.rsqrt,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "neg": lambda x: -x,
    "concat": lambda a, b, *, axis: jnp.concatenate([a, b], axis=axis),
    "embed": lambda table, ids: jnp.take(table, ids, axis=0),
    "gather_rows": lambda table, idx: jnp.take(table, idx, axis=0),
    "argmax": lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32),
    "softmax": lambda x: jax.nn.softmax(x.astype(jnp.float32), axis=-1),
    "cast": lambda x, *, dtype: x.astype(dtype),
    "cache_update": lambda cache, val, pos: jax.lax.dynamic_update_slice(
        cache, val.astype(cache.dtype), (0, pos, 0, 0)),
    # per-row scatter for slot-position decode graphs (continuous batching):
    # row b writes at its own position pos[b] instead of a shared offset
    "cache_update_rows": lambda cache, val, pos: cache.at[
        jnp.arange(cache.shape[0]), pos].set(val[:, 0].astype(cache.dtype)),
    "sdpa": _sdpa,
    "sdpa_prefill": _sdpa_prefill,
    "sdpa_paged": _sdpa_paged,
    "cache_update_paged": _cache_update_paged,
    "sdpa_extend_paged": _sdpa_extend_paged,
    "cache_update_span_paged": _cache_update_span_paged,
    # dynamic (traced-index) slice of one sequence position — the extend
    # graph's "logits at the last VALID chunk position" read; a real
    # gather dispatch, unlike the static slice_seq_last shape op
    "slice_seq_at": lambda x, i: jax.lax.dynamic_slice_in_dim(x, i, 1,
                                                              axis=1),
    # --- fused ops (Table 5 / §6.1) ------------------------------------
    "fused_rmsnorm": _fused_rmsnorm,
    "fused_mlp": _fused_mlp,
    "fused_kv": _fused_kv,
    "fused_kv_nobias": _fused_kv_nobias,
    # --- top-k / sampling ----------------------------------------------
    "top_k": lambda x, *, k: jax.lax.top_k(x, k)[0],
}

# shape-only ops — no dispatch (paper §2.2)
SHAPE_OPS: Dict[str, Callable] = {
    "reshape": lambda x, *, shape: jnp.reshape(x, shape),
    "transpose": lambda x, *, perm: jnp.transpose(x, perm),
    "split_half": lambda x, *, part: jnp.split(x, 2, axis=-1)[part],
    "slice_last": lambda x, *, start, size: jax.lax.slice_in_dim(
        x, start, start + size, axis=-1),
    "slice_seq_last": lambda x: x[:, -1:, :],
    "broadcast_pos": lambda p, *, batch: jnp.broadcast_to(p, (batch, 1)),
}

# Table 10 bucket per op name
TAXONOMY: Dict[str, str] = {
    "matmul": "linear", "fused_kv": "linear", "fused_kv_nobias": "linear",
    "fused_mlp": "linear",
    "mul": "multiply",
    "add": "add", "add_eps": "add",
    "sdpa": "sdpa", "sdpa_prefill": "sdpa", "sdpa_paged": "sdpa",
    "sdpa_extend_paged": "sdpa",
    "silu": "silu", "gelu": "silu",
    "pow": "rmsnorm_comp", "mean": "rmsnorm_comp", "rsqrt": "rmsnorm_comp",
    "fused_rmsnorm": "rmsnorm_comp",
    "concat": "concat", "cache_update": "concat",
    "cache_update_rows": "concat", "cache_update_paged": "concat",
    "cache_update_span_paged": "concat",
}
_OTHER = "other"


# ---------------------------------------------------------------------------
# graph structures
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ref:
    idx: int


@dataclasses.dataclass
class Node:
    idx: int
    op: str
    category: str                     # compute | shape | input
    args: Tuple[Any, ...]             # Ref | concrete array | python scalar
    static: Tuple[Tuple[str, Any], ...]
    aval: jax.ShapeDtypeStruct
    tag: str = ""
    donate: Tuple[int, ...] = ()      # positional args safe to donate

    @property
    def fn(self) -> Callable:
        base = OPS.get(self.op) or SHAPE_OPS[self.op]
        if self.static:
            return functools.partial(base, **dict(self.static))
        return base


@dataclasses.dataclass
class OpGraph:
    nodes: List[Node]
    inputs: Dict[str, int]
    outputs: Dict[str, int]
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- accounting (paper Table 10 / §4.3) ----------------------------
    def compute_nodes(self) -> List[Node]:
        return [n for n in self.nodes if n.category == "compute"]

    def num_dispatches(self) -> int:
        return len(self.compute_nodes())

    def num_shape_ops(self) -> int:
        return sum(1 for n in self.nodes if n.category == "shape")

    def taxonomy(self) -> Counter:
        c: Counter = Counter()
        for n in self.compute_nodes():
            c[TAXONOMY.get(n.op, _OTHER)] += 1
        return c

    def summary(self) -> Dict[str, Any]:
        return {
            "total_nodes": len(self.nodes),
            "compute_ops": self.num_dispatches(),
            "shape_ops": self.num_shape_ops(),
            "inputs": len(self.inputs),
            "taxonomy": dict(self.taxonomy()),
        }


class GraphBuilder:
    """Records ops into an ``OpGraph``; shapes inferred via eval_shape."""

    def __init__(self) -> None:
        self.nodes: List[Node] = []
        self.inputs: Dict[str, int] = {}
        self.outputs: Dict[str, int] = {}

    # ------------------------------------------------------------------
    def _aval(self, x) -> jax.ShapeDtypeStruct:
        if isinstance(x, Ref):
            return self.nodes[x.idx].aval
        arr = jnp.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    def input(self, name: str, shape, dtype) -> Ref:
        node = Node(len(self.nodes), "input", "input", (), (),
                    jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype)), name)
        self.nodes.append(node)
        self.inputs[name] = node.idx
        return Ref(node.idx)

    def op(self, op: str, *args, tag: str = "", donate: Tuple[int, ...] = (),
           **static) -> Ref:
        category = "shape" if op in SHAPE_OPS else "compute"
        base = OPS.get(op) or SHAPE_OPS[op]
        fn = functools.partial(base, **static) if static else base
        avals = [self._aval(a) for a in args]
        out_aval = jax.eval_shape(fn, *avals)
        node = Node(len(self.nodes), op, category, tuple(args),
                    tuple(sorted(static.items())), out_aval, tag, donate)
        self.nodes.append(node)
        return Ref(node.idx)

    def output(self, name: str, ref: Ref) -> None:
        self.outputs[name] = ref.idx

    def build(self, **meta) -> OpGraph:
        return OpGraph(self.nodes, dict(self.inputs), dict(self.outputs),
                       meta)


# ---------------------------------------------------------------------------
# pure execution (used for correctness oracles and the FULL jit mode)
# ---------------------------------------------------------------------------

def run_graph_pure(graph: OpGraph, inputs: Dict[str, Any]) -> Dict[str, Any]:
    """Execute the graph functionally (traceable → whole-graph jit)."""
    env: Dict[int, Any] = {}
    for name, idx in graph.inputs.items():
        env[idx] = inputs[name]
    for node in graph.nodes:
        if node.category == "input":
            continue
        args = [env[a.idx] if isinstance(a, Ref) else a for a in node.args]
        env[node.idx] = node.fn(*args)
    return {name: env[idx] for name, idx in graph.outputs.items()}
