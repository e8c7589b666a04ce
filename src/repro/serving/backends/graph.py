"""Dispatch-graph backends: the paper's measured execution regimes.

``F0``…``F4`` run one jitted executable per op (``DispatchEngine``) at a
progressive fusion level (Table 5); ``FULL`` captures the whole step into
ONE executable (``FullGraphEngine``, the §9.2 CUDA-Graphs analogue).
Numerics are identical across all six — only dispatch granularity changes,
which is exactly the controlled experiment the protocol exposes through
``dispatch_stats()``.

Continuous batching: ``decode_batch`` runs a ``slot_pos=True`` decode
graph (per-row positions, per-row cache scatter) over a slot-major
``SlotKVCache``.  The batched graph has the SAME dispatch count as the
single-request graph, so one cycle's dispatch stream amortizes over every
active slot — the structural escape from the paper's ~95 µs/op batch-1
overhead wall.

Paged KV: ``alloc_slots_paged`` swaps the dense pool for a graph-layout
``BlockPool`` arena (one ``k_arena_i``/``v_arena_i`` input per layer —
exactly the paged OpGraph's named inputs, so no per-cycle re-layout) and
decodes through ``build_decode_graph(paged=True)``, whose dispatch count
is IDENTICAL to the ``slot_pos`` graph — this is the dispatch-measured
path, so paging must stay free in the per-operation accounting the CI
bench job gates.  Chunked prefill runs ``build_extend_graph`` — the same
per-op stream as prefill, through block tables — so radix prefix hits
skip REAL dispatches on the measured regime.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core.engine import (DispatchEngine, FullGraphEngine,
                               MultiStepEngine, RunStats)
from repro.core.graphs import (LEVELS, build_decode_graph, build_extend_graph,
                               build_prefill_graph)
from repro.serving.statecache import (SlotKVCache, empty_graph_cache,
                                      load_prefix)
from repro.serving.backends.base import (BackendCapabilities, BatchState,
                                         ExecutionBackend, MultiStepOutput,
                                         State, StepOutput, device_snapshot,
                                         register_backend)

GRAPH_MODES = tuple(LEVELS) + ("FULL",)


@register_backend(*GRAPH_MODES)
class GraphBackend(ExecutionBackend):
    """Adapter: OpGraph + dispatch engine behind the backend protocol."""

    def __init__(self, model, params, *, mode: str, batch: int = 1,
                 max_len: int = 128) -> None:
        super().__init__()
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.mode = mode
        self.batch = batch
        self.max_len = max_len
        self._full = mode == "FULL"
        self._fusion = LEVELS["F0" if self._full else mode]
        graph = build_decode_graph(params, self.cfg, batch=batch,
                                   max_len=max_len, fusion=self._fusion)
        self._decode_graph = graph
        self._decode_engine = (FullGraphEngine(graph) if self._full
                               else DispatchEngine(graph))
        self._prefill_engines: Dict[int, Any] = {}
        self._batched_engines: Dict[int, Any] = {}   # num_slots → engine
        # paged engines are pool-stateless (arenas/tables are run inputs),
        # so they are shared across schedulers with the same pool geometry
        self._paged_engines: Dict[Any, Any] = {}     # decode, keyed on
        self._paged_extend_engines: Dict[Any, Any] = {}   # pool geometry
        # multi-step super-step engines, keyed on (decode graph, horizon);
        # dense and paged share the cache because the graph identity
        # already encodes num_slots and pool geometry
        self._multi_engines: Dict[Any, Any] = {}
        batchable = self.cfg.family in ("dense", "moe")
        self.capabilities = BackendCapabilities(
            name=mode,
            dispatches_per_token=1 if self._full else graph.num_dispatches(),
            device_argmax=True,
            phase_timeline=True,
            decode_batch=batchable,
            paged_kv=batchable,
            decode_multi=batchable,
        )

    # ------------------------------------------------------------------
    def _prefill_engine(self, prompt_len: int):
        eng = self._prefill_engines.get(prompt_len)
        if eng is None:
            graph = build_prefill_graph(self.params, self.cfg,
                                        batch=self.batch,
                                        prompt_len=prompt_len,
                                        max_len=self.max_len,
                                        fusion=self._fusion)
            eng = (FullGraphEngine(graph) if self._full
                   else DispatchEngine(graph))
            self._prefill_engines[prompt_len] = eng
        return eng

    def prefill(self, tokens) -> Tuple[State, StepOutput]:
        tokens = jnp.asarray(tokens, jnp.int32)
        b, plen = tokens.shape
        assert b == self.batch, f"backend built for batch={self.batch}, got {b}"
        eng = self._prefill_engine(plen)
        with self._dispatch_span("prefill"):
            out, rs = eng.run({"tokens": tokens}, record_timeline=True)
            self._record(rs, op="prefill")
        cache = load_prefix(
            empty_graph_cache(self.cfg, b, self.max_len), out,
            self.cfg.num_layers)
        state: State = {"cache": cache, "pos": plen}
        return state, StepOutput(out["logits"], out["next_token"])

    def decode_step(self, state: State, tok) -> Tuple[State, StepOutput]:
        inputs = dict(state["cache"])
        inputs["tokens"] = jnp.asarray(tok, jnp.int32)
        inputs["pos"] = jnp.int32(state["pos"])
        with self._dispatch_span("decode"):
            out, rs = self._decode_engine.run(inputs, record_timeline=True)
            self._record(rs, op="decode")
        cache = {}
        for l in range(self.cfg.num_layers):
            cache[f"k_cache_{l}"] = out[f"k_cache_{l}"]
            cache[f"v_cache_{l}"] = out[f"v_cache_{l}"]
        new_state: State = {"cache": cache, "pos": state["pos"] + 1}
        return new_state, StepOutput(out["logits"], out["next_token"])

    # -- continuous batching -------------------------------------------
    def _batched_engine(self, num_slots: int):
        eng = self._batched_engines.get(num_slots)
        if eng is None:
            graph = build_decode_graph(self.params, self.cfg,
                                       batch=num_slots, max_len=self.max_len,
                                       fusion=self._fusion, slot_pos=True)
            eng = (FullGraphEngine(graph) if self._full
                   else DispatchEngine(graph))
            self._batched_engines[num_slots] = eng
        return eng

    def alloc_slots(self, num_slots: int) -> BatchState:
        if not self.capabilities.decode_batch:
            return super().alloc_slots(num_slots)
        self._batched_engine(num_slots)    # build/compile the cycle graph
        return {"num_slots": num_slots,
                "kv": SlotKVCache.for_graph(self.cfg, num_slots,
                                            self.max_len)}

    def admit_slot(self, bstate: BatchState, slot: int, state: State
                   ) -> BatchState:
        if "kv" not in bstate:
            return super().admit_slot(bstate, slot, state)
        kvp: SlotKVCache = bstate["kv"]
        kvp.allocate(slot)
        kvp.write(slot, state["cache"], int(state["pos"]))
        return bstate

    def release_slot(self, bstate: BatchState, slot: int,
                     tokens=None) -> BatchState:
        if "paged" in bstate:
            return super().release_slot(bstate, slot, tokens)
        if "kv" not in bstate:
            return super().release_slot(bstate, slot)
        bstate["kv"].free(slot)
        return bstate

    def decode_batch(self, bstate: BatchState, tokens,
                     slots: Sequence[int]) -> Tuple[BatchState, StepOutput]:
        """One dispatch STREAM (F-levels) or ONE dispatch (FULL) per cycle,
        shared by every active slot via per-row graph positions."""
        if "paged" in bstate:
            return self._decode_batch_paged(bstate, tokens, slots)
        if "kv" not in bstate:
            return super().decode_batch(bstate, tokens, slots)
        kvp: SlotKVCache = bstate["kv"]
        eng = self._batched_engine(bstate["num_slots"])
        inputs = dict(kvp.tree)
        inputs["tokens"] = jnp.asarray(tokens, jnp.int32)
        inputs["pos"] = device_snapshot(kvp.pos)
        with self._dispatch_span("decode_batch"):
            out, rs = eng.run(inputs, record_timeline=True)
            self._record(rs, op="decode_batch")
        kvp.tree = {f"{c}_cache_{l}": out[f"{c}_cache_{l}"]
                    for l in range(self.cfg.num_layers) for c in ("k", "v")}
        kvp.advance(slots)
        return bstate, StepOutput(out["logits"], out["next_token"])

    # -- paged KV: block-pool arena + radix cache through the OpGraphs ----
    def alloc_slots_paged(self, num_slots: int, *, block_size: int = 16,
                          prefill_chunk: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          prefix_cache: bool = True,
                          spec_slack: int = 0) -> BatchState:
        self.capabilities.require("paged_kv")
        bstate = self._make_paged_state(num_slots, block_size=block_size,
                                        prefill_chunk=prefill_chunk,
                                        num_blocks=num_blocks,
                                        prefix_cache=prefix_cache,
                                        layout="graph",
                                        spec_slack=spec_slack)
        pg = bstate["paged"]
        key = (num_slots, block_size, pg.pool.num_blocks, pg.width)
        eng = self._paged_engines.get(key)
        if eng is None:
            # the paged cycle graph: dispatch count IDENTICAL to the
            # slot_pos graph (asserted in tests and gated in CI) — paging
            # is free in the per-operation accounting this backend measures
            graph = build_decode_graph(self.params, self.cfg,
                                       batch=num_slots,
                                       max_len=self.max_len,
                                       fusion=self._fusion, paged=True,
                                       block_size=block_size,
                                       num_blocks=pg.pool.num_blocks,
                                       table_width=pg.width)
            eng = (FullGraphEngine(graph) if self._full
                   else DispatchEngine(graph))
            self._paged_engines[key] = eng
        bstate["decode_eng"] = eng
        return bstate

    def _extend_engine(self, bstate: BatchState, chunk: int):
        """One compiled extend stream per (chunk width, pool geometry) —
        shared across schedulers like the per-length prefill engines."""
        pg = bstate["paged"]
        key = (chunk, pg.block_size, pg.pool.num_blocks, pg.width)
        eng = self._paged_extend_engines.get(key)
        if eng is None:
            graph = build_extend_graph(self.params, self.cfg, chunk=chunk,
                                       max_len=self.max_len,
                                       fusion=self._fusion,
                                       block_size=pg.block_size,
                                       num_blocks=pg.pool.num_blocks,
                                       table_width=pg.width)
            eng = (FullGraphEngine(graph) if self._full
                   else DispatchEngine(graph))
            self._paged_extend_engines[key] = eng
        return eng

    def _extend_with_engine(self, bstate, slot, buf, cur, valid, copies):
        """Engine-driven executor for the shared ``_prefill_chunk_with``
        driver: one per-op dispatch stream (or one captured dispatch for
        FULL) per chunk, honestly accounted."""
        pg = bstate["paged"]
        if copies:
            self._record(RunStats(wall_s=0.0, dispatches=copies, shape_ops=0,
                                  sync_mode="none"), op="cow_copy")
        eng = self._extend_engine(bstate, buf.shape[1])
        inputs = dict(pg.pool.tree)
        inputs["tokens"] = jnp.asarray(buf)
        inputs["pos0"] = jnp.int32(cur)
        inputs["valid"] = jnp.int32(valid)
        inputs["block_table"] = device_snapshot(pg.table[slot:slot + 1])
        out, rs = eng.run(inputs, record_timeline=True)
        self._record(rs, op="prefill_chunk")
        pg.pool.set_tree(out)
        return out["logits"], out["next_token"]

    def prefill_paged_chunk(self, bstate: BatchState, slot: int
                            ) -> Optional[StepOutput]:
        return self._prefill_chunk_with(bstate, slot,
                                        self._extend_with_engine)

    def _decode_batch_paged(self, bstate: BatchState, tokens,
                            slots: Sequence[int]
                            ) -> Tuple[BatchState, StepOutput]:
        """The paged cycle: same dispatch stream as the dense slot_pos
        cycle, read/written through per-slot block tables."""
        pg = bstate["paged"]
        with self._dispatch_span("decode_batch"):
            copies = 0
            for s in slots:
                copies += pg.ensure_writable(s, int(pg.pos[s]),
                                             int(pg.pos[s]) + 1)
            if copies:
                self._record(RunStats(wall_s=0.0, dispatches=copies,
                                      shape_ops=0, sync_mode="none"),
                             op="cow_copy")
            eng = bstate["decode_eng"]
            inputs = dict(pg.pool.tree)
            inputs["tokens"] = jnp.asarray(tokens, jnp.int32)
            inputs["pos"] = device_snapshot(pg.pos)
            inputs["block_table"] = device_snapshot(pg.table)
            out, rs = eng.run(inputs, record_timeline=True)
            self._record(rs, op="decode_batch")
            pg.pool.set_tree(out)
            pg.advance(slots)
        return bstate, StepOutput(out["logits"], out["next_token"])

    # -- multi-step decode capture (the host-sync-free super-step) --------
    def _multi_engine(self, graph, horizon: int) -> MultiStepEngine:
        """One captured super-step per (decode graph, horizon) — the graph
        identity already encodes num_slots / pool geometry, so dense and
        paged engines share this cache.  The recorded stream is the
        single-cycle dispatch count (1 for FULL): the host submits that
        stream once per horizon."""
        key = (id(graph), horizon)
        eng = self._multi_engines.get(key)
        if eng is None:
            eng = MultiStepEngine(
                graph, horizon=horizon,
                stream_dispatches=1 if self._full
                else graph.num_dispatches())
            self._multi_engines[key] = eng
        return eng

    def decode_multi(self, bstate: BatchState, tokens,
                     slots: Sequence[int], *, horizon: int,
                     stop_table=None
                     ) -> Tuple[BatchState, MultiStepOutput]:
        """Up to ``horizon`` decode cycles in ONE host submission: the
        captured per-op stream (or the FULL executable) replayed inside a
        device-side loop with in-graph argmax feedback and on-device stop
        detection.  Positions advance by the full horizon — a slot that
        stops early keeps writing into rows/blocks it owns, and release
        caps the published KV at the realized sequence."""
        self.capabilities.require("decode_multi")
        if "paged" in bstate:
            return self._decode_multi_paged(bstate, tokens, slots,
                                            horizon=horizon,
                                            stop_table=stop_table)
        kvp: SlotKVCache = bstate["kv"]
        eng = self._multi_engine(
            self._batched_engine(bstate["num_slots"]).graph, horizon)
        with self._dispatch_span("decode_multi"):
            caches, toks, valid, steps, rs = eng.run(
                kvp.tree, tokens, device_snapshot(kvp.pos),
                stop_table=stop_table)
            self._record(rs, op="decode_multi")
        kvp.tree = dict(caches)
        kvp.pos[list(slots)] += horizon
        return bstate, MultiStepOutput(toks, valid, steps)

    def _decode_multi_paged(self, bstate: BatchState, tokens,
                            slots: Sequence[int], *, horizon: int,
                            stop_table=None
                            ) -> Tuple[BatchState, MultiStepOutput]:
        """The paged super-step: block tables are loop-invariant, so every
        block the horizon can touch is claimed (fresh or COW-forked) up
        front — the same accounting as ``horizon`` single steps, paid in
        one host pass."""
        pg = bstate["paged"]
        with self._dispatch_span("decode_multi"):
            copies = 0
            for s in slots:
                copies += pg.ensure_writable(s, int(pg.pos[s]),
                                             int(pg.pos[s]) + horizon)
            if copies:
                self._record(RunStats(wall_s=0.0, dispatches=copies,
                                      shape_ops=0, sync_mode="none"),
                             op="cow_copy")
            eng = self._multi_engine(bstate["decode_eng"].graph, horizon)
            caches, toks, valid, steps, rs = eng.run(
                pg.pool.tree, tokens, device_snapshot(pg.pos),
                stop_table=stop_table,
                static={"block_table": device_snapshot(pg.table)})
            self._record(rs, op="decode_multi")
        pg.pool.set_tree(caches)
        pg.pos[list(slots)] += horizon
        return bstate, MultiStepOutput(toks, valid, steps)
