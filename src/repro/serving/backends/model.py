"""Production model backend: ONE jitted executable per phase.

The whole prefill (scan over layers) and the whole decode step each lower
to a single XLA dispatch — the regime the paper's §9.2 asks WebGPU
runtimes to reach.  The device-side argmax is computed inside the same
executable, so the greedy path reads back one int32 per token (App. H
"token readback").

Continuous batching: ``decode_batch`` runs ``transformer.decode_step_rows``
over a slot-major ``SlotKVCache`` — every scheduler slot advances in the
SAME single dispatch, at its own per-row cache position, so per-cycle
dispatch overhead is paid once regardless of occupancy.

Recurrent families (Mamba2 / RG-LRU) batch the same way but over a
``RecurrentStateCache`` — constant-size per-slot state, no paging — via
the family's own ``decode_step_rows``; those dispatches are recorded as
``op="decode_recurrent"`` so traces distinguish the cache class.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import RunStats
from repro.models import transformer
from repro.serving.statecache import RecurrentStateCache, SlotKVCache
from repro.serving.backends.base import (BackendCapabilities, BatchState,
                                         ExecutionBackend, State, StepOutput,
                                         device_snapshot, register_backend)


@register_backend("model")
class ModelBackend(ExecutionBackend):
    """Adapter over ``Model.prefill`` / ``Model.decode_step``."""

    def __init__(self, model, params, *, mode: str = "model", batch: int = 1,
                 max_len: int = 128) -> None:
        super().__init__()
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len

        def _prefill(p, t):
            cache, logits = model.prefill(p, {"tokens": t}, max_len)
            return cache, logits, jnp.argmax(logits, -1).astype(jnp.int32)

        def _decode(p, cache, t):
            cache, logits = model.decode_step(p, cache, t)
            return cache, logits, jnp.argmax(logits, -1).astype(jnp.int32)

        def _decode_rows(p, k, v, pos, t):
            cache = {"k": k, "v": v, "pos": pos}
            cache, logits = transformer.decode_step_rows(p, self.cfg, cache, t)
            return (cache["k"], cache["v"], logits,
                    jnp.argmax(logits, -1).astype(jnp.int32))

        def _decode_paged(p, ak, av, table, pos, t):
            from repro.serving.paging import decode_step_paged
            return decode_step_paged(p, self.cfg, ak, av, table, pos, t)

        def _extend_paged(p, ak, av, table_row, pos0, valid, t):
            from repro.serving.paging import extend_step_paged
            return extend_step_paged(p, self.cfg, ak, av, table_row, pos0,
                                     valid, t)

        def _verify_paged(p, ak, av, table, pos, t):
            from repro.serving.paging import verify_step_paged
            return verify_step_paged(p, self.cfg, ak, av, table, pos, t)

        def _decode_recurrent(p, tree, pos, t):
            cache = dict(tree, pos=pos)
            cache, logits = model.decode_step_rows(p, cache, t)
            tree = {k: v for k, v in cache.items() if k != "pos"}
            return tree, logits, jnp.argmax(logits, -1).astype(jnp.int32)

        self._jit_prefill = jax.jit(_prefill)
        self._jit_decode = jax.jit(_decode)
        self._jit_decode_rows = jax.jit(_decode_rows, donate_argnums=(1, 2))
        # the paged programs note the shapes they run at while a tracer
        # is attached, so ``compiled_programs`` can name their ops
        self._ran: Dict[Tuple, Tuple] = {}
        self._jit_decode_paged = self._noting(
            jax.jit(_decode_paged, donate_argnums=(1, 2)))
        self._jit_extend_paged = self._noting(
            jax.jit(_extend_paged, donate_argnums=(1, 2)))
        self._jit_verify_paged = self._noting(
            jax.jit(_verify_paged, donate_argnums=(1, 2)))
        self._jit_decode_recurrent = jax.jit(_decode_recurrent,
                                             donate_argnums=(1,))
        batchable = self.cfg.family in ("dense", "moe")
        # recurrent families batch decode over constant-size state slots;
        # there is nothing to page, so the paged-only capabilities stay
        # honestly False and the scheduler raises instead of corrupting
        self._recurrent = (model.decode_step_rows is not None
                           and self.cfg.family in ("ssm", "hybrid"))
        self.capabilities = BackendCapabilities(
            name=mode, dispatches_per_token=1, device_argmax=True,
            decode_batch=batchable or self._recurrent,
            paged_kv=batchable, speculative=batchable, preemption=batchable,
            state_kind="recurrent" if self._recurrent else "kv")

    # ------------------------------------------------------------------
    def _noting(self, fn):
        """``fn`` as it is called, noting the shapes of its arguments
        after the parameters while a tracer is attached."""
        def call(params, *args):
            if self.tracer.enabled:
                key = (fn,) + tuple(a.shape for a in args)
                if key not in self._ran:
                    self._ran[key] = (fn, tuple(
                        jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=a.sharding)
                        for a in args))
            return fn(params, *args)
        return call

    def compiled_programs(self) -> Dict[str, List[str]]:
        """The compiled HLO text of each paged program this backend ran
        while a tracer was attached, one per shape it ran at, keyed by the
        program's name as the profiler prints it (``jit__decode_paged``,
        ``jit__extend_paged``, ``jit__verify_paged``).

        The text names every instruction as a device trace names its
        ops, so ``repro.obs.scopes.op_scopes`` over it says which part of
        the step (``kv_gather``, ``attn``, ``mlp``, ...) each op of a
        trace belongs to.  Compiles once per call and shape."""
        out: Dict[str, List[str]] = {}
        for fn, shapes in self._ran.values():
            text = fn.lower(self.params, *shapes).compile().as_text()
            name = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>,"
            out.setdefault(name, []).append(text)
        return out

    def _run(self, fn, *args, op: str = "dispatch"
             ) -> Tuple[object, StepOutput]:
        with self._dispatch_span(op):
            t0 = time.perf_counter()
            with self._phase("enqueue"):
                cache, logits, nxt = fn(*args)
            enq = time.perf_counter() - t0  # async call until handle return
            self._record(RunStats(wall_s=enq, dispatches=1, shape_ops=0,
                                  sync_mode="none", enqueue_s=enq), op=op)
        return cache, StepOutput(logits, nxt)

    def prefill(self, tokens) -> Tuple[State, StepOutput]:
        tokens = jnp.asarray(tokens, jnp.int32)
        cache, out = self._run(self._jit_prefill, self.params, tokens,
                               op="prefill")
        return {"cache": cache}, out

    def decode_step(self, state: State, tok) -> Tuple[State, StepOutput]:
        cache, out = self._run(self._jit_decode, self.params, state["cache"],
                               jnp.asarray(tok, jnp.int32), op="decode")
        return {"cache": cache}, out

    # -- continuous batching -------------------------------------------
    def alloc_slots(self, num_slots: int) -> BatchState:
        if self._recurrent:
            return {"num_slots": num_slots,
                    "rstate": RecurrentStateCache(self.model, num_slots,
                                                  self.max_len)}
        if not self.capabilities.decode_batch:
            return super().alloc_slots(num_slots)
        return {"num_slots": num_slots,
                "kv": SlotKVCache.for_model(self.cfg, num_slots,
                                            self.max_len)}

    def admit_slot(self, bstate: BatchState, slot: int, state: State
                   ) -> BatchState:
        if "rstate" in bstate:
            rs: RecurrentStateCache = bstate["rstate"]
            rs.allocate(slot)
            rs.write(slot, state["cache"])
            return bstate
        if "kv" not in bstate:
            return super().admit_slot(bstate, slot, state)
        cache = state["cache"]
        kv: SlotKVCache = bstate["kv"]
        kv.allocate(slot)
        kv.write(slot, {"k": cache["k"], "v": cache["v"]},
                 int(cache["pos"]))
        return bstate

    def release_slot(self, bstate: BatchState, slot: int,
                     tokens=None) -> BatchState:
        if "paged" in bstate:
            return super().release_slot(bstate, slot, tokens)
        if "rstate" in bstate:
            bstate["rstate"].free(slot)
            return bstate
        if "kv" not in bstate:
            return super().release_slot(bstate, slot)
        bstate["kv"].free(slot)
        return bstate

    def decode_batch(self, bstate: BatchState, tokens,
                     slots: Sequence[int]) -> Tuple[BatchState, StepOutput]:
        """ONE dispatch advances every slot at its own cache position."""
        if "paged" in bstate:
            return self._decode_batch_paged(bstate, tokens, slots)
        if "rstate" in bstate:
            return self._decode_batch_recurrent(bstate, tokens, slots)
        if "kv" not in bstate:
            return super().decode_batch(bstate, tokens, slots)
        kv: SlotKVCache = bstate["kv"]
        with self._dispatch_span("decode_batch"):
            t0 = time.perf_counter()
            k, v, logits, nxt = self._jit_decode_rows(
                self.params, kv.tree["k"], kv.tree["v"],
                device_snapshot(kv.pos), jnp.asarray(tokens, jnp.int32))
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=1, shape_ops=0,
                                  sync_mode="none", enqueue_s=enq),
                         op="decode_batch")
            kv.tree = {"k": k, "v": v}
            kv.advance(slots)
        return bstate, StepOutput(logits, nxt)

    def _decode_batch_recurrent(self, bstate: BatchState, tokens,
                                slots: Sequence[int]
                                ) -> Tuple[BatchState, StepOutput]:
        """ONE dispatch advances every recurrent slot's constant-size
        state at its own per-row position (``op="decode_recurrent"``)."""
        rs: RecurrentStateCache = bstate["rstate"]
        with self._dispatch_span("decode_recurrent"):
            t0 = time.perf_counter()
            tree, logits, nxt = self._jit_decode_recurrent(
                self.params, rs.tree, device_snapshot(rs.pos),
                jnp.asarray(tokens, jnp.int32))
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=1, shape_ops=0,
                                  sync_mode="none", enqueue_s=enq),
                         op="decode_recurrent")
            rs.tree = tree
            rs.advance(slots)
        return bstate, StepOutput(logits, nxt)

    # -- paged KV: block pool + radix prefix cache + chunked prefill ------
    def alloc_slots_paged(self, num_slots: int, *, block_size: int = 16,
                          prefill_chunk: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          prefix_cache: bool = True,
                          spec_slack: int = 0) -> BatchState:
        self.capabilities.require("paged_kv")
        return self._make_paged_state(num_slots, block_size=block_size,
                                      prefill_chunk=prefill_chunk,
                                      num_blocks=num_blocks,
                                      prefix_cache=prefix_cache,
                                      spec_slack=spec_slack)

    def prefill_paged_chunk(self, bstate: BatchState, slot: int
                            ) -> Optional[StepOutput]:
        return self._prefill_chunk_with(
            bstate, slot, self._extend_with_jit(self._jit_extend_paged))

    def _decode_batch_paged(self, bstate: BatchState, tokens,
                            slots: Sequence[int]
                            ) -> Tuple[BatchState, StepOutput]:
        with self._dispatch_span("decode_batch"):
            pg = bstate["paged"]
            with self._phase("prepare"):
                copies = sum(pg.ensure_writable(s, int(pg.pos[s]),
                                                int(pg.pos[s]) + 1)
                             for s in slots)
            ak, av, logits, nxt, *routed = self._enqueue_paged(
                self._jit_decode_paged, pg, tokens, copies, "decode_batch")
            with self._phase("adopt"):
                pg.pool.set_arena(ak, av)
                pg.advance(slots)
        return bstate, StepOutput(logits, nxt, *routed)

    def _enqueue_paged(self, fn, pg, tokens, copies: int, op: str):
        """Upload the block table, positions and tokens, then make the
        jitted paged call; ``enqueue_s`` times both, as it always has."""
        t0 = time.perf_counter()
        with self._phase("upload"):
            args = (device_snapshot(pg.table), device_snapshot(pg.pos),
                    jnp.asarray(tokens, jnp.int32))
        with self._phase("enqueue"):
            out = fn(self.params, pg.pool.arena_k, pg.pool.arena_v, *args)
        enq = time.perf_counter() - t0
        self._record(RunStats(wall_s=enq, dispatches=1 + copies, shape_ops=0,
                              sync_mode="none", enqueue_s=enq), op=op)
        return out

    def verify_paged(self, bstate: BatchState, tokens,
                     slots: Sequence[int], spans
                     ) -> Tuple[BatchState, StepOutput]:
        """ONE dispatch scores every slot's candidate span (speculative
        verify).  Writes K/V for the full span but does NOT advance
        ``pos`` — the scheduler commits the accepted prefix through the
        slot-fork API (rollback = pos rewind, zero KV copies)."""
        self.capabilities.require("speculative")
        with self._dispatch_span("verify"):
            pg = bstate["paged"]
            with self._phase("prepare"):
                copies = sum(pg.ensure_writable(
                    s, int(pg.pos[s]), int(pg.pos[s]) + max(int(span), 1))
                    for s, span in zip(slots, spans))
            ak, av, logits, nxt = self._enqueue_paged(
                self._jit_verify_paged, pg, tokens, copies, "verify")
            with self._phase("adopt"):
                pg.pool.set_arena(ak, av)
        return bstate, StepOutput(logits, nxt)
