"""The ``ExecutionBackend`` protocol — ONE execution surface for every
dispatch regime the reproduction measures.

The paper's central result (per-operation overhead, not kernel quality,
dominates batch-1 inference) is reproduced by running the SAME model at
different dispatch granularities.  Each granularity is a backend:

* ``F0``…``F4``  — op-by-op dispatch at a fusion level (Table 5)
* ``FULL``       — whole-graph capture, one executable per token (§9.2)
* ``model``      — production path: jitted scan-based prefill/decode
* ``ondevice``   — the entire generation loop inside one dispatch

Backends share a two-phase contract — ``prefill(tokens) → (state, out)``
then ``decode_step(state, tok) → (state, out)`` — and a uniform
instrumentation surface: ``capabilities`` (static facts: dispatches per
token, device-side argmax, on-device loop) and ``dispatch_stats()`` (the
Table-20-style arg-prep / enqueue / sync phase decomposition accumulated
across every run).  The serving session layer programs ONLY against this
protocol; new scenarios (batching, streaming, new fusion levels) plug in
via ``@register_backend`` without touching the session code.
"""
from __future__ import annotations

import abc
import dataclasses
import time
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import RunStats
from repro.obs.tracer import NULL_SPAN, NULL_TRACER

State = Dict[str, Any]
BatchState = Dict[str, Any]     # opaque slot-pool state (continuous batching)


def device_snapshot(a: np.ndarray) -> jax.Array:
    """Hand mutable host-side metadata (slot positions, block tables) to
    the device WITHOUT aliasing the live buffer.

    XLA:CPU zero-copy-aliases 64-byte-aligned numpy inputs into the
    runtime under immutable-buffer semantics, so passing e.g.
    ``kvp.pos`` straight into an asynchronously executing dispatch and
    then advancing it in place (``pos[slots] += 1``) is a data race —
    whether it bites depends on per-allocation alignment luck, which is
    exactly the kind of once-per-process parity flake it produces.  A
    fresh copy may be zero-copy-aliased too, but nothing ever writes it.
    """
    return jnp.asarray(np.array(a, copy=True))


class PagedAdmit(NamedTuple):
    """Result of admitting a request into a paged slot: how much of the
    prompt the radix prefix cache satisfied (zero prefill dispatches for
    that span) vs. the total prompt length."""
    cached: int
    total: int


class StepOutput(NamedTuple):
    """One prefill/decode step's device-side outputs (nothing read back).

    ``logits``      — (B, 1, V) last-position logits, still on device.
    ``next_token``  — (B, 1) int32 device-side argmax when the backend
                      computes it in-graph (the paper's "token readback"
                      regime, App. H); ``None`` when only logits exist.
    ``routed``      — (L, B, k) int32 expert ids of an MoE decode step,
                      every layer's; read back only for a live tracer.
    """
    logits: jax.Array
    next_token: Optional[jax.Array] = None
    routed: Optional[jax.Array] = None


class MultiStepOutput(NamedTuple):
    """One ``decode_multi`` super-step's device-side outputs.

    ``tokens`` — (num_slots, horizon) int32, column i = the token row s
                 sampled at cycle i; still on device (nothing read back —
                 the scheduler's async double-buffer owns the sync).
    ``valid``  — (num_slots, horizon) bool; False once row s emitted a
                 stop token at an earlier column (the stop token itself is
                 valid), so the host reconciles mid-horizon stops exactly.
    ``steps``  — scalar int32, cycles actually executed before the
                 on-device all-rows-done early exit (≤ horizon).
    """
    tokens: jax.Array
    valid: jax.Array
    steps: jax.Array


class CapabilityError(NotImplementedError, ValueError):
    """A backend was asked for a feature its ``capabilities`` do not
    advertise.  Subclasses BOTH ``NotImplementedError`` (the historical
    backend-method contract) and ``ValueError`` (the historical scheduler
    contract) so every pre-existing call site keeps its exception type.
    """


#: uniform phrasing for ``BackendCapabilities.require`` errors — one place
#: to name what each missing feature means
_FEATURE_PHRASES = {
    "decode_batch": "no batched decode",
    "decode_multi": "no multi-step decode capture",
    "paged_kv": "no paged-KV support",
    "speculative": "no speculative verify",
    "preemption": "no preemption support",
    "device_argmax": "no in-graph argmax",
    "on_device_loop": "no on-device generation loop",
    "phase_timeline": "no host-side phase timeline",
}


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """Static facts the session layer keys decisions on."""
    name: str                       # registry key
    dispatches_per_token: int       # 0 ⇒ amortized (whole loop is 1 dispatch)
    device_argmax: bool = True      # StepOutput.next_token is populated
    on_device_loop: bool = False    # generate_ondevice() is available
    phase_timeline: bool = False    # dispatch_stats() has real phase splits
    decode_batch: bool = False      # TRUE batched decode_batch (one dispatch
                                    # stream per cycle for ALL slots); False
                                    # ⇒ the per-slot-loop fallback runs
    paged_kv: bool = False          # paged block-pool KV + chunked prefill +
                                    # radix prefix cache (alloc_slots_paged /
                                    # admit_paged / prefill_paged_chunk)
    speculative: bool = False       # verify_paged(): score a drafted span
                                    # per slot in ONE batched dispatch over
                                    # the paged KV (requires paged_kv)
    preemption: bool = False        # swap_out_paged()/swap_in_paged(): a
                                    # slot's block chain can move to host
                                    # memory and back byte-exactly, so the
                                    # scheduler may preempt it (requires
                                    # paged_kv + the stacked arena layout)
    state_kind: str = "kv"          # the StateCache class this backend's
                                    # slot pool carries: "kv" (dense rows),
                                    # "paged_kv" (block arena) or
                                    # "recurrent" (constant-size slots —
                                    # Mamba2 / RG-LRU; nothing to page, so
                                    # paged_kv/speculative/preemption are
                                    # honestly False for these families)
    decode_multi: bool = False      # decode_multi(): N decode cycles
                                    # captured into ONE host submission
                                    # (on-device sampling + stop detection;
                                    # graph backends — the host-sync-free
                                    # super-step)

    def require(self, feature: str, *, hint: str = "") -> None:
        """THE capability gate: raise one uniform ``CapabilityError``
        unless the boolean capability ``feature`` is advertised.

        The error names the backend, the feature (as the literal
        ``capabilities.<feature>=False``), and ``state_kind`` — replacing
        the ad-hoc per-call-site checks that accreted one message each.
        ``hint`` appends a caller-specific remedy.
        """
        if getattr(self, feature, False):
            return
        phrase = _FEATURE_PHRASES.get(feature, f"no {feature!r} support")
        why = f"state_kind={self.state_kind!r}"
        if self.state_kind == "recurrent" and feature in (
                "paged_kv", "speculative", "preemption"):
            why += ("; constant-size recurrent slots have nothing to "
                    "page")
        msg = (f"backend {self.name!r} has {phrase}: "
               f"capabilities.{feature}=False ({why})")
        if hint:
            msg += f" — {hint}"
        raise CapabilityError(msg)


@dataclasses.dataclass
class DispatchStats:
    """Uniform cross-backend dispatch accounting (Table 20 analogue).

    Accumulated over every ``prefill``/``decode_step`` run since the last
    ``reset``; phase totals are zero for backends whose engine does not
    record a host-side timeline (single-executable paths).
    """
    steps: int = 0                  # prefill + decode invocations
    dispatches: int = 0
    shape_ops: int = 0
    arg_prep_s: float = 0.0
    enqueue_s: float = 0.0
    sync_s: float = 0.0
    wall_s: float = 0.0

    def add(self, rs: RunStats) -> None:
        self.steps += 1
        self.dispatches += rs.dispatches
        self.shape_ops += rs.shape_ops
        self.arg_prep_s += rs.arg_prep_s
        self.enqueue_s += rs.enqueue_s
        self.sync_s += rs.sync_s
        self.wall_s += rs.wall_s

    @property
    def dispatches_per_step(self) -> float:
        return self.dispatches / max(self.steps, 1)

    def row(self) -> Dict[str, Any]:
        """One uniform reporting row per backend (serve CLI / benchmarks)."""
        return {
            "steps": self.steps,
            "dispatches": self.dispatches,
            "disp_per_step": round(self.dispatches_per_step, 1),
            "arg_prep_ms": round(1e3 * self.arg_prep_s, 3),
            "enqueue_ms": round(1e3 * self.enqueue_s, 3),
            "sync_ms": round(1e3 * self.sync_s, 3),
        }


class ExecutionBackend(abc.ABC):
    """Uniform execution strategy: prefill once, then step token-by-token.

    ``state`` is an opaque per-request dict (KV cache + position).  Every
    request owns its own state, so one backend instance (compiled
    executables are shared) serves many concurrent requests — the seam the
    slot scheduler builds on.
    """

    capabilities: BackendCapabilities

    @abc.abstractmethod
    def prefill(self, tokens: jax.Array) -> Tuple[State, StepOutput]:
        """Process the prompt (B, plen) → fresh request state + first-token
        logits."""

    @abc.abstractmethod
    def decode_step(self, state: State, tok: jax.Array
                    ) -> Tuple[State, StepOutput]:
        """One autoregressive step.  tok (B, 1) int32 → (state', outputs)."""

    # -- optional fast path ------------------------------------------------
    def generate_ondevice(self, state: State, first_tok: jax.Array,
                          n_new: int, sampler, rng) -> jax.Array:
        """Run the remaining loop in one dispatch → (B, n_new) tokens.
        Only for backends with ``capabilities.on_device_loop``."""
        self.capabilities.require("on_device_loop")

    # -- continuous batching (slot pool) -----------------------------------
    # The scheduler drives these four.  ``bstate`` is an opaque batched
    # container; per-request prefill states are admitted into numbered
    # slots and one ``decode_batch`` call advances EVERY active slot.
    # Backends with ``capabilities.decode_batch`` run the whole cycle as
    # one batched dispatch stream (slot-major KV, per-row positions); the
    # default implementation below is the per-slot-loop fallback — same
    # contract, no amortization — for backends that cannot batch (e.g. the
    # pipeline-parallel ``dist`` backend).

    def alloc_slots(self, num_slots: int) -> BatchState:
        """A fresh batched decode state with ``num_slots`` empty slots."""
        return {"num_slots": num_slots, "slots": {}}

    def admit_slot(self, bstate: BatchState, slot: int, state: State
                   ) -> BatchState:
        """Move one prefilled request state into ``slot``."""
        if slot in bstate["slots"]:
            raise RuntimeError(f"slot {slot} already occupied")
        bstate["slots"][slot] = state
        return bstate

    def release_slot(self, bstate: BatchState, slot: int,
                     tokens=None) -> BatchState:
        """Free ``slot`` (request finished or evicted).

        ``tokens`` is the request's REALIZED sequence (prompt + generated,
        host ints) when the caller has it; paged backends use it to insert
        the prompt+completion chain into the radix prefix cache before the
        slot's block references drop, so a follow-up turn that replays the
        conversation gets a warm hit over the generated span too.
        """
        if "paged" in bstate:
            self._release_paged(bstate, slot, tokens)
            return bstate
        bstate["slots"].pop(slot, None)
        return bstate

    def decode_batch(self, bstate: BatchState, tokens, slots: Sequence[int]
                     ) -> Tuple[BatchState, StepOutput]:
        """One decode cycle for every slot in ``slots``.

        ``tokens`` is (num_slots, 1) int32, row s = slot s's last token
        (free rows are don't-care).  Returns a slot-indexed ``StepOutput``
        — row s of ``logits``/``next_token`` belongs to slot s.  Fallback:
        one ``decode_step`` dispatch per active slot; free rows are zeros.
        """
        n = bstate["num_slots"]
        tokens = jnp.asarray(tokens, jnp.int32)
        rows_logits: Dict[int, jax.Array] = {}
        rows_next: Dict[int, Any] = {}
        for s in slots:
            st, out = self.decode_step(bstate["slots"][s], tokens[s:s + 1])
            bstate["slots"][s] = st
            rows_logits[s] = out.logits
            rows_next[s] = out.next_token
        # free rows are zero-padded so the output stays slot-indexed like
        # the true batched implementations; the pad/concat cost is noise
        # next to the per-slot full decode dispatches this fallback pays
        any_row = next(iter(rows_logits.values()))
        zero_l = jnp.zeros_like(any_row)
        logits = jnp.concatenate(
            [rows_logits.get(s, zero_l) for s in range(n)], axis=0)
        if all(rows_next[s] is not None for s in slots):
            any_n = next(iter(rows_next.values()))
            zero_n = jnp.zeros_like(any_n)
            nxt = jnp.concatenate(
                [rows_next.get(s, zero_n) for s in range(n)], axis=0)
        else:
            nxt = None
        return bstate, StepOutput(logits, nxt)

    def decode_multi(self, bstate: BatchState, tokens,
                     slots: Sequence[int], *, horizon: int,
                     stop_table=None
                     ) -> Tuple[BatchState, MultiStepOutput]:
        """Up to ``horizon`` decode cycles in ONE host submission.

        The multi-step seam (``capabilities.decode_multi``): the backend
        replays its captured decode stream ``horizon`` times on device —
        in-graph sampling feeds each cycle's token into the next, per-row
        positions advance on device, and ``stop_table`` (row s = slot s's
        stop-token ids, -1 padded; ``None`` ⇒ no stops) drives on-device
        stop detection with an all-rows-done early exit.  ``tokens`` is
        (num_slots, 1) int32 exactly as for ``decode_batch``.

        Contract: the backend advances every slot's position by the FULL
        ``horizon`` (a slot that stops mid-horizon keeps writing into
        blocks it owns — overshoot K/V past the realized length is never
        republished, because release caps at the realized sequence), and
        records the captured stream's dispatch count ONCE per super-step
        (op ``decode_multi``), so dispatches/token drops ~``horizon``×.
        Returns a slot-indexed ``MultiStepOutput``, nothing read back.
        """
        self.capabilities.require("decode_multi")

    # -- paged KV (block pool + radix prefix cache + chunked prefill) ------
    # Backends advertising ``capabilities.paged_kv`` replace the dense
    # slot pool with fixed-size KV blocks: admission is a radix-cache match
    # plus lazy block-table setup (NO compute), prefill runs as
    # ``prefill_paged_chunk`` calls the scheduler interleaves with decode
    # cycles, and ``decode_batch``/``release_slot`` accept the paged
    # ``bstate`` transparently.  Dense remains the fallback layout.
    #
    # The paged ``bstate`` structure is uniform across backends —
    # ``{"num_slots", "paged": PagedKVCache, "radix", "chunk", "meta"}`` —
    # so admission and release are pure host bookkeeping implemented HERE
    # once; backends own only the device work (``alloc_slots_paged``
    # builds the pool in the backend's arena layout, and
    # ``prefill_paged_chunk``/``decode_batch`` run the dispatches).

    def alloc_slots_paged(self, num_slots: int, *, block_size: int = 16,
                          prefill_chunk: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          prefix_cache: bool = True,
                          spec_slack: int = 0) -> BatchState:
        """A paged batch state: block pool + per-slot tables (+ radix).

        Args:
          num_slots: concurrent request slots (block-table rows).
          block_size: tokens per KV block — the sharing/COW granularity
            of the arena and the radix cache.
          prefill_chunk: prompt tokens per ``prefill_paged_chunk`` call;
            ``None`` prefills whole prompts in one extend dispatch.
          num_blocks: arena capacity; ``None`` sizes for every slot full
            plus two spare prefix-cache chains (see ``PagedKVCache``).
          prefix_cache: attach a ``RadixPrefixCache`` so ``admit_paged``
            can adopt shared prefixes by reference.
          spec_slack: extra table width for speculative verify, whose
            span may overhang ``max_len`` by the draft width before a
            rejection rewinds it (``Scheduler`` passes ``k + 1``).
        """
        self.capabilities.require("paged_kv")

    def _make_paged_state(self, num_slots: int, *, block_size: int,
                          prefill_chunk: Optional[int],
                          num_blocks: Optional[int], prefix_cache: bool,
                          layout: str = "stacked",
                          spec_slack: int = 0) -> BatchState:
        """Construct the uniform paged bstate — pool + radix + chunk/meta
        bookkeeping.  The chunk-slack rule lives here ONCE: padded final
        chunks write up to chunk-1 tokens past the prompt, so tables get
        that much extra width (``spec_slack`` extends it again for
        speculative verify, whose span may overhang ``max_len`` by the
        draft width before rejection rewinds it).  Backends layer their
        device specifics on top (graph: engines over a ``layout="graph"``
        arena; dist: stage-resharding the arena)."""
        from repro.serving.paging import PagedKVCache, RadixPrefixCache
        slack = max(0, (prefill_chunk or 1) - 1) + max(0, spec_slack)
        pg = PagedKVCache(self.cfg, num_slots, self.max_len,
                          block_size=block_size, num_blocks=num_blocks,
                          table_slack=slack, layout=layout)
        radix = RadixPrefixCache(pg.pool, block_size) if prefix_cache \
            else None
        pg.radix = radix
        return {"num_slots": num_slots, "paged": pg, "radix": radix,
                "chunk": prefill_chunk, "meta": {}}

    def admit_paged(self, bstate: BatchState, slot: int, prompt
                    ) -> "PagedAdmit":
        """Bind a prompt to ``slot``: radix prefix match, shared-block
        adoption (COW at a partial boundary), chunk cursor setup.  Cheap —
        the prefill compute happens in ``prefill_paged_chunk``.

        Args:
          bstate: a paged batch state from ``alloc_slots_paged``.
          slot: a free slot index; its block table and ``meta`` entry
            (prompt array + chunk cursor) are initialized here.
          prompt: host token ids, any array-like; the match is capped at
            ``len(prompt) - 1`` so the last token always runs through the
            extend path and first-token logits exist.

        Returns ``PagedAdmit(cached, total)`` — the radix-cache hit depth
        versus the prompt length, i.e. how much prefill is skipped.
        """
        if "paged" not in bstate:
            self.capabilities.require("paged_kv")
            raise ValueError("admit_paged needs the paged batch state "
                             "from alloc_slots_paged")
        pg = bstate["paged"]
        radix = bstate["radix"]
        toks = np.asarray(prompt, np.int32).reshape(-1)
        pg.allocate(slot)
        # cap the match at plen-1: the last prompt token always runs
        # through the extend path so first-token logits exist
        matched, blocks = (radix.match(toks[:-1]) if radix is not None
                           else (0, []))
        with self._dispatch_span("cow_adopt"):
            copies = pg.adopt_prefix(slot, matched, blocks)
            if copies:
                self._record(RunStats(wall_s=0.0, dispatches=copies,
                                      shape_ops=0, sync_mode="none"),
                             op="cow_adopt")
        bstate["meta"][slot] = {"prompt": toks, "cursor": matched}
        return PagedAdmit(cached=matched, total=len(toks))

    def prefill_paged_chunk(self, bstate: BatchState, slot: int
                            ) -> Optional[StepOutput]:
        """Run the next prefill chunk for ``slot`` (one dispatch).

        Args:
          bstate: a paged batch state with ``slot`` admitted via
            ``admit_paged``; the chunk width comes from ``bstate["chunk"]``
            (``None`` → the whole remaining prompt in one extend).
          slot: a slot mid-prefill (its meta cursor < prompt length).

        Returns the first-token ``StepOutput`` when the prompt completes
        (the finished FULL-block prefix is inserted into the radix cache),
        else ``None`` — the scheduler interleaves these calls with
        ``decode_batch`` cycles for chunked prefill.
        """
        self.capabilities.require("paged_kv")

    def _prefill_chunk_with(self, bstate: BatchState, slot: int, run_extend
                            ) -> Optional[StepOutput]:
        """Shared chunked-prefill driver.

        The chunk-cursor bookkeeping, padded-buffer prep, COW block
        preparation and radix insert-on-completion are identical across
        every paged backend and live HERE; only the executable differs.
        ``run_extend(bstate, slot, buf, cur, valid, copies) → (logits,
        next_token)`` runs one extend step and owns its arena adoption and
        dispatch accounting — ``_extend_with_jit`` wraps the common
        array-signature jit (model/dist), the graph backend supplies its
        engine-driven executor.  The whole call is one
        ``dispatch:prefill_chunk`` span.
        """
        with self._dispatch_span("prefill_chunk"):
            pg = bstate["paged"]
            meta = bstate["meta"][slot]
            toks, cur = meta["prompt"], meta["cursor"]
            plen = len(toks)
            c = bstate["chunk"] or (plen - cur)
            valid = min(c, plen - cur)
            buf = np.zeros((1, c), np.int32)
            buf[0, :valid] = toks[cur:cur + valid]
            with self._phase("prepare"):
                copies = pg.ensure_writable(slot, cur, cur + c)
            logits, nxt = run_extend(bstate, slot, buf, cur, valid, copies)
            meta["cursor"] = cur + valid
            pg.pos[slot] = cur + valid
            if meta["cursor"] < plen:
                return None
            self._finish_paged_prefill(bstate, slot)
            return StepOutput(logits, nxt)

    def _extend_with_jit(self, fn):
        """Executor for ``_prefill_chunk_with`` over the shared jitted
        signature ``fn(params, arena_k, arena_v, table_row, pos0, valid,
        tokens) → (arena_k', arena_v', logits, next_token)`` (the
        single-device extend or the dist pipeline extend)."""
        def run(bstate, slot, buf, cur, valid, copies):
            pg = bstate["paged"]
            t0 = time.perf_counter()     # enqueue_s: upload + call
            with self._phase("upload"):
                args = (device_snapshot(pg.table[slot:slot + 1]),
                        jnp.int32(cur), jnp.int32(valid), jnp.asarray(buf))
            with self._phase("enqueue"):
                ak, av, logits, nxt = fn(self.params, pg.pool.arena_k,
                                         pg.pool.arena_v, *args)
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=1 + copies,
                                  shape_ops=0, sync_mode="none",
                                  enqueue_s=enq), op="prefill_chunk")
            with self._phase("adopt"):
                pg.pool.set_arena(ak, av)
            return logits, nxt
        return run

    def verify_paged(self, bstate: BatchState, tokens, slots: Sequence[int],
                     spans) -> Tuple[BatchState, StepOutput]:
        """One speculative-verify cycle: score every slot's candidate span
        in ONE batched target dispatch.

        ``tokens`` is (num_slots, C) int32 — column 0 holds slot s's
        pending last token (an ordinary decode step), columns 1.. its
        drafted continuation, zero-padded.  ``spans[s]`` is how many
        columns slot s actually uses (1 for non-speculating slots).
        Returns a slot-indexed ``StepOutput`` with (S, C, V) logits and
        (S, C) next tokens: ``next_token[s, j]`` is the target's greedy
        pick after consuming ``tokens[s, :j+1]``.  The backend scatters
        K/V for ALL C positions but does NOT advance ``pos`` — the caller
        commits or rolls back through the slot-fork API.
        """
        self.capabilities.require("speculative")

    def swap_out_paged(self, bstate: BatchState, slot: int) -> Dict[str, Any]:
        """Preempt ``slot``: move its block chain off the arena, free the
        slot.

        Shared blocks (radix/COW, refcount > 1) transfer their reference
        into the returned record without touching device memory; exclusive
        blocks are copied to host numpy and freed — that is the arena
        capacity the preemption reclaims (the ``dist/elastic.py`` idiom:
        host arrays carry no placement assumptions, so restore is a plain
        re-upload).  Zero dispatches; the host readback is accounted as a
        ``swap_out`` op.

        Args:
          bstate: a paged batch state (``capabilities.preemption`` only).
          slot: the victim slot; its table row is cleared and its meta
            entry (prompt + chunk cursor) is captured in the record.

        Returns an opaque record for ``swap_in_paged``.  The caller owns
        it: restore exactly once, or discard via
        ``bstate["paged"].drop_swap(record["chain"])``.
        """
        self.capabilities.require("preemption")
        if "paged" not in bstate:
            raise ValueError("swap_out_paged needs the paged batch state "
                             "from alloc_slots_paged")
        pg = bstate["paged"]
        with self._dispatch_span("swap_out"):
            chain = pg.swap_out(slot)
            self._record(RunStats(wall_s=0.0, dispatches=0, shape_ops=0,
                                  sync_mode="none"), op="swap_out")
        return {"chain": chain, "meta": bstate["meta"].pop(slot, None)}

    def swap_in_paged(self, bstate: BatchState, swap: Dict[str, Any],
                      slot: Optional[int] = None) -> int:
        """Restore a ``swap_out_paged`` record into a (possibly different)
        slot, byte-exactly.

        Retained shared blocks re-bind by table assignment (no device
        work); host-copied blocks upload one dispatch each, recorded as a
        ``swap_in`` op so dispatch accounting and the tracer stay exact.

        Args:
          bstate: the same paged batch state the record came from.
          swap: the record returned by ``swap_out_paged``.
          slot: destination slot; ``None`` picks any free one.

        Returns the slot the chain landed in; the slot's meta (prompt +
        cursor) is restored so decode resumes exactly where it stopped.
        """
        pg = bstate["paged"]
        with self._dispatch_span("swap_in"):
            t0 = time.perf_counter()
            slot, uploads = pg.swap_in(swap["chain"], slot)
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=uploads,
                                  shape_ops=0, sync_mode="none",
                                  enqueue_s=enq), op="swap_in")
        if swap["meta"] is not None:
            bstate["meta"][slot] = swap["meta"]
        return slot

    def _finish_paged_prefill(self, bstate: BatchState, slot: int) -> None:
        """Shared end-of-prompt bookkeeping: cache the prompt's FULL blocks
        in the radix tree (the partial tail block stays private — decode
        keeps appending into it)."""
        pg = bstate["paged"]
        radix = bstate["radix"]
        if radix is None:
            return
        toks = bstate["meta"][slot]["prompt"]
        nfull = len(toks) // pg.block_size
        if nfull:
            radix.insert(toks[:nfull * pg.block_size],
                         pg.chain(slot, nfull * pg.block_size))

    def _release_paged(self, bstate: BatchState, slot: int, tokens) -> None:
        """Paged release: insert the prompt+GENERATED chain, then free.

        The slot's cached KV covers positions [0, pos) — the prompt plus
        every generated token that was fed back through decode (the final
        sampled token never was: that is the sampling boundary, so the
        insert stops exactly there and a later adopter COW-forks the
        partial boundary block as usual).  Inserting BEFORE the free keeps
        the chain's blocks referenced by the radix tree when the slot's own
        references drop, so multi-turn follow-ups replaying prompt +
        completion hit warm.
        """
        pg = bstate["paged"]
        radix = bstate["radix"]
        if radix is not None and tokens is not None:
            seq = np.asarray(tokens, np.int32).reshape(-1)
            # cap at the REALIZED length as well as pos: a speculative
            # fork can leave pos past the accepted stream (rejected draft
            # KV parked beyond it), and those draft tokens must never
            # become radix-cache keys
            covered = min(int(pg.pos[slot]), len(seq))
            seq = seq[:covered]
            nfull = len(seq) // pg.block_size
            if nfull:
                radix.insert(seq[:nfull * pg.block_size],
                             pg.chain(slot, nfull * pg.block_size))
        pg.free(slot)
        bstate["meta"].pop(slot, None)

    # -- uniform instrumentation ------------------------------------------
    def __init__(self) -> None:
        self._stats = DispatchStats()
        #: optional span tracer (``repro.obs``).  NULL_TRACER's recording
        #: calls are no-ops, so the hot path pays one branch when tracing
        #: is off; the scheduler swaps a live tracer in when asked.
        self.tracer = NULL_TRACER

    def dispatch_stats(self) -> DispatchStats:
        return self._stats

    def reset_stats(self) -> None:
        self._stats = DispatchStats()

    def _lane(self) -> str:
        return f"backend:{self.capabilities.name}"

    def _dispatch_span(self, op: str):
        """The live span one backend call opens on its dispatch lane
        (``repro.dispatch:<op>`` on the profiler); every ``_record`` inside
        it tallies its counts onto it."""
        tr = self.tracer
        if not tr.enabled:
            return NULL_SPAN
        return tr.span(f"dispatch:{op}", cat="dispatch", track=self._lane(),
                       op=op)

    def _phase(self, name: str):
        """A span on the dispatch lane for one part of a backend call:
        ``prepare`` (COW), ``upload`` (host metadata to the device),
        ``enqueue`` (the jitted call until it returns), ``adopt`` (taking
        the new arena)."""
        tr = self.tracer
        if not tr.enabled:
            return NULL_SPAN
        return tr.span(name, track=self._lane())

    def _record(self, rs: RunStats, op: str = "dispatch") -> None:
        """The SINGLE dispatch-accounting choke point: every backend
        dispatch flows through here, updating ``dispatch_stats()`` AND —
        when a tracer is attached — tallying the same count onto the live
        ``dispatch:<op>`` span the calling backend method opened
        (``_dispatch_span``).  Trace-derived totals therefore equal the
        stats delta exactly (the CI obs gate asserts it)."""
        self._stats.add(rs)
        tr = self.tracer
        if tr.enabled:
            sp = tr.open_span(self._lane(), cat="dispatch")
            if sp is None:
                raise RuntimeError(
                    f"dispatch {op!r} recorded outside a dispatch span: "
                    "open one with _dispatch_span around the backend call")
            sp.tally(dispatches=rs.dispatches,
                     enqueue_us=round(1e6 * rs.enqueue_s, 1),
                     sync_us=round(1e6 * rs.sync_s, 1))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., ExecutionBackend]] = {}


def register_backend(*names: str):
    """Class decorator: ``@register_backend("F0", …)``.  The factory is
    called as ``factory(model, params, mode=name, batch=…, max_len=…)``."""

    def deco(factory):
        taken = [n for n in names if n in _REGISTRY]
        if taken:  # validate BEFORE mutating: no half-registered factories
            raise ValueError(f"backend(s) {taken} already registered")
        for n in names:
            _REGISTRY[n] = factory
        return factory

    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> Callable[..., ExecutionBackend]:
    """Registry round-trip: the factory registered under ``name``."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def create_backend(name: str, model, params, *, batch: int = 1,
                   max_len: int = 128, **kw) -> ExecutionBackend:
    """Instantiate the backend registered under ``name``."""
    factory = get_backend(name)
    return factory(model, params, mode=name, batch=batch, max_len=max_len,
                   **kw)
