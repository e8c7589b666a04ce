"""Paged KV-cache block allocator.

Dense per-slot KV rows (``SlotKVCache``) reserve ``max_len`` tokens per
slot up front, so memory scales with the WORST-CASE sequence length and
identical prefixes are stored once per request.  The paged layout instead
carves one preallocated arena into fixed-size **blocks**:

    arena_k / arena_v : (num_blocks, layers, block_size, kv_heads, head_dim)

or, where ``head_dim`` is not a multiple of the TPU's 128 lanes, the same
with the heads merged into one lane-dense minor axis,
``(num_blocks, layers, block_size, kv_heads * head_dim)``
(``arena_shape``).

``BlockPool`` owns the arena plus the host-side free list and per-block
reference counts; blocks are shared read-only between requests (and the
``RadixPrefixCache``) and copy-on-write forked the moment a writer touches
a block someone else still references.  ``PagedKVCache`` layers the slot
bookkeeping on top: a per-slot **block table** mapping logical token
positions to arena blocks, lazy block allocation as sequences grow, and
LRU eviction of cache-only chains under pool pressure (delegated to the
attached radix cache — an active slot's own references always keep its
blocks alive, so eviction can never corrupt in-flight decode).

Block 0 is reserved as a **trash block**: free slots' table rows point at
it, so the batched decode dispatch can scatter its don't-care rows without
host-side masking.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.statecache.base import StateCache


LANES = 128         # a TPU vector register's lane width


def _ceildiv(a: int, b: int) -> int:
    return -(-a // b)


def arena_shape(cfg: ModelConfig, num_blocks: int, block_size: int
                ) -> Tuple[int, ...]:
    """The stacked arena's shape (K and V alike), block axis first.

    ``(N, L, Bs, KV, hd)`` when ``head_dim`` fills whole 128-lane tiles;
    otherwise ``(N, L, Bs, KV·hd)``.  A (KV, hd) = (2, 64) minor pair is
    tiled half-empty into 128 lanes, and the TPU compiler then relays the
    whole arena out before every gather; merged, the minor axis is lane
    dense and the arena is read as stored.  Readers split the heads after
    the gather (``attention.gather_blocks``) and writers merge them before
    the scatter (``attention.write_kv``), so both shapes share one path.
    """
    hd = cfg.resolved_head_dim
    heads = ((cfg.num_kv_heads, hd) if hd % LANES == 0
             else (cfg.num_kv_heads * hd,))
    return (num_blocks, cfg.num_layers, block_size) + heads


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_block(ak, av, src, dst):
    """Device-side block copy (the COW fork): arena[dst] = arena[src]."""
    def cp(a):
        row = jax.lax.dynamic_index_in_dim(a, src, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(a, row, dst, 0)
    return cp(ak), cp(av)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _load_block(ak, av, kb, vb, dst):
    """Host→device block upload (the swap-in path): arena[dst] = host KV."""
    def ld(a, row):
        return jax.lax.dynamic_update_index_in_dim(a, row, dst, 0)
    return ld(ak, kb), ld(av, vb)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block_tree(tree, src, dst):
    """Graph-layout COW fork: one executable copying block ``src`` → ``dst``
    across every per-layer arena leaf."""
    def cp(a):
        row = jax.lax.dynamic_index_in_dim(a, src, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(a, row, dst, 0)
    return jax.tree.map(cp, tree)


class BlockPool:
    """Fixed-size KV block arena + free list + refcounts + COW.

    The arena is allocated ONCE; every block the serving layer ever uses is
    a row of it.  ``alloc`` hands out the lowest free block id (refcount 1);
    ``incref``/``decref`` manage sharing (radix-cache chains and admitted
    requests each hold their own reference); a block returns to the free
    list exactly when its refcount hits zero.  ``cow`` forks a shared block
    before a write diverges it.

    Two device layouts carry the same host bookkeeping (block ids, the free
    list and refcounts are layout-blind):

    * ``stacked`` — ``arena_k``/``arena_v`` with the layer axis inside,
      ``arena_shape(...)``: ``(num_blocks, L, block_size, KV, hd)``,
      heads merged to ``KV·hd`` where ``hd`` is not lane-aligned; what
      the jitted model-path attention
      (``decode_step_paged``/``extend_step_paged``) consumes.
    * ``graph``   — ``tree`` of one ``k_arena_i``/``v_arena_i`` leaf per
      layer, ``(num_blocks, block_size, KV, hd)`` each, exactly the named
      inputs the paged decode/extend OpGraphs declare — handed to the
      dispatch engines with no per-cycle re-layout.
    """

    def __init__(self, cfg: ModelConfig, num_blocks: int, block_size: int,
                 *, layout: str = "stacked") -> None:
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (one is the trash block)")
        if layout not in ("stacked", "graph"):
            raise ValueError(f"unknown arena layout {layout!r}")
        hd = cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        self.layout = layout
        self.num_layers = cfg.num_layers
        self.kv_heads = cfg.num_kv_heads
        if layout == "graph":
            shape = (num_blocks, block_size, cfg.num_kv_heads, hd)
            self.tree = {}
            for i in range(cfg.num_layers):
                self.tree[f"k_arena_{i}"] = jnp.zeros(shape, dt)
                self.tree[f"v_arena_{i}"] = jnp.zeros(shape, dt)
        else:
            shape = arena_shape(cfg, num_blocks, block_size)
            self.arena_k = jnp.zeros(shape, dt)
            self.arena_v = jnp.zeros(shape, dt)
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.refcount = np.zeros((num_blocks,), np.int32)
        self._free: List[int] = list(range(num_blocks))
        heapq.heapify(self._free)
        self.cow_forks = 0

    # -- lifecycle ------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> int:
        """Claim the lowest free block (refcount 1)."""
        if not self._free:
            raise RuntimeError(
                f"block pool exhausted ({self.num_blocks} blocks)")
        bid = heapq.heappop(self._free)
        assert self.refcount[bid] == 0
        self.refcount[bid] = 1
        return bid

    def incref(self, bid: int) -> None:
        if self.refcount[bid] <= 0:
            raise RuntimeError(f"incref on free block {bid}")
        self.refcount[bid] += 1

    def decref(self, bid: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        if self.refcount[bid] <= 0:
            raise RuntimeError(f"decref on free block {bid}")
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            heapq.heappush(self._free, bid)
            return True
        return False

    # -- device data ----------------------------------------------------
    def copy_block(self, src: int, dst: int) -> None:
        """One device dispatch: fork ``src``'s KV into ``dst``."""
        if self.layout == "graph":
            self.tree = _copy_block_tree(self.tree, jnp.int32(src),
                                         jnp.int32(dst))
            return
        self.arena_k, self.arena_v = _copy_block(
            self.arena_k, self.arena_v, jnp.int32(src), jnp.int32(dst))

    def cow(self, bid: int) -> Tuple[int, bool]:
        """Copy-on-write: return a block safe to write through.

        refcount 1 ⇒ exclusive already, returned as-is; otherwise fork into
        a fresh block (caller keeps its reference on ``bid`` to drop)."""
        if self.refcount[bid] == 1:
            return bid, False
        dst = self.alloc()
        self.copy_block(bid, dst)
        self.cow_forks += 1
        return dst, True

    def set_arena(self, ak: jax.Array, av: jax.Array) -> None:
        """Adopt updated arenas returned by a jitted decode/extend step."""
        self.arena_k, self.arena_v = ak, av

    def set_tree(self, outputs: Dict[str, jax.Array]) -> None:
        """Adopt updated per-layer arenas from a dispatch-engine run (graph
        layout): every ``*_arena_*`` leaf present in ``outputs`` replaces
        the pool's copy."""
        self.tree = {name: outputs[name] for name in self.tree}

    # -- memory accounting (dense-vs-paged utilization table) -----------
    @property
    def block_bytes(self) -> int:
        leaves = (list(self.tree.values()) if self.layout == "graph"
                  else [self.arena_k, self.arena_v])
        total = 0
        for a in leaves:
            per = 1
            for d in a.shape[1:]:
                per *= d
            total += per * jnp.dtype(a.dtype).itemsize
        return total

    @property
    def bytes_allocated(self) -> int:
        return self.num_blocks * self.block_bytes

    @property
    def bytes_live(self) -> int:
        return self.num_live * self.block_bytes


@dataclasses.dataclass(frozen=True)
class SlotFork:
    """Checkpoint of one slot's table state before a speculative write.

    Rollback needs only two integers: the committed valid length and how
    many blocks the slot owned.  Speculative writes past ``pos0`` either
    land in blocks allocated AFTER the checkpoint (tracked by position in
    the slot's ``_owned`` list — drop = decref, zero copies) or in blocks
    the slot already owned exclusively, where positions ≥ the committed
    ``pos`` are dead by construction (the causal mask never reads them and
    the next write overwrites them).  COW forks triggered while the fork
    is open replace entries in-place below ``n_owned0`` and are KEPT on
    rollback — a COW copy is content-identical, so the rewound slot is
    unchanged semantically.
    """
    slot: int
    pos0: int
    n_owned0: int


@dataclasses.dataclass
class SwappedChain:
    """Host-resident image of one preempted slot's block chain.

    The swap-out mirrors ``dist/elastic.py``'s cross-mesh restore idiom:
    state leaves the device as plain host numpy carrying no arena
    assumptions, so the restore can land it on ANY free blocks of the
    (possibly differently occupied) arena — the block table re-binds
    logical positions to whatever physical blocks ``swap_in`` allocates.

    Two kinds of entry, keyed by logical block index:

    * ``retained`` — blocks the radix cache (or another slot) still
      references.  The victim's own pool reference is MOVED into this
      record (no decref at swap-out, no incref at swap-in), so shared
      prefixes cost zero bytes of host memory and zero copy dispatches
      in either direction, and their refcounts are preserved exactly.
    * ``host``     — blocks the victim owned exclusively: their KV is
      copied to host and the block freed, which is the memory the
      preemption actually reclaims.  ``swap_in`` re-uploads each into a
      freshly allocated block (one dispatch per block).
    """
    pos: int                                    # committed valid length
    retained: Dict[int, int]                    # logical idx → block id
    host: Dict[int, Tuple[np.ndarray, np.ndarray]]  # logical idx → (k, v)

    @property
    def n_blocks(self) -> int:
        return len(self.retained) + len(self.host)

    @property
    def host_bytes(self) -> int:
        return sum(k.nbytes + v.nbytes for k, v in self.host.values())


class PagedKVCache(StateCache):
    """Slot bookkeeping over a ``BlockPool``: the paged ``StateCache``.

    Each scheduler slot owns a **block table** row (``(width,)`` int32 of
    arena block ids; unpopulated entries point at the trash block) plus a
    ``pos`` valid-length; the slot lifecycle itself (free list, live set,
    ``allocate``/``free``/``advance``/``occupancy``) is the shared
    ``StateCache`` contract, with the paged specifics in the
    ``_on_allocate``/``_on_free`` hooks (owned-block list, decref + table
    reset).  Blocks are claimed lazily as the sequence crosses block
    boundaries (``ensure_writable``) and shared prefixes are adopted by
    reference from the radix cache (``adopt_prefix``), with the boundary
    partial block COW-forked so the new request can append without
    touching shared state.
    """

    state_kind = "paged_kv"

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 table_slack: int = 0, layout: str = "stacked") -> None:
        self.block_size = block_size
        self.max_len = max_len
        # chunked prefill pads the final chunk, so tables cover a little
        # more than max_len; padded writes land in blocks decode reuses
        self.width = _ceildiv(max_len + table_slack, block_size)
        if num_blocks is None:
            # every slot full + two spare chains for the prefix cache
            num_blocks = (num_slots + 2) * self.width
        self.pool = BlockPool(cfg, num_blocks + 1, block_size, layout=layout)
        self.trash = self.pool.alloc()          # block 0: don't-care writes
        assert self.trash == 0
        self.table = np.zeros((num_slots, self.width), np.int32)
        self._owned: Dict[int, List[int]] = {}
        self._init_slots(num_slots)
        self.radix = None                       # set by the owning backend
        self.cow_copies = 0
        from repro.obs.tracer import NULL_TRACER
        self.tracer = NULL_TRACER               # set by the scheduler

    def attach_tracer(self, tracer) -> None:
        """Record paging events on ``tracer`` from now on.  The first
        attach of a live tracer marks which arena layout runs, once, as an
        ``arena_layout`` instant on the ``paging`` track."""
        if tracer is self.tracer:
            return
        self.tracer = tracer
        if tracer.enabled:
            pool = self.pool
            arena = (pool.arena_k if pool.layout == "stacked"
                     else pool.tree["k_arena_0"])
            tracer.instant("arena_layout", track="paging",
                           layout=pool.layout, shape=list(arena.shape))

    # -- StateCache hooks ------------------------------------------------
    def _on_allocate(self, slot: int) -> None:
        self._owned[slot] = []

    def _on_free(self, slot: int) -> None:
        """Drop every block reference the slot holds.  Blocks the radix
        cache (or another slot) still references stay live."""
        for bid in self._owned.pop(slot):
            self.pool.decref(bid)
        self.table[slot, :] = self.trash

    # -- block management -----------------------------------------------
    def _alloc_block(self) -> int:
        """Alloc, evicting LRU prefix-cache chains under pressure."""
        while self.pool.num_free == 0:
            if self.radix is None or not self.radix.evict_one():
                raise RuntimeError(
                    "paged KV pool exhausted and nothing evictable")
        return self.pool.alloc()

    def _fork_block(self, src: int) -> int:
        """COW fork: copy ``src`` into a fresh exclusively-owned block
        (one device dispatch), evicting cache chains if the pool is dry."""
        dst = self._alloc_block()
        self.pool.copy_block(src, dst)
        self.pool.cow_forks += 1
        self.cow_copies += 1
        if self.tracer.enabled:
            self.tracer.instant("cow_fork", track="paging",
                                src=src, dst=dst)
        return dst

    def adopt_prefix(self, slot: int, matched: int, blocks: Sequence[int]
                     ) -> int:
        """Wire a radix-cache hit into ``slot``'s table.

        Full blocks of the matched span are shared by reference; a partial
        boundary block (prefix split mid-block) is COW-forked so this
        slot's prefill can fill its tail privately.  Returns the number of
        device copy dispatches made (0 or 1)."""
        if slot not in self._live:
            raise RuntimeError(f"adopt into unallocated slot {slot}")
        bs = self.block_size
        nfull = matched // bs
        own = self._owned[slot]
        for i in range(nfull):
            bid = int(blocks[i])
            self.pool.incref(bid)
            self.table[slot, i] = bid
            own.append(bid)
        copies = 0
        if matched % bs:
            dst = self._fork_block(int(blocks[nfull]))
            copies = 1
            self.table[slot, nfull] = dst
            own.append(dst)
        self.pos[slot] = matched
        return copies

    def ensure_writable(self, slot: int, start: int, end: int) -> int:
        """Make token positions [start, end) of ``slot`` writable.

        Unpopulated table entries get fresh blocks; entries still shared
        with the radix cache or another slot are COW-forked first (so a
        write can never diverge someone else's prefix).  Returns the number
        of device copy dispatches made."""
        if slot not in self._live:
            raise RuntimeError(f"write to unallocated slot {slot}")
        bs = self.block_size
        if end > self.width * bs:
            raise RuntimeError(
                f"paged KV overflow: need {end} tokens, table covers "
                f"{self.width * bs}")
        copies = 0
        own = self._owned[slot]
        for i in range(start // bs, _ceildiv(end, bs)):
            bid = int(self.table[slot, i])
            if bid == self.trash:
                nb = self._alloc_block()
                self.table[slot, i] = nb
                own.append(nb)
            elif self.pool.refcount[bid] > 1:
                nb = self._fork_block(bid)
                copies += 1
                self.pool.decref(bid)
                own[own.index(bid)] = nb
                self.table[slot, i] = nb
        return copies

    # -- speculative forks (COW-backed draft/verify/rollback) -----------
    def fork_slot(self, slot: int) -> SlotFork:
        """Checkpoint ``slot`` before speculative writes land past its
        committed position.  O(1): records the valid length and the owned-
        block count — no table copy, no KV copy."""
        if slot not in self._live:
            raise RuntimeError(f"fork of unallocated slot {slot}")
        return SlotFork(slot=slot, pos0=int(self.pos[slot]),
                        n_owned0=len(self._owned[slot]))

    def commit_fork(self, slot: int, fork: SlotFork, new_pos: int) -> None:
        """Adopt the accepted span: valid length becomes ``new_pos`` and
        blocks allocated for the fork that now lie entirely past it are
        returned.  Zero KV copies — accepted tokens were written in place
        by the verify dispatch."""
        if fork.slot != slot:
            raise RuntimeError(
                f"fork belongs to slot {fork.slot}, not {slot}")
        if not fork.pos0 <= new_pos:
            raise RuntimeError(
                f"commit_fork rewinds past checkpoint ({new_pos} < "
                f"{fork.pos0})")
        self.pos[slot] = new_pos
        self._trim_fork_blocks(slot, fork, new_pos)

    def drop_fork(self, slot: int, fork: SlotFork) -> None:
        """Reject the whole speculative span: rewind to the checkpoint and
        release every block the fork allocated.  Zero KV copies — the
        rejected writes sit past ``pos0`` where nothing can read them."""
        self.commit_fork(slot, fork, fork.pos0)

    def _trim_fork_blocks(self, slot: int, fork: SlotFork,
                          keep_upto: int) -> None:
        """Release fork-allocated blocks past logical position
        ``keep_upto``.  Only blocks appended since the checkpoint are
        candidates; COW replacements below ``n_owned0`` stay (they carry
        the committed prefix)."""
        keep_blocks = _ceildiv(keep_upto, self.block_size)
        own = self._owned[slot]
        kept = own[:fork.n_owned0]
        for bid in own[fork.n_owned0:]:
            idxs = np.flatnonzero(self.table[slot] == bid)
            if idxs.size and int(idxs[0]) >= keep_blocks:
                self.pool.decref(bid)
                self.table[slot, idxs] = self.trash
            else:
                kept.append(bid)
        self._owned[slot] = kept

    def chain(self, slot: int, tokens: int) -> List[int]:
        """Block ids covering the first ``tokens`` positions of ``slot``."""
        return [int(self.table[slot, i])
                for i in range(_ceildiv(tokens, self.block_size))]

    # -- preemption: swap block chains to host memory and back -----------
    def swap_out(self, slot: int) -> SwappedChain:
        """Preempt ``slot``: move its block chain off the arena.

        Shared blocks (refcount > 1 — radix-cache chains, other slots)
        keep their device residency and their refcount: the slot's own
        reference transfers into the returned :class:`SwappedChain`
        instead of dropping.  Exclusively-owned blocks are copied to host
        and freed — this is the arena capacity the preemptor reclaims.
        The slot itself is released (table row reset, ``pos`` zeroed) so
        a higher-priority admission can take it immediately.

        Returns the chain record ``swap_in`` restores from; the round
        trip is byte-exact (tested), so a restored request's greedy
        stream is identical to an unpreempted run.
        """
        if slot not in self._live:
            raise RuntimeError(f"swap_out of unallocated slot {slot}")
        if self.pool.layout != "stacked":
            raise NotImplementedError(
                "swap_out supports the stacked arena layout (model/"
                "ondevice backends); graph/dist arenas cannot swap yet")
        pos = int(self.pos[slot])
        n = _ceildiv(pos, self.block_size)
        chain_ids = {int(self.table[slot, i]) for i in range(n)}
        retained: Dict[int, int] = {}
        host: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        ak = av = None
        for i in range(n):
            bid = int(self.table[slot, i])
            if self.pool.refcount[bid] > 1:
                # reference MOVES into the record: no decref here, no
                # incref on restore — refcounts are preserved exactly
                retained[i] = bid
            else:
                if ak is None:      # one host fetch of each arena, lazily
                    ak = np.asarray(self.pool.arena_k)
                    av = np.asarray(self.pool.arena_v)
                host[i] = (ak[bid].copy(), av[bid].copy())
                self.pool.decref(bid)
        # blocks owned past the chain (padded-chunk / spec-slack writes
        # beyond pos) carry no live tokens: plain release
        for bid in self._owned.pop(slot):
            if bid not in chain_ids:
                self.pool.decref(bid)
        self._live.discard(slot)
        self._free.append(slot)
        self.table[slot, :] = self.trash
        self.pos[slot] = 0
        if self.tracer.enabled:
            self.tracer.instant("swap_out", track="paging", slot=slot,
                                blocks=len(host), retained=len(retained))
        return SwappedChain(pos=pos, retained=retained, host=host)

    def swap_in(self, chain: SwappedChain, slot: Optional[int] = None
                ) -> Tuple[int, int]:
        """Restore a swapped chain into a (possibly different) free slot.

        Retained entries re-bind by table assignment alone — their
        reference transfers back from the record, zero dispatches.  Host
        entries upload into freshly allocated blocks, one dispatch each
        (``_load_block``).  Returns ``(slot, upload_dispatches)``; the
        record is consumed and must not be reused.
        """
        slot = self.allocate(slot)
        own = self._owned[slot]
        uploads = 0
        for i in sorted(set(chain.retained) | set(chain.host)):
            if i in chain.retained:
                bid = chain.retained[i]
            else:
                bid = self._alloc_block()
                kb, vb = chain.host[i]
                ak, av = _load_block(self.pool.arena_k, self.pool.arena_v,
                                     jnp.asarray(kb), jnp.asarray(vb),
                                     jnp.int32(bid))
                self.pool.set_arena(ak, av)
                uploads += 1
            self.table[slot, i] = bid
            own.append(bid)
        self.pos[slot] = chain.pos
        if self.tracer.enabled:
            self.tracer.instant("swap_in", track="paging", slot=slot,
                                uploads=uploads,
                                retained=len(chain.retained))
        return slot, uploads

    def drop_swap(self, chain: SwappedChain) -> None:
        """Abandon a swapped chain without restoring (request cancelled):
        release the references it carried on retained blocks."""
        for bid in chain.retained.values():
            self.pool.decref(bid)
        chain.retained = {}
        chain.host = {}

    # -- debug / test readout -------------------------------------------
    def gather(self, slot: int, length: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Host copy of one slot's logical KV (layers, length, KV, hd)."""
        n = int(self.pos[slot]) if length is None else length
        bs = self.block_size
        ids = self.table[slot, :_ceildiv(n, bs)]
        if self.pool.layout == "graph":
            def layer(c, i):
                arena = np.asarray(self.pool.tree[f"{c}_arena_{i}"])
                return np.concatenate([arena[b] for b in ids], axis=0)[:n]
            k = np.stack([layer("k", i)
                          for i in range(self.pool.num_layers)])
            v = np.stack([layer("v", i)
                          for i in range(self.pool.num_layers)])
            return {"k": k, "v": v}

        def heads(arena):
            a = np.asarray(arena)
            x = np.concatenate([a[b] for b in ids], axis=1)[:, :n]
            return x.reshape(x.shape[:2] + (self.pool.kv_heads, -1))
        return {"k": heads(self.pool.arena_k), "v": heads(self.pool.arena_v)}

    # -- memory accounting ----------------------------------------------
    @property
    def bytes_allocated(self) -> int:
        return self.pool.bytes_allocated

    @property
    def bytes_live(self) -> int:
        return self.pool.bytes_live
