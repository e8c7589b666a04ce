"""``dist`` backend: pipeline-parallel serving over a ``("stage",)`` mesh.

Layers are split into contiguous chunks across the mesh's ``stage`` axis
(the transformer's stacked leading layer axis maps directly onto
``PartitionSpec("stage")``, as does the per-layer KV cache), and each
prefill/decode step runs the ``repro.dist.pipeline`` fill/drain schedule
inside ``shard_map``: every tick one stage applies its layer chunk via the
SAME ``transformer.prefill_block`` / ``decode_block`` the single-device
path scans, then activations rotate stage→stage+1 via ``lax.ppermute``.

Serving decodes one token at a time, so each step is a single-microbatch
pipeline — ``n_stages`` ticks, bubble fraction (S−1)/S — which is the
worst-case schedule the paper's dispatch-amortization argument starts
from; ``pipeline_stats()`` reports it next to the uniform
``dispatch_stats()`` row.  The whole step is still ONE jitted executable
(1 dispatch/token), so multi-device serving keeps the §9.2 dispatch
regime.

Paged serving: the dense per-slot-loop fallback could not batch because
the pipeline's cache write was compiled around ONE shared scalar position
— but the paged layout's cache write is a per-row block-table scatter, so
per-slot positions batch fine.  ``alloc_slots_paged`` therefore shards
the block arena's LAYER axis over the mesh (each stage owns its
layer-slice of every block; admission/eviction/refcounts stay host-side
and global), and one paged decode cycle advances EVERY active slot
through a single pipelined executable — multi-device serving joins the
continuous-batching amortization regime.

The mesh is built over the host's devices (force a fleet with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
jax import); on one device it degenerates to a 1-stage pipeline running
the identical code path.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import RunStats
from repro.dist.pipeline import PipelineStats, ring_perm
from repro.models import transformer
from repro.models.transformer import CHUNKED_ATTENTION_MIN_SEQ
from repro.serving.backends.base import (BackendCapabilities, BatchState,
                                         ExecutionBackend, State, StepOutput,
                                         device_snapshot, register_backend)
from repro.serving.paging.attention import gather_blocks, write_kv


def _auto_stages(num_layers: int, n_devices: int) -> int:
    """Largest stage count ≤ n_devices that divides the layer stack."""
    for s in range(min(num_layers, n_devices), 0, -1):
        if num_layers % s == 0:
            return s
    return 1


@register_backend("dist")
class DistBackend(ExecutionBackend):
    """Pipeline-parallel prefill/decode for the transformer families."""

    def __init__(self, model, params, *, mode: str = "dist", batch: int = 1,
                 max_len: int = 128, stages: int = 0) -> None:
        super().__init__()
        cfg = model.cfg
        if cfg.family not in ("dense",) or cfg.moe is not None:
            raise ValueError(
                f"dist backend supports dense transformers only, got "
                f"family={cfg.family!r} (moe={cfg.moe is not None})")
        if not cfg.uniform_layers:
            # the stage scans apply one static window and RoPE base to
            # every layer; per-layer settings would have to be sharded
            # over the stages beside the blocks
            raise ValueError(
                f"dist backend needs every layer alike; {cfg.name} mixes "
                f"attention kinds {sorted(set(cfg.layer_types))} — serve it "
                "with the model backend")
        devs = jax.devices()
        n_stages = stages or _auto_stages(cfg.num_layers, len(devs))
        if cfg.num_layers % n_stages:
            raise ValueError(f"{cfg.num_layers} layers do not divide over "
                             f"{n_stages} stages")
        if n_stages > len(devs):
            raise RuntimeError(
                f"{n_stages} stages need {n_stages} devices, have "
                f"{len(devs)} — set XLA_FLAGS="
                "--xla_force_host_platform_device_count before jax init")
        self.model = model
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.stages = n_stages
        self.mesh = jax.make_mesh((n_stages,), ("stage",),
                                  devices=devs[:n_stages],
                                  axis_types=(AxisType.Auto,))

        # layer-stacked leaves → P("stage") on the stack axis; the rest
        # (embed / final_norm / lm_head) replicate across stages
        stage_sh = NamedSharding(self.mesh, P("stage"))
        repl = NamedSharding(self.mesh, P())
        self.params = {
            k: (jax.tree.map(lambda a: jax.device_put(a, stage_sh), v)
                if k == "blocks" else jax.device_put(v, repl))
            for k, v in params.items()
        }

        self._jit_prefill = jax.jit(self._sharded_prefill)
        self._jit_decode = jax.jit(self._sharded_decode)
        self._jit_decode_paged = jax.jit(self._sharded_decode_paged,
                                         donate_argnums=(1, 2))
        self._jit_extend_paged = jax.jit(self._sharded_extend_paged,
                                         donate_argnums=(1, 2))
        # decode_batch=False: the DENSE pipeline schedule is compiled
        # around a SINGLE shared scalar position (every stage's
        # dynamic_update_slice indexes the same tick), so per-slot
        # positions cannot batch there — the per-slot-loop fallback runs.
        # paged_kv=True: the paged cache write is a per-row block scatter,
        # which batches fine, so kv_layout="paged" IS the batched
        # multi-device serving path.
        self.capabilities = BackendCapabilities(
            name=mode, dispatches_per_token=1, device_argmax=True,
            decode_batch=False, paged_kv=True)

    # ------------------------------------------------------------------
    def pipeline_stats(self) -> PipelineStats:
        """Schedule accounting: serving is single-microbatch per step."""
        return PipelineStats(self.stages, self.cfg.num_layers // self.stages,
                             n_micro=1)

    # ------------------------------------------------------------------
    def _pipeline_blocks(self, block_step):
        """Build the fill/drain shard_map body for one pipeline pass.

        ``block_step(blocks_local, h, carry_local) → (h', carry_local')``
        applies this stage's layer chunk; ``carry_local`` is per-stage
        state (KV caches) that stays resident — only activations rotate.
        """
        S = self.stages
        perm = ring_perm(S)

        def body(blocks_local, x, carry_local):
            stage = lax.axis_index("stage")
            state = x                       # replicated feed; stage 0's view
            for t in range(S):              # 1 microbatch: S fill/drain ticks
                h, new_carry = block_step(blocks_local, state, carry_local)
                keep = stage == t           # tick t is stage t's useful work
                carry_local = jax.tree.map(
                    lambda new, old: jnp.where(keep, new, old),
                    new_carry, carry_local)
                if S > 1:
                    state = lax.ppermute(h, "stage", perm)
                else:
                    state = h
            # after the last rotation stage 0 holds the final activations
            return lax.psum(jnp.where(stage == 0, state, 0), "stage"), \
                carry_local

        return body

    # ------------------------------------------------------------------
    def _sharded_prefill(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens]
        b, s, _ = x.shape
        positions = jnp.arange(s)
        chunked = s >= CHUNKED_ATTENTION_MIN_SEQ
        h = cfg.resolved_head_dim
        kv_shape = (cfg.num_layers // self.stages, b, self.max_len,
                    cfg.num_kv_heads, h)

        def block_step(blocks_local, xc, carry):
            def one(c, p):
                return transformer.prefill_block(p, cfg, c, positions,
                                                 self.max_len,
                                                 chunked=chunked)
            return lax.scan(one, xc, blocks_local)

        body = self._pipeline_blocks(block_step)

        def run(blocks, x):
            kv0 = (jnp.zeros(kv_shape, jnp.dtype(cfg.dtype)),
                   jnp.zeros(kv_shape, jnp.dtype(cfg.dtype)))
            fn = jax.shard_map(lambda bl, xx: body(bl, xx, kv0),
                               mesh=self.mesh,
                               in_specs=(jax.tree.map(lambda _: P("stage"),
                                                      blocks), P()),
                               out_specs=(P(), (P("stage"), P("stage"))),
                               check_vma=False)
            return fn(blocks, x)

        x, (kcache, vcache) = run(params["blocks"], x)
        logits = transformer.unembed(params, cfg, x[:, -1:, :])
        cache = {"k": kcache, "v": vcache, "pos": jnp.int32(s)}
        return cache, logits, jnp.argmax(logits, -1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def _sharded_decode(self, params, cache, tokens):
        cfg = self.cfg
        x = params["embed"][tokens]
        b = x.shape[0]
        pos = cache["pos"]
        positions = jnp.full((b, 1), pos, jnp.int32)

        def block_step(blocks_local, xc, carry):
            kc, vc = carry

            def one(c, scan_in):
                p, kci, vci = scan_in
                return transformer.decode_block(p, cfg, c, kci, vci, pos,
                                                positions)

            xc, (kc, vc) = lax.scan(one, xc, (blocks_local, kc, vc))
            return xc, (kc, vc)

        body = self._pipeline_blocks(block_step)

        def run(blocks, x, kc, vc):
            fn = jax.shard_map(lambda bl, xx, k, v: body(bl, xx, (k, v)),
                               mesh=self.mesh,
                               in_specs=(jax.tree.map(lambda _: P("stage"),
                                                      blocks), P(),
                                         P("stage"), P("stage")),
                               out_specs=(P(), (P("stage"), P("stage"))),
                               check_vma=False)
            return fn(blocks, x, kc, vc)

        x, (kcache, vcache) = run(params["blocks"], x, cache["k"], cache["v"])
        logits = transformer.unembed(params, cfg, x)
        cache = {"k": kcache, "v": vcache, "pos": pos + 1}
        return cache, logits, jnp.argmax(logits, -1).astype(jnp.int32)

    # -- paged KV: per-stage layer-slice arenas under shard_map ----------
    def _sharded_decode_paged(self, params, ak, av, table, pos, tokens):
        """One paged decode cycle for EVERY active slot, pipelined.

        Each stage gathers its layer-slice of the arena through the
        (replicated) block table, runs its layer chunk at per-row
        positions, and scatters its layers' new K/V rows back into its own
        slice — the per-row scatter is what lets per-slot positions batch
        where the dense pipeline could not.  Reads and writes go through
        the paged attention's own ``gather_blocks``/``write_kv``, so the
        stage slices keep the arena layout ``arena_shape`` chose.
        """
        cfg = self.cfg
        x = params["embed"][tokens]             # (S_slots, 1, d)
        nslots = tokens.shape[0]
        lc = cfg.num_layers // self.stages
        hd = cfg.resolved_head_dim

        def inner(blocks_local, xx, ak_l, av_l, tbl, ps):
            kd = gather_blocks(ak_l, tbl, cfg.num_kv_heads)
            vd = gather_blocks(av_l, tbl, cfg.num_kv_heads)

            def block_step(bl, xc, carry):
                def one(c, scan_in):
                    p, kc, vc = scan_in
                    return transformer.decode_core_rows(
                        p, cfg, c, kc, vc, ps, emit_cache=False)
                return lax.scan(one, xc, (bl, kd, vd))

            body = self._pipeline_blocks(block_step)
            init = (jnp.zeros((lc, nslots, cfg.num_kv_heads, hd), xx.dtype),
                    jnp.zeros((lc, nslots, cfg.num_kv_heads, hd), xx.dtype))
            xx, (knew, vnew) = body(blocks_local, xx, init)
            # the write lands in each slot's current block (host made it
            # exclusively ours)
            return xx, write_kv(ak_l, av_l, tbl, ps, knew, vnew)

        def run(blocks, x, ak, av, table, pos):
            fn = jax.shard_map(inner, mesh=self.mesh,
                               in_specs=(jax.tree.map(lambda _: P("stage"),
                                                      blocks), P(),
                                         P(None, "stage"), P(None, "stage"),
                                         P(), P()),
                               out_specs=(P(), (P(None, "stage"),
                                                P(None, "stage"))),
                               check_vma=False)
            return fn(blocks, x, ak, av, table, pos)

        x, (ak, av) = run(params["blocks"], x, ak, av, table, pos)
        logits = transformer.unembed(params, cfg, x)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return ak, av, logits, nxt

    def _sharded_extend_paged(self, params, ak, av, table_row, pos0, valid,
                              tokens):
        """One chunked-prefill step for one slot through the pipeline."""
        cfg = self.cfg
        x = params["embed"][tokens]             # (1, C, d)
        c = tokens.shape[1]
        lc = cfg.num_layers // self.stages
        hd = cfg.resolved_head_dim

        def inner(blocks_local, xx, ak_l, av_l, tbl, p0):
            kd = gather_blocks(ak_l, tbl, cfg.num_kv_heads)
            vd = gather_blocks(av_l, tbl, cfg.num_kv_heads)
            positions = p0 + jnp.arange(c)

            def block_step(bl, xc, carry):
                def one(cr, scan_in):
                    p, kc, vc = scan_in
                    return transformer.extend_block(p, cfg, cr, kc, vc, p0,
                                                    positions)
                return lax.scan(one, xc, (bl, kd, vd))

            body = self._pipeline_blocks(block_step)
            init = (jnp.zeros((lc, 1, c, cfg.num_kv_heads, hd), xx.dtype),
                    jnp.zeros((lc, 1, c, cfg.num_kv_heads, hd), xx.dtype))
            xx, (kch, vch) = body(blocks_local, xx, init)
            # padded positions land in writable blocks and are overwritten
            # before anything attends them
            return xx, write_kv(ak_l, av_l, tbl, positions, kch[:, 0],
                                vch[:, 0])

        def run(blocks, x, ak, av, table_row, pos0):
            fn = jax.shard_map(inner, mesh=self.mesh,
                               in_specs=(jax.tree.map(lambda _: P("stage"),
                                                      blocks), P(),
                                         P(None, "stage"), P(None, "stage"),
                                         P(), P()),
                               out_specs=(P(), (P(None, "stage"),
                                                P(None, "stage"))),
                               check_vma=False)
            return fn(blocks, x, ak, av, table_row, pos0)

        x, (ak, av) = run(params["blocks"], x, ak, av, table_row, pos0)
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = transformer.unembed(params, cfg, x_last)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return ak, av, logits, nxt

    def alloc_slots_paged(self, num_slots: int, *, block_size: int = 16,
                          prefill_chunk: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          prefix_cache: bool = True,
                          spec_slack: int = 0) -> BatchState:
        bstate = self._make_paged_state(num_slots, block_size=block_size,
                                        prefill_chunk=prefill_chunk,
                                        num_blocks=num_blocks,
                                        prefix_cache=prefix_cache,
                                        spec_slack=spec_slack)
        # every stage owns its layer-slice of EVERY block: shard the layer
        # axis over the mesh; block ids / refcounts / the radix tree stay
        # host-side and global, so admission and eviction are driven from
        # the scheduler exactly as on one device
        pool = bstate["paged"].pool
        stage_sh = NamedSharding(self.mesh, P(None, "stage"))
        pool.set_arena(jax.device_put(pool.arena_k, stage_sh),
                       jax.device_put(pool.arena_v, stage_sh))
        return bstate

    def prefill_paged_chunk(self, bstate: BatchState, slot: int
                            ) -> Optional[StepOutput]:
        return self._prefill_chunk_with(
            bstate, slot, self._extend_with_jit(self._jit_extend_paged))

    def decode_batch(self, bstate: BatchState, tokens, slots: Sequence[int]
                     ) -> Tuple[BatchState, StepOutput]:
        """Paged: ONE pipelined dispatch advances every slot (replacing the
        dense per-slot-loop fallback, which the base class still provides
        for ``kv_layout='dense'``)."""
        if "paged" not in bstate:
            return super().decode_batch(bstate, tokens, slots)
        pg = bstate["paged"]
        with self._dispatch_span("decode_batch"):
            copies = 0
            for s in slots:
                copies += pg.ensure_writable(s, int(pg.pos[s]),
                                             int(pg.pos[s]) + 1)
            t0 = time.perf_counter()
            ak, av, logits, nxt = self._jit_decode_paged(
                self.params, pg.pool.arena_k, pg.pool.arena_v,
                device_snapshot(pg.table), device_snapshot(pg.pos),
                jnp.asarray(tokens, jnp.int32))
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=1 + copies,
                                  shape_ops=0, sync_mode="none",
                                  enqueue_s=enq), op="decode_batch")
            pg.pool.set_arena(ak, av)
            pg.advance(slots)
        return bstate, StepOutput(logits, nxt)

    # ------------------------------------------------------------------
    def _run(self, fn, *args, op: str = "dispatch"
             ) -> Tuple[object, StepOutput]:
        with self._dispatch_span(op):
            t0 = time.perf_counter()
            cache, logits, nxt = fn(*args)
            enq = time.perf_counter() - t0
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
