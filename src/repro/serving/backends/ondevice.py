"""Beyond-paper backend: the ENTIRE generation loop in one dispatch.

A ``lax.scan`` over decode steps — including sampling — runs on device, so
the per-token GPU→CPU argmax readback the paper measures at ~11 ms/token
on WebGPU (§5.1) disappears entirely.  Sampling stays inside the loop:
``repro.serving.sampler.sample`` is traceable, so greedy, temperature and
top-k all lower into the single executable.

The backend still implements ``decode_step`` (one jitted step) so that
streaming callbacks, stop conditions, and the slot scheduler — which need
per-token host control — keep working; the session layer picks the
single-dispatch path only when nothing needs to observe tokens mid-loop.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.serving.backends.base import (BackendCapabilities, State,
                                         StepOutput, register_backend)
from repro.serving.backends.model import ModelBackend
from repro.serving.sampler import SamplerConfig, sample


@register_backend("ondevice")
class OnDeviceBackend(ModelBackend):
    """Model backend + a whole-loop single-dispatch generation fast path."""

    def __init__(self, model, params, *, mode: str = "ondevice",
                 batch: int = 1, max_len: int = 128) -> None:
        super().__init__(model, params, mode=mode, batch=batch,
                         max_len=max_len)

        def gen(params, cache, first_tok, keys, n_new: int,
                sampler: SamplerConfig):
            def body(carry, key):
                c, tok = carry
                c, logits = model.decode_step(params, c, tok)
                nxt = sample(logits, sampler, key)
                return (c, nxt), nxt[:, 0]

            (_, _), toks = jax.lax.scan(body, (cache, first_tok), keys)
            return toks.T  # (B, n_new)

        self._ondevice = jax.jit(gen, static_argnums=(4, 5))
        self.capabilities = BackendCapabilities(
            name=mode,
            dispatches_per_token=0,  # amortized: 1 dispatch / whole sequence
            device_argmax=True,
            on_device_loop=True,
            decode_batch=self.capabilities.decode_batch,  # inherited rows path
            paged_kv=self.capabilities.paged_kv,          # inherited paged path
            speculative=self.capabilities.speculative,    # inherited verify
            preemption=self.capabilities.preemption,      # inherited swap
        )

    def generate_ondevice(self, state: State, first_tok, n_new: int,
                          sampler: SamplerConfig = SamplerConfig(),
                          rng=None) -> jax.Array:
        """(B, 1) first token + state → (B, n_new) continuation tokens."""
        import time

        from repro.core.engine import RunStats

        rng = jax.random.PRNGKey(0) if rng is None else rng
        keys = jax.random.split(rng, n_new)
        with self._dispatch_span("ondevice_loop"):
            t0 = time.perf_counter()
            toks = self._ondevice(self.params, state["cache"],
                                  jnp.asarray(first_tok, jnp.int32), keys,
                                  n_new, sampler)
            enq = time.perf_counter() - t0
            self._record(RunStats(wall_s=enq, dispatches=1, shape_ops=0,
                                  sync_mode="none", enqueue_s=enq),
                         op="ondevice_loop")
        return toks
