"""Production serving session over the ``ExecutionBackend`` protocol.

``InferenceSession`` turns any registered backend into a request server:

* pluggable sampling (``SamplerConfig``: greedy / temperature / top-k),
* streaming token callbacks (called in emission order),
* stop conditions (stop-token set / max-new-tokens),
* the paper's App.-H readback variants (``token``: one int32 per step;
  ``logits``: full vocab row read back, host-side argmax),
* the single-dispatch on-device loop when the backend supports it and
  nothing needs to observe tokens mid-generation.

``Scheduler`` queues many requests onto a fixed number of slots and
interleaves their decode steps round-robin — each slot owns its own
backend state (per-request KV cache allocated by the backend via
``kvcache``), which is the seam continuous batching plugs into later.

The step loop is exposed piecewise (``start`` / ``step`` / ``finish``) so
the scheduler — and future async drivers — can interleave requests; plain
``run`` composes them for the single-request case.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.stats import Summary, summarize
from repro.obs.metrics import MetricsRegistry, percentile
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.serving.backends.base import ExecutionBackend, StepOutput
from repro.serving.sampler import SamplerConfig, sample
from repro.serving.spec import (Drafter, NgramDrafter, SpeculativeConfig,
                                greedy_accept)

_req_counter = itertools.count()


@dataclasses.dataclass
class ServeRequest:
    """One generation request.

    ``prompt`` is (plen,) or (B, plen) int tokens; B must match the
    backend's compiled batch.  ``stream`` is called as ``stream(i, toks)``
    with ``toks`` the (B,) int32 tokens emitted at step ``i`` — in order,
    before the next step runs.  ``readback`` selects the App.-H regime.

    ``priority`` orders admission under load (higher admits first; FIFO
    within a priority) and, when the scheduler runs with
    ``preemption != "off"``, lets a strictly-higher-priority arrival evict
    a running lower-priority slot.  ``slo_ttft_ms`` is the request's
    time-to-first-token service objective: attainment and goodput land in
    ``SchedulerStats`` and the ``serving.slo.*`` metrics — it never
    changes scheduling by itself (priority does).
    """
    prompt: np.ndarray
    max_new_tokens: int = 32
    sampler: SamplerConfig = SamplerConfig()
    stop_tokens: Tuple[int, ...] = ()
    seed: int = 0
    request_id: str = ""
    stream: Optional[Callable[[int, np.ndarray], None]] = None
    readback: str = "token"          # "token" | "logits"
    priority: int = 0                # higher = more urgent (scheduler only)
    slo_ttft_ms: Optional[float] = None   # TTFT objective for goodput

    def __post_init__(self) -> None:
        if not self.request_id:
            self.request_id = f"req-{next(_req_counter)}"
        if self.readback not in ("token", "logits"):
            raise ValueError(f"unknown readback {self.readback!r}")
        if self.sampler.kind not in ("greedy", "temperature", "topk"):
            raise ValueError(f"unknown sampler kind {self.sampler.kind!r}")


@dataclasses.dataclass
class ServeResult:
    """Completed request: tokens + timing + uniform dispatch accounting."""
    request_id: str
    tokens: np.ndarray               # (B, n_new)
    n_new: int
    ttft_s: float
    total_s: float
    finish_reason: str               # "stop" | "length"
    backend: str
    dispatches_per_token: int
    queue_wait_s: float = 0.0        # submit → prefill start (scheduler only)

    @property
    def tok_per_s(self) -> float:
        return self.n_new / max(self.total_s, 1e-12)


@dataclasses.dataclass
class BenchmarkReport:
    """mean ± std, CI95, CV over n_runs — the paper's Table 2 row format."""
    mode: str
    arch: str
    tok_per_s: Summary
    ttft_ms: Summary
    dispatches_per_token: int
    all_tps: List[float]
    all_ttft_ms: List[float]
    dispatch_stats: Optional[Dict[str, Any]] = None

    def row(self) -> Dict[str, Any]:
        r = {
            "mode": self.mode, "arch": self.arch,
            "tok_s": round(self.tok_per_s.mean, 2),
            "ci95": [round(x, 2) for x in self.tok_per_s.ci95],
            "cv_pct": round(100 * self.tok_per_s.cv, 1),
            "ttft_ms": round(self.ttft_ms.mean, 2),
            "dispatches_per_token": self.dispatches_per_token,
        }
        if self.dispatch_stats is not None:
            r["dispatch_stats"] = self.dispatch_stats
        return r


@dataclasses.dataclass
class _Active:
    """In-flight request state (one slot's worth of work)."""
    req: ServeRequest
    state: Dict[str, Any]
    rng: jax.Array
    t0: float
    ttft_s: float = 0.0
    queue_wait_s: float = 0.0
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    stopped: Optional[np.ndarray] = None     # (B,) bool: row hit a stop token
    last_tok: Optional[np.ndarray] = None    # (B, 1) int32
    resuming: bool = False    # recompute-preempted: next prefill completion
                              # rebuilds KV only — its logits are NOT a new
                              # first token (that token was already emitted)

    @property
    def done(self) -> bool:
        return (len(self.tokens) >= self.req.max_new_tokens
                or (self.stopped is not None and bool(self.stopped.all())))


class InferenceSession:
    """Serve requests through one compiled ``ExecutionBackend``."""

    def __init__(self, backend: ExecutionBackend) -> None:
        self.backend = backend

    # ------------------------------------------------------------------
    def _select_token(self, out: StepOutput, req: ServeRequest,
                      key: jax.Array) -> np.ndarray:
        """StepOutput → host (B, 1) int32, honoring sampler + readback."""
        greedy = req.sampler.kind == "greedy"
        if req.readback == "logits":
            # App. H full-readback baseline: whole vocab row crosses the bus
            logits = np.asarray(out.logits)
            if greedy:
                return np.argmax(logits, -1).astype(np.int32).reshape(-1, 1)
            tok = sample(jax.numpy.asarray(logits), req.sampler, key)
            return np.asarray(tok, np.int32).reshape(-1, 1)
        if greedy and out.next_token is not None:
            # device-side argmax: one int32 per row crosses the bus
            return np.asarray(out.next_token, np.int32).reshape(-1, 1)
        tok = sample(out.logits, req.sampler, key)
        return np.asarray(tok, np.int32).reshape(-1, 1)

    def _emit(self, a: _Active, tok: np.ndarray) -> None:
        i = len(a.tokens)
        a.tokens.append(tok)
        a.last_tok = tok
        hit = np.isin(tok[:, 0], np.asarray(a.req.stop_tokens, np.int32)) \
            if a.req.stop_tokens else np.zeros(tok.shape[0], bool)
        a.stopped = hit if a.stopped is None else (a.stopped | hit)
        if a.req.stream is not None:
            a.req.stream(i, tok[:, 0].copy())

    # -- piecewise execution (the scheduler drives these) ----------------
    def begin(self, req: ServeRequest) -> _Active:
        """Open a request WITHOUT running prefill — stamps t0 only.  The
        chunked-prefill scheduler spreads the prompt over many cycles, so
        TTFT starts at admission, not at the (much later) final chunk."""
        return _Active(req=req, state=None, rng=jax.random.PRNGKey(req.seed),
                       t0=time.perf_counter())

    def first(self, a: _Active, out: StepOutput) -> None:
        """Consume the prefill output: sample + emit the first token."""
        a.rng, key = jax.random.split(a.rng)
        tok = self._select_token(out, a.req, key)
        a.ttft_s = time.perf_counter() - a.t0
        self._emit(a, tok)

    def start(self, req: ServeRequest) -> _Active:
        """Prefill + first token."""
        prompt = np.atleast_2d(np.asarray(req.prompt, np.int32))
        a = self.begin(req)
        a.state, out = self.backend.prefill(prompt)
        self.first(a, out)
        return a

    def step(self, a: _Active) -> bool:
        """One decode step; returns True when the request is finished."""
        if a.done:
            return True
        a.state, out = self.backend.decode_step(a.state, a.last_tok)
        return self.step_row(a, out)

    def step_row(self, a: _Active, out: StepOutput) -> bool:
        """Consume one ALREADY-COMPUTED decode output for this request —
        the continuous scheduler computes a whole cycle's outputs in one
        batched dispatch and feeds each request its own row here.  Sampler
        RNG, streaming, and stop handling are identical to ``step``."""
        a.rng, key = jax.random.split(a.rng)
        self._emit(a, self._select_token(out, a.req, key))
        return a.done

    def finish(self, a: _Active) -> ServeResult:
        toks = np.concatenate(a.tokens, axis=1)
        stopped = a.stopped is not None and bool(a.stopped.all())
        caps = self.backend.capabilities
        return ServeResult(
            request_id=a.req.request_id,
            tokens=toks,
            n_new=toks.shape[1],
            ttft_s=a.ttft_s,
            total_s=time.perf_counter() - a.t0,
            finish_reason="stop" if stopped else "length",
            backend=caps.name,
            dispatches_per_token=caps.dispatches_per_token,
            queue_wait_s=a.queue_wait_s,
        )

    # ------------------------------------------------------------------
    def run(self, req: ServeRequest) -> ServeResult:
        """Serve one request to completion."""
        caps = self.backend.capabilities
        fast = (caps.on_device_loop and req.stream is None
                and not req.stop_tokens and req.readback == "token"
                and req.max_new_tokens > 1)
        a = self.start(req)
        if fast and not a.done:
            n_rest = req.max_new_tokens - 1
            rest = np.asarray(self.backend.generate_ondevice(
                a.state, a.last_tok, n_rest, req.sampler,
                jax.random.split(a.rng)[1]), np.int32)  # ONE readback
            for i in range(n_rest):
                a.tokens.append(rest[:, i:i + 1])
            return self.finish(a)
        while not self.step(a):
            pass
        return self.finish(a)

    # ------------------------------------------------------------------
    def benchmark(self, prompt: np.ndarray, n_new: int, *, n_runs: int = 10,
                  warmup: int = 3, sampler: SamplerConfig = SamplerConfig(),
                  readback: str = "token") -> BenchmarkReport:
        """The paper's protocol: warmup to steady state, then timed runs."""
        def make_req():
            return ServeRequest(prompt=prompt, max_new_tokens=n_new,
                                sampler=sampler, readback=readback)

        for _ in range(warmup):
            self.run(make_req())
        self.backend.reset_stats()
        tps, ttfts = [], []
        for _ in range(n_runs):
            r = self.run(make_req())
            tps.append(r.tok_per_s)
            ttfts.append(1e3 * r.ttft_s)
        caps = self.backend.capabilities
        cfg = getattr(self.backend, "cfg", None)
        return BenchmarkReport(caps.name, cfg.name if cfg else "?",
                               summarize(tps), summarize(ttfts),
                               caps.dispatches_per_token, tps, ttfts,
                               dispatch_stats=self.backend
                               .dispatch_stats().row())


@dataclasses.dataclass
class SchedulerStats:
    """One continuous-batching run's amortization + fairness accounting.

    ``dispatches`` / ``tokens`` are deltas over the backend's uniform
    ``dispatch_stats()`` across the whole run (prefills included), so
    ``dispatches_per_token`` is directly comparable with the sequential
    Table-2 rows — it visibly DROPS as occupancy rises, which is the
    continuous-batching claim the CI gate asserts.
    """
    num_slots: int = 0
    continuous: bool = True
    kv_layout: str = "dense"
    cycles: int = 0                  # batched decode cycles issued
    admitted: int = 0                # requests prefilled into a slot
    completed: int = 0
    tokens: int = 0                  # tokens emitted (all requests)
    dispatches: int = 0              # backend dispatch delta over the run
    occupancy_sum: int = 0           # Σ active slots per cycle
    wall_s: float = 0.0
    queue_waits_s: List[float] = dataclasses.field(default_factory=list)
    # per-request serving latency samples (filled when the run drains)
    ttfts_s: List[float] = dataclasses.field(default_factory=list)
    tpots_s: List[float] = dataclasses.field(default_factory=list)
    # paged KV / prefix cache / chunked prefill (kv_layout == "paged")
    prefill_chunks: int = 0          # extend dispatches issued
    prefix_hits: int = 0             # admissions with a nonzero radix match
    prefix_hit_tokens: int = 0       # prompt tokens served from shared blocks
    prompt_tokens: int = 0           # total prompt tokens admitted
    cow_copies: int = 0              # copy-on-write block forks this run
    evictions: int = 0               # radix chains evicted under pressure
    # async (double-buffered) device→host readback
    overlap_cycles: int = 0          # cycles issued BEFORE the previous
                                     # cycle's tokens were read back
    sync_readback_s: float = 0.0     # device_get time on the blocking path
    overlap_readback_s: float = 0.0  # device_get time overlapped with the
                                     # next cycle's device work
    # multi-step decode capture (decode_horizon > 1, graph backends)
    decode_horizon: int = 1          # configured super-step horizon
    multi_cycles: int = 0            # super-steps issued (each covers up
                                     # to ``decode_horizon`` decode cycles
                                     # in ONE host submission)
    multi_tokens: int = 0            # tokens emitted by super-steps
    # KV memory utilization (satellite: dense vs paged in one table)
    kv_bytes_allocated: int = 0
    kv_bytes_live_peak: int = 0
    # speculative decoding (Scheduler(speculative=...))
    speculative: str = ""            # drafter name; "" ⇒ speculation off
    spec_cycles: int = 0             # verify cycles issued
    verify_dispatches: int = 0       # ONE batched target dispatch per cycle
    draft_dispatches: int = 0        # drafter-side dispatches (0 for n-gram)
    draft_tokens_proposed: int = 0
    draft_tokens_accepted: int = 0   # drafts the target's argmax agreed with
    bonus_tokens: int = 0            # free token after each accepted span
    spec_tokens: int = 0             # tokens emitted by verify cycles
    # SLO-aware preemption (Scheduler(preemption=...))
    preemptions: int = 0             # slots evicted for higher priority
    preempt_swaps: int = 0           # victims whose chains moved to host
    preempt_recomputes: int = 0      # victims released for re-prefill
    swap_ins: int = 0                # swapped chains restored to the arena
    swap_blocks_host: int = 0        # exclusive blocks copied to host
    swap_blocks_retained: int = 0    # shared blocks parked by reference
    swap_upload_dispatches: int = 0  # host→device uploads on restore
    # SLO attainment + goodput (requests carrying slo_ttft_ms)
    slo_requests: int = 0            # completed requests that declared an SLO
    slo_met: int = 0                 # of those, TTFT within the objective
    goodput_tokens: int = 0          # tokens from SLO-meeting (or SLO-free)
                                     # requests — the useful-work numerator

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / max(self.cycles, 1)

    @property
    def dispatches_per_token(self) -> float:
        return self.dispatches / max(self.tokens, 1)

    @property
    def aggregate_tok_per_s(self) -> float:
        return self.tokens / max(self.wall_s, 1e-12)

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hit_tokens / max(self.prompt_tokens, 1)

    @property
    def kv_utilization(self) -> float:
        return self.kv_bytes_live_peak / max(self.kv_bytes_allocated, 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target's argmax agreed with."""
        return self.draft_tokens_accepted / max(self.draft_tokens_proposed, 1)

    # -- serving-latency percentiles (linear interpolation, numpy rule) --
    @property
    def ttft_p50_ms(self) -> float:
        return 1e3 * percentile(self.ttfts_s, 50)

    @property
    def ttft_p99_ms(self) -> float:
        return 1e3 * percentile(self.ttfts_s, 99)

    @property
    def tpot_p50_ms(self) -> float:
        """Time-per-output-token: (total − ttft) / (n_new − 1) per request."""
        return 1e3 * percentile(self.tpots_s, 50)

    @property
    def tpot_p99_ms(self) -> float:
        return 1e3 * percentile(self.tpots_s, 99)

    @property
    def queue_wait_p50_ms(self) -> float:
        return 1e3 * percentile(self.queue_waits_s, 50)

    @property
    def queue_wait_p99_ms(self) -> float:
        return 1e3 * percentile(self.queue_waits_s, 99)

    @property
    def dispatches_per_accepted_token(self) -> float:
        """Target dispatches per token emitted on the speculative path —
        the paper's amortization lever: one verify dispatch yields
        ``1 + accepted`` tokens, so this sits at ``1 / (1 + a·k̄)`` and
        must undercut the autoregressive ``dispatches_per_token`` (≈ 1)
        for speculation to pay.  Draft dispatches are accounted
        separately (``draft_dispatches``): the n-gram drafter issues
        none, and a small-model drafter's are deliberately cheap.  0.0
        when no speculative token was emitted (the zero-token edge)."""
        if not self.spec_tokens:
            return 0.0
        return self.verify_dispatches / self.spec_tokens

    @property
    def slo_attainment(self) -> float:
        """Fraction of SLO-carrying requests whose TTFT met the objective
        (1.0 when no request declared one)."""
        if not self.slo_requests:
            return 1.0
        return self.slo_met / self.slo_requests

    @property
    def goodput_tok_per_s(self) -> float:
        """Useful throughput: tokens from requests that met their TTFT SLO
        (SLO-free requests count in full) over the run's wall clock —
        the harness's oversubscription headline next to raw
        ``aggregate_tok_per_s``."""
        return self.goodput_tokens / max(self.wall_s, 1e-12)

    def to_dict(self) -> Dict[str, Any]:
        """Every dataclass field plus the derived metrics — the lossless
        serialization ``from_dict`` round-trips (derived keys are
        recomputed, not stored)."""
        d = dataclasses.asdict(self)
        d["mean_occupancy"] = self.mean_occupancy
        d["dispatches_per_token"] = self.dispatches_per_token
        d["aggregate_tok_per_s"] = self.aggregate_tok_per_s
        d["prefix_hit_rate"] = self.prefix_hit_rate
        d["kv_utilization"] = self.kv_utilization
        d["acceptance_rate"] = self.acceptance_rate
        d["dispatches_per_accepted_token"] = self.dispatches_per_accepted_token
        d["ttft_p50_ms"] = self.ttft_p50_ms
        d["ttft_p99_ms"] = self.ttft_p99_ms
        d["tpot_p50_ms"] = self.tpot_p50_ms
        d["tpot_p99_ms"] = self.tpot_p99_ms
        d["queue_wait_p50_ms"] = self.queue_wait_p50_ms
        d["queue_wait_p99_ms"] = self.queue_wait_p99_ms
        d["slo_attainment"] = self.slo_attainment
        d["goodput_tok_per_s"] = self.goodput_tok_per_s
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SchedulerStats":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def row(self) -> Dict[str, Any]:
        return {
            "num_slots": self.num_slots,
            "continuous": self.continuous,
            "kv_layout": self.kv_layout,
            "cycles": self.cycles,
            "admitted": self.admitted,
            "completed": self.completed,
            "tokens": self.tokens,
            "mean_occupancy": round(self.mean_occupancy, 2),
            "dispatches_per_token": round(self.dispatches_per_token, 2),
            "aggregate_tok_s": round(self.aggregate_tok_per_s, 2),
            "queue_wait_ms_max": round(
                1e3 * max(self.queue_waits_s, default=0.0), 2),
            "queue_wait_ms_mean": round(
                1e3 * (sum(self.queue_waits_s)
                       / max(len(self.queue_waits_s), 1)), 2),
            "ttft_p50_ms": round(self.ttft_p50_ms, 2),
            "ttft_p99_ms": round(self.ttft_p99_ms, 2),
            "tpot_p50_ms": round(self.tpot_p50_ms, 2),
            "tpot_p99_ms": round(self.tpot_p99_ms, 2),
            "queue_wait_p50_ms": round(self.queue_wait_p50_ms, 2),
            "queue_wait_p99_ms": round(self.queue_wait_p99_ms, 2),
            "prefill_chunks": self.prefill_chunks,
            "prefix_hit_rate": round(self.prefix_hit_rate, 3),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            "overlap_cycles": self.overlap_cycles,
            "decode_horizon": self.decode_horizon,
            "multi_cycles": self.multi_cycles,
            "multi_tokens": self.multi_tokens,
            "sync_readback_ms": round(1e3 * self.sync_readback_s, 2),
            "overlap_readback_ms": round(1e3 * self.overlap_readback_s, 2),
            "kv_bytes_allocated": self.kv_bytes_allocated,
            "kv_bytes_live_peak": self.kv_bytes_live_peak,
            "kv_utilization": round(self.kv_utilization, 3),
            "speculative": self.speculative,
            "spec_cycles": self.spec_cycles,
            "verify_dispatches": self.verify_dispatches,
            "draft_dispatches": self.draft_dispatches,
            "acceptance_rate": round(self.acceptance_rate, 3),
            "bonus_tokens": self.bonus_tokens,
            "dispatches_per_accepted_token": round(
                self.dispatches_per_accepted_token, 3),
            "preemptions": self.preemptions,
            "preempt_swaps": self.preempt_swaps,
            "preempt_recomputes": self.preempt_recomputes,
            "swap_ins": self.swap_ins,
            "swap_blocks_host": self.swap_blocks_host,
            "swap_blocks_retained": self.swap_blocks_retained,
            "slo_requests": self.slo_requests,
            "slo_met": self.slo_met,
            "slo_attainment": round(self.slo_attainment, 3),
            "goodput_tok_s": round(self.goodput_tok_per_s, 2),
        }


@dataclasses.dataclass
class SchedulerConfig:
    """Every :class:`Scheduler` policy knob in ONE validated dataclass.

    The scheduler's constructor accreted a kwarg per feature PR; this is
    the consolidated surface.  Build one and pass
    ``Scheduler(session, config=cfg)`` — or keep calling with the
    individual kwargs, which now merely populate a config for you.

    Fields:
      num_slots: concurrent request slots — the batch width decode
        cycles amortize dispatch overhead over.
      continuous: ``True`` batches every cycle into ONE
        ``decode_batch`` dispatch; ``False`` is the sequential
        per-slot-dispatch baseline the amortization curve starts at.
      kv_layout: ``"dense"`` (slot-major KV pool) or ``"paged"``
        (block pool + radix prefix cache, see
        :mod:`repro.serving.paging`).
      prefill_chunk: paged only — prompt tokens prefilled per cycle,
        interleaved with decode so long admissions never stall
        running slots; ``None`` prefills whole prompts at once.
      prefix_cache: paged only — radix-cache prompt prefixes so
        shared spans skip prefill (see ``SchedulerStats.prefix_*``).
      block_size: paged only — tokens per KV block (sharing/COW
        granularity).
      num_blocks: paged only — arena capacity in blocks; ``None``
        sizes for worst-case occupancy plus prefix-cache slack.
      async_readback: double-buffer device→host token readback in
        steady state (identical token streams; savings in
        ``SchedulerStats.overlap_*``).
      speculative: draft/verify decoding — ``"ngram"``, a
        :class:`~repro.serving.spec.SpeculativeConfig`, or a
        :class:`~repro.serving.spec.Drafter`; paged layout only.
        Normalized to a ``SpeculativeConfig`` (or ``None``) on
        construction.
      preemption: ``"off"`` | ``"swap"`` | ``"recompute"`` |
        ``"auto"`` — oversubscription policy (paged layout only; see
        the :class:`Scheduler` docstring).  ``"swap"`` needs
        ``capabilities.preemption``; ``"auto"`` degrades to
        recompute when the backend cannot swap.
      decode_horizon: multi-step decode capture — when the backend
        advertises ``capabilities.decode_multi`` and every active
        request is greedy token-readback with no stream callback, the
        scheduler submits up to this many decode cycles as ONE
        ``decode_multi`` super-step (on-device sampling + stop
        detection), cutting host submissions per token by the same
        factor.  ``1`` (default) keeps the per-cycle path; ineligible
        mixes fall back to it automatically.
      tracer: a :class:`repro.obs.Tracer` — scheduler/slot/paging
        tracks plus the backend's dispatch lane feed one timeline.
      metrics: a :class:`repro.obs.MetricsRegistry` — each ``run``
        folds its stats in (``serving.*`` counters/histograms,
        per-priority TTFT, SLO attainment); the traffic harness
        sources its SLO numbers HERE, not from ad-hoc timers.
    """
    num_slots: int = 2
    continuous: bool = True
    kv_layout: str = "dense"
    prefill_chunk: Optional[int] = None
    prefix_cache: bool = True
    block_size: int = 16
    num_blocks: Optional[int] = None
    async_readback: bool = True
    speculative: Any = None
    preemption: str = "off"
    decode_horizon: int = 1
    tracer: Optional[Tracer] = None
    metrics: Optional[MetricsRegistry] = None

    def __post_init__(self) -> None:
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {self.kv_layout!r}")
        if self.kv_layout == "paged" and not self.continuous:
            raise ValueError("paged KV requires the continuous scheduler")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if self.preemption not in ("off", "swap", "recompute", "auto"):
            raise ValueError(f"unknown preemption {self.preemption!r}")
        if self.preemption != "off" and self.kv_layout != "paged":
            raise ValueError(
                "preemption requires kv_layout='paged' (victim state moves "
                "as block chains; the dense pool has nothing to swap)")
        if self.speculative is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "speculative decoding requires kv_layout='paged' (the "
                    "COW block-fork rollback lives in the paging arena)")
            if isinstance(self.speculative, (str, Drafter)):
                self.speculative = SpeculativeConfig(drafter=self.speculative)
            elif not isinstance(self.speculative, SpeculativeConfig):
                raise ValueError(
                    "speculative must be a drafter name, a Drafter, or a "
                    f"SpeculativeConfig; got "
                    f"{type(self.speculative).__name__}")


class Scheduler:
    """Multi-request slot scheduler with continuous batching.

    Requests queue FIFO; up to ``num_slots`` run concurrently.  In the
    default **continuous** mode every cycle issues ONE batched decode
    across all active slots (``backend.decode_batch`` over a slot-major
    KV pool with per-slot positions), so per-cycle dispatch overhead —
    the paper's ~95 µs/op batch-1 wall — is amortized over occupancy.
    Admission is in-flight: whenever a slot frees, the next queued request
    prefills into it between cycles, with no drain barrier; stop
    conditions terminate each slot independently; FIFO admission plus the
    per-request ``queue_wait_s`` recorded in ``last_stats`` give the
    fairness accounting.

    ``continuous=False`` keeps the pre-batching behavior — one
    ``decode_step`` dispatch per active slot per cycle — as the
    measurement baseline the amortization curve is drawn against.
    Backends that cannot batch (``capabilities.decode_batch`` False) run
    the same per-slot loop through the uniform fallback contract.

    ``kv_layout="paged"`` swaps the dense slot-major pool for the paged
    block-pool subsystem (``repro.serving.paging``): admission is a radix
    prefix-cache match (a warm hit skips prefill dispatches for the whole
    shared span), blocks are claimed lazily as sequences grow, and prefill
    is **chunked** — ``prefill_chunk`` prompt tokens per cycle interleaved
    with decode, so one long admission no longer stalls every active slot.
    The paged batch state (block pool + radix cache) persists across
    ``run`` calls, so prefix hits accumulate over a scheduler's lifetime.

    ``speculative=...`` (paged layout only) turns decode cycles into
    draft/verify cycles: a :class:`~repro.serving.spec.Drafter` proposes
    up to K tokens per slot from its realized sequence, the target scores
    pending-token + drafts in ONE batched ``verify_paged`` dispatch, and
    the accepted prefix (plus one free bonus token) is committed through
    a COW block-table fork — rejection is a zero-copy position rewind.
    Greedy output is bit-identical to the autoregressive path; slots with
    non-greedy samplers (or logits readback) transparently fall back to
    plain decode within the same verify dispatch.  Accepts ``"ngram"``, a
    ``SpeculativeConfig``, or a ``Drafter`` instance.

    ``async_readback`` double-buffers the device→host token readback:
    while the run is in a steady state (greedy token-readback requests, no
    stop tokens or stream callbacks, nobody finishing), the NEXT decode
    cycle is issued from the previous cycle's still-on-device
    ``next_token`` before that cycle's tokens are fetched, so the host
    readback + Python bookkeeping overlap device work (the savings land in
    ``SchedulerStats.overlap_*``).  Token streams are identical either way.

    ``decode_horizon > 1`` goes further on backends advertising
    ``capabilities.decode_multi``: when every active slot is greedy
    token-readback with no stream callback, the scheduler wraps up to N
    decode cycles into ONE ``decode_multi`` super-step — on-device argmax
    feeds each cycle's token into the next, an on-device stop table masks
    rows past their stop token, and the host reads one ``(slots, N)``
    token block back per submission, so dispatches per token drop by ~N×
    with a byte-identical greedy stream.  Stop tokens are reconciled on
    retire (nothing past a stop is ever emitted); non-greedy samplers,
    logits readback, or streaming fall back to the per-cycle path.

    ``preemption`` (paged layout only) makes the scheduler survive
    oversubscription: admission is priority-ordered (FIFO within a
    priority), and when every slot is busy a strictly-higher-priority
    waiter evicts the lowest-priority decoding slot.  A victim is either
    **swapped** — its block chain moves to host memory through
    ``swap_out_paged`` (shared radix/COW blocks park by reference, only
    exclusive blocks cross the bus; the ``dist/elastic.py`` restore
    idiom) and later re-uploads byte-exactly — or **recomputed**:
    released through the radix cache (so its prompt+generated chain
    stays warm) and re-prefilled when a slot frees.  ``"auto"`` picks
    per victim from measured costs: EWMA host-side prefill s/token vs
    EWMA swap-in s/block, applied to the victim's exclusive-block count
    versus the tokens a re-prefill would actually recompute after the
    radix hit.  Either way the emitted token stream is byte-identical to
    an unpreempted run.

    ``submit_at`` gives open-loop (arrival-clock) traffic: requests
    enter the queue at scheduled wall-clock times regardless of
    completions, so ``run`` reproduces real bursty load —
    ``benchmarks/bench_traffic.py`` drives this path.
    """

    def __init__(self, session: InferenceSession,
                 num_slots: Optional[int] = None, *,
                 config: Optional[SchedulerConfig] = None,
                 **kwargs: Any) -> None:
        """Args:
          session: the :class:`InferenceSession` whose backend executes
            every dispatch; the scheduler only orchestrates.
          config: a :class:`SchedulerConfig` carrying every policy knob —
            the ONE configuration surface (see its docstring for the
            per-field semantics).
          num_slots / **kwargs: DEPRECATED per-field construction
            (``Scheduler(session, 4, kv_layout="paged", ...)``).  The
            kwargs simply populate a ``SchedulerConfig`` — same fields,
            same validation, same error messages — and cannot be mixed
            with ``config=``.  Prefer passing a config; the kwargs path
            remains for the historical call sites.
        """
        if config is not None:
            if num_slots is not None or kwargs:
                raise ValueError(
                    "pass either config= or the per-field kwargs, not both")
        else:
            if num_slots is not None:
                kwargs["num_slots"] = num_slots
            config = SchedulerConfig(**kwargs)
        self.config = config
        self._spec: Optional[SpeculativeConfig] = config.speculative
        self._drafter: Optional[Drafter] = None
        self.session = session
        self.num_slots = config.num_slots
        self.continuous = config.continuous
        self.kv_layout = config.kv_layout
        self.prefill_chunk = config.prefill_chunk
        self.prefix_cache = config.prefix_cache
        self.block_size = config.block_size
        self.num_blocks = config.num_blocks
        self.async_readback = config.async_readback
        self.preemption = config.preemption
        self.decode_horizon = config.decode_horizon
        self._queue: List[ServeRequest] = []
        self._future: List[Tuple[float, int, ServeRequest]] = []  # heap
        self._preempted: List[Dict[str, Any]] = []   # evicted, awaiting slot
        self._submit_t: Dict[str, float] = {}
        self._req_meta: Dict[str, Tuple[int, Optional[float]]] = {}
        self._finished_meta: List[Tuple[int, Optional[float], ServeResult]] \
            = []
        # measured-cost EWMAs driving the "auto" restore-vs-recompute pick
        # (host-side enqueue costs — the side the scheduler actually pays)
        self._ewma_prefill_s_per_tok: Optional[float] = None
        self._ewma_upload_s_per_block: Optional[float] = None
        self._bstate: Optional[Dict[str, Any]] = None
        self.last_stats: Optional[SchedulerStats] = None
        self.tracer = (config.tracer if config.tracer is not None
                       else NULL_TRACER)
        self.metrics = config.metrics
        if self.tracer.enabled:
            # one accounting source: the backend's _record choke point
            # emits the dispatch-lane spans the CI consistency gate sums
            session.backend.tracer = self.tracer

    def submit(self, req: ServeRequest) -> str:
        self._queue.append(req)
        self._submit_t[req.request_id] = time.perf_counter()
        self._req_meta[req.request_id] = (req.priority, req.slo_ttft_ms)
        return req.request_id

    def submit_at(self, req: ServeRequest, at_s: float) -> str:
        """Open-loop submission: the request enters the queue at the
        absolute ``time.perf_counter()`` instant ``at_s`` (past instants
        enter immediately).  ``run`` keeps draining until every scheduled
        arrival has landed and completed, sleeping through genuinely idle
        gaps — so an arrival-process trace (Poisson, replay) plays back on
        the wall clock regardless of how fast completions drain.
        ``queue_wait_s`` measures from the SCHEDULED arrival, which is
        what an open-loop latency percentile must charge."""
        heapq.heappush(self._future, (at_s, next(_req_counter), req))
        self._submit_t[req.request_id] = at_s
        self._req_meta[req.request_id] = (req.priority, req.slo_ttft_ms)
        return req.request_id

    def _drain_arrivals(self) -> None:
        """Move every due scheduled arrival into the live queue."""
        now = time.perf_counter()
        while self._future and self._future[0][0] <= now:
            self._queue.append(heapq.heappop(self._future)[2])

    def _wait_for_arrival(self, busy: bool) -> None:
        """Idle-sleep until the next scheduled arrival — only when there
        is genuinely nothing to run (open-loop gaps in light traffic)."""
        if not busy and not self._queue and self._future:
            time.sleep(max(0.0, self._future[0][0] - time.perf_counter()))

    def _pop_next(self) -> ServeRequest:
        """Highest priority first, FIFO within a priority."""
        i = min(range(len(self._queue)),
                key=lambda j: (-self._queue[j].priority, j))
        return self._queue.pop(i)

    @property
    def pending(self) -> int:
        return len(self._queue) + len(self._future)

    # ------------------------------------------------------------------
    def _book_admission(self, a: _Active, st: SchedulerStats) -> None:
        """Shared admission accounting (dense and paged paths)."""
        a.queue_wait_s = a.t0 - self._submit_t.pop(a.req.request_id, a.t0)
        st.admitted += 1
        st.queue_waits_s.append(a.queue_wait_s)

    def _start(self, req: ServeRequest, st: SchedulerStats) -> _Active:
        a = self.session.start(req)
        self._book_admission(a, st)
        st.tokens += 1                       # prefill emitted the first token
        return a

    def run(self) -> Dict[str, ServeResult]:
        """Drain the queue; returns {request_id: ServeResult}.  Amortization
        and fairness accounting for the run lands in ``self.last_stats``."""
        st = SchedulerStats(num_slots=self.num_slots,
                            continuous=self.continuous,
                            kv_layout=self.kv_layout,
                            decode_horizon=self.decode_horizon,
                            speculative=self._drafter_name())
        backend = self.session.backend
        d0 = backend.dispatch_stats().dispatches
        t0 = time.perf_counter()
        if not self.continuous:
            results = self._run_sequential(st)
        elif self.kv_layout == "paged":
            results = self._run_paged(st)
        else:
            results = self._run_continuous(st)
        st.wall_s = time.perf_counter() - t0
        st.dispatches = backend.dispatch_stats().dispatches - d0
        st.completed = len(results)
        self._finished_meta = []
        for rid, r in results.items():
            st.ttfts_s.append(r.ttft_s)
            if r.n_new > 1:
                st.tpots_s.append((r.total_s - r.ttft_s) / (r.n_new - 1))
            pri, slo = self._req_meta.pop(rid, (0, None))
            self._finished_meta.append((pri, slo, r))
            met = slo is None or 1e3 * r.ttft_s <= slo
            if slo is not None:
                st.slo_requests += 1
                st.slo_met += int(met)
            if met:
                st.goodput_tokens += r.n_new
        if self.metrics is not None:
            self._publish_metrics(st)
        self.last_stats = st
        return results

    def _publish_metrics(self, st: SchedulerStats) -> None:
        """Fold one run's accounting into the attached registry."""
        m = self.metrics
        m.counter("serving.tokens").inc(st.tokens)
        m.counter("serving.dispatches").inc(st.dispatches)
        m.counter("serving.cycles").inc(st.cycles)
        m.counter("serving.completed").inc(st.completed)
        m.gauge("serving.mean_occupancy").set(st.mean_occupancy)
        m.gauge("serving.dispatches_per_token").set(st.dispatches_per_token)
        for v in st.ttfts_s:
            m.histogram("serving.ttft_s").observe(v)
        for v in st.tpots_s:
            m.histogram("serving.tpot_s").observe(v)
        for v in st.queue_waits_s:
            m.histogram("serving.queue_wait_s").observe(v)
        # SLO attainment + goodput + per-priority latency: the traffic
        # harness reads THESE (not ad-hoc timers) for its reported numbers
        m.counter("serving.preemptions").inc(st.preemptions)
        m.counter("serving.preempt_swaps").inc(st.preempt_swaps)
        m.counter("serving.preempt_recomputes").inc(st.preempt_recomputes)
        m.counter("serving.swap_ins").inc(st.swap_ins)
        m.counter("serving.slo.requests").inc(st.slo_requests)
        m.counter("serving.slo.met").inc(st.slo_met)
        m.counter("serving.goodput_tokens").inc(st.goodput_tokens)
        for pri, _slo, r in self._finished_meta:
            m.histogram(f"serving.ttft_s.p{pri}").observe(r.ttft_s)

    # -- shared cycle plumbing ------------------------------------------
    @staticmethod
    def _check_row(req: ServeRequest) -> np.ndarray:
        prompt = np.atleast_2d(np.asarray(req.prompt, np.int32))
        if prompt.shape[0] != 1:
            raise ValueError(
                "continuous batching schedules one row per slot; got a "
                f"batch-{prompt.shape[0]} prompt")
        return prompt

    def _track_kv(self, bstate, st: SchedulerStats) -> None:
        kv = bstate.get("paged") or bstate.get("kv") or bstate.get("rstate")
        if kv is not None:
            if not st.kv_bytes_allocated:    # constant per pool: compute once
                st.kv_bytes_allocated = kv.bytes_allocated
            st.kv_bytes_live_peak = max(st.kv_bytes_live_peak, kv.bytes_live)

    def _cycle_span(self, st: SchedulerStats, occupancy: int, **args):
        """One ``decode_cycle`` span: the scheduler's host work from
        issuing a cycle's dispatch to retiring the tokens read back in the
        same iteration (issue N+1, retire N when the readback is
        deferred).  Exactly one per ``st.cycles``."""
        return self.tracer.span("decode_cycle", track="scheduler",
                                cycle=st.cycles, occupancy=occupancy, **args)

    def _issue_cycle(self, bstate, active: Dict[int, "_Active"],
                     st: SchedulerStats, tokens):
        """ONE batched decode dispatch for every active slot."""
        slots = tuple(sorted(active))
        bstate, out = self.session.backend.decode_batch(bstate, tokens, slots)
        st.cycles += 1
        st.occupancy_sum += len(slots)
        self._track_kv(bstate, st)
        return bstate, slots, out

    def _host_tokens(self, active: Dict[int, "_Active"]) -> np.ndarray:
        tokens = np.zeros((self.num_slots, 1), np.int32)
        for s, a in active.items():
            tokens[s, 0] = a.last_tok[0, 0]
        return tokens

    @staticmethod
    def _realized(a: _Active) -> np.ndarray:
        """The request's realized token sequence (prompt + emitted), handed
        to ``release_slot`` so paged backends can radix-cache the
        prompt+completion chain for multi-turn reuse."""
        prompt = np.atleast_2d(np.asarray(a.req.prompt, np.int32))[0]
        gen = (np.concatenate(a.tokens, axis=1)[0] if a.tokens
               else np.zeros((0,), np.int32))
        return np.concatenate([prompt, gen])

    def _retire_cycle(self, out: StepOutput, slots, active, results, bstate,
                      st: SchedulerStats, *, overlapped: bool):
        """Read a cycle's tokens back and feed each slot its row."""
        backend = self.session.backend
        tr = self.tracer
        with tr.span("readback", track="scheduler", overlapped=overlapped):
            t0 = time.perf_counter()
            # one host readback per CYCLE (not per slot) in the greedy
            # token-readback regime: a (num_slots,) int32 vector
            nxt = (np.asarray(out.next_token, np.int32)
                   if out.next_token is not None else None)
            dt = time.perf_counter() - t0
        if overlapped:
            st.overlap_readback_s += dt
        else:
            st.sync_readback_s += dt
        with tr.span("sample_emit", track="scheduler", slots=len(slots)):
            for s in slots:
                a = active[s]
                row = StepOutput(out.logits[s:s + 1],
                                 None if nxt is None else nxt[s:s + 1])
                st.tokens += 1
                if self.session.step_row(a, row):
                    results[a.req.request_id] = self.session.finish(a)
                    bstate = backend.release_slot(bstate, s,
                                                  tokens=self._realized(a))
                    tr.instant("release", track=f"slot{s}",
                               req=a.req.request_id, n_new=len(a.tokens))
                    del active[s]
        return bstate

    def _async_safe(self, active: Dict[int, "_Active"]) -> bool:
        """True when deferring the readback cannot change observable
        behavior: greedy device-argmax tokens only, nothing watching the
        stream mid-flight, no stop tokens to react to."""
        return all(a.req.sampler.kind == "greedy"
                   and a.req.readback == "token"
                   and not a.req.stop_tokens
                   and a.req.stream is None for a in active.values())

    def _drain_async(self, bstate, out: StepOutput, slots, active, results,
                     st: SchedulerStats):
        """Double-buffered steady state: issue cycle N+1 from cycle N's
        on-device tokens, THEN read cycle N back — the device computes
        while the host fetches and books the previous tokens.  Exits (and
        sync-retires the in-flight cycle) as soon as a slot is about to
        finish, so every issued cycle's token is emitted — no speculative
        work is ever discarded."""
        backend = self.session.backend
        while (self.async_readback and out.next_token is not None
               and not self._future       # open-loop arrivals poll per cycle
               and self._async_safe(active)
               and all(len(active[s].tokens) + 1
                       < active[s].req.max_new_tokens for s in slots)):
            with self._cycle_span(st, len(slots), overlapped=True):
                bstate, out_next = backend.decode_batch(bstate,
                                                        out.next_token, slots)
                st.cycles += 1
                st.occupancy_sum += len(slots)
                st.overlap_cycles += 1
                self._track_kv(bstate, st)
                bstate = self._retire_cycle(out, slots, active, results,
                                            bstate, st, overlapped=True)
            out = out_next
        return self._retire_cycle(out, slots, active, results, bstate, st,
                                  overlapped=False)

    # -- multi-step decode capture (decode_horizon > 1) ------------------
    def _multi_ok(self, active: Dict[int, "_Active"]) -> bool:
        """Multi-step eligibility: the super-step samples on device, so
        every active slot must be greedy token-readback with no stream
        callback.  Stop tokens ARE allowed — the on-device stop table plus
        retire-time reconciliation handle them."""
        return (self.decode_horizon > 1
                and self.session.backend.capabilities.decode_multi
                and all(a.req.sampler.kind == "greedy"
                        and a.req.readback == "token"
                        and a.req.stream is None
                        for a in active.values()))

    def _multi_horizon(self, active: Dict[int, "_Active"]) -> int:
        """Clip the configured horizon to the tightest remaining token
        budget so no slot can overrun ``max_new_tokens`` mid-capture."""
        rem = min(a.req.max_new_tokens - len(a.tokens)
                  for a in active.values())
        return min(self.decode_horizon, rem)

    def _stop_table(self, active: Dict[int, "_Active"]
                    ) -> Optional[np.ndarray]:
        """(num_slots, W) int32 stop-token table for the on-device stop
        check; −1 pads (never a vocab id).  ``None`` when no active
        request declares stop tokens."""
        width = max((len(a.req.stop_tokens) for a in active.values()),
                    default=0)
        if width == 0:
            return None
        tbl = np.full((self.num_slots, width), -1, np.int32)
        for s, a in active.items():
            if a.req.stop_tokens:
                tbl[s, :len(a.req.stop_tokens)] = a.req.stop_tokens
        return tbl

    def _issue_multi(self, bstate, active: Dict[int, "_Active"],
                     st: SchedulerStats, tokens, horizon: int, stop_table,
                     *, overlapped: bool = False):
        """ONE host submission advancing every active slot ``horizon``
        decode cycles (``backend.decode_multi``)."""
        slots = tuple(sorted(active))
        bstate, out = self.session.backend.decode_multi(
            bstate, tokens, slots, horizon=horizon, stop_table=stop_table)
        st.cycles += 1
        st.multi_cycles += 1
        st.occupancy_sum += len(slots)
        if overlapped:
            st.overlap_cycles += 1
        self._track_kv(bstate, st)
        return bstate, slots, out

    def _retire_multi(self, out, slots, active, results, bstate,
                      st: SchedulerStats, *, overlapped: bool):
        """Read one super-step's (slots, horizon) token block back and
        replay it through the per-request emission path.  ``valid`` masks
        columns past each row's stop token, so reconciliation is a
        host-side truncation — nothing past a stop is ever emitted.  A
        finishing paged slot's published position is clamped to the
        sampling boundary before release: the device may have early-exited
        before feeding the final token back, so only ``len(seq) - 1``
        positions are guaranteed-valid KV (exactly the single-step radix
        insert rule)."""
        backend = self.session.backend
        tr = self.tracer
        with tr.span("readback", track="scheduler", overlapped=overlapped,
                     multi=True):
            t0 = time.perf_counter()
            toks = np.asarray(out.tokens, np.int32)   # ONE readback per N
            valid = np.asarray(out.valid, bool)
            dt = time.perf_counter() - t0
        if overlapped:
            st.overlap_readback_s += dt
        else:
            st.sync_readback_s += dt
        horizon = toks.shape[1]
        with tr.span("sample_emit", track="scheduler", slots=len(slots),
                     horizon=horizon):
            for s in slots:
                a = active[s]
                done = False
                for i in range(horizon):
                    if not valid[s, i]:
                        break
                    st.tokens += 1
                    st.multi_tokens += 1
                    done = self.session.step_row(
                        a, StepOutput(None, toks[s:s + 1, i:i + 1]))
                    if done:
                        break
                if done:
                    seq = self._realized(a)
                    if "paged" in bstate:
                        bstate["paged"].pos[s] = len(seq) - 1
                    results[a.req.request_id] = self.session.finish(a)
                    bstate = backend.release_slot(bstate, s, tokens=seq)
                    tr.instant("release", track=f"slot{s}",
                               req=a.req.request_id, n_new=len(a.tokens))
                    del active[s]
        return bstate

    def _drain_multi(self, bstate, out, slots, active, results,
                     st: SchedulerStats, horizon: int):
        """Double-buffered super-steps: issue super-step N+1 from the last
        on-device token column of super-step N, THEN retire N overlapped —
        the multi-step analogue of ``_drain_async``.  Requires the
        stop-free steady state (``_async_safe``): with stop tokens a row
        may end mid-horizon, making the last column the wrong next
        input."""
        while (self.async_readback
               and not self._future      # open-loop arrivals poll per step
               and self._async_safe(active)
               and all(len(active[s].tokens) + 2 * horizon
                       <= active[s].req.max_new_tokens for s in slots)):
            with self._cycle_span(st, len(slots), horizon=horizon,
                                  multi=True, overlapped=True):
                bstate, _, out_next = self._issue_multi(
                    bstate, active, st, out.tokens[:, -1:], horizon, None,
                    overlapped=True)
                bstate = self._retire_multi(out, slots, active, results,
                                            bstate, st, overlapped=True)
            out = out_next
        return self._retire_multi(out, slots, active, results, bstate, st,
                                  overlapped=False)

    # -- continuous batching (the production path) ----------------------
    def _run_continuous(self, st: SchedulerStats) -> Dict[str, ServeResult]:
        backend = self.session.backend
        if self._bstate is None:
            self._bstate = backend.alloc_slots(self.num_slots)
        bstate = self._bstate
        results: Dict[str, ServeResult] = {}
        active: Dict[int, _Active] = {}
        while self._queue or self._future or active:
            self._drain_arrivals()
            self._wait_for_arrival(busy=bool(active))
            # in-flight admission: prefill queued requests into free slots
            # between decode cycles — running slots never drain or stall
            while self._queue and len(active) < self.num_slots:
                req = self._pop_next()
                self._check_row(req)
                with self.tracer.span("admit", track="scheduler",
                                      req=req.request_id):
                    a = self._start(req, st)
                if a.done:
                    results[a.req.request_id] = self.session.finish(a)
                    continue
                slot = min(s for s in range(self.num_slots)
                           if s not in active)
                bstate = backend.admit_slot(bstate, slot, a.state)
                a.state = None               # KV now lives in the slot pool
                active[slot] = a
            if not active:
                continue
            horizon = self._multi_horizon(active)
            if horizon > 1 and self._multi_ok(active):
                with self._cycle_span(st, len(active), horizon=horizon,
                                      multi=True):
                    bstate, slots, out = self._issue_multi(
                        bstate, active, st, self._host_tokens(active),
                        horizon, self._stop_table(active))
                bstate = self._drain_multi(bstate, out, slots, active,
                                           results, st, horizon)
                continue
            with self._cycle_span(st, len(active)):
                bstate, slots, out = self._issue_cycle(
                    bstate, active, st, self._host_tokens(active))
            bstate = self._drain_async(bstate, out, slots, active, results,
                                       st)
        self._bstate = bstate
        return results

    # -- speculative draft/verify/commit --------------------------------
    def _drafter_name(self) -> str:
        if self._spec is None:
            return ""
        d = self._spec.drafter
        return d if isinstance(d, str) else type(d).__name__

    def _ensure_drafter(self) -> Drafter:
        if self._drafter is None:
            d = self._spec.drafter
            self._drafter = (NgramDrafter(self._spec.max_n, self._spec.min_n)
                             if isinstance(d, str) else d)
        return self._drafter

    @staticmethod
    def _spec_eligible(a: _Active) -> bool:
        """Speculation preserves the exact stream only under greedy
        device-argmax decoding — other slots ride the same verify dispatch
        as plain single-token decodes (column 0)."""
        return (a.req.sampler.kind == "greedy"
                and a.req.readback == "token")

    def _spec_cycle(self, bstate, active: Dict[int, "_Active"], results,
                    st: SchedulerStats):
        """One draft → verify → commit cycle across every active slot.

        Each eligible slot drafts up to K tokens against a COW block-table
        fork; ONE batched ``verify_paged`` dispatch scores every slot's
        pending token + drafts at per-row positions; the longest agreeing
        draft prefix plus the free bonus token is emitted and the fork is
        committed to exactly the consumed span — a full rejection rewinds
        by pure bookkeeping (zero KV copies: the drafted K/V sits past the
        committed position where nothing can read it).
        """
        backend = self.session.backend
        pg = bstate["paged"]
        drafter = self._ensure_drafter()
        k = self._spec.k
        width = k + 1
        slots = tuple(sorted(active))
        tokens = np.zeros((self.num_slots, width), np.int32)
        spans, drafts, forks = [], {}, {}
        disp0 = drafter.dispatches
        tr = self.tracer
        with tr.span("draft", track="scheduler", occupancy=len(slots)):
            for s in slots:
                a = active[s]
                tokens[s, 0] = a.last_tok[0, 0]
                d = np.zeros((0,), np.int32)
                if self._spec_eligible(a):
                    # never draft past the token budget: the final emission
                    # must stay the bonus/decode token so pos bookkeeping
                    # matches the autoregressive invariant exactly
                    cap = min(k, a.req.max_new_tokens - len(a.tokens) - 1)
                    if cap > 0:
                        d = np.asarray(
                            drafter.propose(s, self._realized(a), cap),
                            np.int32).reshape(-1)[:cap]
                if d.size:
                    forks[s] = pg.fork_slot(s)
                    drafts[s] = d
                    tokens[s, 1:1 + d.size] = d
                spans.append(1 + d.size)
        st.draft_dispatches += drafter.dispatches - disp0
        with tr.span("verify", track="scheduler", occupancy=len(slots),
                     cycle=st.cycles):
            bstate, out = backend.verify_paged(bstate, tokens, slots, spans)
        st.cycles += 1
        st.spec_cycles += 1
        st.verify_dispatches += 1
        st.occupancy_sum += len(slots)
        self._track_kv(bstate, st)
        with tr.span("readback", track="scheduler", overlapped=False):
            t0 = time.perf_counter()
            nxt = np.asarray(out.next_token, np.int32)   # (S, width)
            st.sync_readback_s += time.perf_counter() - t0
        for s in slots:
            a = active[s]
            d = drafts.get(s)
            if d is None:
                # plain decode riding the verify dispatch: column 0 IS the
                # ordinary decode step (same K/V write, same logits)
                st.tokens += 1
                st.spec_tokens += 1
                pg.pos[s] += 1
                done = self.session.step_row(
                    a, StepOutput(out.logits[s:s + 1, 0:1], nxt[s:s + 1, 0:1]))
            else:
                accepted = greedy_accept(d, nxt[s])
                emitted = 0
                done = False
                # emit the agreed prefix + the bonus token, stopping early
                # on stop-token/budget (later columns are then rejected)
                for j in range(accepted + 1):
                    st.tokens += 1
                    st.spec_tokens += 1
                    emitted += 1
                    done = self.session.step_row(
                        a, StepOutput(out.logits[s:s + 1, j:j + 1],
                                      nxt[s:s + 1, j:j + 1]))
                    if done:
                        break
                st.draft_tokens_proposed += int(d.size)
                st.draft_tokens_accepted += min(emitted, accepted)
                if emitted == accepted + 1:
                    st.bonus_tokens += 1
                # commit exactly the consumed inputs; everything past is
                # dropped by decref/pos-rewind — never a KV copy
                pg.commit_fork(s, forks[s], forks[s].pos0 + emitted)
                tr.instant("spec_commit", track=f"slot{s}",
                           proposed=int(d.size), accepted=accepted,
                           emitted=emitted)
            if done:
                results[a.req.request_id] = self.session.finish(a)
                bstate = backend.release_slot(bstate, s,
                                              tokens=self._realized(a))
                drafter.release(s)
                del active[s]
        return bstate

    # -- SLO-aware preemption (oversubscription survival) ----------------
    @staticmethod
    def _ewma(prev: Optional[float], x: float, alpha: float = 0.25) -> float:
        return x if prev is None else (1.0 - alpha) * prev + alpha * x

    def _preempt_kind(self, bstate, slot: int, a: _Active) -> str:
        """Restore-vs-recompute for THIS victim, from measured costs.

        Restore pays one host→device upload per **exclusive** block (the
        shared ones park by reference, both ways free).  Recompute pays a
        re-prefill of ``realized[:-1]`` — but the preempt-release inserts
        the victim's chain into the radix cache, so only the partial tail
        block past the last full-block boundary actually recomputes (if
        the chain survives eviction; the estimate is optimistic, which is
        the right bias — a wrong "recompute" pick still yields identical
        tokens, just slower).  Until both EWMAs have a sample, swap wins:
        it is the choice that produces the missing measurement.
        """
        can_swap = self.session.backend.capabilities.preemption
        if self.preemption == "swap":
            if not can_swap:
                raise ValueError(
                    f"backend {self.session.backend.capabilities.name!r} "
                    "cannot swap block chains (capabilities.preemption is "
                    "False); use preemption='recompute' or 'auto'")
            return "swap"
        if self.preemption == "recompute" or not can_swap:
            return "recompute"
        up, pf = self._ewma_upload_s_per_block, self._ewma_prefill_s_per_tok
        if up is None or pf is None:
            return "swap"
        pg = bstate["paged"]
        pos = int(pg.pos[slot])                  # KV covers [0, pos)
        exclusive = sum(1 for b in pg.chain(slot, pos)
                        if pg.pool.refcount[b] == 1)
        tail = pos - (pos // pg.block_size) * pg.block_size
        return "recompute" if max(tail, 1) * pf < exclusive * up else "swap"

    def _maybe_preempt(self, bstate, active: Dict[int, _Active],
                       prefilling: Dict[int, _Active], st: SchedulerStats):
        """Evict lowest-priority decoding slots while a strictly-higher-
        priority request waits and no slot is free.  Strictness is the
        anti-thrash rule: a preempted request can never re-preempt its own
        priority class, so no pair of requests can trade a slot forever.
        Mid-prefill slots are never victims — their KV is cheapest to
        finish, not to throw away."""
        backend = self.session.backend
        while active and len(active) + len(prefilling) >= self.num_slots:
            waiting = [r.priority for r in self._queue] \
                + [rec["a"].req.priority for rec in self._preempted]
            if not waiting:
                return bstate
            head = max(waiting)
            # victim: lowest priority; ties evict the youngest (most
            # recently started) so near-complete work survives
            vslot = min(active, key=lambda s: (active[s].req.priority,
                                               -active[s].t0))
            a = active[vslot]
            if a.req.priority >= head:
                return bstate
            kind = self._preempt_kind(bstate, vslot, a)
            with self.tracer.span("preempt", track="scheduler",
                                  slot=vslot, req=a.req.request_id,
                                  kind=kind, priority=a.req.priority,
                                  for_priority=head):
                if kind == "swap":
                    rec = {"kind": "swap", "a": a,
                           "swap": backend.swap_out_paged(bstate, vslot)}
                    st.preempt_swaps += 1
                    st.swap_blocks_host += len(rec["swap"]["chain"].host)
                    st.swap_blocks_retained += len(
                        rec["swap"]["chain"].retained)
                else:
                    # release THROUGH the radix cache: the chain stays
                    # warm, so the eventual re-prefill is mostly a hit
                    bstate = backend.release_slot(
                        bstate, vslot, tokens=self._realized(a))
                    rec = {"kind": "recompute", "a": a}
                    st.preempt_recomputes += 1
            if self._drafter is not None:
                self._drafter.release(vslot)
            st.preemptions += 1
            del active[vslot]
            self._preempted.append(rec)
        return bstate

    def _resume_one(self, bstate, slot: int, active: Dict[int, _Active],
                    prefilling: Dict[int, _Active], st: SchedulerStats):
        """Give the best waiting preempted request the freed ``slot``.

        Swap records restore byte-exactly (shared blocks re-bind, host
        blocks upload — timed into the upload EWMA) and go straight back
        to decoding.  Recompute records re-admit ``realized[:-1]`` as a
        fresh chunked prefill whose completed logits are DISCARDED
        (``resuming``): the token they would re-produce was already
        emitted before the preemption, and ``last_tok`` still holds the
        pending input, so decode resumes on the exact KV-position
        invariant (KV covers [0, len(realized)-1)).
        """
        backend = self.session.backend
        i = min(range(len(self._preempted)),
                key=lambda j: (-self._preempted[j]["a"].req.priority, j))
        rec = self._preempted.pop(i)
        a = rec["a"]
        with self.tracer.span("resume", track="scheduler", slot=slot,
                              req=a.req.request_id, kind=rec["kind"]):
            if rec["kind"] == "swap":
                uploads = len(rec["swap"]["chain"].host)
                t0 = time.perf_counter()
                backend.swap_in_paged(bstate, rec["swap"], slot)
                if uploads:
                    self._ewma_upload_s_per_block = self._ewma(
                        self._ewma_upload_s_per_block,
                        (time.perf_counter() - t0) / uploads)
                st.swap_ins += 1
                st.swap_upload_dispatches += uploads
                active[slot] = a
            else:
                realized = self._realized(a)
                info = backend.admit_paged(bstate, slot, realized[:-1])
                if info.cached:
                    st.prefix_hits += 1
                    st.prefix_hit_tokens += info.cached
                st.prompt_tokens += info.total
                a.resuming = True
                prefilling[slot] = a
        return bstate

    # -- paged KV + radix prefix cache + chunked prefill -----------------
    def _run_paged(self, st: SchedulerStats) -> Dict[str, ServeResult]:
        backend = self.session.backend
        caps = backend.capabilities
        caps.require("paged_kv", hint="use kv_layout='dense'")
        if self._spec is not None:
            caps.require("speculative",
                         hint="drop speculative= or use the model backend")
        if self._bstate is None:
            self._bstate = backend.alloc_slots_paged(
                self.num_slots, block_size=self.block_size,
                prefill_chunk=self.prefill_chunk,
                num_blocks=self.num_blocks, prefix_cache=self.prefix_cache,
                spec_slack=(self._spec.k + 1) if self._spec else 0)
        bstate = self._bstate
        pg = bstate["paged"]
        radix = bstate["radix"]
        if self.tracer.enabled:
            pg.attach_tracer(self.tracer)
            if radix is not None:
                radix.tracer = self.tracer
        cow0 = pg.cow_copies
        ev0 = radix.evictions if radix is not None else 0
        results: Dict[str, ServeResult] = {}
        active: Dict[int, _Active] = {}
        prefilling: Dict[int, _Active] = {}
        while (self._queue or self._future or self._preempted
               or active or prefilling):
            self._drain_arrivals()
            self._wait_for_arrival(
                busy=bool(active or prefilling or self._preempted))
            if self.preemption != "off":
                bstate = self._maybe_preempt(bstate, active, prefilling, st)
            # admission: radix match + block-table setup only (no compute);
            # preempted requests compete with fresh arrivals by priority
            # (resume wins ties — they already waited once)
            while ((self._queue or self._preempted)
                   and len(active) + len(prefilling) < self.num_slots):
                slot = min(s for s in range(self.num_slots)
                           if s not in active and s not in prefilling)
                qpri = max((r.priority for r in self._queue), default=None)
                ppri = max((rec["a"].req.priority
                            for rec in self._preempted), default=None)
                if ppri is not None and (qpri is None or ppri >= qpri):
                    bstate = self._resume_one(bstate, slot, active,
                                              prefilling, st)
                    continue
                req = self._pop_next()
                prompt = self._check_row(req)
                a = self.session.begin(req)
                self._book_admission(a, st)
                with self.tracer.span("admit", track="scheduler",
                                      req=req.request_id, slot=slot):
                    info = backend.admit_paged(bstate, slot, prompt)
                if info.cached:
                    st.prefix_hits += 1
                    st.prefix_hit_tokens += info.cached
                st.prompt_tokens += info.total
                prefilling[slot] = a
            # ONE prefill chunk per admitting slot, interleaved with the
            # decode cycle below — a long prompt admits over many cycles
            # without ever stalling the slots already decoding
            for slot in sorted(prefilling):
                meta = bstate["meta"][slot]
                cur0 = meta["cursor"]
                tc = time.perf_counter()
                with self.tracer.span("prefill_chunk", track=f"slot{slot}"):
                    out = backend.prefill_paged_chunk(bstate, slot)
                dt = time.perf_counter() - tc
                if meta["cursor"] > cur0:   # feeds the "auto" preempt pick
                    self._ewma_prefill_s_per_tok = self._ewma(
                        self._ewma_prefill_s_per_tok,
                        dt / (meta["cursor"] - cur0))
                st.prefill_chunks += 1
                if out is None:
                    continue
                a = prefilling.pop(slot)
                if a.resuming:
                    # recompute-resume: KV is rebuilt, but this "first
                    # token" was emitted before the preemption — discard
                    # the logits, go straight back to decoding last_tok
                    a.resuming = False
                    active[slot] = a
                    continue
                self.session.first(a, out)
                st.tokens += 1
                if a.done:
                    results[a.req.request_id] = self.session.finish(a)
                    bstate = backend.release_slot(bstate, slot,
                                                  tokens=self._realized(a))
                else:
                    active[slot] = a
            self._track_kv(bstate, st)
            if not active:
                continue
            if self._spec is not None:
                # draft/verify cycles are inherently synchronous: the
                # accept decision needs the verified tokens on the host
                # before the next span can be drafted
                bstate = self._spec_cycle(bstate, active, results, st)
                continue
            # a super-step holds the host for N cycles' worth of device
            # work, so anything needing per-cycle scheduling decisions
            # (mid-prefill chunks, scheduled arrivals, preemption checks,
            # admissions into free slots) keeps the per-cycle path — the
            # same states the async drain below stays synchronous for
            horizon = self._multi_horizon(active)
            if (horizon > 1 and self._multi_ok(active)
                    and not (prefilling or self._future or self._preempted
                             or (self._queue
                                 and (len(active) < self.num_slots
                                      or self.preemption != "off")))):
                with self._cycle_span(st, len(active), horizon=horizon,
                                      multi=True):
                    bstate, slots, out = self._issue_multi(
                        bstate, active, st, self._host_tokens(active),
                        horizon, self._stop_table(active))
                bstate = self._drain_multi(bstate, out, slots, active,
                                           results, st, horizon)
                continue
            # stay synchronous while prompts are mid-prefill (their next
            # chunk must not wait behind a deferred readback), while
            # scheduled arrivals or preempted requests are outstanding
            # (the drain loop would defer their admission/preemption
            # checks), or while a waiter could preempt a running slot
            sync = bool(prefilling or self._future or self._preempted
                        or (self._queue and (len(active) < self.num_slots
                                             or self.preemption != "off")))
            with self._cycle_span(st, len(active)):
                bstate, slots, out = self._issue_cycle(
                    bstate, active, st, self._host_tokens(active))
                if sync:
                    bstate = self._retire_cycle(out, slots, active, results,
                                                bstate, st, overlapped=False)
            if not sync:
                bstate = self._drain_async(bstate, out, slots, active,
                                           results, st)
        st.cow_copies = pg.cow_copies - cow0
        st.evictions = (radix.evictions - ev0) if radix is not None else 0
        self._bstate = bstate
        return results

    # -- sequential baseline (pre-batching behavior) ---------------------
    def _run_sequential(self, st: SchedulerStats) -> Dict[str, ServeResult]:
        results: Dict[str, ServeResult] = {}
        active: Dict[int, _Active] = {}
        while self._queue or self._future or active:
            self._drain_arrivals()
            self._wait_for_arrival(busy=bool(active))
            while self._queue and len(active) < self.num_slots:
                slot = next(i for i in range(self.num_slots)
                            if i not in active)
                a = self._start(self._pop_next(), st)
                if a.done:
                    results[a.req.request_id] = self.session.finish(a)
                else:
                    active[slot] = a
            # one decode DISPATCH per active slot per cycle (no batching)
            st.cycles += 1
            st.occupancy_sum += len(active)
            for slot in sorted(active):
                a = active[slot]
                st.tokens += 1
                if self.session.step(a):
                    results[a.req.request_id] = self.session.finish(a)
                    del active[slot]
        return results
