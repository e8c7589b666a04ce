"""Serving launcher — the paper's benchmark protocol as a CLI, on the
``ExecutionBackend`` registry + ``InferenceSession`` API.

    PYTHONPATH=src python -m repro.launch.serve --model bench-0.5b \
        --modes F0,F3,FULL,model,ondevice --tokens 50 --runs 10

Every mode routes through the same backend protocol, so each row carries
the uniform dispatch accounting (dispatches/step + the Table-20-style
arg-prep / enqueue / sync phase split).

Continuous batching: ``--num-slots N`` additionally drives each mode
through the slot ``Scheduler`` with ``--requests`` overlapping requests
(default 2×N), one batched decode dispatch per cycle; ``--no-continuous``
runs the same workload on the per-slot sequential baseline instead, so the
two rows side by side show the dispatch-amortization the scheduler buys.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", "--config", dest="model", default="bench-0.5b",
                    help="bench-0.5b | bench-1.5b | any registry arch at its "
                         "published config, including the recurrent "
                         "families mamba2-1.3b / recurrentgemma-9b")
    ap.add_argument("--smoke", action="store_true",
                    help="registry archs: use the reduced (CPU-sized) config")
    ap.add_argument("--modes", default="F0,F3,FULL,model")
    ap.add_argument("--tokens", type=int, default=50)
    ap.add_argument("--prompt-len", type=int, default=5)
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--readback", default="token", choices=["token", "logits"])
    ap.add_argument("--sampler", default="greedy",
                    choices=["greedy", "temperature", "topk"])
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--num-slots", type=int, default=0,
                    help="also run the slot scheduler with N slots")
    ap.add_argument("--continuous", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="batched decode cycles (--no-continuous: one "
                         "decode dispatch per slot per cycle)")
    ap.add_argument("--requests", type=int, default=0,
                    help="overlapping requests to schedule (default 2×slots)")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="scheduler KV layout: dense slot rows or the "
                         "paged block pool with radix prefix caching")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged only: split prompts into N-token prefill "
                         "chunks interleaved with decode cycles")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="paged only: radix prefix cache (warm hits skip "
                         "prefill dispatches for the shared span)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged only: KV block size in tokens")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged only: block-pool size (default: every slot "
                         "full + two spare prefix chains)")
    ap.add_argument("--speculative", default=None,
                    help="paged only: draft/verify decoding ('ngram')")
    ap.add_argument("--decode-horizon", type=int, default=1,
                    help="multi-step decode capture: submit up to N decode "
                         "cycles as ONE host super-step (graph backends, "
                         "greedy token readback; 1 = per-cycle path)")
    ap.add_argument("--out", default=None, help="write JSON rows here")
    ap.add_argument("--trace-out", default=None,
                    help="capture a repro.obs dispatch trace of the "
                         "scheduler runs and write Perfetto trace-event "
                         "JSON here (open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the serving metrics registry (p50/p99 "
                         "TTFT/TPOT/queue-wait, dispatch counters) here")
    args = ap.parse_args()

    from repro.configs import REGISTRY, get_config, get_smoke_config
    from repro.configs.bench import BENCH_MODELS
    from repro.models import build_model
    from repro.obs import MetricsRegistry, Tracer, write_metrics, write_trace
    from repro.serving import (CapabilityError, InferenceSession,
                               SamplerConfig, Scheduler, SchedulerConfig,
                               ServeRequest, available_backends,
                               create_backend)

    if args.model in BENCH_MODELS:
        cfg = BENCH_MODELS[args.model]
    elif args.model in REGISTRY:
        cfg = (get_smoke_config(args.model) if args.smoke
               else get_config(args.model))
    else:
        raise SystemExit(f"unknown model {args.model}")

    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size,
                          size=(1, args.prompt_len)).astype(np.int32)
    max_len = args.prompt_len + args.tokens + 8
    sampler = SamplerConfig(args.sampler, temperature=args.temperature,
                            top_k=args.top_k)
    tracing = args.trace_out or args.metrics_out
    tracer = Tracer() if args.trace_out else None
    metrics = MetricsRegistry() if args.metrics_out else None
    if tracing and args.num_slots <= 0:
        raise SystemExit("--trace-out/--metrics-out record the scheduler "
                         "path; add --num-slots N")

    rows = []
    for mode in args.modes.split(","):
        if mode not in available_backends():
            raise SystemExit(f"unknown backend {mode!r}; "
                             f"available: {available_backends()}")
        backend = create_backend(mode, model, params, batch=1,
                                 max_len=max_len)
        session = InferenceSession(backend)
        rep = session.benchmark(prompt, args.tokens, n_runs=args.runs,
                                warmup=args.warmup, sampler=sampler,
                                readback=args.readback)
        row = rep.row()
        print(f"[serve] {row}")
        caps = backend.capabilities
        if args.num_slots > 0:
            # fail loudly, naming the missing capability — a silently
            # skipped scheduler run is how bad flag combos hide.  The
            # uniform capabilities.require() error already names the
            # backend, the feature, and state_kind; wrap it in a
            # SystemExit carrying the offending flag.
            try:
                if args.kv_layout == "paged":
                    caps.require("paged_kv", hint="use --kv-layout dense")
                if args.speculative:
                    caps.require("speculative", hint="drop --speculative")
                if args.decode_horizon > 1:
                    caps.require("decode_multi",
                                 hint="drop --decode-horizon")
            except CapabilityError as e:
                raise SystemExit(f"family {cfg.family!r}: {e}") from e
            n_req = args.requests or 2 * args.num_slots
            sched = Scheduler(session, config=SchedulerConfig(
                num_slots=args.num_slots,
                continuous=args.continuous,
                kv_layout=args.kv_layout,
                prefill_chunk=args.prefill_chunk,
                prefix_cache=args.prefix_cache,
                block_size=args.block_size,
                num_blocks=args.num_blocks,
                speculative=args.speculative,
                decode_horizon=args.decode_horizon,
                tracer=tracer, metrics=metrics))
            for i in range(n_req):
                p = rng.integers(0, cfg.vocab_size,
                                 size=(1, args.prompt_len)).astype(np.int32)
                sched.submit(ServeRequest(prompt=p,
                                          max_new_tokens=args.tokens,
                                          sampler=sampler,
                                          readback=args.readback))
            sched.run()
            row["scheduler"] = sched.last_stats.row()
            print(f"[sched] {row['scheduler']}")
        rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    if args.trace_out:
        print(f"[obs] trace → {write_trace(tracer, args.trace_out)} "
              f"({len(tracer)} events, {tracer.dropped} dropped; open at "
              "ui.perfetto.dev)")
    if args.metrics_out:
        print(f"[obs] metrics → {write_metrics(metrics, args.metrics_out)}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
