"""Serve Qwen2.5-0.5B at its published widths on a TPU and check the output.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the dist pipeline over four chips

One chip: build Qwen2.5-0.5B from ``configs/qwen25_05b.py`` (bf16, all 24
layers) with random weights from ``--seed``; serve 8 requests of hundreds
of prompt tokens through the paged continuous-batching ``Scheduler`` on the
``model`` backend, four slots with overlapping admission; check every
logit row it sampled from against ``transformer.forward`` on the request's
realized sequence.  Then serve one short request through the ``F3`` graph
backend on the Pallas kernels (compiled by Mosaic) and compare its first
logits with the same backend on XLA's fused ops.

Four chips: serve the same requests through the ``dist`` backend (four
pipeline stages of six layers, paged KV, one stage per chip) and through
``model`` on one chip, and compare their logits.

Compile seconds, wall times and device memory go to the earlier lines.
The last line is one JSON object naming the device.  The script exits
non-zero, with no JSON line, unless JAX's first device is a TPU, and when
any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import opgraph  # noqa: E402
from repro.kernels.common import use_interpret  # noqa: E402
from repro.models import build_model, transformer  # noqa: E402
from repro.serving import (InferenceSession, Scheduler,  # noqa: E402
                           SchedulerConfig, ServeRequest, create_backend)

MODEL = "qwen2.5-0.5b"
NUM_SLOTS, BLOCK_SIZE, PREFILL_CHUNK, MAX_LEN = 4, 16, 128, 512
PROMPT_LEN = (160, 417)   # 2-4 prefill chunks, 10-26 KV blocks
NEW_TOKENS = (24, 41)     # staggered finishes keep admission overlapping
STAGES = 4

# Every comparison is of one float32 logit row (vocab 151,936) at a time:
# rel = ||got - ref||_2 / ||ref||_2, and the largest rel must stay under
# the limit.  A wrong position, mask or KV block gives rel of order 1.
#   SERVE_TOL: bf16 weights and activations through 24 layers; the paged
#     path attends in 128-token chunks and one token at a time, the
#     reference over the whole sequence at once, so bf16 roundings differ.
#   KERNEL_TOL: the Pallas kernels accumulate in float32 like XLA's fused
#     ops, but in other tile orders, and round to bf16 at each op's output.
#   DIST_TOL: dist runs the same per-layer code as model, split over
#     stages; only the gather/scatter around each stage differs.
SERVE_TOL = 0.05
KERNEL_TOL = 0.02
DIST_TOL = 0.02


class LogitTap(InferenceSession):
    """An InferenceSession that keeps, per request, every logit row it
    samples from (the scheduler hands each request its own row)."""

    def __init__(self, backend) -> None:
        super().__init__(backend)
        self.rows = {}

    def _select_token(self, a, out):
        # a greedy row's logits stay on the device until ``logits()``
        self.rows.setdefault(a.req.request_id, []).append(out.logits)
        return super()._select_token(a, out)

    def logits(self, request_id: str) -> np.ndarray:
        return np.stack([np.asarray(r, np.float32).reshape(-1)
                         for r in self.rows[request_id]])


class CompileClock:
    """Sums XLA compile seconds and persistent-cache hits as JAX reports
    them; ``lap()`` returns what accrued since the previous lap."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._last = (0.0, 0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self) -> dict:
        now = (self.seconds, self.compiles, self.cache_hits)
        d = [a - b for a, b in zip(now, self._last)]
        self._last = now
        return {"compile_s": round(d[0], 3), "compiles": d[1],
                "cache_hits": d[2]}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + json.dumps(kv), flush=True)


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-row relative L2 error of ``got`` against ``ref``."""
    num = np.linalg.norm(got - ref, axis=-1)
    return float(np.max(num / np.linalg.norm(ref, axis=-1)))


def make_requests(cfg, seed: int, n: int, tag: str):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(*PROMPT_LEN))
        prompt = rng.integers(0, cfg.vocab_size, (1, plen)).astype(np.int32)
        reqs.append(ServeRequest(prompt=prompt,
                                 max_new_tokens=int(rng.integers(*NEW_TOKENS)),
                                 request_id=f"{tag}{i}"))
    return reqs


def serve(backend, reqs):
    """Serve ``reqs`` through the paged scheduler; every request must
    finish with the token count it asked for."""
    session = LogitTap(backend)
    sched = Scheduler(session, config=SchedulerConfig(
        num_slots=NUM_SLOTS, kv_layout="paged", prefill_chunk=PREFILL_CHUNK,
        block_size=BLOCK_SIZE))
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    results = sched.run()
    wall = time.perf_counter() - t0
    for r in reqs:
        n = results[r.request_id].tokens.shape[1]
        check(n == r.max_new_tokens,
              f"{r.request_id} emitted {n} of {r.max_new_tokens} tokens")
        rows = session.logits(r.request_id)
        check(rows.shape[0] == n, f"{r.request_id}: {rows.shape[0]} logit "
              f"rows for {n} tokens")
        check(bool(np.isfinite(rows).all()),
              f"{r.request_id}: non-finite logits")
    return session, results, sched.last_stats, wall


def serve_warm(backend, cfg, seed: int, n: int, tag: str, clock):
    """One warm-up run (compiles every serving shape), then the measured
    run of ``n`` requests."""
    serve(backend, make_requests(cfg, seed + 1, 2, f"{tag}warm"))
    warm = clock.lap()
    reqs = make_requests(cfg, seed, n, tag)
    session, results, st, wall = serve(backend, reqs)
    report(f"serve/{backend.capabilities.name}", warmup=warm,
           run=clock.lap(), requests=n, tokens=st.tokens,
           prefill_chunks=st.prefill_chunks, decode_cycles=st.cycles,
           mean_occupancy=round(st.mean_occupancy, 3),
           wall_s=round(wall, 3), tok_s=round(st.aggregate_tok_per_s, 2),
           ttft_p50_ms=round(st.ttft_p50_ms, 2),
           tpot_p50_ms=round(st.tpot_p50_ms, 2))
    return reqs, session, results


def check_against_forward(model, params, reqs, session, results, clock):
    """Each request's sampled logits against one full forward pass over
    its realized sequence (prompt + every emitted token but the last),
    padded to ``MAX_LEN`` so one compiled program serves all requests;
    causal attention keeps the padding out of every compared row."""
    cfg = model.cfg
    fwd = jax.jit(lambda p, t: transformer.forward(p, cfg, t)[0])
    worst = 0.0
    for r in reqs:
        prompt = r.prompt[0]
        seq = np.concatenate([prompt, results[r.request_id].tokens[0, :-1]])
        buf = np.zeros((1, MAX_LEN), np.int32)
        buf[0, :len(seq)] = seq
        ref = np.asarray(fwd(params, buf)[0, len(prompt) - 1:len(seq)],
                         np.float32)
        worst = max(worst, rel_err(session.logits(r.request_id), ref))
    report("check/forward", rel_err=worst, tol=SERVE_TOL,
           rows=sum(len(session.rows[r.request_id]) for r in reqs),
           **clock.lap())
    check(worst <= SERVE_TOL, f"scheduled logits vs transformer.forward: "
          f"rel {worst} > {SERVE_TOL}")


def kernel_phase(model, params, seed: int, clock) -> None:
    """One short request through ``F3`` on the Pallas kernels, then on
    XLA's fused ops; their first-step logits must agree."""
    cfg = model.cfg
    rng = np.random.default_rng(seed + 2)
    prompt = rng.integers(0, cfg.vocab_size, (1, 16)).astype(np.int32)
    first = {}
    for fused in ("pallas", "xla"):
        opgraph.set_fused_backend(fused)
        session = LogitTap(create_backend("F3", model, params, batch=1,
                                          max_len=64))
        t0 = time.perf_counter()
        res = session.run(ServeRequest(prompt=prompt, max_new_tokens=2,
                                       request_id=f"f3-{fused}"))
        wall = time.perf_counter() - t0
        first[fused] = session.logits(res.request_id)[:1]
        check(bool(np.isfinite(first[fused]).all()),
              f"F3/{fused}: non-finite logits")
        report(f"kernels/F3-{fused}", wall_s=round(wall, 3), **clock.lap())
    opgraph.set_fused_backend("xla")
    err = rel_err(first["pallas"], first["xla"])
    report("check/kernels", rel_err=err, tol=KERNEL_TOL)
    check(err <= KERNEL_TOL, f"F3 Pallas vs XLA fused ops: rel {err} > "
          f"{KERNEL_TOL}")


def check_stage_placement(backend) -> None:
    """Each pipeline stage's layer slice of every block weight must sit
    on its own device, the four stages on four different devices."""
    per_stage = backend.cfg.num_layers // backend.stages
    for leaf in jax.tree.leaves(backend.params["blocks"]):
        owner = {}
        for shard in leaf.addressable_shards:
            sl = shard.index[0]
            check(sl.stop - sl.start == per_stage,
                  f"stage shard of {sl.stop - sl.start} layers")
            owner[sl.start // per_stage] = shard.device
        check(sorted(owner) == list(range(backend.stages))
              and len(set(owner.values())) == backend.stages,
              f"stage placement {owner}")


def dist_phase(model, params, seed: int, clock) -> None:
    """The dist pipeline (``STAGES`` stages, paged) against ``model`` on
    one device, on the same requests.  Logits are compared up to and
    including each request's first differing token, past which the two
    runs condition on different sequences."""
    cfg = model.cfg
    out = {}
    for mode in ("model", "dist"):
        kw = {"stages": STAGES} if mode == "dist" else {}
        backend = create_backend(mode, model, params, batch=1,
                                 max_len=MAX_LEN, **kw)
        if mode == "dist":
            check(backend.stages == STAGES, f"{backend.stages} stages")
            check_stage_placement(backend)
        out[mode] = serve_warm(backend, cfg, seed, NUM_SLOTS, mode, clock)
    worst, rows = 0.0, 0
    for rm, rd in zip(out["model"][0], out["dist"][0]):
        tm = out["model"][2][rm.request_id].tokens[0]
        td = out["dist"][2][rd.request_id].tokens[0]
        diff = np.nonzero(tm != td)[0]
        n = int(diff[0]) + 1 if len(diff) else len(tm)
        lm = out["model"][1].logits(rm.request_id)[:n]
        ld = out["dist"][1].logits(rd.request_id)[:n]
        worst, rows = max(worst, rel_err(ld, lm)), rows + n
    report("check/dist", rel_err=worst, tol=DIST_TOL, rows=rows)
    check(worst <= DIST_TOL, f"dist vs model logits: rel {worst} > "
          f"{DIST_TOL}")


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the dist pipeline over four chips and its "
                         "one-chip comparison")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 "nothing was run")
    if args.four_chips and len(devices) < STAGES:
        sys.exit(f"chip_smoke: --four-chips needs {STAGES} chips, JAX "
                 f"found {len(devices)}")
    clock = CompileClock()
    cfg = get_config(MODEL)
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(args.seed)))
    report("init", model=cfg.name, dtype=cfg.dtype, layers=cfg.num_layers,
           d_model=cfg.d_model, params=cfg.param_count(),
           cache_dir=jax.config.jax_compilation_cache_dir, **clock.lap())

    if args.four_chips:
        dist_phase(model, params, args.seed, clock)
    else:
        backend = create_backend("model", model, params, batch=1,
                                 max_len=MAX_LEN)
        reqs, session, results = serve_warm(backend, cfg, args.seed, 8,
                                            "req", clock)
        check_against_forward(model, params, reqs, session, results, clock)
        check(not use_interpret(), "Pallas kernels would be interpreted")
        kernel_phase(model, params, args.seed, clock)
    report("memory", **memory(dev))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
