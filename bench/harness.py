"""One run of one cell: set up, warm up, serve the window, check, reduce.

The timed path is the program's own: ``Scheduler.run`` →
``InferenceSession`` → the ``model`` backend with ``kv_layout="paged"``,
whose jitted ``_extend_paged`` runs each prefill chunk and
``_decode_paged`` each decode cycle.  The harness adds only a session
subclass that stamps every token's emission on the host clock, and, in a
traced run, ``jax.profiler`` annotations around its calls into each
layer (wrappers on the backend instance, not changes to it).

Order of a run:
  1. weights from the seed (``bench.weights``, one jitted call), the
     program's backend and scheduler, then warm-up requests that compile
     every program the window runs;
  2. the window: ``seconds`` of traffic from ``bench.workload``; a
     request counts if it is sent in the window, and is served to its end
     even past it;
  3. the device's peak memory, then the program is freed (``serve``);
  4. the check (``judge``): a sample of the finished requests, drawn from
     the seed with the longest among them, is run through the plain
     float32 reference (``bench.reference``) on weights it makes again
     from the seed; each served token's logit must lie within the cell's
     limit of the reference's best.  The control goes through the same
     judgement, its own tokens in place of the program's.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import costs, trace_reduce, weights, workload
from bench.families import family, reference

from repro.models import build_model
from repro.serving import (InferenceSession, Scheduler, SchedulerConfig,
                           ServeRequest, create_backend)

ROOT = Path(__file__).resolve().parent


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + json.dumps(kv), flush=True)


# -- the program, as the configuration states it ----------------------------

def check_program_config(mcfg, cfg: Dict) -> None:
    """The program's model config must be the configuration file's, as
    its model family states it."""
    want = family(cfg).program_config(cfg)
    got = {k: getattr(mcfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise SystemExit(f"program config {mcfg.name} differs from the "
                         f"configuration file (got, want): {bad}")


def program_params(cfg: Dict, w: Dict, model, key) -> Dict:
    """The benchmark's weights in the program's parameter tree (the model
    family's ``program_params``), checked leaf by leaf against the tree
    the program's own init would make."""
    p = family(cfg).program_params(w)
    want = jax.eval_shape(model.init_params, key)
    if jax.tree.structure(want) != jax.tree.structure(p):
        raise SystemExit(f"program parameter tree differs: "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(p)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise SystemExit(f"program leaf {a.shape} {a.dtype} vs "
                             f"{b.shape} {b.dtype}")
    return p


def require_chips(chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")


class CompileClock:
    """Compile seconds and count as JAX reports them (copied from
    ``chip_smoke.py``); ``lap()`` returns what accrued since the last."""

    def __init__(self) -> None:
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        self._last = (0.0, 0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def lap(self) -> Dict:
        now = (self.seconds, self.compiles, self.hits)
        d = [a - b for a, b in zip(now, self._last)]
        self._last = now
        return {"compile_s": d[0], "compiles": d[1], "cache_hits": d[2]}


# -- what a run records -------------------------------------------------------

class Record:
    """Per-request host timestamps and results."""

    def __init__(self) -> None:
        self.emits: Dict[str, List[float]] = {}
        self.results: Dict = {}
        self.counted: List[str] = []


class TimedSession(InferenceSession):
    """The program's session; every token's emission is stamped on the
    host clock (``perf_counter``) as the scheduler hands it out."""

    def __init__(self, backend, record: Record, annotate: bool) -> None:
        super().__init__(backend)
        self.record = record
        self.annotate = annotate

    def _emit(self, a, tok) -> None:
        if self.annotate:
            with jax.profiler.TraceAnnotation("bench.token_emit"):
                super()._emit(a, tok)
        else:
            super()._emit(a, tok)
        self.record.emits.setdefault(a.req.request_id, []).append(
            time.perf_counter())

    def finish(self, a):
        result = super().finish(a)
        self.record.results[result.request_id] = result
        return result


class Tracing:
    """The profiler over the last ``seconds`` of the window, with the host
    records the device metrics need: per decode cycle the positions each
    active row attends over, per prefill chunk its start and length.

    The device is drained (``sync``) just before the profiler starts and
    just before it stops, so no program in the traced window is cut off
    at either end.  The trace lasts ``seconds`` from its start, also
    where a host stall delayed the first poll after ``start_at``, and is
    cut short only where the run ends.  Stopping writes the trace out,
    which holds the host for a while (``stall_s``): that is why the trace
    covers the end of the window and not its start."""

    def __init__(self, seconds: float, sync: Callable[[], None]) -> None:
        self.seconds = seconds
        self.sync = sync
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t_start = self.t_stop = None
        self.start_at = self.stop_at = float("inf")
        self.decode: List = []
        self.prefill: List = []
        self.stall_s: Dict[str, float] = {}
        self._window = None

    def arm(self, t0: float, window: float) -> None:
        self.stop_at = t0 + window
        self.start_at = self.stop_at - min(self.seconds, window)

    def poll(self) -> None:
        now = time.perf_counter()
        if self.t_start is None and now >= self.start_at:
            self.sync()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._window.__enter__()
            self.t_start = time.perf_counter()
            self.stop_at = max(self.stop_at, self.t_start + self.seconds)
            self.stall_s["start"] = self.t_start - now
        elif (self.t_start is not None and self.t_stop is None
              and now >= self.stop_at):
            self.stop()

    def stop(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        self.sync()
        self._window.__exit__(None, None, None)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.stall_s["stop"] = time.perf_counter() - self.t_stop

    def inside(self, t: float) -> bool:
        return (self.t_start is not None and self.t_start <= t
                and (self.t_stop is None or t < self.t_stop))

    def wrap(self, backend, sched) -> None:
        """Annotate the calls into each layer, on these instances only."""
        decode, prefill = backend.decode_batch, backend.prefill_paged_chunk
        run = sched.run

        def decode_batch(bstate, tokens, slots):
            self.poll()
            pos = bstate["paged"].pos
            ctx = [int(pos[s]) + 1 for s in slots]
            with jax.profiler.TraceAnnotation("bench.decode_batch"):
                out = decode(bstate, tokens, slots)
            if self.inside(time.perf_counter()):
                self.decode.append(ctx)
            return out

        def prefill_paged_chunk(bstate, slot):
            self.poll()
            meta = bstate["meta"][slot]
            cur, chunk = meta["cursor"], bstate["chunk"]
            valid = min(chunk or len(meta["prompt"]),
                        len(meta["prompt"]) - cur)
            with jax.profiler.TraceAnnotation("bench.prefill_chunk"):
                out = prefill(bstate, slot)
            if self.inside(time.perf_counter()):
                self.prefill.append((cur, valid))
            return out

        def scheduler_run():
            with jax.profiler.TraceAnnotation(trace_reduce.OUTER):
                return run()

        backend.decode_batch = decode_batch
        backend.prefill_paged_chunk = prefill_paged_chunk
        sched.run = scheduler_run

    def reduce(self) -> Optional[Dict]:
        self.stop()
        self.sync = None          # it holds the scheduler: let it be freed
        files = sorted(Path(self.dir).rglob("*.xplane.pb"))
        read = trace_reduce.read_xplane(str(files[-1])) if files else None
        shutil.rmtree(self.dir, ignore_errors=True)
        if read is None or read[2] is None or not read[0]:
            return None
        out = trace_reduce.reduce(*read)
        out["host_window_s"] = self.t_stop - self.t_start
        return out


# -- the window ---------------------------------------------------------------

def device_arrays(root) -> List[jax.Array]:
    """Every live device array reachable from ``root`` through dicts,
    lists, tuples and object attributes."""
    out, seen, todo = [], set(), [root]
    while todo:
        x = todo.pop()
        if id(x) in seen:
            continue
        seen.add(id(x))
        if isinstance(x, jax.Array):
            if not x.is_deleted():
                out.append(x)
        elif isinstance(x, dict):
            todo.extend(x.values())
        elif isinstance(x, (list, tuple)):
            todo.extend(x)
        elif hasattr(x, "__dict__") and not isinstance(
                x, (type, types.ModuleType, types.FunctionType)):
            todo.extend(vars(x).values())
    return out


def drain(bstate) -> None:
    """Wait for every device array of the scheduler's batch state, so
    that no program that writes the cache is still running."""
    jax.block_until_ready(device_arrays(bstate))


def serve_request(q: workload.Req) -> ServeRequest:
    return ServeRequest(prompt=q.prompt.reshape(1, -1),
                        max_new_tokens=q.max_new, request_id=q.rid)


def closed_window(sched, rec: Record, stream, t0: float, seconds: float,
                  tracing: Optional[Tracing]) -> List[workload.Req]:
    """One client: each request is sent when the last one finished, for
    as long as the window is open; the last one is served to its end."""
    sent = []
    t_end = t0 + seconds
    for q in stream:
        now = time.perf_counter()
        if now >= t_end:
            break
        rec.counted.append(q.rid)
        sent.append(q)
        sched.submit(serve_request(q))
        sched.run()
        if tracing is not None:
            tracing.poll()
    return sent


# -- metrics ------------------------------------------------------------------

def load_reader(name: str) -> Callable:
    path = ROOT / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(specs: List[Dict], ctx: Dict) -> Dict:
    out = {}
    for m in specs:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def device_info(chips: int) -> Dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What a window produced, with the program freed: the result line
    without its check, and the finished requests with their tokens."""
    line: Dict
    done: List[workload.Req]
    tokens: Dict[str, np.ndarray]
    unfinished: int


def serve(*, cfg: Dict, traffic: Dict, mcfg, seed: int, seconds: float,
          trace: bool, metrics: List[Dict], chips: int = 1,
          t_process: float, require_tpu: bool = True,
          dtype: str = "bfloat16") -> Served:
    """Set up, warm up and serve one cell's window, read its metrics
    (``metrics``: the cell's entries for this mode, end-to-end without
    trace, per-layer with it), and free the program."""
    t_serve = time.perf_counter()
    peak = None
    if require_tpu:
        require_chips(chips)
        peak = costs.peaks(jax.devices()[0].device_kind)
    workload.check(traffic)
    check_program_config(mcfg, cfg)
    clock = CompileClock()
    vocab = int(cfg["vocab_size"])

    w = weights.make_weights(cfg, seed, dtype)
    model = build_model(mcfg)
    params = program_params(cfg, w, model, jax.random.PRNGKey(0))
    backend = create_backend("model", model, params,
                             batch=traffic["scheduler"]["num_slots"],
                             max_len=traffic["max_len"])
    t_built = time.perf_counter()
    rec = Record()
    session = TimedSession(backend, rec, annotate=trace)
    sched = Scheduler(session, config=SchedulerConfig(
        continuous=True, async_readback=True, **traffic["scheduler"]))
    tracing = Tracing(float(traffic["trace_seconds"]),
                      sync=lambda: drain(sched._bstate)) if trace else None
    for round_ in workload.warmup(traffic, vocab):
        for q in round_:
            sched.submit(serve_request(q))
        sched.run()
    drain(sched._bstate)
    report("setup", **clock.lap(), params=family(cfg).param_count(cfg),
           start_s=t_serve - t_process, build_s=t_built - t_serve,
           warm_up_s=time.perf_counter() - t_built)

    if tracing is not None:
        tracing.wrap(backend, sched)
    stats0 = dataclasses.replace(backend.dispatch_stats())
    t0 = time.perf_counter()
    if tracing is not None:
        tracing.arm(t0, seconds)
        tracing.poll()
    sent = closed_window(sched, rec, workload.closed_loop(
        traffic, vocab, seed), t0, seconds, tracing)
    t_done = time.perf_counter()
    stats1 = backend.dispatch_stats()
    window_compiles = clock.lap()
    device = device_info(chips)
    reduced = tracing.reduce() if tracing is not None else None

    asked = {q.rid: q.max_new for q in sent}
    failed = [rid for rid in rec.counted if rid not in rec.results
              or rec.results[rid].n_new != asked[rid]]
    ctx = {"cfg": cfg, "traffic": traffic, "seconds": seconds, "t0": t0,
           "setup_s": t0 - t_process,
           "emits": {rid: rec.emits.get(rid, []) for rid in rec.counted},
           "dispatch": {"enqueue_s": stats1.enqueue_s - stats0.enqueue_s,
                        "steps": stats1.steps - stats0.steps},
           "trace": reduced, "tracing": tracing, "peak": peak}
    values = read_metrics(metrics, ctx)
    report("window", seconds=seconds, drain_s=t_done - (t0 + seconds),
           attempted=len(rec.counted), failed=len(failed),
           compiles_in_window=window_compiles["compiles"],
           compile_s_in_window=window_compiles["compile_s"],
           trace_stall_s=tracing.stall_s if tracing else None,
           programs=reduced["programs"] if reduced else None)

    line = {"attempted": len(sent), "failed": len(failed),
            "metrics": values, "device": device}
    if reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    counted = set(rec.counted)
    served = {rid: np.asarray(r.tokens[0], np.int32)
              for rid, r in rec.results.items() if rid in counted}
    done = [q for q in sent if q.rid in served]
    del sched, session, backend, params, model, w, rec, tracing, ctx
    gc.collect()
    return Served(line, done, served, len(failed))


def judge(s: Served, *, cfg: Dict, traffic: Dict, seed: int, dtype: str,
          limit: float, quant: Optional[str] = None) -> Dict:
    """Whether a served window is correct: the widest gap of a served
    token below the reference's best logit, over the sample, within
    ``limit``, and every counted request finished with the tokens it asked
    for.  With ``quant`` the reference in that lower precision stands in
    for the program (the control): at each position of the same sample
    its own first choice is judged.  Returns ``correct`` and ``checks``,
    each number beside its limit."""
    t = time.perf_counter()
    picked = workload.sample(s.done, s.tokens, seed,
                             int(traffic["check_tokens"]))
    gap, rows = None, 0
    if picked:
        w = weights.make_weights(cfg, seed, dtype)
        ref = reference(cfg)
        gaps = [ref.token_gaps(w, cfg, q.prompt, s.tokens[q.rid],
                              quant) for q in picked]
        gap = float(max(g.max() for g in gaps))
        rows = int(sum(len(g) for g in gaps))
    report("check", quant=quant, logit_gap=gap, limit=limit, tokens=rows,
           requests=len(picked), seconds=time.perf_counter() - t)
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "unfinished": {"value": s.unfinished, "limit": 0}}
    correct = gap is not None and gap <= limit and not s.unfinished
    return {"correct": bool(correct), "checks": checks}


def run(*, limit: float, **kw) -> Dict:
    """One benchmark run: ``serve`` then ``judge``; returns the result
    line's object, the checks last, and prints them as the last lines of
    standard error."""
    s = serve(**kw)
    v = judge(s, cfg=kw["cfg"], traffic=kw["traffic"], seed=kw["seed"],
              dtype=kw.get("dtype", "bfloat16"), limit=limit)
    for k, c in v["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return {"correct": v["correct"], **s.line, "checks": v["checks"]}
