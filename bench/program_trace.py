"""What the program's own spans and scopes let a profiler trace say.

``trace_reduce`` sees the device and the benchmark's ``bench.*``
annotations; its numbers are left as they are.  The program itself puts
two more things on the trace (``repro.obs``):

* host spans named ``repro.<span>`` — ``repro.decode_cycle``,
  ``repro.dispatch:decode_batch`` and, inside a dispatch,
  ``repro.prepare``, ``repro.upload``, ``repro.enqueue``,
  ``repro.adopt``; ``repro.readback``, ``repro.sample_emit``,
  ``repro.admit``, ``repro.prefill_chunk`` around the scheduler's steps;
* named scopes in the step programs (``kv_gather``, ``attn``, ``mlp``,
  ``unembed``, ``kv_write``), which ``repro.obs.scopes.op_scopes`` maps
  to instruction names from the compiled programs' text
  (``ModelBackend.compiled_programs``).

``reduce`` turns them, with the device spans of ``trace_reduce``, into
per-program device seconds by scope, device-idle seconds inside each
program span, and idle gaps labelled by the innermost program span
covering most of each (the ``bench.*`` label where no program span
covers a gap).  The functions after it are the arithmetic of four
per-layer metrics over that and over the tracer's own ring buffer.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, Iterable, List, Optional, Sequence

from bench import costs, trace_reduce as tr
from bench.families import family
from bench.trace_reduce import Interval, Span

PREFIX = "repro."
DECODE = "jit__decode_paged"
UNSCOPED = "unscoped"


def read_spans(path: str) -> List[Span]:
    """The program's host spans (``repro.*``) in an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events if e.name.startswith(PREFIX)]
    return out


def scope_seconds(devices: Sequence[Dict[str, List[Span]]],
                  scopes: Dict[str, Dict[str, str]], window: Interval
                  ) -> Dict[str, Dict[str, float]]:
    """Per program, device seconds by scope of the ops of its runs that
    lie wholly in the window (the runs ``trace_reduce`` times); ops whose
    instruction has no scope count as ``unscoped``.  Ops that only hold
    others (a loop's ``while``) are left out, as in ``trace_reduce``'s
    breakdown; summed over chips."""
    lo, hi = window
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for dev in devices:
        runs = sorted((p.start, p.end, tr.base_name(p.name))
                      for p in dev["programs"]
                      if p.start >= lo and p.end <= hi)
        starts = [r[0] for r in runs]
        for s in dev["ops"]:
            label, opcode = tr.op_label(s.name)
            i = bisect.bisect_right(starts, s.start) - 1
            if opcode in tr.CONTAINERS or i < 0 or s.start >= runs[i][1]:
                continue
            prog = runs[i][2]
            inst = label.split(" ", 1)[0]
            out[prog][scopes.get(prog, {}).get(inst, UNSCOPED)] += \
                s.end - s.start
    return {p: dict(v) for p, v in out.items()}


def _idle(dev: Dict[str, List[Span]], window: Interval) -> List[Interval]:
    lo, hi = window
    return tr.complement(tr.union(tr.clip(((s.start, s.end)
                                           for s in dev["ops"]), lo, hi)),
                         lo, hi)


def idle_within(devices: Sequence[Dict[str, List[Span]]],
                spans: Iterable[Span], window: Interval
                ) -> Dict[str, float]:
    """Per span name, device-idle seconds of the window that fall inside
    spans of that name (nested spans of one name count once); averaged
    over chips, as ``trace_reduce``'s busy time is."""
    by_name: Dict[str, List[Interval]] = collections.defaultdict(list)
    for s in spans:
        by_name[s.name].append((s.start, s.end))
    merged = {n: tr.union(v) for n, v in by_name.items()}
    out: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        gaps = _idle(dev, window)
        for name, iv in merged.items():
            ends = [b for _, b in iv]
            out[name] += sum(tr.overlap(iv, ends, a, b)
                             for a, b in gaps) / len(devices)
    return dict(out)


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Span],
               annotations: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds per label: each gap goes to the program span that
    covers most of it, the innermost (shortest) where several cover as
    much, and to ``trace_reduce.label_gaps``' ``bench.*`` label where no
    program span touches it."""
    order = sorted(spans, key=lambda s: s.start)
    out: Dict[str, float] = collections.defaultdict(float)
    rest: List[Interval] = []
    active: List[Span] = []
    i = 0
    for lo, hi in sorted(gaps):
        while i < len(order) and order[i].start < hi:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > lo]
        best, got = None, 0.0
        for s in active:
            cover = min(s.end, hi) - max(s.start, lo)
            if cover > got or (best is not None and cover == got
                               and s.end - s.start < best.end - best.start):
                best, got = s, cover
        if best is None or got <= 0:
            rest.append((lo, hi))
        else:
            out[best.name] += hi - lo
    for k, v in tr.label_gaps(rest, annotations).items():
        out[k] += v
    return dict(out)


def reduce(devices: Sequence[Dict[str, List[Span]]],
           annotations: Sequence[Span], window: Interval,
           spans: Sequence[Span], scopes: Dict[str, Dict[str, str]]
           ) -> Dict:
    """The program's view of one traced window: ``scopes`` (per program,
    device seconds by scope), ``idle_in`` (per program span name, idle
    seconds inside it) and ``idle_gaps`` (labelled as ``label_gaps``
    says, averaged over chips, the largest ``trace_reduce.TOP``)."""
    lo, hi = window
    inside = [s for s in spans if s.end > lo and s.start < hi]
    gaps: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        for k, v in label_gaps(_idle(dev, window), inside,
                               annotations).items():
            gaps[k] += v / len(devices)
    return {"scopes": scope_seconds(devices, scopes, window),
            "idle_in": idle_within(devices, inside, window),
            "idle_gaps": tr.top(gaps)}


# -- per-layer metrics --------------------------------------------------------
# ``ctx`` as in ``bench.readers``, with ``trace`` the window's
# ``trace_reduce.reduce``, ``program_trace`` its ``reduce`` above, and
# ``spans`` the tracer's ring-buffer events with the host window
# ``tracing.t_start``..``tracing.t_stop``.

def _decode_runs(ctx: Dict) -> int:
    t = ctx.get("trace")
    return t["programs"][DECODE]["count"] \
        if t and DECODE in t["programs"] else 0


def dispatch_idle_us(ctx: Dict) -> Optional[float]:
    """Device-idle microseconds inside ``repro.dispatch:decode_batch``
    spans per ``jit__decode_paged`` run in the traced window."""
    p, n = ctx.get("program_trace"), _decode_runs(ctx)
    if not p or not n:
        return None
    return 1e6 * p["idle_in"].get(PREFIX + "dispatch:decode_batch", 0.0) / n


def cycle_host_us(ctx: Dict) -> Optional[float]:
    """Mean host microseconds of a ``decode_cycle`` span, less the
    ``dispatch:*`` and ``readback`` spans inside it: the scheduler's own
    work per cycle.  From the tracer's ring buffer (``perf_counter``),
    cycles that start and end inside the traced host window."""
    events, rec = ctx.get("spans"), ctx.get("tracing")
    if not events or rec is None or rec.t_start is None:
        return None
    lo, hi = rec.t_start, rec.t_stop
    cycles = sorted((e.ts, e.ts + e.dur) for e in events
                    if e.name == "decode_cycle" and e.ph == "X"
                    and e.ts >= lo and e.ts + e.dur <= hi)
    if not cycles:
        return None
    starts = [a for a, _ in cycles]
    host = [b - a for a, b in cycles]
    for e in events:
        if e.ph == "X" and (e.name == "readback"
                            or e.name.startswith("dispatch:")):
            i = bisect.bisect_right(starts, e.ts) - 1
            if i >= 0 and e.ts + e.dur <= cycles[i][1]:
                host[i] -= e.dur
    return 1e6 * sum(host) / len(host)


def kv_paging_ms(ctx: Dict) -> Optional[float]:
    """Device milliseconds per ``jit__decode_paged`` run in ops scoped
    ``kv_gather`` or ``kv_write``."""
    p, n = ctx.get("program_trace"), _decode_runs(ctx)
    if not p or not n or DECODE not in p["scopes"]:
        return None
    s = p["scopes"][DECODE]
    return 1e3 * (s.get("kv_gather", 0.0) + s.get("kv_write", 0.0)) / n


def mlp_roofline(ctx: Dict) -> Optional[float]:
    """Least time of the decode steps' MLP (the model family's
    ``mlp_step``, the larger of its FLOPs and its weight bytes over the
    chip's peaks) over the device time scoped ``mlp`` per
    ``jit__decode_paged`` run, in percent."""
    p, n, rec = ctx.get("program_trace"), _decode_runs(ctx), \
        ctx.get("tracing")
    if not p or not n or ctx.get("peak") is None or rec is None \
            or not rec.decode:
        return None
    mlp_s = p["scopes"].get(DECODE, {}).get("mlp", 0.0) / n
    if mlp_s <= 0:
        return None
    rows = sum(len(c) for c in rec.decode) / len(rec.decode)
    cfg = ctx["cfg"]
    least = costs.least_time(*family(cfg).mlp_step(cfg, rows),
                             ctx["peak"])[0]
    return 100.0 * least / mlp_s
