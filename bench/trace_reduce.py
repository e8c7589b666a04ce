"""From a ``jax.profiler`` trace to the numbers the device metrics read.

The reduction works on plain spans (name, start, end in seconds), so it
can be checked on synthetic ones; ``read_xplane`` takes them out of an
``.xplane.pb`` file:

* device ops: the events on each device plane's ``XLA Ops`` line; busy
  time is their union inside the window, so nested or overlapping ops
  count once;
* programs: one event per execution of a compiled program, from the
  ``XLA Modules`` line (named ``jit_<fn>(<id>)``), or, where a trace has
  no such line, the extent of each execution's ops grouped by their
  ``hlo_module`` and ``run_id``;
* annotations: host events whose name starts with ``bench.``, which the
  harness puts around its calls into each layer; ``bench.window`` marks
  the traced window.

An idle gap is a stretch of the window in which no op runs on the
device.  Each gap is put down to the annotation, other than the outer
``bench.scheduler_run``, that covers most of it: what the host was doing
while the chip waited.  A gap no inner annotation touches is the
scheduler's own code, under ``bench.scheduler_run`` where that span is in
the trace and under ``NONE`` where it began before the profiler started.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "bench."
WINDOW = "bench.window"
OUTER = "bench.scheduler_run"
NONE = "(host code outside the inner annotations)"
TOP = 10
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def union(spans: Iterable[Interval]) -> List[Interval]:
    """Merge intervals; what overlaps or touches counts once."""
    out: List[List[float]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(spans: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi]


def length(spans: Iterable[Interval]) -> float:
    return sum(b - a for a, b in spans)


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(merged: Sequence[Interval], ends: Sequence[float], lo: float,
            hi: float) -> float:
    """Seconds of [lo, hi] that ``merged`` (sorted, disjoint, with their
    ``ends``) covers; a bisection, so many gaps stay cheap."""
    i, got = bisect.bisect_right(ends, lo), 0.0
    while i < len(merged) and merged[i][0] < hi:
        got += min(merged[i][1], hi) - max(merged[i][0], lo)
        i += 1
    return got


def base_name(name: str) -> str:
    """``jit__decode_paged(123)`` → ``jit__decode_paged``."""
    return name.split("(", 1)[0].strip()


def op_label(text: str) -> Tuple[str, str]:
    """(short label, opcode) of an op event named by its HLO text, e.g.
    ``%fusion.88 = (f32[4864]{0:T(1024)}, ...) fusion(...), kind=kLoop``
    → ``("fusion.88 (f32[4864], ...) fusion", "fusion")``; layouts go."""
    if " = " not in text:
        return text[:120], ""
    name, rest = text.split(" = ", 1)
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    rest = re.sub(r"/\*[^*]*\*/", "", rest)
    m = re.match(r"(.*?)\s([a-z][a-z0-9_\-]*)\(", rest)
    shape, opcode = (m.group(1), m.group(2)) if m else (rest[:60], "")
    return f"{name.lstrip('%')} {shape[:60]} {opcode}".strip(), opcode


def label_gaps(gaps: Sequence[Interval], annotations: Sequence[Span]
               ) -> Dict[str, float]:
    """Idle seconds per host label (see the module docstring)."""
    by_name: Dict[str, List[Interval]] = collections.defaultdict(list)
    for s in annotations:
        if s.name != WINDOW:
            by_name[s.name].append((s.start, s.end))
    merged = {n: union(v) for n, v in by_name.items()}
    ends = {n: [b for _, b in iv] for n, iv in merged.items()}
    out: Dict[str, float] = collections.defaultdict(float)
    for lo, hi in gaps:
        cover = {n: overlap(iv, ends[n], lo, hi) for n, iv in merged.items()
                 if n != OUTER}
        best = max(cover, key=cover.get, default=None)
        if best is not None and cover[best] > 0:
            out[best] += hi - lo
        elif OUTER in merged and overlap(merged[OUTER], ends[OUTER], lo,
                                         hi) > 0:
            out[OUTER] += hi - lo
        else:
            out[NONE] += hi - lo
    return dict(out)


def top(totals: Dict[str, float], n: int = TOP) -> List[List]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])
            [:n]]


def reduce(devices: Sequence[Dict[str, List[Span]]],
           annotations: Sequence[Span], window: Interval) -> Dict:
    """Numbers of one traced window.

    ``devices`` holds, per chip, its ``ops`` and ``programs`` spans.
    Busy and idle are averaged over the chips; program times and the op
    breakdown are summed over them.  An op is named by its program and
    HLO text; ops that only hold others (a loop's ``while``) are left out
    of the breakdown, their body ops stand for them."""
    lo, hi = window
    busy_s, gaps_by = [], collections.defaultdict(float)
    op_time: Dict[str, float] = collections.defaultdict(float)
    runs: Dict[str, List[float]] = collections.defaultdict(list)
    for dev in devices:
        busy = union(clip(((s.start, s.end) for s in dev["ops"]), lo, hi))
        busy_s.append(length(busy))
        for k, v in label_gaps(complement(busy, lo, hi), annotations).items():
            gaps_by[k] += v / len(devices)
        progs = sorted((p.start, p.end, base_name(p.name))
                       for p in dev["programs"])
        starts = [p[0] for p in progs]
        for s in dev["ops"]:
            label, opcode = op_label(s.name)
            if opcode in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s.start) - 1
            if i >= 0 and s.start < progs[i][1]:
                label = f"{progs[i][2]}/{label}"
            op_time[label] += length(clip([(s.start, s.end)], lo, hi))
        for s in dev["programs"]:
            if s.start >= lo and s.end <= hi:
                runs[base_name(s.name)].append(s.end - s.start)
    programs = {k: {"count": len(v), "total_s": sum(v),
                    "mean_s": sum(v) / len(v)} for k, v in runs.items()}
    return {"window_s": hi - lo,
            "busy_s": sum(busy_s) / max(len(busy_s), 1),
            "programs": programs,
            "device_ops": top(op_time),
            "idle_gaps": top(gaps_by)}


# -- reading an .xplane.pb --------------------------------------------------

def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _device_spans(plane) -> Dict[str, List[Span]]:
    ops: List[Span] = []
    modules: List[Span] = []
    grouped: Dict[Tuple, List[float]] = {}
    for line in plane.lines:
        if line.name == "XLA Modules":
            modules += [Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events]
        elif line.name == "XLA Ops":
            for e in line.events:
                st = _stats(e)
                mod = st.get("hlo_module")
                name = f"{mod}/{e.name}" if mod else e.name
                ops.append(Span(name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                if mod is not None:
                    key = (mod, st.get("run_id"))
                    g = grouped.setdefault(key, [e.start_ns, e.end_ns])
                    g[0], g[1] = min(g[0], e.start_ns), max(g[1], e.end_ns)
    if not modules:
        modules = [Span(k[0], a * 1e-9, b * 1e-9)
                   for k, (a, b) in grouped.items()]
    return {"ops": ops, "programs": modules}


def read_xplane(path: str, device_prefix: str = "/device:TPU:"
                ) -> Tuple[List[Dict[str, List[Span]]], List[Span],
                           Optional[Interval]]:
    """(per-device spans, bench annotations, window) from a trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, ann = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            devices.append(_device_spans(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ann += [Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events if e.name.startswith(PREFIX)]
    win = [s for s in ann if s.name == WINDOW]
    window = (win[0].start, win[0].end) if win else None
    return devices, ann, window
