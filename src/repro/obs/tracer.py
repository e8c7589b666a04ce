"""Span tracer — the dispatch-level timeline store behind ``repro.obs``.

The paper's methodology lives or dies on *where* time goes per dispatch
(host submit vs device compute, §7.2); this tracer records that timeline
for the whole serving stack with three properties:

* **Zero-allocation disabled fast path.**  ``Tracer.span(...)`` on a
  disabled tracer returns one shared ``_NullSpan`` singleton and records
  nothing — the decode hot loop pays an attribute load and a branch, so
  production serving keeps its measured dispatch costs (CI asserts the
  disabled overhead stays under 2% of a decode cycle).
* **Bounded memory.**  Enabled tracing writes into a fixed-capacity ring
  buffer; a run that outlives the buffer drops the OLDEST events (the
  ``dropped`` counter says how many) instead of growing without bound —
  a tracer you can leave on under production traffic.
* **On the profiler's clock.**  A live span also enters
  ``jax.profiler.TraceAnnotation("repro." + name)`` for its extent, so
  under ``jax.profiler.trace`` the same span sits in the ``.xplane.pb``
  beside the device's ops, and device idle time can be put down to what
  the host was doing.  With no profiler session active the annotation
  costs well under a microsecond.

Events are plain ``SpanEvent`` records on named *tracks* ("scheduler",
"slot3", "backend:F3" ...); ``repro.obs.perfetto`` maps tracks to
Perfetto/chrome-tracing threads.  Recording surfaces:

* ``with tracer.span("decode_cycle", track="scheduler"): ...`` — timed
  context manager, nesting tracked per track;
* ``tracer.open_span(track, cat)`` — the innermost span still open on a
  track, whose ``tally`` adds counts into its args (how a backend's
  dispatch accounting lands on the live dispatch span it opened);
* ``tracer.instant(...)`` / ``tracer.counter(...)`` — point events
  (radix hit, COW fork, eviction) and counter samples.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: prefix of every live span's name on the profiler's timeline
PROFILER_PREFIX = "repro."


class SpanEvent(NamedTuple):
    """One recorded event.  ``ts``/``dur`` are ``time.perf_counter``
    seconds; ``ph`` follows the trace-event phase letters ("X" complete
    span, "i" instant, "C" counter sample)."""
    name: str
    cat: str
    track: str
    ts: float
    dur: float
    ph: str
    depth: int
    args: Optional[Dict[str, Any]]


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _LiveSpan:
    """Timed span: stamps entry/exit, pushes one "X" event, and holds a
    profiler annotation of the same name open for its extent."""
    __slots__ = ("_tr", "name", "cat", "track", "args", "_t0", "_stack",
                 "_depth", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, track: str,
                 args: Optional[Dict[str, Any]]) -> None:
        self._tr = tr
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args

    def __enter__(self) -> "_LiveSpan":
        self._stack = self._tr._open.setdefault(self.track, [])
        self._depth = len(self._stack)
        self._stack.append(self)
        self._ann = TraceAnnotation(PROFILER_PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        del self._stack[self._depth:]
        self._tr._push(SpanEvent(self.name, self.cat, self.track, self._t0,
                                 dur, "X", self._depth, self.args))
        return False

    def tally(self, **counts: float) -> None:
        """Add ``counts`` into this span's args (missing keys start at 0)."""
        if self.args is None:
            self.args = {}
        for k, v in counts.items():
            self.args[k] = self.args.get(k, 0) + v


class Tracer:
    """Ring-buffer span store with an allocation-free disabled path."""

    def __init__(self, capacity: int = 65536, enabled: bool = True) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self.dropped = 0
        self._buf: List[Optional[SpanEvent]] = []
        self._head = 0                      # next write index once full
        self._open: Dict[str, List[_LiveSpan]] = {}   # per track, outer first

    # -- recording -----------------------------------------------------
    def span(self, name: str, *, cat: str = "phase", track: str = "main",
             **args):
        """Timed context manager; a no-op singleton when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _LiveSpan(self, name, cat, track, args or None)

    def open_span(self, track: str, cat: Optional[str] = None
                  ) -> Optional[_LiveSpan]:
        """The innermost span still open on ``track`` (of category
        ``cat``, if given), or None."""
        for sp in reversed(self._open.get(track, ())):
            if cat is None or sp.cat == cat:
                return sp
        return None

    def instant(self, name: str, *, cat: str = "event",
                track: str = "main", **args) -> None:
        if not self.enabled:
            return
        self._push(SpanEvent(name, cat, track, time.perf_counter(), 0.0,
                             "i", len(self._open.get(track, ())),
                             args or None))

    def counter(self, name: str, value: float, *, track: str = "main"
                ) -> None:
        if not self.enabled:
            return
        self._push(SpanEvent(name, "counter", track, time.perf_counter(),
                             0.0, "C", 0, {"value": value}))

    # -- ring buffer ---------------------------------------------------
    def _push(self, ev: SpanEvent) -> None:
        if len(self._buf) < self.capacity:
            self._buf.append(ev)
            return
        self._buf[self._head] = ev          # overwrite the oldest
        self._head = (self._head + 1) % self.capacity
        self.dropped += 1

    def events(self) -> List[SpanEvent]:
        """Recorded events, oldest first (wraparound unrolled)."""
        if len(self._buf) < self.capacity:
            return list(self._buf)
        return self._buf[self._head:] + self._buf[:self._head]

    def __len__(self) -> int:
        return len(self._buf)

    def clear(self) -> None:
        self._buf = []
        self._head = 0
        self.dropped = 0
        self._open = {}

    # -- derived accounting -------------------------------------------
    def dispatch_total(self) -> int:
        """Sum of ``args["dispatches"]`` over dispatch-lane spans — the
        trace-derived dispatch count CI checks against the backend's
        ``dispatch_stats()`` delta (``_record`` feeds both, tallying each
        count onto the live dispatch span its backend call opened, so the
        two MUST agree exactly)."""
        return sum(ev.args.get("dispatches", 0)
                   for ev in self.events()
                   if ev.cat == "dispatch" and ev.args)

    def count(self, name: str) -> int:
        return sum(1 for ev in self.events() if ev.name == name)


#: Module-wide disabled tracer: the default everywhere a tracer is
#: optional.  Never enable this instance — hand out your own ``Tracer``.
NULL_TRACER = Tracer(capacity=1, enabled=False)
