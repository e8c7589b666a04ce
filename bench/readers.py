"""The arithmetic of every metric, over one run's record.

Each metric has a file ``bench/metrics/<name>.py`` whose ``read(ctx)``
returns the number, or None where the run has nothing to read (the
harness then leaves the metric out).  ``ctx`` holds:

  seconds, t0       the window: [t0, t0 + seconds) on ``perf_counter``
  setup_s           process start to window start
  emits             counted request id → emission time of each token
  dispatch          the backend's ``enqueue_s`` and ``steps`` over the run
  trace             ``trace_reduce.reduce`` of the traced window, or None
  tracing           the harness's ``Tracing`` (host records of each decode
                    cycle's row contexts and each prefill chunk), or None
  cfg, peak         model sizes and the chip's published peaks

Percentiles are numpy's linear interpolation over every sample: no
reservoir, no bucketing.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import costs


def percentile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def token_gaps_s(ctx: Dict) -> List[float]:
    """Every gap between consecutive tokens of every counted request."""
    out: List[float] = []
    for ts in ctx["emits"].values():
        out.extend(np.diff(np.asarray(ts, np.float64)).tolist())
    return out


def tokens_in_window(ctx: Dict) -> int:
    lo, hi = ctx["t0"], ctx["t0"] + ctx["seconds"]
    return sum(int(np.count_nonzero((np.asarray(ts) >= lo)
                                    & (np.asarray(ts) < hi)))
               for ts in ctx["emits"].values())


# -- end to end ---------------------------------------------------------------

def output_tok_s(ctx: Dict) -> float:
    return tokens_in_window(ctx) / ctx["seconds"]


def itl_p95_ms(ctx: Dict) -> Optional[float]:
    p = percentile(token_gaps_s(ctx), 95)
    return None if p is None else 1e3 * p


def setup_s(ctx: Dict) -> float:
    return ctx["setup_s"]


# -- per layer ----------------------------------------------------------------

def enqueue_us(ctx: Dict) -> Optional[float]:
    d = ctx["dispatch"]
    return 1e6 * d["enqueue_s"] / d["steps"] if d["steps"] else None


def _program_ms(ctx: Dict, name: str) -> Optional[float]:
    tr = ctx["trace"]
    if tr is None or name not in tr["programs"]:
        return None
    return 1e3 * tr["programs"][name]["mean_s"]


def decode_step_ms(ctx: Dict) -> Optional[float]:
    return _program_ms(ctx, "jit__decode_paged")


def decode_roofline(ctx: Dict) -> Optional[float]:
    """Least time of the traced window's decode steps over their measured
    device time, in percent; the steps' shapes come from the host record
    of each cycle, their times from the trace."""
    rec = ctx["tracing"]
    step = _program_ms(ctx, "jit__decode_paged")
    if step is None or rec is None or not rec.decode or ctx["peak"] is None:
        return None
    least = [costs.least_time(*costs.decode_step(ctx["cfg"], c),
                              ctx["peak"])[0] for c in rec.decode]
    return 100.0 * float(np.mean(least)) / (1e-3 * step)


def device_idle_share(ctx: Dict) -> Optional[float]:
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(ctx: Dict) -> Optional[float]:
    """FLOPs that the traced window's decode rows and prefill tokens need,
    over the window's seconds at the chip's bf16 peak, in percent."""
    rec = ctx["tracing"]
    if rec is None or ctx["trace"] is None or ctx["peak"] is None:
        return None
    flops = sum(costs.decode_step(ctx["cfg"], c)[0] for c in rec.decode)
    flops += sum(costs.prefill_chunk(ctx["cfg"], p0, n)[0]
                 for p0, n in rec.prefill)
    secs = ctx["trace"]["host_window_s"]
    return 100.0 * flops / (secs * ctx["peak"]["bf16_flops_per_s"])
