"""Shared benchmark plumbing: result persistence + table printing."""
from __future__ import annotations

import json
import os
import platform
import time
from typing import Any, Dict, List

import jax

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def save_results(name: str, payload: Any) -> str:
    """Persist one benchmark's payload twice: a timestamped copy under
    ``benchmarks/results/`` (local, gitignored) and the canonical
    ``BENCH_<name>.json`` at the repo root — the committed trajectory CI
    uploads as an artifact and gates regressions against."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"results_{name}.json")
    dev = jax.devices()[0]
    doc = {
        "benchmark": name,
        # the device every number below was measured on
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "host": platform.machine(),
        "python": platform.python_version(),
        "time": time.time(),
        "data": payload,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    with open(os.path.join(REPO_ROOT, f"BENCH_{name}.json"), "w") as f:
        json.dump(doc, f, indent=1, default=str)
    return path


def print_table(title: str, rows: List[Dict], cols: List[str]) -> None:
    print(f"\n== {title} ==")
    if not rows:
        print("(no rows)")
        return
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows)) for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))


def _fmt(v) -> str:
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e5 or abs(v) < 1e-3:
            return f"{v:.3e}"
        return f"{v:.3f}"
    return str(v)
