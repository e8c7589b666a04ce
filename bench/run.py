"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and traffic mix are named in ``BENCHMARK.json``
at the root of the checkout; their files are ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` and ``bench/limits/<cell>.json``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  Earlier lines report set-up, compiles in the window, how late
arrivals were queued, and the check.  The run fails, printing no result,
unless JAX's first device is a TPU and there are as many as the cell
needs.  Compiled programs are kept in ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "limits" / f"{name}.json")
                        .read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    metrics = {0: [m for m in spec["end_to_end"] if mine(m)],
               1: [m for m in spec["per_layer"] if mine(m)]}
    return cell, config, traffic, limits, metrics


def configure_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    every program kept however quickly it compiled."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, traffic, limits, metrics = load_cell(args.workload)
    configure_cache()
    from bench import harness
    harness.require_chips(cell["chips"])
    from repro.configs import get_config
    out = harness.run(cfg=config["config"], traffic=traffic,
                      mcfg=get_config(config["registry"]),
                      limit=float(limits["logit_gap"]), seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      metrics=metrics[args.trace], chips=cell["chips"],
                      t_process=T_PROCESS, dtype=config["dtype"])
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
