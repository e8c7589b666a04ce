"""Readings that set a cell's limit, many seeds in one process on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8

Each seed serves the cell's window as the benchmark does (``harness.serve``)
and judges it (``harness.judge``), then judges the control on the same
sample: the reference computed in int8, its own tokens in place of the
program's.  The program's widest logit gap, largest over the seeds, and
the control's, smallest, set the cell's limit (``bench/limits/<cell>.json``);
the control has to come out not correct.
One JSON line per seed; not part of a benchmark run.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    cell, config, traffic, limits, metrics = bench_run.load_cell(
        args.workload)
    bench_run.configure_cache()
    from bench import harness
    from repro.configs import get_config
    harness.require_chips(cell["chips"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        s = harness.serve(cfg=config["config"], traffic=traffic,
                          mcfg=get_config(config["registry"]), seed=seed,
                          seconds=args.seconds, trace=False,
                          metrics=metrics[0], chips=cell["chips"],
                          t_process=time.perf_counter(),
                          dtype=config["dtype"])
        judged = {q or "program": harness.judge(
            s, cfg=config["config"], traffic=traffic, seed=seed,
            dtype=config["dtype"], limit=float(limits["logit_gap"]),
            quant=q) for q in (None, "int8")}
        print(json.dumps({"calibrate": args.workload, "seed": seed,
                          "attempted": s.line["attempted"],
                          "metrics": s.line["metrics"], **judged}),
              flush=True)


if __name__ == "__main__":
    main()
