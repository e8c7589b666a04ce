"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME,...]

| module          | paper table / section                           |
|-----------------|--------------------------------------------------|
| bench_dispatch  | Table 6 — single-op vs sequential dispatch cost  |
| bench_timeline  | Table 20 — per-dispatch phase decomposition      |
| bench_opgraph   | Table 10 — dispatch-graph taxonomy               |
| bench_fusion    | Table 5 — progressive fusion (controlled)        |
| bench_e2e       | Tables 2/3 — end-to-end across backends          |
| bench_scaling   | Table 18 — 0.5B vs 1.5B overhead scaling         |
| bench_overhead  | Table 4 + App. G — overhead accounting           |
| bench_crossover | Table 14 — dispatch-bound crossover B*           |
| bench_matmul    | Tables 8/12 — kernel compute efficiency          |
| bench_tiled     | Table 19 — tiled MLP strategy                    |
| bench_batch     | App. F batch>1 validation (beyond paper)         |
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_batch, bench_crossover, bench_dispatch,
                        bench_e2e, bench_fusion, bench_matmul, bench_opgraph,
                        bench_overhead, bench_scaling, bench_tiled,
                        bench_timeline)

ALL = {
    "dispatch": bench_dispatch,
    "timeline": bench_timeline,
    "opgraph": bench_opgraph,
    "fusion": bench_fusion,
    "e2e": bench_e2e,
    "scaling": bench_scaling,
    "overhead": bench_overhead,
    "crossover": bench_crossover,
    "matmul": bench_matmul,
    "tiled": bench_tiled,
    "batch": bench_batch,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short runs (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma list of benchmark names")
    args = ap.parse_args()

    names = list(ALL) if not args.only else args.only.split(",")
    failed = []
    t0 = time.time()
    for name in names:
        mod = ALL[name]
        print(f"\n##### benchmarks.bench_{name} #####")
        try:
            t1 = time.time()
            mod.run(quick=args.quick)
            print(f"##### bench_{name} done in {time.time()-t1:.1f}s #####")
        except Exception as e:
            import traceback
            traceback.print_exc()
            failed.append((name, repr(e)))
    print(f"\n= benchmarks complete in {time.time()-t0:.1f}s; "
          f"{len(names)-len(failed)}/{len(names)} passed =")
    if failed:
        for name, err in failed:
            print(f"  FAILED {name}: {err}")
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
