# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entrypoint: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

--full uses the paper's N=1024 with full step budgets (slow on CPU);
the default fast mode (N=256) preserves the method ordering.
Roofline rows appear when a dry-run JSON is present (see
repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--records", default="dryrun_single.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")

    # ---- paper Table III: method comparison ---------------------------
    from benchmarks.paper_table import run as paper_run
    n = 1024 if args.full else 256
    budget = "full" if args.full else "fast"
    t0 = time.time()
    rows = paper_run(n=n, budget=budget)
    for r in rows:
        print(f"paper_table.{r['method']},{r['runtime_s'] * 1e6:.0f},"
              f"dpq16={r['dpq16']};params={r['params']};"
              f"valid={r['valid']}")
    sys.stderr.write(f"[paper_table n={n} done in {time.time()-t0:.0f}s]\n")

    # ---- kernel microbench (paper runtime column analogue) ------------
    from benchmarks.kernel_bench import bench, bench_outer_round
    for name, us, derived in bench(ns=(1024, 4096) if args.full
                                   else (1024,)) + bench_outer_round():
        print(f"kernel.{name},{us:.0f},{derived}")

    # ---- roofline terms from the dry-run (figure analogue) ------------
    if os.path.exists(args.records):
        from benchmarks.roofline import analyze
        with open(args.records) as f:
            recs = json.load(f)
        for r in analyze(recs):
            bound_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
            print(f"roofline.{r['arch']}.{r['shape']},{bound_s * 1e6:.0f},"
                  f"dominant={r['dominant']};useful={r['useful_ratio']:.3f};"
                  f"roofline_frac={r['roofline_frac']:.3f}")
    else:
        sys.stderr.write(f"[no {args.records}; run repro.launch.dryrun "
                         "--all --out ... for roofline rows]\n")


if __name__ == "__main__":
    main()
