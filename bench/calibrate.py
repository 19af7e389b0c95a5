"""Readings that the limits in ``bench/limits/`` are set from, for one
cell, in one process so that the programs compile once.

    python3 bench/calibrate.py --workload photo_grid.batch --seeds 12 \
        --seconds 10 --faults state_unchanged,answer_altered \
        --out bench/.work/cal.photo_grid.batch.json

For each of ``--seeds`` fresh seeds: one short window at the cell's own
load, read against the reference (the program's reading) and against
the reference at the next precision below (the control's reading).
Then each fault of ``--faults`` planted under the timed path on
``--fault-seeds`` seeds, and, with ``--program-dtype``, the program
itself run at that compute dtype (its own lower-precision path) on
``--fault-seeds`` seeds.  Writes every reading to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--program-dtype", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run
    run.enable_cache()
    out = {"workload": args.workload, "seconds": args.seconds,
           "program": [], "control": [], "faults": {}}

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    for i in range(args.seeds):
        seed = args.first_seed + i
        t = time.perf_counter()
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         control=True)
        row = {"seed": seed, "correct": r["correct"], "wall_s":
               time.perf_counter() - t, "check_s": r["check_s"],
               "attempted": r["attempted"], "failed": r["failed"],
               "metrics": r["metrics"], "counters": r["counters"]}
        out["program"].append(dict(row, readings=r["readings"],
                                   detail=r["detail"]))
        out["control"].append({"seed": seed,
                               "readings": r["control_readings"],
                               "detail": r["control_detail"]})
        print(json.dumps(out["program"][-1]), flush=True)
        print(json.dumps(out["control"][-1]), flush=True)
        save()
    for kind in filter(None, args.faults.split(",")):
        out["faults"][kind] = []
        for i in range(args.fault_seeds):
            seed = args.first_seed + 1000 + i
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             fault=kind)
            out["faults"][kind].append({"seed": seed,
                                        "correct": r["correct"],
                                        "failed": r["failed"],
                                        "readings": r["readings"],
                                        "detail": r["detail"]})
            print(kind, json.dumps(out["faults"][kind][-1]), flush=True)
            save()
    if args.program_dtype:
        import copy
        import cell
        spec = copy.deepcopy(cell.load_spec(args.workload))
        spec.config["sort"]["compute_dtype"] = args.program_dtype
        rows = out["program_" + args.program_dtype] = []
        for i in range(args.fault_seeds):
            seed = args.first_seed + 2000 + i
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             spec=spec)
            rows.append({"seed": seed, "correct": r["correct"],
                         "readings": r["readings"], "detail": r["detail"]})
            print(args.program_dtype, json.dumps(rows[-1]["readings"]),
                  flush=True)
            save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
