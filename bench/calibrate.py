#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 [--control int8]

Runs the cell once per seed in one process (weights, engine and
reference anew for each seed; programs from the compile cache) and
prints each seed's numbers compared (``max_logit_gap``, the widest logit
gap against the reference, and ``mean_request_gap``, the mean of each
checked request's widest gap).  Without
``--control`` it reads the program as the configuration states it (the
lower reading: the largest over a dozen seeds or more); with
``--control int8``, the program's own int8 path, the precision below
the configuration's bf16 (the upper reading: the smallest over three
seeds or more).  The limit in the cell's file lies between the two;
``PERF.md`` gives the readings.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=("int8",),
                    help="serve on the program's int8 path")
    args = ap.parse_args(argv)
    gaps = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res, r = run.execute(run.ROOT, args.workload, seed, args.seconds,
                             False, int8=args.control == "int8",
                             t_process=time.perf_counter())
        per = r.checked
        g = {"max_logit_gap": max(per), "mean_request_gap": sum(per) /
             len(per)}
        gaps.append(g)
        print(json.dumps({"seed": seed, "control": args.control, **g,
                          "per_request": per, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"]}), flush=True)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      **{k: {"largest": max(g[k] for g in gaps),
                             "smallest": min(g[k] for g in gaps)}
                         for k in gaps[0]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
