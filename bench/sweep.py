#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest offered rate the program
sustains, found once when the cell is defined.

    python bench/sweep.py --workload <cell> --seed 1 --seconds 20 --rates 0.5,1,1.5

One engine serves each rate in turn (a warm-up stretch, a window, the
drain; the queue is emptied before the next rate).  For each rate it
prints the offered and completed rates, the time-to-first-token median
and p90, the queue wait p90 and the requests still queued when the
window closed.  A rate is sustained while the
completed rate keeps up with the offered one and the queue does not grow
through the window.  The cell's ``rate_per_s`` is 0.8x the highest
sustained rate.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import manifest
import run
import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=30.0)
    args = ap.parse_args(argv)
    bench = manifest.load(run.ROOT)
    cell = manifest.cell(run.ROOT, bench, args.workload)
    run.device_info(cell["chips"], True)
    run.enable_cache()
    import serve_adapter
    sess = serve_adapter.Session(cell, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    for i, rate in enumerate(rates):
        c = dict(cell, rate_per_s=rate, drain_s=args.drain)
        r = run.drive(sess, c, args.seed, args.seconds, None)
        while sess.has_work and i + 1 < len(rates):
            sess.step()
        wr = r.window_recs()
        ok = [x for x in wr if x.ok]
        ws, we = r.window
        done_in = sum(1 for x in ok if x.times and x.times[-1] < we)
        late = sum(1 for x in wr if x.admitted is None or x.admitted >= we)
        t = stats.ttfts([x.due for x in wr],
                        [x.times[0] if (x.ok and x.times) else None
                         for x in wr])
        print(json.dumps({
            "rate_offered": rate, "requests": len(wr), "ok": len(ok),
            "rate_completed_in_window": done_in / args.seconds,
            "not_admitted_by_window_end": late,
            "ttft_p50_ms": 1e3 * stats.percentile(t, 50),
            "ttft_p90_ms": 1e3 * stats.percentile(t, 90),
            "queue_wait_p90_ms": 1e3 * stats.percentile(
                [stats.MISSING if x.admitted is None else x.admitted - x.due
                 for x in wr], 90),
            "iterations": len(r.iters),
            "mean_iteration_ms": 1e3 * sum(i.t1 - i.t0 for i in r.iters)
            / max(1, len(r.iters))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
