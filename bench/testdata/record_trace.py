#!/usr/bin/env python3
"""Records the test trace on the chip: the test cell ``tiny.chat`` with
``--trace 1``, profiling the last 20 ms of a 1 s window, kept as
``bench/testdata/tiny/tiny_chat.xplane.pb`` beside the run's records and
per-layer readings (``tiny_chat_run.json``) that the trace test replays.

    python bench/testdata/record_trace.py
"""
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tests"))
import tinyroot  # noqa: E402
import run  # noqa: E402


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    root = tinyroot.make(tmp)
    path = os.path.join(root, "bench", "workloads", f"{tinyroot.CELL}.json")
    with open(path) as f:
        cell = json.load(f)
    cell["trace_s"] = 0.02
    with open(path, "w") as f:
        json.dump(cell, f)
    keep = os.path.join(tmp, "trace")
    res, r = run.execute(root, tinyroot.CELL, 5, 1.0, True, keep_trace=keep)
    out = os.path.join(HERE, "tiny")
    from xtrace import find_xplane
    shutil.copy(find_xplane(keep), os.path.join(out, "tiny_chat.xplane.pb"))
    rec = {
        "device_kind": res["device"]["kind"],
        "window": list(r.window), "seconds": r.seconds,
        "iters": [[i.t0, i.t1, i.chunks, i.decodes]
                  for i in r.iters],
        "recs": [[x.id, x.due, len(x.prompt), x.admitted, x.times, x.status,
                  x.in_window] for x in r.recs.values()],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "busy_s": res["device"]["busy_s"],
        "window_s": res["device"]["window_s"],
        "breakdown": res["breakdown"],
    }
    with open(os.path.join(out, "tiny_chat_run.json"), "w") as f:
        json.dump(rec, f)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
