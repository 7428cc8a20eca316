"""The trace reduction: on synthetic events, and on a small trace of the
test cell recorded on a TPU v5e (``bench/testdata/record_trace.py``),
replaying the per-layer readings the recording run printed."""
import json
import math
import os

import numpy as np
import pytest

import tinyroot
import manifest
import run
import xtrace
from xtrace import Device, Event, Trace

DATA = os.path.join(tinyroot.TINY, "tiny_chat.xplane.pb")
RUN = os.path.join(tinyroot.TINY, "tiny_chat_run.json")


def _ev(name, s, e):
    return Event(name, s, e)


def test_busy_union_and_idle_gaps():
    mods = [_ev("jit_prefill_chunk", 1.0, 3.0), _ev("jit_decode", 4.0, 5.0)]
    ops = [_ev("fusion.1", 1.0, 2.0), _ev("matmul_kernel", 1.5, 2.5),
           _ev("paged_kernel", 2.5, 3.0), _ev("fusion.2", 4.0, 5.0)]
    spans = [_ev("bench.traced", 0.0, 6.0), _ev("bench.step", 0.5, 3.2),
             _ev("bench.wait", 3.2, 3.9), _ev("bench.step", 3.9, 5.5)]
    tr = Trace([Device("/device:TPU:0", mods, ops)], spans, 0.0, 6.0)
    assert xtrace.busy_s(tr) == 3.0
    gaps = xtrace.idle_gaps(tr)
    assert gaps[0] == ["bench.step", 1.0] or gaps[0][1] == 1.0
    labels = {g[0] for g in gaps}
    assert labels <= {"bench.step", "bench.wait", "no host span"}
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert math.isclose(sum(g[1] for g in gaps), 3.0)
    ex = xtrace.executions(tr.devices[0], r"prefill_chunk")
    assert [e.name for e in ex] == ["jit_prefill_chunk"]
    assert [o.name for o in xtrace.ops_within(tr.devices[0], ex[0],
                                              r"kernel")] == \
        ["matmul_kernel", "paged_kernel"]
    assert xtrace.top_ops(tr)[0][1] == 1.0


def _recorded():
    if not (os.path.exists(DATA) and os.path.exists(RUN)):
        pytest.fail("the recorded test trace is missing; run "
                    "bench/testdata/record_trace.py on a TPU")
    with open(RUN) as f:
        return json.load(f)


def test_recorded_trace_is_small():
    _recorded()
    assert os.path.getsize(DATA) < 1_000_000


def test_recorded_trace_reduces_as_recorded(tmp_path):
    rec = _recorded()
    tr = xtrace.load(DATA)
    assert tr.devices and tr.devices[0].ops and tr.devices[0].modules
    assert math.isclose(xtrace.busy_s(tr), rec["busy_s"], rel_tol=1e-9)
    assert math.isclose(tr.window_s, rec["window_s"], rel_tol=1e-9)
    assert 0 < xtrace.busy_s(tr) <= tr.window_s
    assert xtrace.top_ops(tr) == [list(x) for x in
                                  rec["breakdown"]["device_ops"]]
    # rebuild the run the recorder measured and read its metrics again
    root = tinyroot.make(str(tmp_path))
    bench = manifest.load(root)
    cell = manifest.cell(root, bench, tinyroot.CELL)
    with open(os.path.join(tinyroot.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"][rec["device_kind"]]
    recs = {}
    for rid, due, plen, adm, times, status, inw in rec["recs"]:
        recs[rid] = run.Rec(rid, due, np.zeros(plen, np.int32), 0, inw,
                            admitted=adm, times=times, status=status)
    iters = [run.Iteration(t0, t1, [tuple(c) for c in ch], de)
             for t0, t1, ch, de, *_ in rec["iters"]]
    r = run.Run(cell, cell["config_file"], peaks, rec["seconds"],
                tuple(rec["window"]), recs, iters, trace=tr)
    names = {m["name"] for m in manifest.per_layer(bench, tinyroot.CELL)}
    replayed = [n for n in rec["metrics"] if n in names]
    assert {"device_idle_share.itl", "step_mfu.itl"} <= set(replayed)
    for name in replayed:
        value = rec["metrics"][name]
        got = manifest.metric_module(root, name).read(r)
        assert got is not None and math.isclose(got, value, rel_tol=1e-9)
        if name.startswith(("step_mfu", "device_idle_share")) or \
                "roofline" in name:
            assert 0 <= got <= 100
