"""Percentile and window arithmetic; failed requests count as missing;
latencies are timed from the due time."""
import math

import numpy as np

import tinyroot  # noqa: F401
import stats


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert math.isnan(stats.percentile([], 90))
    assert stats.beyond(100, 90) == 10


def test_missing_requests_push_the_tail():
    due = [0.0] * 10
    first = [1.0] * 9 + [None]            # one request failed
    t = stats.ttfts(due, first)
    assert t.count(stats.MISSING) == 1
    assert stats.percentile(t, 90) == 1.0
    assert stats.percentile(t, 95) == stats.MISSING
    first = [1.0] * 8 + [None, None]
    assert stats.percentile(stats.ttfts(due, first), 90) == stats.MISSING


def test_ttft_from_due_not_from_submission():
    # due at 2.0, the generator ran late and sent it at 2.5, first token 3.0
    assert stats.ttfts([2.0], [3.0]) == [1.0]


def test_gaps_and_windows():
    assert stats.token_gaps([1.0, 1.5, 3.0]) == [0.5, 1.5]
    assert stats.union_length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert stats.union_length([]) == 0


def test_end_to_end_from_records():
    import run
    cell = {"name": "c"}
    recs = {}
    for i in range(20):
        r = run.Rec(i, due=10.0 + i, prompt=np.zeros(4, np.int32),
                    max_new=3, in_window=True)
        r.status = "ok"
        r.times = [r.due + 0.1 * (i + 1), r.due + 0.1 * (i + 1) + 0.05,
                   r.due + 0.1 * (i + 1) + 0.15]
        recs[i] = r
    recs[19].status, recs[19].times = "unfinished", []
    r = run.Run(cell, {}, {}, 20.0, (10.0, 30.0), recs, [])
    out = run.end_to_end(r, setup_s=7.0)
    # 19 served with TTFT 0.1..1.9 s and one missing: p90 is the 18th
    assert math.isclose(run.earlier_line(r)["ttft_p90_ms"], 1800.0)
    assert "ttft_p90_ms" not in out
    assert out["itl_p95_ms"] == 100.0 or math.isclose(out["itl_p95_ms"],
                                                      100.0)
    assert out["setup_s"] == 7.0
    assert set(out) == {"itl_p95_ms", "setup_s"}
    # no iteration and no peaks: nothing for the utilisation to read
    info = run.earlier_line(r)
    assert info["step_mfu"] is None and info["mean_iteration_ms"] is None
