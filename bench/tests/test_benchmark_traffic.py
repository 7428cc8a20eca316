"""The traffic generator: deterministic for a seed, the same sizes for
every seed, and the stated medians and clips."""
import json
import os

import numpy as np
import pytest

import tinyroot
import traffic

MIXES = os.path.join(tinyroot.BENCH, "traffic")
NAMES = ["chat", "tinymix"]


def _mix(name):
    d = tinyroot.TINY if name == "tinymix" else MIXES
    with open(os.path.join(d, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NAMES)
def test_open_loop_is_deterministic_and_seed_permutes(name):
    mix = _mix(name)
    a = traffic.open_loop(mix, 2.0, 10.0, 50.0, 2**40 + 7, 2, 1000)
    b = traffic.open_loop(mix, 2.0, 10.0, 50.0, 2**40 + 7, 2, 1000)
    c = traffic.open_loop(mix, 2.0, 10.0, 50.0, 12345, 2, 1000)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert len(a) == len(c) == 100
    # another seed: another order, the same multiset of sizes and gaps
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    def gaps(reqs):
        d = [r.due for r in reqs] + [60.0]     # the stretch ends at 60
        return np.sort(np.diff(d))
    assert np.allclose(gaps(a), gaps(c))


@pytest.mark.parametrize("name", NAMES)
def test_open_loop_window_and_rate(name):
    reqs = traffic.open_loop(_mix(name), 1.5, 10.0, 40.0, 3, 2, 1000)
    dues = [r.due for r in reqs]
    assert len(reqs) == 60
    assert dues == sorted(dues)
    assert dues[0] == 10.0 and dues[-1] < 50.0
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


@pytest.mark.parametrize("name", NAMES)
def test_lengths_follow_medians_and_clips(name):
    mix = _mix(name)
    for part in ("prompt", "output"):
        spec = mix[part]
        q = traffic.length_quantiles(spec, 1001)
        assert q.min() >= spec["min"] and q.max() <= spec["max"]
        assert abs(np.median(q) - spec["median"]) <= 1
        assert np.all(np.diff(q) >= 0)
