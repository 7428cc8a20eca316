"""The one traffic generator: turns a mix's data file and a seed into
requests.

Every seed gets the same multiset of sizes and gaps, in another order:
lengths are the mix's distribution read at evenly spaced quantiles, and
open-loop gaps are the exponential distribution's quantiles at the
cell's rate.  The seed permutes them and draws the token ids.  A run's
amount of work is then fixed by the mix and the window, and seeds differ
only in the order of arrivals and in what the prompts say.

A mix file (``bench/traffic/<name>.json``) holds:

  ``arrival``  ``"poisson"``: open loop at the cell's ``rate_per_s``;
  ``prompt``, ``output``  ``{"dist": "lognormal", "median", "sigma",
               "min", "max"}`` in tokens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

@dataclasses.dataclass(frozen=True)
class Req:
    id: int
    due: float              # seconds after the schedule's origin
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def length_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the clipped
    distribution ``spec``, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = np.array([math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
                    for i in range(n)])
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at rate ``rate``, at the
    quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _lengths(mix: Dict, n: int, rng: np.random.Generator):
    p = length_quantiles(mix["prompt"], n)
    o = length_quantiles(mix["output"], n)
    return p[rng.permutation(n)], o[rng.permutation(n)]


def _prompts(lens, vocab: int, rng: np.random.Generator) -> List[np.ndarray]:
    return [rng.integers(0, vocab, size=int(n), dtype=np.int32) for n in lens]


def open_loop(mix: Dict, rate: float, start: float, seconds: float,
              seed: int, stream: int, vocab: int, first_id: int = 0
              ) -> List[Req]:
    """Requests due in [start, start + seconds) at mean rate ``rate``: the
    first at ``start``, then the n = round(rate * seconds) quantile gaps
    in seeded order, scaled to fill the stretch exactly (the last gap runs
    from the last arrival to the stretch's end)."""
    if mix["arrival"] != "poisson":
        raise ValueError("open_loop needs a poisson mix")
    n = max(1, int(round(rate * seconds)))
    rng = _seed_rng(seed, stream)
    gaps = gap_quantiles(rate, n)[rng.permutation(n)]
    t = start + (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    p, o = _lengths(mix, n, rng)
    prompts = _prompts(p, vocab, rng)
    return [Req(first_id + i, float(t[i]), prompts[i], int(o[i]))
            for i in range(n)]
