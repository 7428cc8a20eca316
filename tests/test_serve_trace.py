"""The serving path's own spans and per-step record: the record's counts
match a hand count over a fixed set of requests (a shed, an admission
blocked for pages, retires, both ways into the pick buffer), its lane
snapshot is the scheduler's lanes, and under a profiler every phase span
nests inside ``serve.step`` in the documented order."""
import dataclasses
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.lm import Model
from repro.serve import trace
from repro.serve.api import Request, SamplingParams, StepRecord
from repro.serve.engine import ServeConfig, ServeEngine

ARCH = "internlm2-1.8b"


@pytest.fixture(scope="module")
def model():
    return Model(get_config(ARCH, smoke=True), make_mesh(1, 1))


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(0)


def _engine(model, params, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return ServeEngine(model, params, ServeConfig(**kw))


def _req(model, rid, n, new):
    toks = (np.arange(n) * 7 % model.cfg.vocab).astype(np.int32)
    return Request(id=rid, tokens=toks,
                   sampling=SamplingParams(max_new_tokens=new))


def _lanes(sched):
    return tuple((a.req.id, a.n_prefilled, len(a.tokens), len(a.req.tokens))
                 for a in sched.lanes if a is not None)


# Two lanes, chunk 8, pages of 8, 4 pages a lane, a pool of 4 pages.
# D (40 positions, 5 pages) can never fit and is shed; A (12 + 3) takes
# 2 pages, B (5 + 2) one; C (20 + 4) needs 3 and waits for pages while A
# holds 2 of the 3 left after B retires.  Fields: step, queue depth,
# admitted, shed, prefill lanes / rows / rows dispatched, decode lanes /
# lanes dispatched / pages live, picked, pages held / written / free,
# lanes, retired.
EXPECTED = [
    (0, 4, ("A", "B"), ("D",), 2, 13, 16, 0, 0, 0, 1, 3, 2, 1,
     (("A", 8, 0, 12), ("B", 5, 1, 5)), ()),
    (1, 1, (), (), 1, 4, 16, 1, 2, 1, 2, 2, 2, 2,
     (("A", 12, 1, 12),), (("B", "ok"),)),
    (2, 1, (), (), 0, 0, 0, 1, 2, 2, 1, 2, 2, 2,
     (("A", 12, 2, 12),), ()),
    (3, 1, (), (), 0, 0, 0, 1, 2, 2, 1, 0, 0, 4,
     (), (("A", "ok"),)),
    # C admitted; a chunk with no pick: the step returns after deadlines
    (4, 1, ("C",), (), 1, 8, 16, 0, 0, 0, 0, 3, 1, 1,
     (("C", 8, 0, 20),), ()),
    (5, 0, (), (), 1, 8, 16, 0, 0, 0, 0, 3, 2, 1,
     (("C", 16, 0, 20),), ()),
    (6, 0, (), (), 1, 4, 16, 0, 0, 0, 1, 3, 3, 1,
     (("C", 20, 1, 20),), ()),
    (7, 0, (), (), 0, 0, 0, 1, 2, 3, 1, 3, 3, 1,
     (("C", 20, 2, 20),), ()),
    (8, 0, (), (), 0, 0, 0, 1, 2, 3, 1, 3, 3, 1,
     (("C", 20, 3, 20),), ()),
    (9, 0, (), (), 0, 0, 0, 1, 2, 3, 1, 0, 0, 4,
     (), (("C", "ok"),)),
]


def _submit_fixed(eng, model):
    for rid, n, new in (("D", 30, 10), ("A", 12, 3), ("B", 5, 2),
                        ("C", 20, 4)):
        eng.submit(_req(model, rid, n, new))


def test_step_record_matches_hand_count(model, params):
    eng = _engine(model, params, n_lanes=2, page_size=8, prefill_chunk=8,
                  max_seq_len=32, n_pages=4)
    assert eng.last_step is None
    _submit_fixed(eng, model)
    got = []
    while eng.pending:
        eng.step()
        rec = eng.last_step
        assert isinstance(rec, StepRecord)
        assert rec is eng.scheduler.last_step
        # the lane snapshot is the scheduler's lanes at the step's end
        assert rec.lanes == _lanes(eng.scheduler)
        # every page is held by a lane or free
        assert rec.pages_held + rec.pages_free == 4
        got.append(tuple(getattr(rec, f.name)
                         for f in StepRecord.__dataclass_fields__.values()))
    assert got == EXPECTED
    outs = {o.id: o for o in eng.collect()}
    assert outs["D"].status == "shed" and len(outs["C"].tokens) == 4


def test_decode_pages_live_counts_pages_at_or_below_positions(model,
                                                              params):
    """The pages the decode reads, recomputed from the previous step's
    lane snapshot: a lane that had its whole prompt and a token decodes
    at prompt + tokens - 1 and reads the pages up to that position's.
    A step with no decode counts 0."""
    ps = 8
    eng = _engine(model, params, n_lanes=2, page_size=ps, prefill_chunk=8,
                  max_seq_len=32, n_pages=4)
    _submit_fixed(eng, model)
    prev, live = (), []
    while eng.pending:
        eng.step()
        rec = eng.last_step
        want = sum((plen + tok - 1) // ps + 1
                   for _, pre, tok, plen in prev if pre == plen and tok)
        assert rec.decode_pages_live == want, (rec.step, prev)
        if rec.decode_lanes == 0:
            assert rec.decode_pages_live == 0
        live.append(rec.decode_pages_live)
        prev = rec.lanes
    assert live == [e[9] for e in EXPECTED]
    assert any(live) and not all(live)


def test_step_record_is_frozen(model, params):
    eng = _engine(model, params, n_lanes=2, page_size=8, prefill_chunk=8,
                  max_seq_len=32)
    eng.submit(_req(model, 0, 4, 2))
    eng.step()
    with pytest.raises(dataclasses.FrozenInstanceError):
        eng.last_step.step = 5


def _host_spans(directory):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def test_phase_spans_nest_in_step_in_order(model, params, tmp_path):
    eng = _engine(model, params, n_lanes=2, page_size=8, prefill_chunk=8,
                  max_seq_len=32, n_pages=4)
    _submit_fixed(eng, model)
    eng.drain()                          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _submit_fixed(eng, model)
        while eng.pending:
            eng.step()
    spans = _host_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == trace.STEP]
    kids = [s for s in spans if s[0] in trace.STEP_PHASES]
    assert len(steps) == len(EXPECTED)
    assert sum(s[0] == trace.SUBMIT for s in spans) == 4
    assert {s[0] for s in spans} == \
        {trace.STEP, trace.SUBMIT, *trace.STEP_PHASES}
    order = {name: i for i, name in enumerate(trace.STEP_PHASES)}
    placed = 0
    for name, s0, s1 in steps:
        inside = [k for k in kids if s0 <= k[1] and k[2] <= s1]
        placed += len(inside)
        ranks = [order[k[0]] for k in inside]
        assert ranks == sorted(set(ranks)), [k[0] for k in inside]
        # children follow one another without overlap
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
        assert inside[0][0] == "serve.admit"
        assert inside[-1][0] in ("serve.commit", "serve.deadlines")
    assert placed == len(kids)
