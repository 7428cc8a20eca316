"""Continuous-batching request scheduler over the paged KV cache.

One scheduler owns a fixed set of decode LANES (the jit batch width) and
a page pool; requests flow queue -> lane -> retired while the compiled
programs never change shape:

  * admission  — a queued request takes the lowest free lane and
    allocates ``ceil((prompt + max_new) / page_size)`` pages from the
    free list; transient page exhaustion keeps it queued, an impossible
    fit (longer than a lane can ever hold) sheds it with a structured
    status.  Retired requests free their pages for immediate reuse.
  * chunked prefill — at most ONE fixed-size prompt chunk per lane per
    iteration, every prefilling lane batched into a single [L, chunk]
    dispatch, so a long prompt is spread across iterations and never
    stalls the in-flight decodes it shares the device with.  The last
    chunk's logits seed the request's first token pick.
  * decode     — every lane with at least one picked token steps in a
    single [L]-wide dispatch; idle lanes ride along with position -1
    (their cache writes land on the trash page, their logits rows are
    ignored).  A lane's math is bitwise independent of its neighbors,
    which is what keeps a request's tokens identical whether it runs
    alone or amid churn.  The attention inside the dispatch is the
    paged flash-decode kernel — per-tile dots at the pools' storage
    dtype, rank-order split combine — whose masking gives unmapped
    pages and idle lanes exact-zero contributions, so the isolation
    invariant holds at the kernel level, not by host bookkeeping.
  * pick       — one fused guarded dispatch picks every fresh lane's
    token with per-request sampling params (greedy mask, temperature,
    fold_in(request seed, step) keys) and the PR 5 health probes; the
    per-request quarantine/degrade/timeout/shed statuses come out of the
    same host bookkeeping that owned them per-lane before.

The host loop is ordered to OVERLAP with the device: admissions (a few
microseconds of allocator bookkeeping) run first so a lane freed last
iteration refills before this iteration's dispatches, then the chunk and
decode steps go out back-to-back, fault/deadline bookkeeping and output
assembly run while the device works, and only the token pick's host
transfer synchronizes.  ``FaultPlan`` hooks ride at the same boundaries
as the fixed-batch loop (``maybe_stall_lanes`` / ``perturb_logits_lanes``
— per-lane step vectors instead of one global step).

Each step runs inside the host span ``serve.step``, its phases inside
the child spans ``repro.serve.trace.STEP_PHASES`` names, and leaves a
``StepRecord`` of its counts in ``last_step``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.robust.guards import (
    STATUS_DEGRADED,
    STATUS_NONFINITE,
    STATUS_OK,
    STATUS_SHED,
    STATUS_TIMEOUT,
    NumericalHealthError,
)
from repro.serve.api import Request, RequestOutput, SamplingParams, StepRecord
from repro.serve.kv_cache import PagedKVCache
from repro.serve.trace import STEP, span


@dataclasses.dataclass
class _Lane:
    """One admitted request's host-side state."""

    req: Request
    sp: SamplingParams
    seq: int                          # admission order (prefill FIFO)
    key_base: np.ndarray              # uint32[2] PRNGKey(req.seed)
    n_prefilled: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = STATUS_OK
    fault_step: int = -1
    degraded: bool = False
    calib: float = 1.0
    calibrated: bool = False
    deadline: Optional[float] = None

    @property
    def prefilled(self) -> bool:
        return self.n_prefilled >= len(self.req.tokens)


class PagedScheduler:
    """Fixed-lane continuous-batching loop; see the module docstring.

    Built by ``ServeEngine`` (which owns the jitted programs); exposed
    knobs are the jit-shape constants: lane count, page geometry, and the
    prefill chunk size."""

    def __init__(self, engine, *, n_lanes: int, pages_per_lane: int,
                 n_pages: int, page_size: int, chunk: int):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.engine = engine
        self.n_lanes = n_lanes
        self.chunk = chunk
        self.kv = PagedKVCache(engine.model, n_lanes, n_pages, page_size,
                               pages_per_lane)
        self.lanes: List[Optional[_Lane]] = [None] * n_lanes
        self.queue: deque = deque()
        self.timed_out = False
        self._logits = None               # [L, Vp] device pick buffer
        self._last_tok = np.zeros((n_lanes,), np.int32)
        self._stall_fired: set = set()
        self._seq = 0
        # lane-constant pick args (keys, sampling modes, calibration) are
        # device-cached and only re-uploaded when lane membership or a
        # lane's calibration/degradation changes — the per-iteration
        # upload is just the step vector
        self._lane_gen = 0
        self._pick_gen = -1
        self._pick_const = None
        self._degr_dev = None
        self._n_steps = 0
        # the latest step's record (None before the first step)
        self.last_step: Optional[StepRecord] = None

    # -- surface ---------------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(1 for a in self.lanes if a is not None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def reset_fault_state(self) -> None:
        """Per-drain fault bookkeeping (stall once-per-drain tracking and
        the timeout flag) — cleared by the shim between generate calls so
        a reused scheduler replays a FaultPlan from scratch."""
        self._stall_fired.clear()
        self.timed_out = False

    def submit(self, req: Request) -> None:
        sp = req.sampling if req.sampling is not None \
            else self.engine.scfg.sampling_defaults()
        self.queue.append((req, sp))

    def run_to_completion(self, fault_plan=None) -> List[RequestOutput]:
        outs: List[RequestOutput] = []
        idle = 0
        while self.has_work:
            before = self.n_active
            outs.extend(self.step(fault_plan))
            if self.queue and before == 0 and self.n_active == 0:
                idle += 1
                if idle > 2:
                    raise RuntimeError(
                        "scheduler stalled: queue non-empty but nothing "
                        "admits (page pool smaller than one request?)")
            else:
                idle = 0
        return outs

    # -- one iteration ---------------------------------------------------------

    def step(self, fault_plan=None) -> List[RequestOutput]:
        """Advance every phase one tick; returns requests finished NOW.

        The step runs inside the host span ``serve.step`` and its phases
        inside its children (``repro.serve.trace``); ``last_step`` then
        holds the step's ``StepRecord``."""
        with span(STEP):
            return self._step(fault_plan)

    def _step(self, fault_plan) -> List[RequestOutput]:
        eng = self.engine
        plan = fault_plan if (fault_plan is not None
                              and fault_plan.enabled) else None
        finished: List[RequestOutput] = []
        L = self.n_lanes
        fresh = np.zeros((L,), bool)
        count = dict(step=self._n_steps, queue_depth=len(self.queue))
        self._n_steps += 1

        # 1. admissions first, so a request admitted into a lane freed
        # LAST iteration rides this iteration's chunk dispatch instead of
        # waiting one more tick (page-allocator bookkeeping is a few
        # microseconds of host work)
        with span("serve.admit"):
            count["admitted"], count["shed"] = self._admit(finished)

        # 2. chunked prefill: ONE chunk per prefilling lane, ALL such
        # lanes batched into a single [L, C] dispatch (idle lanes ride
        # with positions -1 — trash-page writes, masked attention, a
        # gathered logits row the host ignores).  A long prompt spreads
        # over iterations instead of stalling in-flight decodes, while
        # same-time admissions stay in lockstep (what makes the
        # generate(batch) shim bitwise-match the fixed loop).
        with span("serve.chunk_build"):
            pre = [l for l, a in enumerate(self.lanes)
                   if a is not None and not a.prefilled]
            dec = [l for l, a in enumerate(self.lanes)
                   if a is not None and a.prefilled and a.tokens]
            completed = np.zeros((L,), bool)
            rows = 0
            if pre:
                tc = np.zeros((L, self.chunk), np.int32)
                pc = np.full((L, self.chunk), -1, np.int32)
                last = np.full((L,), -1, np.int32)
                for l in pre:
                    a = self.lanes[l]
                    start = a.n_prefilled
                    n = min(self.chunk, len(a.req.tokens) - start)
                    tc[l, :n] = a.req.tokens[start:start + n]
                    pc[l, :n] = np.arange(start, start + n, dtype=np.int32)
                    last[l] = n - 1
                    a.n_prefilled += n
                    rows += n
                    if a.prefilled:
                        completed[l] = True  # row seeds the first pick
                chunk_args = (jnp.asarray(tc), jnp.asarray(pc),
                              self.kv.table_device(), jnp.asarray(last))
            count.update(prefill_lanes=len(pre), prefill_rows=rows,
                         prefill_rows_dispatched=L * self.chunk if pre else 0,
                         decode_lanes=len(dec),
                         decode_lanes_dispatched=L if dec else 0,
                         decode_pages_live=0)
        chunk_rows = None
        if pre:
            with span("serve.prefill_dispatch"):
                chunk_rows, self.kv.pools = eng._prefill_chunk(
                    eng.params, self.kv.pools, *chunk_args)

        # 3. decode: one [L]-wide step for every lane holding tokens (a
        # lane that finished its prompt above holds none yet); its inputs
        # upload while the prefill chunk runs
        fp_logits = None
        if dec:
            with span("serve.decode_dispatch"):
                pos_np = np.full((L,), -1, np.int32)
                for l in dec:
                    a = self.lanes[l]
                    pos_np[l] = len(a.req.tokens) + len(a.tokens) - 1
                count["decode_pages_live"] = int(
                    (pos_np[dec] // self.kv.page_size + 1).sum())
                tok_dev = jnp.asarray(self._last_tok[:, None])
                pos_dev = jnp.asarray(pos_np)
                pt_dev = self.kv.table_device()
                if (eng._decode_paged_fp is not None
                        and any(self.lanes[l].degraded for l in dec)):
                    # dispatched BEFORE the donating step: it reads the
                    # pool buffers that step consumes
                    fp_logits, _ = eng._decode_paged_fp(
                        eng._fp_params, self.kv.pools, tok_dev, pos_dev,
                        pt_dev)
                self._logits, self.kv.pools = eng._decode_paged(
                    eng.params, self.kv.pools, tok_dev, pos_dev, pt_dev)
            fresh[dec] = True

        # 4. inject completed lanes' final-chunk logits rows into the
        # pick buffer — one masked dispatch for every lane that finished
        # its prompt this iteration
        if completed.any():
            with span("serve.inject"):
                if self._logits is None:
                    self._logits = chunk_rows
                else:
                    self._logits = eng._inject_rows(
                        self._logits, chunk_rows, jnp.asarray(completed))
            fresh |= completed

        # 5. faults + per-request deadlines (stall first, like the fixed
        # loop: a stalled host is exactly what the budget must convert)
        with span("serve.deadlines"):
            steps = np.full((L,), -1, np.int64)
            for l, a in enumerate(self.lanes):
                if a is not None and fresh[l]:
                    steps[l] = len(a.tokens)
            if plan is not None:
                plan.maybe_stall_lanes(steps, self._stall_fired)
            now = time.monotonic()
            for l, a in enumerate(self.lanes):
                if a is not None and a.deadline is not None \
                        and now > a.deadline:
                    a.status = STATUS_TIMEOUT
                    a.fault_step = len(a.tokens)
                    self.timed_out = True
                    fresh[l] = False
                    steps[l] = -1
                    self._retire(l, finished)
            if not fresh.any():
                self.last_step = self._record(count, 0, finished)
                return finished
            if plan is not None:
                self._logits = plan.perturb_logits_lanes(steps,
                                                         self._logits)

        # 6. one fused pick + health probe over all lanes.  The
        # lane-constant args (keys, sampling modes, calibration) come
        # from the generation-counted device cache; only the step vector
        # uploads every iteration.  Non-fresh lanes carry step -1 — their
        # fold_in keys differ from a live lane's but their picks are
        # never read.
        with span("serve.pick_dispatch"):
            if self._pick_gen != self._lane_gen:
                kb = np.zeros((L, 2), np.uint32)
                greedy = np.ones((L,), bool)
                temp = np.ones((L,), np.float32)
                calib = np.ones((L,), np.float32)
                degr = np.zeros((L,), bool)
                for l, a in enumerate(self.lanes):
                    if a is None:
                        continue
                    kb[l] = a.key_base
                    greedy[l] = a.sp.greedy
                    temp[l] = a.sp.temperature
                    calib[l] = a.calib
                    degr[l] = a.degraded
                self._pick_const = (jnp.asarray(kb), jnp.asarray(greedy),
                                    jnp.asarray(temp), jnp.asarray(calib))
                self._degr_dev = jnp.asarray(degr)
                self._pick_gen = self._lane_gen
            kb_d, greedy_d, temp_d, calib_d = self._pick_const
            steps_d = jnp.asarray(steps.astype(np.int32))
            pick_args = (kb_d, steps_d, greedy_d, temp_d, calib_d)
            tok_j, fin_j, absmax_j, sat_j = eng._pick_paged(
                self._logits, *pick_args)
            if fp_logits is not None:
                # degraded lanes pick from the fp32 fallback logits; the
                # same keys keep healthy lanes bitwise unchanged
                tok_fp, _, _, _ = eng._pick_paged(fp_logits, *pick_args)
                tok_j = jnp.where(self._degr_dev, tok_fp, tok_j)
        with span("serve.pick_sync"):
            tok_np = np.asarray(tok_j)
            fin_np = np.asarray(fin_j)
            absmax_np = np.asarray(absmax_j)
            sat_np = np.asarray(sat_j)

        # 7. guards + commit + retire
        with span("serve.commit"):
            self._commit(fresh, tok_np, fin_np, absmax_np, sat_np,
                         finished)
            self.last_step = self._record(count, int(fresh.sum()),
                                          finished)
        return finished

    def _commit(self, fresh, tok_np, fin_np, absmax_np, sat_np,
                finished: List[RequestOutput]) -> None:
        scfg = self.engine.scfg
        L = self.n_lanes
        guards_on = scfg.guards and scfg.on_nonfinite != "off"
        sat_on = scfg.guards and scfg.int8
        if guards_on and scfg.on_nonfinite == "raise":
            bad = [l for l in range(L) if fresh[l] and not fin_np[l]]
            if bad:
                t = len(self.lanes[bad[0]].tokens)
                raise NumericalHealthError(
                    f"non-finite logits at decode step {t} in lanes {bad}")
        for l in range(L):
            a = self.lanes[l]
            if a is None or not fresh[l]:
                continue
            t = len(a.tokens)
            if guards_on and not fin_np[l]:
                a.status = STATUS_NONFINITE
                a.fault_step = t
                self._retire(l, finished)
                continue
            if sat_on:
                if not a.calibrated:
                    # the request's first decode logits calibrate its probe
                    a.calib = float(np.maximum(absmax_np[l],
                                               np.float32(1e-6)))
                    a.calibrated = True
                    self._lane_gen += 1
                elif (fin_np[l] and not a.degraded
                        and sat_np[l] > scfg.saturation_threshold):
                    a.degraded = True
                    self._lane_gen += 1
                    if a.status == STATUS_OK:
                        a.status = STATUS_DEGRADED
                        a.fault_step = t
            tk = int(tok_np[l])
            a.tokens.append(tk)
            self._last_tok[l] = tk
            if (a.sp.eos_id is not None and tk == a.sp.eos_id) \
                    or len(a.tokens) >= a.sp.max_new_tokens:
                self._retire(l, finished)

    def _record(self, count, picked: int,
                finished: List[RequestOutput]) -> StepRecord:
        """The step's record, from the counts taken as it ran and the
        lanes and pages at its end."""
        ps = self.kv.page_size
        lanes = tuple((a.req.id, a.n_prefilled, len(a.tokens),
                       len(a.req.tokens))
                      for a in self.lanes if a is not None)
        # a lane has written its prompt chunks and, per decode, the token
        # before its newest (the newest is written by the next decode)
        written = sum(-(-(pre + max(tok - 1, 0)) // ps)
                      for _, pre, tok, _ in lanes)
        return StepRecord(
            **count, picked=picked,
            pages_held=sum(len(p) for p in self.kv.lane_pages
                           if p is not None),
            pages_written=written,
            pages_free=self.kv.allocator.n_free,
            lanes=lanes,
            retired=tuple((o.id, o.status) for o in finished
                          if o.status != STATUS_SHED))

    # -- internals -------------------------------------------------------------

    def _admit(self, finished: List[RequestOutput]):
        """Admit queued requests into free lanes; returns the ids
        admitted and the ids shed."""
        admitted, shed = [], []
        while self.queue:
            free = [l for l, a in enumerate(self.lanes) if a is None]
            if not free:
                break
            req, sp = self.queue[0]
            total = len(req.tokens) + sp.max_new_tokens
            if not self.kv.fits_ever(total):
                # could NEVER fit a lane: structured shed, not a crash.
                # Covers over-wide requests AND zero-length ones (empty
                # prompt + zero budget, total == 0): fits_ever is the
                # single gate, so ceil-div/alloc(0) never see them —
                # reaching admit with an unservable total is a bug it
                # raises on rather than leaking pages over
                self.queue.popleft()
                finished.append(RequestOutput(
                    id=req.id, tokens=np.zeros((0,), np.int32),
                    status=STATUS_SHED, fault_step=-1, n_steps=0,
                    prompt_len=0))
                shed.append(req.id)
                continue
            l = free[0]
            if not self.kv.admit(l, total):
                break  # transient page exhaustion: stay queued
            self.queue.popleft()
            a = _Lane(req=req, sp=sp, seq=self._seq,
                      key_base=self.engine._request_key(req.seed))
            self._seq += 1
            scfg = self.engine.scfg
            if scfg.request_timeout_s is not None:
                a.deadline = time.monotonic() + scfg.request_timeout_s
            self.lanes[l] = a
            self._lane_gen += 1
            admitted.append(req.id)
        return tuple(admitted), tuple(shed)

    def _retire(self, lane: int, finished: List[RequestOutput]) -> None:
        a = self.lanes[lane]
        self.kv.release(lane)
        self.lanes[lane] = None
        self._lane_gen += 1
        finished.append(RequestOutput(
            id=a.req.id, tokens=np.asarray(a.tokens, np.int32),
            status=a.status, fault_step=a.fault_step,
            n_steps=len(a.tokens), prompt_len=len(a.req.tokens)))
