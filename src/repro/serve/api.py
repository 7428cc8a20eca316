"""Typed request-level serving API.

The PR 1-7 engine exposed one batch-shaped call — ``generate(batch)``
with engine-global sampling settings.  Real traffic is per-request:
prompts of different lengths arrive at different times, each with its
own sampling knobs and token budget.  This module is the contract for
that surface:

  * ``SamplingParams`` — per-request sampling (previously engine-global
    ``ServeConfig`` fields), validated as loudly as the engine config;
  * ``Request``        — one prompt plus its sampling params;
  * ``RequestOutput``  — the generated tokens plus the PR 5 structured
    status/fault_step, per request instead of per batch lane;
  * ``StepRecord``     — what one scheduler step did, counted where the
    work happened (``ServeEngine.last_step``).

``ServeEngine.submit()/step()/collect()`` consumes and produces these;
``generate()``/``generate_with_status()`` remain as thin fixed-batch
shims over the same scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import numpy as np

from repro.robust.guards import STATUS_OK


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling: greedy or temperature sampling, the token
    budget, and the stop token.  Defaults match the historical
    ``ServeConfig`` defaults; ``ServeConfig.sampling_defaults()`` builds
    the engine-default instance for requests that do not carry one."""

    greedy: bool = True
    temperature: float = 1.0
    max_new_tokens: int = 32
    eos_id: Optional[int] = None

    def __post_init__(self):
        # the SAME messages ServeConfig.__post_init__ has always raised —
        # a per-request typo fails as loudly as an engine-config typo
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if not (self.temperature >= 0.0):  # also rejects NaN
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.eos_id is not None and self.eos_id < 0:
            raise ValueError(f"eos_id must be >= 0, got {self.eos_id}")


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request: an id the caller correlates outputs by, the
    prompt token ids, and optional per-request sampling (``None`` = the
    engine's ``ServeConfig`` defaults).  ``seed`` roots the request's
    private sampling-key stream — the step-``t`` key is
    ``fold_in(PRNGKey(seed), t)``, independent of which lane the request
    lands on or what its neighbors do, so sampled tokens are reproducible
    under arbitrary scheduler churn."""

    id: Union[int, str]
    tokens: np.ndarray
    sampling: Optional[SamplingParams] = None
    seed: int = 0

    def __post_init__(self):
        toks = np.asarray(self.tokens)
        if toks.ndim != 1 or toks.size == 0:
            raise ValueError(
                f"Request.tokens must be a non-empty 1-D id array, got "
                f"shape {toks.shape}")
        if not np.issubdtype(toks.dtype, np.integer):
            raise ValueError(
                f"Request.tokens must be integer ids, got {toks.dtype}")
        object.__setattr__(self, "tokens", toks.astype(np.int32))


@dataclasses.dataclass
class RequestOutput:
    """Structured per-request outcome (the per-lane ``GenerateResult``
    fields, re-keyed by request).

    ``tokens``     [n] generated ids — real tokens only, no pad filler
                   (a quarantined request's array simply ends at its
                   fault step).
    ``status``     one of ``repro.robust.guards.STATUSES``.
    ``fault_step`` step at which the request left ``ok``; -1 if it never
                   did (including ``shed`` — rejected before any step).
    ``n_steps``    decode steps executed for this request.
    ``prompt_len`` prompt tokens consumed (0 for shed requests).
    """

    id: Union[int, str]
    tokens: np.ndarray
    status: str = STATUS_OK
    fault_step: int = -1
    n_steps: int = 0
    prompt_len: int = 0

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK


@dataclasses.dataclass(frozen=True)
class StepRecord:
    """What one ``PagedScheduler.step`` did, counted where the work
    happened.  The scheduler keeps only its latest record
    (``PagedScheduler.last_step``, re-exported as
    ``ServeEngine.last_step``); a caller that wants a history copies it
    after each step.  Request ids tie a request's steps together.

    ``step``            index of the step on its scheduler, from 0.
    ``queue_depth``     requests queued when the step started.
    ``admitted``        ids admitted into lanes this step.
    ``shed``            ids shed at admission (they never held a lane).
    ``prefill_lanes``   lanes in the prefill dispatch with a prompt chunk.
    ``prefill_rows``    real prompt rows in that dispatch.
    ``prefill_rows_dispatched``  rows it computed (lanes x chunk; 0
                        without a prefill dispatch).
    ``decode_lanes``    lanes the decode dispatch stepped for a request.
    ``decode_lanes_dispatched``  lanes it computed (0 without one).
    ``decode_pages_live``  pages the decode's attention reads: over the
                        lanes it stepped for a request, the pages
                        holding a position at or below the lane's
                        position (0 without a decode).  Beside
                        ``decode_lanes_dispatched`` x pages a lane, the
                        share of the page table the kernel skips.
    ``picked``          lanes the token pick read.
    ``pages_held``      pages mapped to lanes at the step's end.
    ``pages_written``   of those, pages holding a written position.
    ``pages_free``      pages left in the pool's free list.
    ``lanes``           ``(id, prompt tokens prefilled, tokens picked,
                        prompt length)`` of every occupied lane at the
                        step's end, in lane order.
    ``retired``         ``(id, status)`` of every request that left a
                        lane this step.
    """

    step: int
    queue_depth: int
    admitted: Tuple[Union[int, str], ...]
    shed: Tuple[Union[int, str], ...]
    prefill_lanes: int
    prefill_rows: int
    prefill_rows_dispatched: int
    decode_lanes: int
    decode_lanes_dispatched: int
    decode_pages_live: int
    picked: int
    pages_held: int
    pages_written: int
    pages_free: int
    lanes: Tuple[Tuple[Union[int, str], int, int, int], ...]
    retired: Tuple[Tuple[Union[int, str], str], ...]
