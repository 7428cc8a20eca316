"""Request-level serving engine: continuous batching over a paged KV
cache, behind the typed ``submit()/step()/collect()`` API.

Two generations of serving loop live here:

  * the PAGED path (``repro.serve.scheduler.PagedScheduler``): requests
    admit into recycled decode lanes backed by a page-table-addressed KV
    pool, prompts prefill in fixed-size chunks interleaved with decode
    steps, and the whole engine compiles exactly TWO step programs — one
    ``[n_lanes]``-wide decode and one ``[n_lanes, chunk]`` prefill —
    that never retrace as requests come and go;
  * the FIXED path (``generate_with_status_fixed``): the PR 5-7
    lockstep batch loop, kept verbatim as the fallback for model
    families the paged attention path does not cover (encoder-decoder,
    prefix-token conditioning, multi-device meshes) and as the reference
    the shim is proven bitwise-equal against.

``generate()`` / ``generate_with_status()`` remain the batch-shaped
surface: on paged-capable models they are thin shims that submit one
request per batch row to a cached fixed-geometry scheduler and reshape
the ``RequestOutput``s into the legacy ``GenerateResult``.

Robustness contract (see ``docs/robustness.md`` for the fault model):

  * one poisoned lane never takes down the batch: a NaN/Inf logit
    quarantines THAT request to a structured ``quarantined_nonfinite``
    status while its peers keep decoding bitwise-unchanged;
  * int8 decode degrades instead of corrupting: a fixed-scale saturation
    probe (calibrated on each request's first decode logits) flags
    requests whose activation range drifted past the int8 envelope, and
    with ``fp32_fallback`` their remaining tokens come from the retained
    full-precision weights;
  * a wall-clock budget (``request_timeout_s``) converts a hung host
    step into per-request ``timeout`` statuses with partial tokens;
  * admission is never a crash: a request that could never fit a lane's
    page budget (or a batch row past ``max_lanes``) is shed with a
    structured ``shed`` status, ``fault_step = -1``.

The guards ride INSIDE the jitted token pick (one fused dispatch per
step either way), so the traced decode HLO — dense or paged — is
byte-identical with guards on/off and all PR 2-4 HLO invariants (single
packed-QKV GEMM dispatch, zero int8 bounces, schedule determinism) are
untouched.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import Model
from repro.robust.guards import (
    STATUS_DEGRADED,
    STATUS_NONFINITE,
    STATUS_OK,
    STATUS_SHED,
    STATUS_TIMEOUT,
    GenerateResult,
    NumericalHealthError,
)
from repro.serve.api import Request, RequestOutput, SamplingParams, StepRecord
from repro.serve.scheduler import PagedScheduler
from repro.serve.trace import SUBMIT, span

_ON_NONFINITE = ("quarantine", "raise", "off")

# ServeConfig fields that moved to SamplingParams (PR 8); kept as
# engine-wide DEFAULTS for requests that do not carry their own.
_SAMPLING_DEFAULTS = dict(max_new_tokens=32, eos_id=None, greedy=True,
                          temperature=1.0)


def _decode_jit(model: Model):
    """The fixed-path decode-step program: KV cache donated (argnums 1).

    Single construction site, used by both ``ServeEngine.__init__`` and
    the contract auditor (``ServeEngine.decode_step_lowered``) — the
    served program and the audited program cannot drift apart."""
    return jax.jit(model.decode_step, donate_argnums=(1,))


def _paged_decode_jit(model: Model):
    """The paged decode-step program: page pools donated (argnums 1).
    Shared by the scheduler and ``ServeEngine.paged_decode_lowered``."""
    return jax.jit(model.decode_step_paged, donate_argnums=(1,))


def _prefill_chunk_jit(model: Model):
    """The chunked-prefill program: page pools donated (argnums 1).
    Shared by the scheduler and ``ServeEngine.prefill_chunk_lowered``."""
    return jax.jit(model.prefill_chunk, donate_argnums=(1,))


def _inject_rows(buf: jnp.ndarray, rows: jnp.ndarray,
                 mask: jnp.ndarray) -> jnp.ndarray:
    """Overwrite the masked lanes of the [L, V] pick buffer with the
    matching rows of ``rows`` ([L, V]) — how the final prefill chunks'
    logits enter the fused pick without a per-lane retrace (the mask is
    data, not a trace constant)."""
    return jnp.where(mask[:, None], rows.astype(buf.dtype), buf)


@dataclasses.dataclass
class ServeConfig:
    # -- sampling DEFAULTS (deprecated here; see SamplingParams) -------------
    # These four moved to per-request ``repro.serve.api.SamplingParams``;
    # setting them on ServeConfig still works (they become the engine-wide
    # defaults via ``sampling_defaults()``) but warns: new code should pass
    # SamplingParams on the Request.
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    greedy: bool = True
    temperature: float = 1.0
    # End-to-end int8 serving: projection weights are quantized ONCE at
    # engine construction (column-wise scales) and decode runs
    # int8 x int8 -> int32 GEMMs with scales re-applied in the fused
    # epilogues — no fp32 dequant/requant bounce between GEMMs (the
    # paper's headline 14x-over-fp32 pipeline, §IV-C1).
    int8: bool = False
    # -- robustness ----------------------------------------------------------
    # per-lane health guards (finite logits; int8 saturation probe).
    # Cost rides inside the jitted token pick — see the guard-overhead
    # bench row; the traced decode HLO is identical either way.
    guards: bool = True
    # what a non-finite logit does: 'quarantine' the lane (structured
    # per-request status, peers unaffected), 'raise' NumericalHealthError
    # (fail-stop), or 'off' (pre-hardening behavior)
    on_nonfinite: str = "quarantine"
    # token id emitted for a lane past its quarantine/shed point
    pad_id: int = 0
    # dtype logits are sampled in (jit-cast before the pick)
    logits_dtype: str = "float32"
    # admission control: lanes beyond this are shed at the door (None =
    # admit the whole batch, the pre-hardening behavior)
    max_lanes: Optional[int] = None
    # wall-clock budget per request; on expiry running requests get a
    # structured 'timeout' status with their partial tokens (None = no
    # budget)
    request_timeout_s: Optional[float] = None
    # int8 only: retain the fp32 weights and finish saturated lanes on
    # them (memory cost: both copies live; off by default)
    fp32_fallback: bool = False
    # int8 only: per-lane fraction of logit values outside the calibrated
    # int8 envelope above which the lane degrades
    saturation_threshold: float = 0.25
    # -- paged scheduler geometry (jit-shape constants) ----------------------
    # decode lanes the default scheduler steps in one dispatch
    n_lanes: int = 4
    # positions per KV page
    page_size: int = 16
    # prompt tokens prefilled per chunk dispatch
    prefill_chunk: int = 32
    # per-request position ceiling (prompt + max_new) for the default
    # scheduler; sets the page-table width
    max_seq_len: int = 256
    # total pages in the pool (None = n_lanes full lanes' worth)
    n_pages: Optional[int] = None

    def __post_init__(self):
        # fail LOUDLY on bad values (mirrors XYZConfig's unknown-schedule
        # ValueError): a serving config typo silently defaulting is the
        # failure mode the validation exists to prevent
        moved = [k for k, d in _SAMPLING_DEFAULTS.items()
                 if getattr(self, k) != d]
        if moved:
            warnings.warn(
                f"ServeConfig sampling fields {moved} are deprecated: pass "
                f"repro.serve.api.SamplingParams on each Request (the "
                f"ServeConfig values remain the engine-wide defaults)",
                DeprecationWarning, stacklevel=3)
        # sampling validation lives with the fields now — SamplingParams
        # raises the exact messages this config always raised
        SamplingParams(greedy=self.greedy, temperature=self.temperature,
                       max_new_tokens=self.max_new_tokens,
                       eos_id=self.eos_id)
        if self.pad_id < 0:
            raise ValueError(f"pad_id must be >= 0, got {self.pad_id}")
        if self.on_nonfinite not in _ON_NONFINITE:
            raise ValueError(
                f"unknown on_nonfinite {self.on_nonfinite!r}; valid "
                f"modes are {_ON_NONFINITE}")
        try:
            dt = jnp.dtype(self.logits_dtype)
        except TypeError as e:
            raise ValueError(
                f"unknown logits_dtype {self.logits_dtype!r}: {e}") from None
        if not jnp.issubdtype(dt, jnp.floating):
            raise ValueError(
                f"logits_dtype must be a float dtype, got "
                f"{self.logits_dtype!r}")
        if self.max_lanes is not None and self.max_lanes < 1:
            raise ValueError(
                f"max_lanes must be >= 1 (or None), got {self.max_lanes}")
        if self.request_timeout_s is not None \
                and not (self.request_timeout_s > 0):
            raise ValueError(
                f"request_timeout_s must be > 0 (or None), got "
                f"{self.request_timeout_s}")
        if not (0.0 < self.saturation_threshold <= 1.0):
            raise ValueError(
                f"saturation_threshold must be in (0, 1], got "
                f"{self.saturation_threshold}")
        if self.fp32_fallback and not self.int8:
            raise ValueError(
                "fp32_fallback without int8 is meaningless: the engine "
                "already serves full precision")
        if self.n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {self.n_lanes}")
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.max_seq_len < 2:
            raise ValueError(
                f"max_seq_len must be >= 2, got {self.max_seq_len}")
        if self.n_pages is not None and self.n_pages < 1:
            raise ValueError(
                f"n_pages must be >= 1 (or None), got {self.n_pages}")

    def sampling_defaults(self) -> SamplingParams:
        """The engine-wide SamplingParams for requests that carry none —
        built from the deprecated ServeConfig fields, so old configs keep
        their exact behavior."""
        return SamplingParams(greedy=self.greedy,
                              temperature=self.temperature,
                              max_new_tokens=self.max_new_tokens,
                              eos_id=self.eos_id)


class ServeEngine:
    def __init__(self, model: Model, params, scfg: ServeConfig = ServeConfig()):
        self.model = model
        self._fp_params = None
        if scfg.int8:
            # one-shot weight-quantization pass (idempotent): the fp
            # weights are replaced, not duplicated — unless fp32_fallback
            # asks the engine to keep them for saturated-lane degradation
            fp = params
            params = model.quantize_params_for_serving(params)
            if scfg.fp32_fallback:
                self._fp_params = fp
        self.params = params
        self.scfg = scfg
        self._ldtype = jnp.dtype(scfg.logits_dtype)
        self._prefill = jax.jit(
            lambda p, b, ml: model.prefill(p, b, max_len=ml),
            static_argnums=(2,))
        self._decode = _decode_jit(model)
        # fp32 fallback decode: non-donating (it reads the cache the int8
        # step subsequently consumes) and traced on the fp param tree
        self._decode_fp = (jax.jit(model.decode_step)
                           if self._fp_params is not None else None)
        self._pick_guarded = jax.jit(self._pick_and_probe)
        # -- paged serving programs (one decode shape per lane count) ----
        self._paged_ok = model.supports_paged_serving
        if self._paged_ok:
            self._decode_paged = _paged_decode_jit(model)
            self._prefill_chunk = _prefill_chunk_jit(model)
            self._decode_paged_fp = (jax.jit(model.decode_step_paged)
                                     if self._fp_params is not None
                                     else None)
            self._pick_paged = jax.jit(self._pick_and_probe_lanes)
            self._inject_rows = jax.jit(_inject_rows)
        else:
            self._decode_paged = self._prefill_chunk = None
            self._decode_paged_fp = None
            self._pick_paged = self._inject_rows = None
        self._sched: Optional[PagedScheduler] = None
        self._finished: List[RequestOutput] = []
        self._shim_cache: Dict[tuple, PagedScheduler] = {}
        self._key_cache: Dict[int, np.ndarray] = {}

    # -- abstract lowerings for the HLO contract auditor ----------------------

    @classmethod
    def decode_step_lowered(cls, model: Model, scfg: ServeConfig,
                            batch: int, prompt_len: int):
        """Lower the fixed-path decode step ABSTRACTLY (no real weights)
        for the HLO contract auditor.

        Returns ``(lowered, donated_param_numbers)``: the same jit the
        engine serves (``_decode_jit`` — KV cache donated), lowered on
        ShapeDtypeStructs, plus the flat parameter numbers of the donated
        cache leaves (params flatten first, then cache — the numbers the
        compiled module's ``input_output_alias`` must cover for the
        donation to have actually been granted)."""
        aparams = model.abstract_params()
        if scfg.int8:
            aparams = jax.eval_shape(model.quantize_params_for_serving,
                                     aparams)
        max_len = prompt_len + scfg.max_new_tokens
        acache = model.abstract_cache(batch, max_len)
        tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = _decode_jit(model).lower(aparams, acache, tok, pos)
        n_p = len(jax.tree_util.tree_leaves(aparams))
        n_c = len(jax.tree_util.tree_leaves(acache))
        return lowered, tuple(range(n_p, n_p + n_c))

    @classmethod
    def paged_decode_lowered(cls, model: Model, scfg: ServeConfig,
                             n_lanes: int, pages_per_lane: int,
                             page_size: int):
        """Lower the scheduler's paged decode step abstractly — the SAME
        ``_paged_decode_jit`` the scheduler dispatches, with the page
        pools as the donated tree (params flatten first, then pools)."""
        aparams = model.abstract_params()
        if scfg.int8:
            aparams = jax.eval_shape(model.quantize_params_for_serving,
                                     aparams)
        acache = model.abstract_paged_cache(n_lanes * pages_per_lane,
                                            page_size)
        tok = jax.ShapeDtypeStruct((n_lanes, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((n_lanes,), jnp.int32)
        pt = jax.ShapeDtypeStruct((n_lanes, pages_per_lane), jnp.int32)
        lowered = _paged_decode_jit(model).lower(aparams, acache, tok,
                                                 pos, pt)
        n_p = len(jax.tree_util.tree_leaves(aparams))
        n_c = len(jax.tree_util.tree_leaves(acache))
        return lowered, tuple(range(n_p, n_p + n_c))

    @classmethod
    def prefill_chunk_lowered(cls, model: Model, scfg: ServeConfig,
                              n_lanes: int, chunk: int,
                              pages_per_lane: int, page_size: int):
        """Lower the scheduler's chunked-prefill step abstractly (pools
        donated, all lanes batched into one [L, chunk] dispatch — the
        same shapes the scheduler serves)."""
        aparams = model.abstract_params()
        if scfg.int8:
            aparams = jax.eval_shape(model.quantize_params_for_serving,
                                     aparams)
        acache = model.abstract_paged_cache(n_lanes * pages_per_lane,
                                            page_size)
        tok = jax.ShapeDtypeStruct((n_lanes, chunk), jnp.int32)
        pos = jax.ShapeDtypeStruct((n_lanes, chunk), jnp.int32)
        pt = jax.ShapeDtypeStruct((n_lanes, pages_per_lane), jnp.int32)
        last = jax.ShapeDtypeStruct((n_lanes,), jnp.int32)
        lowered = _prefill_chunk_jit(model).lower(aparams, acache, tok,
                                                  pos, pt, last)
        n_p = len(jax.tree_util.tree_leaves(aparams))
        n_c = len(jax.tree_util.tree_leaves(acache))
        return lowered, tuple(range(n_p, n_p + n_c))

    @classmethod
    def from_checkpoint(cls, model: Model, ckpt_dir: str,
                        step: Optional[int] = None,
                        scfg: ServeConfig = ServeConfig(),
                        fallback: bool = True) -> "ServeEngine":
        """Restore params onto the model's mesh and serve them.  Legacy
        checkpoints with unpacked wq/wk/wv leaves are packed into the
        ``wqkv`` schema in place (CheckpointManager migration).  With
        ``fallback`` (the serving default) a checkpoint that fails
        integrity verification is reported and the newest earlier intact
        step is served instead — stale weights beat no weights.  With
        ``scfg.int8`` the restored weights immediately go through the
        one-shot serving quantization pass (see ``ServeEngine.__init__``);
        the fp checkpoint on disk is untouched."""
        from repro.checkpoint import CheckpointManager
        from repro.launch.specs import param_io_specs
        mgr = CheckpointManager(ckpt_dir)
        abstract, specs = param_io_specs(model)
        _, params = mgr.restore(step, abstract, mesh=model.mesh,
                                specs=specs, defs=model.param_defs(),
                                fallback=fallback)
        return cls(model, params, scfg)

    # -- token pick + fused health probe --------------------------------------

    def _pick_math(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        v = self.model.cfg.vocab
        logits = logits[:, :v].astype(self._ldtype)
        if self.scfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / max(self.scfg.temperature, 1e-6)
        return jax.random.categorical(key, scaled).astype(jnp.int32)

    def _pick(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        return self._pick_math(logits, key)

    def _pick_and_probe(self, logits, key, calib):
        """Token pick + per-lane health probes in ONE jitted dispatch (the
        guarded path costs one fused call, same as the unguarded pick):

          finite  [B] — all-finite over the lane's real-vocab logits;
          absmax  [B] — per-lane absmax (step-0 calibration source);
          sat     [B] — fraction of the lane's logits that saturate a
                        fixed int8 scale calibrated to ``calib`` (the
                        quantize-epilogue saturation counter applied to
                        the decode canary tensor).
        """
        from repro.kernels.quantize import (quantize_fixed_scale,
                                            saturation_fraction)
        v = self.model.cfg.vocab
        real = logits[:, :v]
        tok = self._pick_math(logits, key)
        finite = jnp.all(jnp.isfinite(real), axis=-1)
        absmax = jnp.max(jnp.abs(real), axis=-1)
        scale = jnp.maximum(calib, 1e-6)[:, None] / 127.0
        sat = saturation_fraction(quantize_fixed_scale(real, scale))
        return tok, finite, absmax, sat

    def _pick_and_probe_lanes(self, logits, key_base, steps, greedy,
                              temp, calib):
        """Per-REQUEST pick + probes, one fused dispatch for all lanes.

        Unlike ``_pick_and_probe`` (one engine-global key and sampling
        mode), every lane carries its own request's sampling: ``greedy``
        [L] bool mask, ``temp`` [L] temperatures, and a private key
        stream ``fold_in(key_base[l], steps[l])`` rooted at the request's
        seed — so a sampled request's tokens are identical no matter
        which lane it lands on or how its neighbors churn."""
        from repro.kernels.quantize import (quantize_fixed_scale,
                                            saturation_fraction)
        v = self.model.cfg.vocab
        real = logits[:, :v]
        lf = real.astype(self._ldtype)
        tok_g = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        keys = jax.vmap(jax.random.fold_in)(key_base, steps)
        scaled = lf / jnp.maximum(temp, 1e-6)[:, None]
        tok_s = jax.vmap(jax.random.categorical)(keys, scaled)
        tok = jnp.where(greedy, tok_g, tok_s.astype(jnp.int32))
        finite = jnp.all(jnp.isfinite(real), axis=-1)
        absmax = jnp.max(jnp.abs(real), axis=-1)
        scale = jnp.maximum(calib, 1e-6)[:, None] / 127.0
        sat = saturation_fraction(quantize_fixed_scale(real, scale))
        return tok, finite, absmax, sat

    def _request_key(self, seed: int) -> np.ndarray:
        """Host-cached uint32[2] PRNGKey(seed) — roots a request's
        private fold_in key stream (one tiny device dispatch per distinct
        seed, not per admission)."""
        k = self._key_cache.get(seed)
        if k is None:
            if len(self._key_cache) > 4096:
                self._key_cache.clear()
            k = np.asarray(jax.random.PRNGKey(seed))
            self._key_cache[seed] = k
        return k

    # -- request-level API -----------------------------------------------------

    @property
    def scheduler(self) -> PagedScheduler:
        """The engine's default continuous-batching scheduler (built
        lazily from the ServeConfig paged-geometry fields)."""
        if self._sched is None:
            self._require_paged()
            scfg = self.scfg
            ppl = -(-scfg.max_seq_len // scfg.page_size)
            n_pages = (scfg.n_pages if scfg.n_pages is not None
                       else scfg.n_lanes * ppl)
            self._sched = PagedScheduler(
                self, n_lanes=scfg.n_lanes, pages_per_lane=ppl,
                n_pages=n_pages, page_size=scfg.page_size,
                chunk=scfg.prefill_chunk)
        return self._sched

    def submit(self, request: Request) -> None:
        """Queue one request (admitted into a lane as capacity frees)."""
        with span(SUBMIT):
            self.scheduler.submit(request)

    def step(self, fault_plan=None) -> List[RequestOutput]:
        """Advance the scheduler one iteration: admissions, at most one
        prefill chunk per prefilling lane, one decode dispatch, one fused
        pick.  Returns the requests that finished THIS step (they are
        also buffered for ``collect()``)."""
        outs = self.scheduler.step(fault_plan)
        self._finished.extend(outs)
        return outs

    @property
    def last_step(self) -> Optional[StepRecord]:
        """The default scheduler's record of its latest ``step()`` (None
        before the first); see ``StepRecord``."""
        return self._sched.last_step if self._sched is not None else None

    def collect(self) -> List[RequestOutput]:
        """Drain every finished-but-uncollected RequestOutput."""
        out, self._finished = self._finished, []
        return out

    @property
    def pending(self) -> bool:
        """True while the default scheduler holds queued or active work."""
        return self._sched is not None and self._sched.has_work

    def drain(self, fault_plan=None) -> List[RequestOutput]:
        """Step until idle; returns all outputs finished along the way
        (including previously buffered ones)."""
        self._finished.extend(self.scheduler.run_to_completion(fault_plan))
        return self.collect()

    def _require_paged(self) -> None:
        if not self._paged_ok:
            raise NotImplementedError(
                "paged serving needs a single-device decoder-only model "
                "with global/local/chunked attention; use "
                "generate_with_status_fixed() for this model")

    def _shim_scheduler(self, n_lanes: int, prompt_len: int,
                        max_new: int) -> PagedScheduler:
        """Fixed-geometry scheduler for the ``generate(batch)`` shim: one
        lane per batch row, pool sized so every row admits immediately
        (the legacy loop's capacity), cached per (lanes, prompt, budget)
        so repeated same-shape calls reuse the compiled programs."""
        key = (n_lanes, prompt_len, max_new)
        sched = self._shim_cache.get(key)
        if sched is None:
            ps = self.scfg.page_size
            ppl = -(-(prompt_len + max_new) // ps)
            sched = PagedScheduler(self, n_lanes=n_lanes,
                                   pages_per_lane=ppl,
                                   n_pages=n_lanes * ppl, page_size=ps,
                                   chunk=self.scfg.prefill_chunk)
            while len(self._shim_cache) >= 4:
                self._shim_cache.pop(next(iter(self._shim_cache)))
            self._shim_cache[key] = sched
        return sched

    # -- batch-shaped generation (shims over the scheduler) -------------------

    def generate(self, batch: Dict[str, jnp.ndarray], seed: int = 0
                 ) -> np.ndarray:
        """batch['tokens'] [B, S] -> generated tokens [B, <=max_new]."""
        return self.generate_with_status(batch, seed).tokens

    def generate_with_status(self, batch: Dict[str, jnp.ndarray],
                             seed: int = 0,
                             fault_plan=None) -> GenerateResult:
        """Guarded generation with structured per-lane outcomes.

        On paged-capable models this is a thin shim over the scheduler:
        each batch row becomes a Request (engine-default sampling, shared
        seed) on a cached fixed-geometry scheduler, and the RequestOutputs
        are reassembled into the legacy GenerateResult — greedy outputs
        are bitwise-identical to the fixed loop's.  Other model families
        fall through to ``generate_with_status_fixed``.

        ``fault_plan`` (a ``repro.robust.FaultPlan``) injects
        deterministic faults for testing; ``None`` (production) leaves
        the loop on the exact pre-hardening compute path.
        """
        if not self._paged_ok:
            return self.generate_with_status_fixed(batch, seed, fault_plan)
        scfg = self.scfg
        plan = fault_plan if (fault_plan is not None
                              and fault_plan.enabled) else None
        if plan is not None:
            plan.on_generate_start()

        toks = np.asarray(batch["tokens"])
        b_full = toks.shape[0]
        if toks.ndim != 2 or toks.shape[1] == 0:
            # zero-length prompts can never be served (the first pick
            # needs at least one prefilled position): structured shed for
            # the whole batch, same contract as the scheduler's
            # fits_ever rejection — never a Request-validation crash
            return GenerateResult(
                tokens=np.zeros((b_full, 0), np.int32),
                status=[STATUS_SHED] * b_full,
                fault_step=np.full((b_full,), -1, np.int64),
                n_steps=0, timed_out=False, admitted=0)
        admit = b_full if scfg.max_lanes is None \
            else min(b_full, scfg.max_lanes)
        sp = scfg.sampling_defaults()
        sched = self._shim_scheduler(admit, toks.shape[1],
                                     sp.max_new_tokens)
        sched.reset_fault_state()
        for r in range(admit):
            sched.submit(Request(id=r, tokens=toks[r], sampling=sp,
                                 seed=seed))
        try:
            outs = sched.run_to_completion(plan)
        except Exception:
            # a raise mid-drain (on_nonfinite='raise') leaves lanes
            # mapped; drop the scheduler rather than reuse a dirty one
            self._shim_cache = {k: v for k, v in self._shim_cache.items()
                                if v is not sched}
            raise

        n_steps = max((len(o.tokens) for o in outs), default=0)
        tokens = np.full((b_full, n_steps), scfg.pad_id, np.int32)
        status = np.array([STATUS_SHED] * b_full, dtype=object)
        fault_step = np.full((b_full,), -1, np.int64)
        for o in outs:
            tokens[o.id, :len(o.tokens)] = o.tokens
            status[o.id] = o.status
            fault_step[o.id] = o.fault_step
        return GenerateResult(tokens=tokens, status=list(status),
                              fault_step=fault_step, n_steps=n_steps,
                              timed_out=sched.timed_out, admitted=admit)

    def generate_with_status_fixed(self, batch: Dict[str, jnp.ndarray],
                                   seed: int = 0,
                                   fault_plan=None) -> GenerateResult:
        """The PR 5-7 lockstep fixed-batch loop: every lane prefills and
        decodes in step, one engine-global sampling config.  Kept as the
        serving path for model families the paged attention kernel does
        not cover, and as the reference the scheduler shim is proven
        bitwise-equal against."""
        scfg = self.scfg
        plan = fault_plan if (fault_plan is not None
                              and fault_plan.enabled) else None
        if plan is not None:
            plan.on_generate_start()

        # admission control: shed surplus lanes before any compute
        b_full = batch["tokens"].shape[0]
        toks0 = np.asarray(batch["tokens"])
        if toks0.ndim != 2 or toks0.shape[1] == 0:
            # same zero-length structured shed as the scheduler shim
            return GenerateResult(
                tokens=np.zeros((b_full, 0), np.int32),
                status=[STATUS_SHED] * b_full,
                fault_step=np.full((b_full,), -1, np.int64),
                n_steps=0, timed_out=False, admitted=0)
        admit = b_full if scfg.max_lanes is None \
            else min(b_full, scfg.max_lanes)
        if admit < b_full:
            batch = {k: v[:admit] for k, v in batch.items()}

        cfg = self.model.cfg
        b, s = batch["tokens"].shape
        prompt_len = s + (cfg.prefix_tokens or 0)
        max_len = prompt_len + scfg.max_new_tokens
        logits, cache = self._prefill(self.params, batch, max_len)
        # the clock starts once prefill is dispatched: the budget bounds
        # the decode loop (where a hung host step strands a request), not
        # the one-time jit compile of a cold engine
        deadline = (time.monotonic() + scfg.request_timeout_s
                    if scfg.request_timeout_s is not None else None)

        status = np.array([STATUS_OK] * admit, dtype=object)
        fault_step = np.full((admit,), -1, np.int64)
        done = np.zeros((admit,), bool)
        degraded = np.zeros((admit,), bool)
        timed_out = False
        calib = None          # step-0 per-lane absmax (int8 probe)
        fp_logits = None      # fp32-fallback logits for degraded lanes
        out: List[np.ndarray] = []

        key = jax.random.PRNGKey(seed)
        pick_key = key  # token 0 samples with the unsplit key (legacy)
        guards_on = scfg.guards and scfg.on_nonfinite != "off"
        sat_on = scfg.guards and scfg.int8

        for i in range(scfg.max_new_tokens):
            if plan is not None:
                plan.maybe_stall(i)
            if deadline is not None and time.monotonic() > deadline:
                running = ~done
                status[running] = STATUS_TIMEOUT
                fault_step[running & (fault_step < 0)] = i
                timed_out = True
                break
            if plan is not None:
                logits = plan.perturb_logits(i, logits)

            if guards_on or sat_on:
                cal = (jnp.ones((admit,), jnp.float32) if calib is None
                       else calib)
                tok, fin_j, absmax_j, sat_j = self._pick_guarded(
                    logits, pick_key, cal)
                if guards_on:
                    newly_bad = ~np.asarray(fin_j) & ~done
                    if newly_bad.any():
                        lanes = np.flatnonzero(newly_bad)
                        if scfg.on_nonfinite == "raise":
                            raise NumericalHealthError(
                                f"non-finite logits at decode step {i} in "
                                f"lanes {lanes.tolist()}")
                        status[newly_bad] = STATUS_NONFINITE
                        fault_step[newly_bad & (fault_step < 0)] = i
                if sat_on:
                    if calib is None:
                        calib = jnp.maximum(absmax_j, 1e-6)
                    else:
                        sat = np.asarray(sat_j)
                        newly_sat = ((sat > scfg.saturation_threshold)
                                     & ~degraded & ~done
                                     & np.asarray(fin_j))
                        if newly_sat.any():
                            degraded |= newly_sat
                            mark = newly_sat & (status == STATUS_OK)
                            status[mark] = STATUS_DEGRADED
                            fault_step[mark & (fault_step < 0)] = i
            else:
                tok = self._pick(logits, pick_key)

            if fp_logits is not None:
                # degraded lanes pick from the fp32 fallback logits; the
                # same key keeps healthy lanes bitwise unchanged
                tok_fp = self._pick(fp_logits, pick_key)
                tok = jnp.where(jnp.asarray(degraded), tok_fp, tok)

            tok_np = np.asarray(tok)
            quarantined = status == STATUS_NONFINITE
            if quarantined.any():
                tok_np = np.where(quarantined, scfg.pad_id,
                                  tok_np).astype(tok_np.dtype)
            out.append(tok_np)
            if scfg.eos_id is not None:
                done = done | (tok_np == scfg.eos_id)
            done = done | quarantined
            if done.all() or i == scfg.max_new_tokens - 1:
                break

            pos = jnp.asarray(prompt_len + i, jnp.int32)
            tok_dev = jnp.asarray(tok_np)[:, None]
            if degraded.any() and self._decode_fp is not None:
                # dispatched BEFORE the donating int8 step: it reads the
                # cache buffers that step consumes
                fp_logits, _ = self._decode_fp(self._fp_params, cache,
                                               tok_dev, pos)
            else:
                fp_logits = None
            logits, cache = self._decode(self.params, cache, tok_dev, pos)
            key, pick_key = jax.random.split(key)

        tokens = (np.stack(out, axis=1) if out
                  else np.zeros((admit, 0), np.int32))
        if admit < b_full:
            shed = b_full - admit
            full = np.full((b_full, tokens.shape[1]), scfg.pad_id,
                           tokens.dtype)
            full[:admit] = tokens
            tokens = full
            status = np.concatenate(
                [status, np.array([STATUS_SHED] * shed, dtype=object)])
            # shed lanes never ran: fault_step is the documented -1
            # sentinel, not 0 (which would claim a step-0 fault)
            fault_step = np.concatenate(
                [fault_step, np.full((shed,), -1, np.int64)])
        return GenerateResult(tokens=tokens, status=list(status),
                              fault_step=fault_step, n_steps=len(out),
                              timed_out=timed_out, admitted=admit)

    # -- introspection ---------------------------------------------------------

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-program counts per serving jit — what the
        zero-recompilation-under-churn test pins down."""
        sizes = {"decode": self._decode._cache_size()}
        if self._paged_ok:
            sizes["decode_paged"] = self._decode_paged._cache_size()
            sizes["prefill_chunk"] = self._prefill_chunk._cache_size()
            sizes["pick_paged"] = self._pick_paged._cache_size()
        return sizes
