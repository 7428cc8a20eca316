"""Serving launcher: batched greedy generation with a KV cache, health
guards, and optional fault-injection drills.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --smoke \
        --batch 4 --prompt-len 32 --max-new 16

Chaos drill (prove the guards on a live engine — lane 1 gets NaN logits
at step 2 and is quarantined while its peers finish):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --smoke \
        --inject-nan 2:1 --timeout-s 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.lm import Model
from repro.robust import FaultPlan, LogitFault, StallFault, generate_with_retry
from repro.serve.engine import ServeConfig, ServeEngine


def _parse_faults(args) -> FaultPlan | None:
    logit_faults = []
    stalls = []
    for spec in args.inject_nan or ():
        step, lane = spec.split(":")
        logit_faults.append(LogitFault(step=int(step), lanes=(int(lane),),
                                       kind="nan"))
    for spec in args.inject_saturation or ():
        step, lane = spec.split(":")
        logit_faults.append(LogitFault(step=int(step), lanes=(int(lane),),
                                       kind="scale", scale=100.0))
    for spec in args.inject_stall or ():
        step, seconds = spec.split(":")
        stalls.append(StallFault(step=int(step), seconds=float(seconds)))
    if not (logit_faults or stalls or args.inject_transient):
        return None
    return FaultPlan(seed=args.seed, logit_faults=tuple(logit_faults),
                     stalls=tuple(stalls),
                     fail_first_generates=args.inject_transient)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="end-to-end int8 decode: one-shot column-wise "
                         "weight quantization, int8 GEMMs with scales "
                         "re-applied in the fused epilogues (single-shard)")
    ap.add_argument("--fp32-fallback", action="store_true",
                    help="with --int8: keep the fp32 weights and finish "
                         "saturation-degraded lanes on them")
    ap.add_argument("--no-guards", action="store_true",
                    help="disable per-lane numerical-health guards "
                         "(pre-hardening decode loop)")
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="wall-clock budget per request; expired lanes "
                         "get a structured 'timeout' status")
    ap.add_argument("--max-lanes", type=int, default=None,
                    help="admission limit; surplus batch rows are shed "
                         "with a 'shed' status instead of decoded")
    ap.add_argument("--retries", type=int, default=2,
                    help="transient-failure retries (exponential backoff)")
    # fault-injection drills ("step:lane" / "step:seconds")
    ap.add_argument("--inject-nan", action="append", metavar="STEP:LANE")
    ap.add_argument("--inject-saturation", action="append",
                    metavar="STEP:LANE")
    ap.add_argument("--inject-stall", action="append",
                    metavar="STEP:SECONDS")
    ap.add_argument("--inject-transient", type=int, default=0,
                    help="fail the first N generate() calls with a "
                         "retryable error (exercises the retry wrapper)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = make_mesh(jax.device_count(), 1)
    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg, mesh)
    params = model.init_params(args.seed)

    key = jax.random.PRNGKey(args.seed)
    text_len = args.prompt_len - (cfg.prefix_tokens or 0)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, text_len), 0, cfg.vocab, jnp.int32)}
    if cfg.prefix_tokens:
        batch["patches"] = jax.random.normal(
            key, (args.batch, cfg.prefix_tokens, cfg.d_model), jnp.float32)
    if cfg.encdec:
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_frames, cfg.d_model), jnp.float32)

    eng = ServeEngine(model, params,
                      ServeConfig(max_new_tokens=args.max_new,
                                  int8=args.int8,
                                  fp32_fallback=args.fp32_fallback,
                                  guards=not args.no_guards,
                                  request_timeout_s=args.timeout_s,
                                  max_lanes=args.max_lanes))
    plan = _parse_faults(args)
    t0 = time.time()
    res = generate_with_retry(eng, batch, args.seed, retries=args.retries,
                              fault_plan=plan)
    dt = time.time() - t0
    print(f"generated {res.tokens.shape} tokens in {dt:.2f}s "
          f"({res.tokens.size / dt:.1f} tok/s), "
          f"{res.admitted}/{args.batch} lanes admitted"
          f"{', TIMED OUT' if res.timed_out else ''}")
    for lane, (st, fs) in enumerate(zip(res.status, res.fault_step)):
        extra = f" (at step {fs})" if fs >= 0 else ""
        print(f"  lane {lane}: {st}{extra}")
    print(res.tokens[:, :12])


if __name__ == "__main__":
    main()
