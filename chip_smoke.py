#!/usr/bin/env python3
"""Chip smoke test: the serving path of internlm2-1.8b at its full
published widths on one TPU v5e chip.

    python chip_smoke.py                # one v5e chip
    python chip_smoke.py --four-chips   # the multi-chip paths, 2x2 v5e host

One chip: ``ServeEngine`` -> ``PagedScheduler`` -> paged KV cache ->
Pallas kernels serve 8 seeded greedy requests (prompts of 64-512 tokens,
32 new tokens each), once on the bf16-compute path and once on the int8
path.  Every request must end ``ok`` with all its tokens, a second pass
must repeat the first bit for bit without recompiling, the prefill logits
of the Pallas path must agree with the XLA path on the same chip, and the
compiled paged decode step must contain Pallas kernels.

``--four-chips`` runs only what exists across chips: the XYZ collective
matmul under all four schedules, the full-width loss forward on a (1, 4)
model-parallel mesh against one device, and three train steps on it.

Weights are random (``init_params(seed)``); nothing is downloaded.  No
phase catches an exception, so any failure exits nonzero.  The last line
of stdout is one JSON object naming the device.  With no TPU attached the
script exits nonzero before doing anything.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "internlm2-1.8b"
V5E_KINDS = ("TPU v5 lite", "TPU v5e")   # the only device TPU_V5E models


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Sizes of one run.  ``chip_smoke`` uses ``FULL_RUN``; the phases
    take it as an argument so they can be rehearsed small."""
    n_requests: int = 8
    prompt_len: tuple = (64, 512)      # inclusive range, seeded draw
    new_tokens: int = 32
    n_lanes: int = 8
    page_size: int = 16
    prefill_chunk: int = 128
    logits_batch: tuple = (2, 256)     # prefill-logits check [B, S]
    xyz_rows: tuple = (2, 128)         # xyz matmul activations [B, S]
    train_batch: tuple = (4, 256)      # loss forward / train steps [B, S]
    train_steps: int = 3


FULL_RUN = Geometry()


def log(phase: str, **fields) -> None:
    """One line per phase, ending with the device's peak memory so far."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    fields["peak_bytes"] = stats.get("peak_bytes_in_use", "not_reported")
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require_v5e(n_chips: int):
    """Exit nonzero unless JAX sees at least ``n_chips`` TPU v5e chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees platform "
                 f"{devs[0].platform!r})")
    if devs[0].device_kind not in V5E_KINDS:
        sys.exit(f"chip_smoke: device {devs[0].device_kind!r} is not a TPU "
                 f"v5e, the only device the planner models")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX sees {len(devs)}")
    return devs


@contextlib.contextmanager
def kernel_mode(mode: str, precision=None):
    """Trace under kernel mode ``mode`` (and matmul ``precision``)."""
    import jax
    from repro.kernels import ops
    prev = ops.kernel_mode()
    ops.set_kernel_mode(mode)
    try:
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            yield
    finally:
        ops.set_kernel_mode(prev)


def check(ok, what) -> None:
    """Fail the run; unlike ``assert``, also under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# ---------------------------------------------------------------------------
# one chip: paged serving
# ---------------------------------------------------------------------------

def make_prompts(vocab: int, geo: Geometry, seed: int):
    rng = np.random.default_rng(seed)
    lo, hi = geo.prompt_len
    lens = rng.integers(lo, hi + 1, size=geo.n_requests)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def serve_config(geo: Geometry, int8: bool):
    from repro.serve.engine import ServeConfig
    return ServeConfig(int8=int8, n_lanes=geo.n_lanes,
                       page_size=geo.page_size,
                       prefill_chunk=geo.prefill_chunk,
                       max_seq_len=geo.prompt_len[1] + geo.new_tokens)


def serve(model, params, scfg, prompts, geo: Geometry, seed: int, tag: str):
    """Serve ``prompts`` twice through submit/drain: a cold pass that
    compiles and a warm pass that must repeat it bit for bit.  Returns
    the engine (its ``params`` are the served, possibly int8, tree)."""
    from repro.robust.guards import STATUS_OK
    from repro.serve.api import Request, SamplingParams
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(model, params, scfg)
    sp = SamplingParams(greedy=True, max_new_tokens=geo.new_tokens)
    passes = []
    for _ in range(2):
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.submit(Request(id=i, tokens=p, sampling=sp, seed=seed))
        outs = sorted(eng.drain(), key=lambda o: o.id)
        passes.append((time.perf_counter() - t0, outs))
    (cold_s, cold), (warm_s, warm) = passes
    for outs in (cold, warm):
        check([o.id for o in outs] == list(range(len(prompts))), (
            f"{tag}: outputs for {[o.id for o in outs]}"))
        bad = [(o.id, o.status, len(o.tokens)) for o in outs
               if o.status != STATUS_OK or len(o.tokens) != geo.new_tokens]
        check(not bad, f"{tag}: requests not served in full: {bad}")
    for a, b in zip(cold, warm):
        check(np.array_equal(a.tokens, b.tokens), (
            f"{tag}: request {a.id} decoded differently on the second pass"))
    sizes = eng.jit_cache_sizes()
    check(sizes["decode_paged"] == 1 and sizes["prefill_chunk"] == 1, (
        f"{tag}: step programs recompiled: {sizes}"))
    log(f"serve_{tag}", ok=f"{len(warm)}/{len(prompts)}",
        tokens_each=geo.new_tokens,
        prompt_lens=",".join(str(len(p)) for p in prompts),
        compile_s=f"{cold_s - warm_s:.1f}", wall_s=f"{warm_s:.2f}",
        first_tokens=",".join(str(int(o.tokens[0])) for o in warm))
    return eng


def prefill_logits(model, params, tokens, mode: str, precision=None):
    """Last-token prefill logits over the real vocab, traced under
    ``mode``; also returns the compiled program's Pallas kernel count."""
    import jax
    with kernel_mode(mode, precision):
        f = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0])
        compiled = f.lower(params, tokens).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    logits = np.asarray(compiled(params, tokens), np.float32)
    return logits[:, :model.cfg.vocab], n_kernels


def check_prefill_logits(model, params, ref_model, ref_params, tokens,
                         tag: str, pallas_mode: str = "pallas"):
    """Pallas-path prefill logits against the XLA path on the same chip.

    Tolerance: the repo's consistency budget — the two paths may differ
    by at most 4x the XLA path's own rounding error, measured against an
    fp32 reference (fp32 compute, ``highest`` matmul precision, the
    unquantized weights)."""
    got, n_pallas = prefill_logits(model, params, tokens, pallas_mode)
    xla, n_xla = prefill_logits(model, params, tokens, "xla")
    ref, _ = prefill_logits(ref_model, ref_params, tokens, "xla", "highest")
    check(n_xla == 0, f"{tag}: XLA-mode prefill holds {n_xla} kernels")
    if pallas_mode == "pallas":
        check(n_pallas > 0, f"{tag}: Pallas-mode prefill holds no kernel")
    err, own = max_err(got, xla), max_err(xla, ref)
    tol = 4.0 * own
    log(f"logits_{tag}", max_abs_err=f"{err:.6g}",
        rel_err=f"{err / float(np.max(np.abs(xla))):.6g}",
        tol=f"{tol:.6g}", xla_vs_fp32_ref=f"{own:.6g}",
        prefill_kernels=n_pallas)
    check(np.all(np.isfinite(got)), f"{tag}: non-finite Pallas logits")
    check(err <= tol, f"{tag}: Pallas vs XLA logits {err} > {tol}")


def count_decode_kernels(model, scfg, tag: str) -> int:
    """Pallas kernels in the compiled paged decode step the scheduler
    serves (the same jit, lowered at the engine's geometry)."""
    from repro.serve.engine import ServeEngine
    ppl = -(-scfg.max_seq_len // scfg.page_size)
    lowered, _ = ServeEngine.paged_decode_lowered(
        model, scfg, scfg.n_lanes, ppl, scfg.page_size)
    n = lowered.compile().as_text().count("tpu_custom_call")
    log(f"decode_step_{tag}", tpu_custom_call=n)
    return n


def one_chip(cfg, geo: Geometry, seed: int, pallas_mode: str = "pallas"):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    mesh = make_mesh(1, 1)
    model = Model(cfg, mesh)
    check(model.supports_paged_serving, f"{cfg.name}: no paged serving")
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(seed))
    log("init", arch=cfg.name, params=model.n_params(),
        seconds=f"{time.perf_counter() - t0:.1f}")
    ref_model = Model(dataclasses.replace(cfg, compute_dtype="float32"),
                      mesh)
    prompts = make_prompts(cfg.vocab, geo, seed)
    rng = np.random.default_rng(seed + 1)
    tokens = rng.integers(0, cfg.vocab, size=geo.logits_batch,
                          dtype=np.int32)
    for int8 in (False, True):
        tag = "int8" if int8 else "bf16"
        scfg = serve_config(geo, int8)
        eng = serve(model, params, scfg, prompts, geo, seed, tag)
        served = eng.params
        del eng                      # free the page pools
        check_prefill_logits(model, served, ref_model, params, tokens, tag,
                             pallas_mode)
        n = count_decode_kernels(model, scfg, tag)
        if pallas_mode == "pallas":
            check(n > 0, f"{tag}: paged decode step holds no Pallas kernel")
        del served


# ---------------------------------------------------------------------------
# four chips: collective matmul, model-parallel forward and training
# ---------------------------------------------------------------------------

def check_xyz_schedules(mesh, geo: Geometry, seed: int, cfg):
    """All four collective-matmul schedules at the model's MLP widths in
    fp32: bitwise equal to each other, and within 2^-8 of the output
    scale of a one-device ``jnp.dot`` at ``highest`` precision (the bound
    holds even for one-pass bf16 MXU products)."""
    import jax
    import jax.numpy as jnp
    from repro.core.maxeva_matmul import (SCHEDULES, XYZConfig,
                                          shard_weight_xyz, xyz_matmul)
    from repro.core.sharding import model_size
    model = model_size(mesh)
    d, f = cfg.d_model, cfg.d_ff
    cells = (("up", d, f, 2, "replicated"), ("down", f, d, model, "ksharded"))
    for name, k, n, y, layout in cells:
        kx, kw = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (*geo.xyz_rows, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
        want = np.asarray(jnp.dot(x, w, precision="highest"))
        w_xyz = shard_weight_xyz(w, model, y)
        outs = {}
        for sched in SCHEDULES:
            xcfg = XYZConfig(y=y, schedule=sched, x_layout=layout)
            with jax.set_mesh(mesh):
                fn = jax.jit(lambda a, b, c=xcfg: xyz_matmul(
                    a, b, mesh=mesh, cfg=c))
                outs[sched] = np.asarray(fn(x, w_xyz))
        first = outs[SCHEDULES[0]]
        for sched in SCHEDULES:
            check(np.array_equal(outs[sched], first), (
                f"xyz {name}: {sched} != {SCHEDULES[0]} bitwise"))
        err = max_err(first, want)
        tol = 2.0 ** -8 * float(np.max(np.abs(want)))
        log(f"xyz_{name}", k=k, n=n, y=y, layout=layout,
            schedules=",".join(SCHEDULES), bitwise_equal=True,
            max_abs_err=f"{err:.6g}", tol=f"{tol:.6g}")
        check(err <= tol, f"xyz {name}: {err} > {tol}")


def one_device_params(params4, model1):
    """The same weights in the one-device layout, on model1's device.
    Leaves in the xyz layout (``[..., model, K/Y, N/Z]``: the MLP and
    o-projection weights) are put back to model1's ``[..., K, N]``."""
    import jax
    from repro.core.maxeva_matmul import unshard_weight_xyz
    dev = model1.mesh.devices.flat[0]

    def relayout(path, leaf, like):
        if leaf.shape != like.shape:
            k, n = like.shape[-2:]
            y = k // leaf.shape[-2]
            flat = leaf.reshape(-1, *leaf.shape[-3:])
            logical = jax.vmap(lambda w: unshard_weight_xyz(w, y))(flat)
            check(logical.shape[-2:] == (k, n), (path, logical.shape))
            leaf = logical.reshape(like.shape)
        # through the host: a device-to-device put keeps the 4-device
        # mesh in the array's type, which a one-device trace rejects
        return jax.device_put(np.asarray(leaf), dev)

    return jax.tree_util.tree_map_with_path(relayout, params4,
                                            model1.abstract_params())


def check_sharded_forward(cfg, mesh4, mesh1, params4, batch,
                          pallas_mode: str = "pallas"):
    """The loss forward on the (1, 4) mesh against the same weights on one
    device.  Final hidden states: the 4-chip path's error against an
    fp32 reference (one device, fp32 compute, ``highest`` precision) may
    be at most 4x the one-device path's own — the repo's consistency
    budget.  Losses must agree to one bf16 ulp (2^-8) relative."""
    import jax
    from repro.models.lm import Model
    model4, model1 = Model(cfg, mesh4), Model(cfg, mesh1)
    ref_model = Model(dataclasses.replace(cfg, compute_dtype="float32"),
                      mesh1)
    params1 = one_device_params(params4, model1)

    def run(model, params, mesh, mode="pallas", precision=None):
        with kernel_mode(mode, precision), jax.set_mesh(mesh):
            f = jax.jit(lambda p, b: (model.loss(p, b),
                                      model.forward(p, b, mode="train")[0]))
            loss, h = f(params, batch)
        return float(loss), np.asarray(h, np.float32)

    l4, h4 = run(model4, params4, mesh4, pallas_mode)
    l1, h1 = run(model1, params1, mesh1, pallas_mode)
    lr, hr = run(ref_model, params1, mesh1, "xla", "highest")
    del params1
    e4, e1 = max_err(h4, hr), max_err(h1, hr)
    log("loss_forward", mesh="1x4", loss_4chip=f"{l4:.6f}",
        loss_1chip=f"{l1:.6f}", loss_fp32_ref=f"{lr:.6f}",
        h_err_4chip=f"{e4:.6g}", h_err_1chip=f"{e1:.6g}",
        tol=f"{4.0 * e1:.6g}")
    check(np.isfinite(l4) and np.isfinite(l1), (l4, l1))
    check(e4 <= 4.0 * e1, f"4-chip hidden state error {e4} > 4 x {e1}")
    check(abs(l4 - l1) <= 2.0 ** -8 * abs(l1), (l4, l1))


def check_train_steps(model4, params4, batch, steps: int):
    import jax
    from repro.optim import AdamWConfig, init_opt_state
    from repro.train.step import jit_train_step
    opt_cfg = AdamWConfig()
    step_fn = jit_train_step(model4, opt_cfg)
    opt = init_opt_state(params4, opt_cfg)
    losses, t0 = [], time.perf_counter()
    with jax.set_mesh(model4.mesh):
        for _ in range(steps):
            params4, opt, metrics = step_fn(params4, opt, batch)
            losses.append(float(metrics["loss"]))
    log("train", mesh="1x4", steps=steps,
        losses=",".join(f"{v:.6f}" for v in losses),
        seconds=f"{time.perf_counter() - t0:.1f}")
    check(all(np.isfinite(losses)), losses)


def four_chips(cfg, geo: Geometry, seed: int, pallas_mode: str = "pallas"):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    mesh4, mesh1 = make_mesh(1, 4), make_mesh(1, 1)
    with kernel_mode(pallas_mode):
        check_xyz_schedules(mesh4, geo, seed, cfg)
    model4 = Model(cfg, mesh4)
    t0 = time.perf_counter()
    params4 = jax.block_until_ready(model4.init_params(seed))
    log("init", arch=cfg.name, mesh="1x4", params=model4.n_params(),
        seconds=f"{time.perf_counter() - t0:.1f}")
    rng = np.random.default_rng(seed + 2)
    batch = {k: rng.integers(0, cfg.vocab, size=geo.train_batch,
                             dtype=np.int32)
             for k in ("tokens", "targets")}
    check_sharded_forward(cfg, mesh4, mesh1, params4, batch, pallas_mode)
    with kernel_mode(pallas_mode):
        check_train_steps(model4, params4, batch, geo.train_steps)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip phases, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devs = require_v5e(n_chips)

    import jax
    from repro.configs import get_config
    from repro.kernels.ops import kernel_mode as resolved_mode
    from repro.launch.compile_cache import enable_compile_cache
    check(resolved_mode() == "pallas", (
        f"kernel mode is {resolved_mode()!r} on the TPU; unset "
        f"REPRO_KERNEL_MODE"))
    log("device", kind=devs[0].device_kind, count=len(devs),
        compile_cache=enable_compile_cache())
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(cfg, FULL_RUN, args.seed)
    else:
        one_chip(cfg, FULL_RUN, args.seed)
    log("done", seconds=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
