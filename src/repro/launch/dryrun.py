import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  Do not move them.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, runnable_cells  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import collective_wire_bytes, roofline_terms  # noqa: E402
from repro.launch.specs import input_specs, param_io_specs  # noqa: E402
from repro.models.lm import Model  # noqa: E402
from repro.optim import AdamWConfig, abstract_opt_state, opt_state_specs  # noqa: E402
from repro.train.step import batch_specs, make_train_step  # noqa: E402

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell
against ShapeDtypeStruct inputs on the production mesh and record
memory_analysis / cost_analysis / collective wire bytes for the roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def _mem_analysis_dict(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    if "argument_size_in_bytes" in out:
        out["total_per_device_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out


def model_flops_global(cfg, cell) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N*D for inference, with N the
    active (per-token) parameter count."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * cell.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool,
             save_hlo: Optional[str] = None) -> Dict[str, Any]:
    t0 = time.time()
    cfg = get_config(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, mesh)
    opt_cfg = AdamWConfig(state_mode=cfg.opt_state_mode)

    with jax.set_mesh(mesh):
        aparams, pspecs = param_io_specs(model)
        if cell.kind == "train":
            fn = make_train_step(model, opt_cfg)
            aopt = abstract_opt_state(aparams, opt_cfg)
            abatch = input_specs(cfg, shape)
            in_sh = (_ns(mesh, pspecs),
                     _ns(mesh, opt_state_specs(pspecs, opt_cfg)),
                     _ns(mesh, batch_specs(cfg, mesh, "train")))
            jf = jax.jit(fn, in_shardings=in_sh, donate_argnums=(0, 1))
            lowered = jf.lower(aparams, aopt, abatch)
        elif cell.kind == "prefill":
            abatch = input_specs(cfg, shape)
            in_sh = (_ns(mesh, pspecs),
                     _ns(mesh, batch_specs(cfg, mesh, "prefill")))
            jf = jax.jit(model.prefill, in_shardings=in_sh)
            lowered = jf.lower(aparams, abatch)
        else:  # decode
            acache, atok, apos = input_specs(cfg, shape, model)
            from repro.core.sharding import dp_axes, dp_size
            b = cell.global_batch
            tok_spec = P(dp_axes(mesh), None) \
                if b % max(dp_size(mesh), 1) == 0 and b > 1 else P(None, None)
            in_sh = (_ns(mesh, pspecs),
                     _ns(mesh, model.cache_specs(b, cell.seq_len)),
                     NamedSharding(mesh, tok_spec), None)
            jf = jax.jit(model.decode_step, in_shardings=in_sh,
                         donate_argnums=(1,))
            lowered = jf.lower(aparams, acache, atok, apos)

        compiled = lowered.compile()

    raw_cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()
    # trip-count-aware analysis: XLA's cost_analysis counts while bodies
    # once, which under-reports every scanned program (see hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze_hlo
    an = analyze_hlo(hlo)
    # memory term from the fused-model bytes (ops a TPU compiler fuses are
    # excluded); the raw conservative count is recorded alongside.
    cost = {"flops": an["flops"], "bytes accessed": an["bytes_fused"],
            "bytes_conservative": an["bytes"]}
    wire = {k: v for k, v in an.items() if k.startswith("wire_")}
    wire["total_wire_bytes"] = an["total_wire_bytes"]
    wire["raw_once_counted"] = collective_wire_bytes(hlo)["total_wire_bytes"]
    mem = _mem_analysis_dict(compiled)
    n_dev = mesh.devices.size
    mf = model_flops_global(cfg, cell) / n_dev
    terms = roofline_terms(cost, wire, model_flops_per_device=mf)

    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    return {
        "arch": arch,
        "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "kind": cell.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "compile_s": round(time.time() - t0, 1),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "xla_cost_analysis_flops_once": float(raw_cost.get("flops", 0.0)),
        "collectives": wire,
        "memory": mem,
        "roofline": terms.as_dict(),
        "ok": True,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in runnable_cells(a)]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            if shape in get_config(arch).skip_shapes:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single", "ok": True,
                       "skipped": True,
                       "reason": "see DESIGN.md shape-cell skips"}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                print(f"[skip-cell] {tag}")
                continue
            try:
                rec = run_cell(arch, shape, mp, save_hlo=args.save_hlo)
                print(f"[ok] {tag}: compile={rec['compile_s']}s "
                      f"flops/dev={rec['cost_analysis'].get('flops', 0):.3e} "
                      f"wire/dev={rec['collectives']['total_wire_bytes']:.3e} "
                      f"dominant={rec['roofline']['dominant']}")
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single", "ok": False,
                       "error": str(e),
                       "traceback": traceback.format_exc()}
                print(f"[FAIL] {tag}: {e}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2, default=str)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
