"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --smoke --steps 50 --batch 8 --seq 128

Full-config runs on real hardware use the same entry point with the
production mesh; on this CPU container, --smoke selects the reduced config.
"""
from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenSource, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.lm import Model
from repro.optim import AdamWConfig
from repro.optim.schedule import warmup_cosine
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="data axis size (0 = all local devices)")
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    data_ax = args.data_mesh or (jax.device_count() // args.model_mesh)
    mesh = make_mesh(data_ax, args.model_mesh)
    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg, mesh)
    print(f"arch={cfg.name} params={model.n_params():,} mesh={mesh.shape}")

    opt_cfg = AdamWConfig(
        lr=args.lr, state_mode=cfg.opt_state_mode,
        schedule=warmup_cosine(args.lr, args.warmup, args.steps))
    tcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir)

    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      seed=args.seed)
    src = SyntheticTokenSource(cfg.vocab, args.seed)

    def pipeline_factory(start_step):
        return TokenPipeline(src, dcfg, mesh, cfg, start_step=start_step)

    trainer = Trainer(model, opt_cfg, tcfg, pipeline_factory)
    trainer.run(args.seed)
    losses = [m["loss"] for m in trainer.metrics]
    if losses:
        print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")
        print(f"stragglers flagged: {len(trainer.watchdog.events)}")


if __name__ == "__main__":
    main()
