"""End-to-end behaviour tests for the system: a real train->checkpoint->
serve round trip, and a miniature dry-run (lower+compile+roofline) on an
8-device subprocess mesh."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_then_serve_roundtrip(tmp_path):
    """Train a tiny LM on a repeating corpus until it memorizes local
    bigram structure, checkpoint it, restore into a fresh model, and
    verify the served continuation beats chance."""
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticTokenSource, TokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    from repro.optim import AdamWConfig
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.train.trainer import Trainer, TrainerConfig

    mesh = make_mesh(1, 1)
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              vocab=64, compute_dtype="float32")
    model = Model(cfg, mesh)

    class CyclicSource:
        """tokens follow t_{i+1} = (t_i + 1) % vocab — learnable."""
        def batch(self, step, rows, dcfg):
            n = rows.stop - rows.start
            start = (np.arange(n) + step) % cfg.vocab
            return ((start[:, None] + np.arange(dcfg.seq_len + 1))
                    % cfg.vocab).astype(np.int32)

    tcfg = TrainerConfig(steps=60, ckpt_every=30, ckpt_dir=str(tmp_path),
                         log_every=100)
    dcfg = DataConfig(global_batch=4, seq_len=32)
    trainer = Trainer(model, AdamWConfig(lr=3e-3), tcfg,
                      lambda s: TokenPipeline(CyclicSource(), dcfg, mesh,
                                              cfg, start_step=s))
    trainer.run(0)
    assert trainer.metrics[-1]["loss"] < trainer.metrics[0]["loss"]

    # restore into a FRESH model instance (as a new process would)
    model2 = Model(cfg, mesh)
    t2 = Trainer(model2, AdamWConfig(lr=3e-3), tcfg,
                 lambda s: TokenPipeline(CyclicSource(), dcfg, mesh, cfg,
                                         start_step=s))
    step, params, _ = t2.restore()
    assert step == 60

    prompt = (np.arange(16)[None] % cfg.vocab).astype(np.int32)
    eng = ServeEngine(model2, params, ServeConfig(max_new_tokens=8))
    out = eng.generate({"tokens": jnp.asarray(prompt)})
    want = (16 + np.arange(8)) % cfg.vocab
    acc = float(np.mean(out[0] == want))
    assert acc > 0.5, (out[0], want)  # learned the +1 structure


MINI_DRYRUN = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, REPO_SRC)
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.hlo_analysis import analyze_hlo
from repro.models.lm import Model
from repro.optim import AdamWConfig, abstract_opt_state, opt_state_specs
from repro.train.step import batch_specs, make_train_step
import dataclasses

mesh = make_mesh(2, 4)
for arch in ("internlm2-1.8b", "gemma2-27b"):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              seq_shard_activations=True)
    model = Model(cfg, mesh)
    opt_cfg = AdamWConfig()
    fn = make_train_step(model, opt_cfg)
    ap = model.abstract_params()
    ao = abstract_opt_state(ap, opt_cfg)
    ab = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
          "targets": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t)
    with jax.set_mesh(mesh):
        jf = jax.jit(fn, in_shardings=(ns(model.param_specs()),
                     ns(opt_state_specs(model.param_specs(), opt_cfg)),
                     ns(batch_specs(cfg, mesh, "train"))))
        compiled = jf.lower(ap, ao, ab).compile()
    an = analyze_hlo(compiled.as_text())
    assert an["flops"] > 0
    assert an["total_wire_bytes"] > 0  # TP collectives present
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes > 0
    print(arch, "flops", an["flops"], "wire", an["total_wire_bytes"])
print("ALL_OK")
"""


def test_mini_dryrun_multidev(tmp_path):
    """lower+compile a sharded train step for two archs on an 8-device
    mesh; collective parser and memory analysis must produce signals."""
    script = tmp_path / "mini.py"
    script.write_text(MINI_DRYRUN.replace(
        "REPO_SRC", repr(os.path.join(_ROOT, "src"))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # fake-device CPU mesh, never the chip
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    assert "ALL_OK" in r.stdout
