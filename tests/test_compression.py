"""int8 error-feedback gradient compression: exactness properties on one
device; wire-byte reduction + convergence on an 8-device subprocess."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quantize_error_feedback_accumulates():
    """EF: the long-run average of compressed values converges to the true
    value (residual is carried, not dropped)."""
    from repro.optim.compression import compressed_psum_mean
    # single shard via a fake axis: emulate with axis over 1-device mesh
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(1, 1)
    from repro.core.maxeva_matmul import _shard_map
    from jax.sharding import PartitionSpec as P

    x = jnp.full((64,), 0.001234, jnp.float32)  # small vs absmax
    big = jnp.zeros((64,)).at[0].set(1.0)       # forces coarse scale
    v = x + big

    def body(v):
        err = jnp.zeros_like(v)
        tot = jnp.zeros_like(v)
        for _ in range(64):
            out, err = compressed_psum_mean(v, "data", err)
            tot = tot + out
        return tot / 64

    avg = _shard_map(body, mesh, (P(),), P())(v)
    np.testing.assert_allclose(np.asarray(avg)[1:], 0.001234, rtol=0.02)


MULTIDEV = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig
from repro.optim.compression import init_error_state, make_dp_train_step

mesh = make_mesh(8, 1)

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

kw, kx = jax.random.split(jax.random.PRNGKey(0))
w_true = jax.random.normal(kw, (16, 4))
params = {"w": jnp.zeros((16, 4))}

def data(step):
    k = jax.random.PRNGKey(step)
    x = jax.random.normal(k, (64, 16))
    return {"x": x, "y": x @ w_true}

results = {}
for mode in ("none", "int8_ef"):
    from repro.optim import init_opt_state
    p = {"w": jnp.zeros((16, 4))}
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, grad_clip=0.0)
    opt = init_opt_state(p, cfg)
    err = init_error_state(p, 8)
    step = make_dp_train_step(loss_fn, cfg, mesh, "data", mode)
    with jax.set_mesh(mesh):
        for s in range(150):
            loss, p, opt, err = step(p, opt, err, data(s))
    results[mode] = (float(loss), float(jnp.max(jnp.abs(p["w"] - w_true))))

print("none", results["none"], "int8_ef", results["int8_ef"])
assert results["none"][1] < 0.05, results
assert results["int8_ef"][1] < 0.1, results

# wire bytes: the compressed step's all-reduce payload must be ~4x smaller
from repro.launch.hlo_analysis import analyze_hlo
from repro.optim import init_opt_state
outs = {}
for mode in ("none", "int8_ef"):
    cfg = AdamWConfig(lr=0.05)
    p = {"w": jnp.zeros((256, 256))}
    opt = init_opt_state(p, cfg)
    err = init_error_state(p, 8)
    step = make_dp_train_step(loss_fn, cfg, mesh, "data", mode)
    b = {"x": jnp.zeros((64, 256)), "y": jnp.zeros((64, 256))}
    with jax.set_mesh(mesh):
        txt = step.lower(p, opt, err, b).compile().as_text()
    an = analyze_hlo(txt)
    outs[mode] = an["total_wire_bytes"]
print("wire none:", outs["none"], "int8:", outs["int8_ef"])
# int16 transport: ~2x fewer wire bytes than fp32 (+ tiny scale pmax)
assert outs["int8_ef"] < 0.65 * outs["none"], outs
print("ALL_OK")
"""


def test_dp_train_step_compression_multidev(tmp_path):
    script = tmp_path / "check.py"
    script.write_text(MULTIDEV)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake-device CPU mesh, never the chip
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=900, env=env,
                       cwd=os.path.join(_ROOT, "tests"))
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    assert "ALL_OK" in r.stdout
