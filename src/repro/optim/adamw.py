"""AdamW with optional 8-bit (row-block-quantized) moment states.

The int8 state mode reuses the paper's int8 pipeline idea (int8 storage,
32-bit arithmetic): moments are stored as int8 with one fp32 scale per
trailing row, dequantized, updated in fp32, and requantized each step.
For the 314B-parameter MoE this cuts optimizer-state HBM by 4x vs fp32
(recorded in the dry-run memory analysis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_mode: str = "fp32"     # 'fp32' | 'int8'
    schedule: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None


# -- int8 moment codecs -------------------------------------------------------

def _q8(x: jnp.ndarray, sqrt_scale: bool = False) -> Dict[str, jnp.ndarray]:
    """Row-wise int8.  ``sqrt_scale`` stores sqrt(x) (x >= 0): linear
    quantization of the SECOND moment rounds small entries to zero, and
    m/(sqrt(0)+eps) then explodes — the sqrt codec compresses v's dynamic
    range so small entries survive (the 8-bit-Adam trick)."""
    xe = jnp.sqrt(jnp.maximum(x, 0.0)) if sqrt_scale else x
    absmax = jnp.max(jnp.abs(xe), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(xe / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale.astype(jnp.float32)}


def _dq8(p: Dict[str, jnp.ndarray], sqrt_scale: bool = False) -> jnp.ndarray:
    x = p["q"].astype(jnp.float32) * p["s"]
    return x * x if sqrt_scale else x


def _is_q8(x: Any) -> bool:
    return isinstance(x, dict) and set(x) == {"q", "s"}


def _encode(x: jnp.ndarray, mode: str, sqrt_scale: bool = False):
    if mode == "int8" and x.ndim >= 1 and x.size > 1:
        return _q8(x, sqrt_scale)
    return x.astype(jnp.float32)


def _decode(x, sqrt_scale: bool = False) -> jnp.ndarray:
    return _dq8(x, sqrt_scale) if _is_q8(x) else x


# -- API ----------------------------------------------------------------------

def init_opt_state(params: Any, cfg: AdamWConfig) -> Dict[str, Any]:
    def zeros_like_enc(p, sqrt_scale=False):
        # zeros_like keeps the parameter's sharding: sharded params get
        # sharded moments, not a whole copy on the default device
        z = jnp.zeros_like(p, dtype=jnp.float32)
        return _encode(z, cfg.state_mode, sqrt_scale)

    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros_like_enc, params),
        "v": jax.tree.map(lambda p: zeros_like_enc(p, True), params),
    }


def abstract_opt_state(abstract_params: Any, cfg: AdamWConfig):
    """ShapeDtypeStruct pytree of the optimizer state (dry-run)."""
    def enc_struct(p):
        if cfg.state_mode == "int8" and len(p.shape) >= 1:
            n = 1
            for d in p.shape:
                n *= d
            if n > 1:
                return {
                    "q": jax.ShapeDtypeStruct(p.shape, jnp.int8),
                    "s": jax.ShapeDtypeStruct((*p.shape[:-1], 1),
                                              jnp.float32),
                }
        return jax.ShapeDtypeStruct(p.shape, jnp.float32)

    return {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": jax.tree.map(enc_struct, abstract_params),
        "v": jax.tree.map(enc_struct, abstract_params),
    }


def opt_state_specs(param_specs: Any, cfg: AdamWConfig):
    from jax.sharding import PartitionSpec as P

    def enc_spec(s):
        if cfg.state_mode == "int8":
            # the row scale has a trailing singleton dim: drop any sharding
            # of the last axis
            parts = list(s) if len(s) else []
            if parts:
                parts[-1] = None
            return {"q": s, "s": P(*parts)}
        return s

    return {
        "step": P(),
        "m": jax.tree.map(enc_spec, param_specs),
        "v": jax.tree.map(enc_spec, param_specs),
    }


def _barrier_on(x: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """Make ``x`` depend on ``token`` without changing its value."""
    x, _ = jax.lax.optimization_barrier((x, token))
    return x


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_update(params: Any, grads: Any, state: Dict[str, Any],
                 cfg: AdamWConfig) -> Tuple[Any, Dict[str, Any]]:
    step = state["step"] + 1
    lr = cfg.schedule(step) if cfg.schedule is not None else cfg.lr

    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if cfg.grad_clip else 1.0

    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd_core(p, g, m_enc, v_enc):
        g = g.astype(jnp.float32) * clip
        m = cfg.b1 * _decode(m_enc) + (1 - cfg.b1) * g
        v = cfg.b2 * _decode(v_enc, True) + (1 - cfg.b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        pf = p.astype(jnp.float32)
        pf = pf - lr * (delta + cfg.weight_decay * pf)
        return (pf.astype(p.dtype), _encode(m, cfg.state_mode),
                _encode(v, cfg.state_mode, True))

    upd = upd_core

    is_leaf = _is_q8
    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"], is_leaf=is_leaf)
    flat_v = jax.tree.leaves(state["v"], is_leaf=is_leaf)
    # Chain big-leaf updates with optimization barriers: XLA's latency
    # scheduler otherwise runs many leaves' fp32 decode/update chains
    # concurrently (measured ~10 GB of optimizer temporaries on the 314B
    # MoE); serializing keeps one leaf's working set live at a time.
    out = []
    token = None
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        if token is not None and p.size > (1 << 24):
            g = _barrier_on(g, token)
        res = upd(p, g, m, v)
        if p.size > (1 << 24):
            token = res[0]
        out.append(res)
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
    return new_p, {"step": step, "m": new_m, "v": new_v}
