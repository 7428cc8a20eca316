"""Row-wise symmetric int8 quantization Pallas kernel, plus the
``QuantizedWeight`` container the int8 serving path stores weights in.

Supports the paper's int8 MatMul pipeline (int8 inputs, int32 accumulation,
scales re-applied on the way out), the int8 error-feedback gradient
compression used by the distributed optimizer (``optim.compression``), and
the one-shot column-wise weight quantization of the serving engine.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.device_model import TPU_V5E


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedWeight:
    """An int8-quantized GEMM weight with per-column scales.

    ``q`` keeps the original weight's shape (possibly with leading stack
    axes: a scan group axis, or the xyz layout's model axis); ``scale`` is
    f32 with the second-to-last axis reduced to 1 (one scale per output
    column), so both leaves share every leading axis and a ``lax.scan``
    over stacked layer groups slices them in lockstep.

    Serving-only: produced by ``Model.quantize_params_for_serving`` after
    checkpoint restore, never checkpointed or trained.
    """

    q: jnp.ndarray       # int8 [..., K, N]
    scale: jnp.ndarray   # f32  [..., 1, N]

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def as_matrix(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Collapse leading singleton axes to the 2D GEMM operand pair
        ``(q [K, N], scale [1, N])`` — e.g. the xyz layout's ``[1, K, N]``
        single-shard weight."""
        k, n = self.q.shape[-2], self.q.shape[-1]
        assert all(s == 1 for s in self.q.shape[:-2]), self.q.shape
        return self.q.reshape(k, n), self.scale.reshape(1, n)

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def quantize_weight_colwise(w: jnp.ndarray) -> QuantizedWeight:
    """One-shot column-wise weight quantization (the serving pass): one
    scale per output column, shared by every row of the contraction — the
    layout the int8 GEMM's store-phase epilogue folds back in."""
    from repro.kernels.ref import quantize_colwise_ref
    # one fused program per weight: the eager op chain would hold several
    # fp32 temporaries of the whole (layer-stacked) weight at once
    q, s = jax.jit(quantize_colwise_ref)(w)
    return QuantizedWeight(q, s)


def quantize_fixed_scale(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Quantize with a FIXED (externally calibrated) scale.

    Unlike ``quantize_symmetric`` — whose per-call absmax scale maps the
    largest value to exactly ±127 and therefore never clips — a fixed
    calibrated scale CAN saturate when the activation range drifts past
    calibration.  The clip at ±127 is exactly where that saturation lands,
    which makes it countable: ``saturation_fraction`` on this function's
    output is the quantize-epilogue health counter the serving guard
    monitors (``ServeEngine``'s int8 -> fp32 graceful degradation).
    """
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8)


def saturation_fraction(q: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Fraction of int8 values pinned at the clip boundary (|q| == 127)
    along ``axis`` — per-row with the default, i.e. one health number per
    batch lane for a ``[B, N]`` activation tile.  A freshly
    absmax-quantized tensor reports ~1/N (only the max element sits at
    127); values approaching 1.0 mean the fixed scale is clipping most of
    the tensor and the int8 GEMM results are garbage."""
    sat = (jnp.abs(q.astype(jnp.int32)) >= 127).astype(jnp.float32)
    return jnp.mean(sat, axis=axis)


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_rowwise_pallas(
    x: jnp.ndarray,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(q int8 [M, N], scale f32 [M, 1]) = rowwise-quantize(x [M, N]).

    Rows must be block-complete (the scale is a full-row reduction), so the
    grid tiles M only and each block spans all of N.
    """
    assert x.ndim == 2
    m, n = x.shape
    if m == 0:
        # zero rows: nothing to reduce — a 0-length grid is ill-formed, so
        # return the (well-defined) empty result directly
        return (jnp.zeros((0, n), jnp.int8), jnp.zeros((0, 1), jnp.float32))
    pm = (-m) % block_rows
    xp = jnp.pad(x, ((0, pm), (0, 0))) if pm else x
    mp = xp.shape[0]
    grid = (mp // block_rows,)

    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n), jnp.int8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=TPU_V5E.vmem_bytes),
        interpret=interpret,
    )(xp)
    return q[:m], s[:m]
