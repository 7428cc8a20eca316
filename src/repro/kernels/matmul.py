"""Blocked MatMul Pallas kernel with fused epilogues — the TPU adaptation
of the paper's single-AIE MatMul kernel (§IV-C1).

The AIE kernel computes an ``M x K x N`` tile chosen so that (a) the vector
unit runs near peak, (b) streaming the tile in/out does not outrun the
stream bandwidth, and (c) the double-buffered working set fits the 32 KB
local memory.  Here the same three constraints pick the VMEM block
``(bm, bk, bn)`` (see ``core.planner.plan_tpu_block``): MXU-aligned shapes,
HBM-bandwidth-balanced ``bm``/``bn``, and a double-buffered working set
within the VMEM budget.  Pallas' pipeline emitter provides the double
buffering that Fig. 5 of the paper builds by hand.

Accumulation is always 32-bit (fp32 / int32), matching the paper's int8
pipeline with int32 accumulators.

Fused epilogues (the ``Epilogue`` spec, ``kernels.epilogue``)
-------------------------------------------------------------
MaxEVA's efficiency comes from never letting partial results touch slow
memory: partial products ping-pong through local memory (§IV-C, Fig. 5)
and are reduced on-array by the adder tree (§IV-B) before a single PLIO
write-out.  The TPU analogue of that discipline is applying the GEMM
epilogue — bias add, gelu/silu/relu, residual add, output cast, rowwise
int8 quantize — on the VMEM accumulator tile in the kernel's store phase,
instead of writing the fp32 accumulator to HBM and reading it back in a
separate XLA op.  Declaratively:

    ep = Epilogue(bias=True, activation="gelu", out_dtype=jnp.bfloat16)
    y = matmul_pallas(a, b, block=blk, epilogue=ep, bias=bias_row)

    epq = Epilogue(activation="silu", quantize=True)
    q, scale = matmul_pallas(a, b, block=blk, epilogue=epq)

Semantics are defined once in ``kernels.epilogue.apply_epilogue``; the XLA
reference path (``kernels.ref.matmul_fused_ref``) calls the same function
on the full accumulator, so both paths are numerically identical.

Constraint: ``quantize`` computes a full-row absmax, so the N dimension
must not be blocked — the kernel pads N to one block (``bn = N_padded``)
and grids over M and K only, exactly like ``kernels.quantize``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.device_model import TPU_V5E
from repro.kernels.epilogue import Epilogue, apply_epilogue
from repro.kernels.ref import accum_dtype


def _matmul_kernel(*refs, k_steps: int, out_dtype, epilogue: Epilogue,
                   has_a_scale: bool, has_b_scale: bool,
                   has_bias: bool, has_residual: bool,
                   has_operand2: bool, has_norm_scale: bool,
                   norm_n: Optional[int]):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (sequential) axis; the
    fp32/int32 accumulator tile lives in VMEM scratch across K steps.  The
    epilogue runs on the accumulator tile at the final K step (the store
    phase), so the only HBM write is the finished output.  With int8
    inputs the row/col quantization scales are re-applied right there (the
    paper's int32 -> output boundary), never via a separate dequant op."""
    refs = list(refs)
    a_ref, b_ref = refs[:2]
    pos = 2
    as_ref = refs[pos] if has_a_scale else None
    pos += int(has_a_scale)
    bs_ref = refs[pos] if has_b_scale else None
    pos += int(has_b_scale)
    bias_ref = refs[pos] if has_bias else None
    pos += int(has_bias)
    res_ref = refs[pos] if has_residual else None
    pos += int(has_residual)
    op2_ref = refs[pos] if has_operand2 else None
    pos += int(has_operand2)
    ns_ref = refs[pos] if has_norm_scale else None
    pos += int(has_norm_scale)
    out_refs = refs[pos:-1]
    acc_ref = refs[-1]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_ref.dtype
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        acc = acc_ref[...]
        if epilogue.is_identity and not (has_a_scale or has_b_scale):
            out_refs[0][...] = acc.astype(out_dtype)
            return
        out = apply_epilogue(
            acc, epilogue,
            bias=bias_ref[...] if has_bias else None,
            residual=res_ref[...] if has_residual else None,
            row_scale=as_ref[...] if has_a_scale else None,
            col_scale=bs_ref[...] if has_b_scale else None,
            operand2=op2_ref[...] if has_operand2 else None,
            norm_scale=ns_ref[...] if has_norm_scale else None,
            norm_n=norm_n,
        )
        if epilogue.quantize:
            q, s = out
            out_refs[0][...] = q
            out_refs[1][...] = s
        elif epilogue.norm != "none":
            value, normed = out
            out_refs[0][...] = value.astype(out_dtype)
            out_refs[1][...] = normed.astype(out_dtype)
        else:
            out_refs[0][...] = out.astype(out_dtype)


def _pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    pm = (-x.shape[0]) % m0
    pn = (-x.shape[1]) % m1
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("block", "out_dtype", "interpret", "cost_hint",
                     "epilogue"),
)
def matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block: Tuple[int, int, int],
    out_dtype=None,
    interpret: bool = False,
    cost_hint: bool = True,
    epilogue: Optional[Epilogue] = None,
    a_scale: Optional[jnp.ndarray] = None,
    b_scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    operand2: Optional[jnp.ndarray] = None,
    norm_scale: Optional[jnp.ndarray] = None,
):
    """C[M, N] = epilogue(A[M, K] @ B[K, N]) via the blocked Pallas kernel.

    Inputs are zero-padded to block multiples (the paper's Fig. 8 padding
    model) and the result is sliced back.  With ``epilogue.quantize`` the
    return value is ``(q int8 [M, N], scale f32 [M, 1])`` (``[1, N]``
    under ``quantize_axis='col'``); with ``epilogue.norm`` it is
    ``(value, normed)``, both ``[M, N]``; otherwise a single ``[M, N]``
    array in the epilogue/out dtype.

    ``a_scale [M, 1]`` / ``b_scale [1, N]`` are the int8 pipeline's
    quantization scales, re-applied on the int32 accumulator tile in the
    store phase (before bias/activation) — int8 in, one HBM write out.
    ``operand2 [M, N]`` is the gate epilogue's second tensor operand
    (tiled like the residual); ``norm_scale [N]`` the rmsnorm scale row
    (tiled like the bias).
    """
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0]
    ep = epilogue or Epilogue()
    m, k = a.shape
    _, n = b.shape
    bm, bk, bn = block
    acc = accum_dtype(a.dtype)
    scaled = a_scale is not None or b_scale is not None
    out_dtype = ep.out_dtype or out_dtype or (jnp.float32 if scaled
                                              else acc)

    if ep.quantize and ep.quantize_axis == "col":
        # colwise scale needs the whole column in one tile: M is one block
        # (sublane-aligned); zero-pad rows cannot raise a column's absmax.
        bm = _ceil_mult(m, 8)
    ap = _pad_to(a, bm, bk)
    if (ep.quantize and ep.quantize_axis == "row") or ep.norm != "none":
        # rowwise scale / rmsnorm needs the whole row in one tile: N is
        # one block (lane-aligned), exactly like kernels.quantize —
        # zero-pad columns cannot raise a row's absmax, and they
        # contribute exact +0.0 to the rmsnorm sum of squares (the mean
        # divides by the TRUE n via norm_n below).
        bn = _ceil_mult(n, 128)
    bp = _pad_to(b, bk, bn)
    mp, kp = ap.shape
    np_ = bp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
    ]
    operands = [ap, bp]
    if a_scale is not None:
        assert a_scale.shape == (m, 1), (a_scale.shape, m)
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)))
        operands.append(_pad_to(a_scale, bm, 1))
    if b_scale is not None:
        assert b_scale.shape == (1, n), (b_scale.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        operands.append(_pad_to(b_scale, 1, bn))
    if ep.bias:
        assert bias is not None and bias.shape[-1] == n, (
            "epilogue.bias requires a [N] bias operand")
        b2 = bias.reshape(1, n)
        b2 = jnp.pad(b2, ((0, 0), (0, np_ - n))) if np_ != n else b2
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        operands.append(b2)
    if ep.residual:
        assert residual is not None and residual.shape == (m, n), (
            "epilogue.residual requires a [M, N] residual operand")
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)))
        operands.append(_pad_to(residual, bm, bn))
    if ep.gate != "none":
        assert operand2 is not None and operand2.shape == (m, n), (
            "epilogue.gate requires a [M, N] operand2")
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)))
        operands.append(_pad_to(operand2, bm, bn))
    if ep.norm != "none":
        assert norm_scale is not None and norm_scale.shape[-1] == n, (
            "epilogue.norm requires a [N] norm_scale operand")
        ns2 = norm_scale.reshape(1, n)
        ns2 = jnp.pad(ns2, ((0, 0), (0, np_ - n))) if np_ != n else ns2
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        operands.append(ns2)

    if ep.quantize:
        out_specs = [pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))]
        out_shape = [jax.ShapeDtypeStruct((mp, np_), jnp.int8)]
        if ep.quantize_axis == "row":
            out_specs.append(pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((mp, 1), jnp.float32))
        else:
            out_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
            out_shape.append(jax.ShapeDtypeStruct((1, np_), jnp.float32))
    elif ep.norm != "none":
        out_specs = [pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
                     pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))]
        out_shape = [jax.ShapeDtypeStruct((mp, np_), out_dtype),
                     jax.ShapeDtypeStruct((mp, np_), out_dtype)]
    else:
        out_specs = pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))
        out_shape = jax.ShapeDtypeStruct((mp, np_), out_dtype)

    kernel = functools.partial(
        _matmul_kernel, k_steps=grid[2], out_dtype=out_dtype, epilogue=ep,
        has_a_scale=a_scale is not None, has_b_scale=b_scale is not None,
        has_bias=ep.bias, has_residual=ep.residual,
        has_operand2=ep.gate != "none",
        has_norm_scale=ep.norm != "none",
        norm_n=n if ep.norm != "none" else None,
    )
    cost = None
    if cost_hint:
        # the fused path stores the finished epilogue output ONCE; the
        # unfused sequence would add an fp32 accumulator write + read.
        out_bytes = mp * np_ * ep.out_itemsize(acc)
        if ep.quantize:
            # scale vector: a column (rowwise) or a row (colwise)
            out_bytes += (mp if ep.quantize_axis == "row" else np_) * 4
        if ep.norm != "none":
            # second [M, N] output: the normed residual-stream view
            out_bytes += mp * np_ * ep.out_itemsize(acc)
        extra_in = (np_ * 4 if ep.bias else 0) + (
            mp * np_ * jnp.dtype(residual.dtype).itemsize
            if ep.residual else 0)
        extra_in += (mp * np_ * jnp.dtype(operand2.dtype).itemsize
                     if ep.gate != "none" else 0)
        extra_in += np_ * 4 if ep.norm != "none" else 0
        extra_in += (mp * 4 if a_scale is not None else 0) + (
            np_ * 4 if b_scale is not None else 0)
        transc = mp * np_ if ep.activation in ("gelu", "silu") else 0
        transc += mp * np_ if ep.gate in ("gelu", "silu") else 0
        transc += mp if ep.norm != "none" else 0
        cost = pl.CostEstimate(
            flops=2 * mp * kp * np_,
            bytes_accessed=(mp * kp * ap.dtype.itemsize
                            + kp * np_ * bp.dtype.itemsize
                            + out_bytes + extra_in),
            transcendentals=transc,
        )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        interpret=interpret,
        cost_estimate=cost,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_V5E.vmem_bytes),
    )(*operands)
    if ep.quantize:
        q, s = out
        return (q[:m, :n], s[:m]) if ep.quantize_axis == "row" \
            else (q[:m, :n], s[:, :n])
    if ep.norm != "none":
        value, normed = out
        return value[:m, :n], normed[:m, :n]
    return out[:m, :n]


def _ceil_mult(v: int, a: int) -> int:
    return a * ((v + a - 1) // a)
