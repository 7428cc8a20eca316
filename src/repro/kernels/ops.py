"""jit'd dispatch wrappers over the Pallas kernels.

On a TPU backend the Pallas path compiles natively; everywhere else (this
container is CPU-only) callers either get the XLA reference path (identical
semantics, real HLO for the dry-run/roofline) or may force
``interpret=True`` to execute the kernel bodies in Python for validation.
The mode is a process-global policy so that model code never has to thread
a backend flag through every layer.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.device_model import TPU_V5E
from repro.core.planner import plan_tpu_block
from repro.kernels import ref
from repro.kernels.epilogue import Epilogue
from repro.kernels.matmul import matmul_pallas
from repro.kernels.addertree import addertree_pallas
from repro.kernels.quantize import QuantizedWeight, quantize_rowwise_pallas

# 'auto': pallas on TPU, XLA elsewhere.  'pallas': force pallas (native).
# 'interpret': force pallas interpret mode (CPU validation).  'xla': force
# the reference path.
_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")
_VALID_MODES = ("auto", "pallas", "interpret", "xla")


def set_kernel_mode(mode: str) -> None:
    global _MODE
    assert mode in _VALID_MODES, mode
    _MODE = mode


def kernel_mode() -> str:
    if _MODE == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return _MODE


# Planner dtype keys for the dtypes the paper pipeline uses natively; any
# other dtype falls back by itemsize (2-byte floats plan like bf16, 1-byte
# ints like int8, everything else like fp32) instead of raising KeyError.
_PLANNER_DTYPE = {"bfloat16": "bf16", "float32": "fp32", "int8": "int8"}


def planner_dtype_key(dtype) -> str:
    if isinstance(dtype, str) and dtype in ("bf16", "fp32", "int8"):
        return dtype  # already a planner key
    dt = jnp.dtype(dtype)
    key = _PLANNER_DTYPE.get(dt.name)
    if key is not None:
        return key
    if dt.kind in ("i", "u") and dt.itemsize == 1:
        return "int8"
    if dt.kind == "f" and dt.itemsize == 2:
        return "bf16"
    return "fp32"


@functools.lru_cache(maxsize=None)
def default_block(m: int, k: int, n: int, dtype: str,
                  epilogue: Optional[Epilogue] = None
                  ) -> Tuple[int, int, int]:
    b = plan_tpu_block(m, k, n, planner_dtype_key(dtype),
                       epilogue=epilogue)
    return (b.bm, b.bk, b.bn)


def _clamped_default_block(m: int, k: int, n: int, dtype: str,
                           epilogue: Optional[Epilogue] = None
                           ) -> Tuple[int, int, int]:
    """Planned block, never exceeding the (padded) problem itself; the
    row block stays a whole native tile of the input dtype."""
    block = default_block(m, k, n, dtype, epilogue)
    rows = TPU_V5E.row_tile(jnp.dtype(dtype).itemsize)
    return (
        min(block[0], max(rows, _round_pow2_up(m))),
        min(block[1], _round_pow2_up(k)),
        min(block[2], _round_pow2_up(n)),
    )


def _planned_epilogue(epilogue: Optional[Epilogue],
                      out_dtype) -> Epilogue:
    """The store phase the kernel will run: the spec with the call's
    ``out_dtype`` filled in (what the planner sizes output tiles by)."""
    ep = epilogue or Epilogue()
    if out_dtype is not None and ep.out_dtype is None:
        ep = dataclasses.replace(ep, out_dtype=out_dtype)
    return ep


def _with_reference_vjp(kernel, reference):
    """``kernel`` forward, differentiated through ``reference`` — the XLA
    mirror with the same semantics (Pallas calls have no transpose rule,
    so a training step could not take their gradient otherwise).  The
    backward pass recomputes the forward in XLA."""
    @jax.custom_vjp
    def f(*operands):
        return kernel(*operands)

    def fwd(*operands):
        return kernel(*operands), operands

    def bwd(operands, g):
        return jax.vjp(reference, *operands)[1](g)

    f.defvjp(fwd, bwd)
    return f


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    out_dtype=None,
    block: Optional[Tuple[int, int, int]] = None,
    mode: Optional[str] = None,
    epilogue: Optional[Epilogue] = None,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    operand2: Optional[jnp.ndarray] = None,
    norm_scale: Optional[jnp.ndarray] = None,
):
    """Planned, blocked matmul (2D x 2D) with an optional fused epilogue.
    Higher-rank callers flatten the leading dims (activation rows are the
    M axis, as in the paper).

    With ``epilogue`` the bias/activation/residual/cast/quantize sequence
    runs in the kernel's store phase (one HBM write); the XLA path applies
    the same spec via ``ref.matmul_fused_ref`` (identical semantics, and
    XLA fuses the elementwise tail into the dot consumer).

    ``b`` may be a ``QuantizedWeight`` (the int8 serving path): ``a`` is
    then rowwise-quantized and the GEMM runs int8 x int8 -> int32 with
    both scales re-applied inside the epilogue — no fp32 weight dequant
    ever reaches the HLO."""
    mode = mode or kernel_mode()
    if epilogue is None:
        assert bias is None and residual is None and operand2 is None \
            and norm_scale is None, (
                "bias/residual/operand2/norm_scale operands require an "
                "Epilogue spec (e.g. epilogue=Epilogue(bias=True))")
    if isinstance(b, QuantizedWeight):
        qa, sa = quantize_rowwise(a, mode=mode)
        qb, sb = b.as_matrix()
        return int8_matmul(qa, sa, qb, sb, out_dtype=out_dtype,
                           block=block, mode=mode, epilogue=epilogue,
                           bias=bias, residual=residual,
                           operand2=operand2, norm_scale=norm_scale)
    def reference(a, b, bias, residual, operand2, norm_scale):
        if epilogue is None:
            return ref.matmul_ref(a, b, out_dtype)
        # honor the out_dtype argument exactly like the kernel path
        return ref.matmul_fused_ref(a, b,
                                    _planned_epilogue(epilogue, out_dtype),
                                    bias=bias, residual=residual,
                                    operand2=operand2,
                                    norm_scale=norm_scale)

    operands = (a, b, bias, residual, operand2, norm_scale)
    if mode == "xla":
        return reference(*operands)
    if block is None:
        block = _clamped_default_block(
            a.shape[0], a.shape[1], b.shape[1], str(a.dtype),
            _planned_epilogue(epilogue, out_dtype))

    def kernel(a, b, bias, residual, operand2, norm_scale):
        return matmul_pallas(
            a, b, block=block, out_dtype=out_dtype,
            interpret=(mode == "interpret"), epilogue=epilogue, bias=bias,
            residual=residual, operand2=operand2, norm_scale=norm_scale)

    return _with_reference_vjp(kernel, reference)(*operands)


def int8_matmul(
    qa: jnp.ndarray,
    sa: jnp.ndarray,
    qb: jnp.ndarray,
    sb: jnp.ndarray,
    *,
    out_dtype=None,
    block: Optional[Tuple[int, int, int]] = None,
    mode: Optional[str] = None,
    epilogue: Optional[Epilogue] = None,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    operand2: Optional[jnp.ndarray] = None,
    norm_scale: Optional[jnp.ndarray] = None,
):
    """Planned, blocked int8 x int8 -> int32 GEMM with both quantization
    scales folded into the fused epilogue (paper §IV-C1: int8 inputs,
    int32 accumulation, scales re-applied on the way out).

    ``qa [M, K]`` int8 activations with rowwise scales ``sa [M, 1]`` —
    exactly the ``(q, scale)`` pair the fused quantize epilogue of the
    previous GEMM (or ``quantize_rowwise``) emits; ``qb [K, N]`` int8
    weights with columnwise scales ``sb [1, N]`` (the one-shot serving
    weight-quantization layout).  The int32 -> fp32 boundary lives inside
    the store phase, so consecutive quantized GEMMs never bounce through
    a dequantized fp32 tensor in HBM."""
    assert qa.dtype == jnp.int8 and qb.dtype == jnp.int8, (qa.dtype,
                                                          qb.dtype)
    mode = mode or kernel_mode()
    ep = epilogue or Epilogue()
    assert ep.bias or bias is None, (
        "a bias operand requires Epilogue(bias=True)")
    assert ep.residual or residual is None, (
        "a residual operand requires Epilogue(residual=True)")
    assert ep.gate != "none" or operand2 is None, (
        "an operand2 requires Epilogue(gate=...)")
    ep = _planned_epilogue(ep, out_dtype)
    if mode == "xla":
        return ref.int8_matmul_ref(qa, sa, qb, sb, ep, bias=bias,
                                   residual=residual, operand2=operand2,
                                   norm_scale=norm_scale)
    if block is None:
        block = _clamped_default_block(qa.shape[0], qa.shape[1],
                                       qb.shape[1], "int8", ep)
    return matmul_pallas(
        qa, qb, block=block, interpret=(mode == "interpret"), epilogue=ep,
        a_scale=sa, b_scale=sb, bias=bias, residual=residual,
        operand2=operand2, norm_scale=norm_scale,
    )


def addertree(
    partials: jnp.ndarray,
    *,
    out_dtype=None,
    block: Tuple[int, int] = (256, 256),
    mode: Optional[str] = None,
) -> jnp.ndarray:
    mode = mode or kernel_mode()
    if mode == "xla":
        return ref.addertree_ref(partials, out_dtype)
    block = (
        min(block[0], _round_pow2_up(partials.shape[1])),
        min(block[1], _round_pow2_up(partials.shape[2])),
    )
    return addertree_pallas(
        partials, block=block, out_dtype=out_dtype,
        interpret=(mode == "interpret"),
    )


def quantize_rowwise(
    x: jnp.ndarray, *, block_rows: int = 256, mode: Optional[str] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # the named_scope marks this as a STANDALONE quantize dispatch in the
    # traced HLO's op_name metadata — the fusion audit
    # (analysis/passes.py::fusion_scope_pass) counts these to prove the
    # fused (q, scale) handoffs really replaced separate quantize ops
    mode = mode or kernel_mode()
    with jax.named_scope("quantize_rowwise"):
        if mode == "xla":
            return ref.quantize_rowwise_ref(x)
        return quantize_rowwise_pallas(
            x, block_rows=min(block_rows, _round_pow2_up(x.shape[0])),
            interpret=(mode == "interpret"),
        )


def quantize_colwise(
    x: jnp.ndarray, *, block_rows: int = 256, mode: Optional[str] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Column-wise symmetric int8 quantization (weight / weight-grad
    layout): (q [M, N], scale [1, N]).  The Pallas path reuses the rowwise
    kernel on the transpose (the reduction is the kernel's fast axis
    either way); XLA mode uses the direct reference."""
    mode = mode or kernel_mode()
    if mode == "xla" or x.ndim != 2:
        return ref.quantize_colwise_ref(x)
    q_t, s_t = quantize_rowwise_pallas(
        x.T, block_rows=min(block_rows, _round_pow2_up(x.shape[1])),
        interpret=(mode == "interpret"))
    return q_t.T, s_t.reshape(1, -1)


def dequantize_rowwise(q, scale, dtype=jnp.float32):
    return ref.dequantize_rowwise_ref(q, scale, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
#
# The decode wrappers dispatch all three modes ('xla' -> the tiled-XLA
# mirror with identical tile semantics).  The prefill wrapper covers the
# Pallas kernel only: the XLA prefill path is the chunked running-softmax
# scan in models/attention.py (it predates the kernel and stays the CPU
# production path), so models code calls this wrapper only when
# ``kernel_mode() != 'xla'``.

def flash_attention(q, k, v, *, kind="global", window=0, prefix_len=0,
                    softcap=None, q_offset=0, block_q=128, block_k=128,
                    mode: Optional[str] = None):
    from repro.kernels.flash_attention import flash_attention_pallas
    mode = mode or kernel_mode()
    assert mode in ("pallas", "interpret"), mode

    def kernel(q, k, v):
        return flash_attention_pallas(
            q, k, v, kind=kind, window=window, prefix_len=prefix_len,
            softcap=softcap, q_offset=q_offset, block_q=block_q,
            block_k=block_k, interpret=(mode == "interpret"))

    def reference(q, k, v):
        return ref.flash_attention_ref(
            q, k, v, kind=kind, window=window, prefix_len=prefix_len,
            softcap=softcap, q_offset=q_offset)

    return _with_reference_vjp(kernel, reference)(q, k, v)


def flash_decode(q, k_cache, v_cache, pos, *, kind="global", softcap=None,
                 kv_tile: Optional[int] = None, n_splits: int = 1,
                 mode: Optional[str] = None):
    from repro.kernels import flash_attention as fa
    mode = mode or kernel_mode()
    kv_tile = kv_tile or fa.DEFAULT_KV_TILE
    if mode == "xla":
        return fa.flash_decode_xla(q, k_cache, v_cache, pos, kind=kind,
                                   softcap=softcap, kv_tile=kv_tile)
    return fa.flash_decode_pallas(
        q, k_cache, v_cache, pos, kind=kind, softcap=softcap,
        kv_tile=kv_tile, n_splits=n_splits,
        interpret=(mode == "interpret"))


def paged_flash_decode(q, k_pool, v_pool, page_table, positions, *,
                       kind="global", window=0, softcap=None,
                       kv_tile: Optional[int] = None,
                       mode: Optional[str] = None):
    from repro.kernels import flash_attention as fa
    mode = mode or kernel_mode()
    kv_tile = kv_tile or fa.DEFAULT_KV_TILE
    if mode == "xla" or q.shape[1] != 1:
        # the Pallas paged kernel is decode-only; prefill chunks (S > 1)
        # always take the tiled-XLA mirror
        return fa.paged_flash_decode_xla(
            q, k_pool, v_pool, page_table, positions, kind=kind,
            window=window, softcap=softcap, kv_tile=kv_tile)
    return fa.paged_flash_decode_pallas(
        q, k_pool, v_pool, page_table, positions.reshape(-1), kind=kind,
        window=window, softcap=softcap, interpret=(mode == "interpret"))


def _round_pow2_up(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p
