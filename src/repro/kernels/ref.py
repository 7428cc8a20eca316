"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's tests sweep shapes and
dtypes and ``assert_allclose`` against these functions.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_ACCUM = {
    jnp.dtype("int8"): jnp.int32,
    jnp.dtype("bfloat16"): jnp.float32,
    jnp.dtype("float32"): jnp.float32,
}


def x64():
    """Context manager enabling 64-bit types: the scope every f64 oracle
    (the consistency budgets' precision reference) runs in."""
    return jax.enable_x64(True)


def accum_dtype(dtype) -> jnp.dtype:
    """32-bit accumulator for a given input dtype; unlisted dtypes fall
    back by kind (ints -> int32, floats -> fp32) — EXCEPT inputs already
    wider than 32 bits, which keep their width (an f64 reference run must
    not silently accumulate at fp32)."""
    dt = jnp.dtype(dtype)
    if dt in _ACCUM:
        return _ACCUM[dt]
    if dt.kind in ("i", "u"):
        return jnp.int32 if dt.itemsize <= 4 else jnp.int64
    if dt.kind == "f" and dt.itemsize >= 8:
        return jnp.float64
    return jnp.float32


def matmul_ref(a: jnp.ndarray, b: jnp.ndarray,
               out_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """C = A @ B with 32-bit accumulation (paper §IV-C1: int8 inputs
    accumulate in int32; floats accumulate in fp32)."""
    acc = accum_dtype(a.dtype)
    out_dtype = out_dtype or acc
    return jnp.dot(a, b, preferred_element_type=acc).astype(out_dtype)


def matmul_fused_ref(a: jnp.ndarray, b: jnp.ndarray, epilogue,
                     bias: Optional[jnp.ndarray] = None,
                     residual: Optional[jnp.ndarray] = None,
                     operand2: Optional[jnp.ndarray] = None,
                     norm_scale: Optional[jnp.ndarray] = None):
    """epilogue(A @ B): the XLA mirror of the fused-epilogue Pallas kernel.

    Shares ``kernels.epilogue.apply_epilogue`` with the kernel's store
    phase, so both paths are numerically identical by construction — and
    f64 inputs keep the whole chain (dot AND epilogue) at f64, making
    this the oracle for the two-operand stages too.  Returns ``(q,
    scale)`` under ``epilogue.quantize``, ``(value, normed)`` under
    ``epilogue.norm``, else one array."""
    from repro.kernels.epilogue import apply_epilogue
    acc = jnp.dot(a, b, preferred_element_type=accum_dtype(a.dtype))
    return apply_epilogue(acc, epilogue, bias=bias, residual=residual,
                          operand2=operand2, norm_scale=norm_scale)


def addertree_ref(partials: jnp.ndarray,
                  out_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """Sum of Y stacked (M, N) partial products -- the paper's adder tree
    (Y-1 sequential Add kernels on one core)."""
    acc = accum_dtype(partials.dtype) if partials.dtype in _ACCUM else partials.dtype
    out_dtype = out_dtype or partials.dtype
    return jnp.sum(partials.astype(acc), axis=0).astype(out_dtype)


def quantize_rowwise_ref(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise symmetric int8 quantization: q = round(x / s), s = absmax/127.
    Returns (q int8 [M, N], scale f32 [M, 1])."""
    from repro.kernels.epilogue import quantize_symmetric
    return quantize_symmetric(x.astype(jnp.float32), axis=-1)


def quantize_colwise_ref(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Column-wise symmetric int8 quantization (the weight / weight-grad
    layout): q = round(x / s), s = per-column absmax / 127.  Works on any
    rank >= 2 (leading axes, e.g. a scan group axis, pass through).
    Returns (q int8 [..., K, N], scale f32 [..., 1, N])."""
    from repro.kernels.epilogue import quantize_symmetric
    return quantize_symmetric(x.astype(jnp.float32), axis=-2)


def dequantize_rowwise_ref(q: jnp.ndarray, scale: jnp.ndarray,
                           dtype=jnp.float32) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def int8_matmul_ref(qa: jnp.ndarray, sa: jnp.ndarray, qb: jnp.ndarray,
                    sb: jnp.ndarray, epilogue=None,
                    bias: Optional[jnp.ndarray] = None,
                    residual: Optional[jnp.ndarray] = None,
                    operand2: Optional[jnp.ndarray] = None,
                    norm_scale: Optional[jnp.ndarray] = None):
    """epilogue(sa * sb * (QA @ QB)): the serving int8 GEMM's XLA mirror.

    ``qa [M, K]`` int8 with rowwise scales ``sa [M, 1]``; ``qb [K, N]``
    int8 with columnwise scales ``sb [1, N]``.  Accumulation is int32 and
    both scales are re-applied at the int32 -> fp32 boundary INSIDE the
    epilogue (paper §IV-C1: scales come back on the way out), so the
    quantized pipeline never materializes a dequantized fp32 operand.
    Shares ``apply_epilogue`` with the Pallas kernel's store phase."""
    from repro.kernels.epilogue import Epilogue, apply_epilogue
    acc = jnp.dot(qa, qb, preferred_element_type=jnp.int32)
    return apply_epilogue(acc, epilogue or Epilogue(), bias=bias,
                          residual=residual, row_scale=sa, col_scale=sb,
                          operand2=operand2, norm_scale=norm_scale)


def quantized_matmul_ref(a: jnp.ndarray, b: jnp.ndarray,
                         out_dtype=jnp.float32) -> jnp.ndarray:
    """int8 x int8 -> int32 matmul with row/col scales applied afterwards:
    the fully-quantized MatMul path (paper's int8 pipeline)."""
    qa, sa = quantize_rowwise_ref(a)
    qb, sb = quantize_colwise_ref(b)
    from repro.kernels.epilogue import Epilogue
    return int8_matmul_ref(qa, sa, qb, sb,
                           Epilogue(out_dtype=out_dtype))


# ---------------------------------------------------------------------------
# flash-attention oracles
# ---------------------------------------------------------------------------
#
# Ground truth for the flash kernels is the PLAIN (untiled) masked softmax
# at ``accum_dtype`` width: with f64 inputs the whole softmax runs at f64,
# which is what anchors the consistency-budget comparisons.  The tiled
# kernels and their tiled XLA mirrors must land within rounding distance
# of these, never bitwise — the bitwise contracts (split-count invariance,
# dense == paged) are between tiled paths sharing one combine.

_NEG_REF = -1e30


def attention_mask_ref(qpos: jnp.ndarray, kpos: jnp.ndarray, *,
                       kind: str = "global", window: int = 0,
                       prefix_len: int = 0) -> jnp.ndarray:
    """[Q, K] bool mask shared by every attention oracle — the same
    semantics as ``models.attention._block_attend``: causal for
    global/local/chunked/prefix, sliding window for 'local', block-local
    for 'chunked', bidirectional prefix override for 'prefix', everything
    valid for 'full'; ``kpos < 0`` always masks (padding sentinel)."""
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if kind in ("global", "local", "chunked", "prefix"):
        mask &= qpos[:, None] >= kpos[None, :]
    if kind == "local":
        mask &= (qpos[:, None] - kpos[None, :]) < window
    if kind == "chunked":
        mask &= (qpos[:, None] // window) == (kpos[None, :] // window)
    if kind == "prefix":
        mask |= kpos[None, :] < prefix_len
    mask &= kpos[None, :] >= 0
    return mask


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        kind: str = "global", window: int = 0,
                        prefix_len: int = 0, softcap=None,
                        q_offset: int = 0) -> jnp.ndarray:
    """Prefill/train attention oracle, head-expanded [B, S, H, hd] (k/v
    may carry KV < H heads; GQA head h reads kv head h // (H // KV)).
    Plain softmax — the S x S scores ARE materialized here; that is the
    point of an oracle."""
    b, sq, n_h, hd = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    acc = accum_dtype(q.dtype)
    if n_kv != n_h:
        g = n_h // n_kv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bKhd->bhqK", q.astype(acc), k.astype(acc))
    s = s * jnp.asarray(hd, acc) ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)
    mask = attention_mask_ref(qpos, kpos, kind=kind, window=window,
                              prefix_len=prefix_len)
    s = jnp.where(mask[None, None], s, _NEG_REF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[None, None], jnp.exp(s - m), 0.0).astype(acc)
    out = jnp.einsum("bhqK,bKhd->bhqd", p, v.astype(acc))
    out = out / jnp.maximum(jnp.sum(p, axis=-1)[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def flash_decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos, *, kind: str = "global",
                     softcap=None) -> jnp.ndarray:
    """Decode oracle: q [B, 1, KV, G, hd] against dense caches
    [B, K, KV, hd]; ``pos`` is the current position ('global' attends
    slots <= pos; 'full' attends every slot — cross-attention).  Plain
    masked softmax at ``accum_dtype``."""
    hd = q.shape[-1]
    acc = accum_dtype(q.dtype)
    s = jnp.einsum("bqkgd,bKkd->bkgqK", q.astype(acc),
                   k_cache.astype(acc))
    s = s * jnp.asarray(hd, acc) ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    slots = jnp.arange(k_cache.shape[1])
    valid = slots >= 0 if kind == "full" else slots <= pos
    s = jnp.where(valid[None, None, None, None, :], s, _NEG_REF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid[None, None, None, None, :], jnp.exp(s - m),
                  0.0).astype(acc)
    out = jnp.einsum("bkgqK,bKkd->bkgqd", p, v_cache.astype(acc))
    out = out / jnp.maximum(jnp.sum(p, axis=-1)[..., None], 1e-30)
    return jnp.einsum("bkgqd->bqkgd", out).astype(q.dtype)


def paged_flash_decode_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           positions: jnp.ndarray, *,
                           kind: str = "global", window: int = 0,
                           softcap=None) -> jnp.ndarray:
    """Paged decode/prefill-chunk oracle: q [B, S, KV, G, hd] against the
    page pools [NP, PS, KV, hd] through ``page_table`` [B, P] (-1 =
    unmapped -> trash page NP-1, masked to contribute exact zeros);
    ``positions`` [B, S] global query positions, -1 = inactive."""
    n_pool, ps = k_pool.shape[0], k_pool.shape[1]
    b, p_max = page_table.shape
    hd = q.shape[-1]
    acc = accum_dtype(q.dtype)
    mapped = page_table >= 0
    ptc = jnp.where(mapped, page_table, n_pool - 1)
    kl = k_pool[ptc].reshape(b, p_max * ps, *k_pool.shape[2:])
    vl = v_pool[ptc].reshape(b, p_max * ps, *v_pool.shape[2:])
    s = jnp.einsum("bqkgd,bKkd->bkgqK", q.astype(acc), kl.astype(acc))
    s = s * jnp.asarray(hd, acc) ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kvpos = jnp.arange(p_max * ps)
    kvalid = jnp.repeat(mapped, ps, axis=1)
    qpos = positions
    mask = (kvalid[:, None, :]
            & (kvpos[None, None, :] <= qpos[:, :, None])
            & (qpos[:, :, None] >= 0))
    if kind == "local":
        mask &= (qpos[:, :, None] - kvpos[None, None, :]) < window
    elif kind == "chunked":
        mask &= ((qpos[:, :, None] // window)
                 == (kvpos[None, None, :] // window))
    m4 = mask[:, None, None]
    s = jnp.where(m4, s, _NEG_REF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(m4, jnp.exp(s - m), 0.0).astype(acc)
    out = jnp.einsum("bkgqK,bKkd->bkgqd", p, vl.astype(acc))
    out = out / jnp.maximum(jnp.sum(p, axis=-1)[..., None], 1e-30)
    return jnp.einsum("bkgqd->bqkgd", out).astype(q.dtype)
