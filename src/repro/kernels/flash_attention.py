"""Fused flash-attention Pallas kernels: prefill, decode, paged decode.

The recipe follows ``kernels/matmul.py``: a Pallas kernel with an explicit
grid and VMEM scratch, an XLA mirror with IDENTICAL tile semantics for the
CPU production path, and f64-capable oracles in ``kernels/ref.py``.  The
prefill kernel is the classic online-softmax flash loop (no S x S score
materialization); decode and paged decode are split-K flash-decode in the
SNIPPETS flashdecode shape: partial softmax per KV split, combined after.

Determinism contract — the MaxEVA rank-order rule applied to softmax
----------------------------------------------------------------------
Every decode path reduces the KV axis in fixed ``kv_tile`` tiles anchored
at position 0.  Each tile yields an independent partial

    m_t   = max of its masked scores            (fp32)
    l_t   = sum exp(s - m_t) over the tile      (fp32)
    acc_t = sum exp(s - m_t) * v over the tile  (fp32)

and the combine is a global fp-max over tiles (associative and
commutative, so order-free) followed by an elementwise rescale
``alpha_t = exp(m_t - m)`` and an ASCENDING rank-order fold at fp32 —
``_rank_order_sum`` from ``core/maxeva_matmul.py``, the same association
that locked the four collective schedules bitwise-equal.  Partial values
never depend on how tiles are grouped into kernel programs, and the fold
order never depends on the split count, so ``n_splits`` in {1, 2, 4}
produces bitwise-identical fp32 outputs.  A fully masked tile (cache
padding, future positions, unmapped/trash pages) contributes exact +0.0
to the fold, which is what keeps a paged lane's output bitwise-equal to
the same history in a dense cache: the tiles they share see identical
rows at the valid slots, and everything else folds in as +0.0.

Score dots run at the storage dtype with ``preferred_element_type=fp32``
— a single dot_general per tile, no full-pool ``convert`` in the traced
HLO (XLA CPU legalizes bf16 dots by upcasting per-tile operands inside
the dot fusion, never the whole cache).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.device_model import TPU_V5E

_NEG = -1e30

# Default KV tile of the decode paths.  Both the dense and the paged
# decode MUST use the same value (and the paged logical view is tiled
# from position 0) or their partials stop lining up bitwise.
DEFAULT_KV_TILE = 32


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _ceil_mult(v: int, m: int) -> int:
    return _ceil_div(v, m) * m


def _pad_axis(x: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    if x.shape[axis] == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pads)


def _softcap(s: jnp.ndarray, softcap) -> jnp.ndarray:
    if softcap:
        return softcap * jnp.tanh(s / softcap)
    return s


def combine_tile_partials(m_t: jnp.ndarray, l_t: jnp.ndarray,
                          acc_t: jnp.ndarray) -> jnp.ndarray:
    """Combine per-tile softmax partials stacked on axis 0.

    ``m_t``/``l_t`` [T, ...], ``acc_t`` [T, ..., hd], all fp32.  Returns
    the normalized attention output [..., hd] fp32.  The fold is the
    rank-order association from ``core/maxeva_matmul`` so the result is
    independent of how tiles were grouped into splits; fully masked
    tiles (m_t == _NEG while any tile is live) rescale to exact 0.
    """
    from repro.core.maxeva_matmul import _rank_order_sum
    m = jnp.max(m_t, axis=0)
    alpha = jnp.exp(m_t - m[None])
    l = _rank_order_sum(l_t * alpha, jnp.float32)
    acc = _rank_order_sum(acc_t * alpha[..., None], jnp.float32)
    return acc / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# decode: dense cache, tiled XLA mirror
# ---------------------------------------------------------------------------

def _decode_tile_partials_xla(q, k_cache, v_cache, pos, *, kind, softcap,
                              kv_tile):
    """Per-tile partials over a dense cache.  q [B, S, KV, G, hd],
    caches [B, K, KV, hd].  Returns (m_t, l_t, acc_t) stacked on axis 0
    with inner layout [B, KV, G, S(, hd)].

    One dot_general PER TILE (a static unrolled loop): XLA CPU legalizes
    each bf16 dot by converting only that tile's operands, so the traced
    HLO never contains a full-cache fp32 ``convert`` — the bug the
    einsum fallback had.
    """
    hd = q.shape[-1]
    kv_len = k_cache.shape[1]
    n_tiles = _ceil_div(kv_len, kv_tile)
    kp = _pad_axis(k_cache, 1, n_tiles * kv_tile)
    vp = _pad_axis(v_cache, 1, n_tiles * kv_tile)
    scale = jnp.float32(hd) ** -0.5
    ms, ls, accs = [], [], []
    for t in range(n_tiles):
        kt = jax.lax.slice_in_dim(kp, t * kv_tile, (t + 1) * kv_tile, axis=1)
        vt = jax.lax.slice_in_dim(vp, t * kv_tile, (t + 1) * kv_tile, axis=1)
        s = jnp.einsum("bqkgd,bKkd->bkgqK", q, kt,
                       preferred_element_type=jnp.float32) * scale
        s = _softcap(s, softcap)
        slots = t * kv_tile + jnp.arange(kv_tile)
        valid = slots < kv_len
        if kind != "full":
            valid &= slots <= pos
        v5 = valid[None, None, None, None, :]
        s = jnp.where(v5, s, _NEG)
        m_t = jnp.max(s, axis=-1)
        p = jnp.where(v5, jnp.exp(s - m_t[..., None]), 0.0)
        l_t = jnp.sum(p, axis=-1)
        acc_t = jnp.einsum("bkgqK,bKkd->bkgqd", p, vt,
                           preferred_element_type=jnp.float32)
        ms.append(m_t)
        ls.append(l_t)
        accs.append(acc_t)
    return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)


def flash_decode_xla(q, k_cache, v_cache, pos, *, kind="global",
                     softcap=None, kv_tile=DEFAULT_KV_TILE) -> jnp.ndarray:
    """Tiled-XLA flash decode: q [B, S, KV, G, hd] against dense caches
    [B, K, KV, hd] -> [B, S, KV, G, hd].  'global' attends slots <= pos,
    'full' attends every slot (cross-attention)."""
    m_t, l_t, acc_t = _decode_tile_partials_xla(
        q, k_cache, v_cache, pos, kind=kind, softcap=softcap,
        kv_tile=kv_tile)
    out = combine_tile_partials(m_t, l_t, acc_t)
    return jnp.einsum("bkgqd->bqkgd", out).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: paged pools, tiled XLA mirror
# ---------------------------------------------------------------------------

def _paged_tile_partials_xla(q, k_pool, v_pool, page_table, positions, *,
                             kind, window, softcap, kv_tile):
    """Per-tile partials over the gathered logical view [B, P*PS, ...].

    The gather stays at the pools' storage dtype (bf16 moves, no
    convert); tiles are anchored at logical position 0 with the SAME
    ``kv_tile`` as the dense path, so a lane's live tiles are
    bitwise-identical to the dense-cache tiles over the same history
    and every masked slot (unmapped page, future position, inactive
    lane) contributes exact +0.0 to the fold.
    """
    n_pool, ps = k_pool.shape[0], k_pool.shape[1]
    b, p_max = page_table.shape
    hd = q.shape[-1]
    mapped = page_table >= 0
    ptc = jnp.where(mapped, page_table, n_pool - 1)
    kl = k_pool[ptc].reshape(b, p_max * ps, *k_pool.shape[2:])
    vl = v_pool[ptc].reshape(b, p_max * ps, *v_pool.shape[2:])
    kv_len = p_max * ps
    n_tiles = _ceil_div(kv_len, kv_tile)
    kl = _pad_axis(kl, 1, n_tiles * kv_tile)
    vl = _pad_axis(vl, 1, n_tiles * kv_tile)
    kvalid = _pad_axis(jnp.repeat(mapped, ps, axis=1), 1,
                       n_tiles * kv_tile)
    scale = jnp.float32(hd) ** -0.5
    qpos = positions                                         # [B, S]
    ms, ls, accs = [], [], []
    for t in range(n_tiles):
        kt = jax.lax.slice_in_dim(kl, t * kv_tile, (t + 1) * kv_tile, axis=1)
        vt = jax.lax.slice_in_dim(vl, t * kv_tile, (t + 1) * kv_tile, axis=1)
        s = jnp.einsum("bqkgd,bKkd->bkgqK", q, kt,
                       preferred_element_type=jnp.float32) * scale
        s = _softcap(s, softcap)
        kvpos = t * kv_tile + jnp.arange(kv_tile)
        mask = (kvalid[:, t * kv_tile:(t + 1) * kv_tile][:, None, :]
                & (kvpos[None, None, :] <= qpos[:, :, None])
                & (qpos[:, :, None] >= 0))
        if kind == "local":
            mask &= (qpos[:, :, None] - kvpos[None, None, :]) < window
        elif kind == "chunked":
            mask &= ((qpos[:, :, None] // window)
                     == (kvpos[None, None, :] // window))
        m5 = mask[:, None, None]                             # [B,1,1,S,T]
        s = jnp.where(m5, s, _NEG)
        m_t = jnp.max(s, axis=-1)
        p = jnp.where(m5, jnp.exp(s - m_t[..., None]), 0.0)
        l_t = jnp.sum(p, axis=-1)
        acc_t = jnp.einsum("bkgqK,bKkd->bkgqd", p, vt,
                           preferred_element_type=jnp.float32)
        ms.append(m_t)
        ls.append(l_t)
        accs.append(acc_t)
    return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)


def paged_flash_decode_xla(q, k_pool, v_pool, page_table, positions, *,
                           kind="global", window=0, softcap=None,
                           kv_tile=DEFAULT_KV_TILE) -> jnp.ndarray:
    """Tiled-XLA paged flash decode: q [B, S, KV, G, hd] through the page
    table against pools [NP, PS, KV, hd] -> [B, S, KV, G, hd]."""
    m_t, l_t, acc_t = _paged_tile_partials_xla(
        q, k_pool, v_pool, page_table, positions, kind=kind, window=window,
        softcap=softcap, kv_tile=kv_tile)
    out = combine_tile_partials(m_t, l_t, acc_t)
    return jnp.einsum("bkgqd->bqkgd", out).astype(q.dtype)


# ---------------------------------------------------------------------------
# prefill: online-softmax Pallas kernel
# ---------------------------------------------------------------------------

def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    n_k: int, kind: str, window: int, prefix_len: int,
                    softcap, q_offset: int, kv_len: int, scale: float,
                    block_q: int, block_k: int):
    """Grid = (B*H, Sq/bq, Skv/bk); the kv axis is the innermost
    (sequential) axis and the running (m, l, acc) live in VMEM scratch
    across kv steps — the matmul kernel's zero/accumulate/store phasing
    with the online-softmax rescale in the accumulate step."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (bq, hd_p)
    k = k_ref[0]                                   # (bk, hd_p)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qpos = (q_offset + i * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    kpos = (j * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    mask = kpos < kv_len                           # right padding
    if kind in ("global", "local", "chunked", "prefix"):
        causal = qpos >= kpos
        if kind == "local":
            causal &= (qpos - kpos) < window
        elif kind == "chunked":
            causal &= (qpos // window) == (kpos // window)
        elif kind == "prefix":
            causal |= kpos < prefix_len
        mask &= causal
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows: exp(_NEG - _NEG) would be 1
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_k - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "prefix_len", "softcap", "q_offset",
                     "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, kind="global", window=0,
                           prefix_len=0, softcap=None, q_offset=0,
                           block_q=128, block_k=128,
                           interpret=False) -> jnp.ndarray:
    """Online-softmax flash prefill kernel.

    Head-expanded ``q [B, Sq, H, hd]``; ``k``/``v`` [B, Skv, KV, hd] may
    carry fewer (GQA) heads — the kernel's index maps point q head h at
    kv head ``h // (H // KV)``, so the grouped K/V views coming off the
    packed ``wqkv`` projection are consumed WITHOUT materializing the
    ``jnp.repeat`` head expansion the XLA path pays.
    """
    b, sq, n_h, hd = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    assert n_h % n_kv == 0, (n_h, n_kv)
    g = n_h // n_kv
    bq = min(block_q, _ceil_mult(sq, 8))
    bk = min(block_k, _ceil_mult(skv, 8))
    sq_p, skv_p = _ceil_mult(sq, bq), _ceil_mult(skv, bk)
    hd_p = max(_ceil_mult(hd, 128), 128)

    qr = jnp.moveaxis(q, 2, 1).reshape(b * n_h, sq, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, skv, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, skv, hd)
    qr = _pad_axis(_pad_axis(qr, 1, sq_p), 2, hd_p)
    kr = _pad_axis(_pad_axis(kr, 1, skv_p), 2, hd_p)
    vr = _pad_axis(_pad_axis(vr, 1, skv_p), 2, hd_p)
    n_q, n_k = sq_p // bq, skv_p // bk

    def kv_row(bh):
        return (bh // n_h) * n_kv + (bh % n_h) // g

    grid = (b * n_h, n_q, n_k)
    kernel = functools.partial(
        _prefill_kernel, n_k=n_k, kind=kind, window=window,
        prefix_len=prefix_len, softcap=softcap, q_offset=q_offset,
        kv_len=skv, scale=float(hd) ** -0.5, block_q=bq, block_k=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd_p), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd_p), lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, bk, hd_p), lambda bh, i, j: (kv_row(bh), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd_p), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n_h, sq_p, hd_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_V5E.vmem_bytes),
        interpret=interpret,
    )(qr, kr, vr)
    out = out[:, :sq, :hd].reshape(b, n_h, sq, hd)
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# decode: split-K flash-decode Pallas kernel
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                   tiles_per_split: int, kv_tile: int, kv_len: int,
                   kind: str, softcap, scale: float, g_p: int):
    """Grid = (B*KV, n_splits); each program emits per-tile partials for
    its split's tiles.  Nothing is carried across tiles — partial values
    are a pure function of (tile index, inputs), which is what makes the
    split count irrelevant to the combine's numerics."""
    split = pl.program_id(1)
    pos = pos_ref[0]
    q = q_ref[0]                                    # (g_p, hd_p)
    for tt in range(tiles_per_split):
        k_t = k_ref[0, tt * kv_tile:(tt + 1) * kv_tile]
        v_t = v_ref[0, tt * kv_tile:(tt + 1) * kv_tile]
        s = jax.lax.dot_general(q, k_t, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        slot = ((split * tiles_per_split + tt) * kv_tile
                + jax.lax.broadcasted_iota(jnp.int32, (g_p, kv_tile), 1))
        valid = slot < kv_len
        if kind != "full":
            valid &= slot <= pos
        s = jnp.where(valid, s, _NEG)
        m_t = jnp.max(s, axis=-1)                   # (g_p,)
        p = jnp.where(valid, jnp.exp(s - m_t[:, None]), 0.0)
        l_t = jnp.sum(p, axis=-1)
        acc_t = jax.lax.dot_general(p, v_t, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        m_ref[0, tt] = m_t[None]
        l_ref[0, tt] = l_t[None]
        acc_ref[0, tt] = acc_t


@functools.partial(
    jax.jit,
    static_argnames=("kind", "softcap", "kv_tile", "n_splits", "interpret"))
def flash_decode_pallas(q, k_cache, v_cache, pos, *, kind="global",
                        softcap=None, kv_tile=DEFAULT_KV_TILE, n_splits=1,
                        interpret=False) -> jnp.ndarray:
    """Split-K flash decode: q [B, 1, KV, G, hd] against dense caches
    [B, K, KV, hd] -> [B, 1, KV, G, hd].  ``n_splits`` partitions the KV
    tiles over kernel programs; partials combine OUTSIDE the kernel via
    ``combine_tile_partials``, so any split count is bitwise-identical.
    """
    b, s_q, n_kv, g, hd = q.shape
    assert s_q == 1, "flash decode is single-token (use prefill for S>1)"
    kv_len = k_cache.shape[1]
    n_tiles = _ceil_mult(_ceil_div(kv_len, kv_tile), n_splits)
    tiles_per_split = n_tiles // n_splits
    kv_p = n_tiles * kv_tile
    split_len = tiles_per_split * kv_tile
    hd_p = max(_ceil_mult(hd, 128), 128)
    g_p = _ceil_mult(g, 8)

    qr = _pad_axis(_pad_axis(
        q.reshape(b, n_kv, g, hd), 2, g_p), 3, hd_p)
    qr = qr.reshape(b * n_kv, g_p, hd_p)
    kr = jnp.moveaxis(k_cache, 2, 1).reshape(b * n_kv, kv_len, hd)
    vr = jnp.moveaxis(v_cache, 2, 1).reshape(b * n_kv, kv_len, hd)
    kr = _pad_axis(_pad_axis(kr, 1, kv_p), 2, hd_p)
    vr = _pad_axis(_pad_axis(vr, 1, kv_p), 2, hd_p)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, tiles_per_split=tiles_per_split, kv_tile=kv_tile,
        kv_len=kv_len, kind=kind, softcap=softcap,
        scale=float(hd) ** -0.5, g_p=g_p)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * n_kv, n_splits),
        in_specs=[
            pl.BlockSpec((1, g_p, hd_p), lambda r, s, pos_ref: (r, 0, 0)),
            pl.BlockSpec((1, split_len, hd_p),
                         lambda r, s, pos_ref: (r, s, 0)),
            pl.BlockSpec((1, split_len, hd_p),
                         lambda r, s, pos_ref: (r, s, 0)),
        ],
        # m/l carry a unit axis so each block's trailing dims are whole
        # array dims (the TPU block-shape rule) for any split count
        out_specs=[
            pl.BlockSpec((1, tiles_per_split, 1, g_p),
                         lambda r, s, pos_ref: (r, s, 0, 0)),
            pl.BlockSpec((1, tiles_per_split, 1, g_p),
                         lambda r, s, pos_ref: (r, s, 0, 0)),
            pl.BlockSpec((1, tiles_per_split, g_p, hd_p),
                         lambda r, s, pos_ref: (r, s, 0, 0)),
        ],
    )
    m_t, l_t, acc_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * n_kv, n_tiles, 1, g_p), jnp.float32),
            jax.ShapeDtypeStruct((b * n_kv, n_tiles, 1, g_p), jnp.float32),
            jax.ShapeDtypeStruct((b * n_kv, n_tiles, g_p, hd_p),
                                 jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=TPU_V5E.vmem_bytes),
        interpret=interpret,
    )(pos_arr, qr, kr, vr)
    # tiles to axis 0, then the shared deterministic combine
    out = combine_tile_partials(jnp.moveaxis(m_t[:, :, 0], 1, 0),
                                jnp.moveaxis(l_t[:, :, 0], 1, 0),
                                jnp.moveaxis(acc_t, 1, 0))
    out = out.reshape(b, n_kv, g_p, hd_p)[:, :, :g, :hd]
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: paged flash-decode Pallas kernel (gather-in-kernel)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref,
                         m_ref, l_ref, acc_ref, *, ps: int, kind: str,
                         window: int, softcap, scale: float, g_p: int,
                         n_kv: int):
    """Grid = (B, KV, P): one program per (lane, kv head, logical page).
    The page gather happens in the BlockSpec index map (scalar-prefetched
    page table -> pool row), so only mapped pages move — unmapped slots
    read the trash page and are masked to exact zeros here."""
    lane, page = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[lane]
    q = q_ref[0, 0]                                 # (g_p, hd_p)
    k_t = k_ref[0]                                  # (ps, hd_p)
    v_t = v_ref[0]
    s = jax.lax.dot_general(q, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kvpos = (page * ps
             + jax.lax.broadcasted_iota(jnp.int32, (g_p, ps), 1))
    valid = (table_ref[lane, page] >= 0) & (kvpos <= pos) & (pos >= 0)
    if kind == "local":
        valid &= (pos - kvpos) < window
    elif kind == "chunked":
        valid &= (kvpos // window) == (pos // window)
    s = jnp.where(valid, s, _NEG)
    m_t = jnp.max(s, axis=-1)
    p = jnp.where(valid, jnp.exp(s - m_t[:, None]), 0.0)
    l_t = jnp.sum(p, axis=-1)
    acc_t = jax.lax.dot_general(p, v_t, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    m_ref[0, 0, 0] = m_t[None]
    l_ref[0, 0, 0] = l_t[None]
    acc_ref[0, 0, 0] = acc_t


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "softcap", "interpret"))
def paged_flash_decode_pallas(q, k_pool, v_pool, page_table, positions, *,
                              kind="global", window=0, softcap=None,
                              interpret=False) -> jnp.ndarray:
    """Paged flash decode, gather-in-kernel: q [B, 1, KV, G, hd] against
    pools [NP, PS, KV, hd] through ``page_table`` [B, P] (-1 = unmapped
    -> trash page NP-1, masked to exact zeros) at per-lane ``positions``
    [B] (-1 = idle lane -> all-zero output).  The KV tile is one page;
    partials combine outside the kernel with the same deterministic fold
    as the dense path.
    """
    b, s_q, n_kv, g, hd = q.shape
    assert s_q == 1, "paged flash kernel is decode-only (S == 1)"
    n_pool, ps = k_pool.shape[0], k_pool.shape[1]
    p_max = page_table.shape[1]
    hd_p = max(_ceil_mult(hd, 128), 128)
    g_p = _ceil_mult(g, 8)

    qr = _pad_axis(_pad_axis(q.reshape(b, n_kv, g, hd), 2, g_p), 3, hd_p)
    kr = jnp.moveaxis(k_pool, 2, 1).reshape(n_pool * n_kv, ps, hd)
    vr = jnp.moveaxis(v_pool, 2, 1).reshape(n_pool * n_kv, ps, hd)
    kr = _pad_axis(kr, 2, hd_p)
    vr = _pad_axis(vr, 2, hd_p)
    table = jnp.asarray(page_table, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(b)

    def pool_row(lane, h, page, table_ref, pos_ref):
        t = table_ref[lane, page]
        return (jnp.where(t >= 0, t, n_pool - 1) * n_kv + h, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, kind=kind, window=window,
        softcap=softcap, scale=float(hd) ** -0.5, g_p=g_p, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv, p_max),
        in_specs=[
            pl.BlockSpec((1, 1, g_p, hd_p),
                         lambda lane, h, page, t, p: (lane, h, 0, 0)),
            pl.BlockSpec((1, ps, hd_p), pool_row),
            pl.BlockSpec((1, ps, hd_p), pool_row),
        ],
        # m/l carry a unit axis so each block's trailing dims are whole
        # array dims (the TPU block-shape rule)
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, g_p),
                         lambda lane, h, page, t, p: (page, lane, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, g_p),
                         lambda lane, h, page, t, p: (page, lane, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, g_p, hd_p),
                         lambda lane, h, page, t, p: (page, lane, h, 0, 0)),
        ],
    )
    m_t, l_t, acc_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((p_max, b, n_kv, 1, g_p), jnp.float32),
            jax.ShapeDtypeStruct((p_max, b, n_kv, 1, g_p), jnp.float32),
            jax.ShapeDtypeStruct((p_max, b, n_kv, g_p, hd_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_V5E.vmem_bytes),
        interpret=interpret,
    )(table, pos, qr, kr, vr)
    out = combine_tile_partials(m_t[..., 0, :], l_t[..., 0, :],
                                acc_t)               # [B, KV, g_p, hd_p]
    out = out[:, :, :g, :hd]
    return out[:, None].astype(q.dtype)
