"""Sharded MaxEVA matmul: the paper's X x Y x Z array mapping on a TPU mesh.

Terminology (paper §IV-A/B):
  X — shards of the M dimension (activation rows). On TPU this is the
      data-parallel sharding of the batch and is fixed by the mesh.
  Y — shards of the contraction dimension K.  Partial products are reduced
      *on the array* by the adder tree; here by ``psum``/``psum_scatter``
      over Y-subgroups of the model axis (``axis_index_groups``).
  Z — shards of the N dimension (output columns), i.e. column parallelism.
  broadcast — A tiles are broadcast to their Z consumers; here the
      activation is either already replicated over the model axis (the
      in_spec performs the broadcast) or all-gathered over Z-subgroups when
      it arrives K-sharded from the previous layer.

The model axis of size ``model`` is factored as ``Y * Z = model`` with the
device's model-axis index decomposed z-major: ``y = md % Y, z = md // Y``.

Layout convention (makes consecutive layers compose with ZERO resharding):
  * All K/N chunking is at ``model`` granularity: dimension D is split into
    ``model`` chunks of D/model.
  * Output: device ``md`` emits N-chunk ``md`` — natural order.
  * K blocks are interleaved: Y-block ``y`` = chunks {c : c % Y == y}
    (ordered by c).  Consequently a previous layer's natural-order output
    (K-chunk md on device md) is exactly what the z-subgroup all-gather
    assembles for this layer's Y-block — the neighbour-memory-sharing
    analogue: data is already where the next kernel needs it.
  * Weights are stored pre-sharded in "xyz layout" ``[model, K/Y, N/Z]``
    (sharded on dim 0), the analogue of MaxEVA pinning each kernel's
    buffers at compile time.

Reduction schedules (placement-pattern analogues, §IV-D):
  'allreduce'       — P1 analogue: one heavy reduction; every y-replica
                      materializes the full N/Z block, then keeps its slice.
  'reduce_scatter'  — P2 analogue: strictly fewer wire bytes ((Y-1)/Y vs
                      2(Y-1)/Y) and the output lands pre-sliced.
  'ring'            — beyond-paper: a TRUE collective matmul.  The local
                      GEMM is split into Y N-chunk GEMMs and each chunk's
                      ppermute hop is interleaved with the next chunk's
                      GEMM, so XLA's latency-hiding scheduler overlaps
                      compute with communication (§IV-C ping-pong applied
                      to the wire).
  'bidir_ring'      — the overlapped ring with each N-chunk split into two
                      half-chunks shipped by two OPPOSITE rotation ppermute
                      sets.  Total wire bytes equal 'ring', but each
                      direction's links carry half of them, so on a
                      full-duplex torus the wire time halves (the per-link
                      traffic-balancing lesson of the Versal GEMM energy
                      study applied to the ICI ring).

Determinism contract (extends across ALL FOUR schedules): every y>1
schedule builds its local partial from the SAME per-N-chunk GEMMs (the
shared ``chunk_fn``) and reduces contributions in ascending y-position
order, so the schedule choice never changes numerics — 'ring' and
'bidir_ring' match 'reduce_scatter' bit-for-bit at fp32 (the split-chunk
merge concatenates the rank-order-reduced half-chunk buffers, an
elementwise-identical association), and the planner is free to switch
schedules step-to-step (the placement-pattern analogue: P1 and P2 compute
identical results).  ``tests/test_schedule_equivalence.py`` sweeps the
full (schedule x x_layout x Y x Z x epilogue) grid and asserts it.

Overlapped all-gather (``x_layout='ksharded'``, Z > 1, Y > 1): instead of
a barrier ``all_gather`` of A before the local GEMM, the gather is
CHUNKED — each z-subgroup peer's K-piece arrives by its own rotation
ppermute and is consumed immediately by that piece's GEMM against the
matching weight row-block, so the gather hops hide behind the MXU work
(GotoBLAS2-on-Versal packing/compute overlap applied to the gather side).
The per-piece products are reduced in ascending global K-piece order at
fp32 by EVERY schedule on this path, which keeps the cross-schedule
bitwise contract intact (the K-piece association differs from the
monolithic-GEMM accumulation of the replicated path, so 'ksharded' Z>1
numerics are layout-specific but schedule-independent).  The Y == 1 path
keeps the barrier gather: there is no chunk GEMM to overlap with, and the
whole epilogue stays fused in the kernel's store phase.

Fused epilogues: ``XYZConfig.epilogue`` (a ``kernels.epilogue.Epilogue``)
runs bias/activation/residual/cast/quantize on the GEMM output without an
extra HBM round trip.  With Y == 1 the epilogue runs inside the Pallas
kernel's store phase; with Y > 1 the nonlinear steps must follow the
adder-tree reduction, so they run on the reduced shard inside the same
shard_map body (XLA fuses them into the collective's consumer).  Bias is
passed replicated ``[N]`` and sliced per shard; residual matches the
OUTPUT sharding.  ``quantize`` emits per-N-shard rowwise scales:
``(q [..., N], scale [..., model])``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.sharding import dp_axes, model_size
from repro.kernels import ops as kops
from repro.kernels.epilogue import Epilogue, apply_epilogue


SCHEDULES = ("allreduce", "reduce_scatter", "ring", "bidir_ring")
X_LAYOUTS = ("replicated", "ksharded")


@dataclasses.dataclass(frozen=True)
class XYZConfig:
    """Per-GEMM plan consumed by ``xyz_matmul``."""

    y: int = 1                        # K shards (adder-tree width)
    schedule: str = "reduce_scatter"  # one of SCHEDULES
    x_layout: str = "replicated"      # 'replicated' (broadcast) | 'ksharded'
    out_dtype: Optional[jnp.dtype] = None
    epilogue: Optional[Epilogue] = None   # fused store-phase epilogue

    def __post_init__(self):
        # fail LOUDLY on typos ('ring ' / 'reduce-scatter' / ...): an
        # unknown string silently running some default schedule is exactly
        # the failure mode the determinism contract exists to prevent.
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; valid schedules are "
                f"{SCHEDULES}")
        if self.x_layout not in X_LAYOUTS:
            raise ValueError(
                f"unknown x_layout {self.x_layout!r}; valid layouts are "
                f"{X_LAYOUTS}")

    def z(self, model: int) -> int:
        assert model % self.y == 0, (model, self.y)
        return model // self.y


def _y_groups(model: int, y: int) -> Optional[Sequence[Sequence[int]]]:
    """Devices sharing z (the adder-tree groups): [[z*Y+y for y] for z]."""
    if y == model:
        return None  # full-axis collective
    z = model // y
    return [[zz * y + yy for yy in range(y)] for zz in range(z)]


def _z_groups(model: int, y: int) -> Optional[Sequence[Sequence[int]]]:
    """Devices sharing y (the broadcast groups): [[z*Y+y for z] for y]."""
    z = model // y
    if z == model:
        return None
    return [[zz * y + yy for zz in range(z)] for yy in range(y)]


def schedule_wire_ops(cfg: XYZConfig, model: int) -> Tuple[str, ...]:
    """Collective HLO ops the XYZ plan prices for one forward GEMM — the
    contract auditor's allowed set (``repro.analysis``): any OTHER
    collective in the traced module is a barrier the overlap model never
    accounted for.

    Derived from the same branch structure as ``xyz_matmul``'s body:

    * Y > 1 reductions: 'allreduce' -> all-reduce, 'reduce_scatter' ->
      reduce-scatter, 'ring'/'bidir_ring' -> collective-permute hops;
    * ksharded X with Z > 1: Y > 1 overlaps the gather as ppermute hops
      (collective-permute), Y == 1 keeps the barrier all-gather ON
      PURPOSE (no chunk GEMMs to hide it under — see the Y == 1 branch).
    """
    y, z = cfg.y, cfg.z(model)
    ops = set()
    if y > 1:
        ops.add({"allreduce": "all-reduce",
                 "reduce_scatter": "reduce-scatter",
                 "ring": "collective-permute",
                 "bidir_ring": "collective-permute"}[cfg.schedule])
    if cfg.x_layout == "ksharded" and z > 1:
        ops.add("collective-permute" if y > 1 else "all-gather")
    return tuple(sorted(ops))


def shard_weight_xyz(w: jnp.ndarray, model: int, y: int) -> jnp.ndarray:
    """Repack a [K, N] weight into xyz layout [model, K/Y, N/Z].

    Device md = z*Y+y holds K-chunks {c : c % Y == y} (ordered) of the
    contiguous N-block z."""
    k, n = w.shape
    z = model // y
    assert k % model == 0 and n % z == 0, (w.shape, model, y)
    # (kz, ky, krow, nz, ncol): K-chunk c = kz*Y + ky
    w5 = w.reshape(z, y, k // model, z, n // z)
    # device md = nz*Y + ky  ->  [:, ky, :, nz, :]
    w_dev = jnp.transpose(w5, (3, 1, 0, 2, 4))  # (nz, ky, kz, krow, ncol)
    return w_dev.reshape(model, k // y, n // z)


def unshard_weight_xyz(w_xyz: jnp.ndarray, y: int) -> jnp.ndarray:
    """Inverse of ``shard_weight_xyz`` (checkpoints / elastic resharding)."""
    model, ky_rows, ncol = w_xyz.shape
    z = model // y
    k = ky_rows * y
    w_dev = w_xyz.reshape(z, y, z, k // model, ncol)   # (nz, ky, kz, krow, ncol)
    w5 = jnp.transpose(w_dev, (2, 1, 3, 0, 4))         # (kz, ky, krow, nz, ncol)
    return w5.reshape(k, z * ncol)


def xyz_weight_shape(k: int, n: int, model: int, y: int) -> Tuple[int, int, int]:
    return (model, k // y, n // (model // y))


def _slice_k_block(x2: jnp.ndarray, yid, y: int, model: int) -> jnp.ndarray:
    """From replicated x [rows, K], extract the interleaved Y-block ``yid``:
    K-chunks {c : c % Y == yid}, ordered by c."""
    if y == 1:
        return x2
    rows, k = x2.shape
    z = model // y
    x4 = x2.reshape(rows, z, y, k // model)   # chunk c = kz*Y + ky
    xb = jax.lax.dynamic_index_in_dim(x4, yid, axis=2, keepdims=False)
    return xb.reshape(rows, k // y)


def _local_matmul(x2d: jnp.ndarray, w: jnp.ndarray, *,
                  out_dtype=jnp.float32, epilogue: Optional[Epilogue] = None,
                  bias=None, residual=None, operand2=None, norm_scale=None):
    return kops.matmul(x2d, w, out_dtype=out_dtype, epilogue=epilogue,
                       bias=bias, residual=residual, operand2=operand2,
                       norm_scale=norm_scale)


def _chunk_gemm(x2: jnp.ndarray, wl: jnp.ndarray, c, chunk: int,
                wire_dtype) -> jnp.ndarray:
    """GEMM against N-chunk ``c`` of the local weight shard; the wire cast
    is fused into the kernel's store phase (bitwise identical to casting
    the fp32 accumulator afterwards).  ``c`` may be traced."""
    wc = jax.lax.dynamic_slice_in_dim(wl, c * chunk, chunk, axis=-1)
    return kops.matmul(x2, wc, out_dtype=wire_dtype)


def _partial_from_chunks(chunk_fn, y: int) -> jnp.ndarray:
    """The local partial as a concat of per-N-chunk GEMMs — the SAME chunk
    GEMMs the ring schedules issue, so every schedule sees bitwise
    identical local contributions (cross-schedule determinism)."""
    return jnp.concatenate([chunk_fn(c) for c in range(y)], axis=-1)


def _rotation_pairs(groups, y: int, s: int):
    """ppermute pairs rotating each subgroup by ``s`` positions (``s`` may
    be negative: the opposite ring direction)."""
    if groups is None:
        return [(i, (i + s) % y) for i in range(y)]
    pairs = []
    for g in groups:
        for i, src in enumerate(g):
            pairs.append((src, g[(i + s) % len(g)]))
    return pairs


def _rank_order_sum(buf: jnp.ndarray, wire_dtype) -> jnp.ndarray:
    """Reduce stacked contributions over axis 0 in ascending rank order at
    fp32 — the association XLA's reduce-scatter uses, shared by every
    schedule so the reduction never depends on the wire pattern."""
    acc = buf[0].astype(jnp.float32)
    for i in range(1, buf.shape[0]):
        acc = acc + buf[i].astype(jnp.float32)
    return acc.astype(wire_dtype)


def _ring_collective_matmul(chunk_fn, yid, axis: str, groups, y: int,
                            rows: int, chunk: int,
                            wire_dtype) -> jnp.ndarray:
    """Overlapped collective matmul (the 'ring' schedule).

    The local [rows, Nz] GEMM is split into Y N-chunks.  In round ``s``
    (s = 1..y-1) each device ships its GEMM for chunk ``yid + s`` straight
    to that chunk's owner (a rotation-by-s ppermute within the y-subgroup)
    and issues the NEXT chunk's GEMM before consuming the hop, so the
    compiler can overlap the wire transfer with the MXU work — the §IV-C
    ping-pong discipline applied to inter-chip traffic.  Wire bytes equal
    the classic ring reduce-scatter: (Y-1)/Y of the partial.

    The owner buffers contributions by source y-position and reduces in
    ascending rank order — the association XLA's reduce-scatter uses — so
    the result matches 'reduce_scatter' bit-for-bit at fp32.

    ``chunk_fn(c) -> [rows, chunk]`` is the SHARED per-N-chunk GEMM (or a
    slice of the shared gather-overlap partial on the ksharded path); ``c``
    may be traced.
    """
    buf = jnp.zeros((y, rows, chunk), wire_dtype)
    # own contribution to the chunk this device keeps (no hop)
    buf = jax.lax.dynamic_update_index_in_dim(buf, chunk_fn(yid), yid, 0)
    send = chunk_fn(jax.lax.rem(yid + 1, y))
    for s in range(1, y):
        recv = jax.lax.ppermute(send, axis, _rotation_pairs(groups, y, s))
        if s + 1 < y:
            # issue round s+1's GEMM before consuming round s's hop: the
            # chunk GEMM has no data dependence on the in-flight permute
            send = chunk_fn(jax.lax.rem(yid + s + 1, y))
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, recv, jax.lax.rem(yid - s + y, y), 0)
    return _rank_order_sum(buf, wire_dtype)


def _bidir_ring_collective_matmul(chunk_fn, yid, axis: str, groups, y: int,
                                  rows: int, chunk: int,
                                  wire_dtype) -> jnp.ndarray:
    """Bidirectional overlapped collective matmul ('bidir_ring').

    Each N-chunk GEMM is computed ONCE (same ``chunk_fn`` as 'ring') and
    split into two half-chunks: the low half rides the forward rotation
    set (+s) to the chunk's owner, the high half rides the SECOND,
    opposite rotation set (-s).  Total wire bytes match 'ring', but each
    direction's links carry half of them — on a full-duplex torus both
    directions progress simultaneously and per-link time halves (the
    planner's ``reduction_wire_bytes_per_link`` models exactly this).

    Split-chunk merge: the owner buffers half-chunks by source y-position
    and rank-order-reduces each half independently, then concatenates.
    fp32 addition is elementwise, so reduce-then-concat is bitwise
    identical to 'ring's concat-then-reduce — the determinism contract
    extends to this schedule with no new numeric cases.
    """
    half = chunk // 2
    if half == 0:
        # a 1-column chunk cannot be split; the unidirectional ring is
        # bitwise identical (shared chunk_fn + shared rank-order merge)
        return _ring_collective_matmul(chunk_fn, yid, axis, groups, y,
                                       rows, chunk, wire_dtype)
    gemms = {}

    def g(d: int) -> jnp.ndarray:
        # chunk GEMM at y-offset ``d``, cached: offset d's low half ships
        # in round s=d, its high half in round s=y-d — one GEMM feeds both
        if d not in gemms:
            gemms[d] = chunk_fn(jax.lax.rem(yid + d, y))
        return gemms[d]

    buf_lo = jnp.zeros((y, rows, half), wire_dtype)
    buf_hi = jnp.zeros((y, rows, chunk - half), wire_dtype)
    own = g(0)
    buf_lo = jax.lax.dynamic_update_index_in_dim(buf_lo, own[:, :half],
                                                 yid, 0)
    buf_hi = jax.lax.dynamic_update_index_in_dim(buf_hi, own[:, half:],
                                                 yid, 0)
    for s in range(1, y):
        # forward hop: low half of the chunk owned s positions ahead;
        # backward hop: high half of the chunk owned s positions behind.
        # Neither send depends on any earlier hop, so the second ppermute
        # set overlaps both the first set and the remaining chunk GEMMs.
        recv_lo = jax.lax.ppermute(g(s)[:, :half], axis,
                                   _rotation_pairs(groups, y, s))
        recv_hi = jax.lax.ppermute(g(y - s)[:, half:], axis,
                                   _rotation_pairs(groups, y, -s))
        buf_lo = jax.lax.dynamic_update_index_in_dim(
            buf_lo, recv_lo, jax.lax.rem(yid - s + y, y), 0)
        buf_hi = jax.lax.dynamic_update_index_in_dim(
            buf_hi, recv_hi, jax.lax.rem(yid + s, y), 0)
    return jnp.concatenate([_rank_order_sum(buf_lo, wire_dtype),
                            _rank_order_sum(buf_hi, wire_dtype)], axis=-1)


def _overlapped_gather_partial(x2: jnp.ndarray, wl: jnp.ndarray, axis: str,
                               zgroups, z: int, y: int,
                               wire_dtype) -> jnp.ndarray:
    """Chunked all-gather of A overlapped with the local GEMMs (the
    'ksharded' Z>1, Y>1 path).

    Each z-subgroup peer's natural-order K-piece arrives by its own
    rotation ppermute of the ORIGINAL local piece — no hop depends on an
    earlier hop, so every transfer is in flight while the already-arrived
    pieces' GEMMs run (the GotoBLAS2-on-Versal pack/compute overlap on the
    gather side; the barrier ``all_gather`` + monolithic GEMM this
    replaces serialized the whole gather before the first MAC).

    Every arriving piece is multiplied against its matching weight
    row-block immediately; products are buffered by GLOBAL K-piece
    position and reduced in ascending order at fp32.  All schedules build
    their partial from this ONE helper on this path, so the K-piece
    association is layout-specific but schedule-independent — the bitwise
    cross-schedule contract survives.
    """
    md = jax.lax.axis_index(axis)
    zz = md // y                  # z-position within the gather subgroup
    rows, kloc = x2.shape         # kloc = K/model (one natural-order piece)
    nz = wl.shape[-1]

    def piece_gemm(piece, j):
        # global K-piece j multiplies weight rows [j*kloc, (j+1)*kloc): the
        # interleaved Y-block keeps pieces in ascending z-position order
        wj = jax.lax.dynamic_slice_in_dim(wl, j * kloc, kloc, axis=0)
        return kops.matmul(piece, wj, out_dtype=jnp.float32)

    buf = jnp.zeros((z, rows, nz), jnp.float32)
    buf = jax.lax.dynamic_update_index_in_dim(buf, piece_gemm(x2, zz), zz, 0)
    for s in range(1, z):
        recv = jax.lax.ppermute(x2, axis, _rotation_pairs(zgroups, z, s))
        src = jax.lax.rem(zz - s + z, z)
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, piece_gemm(recv, src), src, 0)
    return _rank_order_sum(buf, wire_dtype)


def _shard_map(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _check_epilogue_operands(ep: Optional[Epilogue], bias, residual,
                             operand2=None, norm_scale=None):
    """Fail fast (outside the shard_map trace) on spec/operand mismatch."""
    if ep is None:
        assert bias is None and residual is None and operand2 is None \
            and norm_scale is None, (
                "bias/residual/operand2/norm_scale operands require an "
                "XYZConfig.epilogue")
        return
    if ep.bias:
        assert bias is not None, "epilogue.bias set but no bias operand"
    if ep.residual:
        assert residual is not None, (
            "epilogue.residual set but no residual operand")
    if ep.gate != "none":
        assert operand2 is not None, (
            "epilogue.gate set but no operand2")
    if ep.norm != "none":
        assert norm_scale is not None, (
            "epilogue.norm set but no norm_scale operand")


def xyz_matmul(
    x: jnp.ndarray,
    w_xyz: jnp.ndarray,
    *,
    mesh: Mesh,
    cfg: XYZConfig,
    batch_sharded: bool = True,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    operand2: Optional[jnp.ndarray] = None,
    norm_scale: Optional[jnp.ndarray] = None,
):
    """out[..., N] = epilogue(x[..., K] @ W), distributed per the XYZ plan.

    ``w_xyz`` is in xyz layout ([model, K/Y, N/Z], sharded on dim 0).
    Output is N-sharded over the model axis in natural chunk order; ``x``
    is row-sharded over the data axes (X) and either replicated over model
    ('replicated' — the broadcast) or K-sharded in natural order
    ('ksharded' — a previous layer's output).

    ``bias`` is replicated ``[N]``; ``residual`` and ``operand2`` (the
    gate epilogue's second tensor) match the OUTPUT (N-sharded over
    model).  With ``cfg.epilogue.quantize`` the return is ``(q [..., N]
    int8, scale [..., model] f32)`` with per-N-shard rowwise scales.

    ``norm='rmsnorm'`` epilogues need the FULL output row for the mean of
    squares, which an N-sharded output never holds — they are valid here
    only on a model==1 mesh; multi-shard callers use
    ``xyz_matmul_replicated_out`` (full N on every replica after the
    psum) instead.
    """
    model = model_size(mesh)
    ep = cfg.epilogue
    _check_epilogue_operands(ep, bias, residual, operand2, norm_scale)
    if ep is not None and ep.norm != "none" and model > 1:
        raise ValueError(
            "norm epilogues need the full output row; xyz_matmul shards N "
            "over the model axis — use xyz_matmul_replicated_out "
            "(Y == model) or fall back to a standalone norm")
    if model == 1:
        from repro.kernels.quantize import QuantizedWeight
        if isinstance(w_xyz, QuantizedWeight):
            # int8 serving path: the single-shard xyz layout [1, K, N] is
            # consumed as the quantized matrix directly (kops.matmul
            # quantizes x rowwise and folds both scales into the store
            # phase — no dequantized fp32 weight ever materializes)
            w = w_xyz
        else:
            w = unshard_weight_xyz(w_xyz, cfg.y)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if ep is None:
            out = _local_matmul(x2, w)
            return out.astype(cfg.out_dtype or x.dtype).reshape(*lead, -1)
        ep1 = dataclasses.replace(
            ep, out_dtype=ep.out_dtype or cfg.out_dtype or x.dtype)
        res2 = residual.reshape(-1, residual.shape[-1]) \
            if residual is not None else None
        o2 = operand2.reshape(-1, operand2.shape[-1]) \
            if operand2 is not None else None
        out = _local_matmul(x2, w, epilogue=ep1, bias=bias, residual=res2,
                            operand2=o2, norm_scale=norm_scale)
        if ep1.quantize:
            q, s = out
            return (q.reshape(*lead, -1), s.reshape(*lead, -1))
        if ep1.norm != "none":
            value, normed = out
            return (value.reshape(*lead, -1), normed.reshape(*lead, -1))
        return out.reshape(*lead, -1)

    y, z = cfg.y, cfg.z(model)
    from repro.core.sharding import row_axes
    row_spec = row_axes(mesh, x.shape[0]) if batch_sharded else None
    mid = [None] * (x.ndim - 2)

    x_spec = P(row_spec, *mid,
               "model" if cfg.x_layout == "ksharded" else None)
    out_spec = P(row_spec, *mid, "model")

    ygroups = _y_groups(model, y)
    zgroups = _z_groups(model, y)
    wire_dtype = cfg.out_dtype or x.dtype
    n_total = w_xyz.shape[-1] * z          # global N
    nloc_out = n_total // model            # every device emits N-chunk md

    def _finish(out2, md, res2, o2):
        """Post-reduction epilogue on the device's [rows, N/model] shard.
        Elementwise per output element (gate included — operand2 is
        sharded exactly like the output), so applying it after ANY of the
        four reductions preserves the bitwise cross-schedule contract."""
        if ep is None or (ep.is_identity and ep.out_dtype is None):
            return out2.astype(wire_dtype)
        b_loc = jax.lax.dynamic_slice_in_dim(
            bias, md * nloc_out, nloc_out, axis=-1) if ep.bias else None
        return apply_epilogue(out2, dataclasses.replace(
            ep, out_dtype=ep.out_dtype or wire_dtype), bias=b_loc,
            residual=res2, operand2=o2)

    def body(*args):
        xl, wl = args[0], args[1]
        pos = 2
        res_l = None
        if ep is not None and ep.residual:
            res_l = args[pos]
            pos += 1
        op2_l = None
        if ep is not None and ep.gate != "none":
            op2_l = args[pos]
            pos += 1
        wl = wl[0]  # [K/Y, N/Z]
        md = jax.lax.axis_index("model")
        yid = jax.lax.rem(md, y)
        lead = xl.shape[:-1]
        x2 = xl.reshape(-1, xl.shape[-1])
        res2 = res_l.reshape(-1, res_l.shape[-1]) if res_l is not None \
            else None
        o2 = op2_l.reshape(-1, op2_l.shape[-1]) if op2_l is not None \
            else None

        gather_partial = None
        if cfg.x_layout == "replicated":
            x2 = _slice_k_block(x2, yid, y, model)
        elif z > 1 and y > 1:
            # overlapped all-gather: the Y-block is never materialized —
            # each natural-order K-piece hops in by ppermute and is
            # consumed by its GEMM immediately (every schedule shares this
            # partial, keeping numerics schedule-independent)
            gather_partial = _overlapped_gather_partial(
                x2, wl, "model", zgroups, z, y, wire_dtype)
        elif z > 1:
            # Y == 1: no chunk GEMMs to overlap with — barrier-gather the
            # Y-block from natural-order K shards (the z-subgroup gather
            # concatenates chunks {y, Y+y, ...} in order, exactly the
            # interleaved block the weight layout expects) so the whole
            # epilogue stays fused in the kernel's store phase below.
            x2 = jax.lax.all_gather(x2, "model", axis_index_groups=zgroups,
                                    axis=1, tiled=True)

        nz = wl.shape[-1]
        if y == 1:
            # no reduction: the WHOLE epilogue fuses into the kernel's
            # store phase (bias sliced to this device's N-block).
            if ep is None:
                out = _local_matmul(x2, wl, out_dtype=wire_dtype)
            else:
                ep1 = dataclasses.replace(
                    ep, out_dtype=ep.out_dtype or wire_dtype)
                b_loc = jax.lax.dynamic_slice_in_dim(
                    bias, md * nloc_out, nloc_out, axis=-1) \
                    if ep.bias else None
                out = _local_matmul(x2, wl, epilogue=ep1, bias=b_loc,
                                    residual=res2, operand2=o2)
        else:
            # the wire format (and its AD transpose buffers) stays 16-bit
            # when out_dtype says so; the rank-order reduction upcasts.
            assert nz % y == 0, (nz, y)  # else chunking silently drops cols
            chunk = nz // y
            if gather_partial is not None:
                # ksharded Z>1: the GEMM work already ran inside the
                # overlapped gather — chunks are slices of ONE partial
                def chunk_fn(c):
                    return jax.lax.dynamic_slice_in_dim(
                        gather_partial, c * chunk, chunk, axis=-1)
            else:
                def chunk_fn(c):
                    return _chunk_gemm(x2, wl, c, chunk, wire_dtype)
            rows2 = x2.shape[0]
            if cfg.schedule == "allreduce":
                partial = _partial_from_chunks(chunk_fn, y)
                red = jax.lax.psum(partial, "model",
                                   axis_index_groups=ygroups)
                out = jax.lax.dynamic_slice_in_dim(
                    red, yid * chunk, chunk, axis=-1)
            elif cfg.schedule == "reduce_scatter":
                partial = _partial_from_chunks(chunk_fn, y)
                out = jax.lax.psum_scatter(
                    partial, "model", scatter_dimension=partial.ndim - 1,
                    axis_index_groups=ygroups, tiled=True)
            elif cfg.schedule == "ring":
                out = _ring_collective_matmul(chunk_fn, yid, "model",
                                              ygroups, y, rows2, chunk,
                                              wire_dtype)
            elif cfg.schedule == "bidir_ring":
                out = _bidir_ring_collective_matmul(chunk_fn, yid, "model",
                                                    ygroups, y, rows2,
                                                    chunk, wire_dtype)
            else:  # unreachable: XYZConfig.__post_init__ validates
                raise ValueError(cfg.schedule)
            if ep is not None:
                out = _finish(out, md, res2, o2)

        if ep is not None and ep.quantize:
            q, s = out
            return (q.reshape(*lead, -1), s.reshape(*lead, -1))
        out = out.astype(ep.out_dtype if ep is not None and ep.out_dtype
                         else wire_dtype)
        return out.reshape(*lead, -1)

    in_specs = [x_spec, P("model", None, None)]
    operands = [x, w_xyz]
    if ep is not None and ep.residual:
        assert residual is not None
        in_specs.append(P(row_spec, *mid, "model"))
        operands.append(residual)
    if ep is not None and ep.gate != "none":
        assert operand2 is not None
        # the gate operand is an [.., N] tensor matching the OUTPUT
        # sharding (the gated MLP's g matches the up GEMM's output)
        in_specs.append(P(row_spec, *mid, "model"))
        operands.append(operand2)
    if ep is not None and ep.quantize:
        out_specs = (out_spec, P(row_spec, *mid, "model"))
    else:
        out_specs = out_spec
    return _shard_map(body, mesh, tuple(in_specs), out_specs)(*operands)


def xyz_matmul_replicated_out(
    x: jnp.ndarray,
    w_xyz: jnp.ndarray,
    *,
    mesh: Mesh,
    cfg: XYZConfig,
    batch_sharded: bool = True,
    bias: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
    operand2: Optional[jnp.ndarray] = None,
    norm_scale: Optional[jnp.ndarray] = None,
):
    """Row-parallel variant with fully replicated (over model) output:
    Y = model, Z = 1, one psum/ring-allreduce — the classic Megatron
    down-projection.  Used when the next op needs the full feature
    dimension on every device (residual adds on replicated activations).

    The epilogue (bias [N], residual / operand2 [.., N] replicated) is
    applied after the psum on every replica — still inside the shard_map
    body, so XLA fuses it into the all-reduce consumer.  Because every
    replica holds the FULL feature row post-psum, this is the multi-shard
    home of the ``norm='rmsnorm'`` epilogue: the down-projection emits
    ``(h_new, rmsnorm(h_new))`` and the next block's input norm never
    re-reads the residual stream."""
    model = model_size(mesh)
    ep = cfg.epilogue
    _check_epilogue_operands(ep, bias, residual, operand2, norm_scale)
    if model == 1:
        return xyz_matmul(x, w_xyz, mesh=mesh, cfg=cfg,
                          batch_sharded=batch_sharded, bias=bias,
                          residual=residual, operand2=operand2,
                          norm_scale=norm_scale)
    assert cfg.y == model, "replicated-out requires Y == model"
    from repro.core.sharding import row_axes
    row_spec = row_axes(mesh, x.shape[0]) if batch_sharded else None
    mid = [None] * (x.ndim - 2)
    x_spec = P(row_spec, *mid,
               "model" if cfg.x_layout == "ksharded" else None)
    wire_dtype = cfg.out_dtype or x.dtype

    def body(*args):
        xl, wl = args[0], args[1]
        pos = 2
        res_l = None
        if ep is not None and ep.residual:
            res_l = args[pos]
            pos += 1
        op2_l = None
        if ep is not None and ep.gate != "none":
            op2_l = args[pos]
            pos += 1
        ns_l = None
        if ep is not None and ep.norm != "none":
            ns_l = args[pos]
            pos += 1
        wl = wl[0]
        md = jax.lax.axis_index("model")
        lead = xl.shape[:-1]
        x2 = xl.reshape(-1, xl.shape[-1])
        if cfg.x_layout == "replicated":
            x2 = _slice_k_block(x2, md, model, model)
        # wire cast fused into the kernel's store phase
        partial = _local_matmul(x2, wl, out_dtype=wire_dtype)
        out = jax.lax.psum(partial, "model")
        if ep is not None:
            res2 = res_l.reshape(-1, res_l.shape[-1]) if res_l is not None \
                else None
            o2 = op2_l.reshape(-1, op2_l.shape[-1]) if op2_l is not None \
                else None
            out = apply_epilogue(out, dataclasses.replace(
                ep, out_dtype=ep.out_dtype or wire_dtype), bias=bias,
                residual=res2, operand2=o2, norm_scale=ns_l)
            if ep.quantize:
                q, s = out
                return (q.reshape(*lead, -1), s.reshape(*lead, -1))
            if ep.norm != "none":
                value, normed = out
                return (value.reshape(*lead, -1),
                        normed.reshape(*lead, -1))
        return out.reshape(*lead, -1)

    in_specs = [x_spec, P("model", None, None)]
    operands = [x, w_xyz]
    if ep is not None and ep.residual:
        assert residual is not None
        in_specs.append(P(row_spec, *mid, None))
        operands.append(residual)
    if ep is not None and ep.gate != "none":
        assert operand2 is not None
        in_specs.append(P(row_spec, *mid, None))
        operands.append(operand2)
    if ep is not None and ep.norm != "none":
        assert norm_scale is not None
        in_specs.append(P(None))
        operands.append(norm_scale)
    if ep is not None and (ep.quantize or ep.norm != "none"):
        out_specs = (P(row_spec, *mid, None), P(row_spec, *mid, None))
    else:
        out_specs = P(row_spec, *mid, None)
    return _shard_map(body, mesh, tuple(in_specs), out_specs)(*operands)
