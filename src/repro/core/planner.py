"""MaxEVA analytical planner.

Two levels, mirroring the paper (§IV-C):

1. Single-kernel optimization — choose the per-core tile ``M x K x N``
   (paper eq. 1-6).  On the AIE this is the per-AIE-core kernel; on TPU it
   is the Pallas block ``(bm, bk, bn)`` pipelined through VMEM.

2. Array-level optimization — choose the spatial decomposition ``X x Y x Z``
   (paper eq. 7-9): X shards the M dimension, Y shards the contraction K
   (reduced on-array by the adder tree / ``psum``), Z shards N.  On the AIE
   the constraints are core count and PLIO ports; on TPU they are HBM
   capacity and ICI wire-time.

Both searches are exhaustive over powers of two, exactly as the paper
argues is sufficient (§IV-C, §V-A).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.device_model import AIE_VC1902, TPU_V5E, AIEDevice, TPUDevice, DTYPE_BYTES


def _pow2_range(lo: int, hi: int) -> List[int]:
    out = []
    v = 1
    while v <= hi:
        if v >= lo:
            out.append(v)
        v *= 2
    return out


# ---------------------------------------------------------------------------
# 1. Single-kernel level (paper eq. 1-6)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelTile:
    m: int
    k: int
    n: int
    macs: int
    buffer_bytes: int

    def as_tuple(self) -> Tuple[int, int, int]:
        return (self.m, self.k, self.n)


def solve_aie_kernel_tiles(
    precision: str,
    device: AIEDevice = AIE_VC1902,
    eff_lb: float = 0.95,
    max_dim: int = 1024,
) -> List[KernelTile]:
    """Exhaustive power-of-two IP for M, K, N (paper eq. 3-6).

    Returns all maximal-MAC feasible tiles, sorted by (-macs, m, k, n).
    For int8 the paper reports a unique solution 32x128x32; for fp32 a
    family of ties at 32768 MACs including 32x32x32.
    """
    peak = device.peak_macs[precision]
    sa = device.sizeof_in(precision)
    sb = device.sizeof_in(precision)
    sc = device.sizeof_out(precision)
    bw = device.bw_io_bytes_per_cyc

    # eq. 3-5: I/O-bandwidth lower bounds on N, M, K.
    n_min = eff_lb * peak * sa / bw
    m_min = eff_lb * peak * sb / bw
    k_min = eff_lb * peak * sc / bw

    feas: List[KernelTile] = []
    for m, k, n in itertools.product(_pow2_range(1, max_dim), repeat=3):
        if n < n_min or m < m_min or k < k_min:
            continue
        # eq. 6: double-buffered working set fits the usable local memory.
        buf = m * k * sa + k * n * sb + m * n * sc
        if buf > device.usable_buffer_bytes:
            continue
        feas.append(KernelTile(m, k, n, m * k * n, buf))
    feas.sort(key=lambda t: (-t.macs, t.m, t.k, t.n))
    if not feas:
        return []
    best = feas[0].macs
    return [t for t in feas if t.macs == best]


# ---------------------------------------------------------------------------
# 2. Array level (paper eq. 7-9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArrayConfig:
    x: int
    y: int
    z: int

    @property
    def matmul_kernels(self) -> int:
        return self.x * self.y * self.z

    @property
    def adder_cores(self) -> int:
        # One core runs the whole (Y-1)-kernel adder tree of each (x, z)
        # group (paper §IV-B); Y == 1 needs no reduction at all.
        return self.x * self.z if self.y > 1 else 0

    @property
    def total_cores(self) -> int:
        return self.matmul_kernels + self.adder_cores

    @property
    def plio_in(self) -> int:
        return self.x * self.y + self.y * self.z

    @property
    def plio_out(self) -> int:
        return self.x * self.z

    @property
    def pattern(self) -> str:
        # Placement patterns are proposed for Y=3 (P2, DMA-free) and Y=4
        # (P1, T-shapes with a little DMA) — paper §IV-D / Fig. 7.
        return {3: "P2", 4: "P1"}.get(self.y, "P?")

    @property
    def dma_banks(self) -> int:
        # P1's T-shapes spill one MatMul output buffer (double-buffered,
        # 4KB each half => 2 banks) per T-shape.  Fitted to the paper's
        # reported 16-18 banks for X=12..13: one T-shape per 'column pair'
        # of groups, empirically ~ceil(x*z/9) shapes. We model DMA banks
        # as reported by the paper for its configs (see perf_model tables)
        # and approximate 2*ceil(x*z/9) elsewhere.
        if self.pattern != "P1":
            return 0
        return 2 * math.ceil(self.x * self.z / 9)


def solve_aie_array(
    device: AIEDevice = AIE_VC1902,
    y_values: Sequence[int] = (2, 3, 4, 5, 6, 7, 8),
    top: int = 10,
    require_placement_pattern: bool = False,
) -> List[ArrayConfig]:
    """Exhaustive search of X, Y, Z maximizing MatMul kernels (eq. 7-9).

    ``require_placement_pattern`` restricts to Y in {3, 4}, the values for
    which the paper provides placement patterns (§IV-D).
    """
    cands: List[ArrayConfig] = []
    seen = set()
    for y in y_values:
        if require_placement_pattern and y not in (3, 4):
            continue
        for x in range(1, device.n_cores + 1):
            for z in range(1, device.n_cores // max(1, x * y) + 2):
                cfg = ArrayConfig(x, y, z)
                if cfg.total_cores > device.n_cores:
                    continue
                if cfg.plio_in > device.plio_in:
                    continue
                if cfg.plio_out > device.plio_out:
                    continue
                # X<->Z mirror images are identical designs (same kernel
                # count, same port usage); keep the X >= Z representative,
                # matching the paper's reporting.
                key = (y, min(x, z), max(x, z))
                if key in seen:
                    continue
                seen.add(key)
                cands.append(ArrayConfig(max(x, z), y, min(x, z)))
    cands.sort(key=lambda c: (-c.matmul_kernels, c.adder_cores, c.x))
    return cands[:top]


def pnr_feasible(cfg: ArrayConfig, device: AIEDevice = AIE_VC1902) -> bool:
    """Routability model calibrated on the paper's account (§V-B1): the
    MAC-maximal 10x4x8 point fails AIE place-and-route because it combines
    100% core utilization with pattern-P1 DMA routing; P2 designs route
    even at 100% utilization (10x3x10)."""
    full = cfg.total_cores >= device.n_cores
    return not (full and cfg.dma_banks > 0)


# ---------------------------------------------------------------------------
# 3. TPU-mode planner (hardware adaptation of eq. 1-9)
# ---------------------------------------------------------------------------


def epilogue_hbm_bytes(m: int, n: int, epilogue=None,
                       fused: bool = True) -> int:
    """HBM bytes the GEMM's output stage moves for an ``[m, n]`` result.

    fused:   the kernel stores the finished epilogue output once (plus the
             quantize scale column and any bias/residual operand reads).
    unfused: the kernel writes the fp32 accumulator, and a separate
             elementwise op reads it back and writes the final output —
             the 2 * 4 * m * n round trip the fusion deletes (the paper's
             §IV-C discipline of never letting partials touch slow
             memory, applied to the epilogue).

    The v2 algebra's stages price per their operand traffic: the gate
    stage reads a second ``[m, n]`` tensor either way, but unfused it
    also re-reads the GEMM output and re-writes the product (a whole
    extra elementwise pass); the rmsnorm stage writes a second ``[m, n]``
    output (the normed stream) plus the ``[n]`` scale either way, but
    unfused it re-reads the just-stored value and the standalone add +
    norm round-trips the residual stream once more — the read + write
    per block the fold deletes.
    """
    if epilogue is None:
        return 4 * m * n if fused else 3 * 4 * m * n
    item = epilogue.out_itemsize()
    gate = getattr(epilogue, "gate", "none") != "none"
    norm = getattr(epilogue, "norm", "none") != "none"
    out_b = m * n * item
    if epilogue.quantize:
        # scale vector: one f32 per row ('row') or per column ('col')
        out_b += (m if getattr(epilogue, "quantize_axis", "row") == "row"
                  else n) * 4
    operand_b = (n * 4 if epilogue.bias else 0) + (
        m * n * item if epilogue.residual else 0) + (
        m * n * item if gate else 0)
    if norm:
        out_b += m * n * item + 4 * n      # normed stream + scale vector
    if fused:
        return out_b + operand_b
    unfused = 2 * 4 * m * n + out_b + operand_b
    if gate:
        # standalone silu(g) * u: re-read the GEMM output, re-write the
        # product (the g read is already in operand_b)
        unfused += 2 * m * n * item
    if norm:
        # standalone add + rmsnorm: the residual stream's extra read +
        # write between the down projection and the next block
        unfused += 2 * m * n * item
    return unfused


def int8_gemm_hbm_bytes(m: int, k: int, n: int, fused: bool = True,
                        out_itemsize: int = 2) -> int:
    """HBM bytes of the serving int8 GEMM ``[m, k] x [k, n]``.

    fused:   the paper's pipeline (§IV-C1) — int8 operands stream in with
             their f32 row/col scale vectors, the int32 accumulator never
             leaves VMEM, scales are re-applied in the store phase, and
             ONE finished output is written.
    unfused: the fp32 *bounce* the serving path must avoid — both
             operands are dequantized to fp32 (int8 read + fp32 write +
             fp32 read-back each), the GEMM runs on 4-byte operands, and
             the fp32 result round-trips once more before the output
             store.  ``hlo_analysis.int8_bounce_count`` is the HLO-level
             guard against exactly this pattern.
    """
    a_b, w_b, o_b = m * k, k * n, m * n
    scales = 4 * (m + n)
    if fused:
        return a_b + w_b + scales + out_itemsize * o_b
    dequant = (a_b + 4 * a_b + 4 * a_b) + (w_b + 4 * w_b + 4 * w_b)
    return dequant + scales + 2 * 4 * o_b + out_itemsize * o_b


@dataclasses.dataclass(frozen=True)
class TPUBlockPlan:
    """Pallas block choice for one GEMM executed per-chip."""

    bm: int
    bk: int
    bn: int
    vmem_bytes: int
    macs: int
    # amortized HBM bytes moved per output element with this blocking
    hbm_bytes_per_flop: float


def reduction_wire_bytes_per_link(c_bytes: int, y: int,
                                  schedule: str) -> float:
    """Per-link wire bytes of the Y-subgroup reduction of a ``c_bytes``
    partial (the PLIO-port traffic analog, per ICI link).

    'allreduce' pays the RS+AG decomposition (2(Y-1)/Y); 'reduce_scatter'
    and 'ring' ship (Y-1)/Y of the partial over each link; 'bidir_ring'
    moves the SAME total bytes but splits every chunk across the two ring
    directions, so each (full-duplex) link carries half — the per-link
    traffic halving the Versal torus energy study identifies as the
    efficiency headroom.
    """
    if y <= 1 or schedule == "none":
        return 0.0
    if schedule == "allreduce":
        return 2.0 * (y - 1) / y * c_bytes
    if schedule in ("reduce_scatter", "ring"):
        return (y - 1) / y * c_bytes
    if schedule == "bidir_ring":
        return (y - 1) / (2.0 * y) * c_bytes
    raise ValueError(f"unknown schedule {schedule!r}")


def gather_wire_bytes_per_link(a_bytes: int, z: int) -> float:
    """Per-link bytes of (all-)gathering A over a Z-subgroup: each link
    carries (Z-1)/Z of the gathered block, whether the gather is the
    barrier ``all_gather`` (Y == 1) or the chunked ppermute ring the
    overlapped path uses (Y > 1) — the overlap changes WHEN the bytes
    move, not how many."""
    if z <= 1:
        return 0.0
    return (z - 1) / z * a_bytes


@dataclasses.dataclass(frozen=True)
class XYZShardPlan:
    """Array-level decomposition of one GEMM over mesh axes.

    x_shards: shards of M (data-parallel axis)
    y_shards: shards of K (contraction; needs on-array reduction = psum)
    z_shards: shards of N (column-parallel)
    schedule: 'allreduce' (P1 analog) | 'reduce_scatter' (P2 analog)
              | 'ring' / 'bidir_ring' (beyond-paper overlapped collective
                matmuls; bidir halves per-link reduction bytes)
              | 'none' (y_shards == 1)
    est_gather_s: per-link seconds of gathering a model-sharded A over the
                  Z-subgroup (0 when A is replicated or Z == 1)
    """

    x_shards: int
    y_shards: int
    z_shards: int
    schedule: str
    est_collective_s: float
    est_compute_s: float
    est_hbm_s: float
    est_gather_s: float = 0.0

    @property
    def est_step_s(self) -> float:
        """Step time under the schedule's overlap model: the overlapped
        collective matmuls ('ring' / 'bidir_ring') interleave chunk GEMMs
        with ppermute hops AND overlap the chunked gather of A, so compute
        and wire overlap (max; reduction and gather share the ICI links,
        so their times add inside the wire term).  The barrier reductions
        serialize the collective after the local GEMM, but their partial
        GEMMs still ride the chunked gather (max with compute).  Y == 1
        ('none') keeps the serial barrier gather before its single GEMM.
        """
        if self.y_shards <= 1 or self.schedule == "none":
            # no reduction AND no chunk GEMMs to hide the gather behind:
            # xyz_matmul keeps the serial barrier gather at Y == 1
            # whatever the schedule string says
            return max(self.est_hbm_s,
                       self.est_compute_s + self.est_gather_s)
        if self.schedule in ("ring", "bidir_ring"):
            return max(self.est_compute_s, self.est_hbm_s,
                       self.est_collective_s + self.est_gather_s)
        return max(self.est_hbm_s,
                   max(self.est_compute_s, self.est_gather_s)
                   + self.est_collective_s)


@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    m: int
    k: int
    n: int
    dtype: str
    block: TPUBlockPlan
    shard: XYZShardPlan


def _tile_bytes(rows: int, cols: int, itemsize: int,
                device: TPUDevice) -> int:
    """VMEM bytes of a ``(rows, cols)`` block: the layout pads rows to the
    dtype's native tile height and columns to the 128-lane width."""
    r = -(-rows // device.row_tile(itemsize)) * device.row_tile(itemsize)
    c = -(-cols // 128) * 128
    return r * c * itemsize


def block_vmem_bytes(bm: int, bk: int, bn: int, dtype: str = "bf16",
                     device: TPUDevice = TPU_V5E, accum_bytes: int = 4,
                     epilogue=None) -> int:
    """VMEM working set of one ``matmul_pallas`` program with block
    ``(bm, bk, bn)``: the pipeline double-buffers every blocked operand
    and output, the accumulator tile is a single scratch, and a
    non-trivial store phase holds fp32 temporaries of the tile.

    Epilogue tensor operands (residual, gate operand) are counted at
    4 bytes, the widest dtype they arrive in.  int8 inputs always carry
    their row/column scales into the store phase."""
    ebytes = DTYPE_BYTES[dtype]
    ep = epilogue
    scaled = dtype == "int8"
    row_vec = _tile_bytes(bm, 1, 4, device)      # [bm, 1] f32 column
    col_vec = _tile_bytes(1, bn, 4, device)      # [1, bn] f32 row
    tile32 = _tile_bytes(bm, bn, 4, device)
    buffered = (_tile_bytes(bm, bk, ebytes, device)
                + _tile_bytes(bk, bn, ebytes, device))
    if scaled:
        buffered += row_vec + col_vec
    if ep is not None:
        buffered += col_vec * (int(ep.bias) + int(ep.norm != "none"))
        buffered += tile32 * (int(ep.residual) + int(ep.gate != "none"))
        out_bytes = ep.out_itemsize()
    else:
        out_bytes = accum_bytes
    out = _tile_bytes(bm, bn, out_bytes, device)
    if ep is not None and ep.quantize:
        out += row_vec
    elif ep is not None and ep.norm != "none":
        out *= 2
    buffered += out
    temps = 2 * tile32 if (scaled or (ep is not None
                                      and not ep.is_identity)) else 0
    return 2 * buffered + bm * bn * accum_bytes + temps


def plan_tpu_block(
    m: int,
    k: int,
    n: int,
    dtype: str = "bf16",
    device: TPUDevice = TPU_V5E,
    accum_bytes: int = 4,
    epilogue=None,
) -> TPUBlockPlan:
    """Single-kernel level on TPU: pick the Pallas block (bm, bk, bn).

    The constraint structure mirrors eq. 1-6:
      * eq. 1 (efficiency bound)  -> MXU alignment: bn, bk multiples of
        the systolic tile and bm a multiple of the dtype's native tile
        height, so the MXU runs at rated throughput;
      * eq. 2 (I/O bound)         -> HBM-bandwidth bound: streaming the A
        and B blocks must not take longer than the MXU needs for the block,
        i.e. bn, bm >= peak_flops * sizeof / (2 * hbm_bw)  (the roofline
        ridge point expressed per-dimension);
      * eq. 6 (local memory)      -> the whole pipelined working set
        (``block_vmem_bytes``: double-buffered operands, epilogue
        operands and outputs, accumulator, store-phase temporaries)
        fits the VMEM budget.
    Objective identical to the paper: maximize MACs per block (data reuse
    in registers/VMEM), tie-break to the squarest block.
    """
    ebytes = DTYPE_BYTES[dtype]
    flops = device.peak_flops[dtype]
    # ridge-point lower bound (eq. 2 analog). 240 for bf16 on v5e.
    io_min = flops * ebytes / (2.0 * device.hbm_bw)
    row_tile = device.row_tile(ebytes)

    best: Optional[TPUBlockPlan] = None
    dim_cap = 4096
    full_row = epilogue is not None and (
        (epilogue.quantize and epilogue.quantize_axis == "row")
        or epilogue.norm != "none")
    if full_row:
        # rowwise scale / rmsnorm needs the whole row in one block: the
        # kernel runs with bn = ceil128(n) regardless of the plan, so plan
        # (and account VMEM for) exactly that block.  With the block
        # covering all of N, A-reuse is maximal and eq. 2's bm bound is
        # waived — the fp32 row tiles dominate VMEM instead.
        bn_candidates = [max(device.mxu_dim, 128 * ((n + 127) // 128))]
    else:
        bn_candidates = [bn for bn in _pow2_range(device.mxu_dim, dim_cap)
                         if bn <= max(n, device.mxu_dim) * 2]
    for bm in _pow2_range(row_tile, dim_cap):
        if bm > max(m, row_tile) * 2:
            continue
        for bn in bn_candidates:
            for bk in _pow2_range(device.mxu_dim, dim_cap):
                if bk > max(k, device.mxu_dim) * 2:
                    continue
                # eq. 2 analog: HBM streaming must keep up with the MXU,
                # unless the dimension is exhausted (block covers it).
                if bn < min(io_min, n) or (not full_row
                                           and bm < min(io_min, m)):
                    continue
                # eq. 6 analog: the pipelined working set.
                vmem = block_vmem_bytes(bm, bk, bn, dtype, device,
                                        accum_bytes, epilogue)
                if vmem > device.vmem_budget:
                    continue
                macs = bm * bk * bn
                cand = TPUBlockPlan(
                    bm, bk, bn, vmem, macs,
                    hbm_bytes_per_flop=(bm * bk + bk * bn) * ebytes / (2 * macs),
                )
                key = (macs, -abs(math.log2(bm) - math.log2(bn)), -vmem)
                if best is None or key > (
                    best.macs,
                    -abs(math.log2(best.bm) - math.log2(best.bn)),
                    -best.vmem_bytes,
                ):
                    best = cand
    assert best is not None, "no feasible TPU block plan"
    return best


def _ring_collective_s(bytes_total: int, shards: int, device: TPUDevice) -> float:
    """Ring all-reduce time over one mesh axis (2(n-1)/n, the RS+AG
    decomposition) — kept for callers that want the schedule-agnostic
    upper bound; the planner itself now prices each schedule via
    ``reduction_wire_bytes_per_link``."""
    if shards <= 1 or bytes_total == 0:
        return 0.0
    return 2.0 * (shards - 1) / shards * bytes_total / device.ici_bw_per_link


def plan_tpu_shard(
    m: int,
    k: int,
    n: int,
    dtype: str,
    mesh_axes: Dict[str, int],
    device: TPUDevice = TPU_V5E,
    batch_axis: str = "data",
    model_axis: str = "model",
    a_sharded_on_model: bool = False,
    prefer_schedule: Optional[str] = None,
    epilogue=None,
) -> XYZShardPlan:
    """Array-level XYZ search on TPU (eq. 7-9 analog).

    X is fixed by the batch axis (M is activation rows).  The search is
    over the factorization of the model axis into Y (K-shards, reduced by
    the adder-tree analog: psum/psum_scatter) times Z (N-shards), plus the
    reduction schedule.  Constraints: per-device weight shard must fit a
    HBM fraction; objective: minimize the max of compute / HBM / wire time
    (the paper maximizes MatMul kernels subject to port limits; with fixed
    chip count the dual is minimizing the bottleneck term).
    """
    ebytes = DTYPE_BYTES[dtype]
    x = mesh_axes.get(batch_axis, 1)
    model = mesh_axes.get(model_axis, 1)
    flops = device.peak_flops[dtype]

    best: Optional[XYZShardPlan] = None
    y = 1
    while y <= model:
        z = model // y
        if y * z == model:
            m_loc = max(1, m // x)
            # per-device compute (eq. 1 analog at array scale)
            comp = 2.0 * m_loc * (k // y) * (n // z) / flops
            # per-device HBM traffic: activation in + weight shard, plus
            # the output stage.  A fused epilogue writes the finished
            # output once; the unfused baseline would round-trip the fp32
            # accumulator (epilogue_hbm_bytes accounts for the savings).
            in_bytes = (m_loc * (k // y) + (k // y) * (n // z)) * ebytes
            if dtype == "int8":
                # the quantized pipeline streams f32 scale vectors next to
                # the 1-byte operands (rowwise for A, colwise for W)
                in_bytes += 4 * (m_loc + n // z)
            out_bytes = epilogue_hbm_bytes(m_loc, n // z, epilogue,
                                           fused=True) \
                if epilogue is not None else m_loc * (n // z) * ebytes
            hbm = (in_bytes + out_bytes) / device.hbm_bw
            # wire bytes (PLIO analog):
            #  * A gathered over Z (paper: A_{x,y} broadcast Z times) --
            #    charged only if A arrives sharded over the model axis;
            #  * partial-C reduction over Y (the adder tree), priced
            #    per-link per schedule.
            a_bytes = m_loc * (k // y) * ebytes
            c_bytes = m_loc * (n // z) * 4  # 32-bit partials (fp32/int32)
            gather_s = 0.0
            if a_sharded_on_model:
                gather_s = gather_wire_bytes_per_link(a_bytes, z) \
                    / device.ici_bw_per_link
            if prefer_schedule is not None:
                scheds = [prefer_schedule]
            elif y == 1:
                scheds = ["none"]
            else:
                scheds = ["allreduce", "reduce_scatter", "ring",
                          "bidir_ring"]
            for sched in scheds:
                coll_s = reduction_wire_bytes_per_link(c_bytes, y, sched) \
                    / device.ici_bw_per_link
                cand = XYZShardPlan(x, y, z, sched, coll_s, comp, hbm,
                                    gather_s)
                # ties (compute- or HBM-bound points) break toward the
                # fewest per-link wire bytes, so 'bidir_ring' wins over
                # 'ring'/'reduce_scatter' exactly when wire cost is moot
                key = (cand.est_step_s, coll_s + gather_s)
                if best is None or key < (best.est_step_s,
                                          best.est_collective_s
                                          + best.est_gather_s):
                    best = cand
        y *= 2
    assert best is not None
    return best


def plan_tpu_matmul(
    m: int,
    k: int,
    n: int,
    dtype: str = "bf16",
    mesh_axes: Optional[Dict[str, int]] = None,
    device: TPUDevice = TPU_V5E,
    **shard_kwargs,
) -> MatmulPlan:
    mesh_axes = mesh_axes or {"data": 1, "model": 1}
    shard = plan_tpu_shard(m, k, n, dtype, mesh_axes, device, **shard_kwargs)
    # the per-device local GEMM that the Pallas block plan tiles
    m_loc = max(1, m // shard.x_shards)
    k_loc = max(1, k // shard.y_shards)
    n_loc = max(1, n // shard.z_shards)
    block = plan_tpu_block(m_loc, k_loc, n_loc, dtype, device,
                           epilogue=shard_kwargs.get("epilogue"))
    return MatmulPlan(m, k, n, dtype, block, shard)
