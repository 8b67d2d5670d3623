"""TPU instantiation of the blocking model (DESIGN.md §3).

The paper's model is hierarchy-agnostic; on TPU v5e the hierarchy is
HBM (16 GiB, 819 GB/s) -> VMEM (~128 MiB/core) -> VREGs, and the MXU wants
matmul operands tiled to multiples of (8, 128) sublane x lane (128x128 for
full systolic utilization).  This module runs the paper's optimizer with
that hierarchy + alignment constraints and emits:

* ``matmul_tiles``  — (bm, bk, bn) BlockSpec tiles for the blocked-GEMM
  Pallas kernel (every transformer projection / FC layer);
* ``conv_tiles``    — (bx, by, bc, bk) tiles for the direct blocked-conv
  Pallas kernel;
* ``flash_tiles``   — (block_q, block_kv) for the attention kernel (the
  K/V tiles play the paper's KB role; the running softmax accumulator is
  the OB);
* ``sharding_advice`` — the §3.3 K-vs-XY partitioning rule mapped to
  tensor-vs-data parallelism for a layer's operand sizes.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

from repro.core.hierarchy import MemLevel
from repro.core.loopnest import Dim, Problem, divisors
from repro.core.optimizer import ranked_level0_tiles


@dataclasses.dataclass(frozen=True)
class TpuTarget:
    name: str
    peak_bf16_flops: float
    hbm_bytes_per_s: float
    vmem_bytes: int
    ici_bytes_per_s_per_link: float
    mxu: tuple[int, int] = (128, 128)
    sublane: int = 8
    lane: int = 128
    hbm_bytes: int = 16 * 1024**3


TPU_V5E = TpuTarget(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bytes_per_s=819e9,
    vmem_bytes=128 * 1024 * 1024,
    ici_bytes_per_s_per_link=50e9,
)

# The one table of per-chip peaks, keyed by ``jax.Device.device_kind``
# (source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).  A kind missing here is an error, not a
# default: a roofline priced against another chip's peaks is wrong.
DEVICE_TARGETS: dict[str, TpuTarget] = {"TPU v5 lite": TPU_V5E}


def target_for(device_kind: str) -> TpuTarget:
    """The published peaks of the chip JAX reports as ``device_kind``."""
    try:
        return DEVICE_TARGETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_TARGETS)}") from None


def default_vmem_budget(target: TpuTarget = TPU_V5E,
                        vmem_budget_bytes: int | None = None) -> int:
    """Working-set budget for tile derivation: 1/8 of VMEM unless
    overridden — headroom for Pallas pipeline buffers and the compiler.
    The single definition shared by the snap loops here and the candidate
    filter in ``repro.tune.lowering``."""
    return vmem_budget_bytes or target.vmem_bytes // 8


def _round_to(v: int, mult: int, lo: int, hi: int) -> int:
    v = max(lo, min(hi, (v // mult) * mult))
    return v if v >= mult else min(hi, mult)


def _pick_tile(extent: int, target: int, mult: int) -> int:
    """Largest tile <= target that is a multiple of ``mult`` and <= extent;
    prefers exact divisors of extent to avoid ragged tail blocks."""
    if extent <= mult:
        return extent
    cap = min(target, extent)
    aligned_divs = [d for d in divisors(extent) if d % mult == 0 and d <= cap]
    if aligned_divs:
        return max(aligned_divs)
    return _round_to(cap, mult, mult, extent)


def _matmul_fits(bm: int, bk: int, bn: int, bytes_per_elem: int,
                 budget: int, weight_bytes: int | None = None) -> bool:
    # lazy import: the kernel module (jax) owns its VMEM layout; core
    # stays importable without jax until tiles are actually derived.
    if weight_bytes is not None:
        from repro.kernels.matmul_q import vmem_bytes_required
        return vmem_bytes_required(bm, bk, bn, bytes_per_elem,
                                   weight_bytes) <= budget
    from repro.kernels.matmul_blocked import vmem_bytes_required
    return vmem_bytes_required(bm, bk, bn, bytes_per_elem) <= budget


def _snap_matmul(bm: int, bk: int, bn: int, M: int, N: int, K: int,
                 bytes_per_elem: int, budget: int,
                 target: TpuTarget,
                 weight_bytes: int | None = None) -> tuple[int, int, int]:
    """Snap an analytical (bm, bk, bn) to MXU alignment + VMEM fit."""
    # lanes on the minor (N, K) dims, sublanes on M
    bm = _pick_tile(M, max(bm, target.sublane), target.sublane)
    bn = _pick_tile(N, max(bn, target.lane), target.lane)
    bk = _pick_tile(K, max(bk, target.lane), target.lane)
    while not _matmul_fits(bm, bk, bn, bytes_per_elem, budget,
                           weight_bytes):
        # shrink the largest contributor, re-snapped: halving an aligned
        # divisor (12800 -> 800) can leave the alignment Mosaic needs
        if bk * (bm + bn) >= bm * bn and bk > target.lane:
            bk = _pick_tile(K, max(target.lane, bk // 2), target.lane)
        elif bm >= bn and bm > target.sublane:
            bm = _pick_tile(M, max(target.sublane, bm // 2), target.sublane)
        elif bn > target.lane:
            bn = _pick_tile(N, max(target.lane, bn // 2), target.lane)
        else:
            break
    return bm, bk, bn


@functools.lru_cache(maxsize=512)
def matmul_tile_candidates(M: int, N: int, K: int, bytes_per_elem: int = 2,
                           vmem_budget_bytes: int | None = None,
                           target: TpuTarget = TPU_V5E,
                           top: int = 8,
                           weight_bytes: int | None = None,
                           ) -> tuple[tuple[int, int, int], ...]:
    """Ranked (bm, bk, bn) candidates for C[M,N] += A[M,K] @ B[K,N].

    The optimizer sees a 2-level hierarchy (VMEM working set, HBM above)
    and alignment candidates restricted to MXU multiples; each analytical
    winner is then snapped to hardware alignment and the VMEM budget.
    Order follows the optimizer's energy ranking; the autotuner
    (``repro.tune``) re-ranks by predicted DRAM traffic and measurement.

    ``weight_bytes`` gives the B operand its own element width (int8
    weights: 1) — the search then sizes the weight tile in those bytes
    and the VMEM fit uses the quantized kernel's footprint model.
    """
    budget = default_vmem_budget(target, vmem_budget_bytes)
    problem = Problem.gemm(M=M, N_cols=N, K_reduce=K,
                           bytes_per_elem=bytes_per_elem,
                           weight_bytes=weight_bytes)
    levels = [MemLevel.sram("VMEM", budget), MemLevel.dram("HBM")]
    align = {Dim.X: target.sublane, Dim.K: target.lane, Dim.C: target.lane}
    raw: list[tuple[int, int, int]] = []
    try:
        for e in ranked_level0_tiles(problem, levels, align=align, top=top):
            raw.append((e.X, e.C, e.K))          # (bm, bk, bn)
    except Exception as exc:
        warnings.warn(f"blocking search failed for GEMM {M}x{N}x{K} "
                      f"({exc!r}); using heuristic seed tiles")
    raw.append((256, 512, 256))                  # heuristic fallback seed
    out: list[tuple[int, int, int]] = []
    for bm, bk, bn in raw:
        cand = _snap_matmul(bm, bk, bn, M, N, K, bytes_per_elem, budget,
                            target, weight_bytes)
        if cand not in out:
            out.append(cand)
    return tuple(out[:top])


def matmul_tiles(M: int, N: int, K: int, bytes_per_elem: int = 2,
                 vmem_budget_bytes: int | None = None,
                 target: TpuTarget = TPU_V5E) -> tuple[int, int, int]:
    """Top analytical (bm, bk, bn) tile (see matmul_tile_candidates)."""
    return matmul_tile_candidates(M, N, K, bytes_per_elem,
                                  vmem_budget_bytes, target)[0]


def _conv_fits(bx: int, by: int, bc: int, bk: int, Fw: int, Fh: int,
               bytes_per_elem: int, budget: int, stride: int) -> bool:
    from repro.kernels.conv2d_blocked import vmem_bytes_required
    return vmem_bytes_required(bx, by, bc, bk, Fh, Fw,
                               bytes_per_elem, stride) <= budget


def _snap_conv(bx: int, by: int, bc: int, bk: int,
               X: int, Y: int, C: int, K: int, Fw: int, Fh: int,
               bytes_per_elem: int, budget: int,
               target: TpuTarget, stride: int) -> tuple[int, int, int, int]:
    bx = _pick_tile(X, max(bx, target.sublane), 1)
    by = _pick_tile(Y, by, 1)
    bc = _pick_tile(C, max(bc, min(C, target.lane)),
                    min(C, target.lane) if C >= target.lane else 1)
    bk = _pick_tile(K, max(bk, min(K, target.lane)),
                    min(K, target.lane) if K >= target.lane else 1)
    while not _conv_fits(bx, by, bc, bk, Fw, Fh, bytes_per_elem, budget,
                         stride):
        if bx >= by and bx > 8:
            bx = max(8, bx // 2)
        elif by > 1:
            by = max(1, by // 2)
        elif bk > target.lane:
            bk = max(target.lane, bk // 2)
        elif bc > target.lane:
            bc = max(target.lane, bc // 2)
        else:
            break
    return bx, by, bc, bk


@functools.lru_cache(maxsize=256)
def conv_tile_candidates(X: int, Y: int, C: int, K: int, Fw: int, Fh: int,
                         bytes_per_elem: int = 2,
                         vmem_budget_bytes: int | None = None,
                         target: TpuTarget = TPU_V5E, top: int = 8,
                         stride: int = 1,
                         ) -> tuple[tuple[int, int, int, int], ...]:
    """Ranked (bx, by, bc, bk) VMEM tiles for the direct blocked conv."""
    budget = default_vmem_budget(target, vmem_budget_bytes)
    problem = Problem(X=X, Y=Y, C=C, K=K, Fw=Fw, Fh=Fh, stride=stride,
                      bytes_per_elem=bytes_per_elem)
    levels = [MemLevel.sram("VMEM", budget), MemLevel.dram("HBM")]
    align = {Dim.K: target.lane, Dim.C: target.lane}
    raw: list[tuple[int, int, int, int]] = []
    try:
        for e in ranked_level0_tiles(problem, levels, align=align, top=top,
                                     max_orders=24):
            raw.append((e.X, e.Y, e.C, e.K))
    except Exception as exc:
        warnings.warn(f"blocking search failed for conv "
                      f"{(X, Y, C, K, Fw, Fh)} ({exc!r}); using heuristic "
                      "seed tiles")
    raw.append((X, Y, min(C, target.lane), min(K, target.lane)))
    out: list[tuple[int, int, int, int]] = []
    for bx, by, bc, bk in raw:
        cand = _snap_conv(bx, by, bc, bk, X, Y, C, K, Fw, Fh,
                          bytes_per_elem, budget, target, stride)
        if cand not in out:
            out.append(cand)
    return tuple(out[:top])


def conv_tiles(X: int, Y: int, C: int, K: int, Fw: int, Fh: int,
               bytes_per_elem: int = 2,
               vmem_budget_bytes: int | None = None,
               target: TpuTarget = TPU_V5E) -> tuple[int, int, int, int]:
    """Top analytical (bx, by, bc, bk) tile (see conv_tile_candidates)."""
    return conv_tile_candidates(X, Y, C, K, Fw, Fh, bytes_per_elem,
                                vmem_budget_bytes, target)[0]


def backward_tile_candidates(op: str, dims: tuple[int, ...],
                             bytes_per_elem: int = 2,
                             vmem_budget_bytes: int | None = None,
                             target: TpuTarget = TPU_V5E, top: int = 8,
                             stride: int = 1) -> tuple[tuple[int, ...], ...]:
    """Ranked tiles for the backward nests, reusing the forward searches.

    The backward passes are the same loop-nest families (the paper's
    analysis is indifferent to which operand is written), so no new
    search is grown: ``matmul_dgrad`` is a GEMM over the cotangent's
    (M, N, K); ``conv2d_dgrad`` is the transposed conv as a direct conv
    (channels swapped, stride folded into host dilation, hence stride 1
    here); ``conv2d_wgrad`` shares the forward conv's dims with (bx, by)
    blocking the spatial reduction.  Candidate ranking flows through
    ``core.optimizer.ranked_level0_tiles`` exactly as for the forward.
    """
    if op == "matmul_dgrad":
        M, N, K = dims
        return matmul_tile_candidates(M, N, K, bytes_per_elem,
                                      vmem_budget_bytes, target, top)
    if op not in ("conv2d_dgrad", "conv2d_wgrad"):
        raise ValueError(f"not a backward op: {op!r}")
    X, Y, C, K, Fw, Fh = dims
    return conv_tile_candidates(X, Y, C, K, Fw, Fh, bytes_per_elem,
                                vmem_budget_bytes, target, top,
                                stride=1 if op == "conv2d_dgrad" else stride)


@functools.lru_cache(maxsize=256)
def flash_decode_tile_candidates(groups: int, seq_kv: int, head_dim: int,
                                 bytes_per_elem: int = 2,
                                 vmem_budget_bytes: int | None = None,
                                 target: TpuTarget = TPU_V5E, top: int = 8,
                                 kv_bytes: int | None = None,
                                 ) -> tuple[tuple[int], ...]:
    """Ranked ``(block_kv,)`` candidates for the paged flash-decode kernel.

    Decode attention per (batch, kv-head) is the skinny GEMM
    ``out[G, D] = softmax(q[G, D] @ K^T[D, S]) @ V[S, D]`` — a
    memory-bound nest whose only free blocking choice is how much of the
    S-long KV stream is resident per step.  The optimizer search runs on
    that nest (C = the KV reduction dim); each winner's C extent is
    snapped to lane alignment, to a divisor of ``seq_kv`` (the kernel
    grid requires whole blocks), and to the kernel's VMEM model.  The
    chosen block doubles as the paged cache's page size.

    ``kv_bytes`` gives the streamed K/V pages their own element width
    (fp8 cache: 1); the q rows and the fp32 running state keep
    ``bytes_per_elem`` — an fp8 cache fits twice the page in the same
    VMEM, so the fp8-aware search can pick larger pages.
    """
    from repro.kernels.flash_decode import vmem_bytes_required
    budget = default_vmem_budget(target, vmem_budget_bytes)
    problem = Problem.gemm(M=groups, N_cols=head_dim, K_reduce=seq_kv,
                           bytes_per_elem=bytes_per_elem,
                           weight_bytes=kv_bytes)
    levels = [MemLevel.sram("VMEM", budget), MemLevel.dram("HBM")]
    align = {Dim.C: target.lane}
    raw: list[int] = []
    try:
        for e in ranked_level0_tiles(problem, levels, align=align, top=top):
            raw.append(e.C)
    except Exception as exc:
        warnings.warn(f"blocking search failed for flash_decode "
                      f"{(groups, seq_kv, head_dim)} ({exc!r}); using "
                      "heuristic seed block")
    raw.append(min(seq_kv, 512))                 # heuristic fallback seed
    out: list[tuple[int]] = []
    for bkv in raw:
        # the page is the sublane dim of the kernel's (page, D) block:
        # lane multiples also meet the fp8 pool's 32-row packing
        mult = target.lane if seq_kv >= target.lane else 1
        bkv = _pick_tile(seq_kv, max(bkv, mult), mult)
        while (vmem_bytes_required(bkv, groups, head_dim, bytes_per_elem,
                                   kv_bytes=kv_bytes) > budget
               and bkv > mult):
            bkv = max(mult, bkv // 2)
        # the kernel iterates whole pages: snap to a divisor of seq_kv
        if seq_kv % bkv:
            bkv = max(d for d in divisors(seq_kv) if d <= bkv)
        if (bkv,) not in out:
            out.append((bkv,))
    return tuple(out[:top])


@functools.lru_cache(maxsize=256)
def flash_tiles(seq_q: int, seq_kv: int, head_dim: int,
                bytes_per_elem: int = 2,
                vmem_budget_bytes: int | None = None,
                target: TpuTarget = TPU_V5E) -> tuple[int, int]:
    """(block_q, block_kv) for the streaming-softmax attention kernel.

    In the paper's vocabulary the KV tile is the kernel buffer (reused by
    every query block -> big tiles amortize HBM fetches) and the running
    (m, l, acc) state is the output buffer held across the KV loop.
    """
    budget = default_vmem_budget(target, vmem_budget_bytes)
    bq = _pick_tile(seq_q, 512, target.sublane)
    bkv = _pick_tile(seq_kv, 1024, target.lane if seq_kv >= target.lane
                     else 1)

    def fits(bq, bkv) -> bool:
        q = bq * head_dim * bytes_per_elem
        kv = 2 * bkv * head_dim * bytes_per_elem
        scores = bq * bkv * 4
        acc = bq * head_dim * 4 + 2 * bq * 4
        return q + kv + scores + acc <= budget
    while not fits(bq, bkv):
        if bkv >= bq and bkv > target.lane:
            bkv = max(target.lane, bkv // 2)
        elif bq > target.sublane:
            bq = max(target.sublane, bq // 2)
        else:
            break
    return bq, bkv


def layer_sharding_advice(weight_bytes: int, activation_bytes: int) -> str:
    """Paper §3.3 / §5.3 rule at mesh scale: shard (partition) the LARGE
    operand so the small one is the broadcast; sharing the large buffer
    makes its broadcast free."""
    return "model" if weight_bytes >= activation_bytes else "data"
