"""Lowering from the analytical blocking model to Pallas kernel schedules.

This is the ``core -> kernels`` bridge the optimizer output flows through:

1. :func:`candidates` runs the paper's schedule search for the op's loop
   nest on the TPU hierarchy (via ``core.tpu_adapter``), snaps each winner
   to MXU alignment + the VMEM budget, and drops candidates the kernels
   cannot execute directly (tile sizes must divide the problem dims, or
   ``kernels.ops`` would take its oracle fallback);
2. :func:`schedule_to_string` maps a concrete tile tuple back onto the
   blocking string the kernel's grid actually executes, so
3. :func:`predicted_dram_accesses` can score any candidate with the exact
   per-level access counts of paper §3.4 — the analytic rank the
   measurement harness then refines.

Backward ops (``matmul_dgrad`` / ``conv2d_dgrad`` / ``conv2d_wgrad``)
flow through the same three steps: their nests share the forward
families' access geometry (the model counts element touches of the same
three operands; which one is written does not change the counts), so the
candidate search and scoring are reused with relabelled dims — see
``core.tpu_adapter.backward_tile_candidates`` and docs/training.md.

``flash_decode`` (the serving nest, docs/serving.md) is a skinny GEMM
whose reduction dim is the KV length; its single tile ``(block_kv,)`` is
both the kernel's KV block and the paged cache's page size — see
``core.tpu_adapter.flash_decode_tile_candidates``.

The quantized variants (``matmul_w8`` / ``flash_decode_fp8``,
docs/quantization.md) reuse the same nests with a 1-byte weight / KV
stream: their specs' ``problem()`` carries per-operand byte widths, so
the candidate search, the VMEM fit (each quantized kernel's own
footprint model) and :func:`predicted_dram_bytes` all see the narrow
operand, while dims/tiles keep the wide ops' conventions.
"""

from __future__ import annotations

from repro.core.hierarchy import MemLevel, cache_accesses
from repro.core.loopnest import BlockingString, Dim, Loop
from repro.core.tpu_adapter import (TPU_V5E, TpuTarget,
                                    backward_tile_candidates,
                                    conv_tile_candidates,
                                    default_vmem_budget,
                                    flash_decode_tile_candidates,
                                    matmul_tile_candidates)
from repro.tune.schedule import (ATTN_OPS, FUSED_OPS, GEMM_OPS,
                                 NARROW_WEIGHT_BYTES, OpSpec, Schedule)

# the one budget rule, shared with the snap loops in core.tpu_adapter
vmem_budget = default_vmem_budget


def fits_vmem(spec: OpSpec, tiles: tuple[int, ...], budget: int) -> bool:
    """Check a tile tuple against the kernel's own VMEM footprint model.

    Each kernel family owns its footprint accounting: the forward GEMM
    model also covers the NT/TN dgrad kernels (same streamed-operands +
    resident-accumulator layout), the forward conv model covers dgrad
    (which runs the forward kernel), and the wgrad kernel has its own
    (resident dW block, streamed input/cotangent tiles).
    """
    if spec.op == "matmul_w8":
        from repro.kernels.matmul_q import vmem_bytes_required
        bm, bk, bn = tiles
        return vmem_bytes_required(bm, bk, bn, spec.itemsize,
                                   NARROW_WEIGHT_BYTES[spec.op]) <= budget
    if spec.op == "matmul_fused":
        # fused VMEM filter: sized for the worst epilogue (bias + mul +
        # residual) so one cached schedule serves every combination
        from repro.kernels.matmul_fused import vmem_bytes_required
        bm, bk, bn = tiles
        return vmem_bytes_required(bm, bk, bn, spec.itemsize) <= budget
    if spec.op == "qkv_fused":
        from repro.kernels.qkv_fused import vmem_bytes_required
        _, _, _, G = spec.dims
        bm, bk, bn = tiles
        return vmem_bytes_required(bm, bk, bn, G,
                                   spec.itemsize) <= budget
    if spec.op == "flash_decode_oproj":
        from repro.kernels.flash_decode import oproj_vmem_bytes_required
        G, _, D, E = spec.dims
        (bkv,) = tiles
        return oproj_vmem_bytes_required(bkv, G, D, E,
                                         spec.itemsize) <= budget
    if spec.op in GEMM_OPS:
        from repro.kernels.matmul_blocked import vmem_bytes_required
        bm, bk, bn = tiles
        return vmem_bytes_required(bm, bk, bn, spec.itemsize) <= budget
    if spec.op in ATTN_OPS:
        # priced at q_span=1 (single-position decode); chunked prefill
        # re-prices the winning block with its span via
        # serve.kv_cache.choose_prefill_chunk
        from repro.kernels.flash_decode import vmem_bytes_required
        G, _, D = spec.dims
        (bkv,) = tiles
        return vmem_bytes_required(
            bkv, G, D, spec.itemsize,
            kv_bytes=NARROW_WEIGHT_BYTES.get(spec.op)) <= budget
    if spec.op == "conv2d_wgrad":
        from repro.kernels.conv2d_bwd import vmem_bytes_required
    else:
        from repro.kernels.conv2d_blocked import vmem_bytes_required
    bx, by, bc, bk = tiles
    _, _, _, _, Fw, Fh = spec.dims
    return vmem_bytes_required(bx, by, bc, bk, Fh, Fw, spec.itemsize,
                               spec.stride) <= budget


def divides(spec: OpSpec, tiles: tuple[int, ...]) -> bool:
    """True iff the kernels can run these tiles without a fallback path."""
    if spec.op in GEMM_OPS:
        M, N, K = spec.dims
        bm, bk, bn = tiles
        return M % bm == 0 and K % bk == 0 and N % bn == 0
    if spec.op == "qkv_fused":
        M, Nkv, K, _ = spec.dims
        bm, bk, bn = tiles
        return M % bm == 0 and K % bk == 0 and Nkv % bn == 0
    if spec.op == "flash_decode_oproj":
        _, S, _, _ = spec.dims
        (bkv,) = tiles
        return S % bkv == 0
    if spec.op in ATTN_OPS:
        _, S, _ = spec.dims
        (bkv,) = tiles
        return S % bkv == 0
    X, Y, C, K, _, _ = spec.dims
    bx, by, bc, bk = tiles
    # bc/bk divisibility is a hard kernel assert; bx/by divisibility avoids
    # the single-spatial-tile fallback in the level-1 host loops.
    return C % bc == 0 and K % bk == 0 and X % bx == 0 and Y % by == 0


def schedule_to_string(spec: OpSpec,
                       tiles: tuple[int, ...]) -> BlockingString:
    """The blocking string the Pallas kernels execute for these tiles.

    Loop order mirrors the kernels exactly (inner -> outer):

    * matmul / matmul_dgrad: level-0 (bk, bm, bn) VMEM block, then the
      grid (m, n, k) with k minor-most (the fp32 accumulator is the OB
      held across C);
    * conv2d / conv2d_dgrad: Fw/Fh window loops inside the block, the
      (bx, by, bc, bk) VMEM block, then the kernel grid (k, c) with c
      minor-most, then the spatial halo tiles the host slices (X inside
      Y);
    * conv2d_wgrad: the spatial tile is the *innermost* reduction (one
      whole (bx, by) tile dots into the resident dW block per Fw/Fh
      step), then the channel blocks, then the (k, c) grid, then the
      host's spatial reduction tiles.
    """
    p = spec.problem()
    loops: list[Loop] = []
    if spec.op in GEMM_OPS:
        M, N, K = spec.dims
        bm, bk, bn = tiles
        loops = [Loop(Dim.C, bk), Loop(Dim.X, bm), Loop(Dim.K, bn),
                 Loop(Dim.C, K), Loop(Dim.K, N), Loop(Dim.X, M)]
    elif spec.op == "qkv_fused":
        # one grid step touches (G+2)*bn columns of the joint output
        # from a single A tile — the GEMM string over the joint width
        M, Nkv, K, G = spec.dims
        bm, bk, bn = tiles
        cols = (G + 2) * Nkv
        loops = [Loop(Dim.C, bk), Loop(Dim.X, bm),
                 Loop(Dim.K, (G + 2) * bn),
                 Loop(Dim.C, K), Loop(Dim.K, cols), Loop(Dim.X, M)]
    elif spec.op == "flash_decode_oproj":
        # the decode nest proper.  The fused projection's wo traffic is
        # independent of the KV block, so it cannot change the rank and
        # is deliberately absent here — E enters the schedule choice
        # only through the VMEM filter (the resident wo slab squeezes
        # the budget); the kernel's exact traffic lives in
        # flash_decode.oproj_hbm_bytes (benchmarked, not ranked)
        G, S, D, _ = spec.dims
        (bkv,) = tiles
        loops = [Loop(Dim.C, bkv), Loop(Dim.X, G), Loop(Dim.K, D),
                 Loop(Dim.C, S)]
    elif spec.op in ATTN_OPS:
        # one query block (all G rows, all D cols) resident; the grid
        # streams KV pages of block_kv — the running (m, l, acc) state is
        # the OB held across the whole C (KV) reduction.
        G, S, D = spec.dims
        (bkv,) = tiles
        loops = [Loop(Dim.C, bkv), Loop(Dim.X, G), Loop(Dim.K, D),
                 Loop(Dim.C, S)]
    elif spec.op == "conv2d_wgrad":
        X, Y, C, K, Fw, Fh = spec.dims
        bx, by, bc, bk = tiles
        loops = [Loop(Dim.X, bx), Loop(Dim.Y, by)]
        if Fw > 1:
            loops.append(Loop(Dim.FW, Fw))
        if Fh > 1:
            loops.append(Loop(Dim.FH, Fh))
        loops += [Loop(Dim.C, bc), Loop(Dim.K, bk),
                  Loop(Dim.C, C), Loop(Dim.K, K),
                  Loop(Dim.X, X), Loop(Dim.Y, Y)]
        return BlockingString(loops, p)
    else:
        X, Y, C, K, Fw, Fh = spec.dims
        bx, by, bc, bk = tiles
        if Fw > 1:
            loops.append(Loop(Dim.FW, Fw))
        if Fh > 1:
            loops.append(Loop(Dim.FH, Fh))
        loops += [Loop(Dim.X, bx), Loop(Dim.Y, by),
                  Loop(Dim.C, bc), Loop(Dim.K, bk),
                  Loop(Dim.C, C), Loop(Dim.K, K),
                  Loop(Dim.X, X), Loop(Dim.Y, Y)]
    return BlockingString(loops, p)


def predicted_dram_accesses(spec: OpSpec, tiles: tuple[int, ...],
                            vmem_budget_bytes: int | None = None,
                            target: TpuTarget = TPU_V5E) -> int:
    """HBM-boundary accesses (elements) of this schedule under the paper's
    access model with a VMEM-sized on-chip level (working sets that
    overflow the budget spill, exactly like the Fig. 3/4 methodology)."""
    if not divides(spec, tiles):
        raise ValueError(
            f"tiles {tiles} do not divide {spec.op} dims {spec.dims}; "
            "the kernels would take their oracle fallback, which the "
            "blocking model cannot score")
    budget = vmem_budget(target, vmem_budget_bytes)
    levels = [MemLevel.sram("VMEM", budget), MemLevel.dram("HBM")]
    s = schedule_to_string(spec, tiles)
    return cache_accesses(s, levels)[levels[-1].name]


def predicted_dram_bytes(spec: OpSpec, tiles: tuple[int, ...],
                         vmem_budget_bytes: int | None = None,
                         target: TpuTarget = TPU_V5E) -> int:
    """HBM-boundary traffic in BYTES, weighting each operand's accesses
    by its own element width (``core.buffers.operand_bytes``).

    Element *counts* are dtype-invariant — :func:`predicted_dram_accesses`
    reports the same number for a bf16 and an int8 weight stream — so
    this is the quantity that shows what quantization buys: the same
    schedule moves half (or a quarter) of the bytes.  Shares the exact
    placement walk of the access-count rank (``core.hierarchy.
    cache_accesses`` with per-operand byte weights), so the two ranks
    cannot disagree about the miss-path rules.
    """
    if not divides(spec, tiles):
        raise ValueError(
            f"tiles {tiles} do not divide {spec.op} dims {spec.dims}")
    from repro.core.buffers import Operand, operand_bytes
    budget = vmem_budget(target, vmem_budget_bytes)
    levels = [MemLevel.sram("VMEM", budget), MemLevel.dram("HBM")]
    s = schedule_to_string(spec, tiles)
    weights = {op: operand_bytes(s.problem, op) for op in Operand}
    return cache_accesses(s, levels,
                          operand_weights=weights)[levels[-1].name]


def _operand_level0_traffic(s: BlockingString, op, footprint: int) -> int:
    """Parent-side traffic (elements) of the outermost model buffer that
    fits the kernel's level-0 tile footprint for this operand.

    This is where the model and the kernel meet: a Pallas kernel holds
    exactly one level-0 block per operand in VMEM, so the DRAM-boundary
    traffic it generates is the fills+writebacks of the *largest* model
    buffer no bigger than that block — including the degenerate pos=-1
    register when no placed buffer fits (a streamed operand with no
    reuse), whose parent traffic is the full compulsory stream.
    """
    from repro.core.access import analyze
    from repro.core.buffers import buffers_by_operand, place_buffers
    rep = analyze(s)
    chain = buffers_by_operand(place_buffers(s))[op]     # inner -> outer
    fitting = [b for b in chain if b.size_elems <= footprint]
    pick = fitting[-1]
    for bt in rep.per_buffer:
        if bt.buffer.name == pick.name and bt.buffer.operand is op:
            return bt.parent_traffic
    raise KeyError(pick.name)


def _level0_footprints(s: BlockingString) -> dict:
    """Level-0 tile footprint (elements) per operand, read off the
    innermost extent of each dim in the blocking string."""
    from repro.core.buffers import OPERAND_DIMS, Operand
    inner: dict[Dim, int] = {}
    for loop in s.loops:
        inner.setdefault(loop.dim, loop.extent)
    out = {}
    for op in Operand:
        fp = 1
        for d in OPERAND_DIMS[op]:
            fp *= inner.get(d, 1)
        out[op] = fp
    return out


def level0_dram_bytes(spec: OpSpec, tiles: tuple[int, ...]) -> int:
    """The blocking model's level-0 HBM traffic (bytes) for the exact
    nest(s) the kernel executes with ``tiles`` — no finite-VMEM packing,
    no spill: per operand, the parent traffic of the outermost placed
    buffer that fits the kernel's level-0 block.

    This is the model-side half of the kernel-vs-model byte-agreement
    property (``tests/test_profile.py``): on exact-divisor shapes it
    equals the kernels' exported ``hbm_bytes`` bit for bit, because both
    count the same thing — the Pallas grid's block transfers under DMA
    elision.  Covers the GEMM family (incl. the fused/quantized
    variants' base streams) and ``flash_decode``; the conv nests carry
    halo refetch terms the kernels account for directly.
    """
    from repro.core.buffers import Operand, operand_bytes
    if not divides(spec, tiles):
        raise ValueError(
            f"tiles {tiles} do not divide {spec.op} dims {spec.dims}")
    if spec.op in ATTN_OPS:
        return _flash_decode_level0_bytes(spec, tiles)
    if spec.op not in GEMM_OPS and spec.op != "qkv_fused":
        raise ValueError(
            f"level0_dram_bytes covers the GEMM family and flash_decode, "
            f"not {spec.op!r}")
    s = schedule_to_string(spec, tiles)
    fps = _level0_footprints(s)
    return sum(_operand_level0_traffic(s, op, fps[op])
               * operand_bytes(s.problem, op) for op in Operand)


def _flash_decode_level0_bytes(spec: OpSpec, tiles: tuple[int, ...]) -> int:
    """Two-nest decomposition of the decode-attention kernel.

    The single-GEMM stand-in the tuner ranks with (INPUT = the G x S
    score matrix) cannot describe the kernel's real streams — the score
    block lives only in VMEM.  The kernel is two chained GEMMs sharing
    the KV block loop: ``scores = q @ K^T`` (count q and K; the score
    output is the VMEM intermediate) and ``out = P @ V`` (count V and
    the output; P is the same intermediate).  Per (batch, kv-head) row;
    scalar-prefetch block tables/lengths are excluded, matching the
    kernel's ``hbm_bytes``.
    """
    from repro.core.buffers import Operand, operand_bytes
    from repro.core.loopnest import Problem
    G, S, D = spec.dims
    (bkv,) = tiles
    kvb = NARROW_WEIGHT_BYTES.get(spec.op)
    p1 = Problem.gemm(M=G, N_cols=S, K_reduce=D,
                      bytes_per_elem=spec.itemsize, weight_bytes=kvb)
    s1 = BlockingString([Loop(Dim.C, D), Loop(Dim.X, G), Loop(Dim.K, bkv),
                         Loop(Dim.C, D), Loop(Dim.K, S), Loop(Dim.X, G)],
                        p1)
    p2 = Problem.gemm(M=G, N_cols=D, K_reduce=S,
                      bytes_per_elem=spec.itemsize, weight_bytes=kvb)
    s2 = BlockingString([Loop(Dim.C, bkv), Loop(Dim.X, G), Loop(Dim.K, D),
                         Loop(Dim.C, S), Loop(Dim.K, D), Loop(Dim.X, G)],
                        p2)
    total = 0
    for s, counted in ((s1, (Operand.INPUT, Operand.WEIGHT)),
                       (s2, (Operand.WEIGHT, Operand.OUTPUT))):
        fps = _level0_footprints(s)
        for op in counted:
            total += _operand_level0_traffic(s, op, fps[op]) \
                * operand_bytes(s.problem, op)
    if spec.op == "flash_decode_fp8":
        total += 2 * 4        # per-head dequant scale scalars, one row
    return total


def candidates(spec: OpSpec,
               vmem_budget_bytes: int | None = None,
               target: TpuTarget = TPU_V5E,
               top: int = 8) -> list[Schedule]:
    """Analytically-ranked kernel schedules for one op instance.

    Always returns at least one schedule.  When no snapped candidate
    divides the problem cleanly the top raw candidate is returned anyway
    (``kernels.ops`` will take its oracle fallback for it), with
    ``predicted_dram_accesses`` left unset.
    """
    budget = vmem_budget(target, vmem_budget_bytes)
    if spec.op in ("matmul", "matmul_w8", "matmul_fused"):
        M, N, K = spec.dims
        raw = matmul_tile_candidates(
            M, N, K, spec.itemsize, budget, target, top=top,
            weight_bytes=NARROW_WEIGHT_BYTES.get(spec.op))
    elif spec.op == "qkv_fused":
        # search the joint nest (one A stream, (G+2)*Nkv columns), then
        # express the winner's bn in per-projection columns, snapped to
        # a lane-aligned divisor of Nkv (integer division by G+2 would
        # silently drop the MXU alignment every other GEMM candidate
        # carries, and Mosaic refuses a block that splits the lane dim
        # unaligned); the fused VMEM filter rejects what the joint
        # residents overflow
        from repro.core.loopnest import divisors
        M, Nkv, K, G = spec.dims
        joint = matmul_tile_candidates(M, (G + 2) * Nkv, K,
                                       spec.itemsize, budget, target,
                                       top=top)
        lane = min(target.lane, Nkv)

        def per_projection(bn_joint: int) -> int:
            cap = max(bn_joint // (G + 2), 1)
            aligned = [d for d in divisors(Nkv)
                       if d <= cap and d % lane == 0]
            return max(aligned) if aligned else lane

        raw = []
        for bm, bk, bn in joint:
            cand = (bm, bk, per_projection(bn))
            if cand not in raw:
                raw.append(cand)
        raw.append((min(M, 256), min(K, 512), min(Nkv, 128)))
    elif spec.op in ("flash_decode", "flash_decode_fp8"):
        G, S, D = spec.dims
        raw = flash_decode_tile_candidates(
            G, S, D, spec.itemsize, budget, target, top=top,
            kv_bytes=NARROW_WEIGHT_BYTES.get(spec.op))
    elif spec.op == "flash_decode_oproj":
        # same candidate family as flash_decode; ONLY the fusion delta
        # (wo slab + output accumulator) squeezes the budget — the base
        # decode residents are already accounted for inside the
        # flash_decode candidate search
        from repro.kernels.flash_decode import (oproj_vmem_bytes_required,
                                                vmem_bytes_required)
        G, S, D, E = spec.dims
        oproj_extra = (oproj_vmem_bytes_required(0, G, D, E, spec.itemsize)
                       - vmem_bytes_required(0, G, D, spec.itemsize))
        raw = flash_decode_tile_candidates(
            G, S, D, spec.itemsize, max(budget - oproj_extra, 1),
            target, top=top)
    elif spec.op == "conv2d":
        X, Y, C, K, Fw, Fh = spec.dims
        raw = conv_tile_candidates(X, Y, C, K, Fw, Fh, spec.itemsize,
                                   budget, target, top=top,
                                   stride=spec.stride)
    else:
        raw = backward_tile_candidates(spec.op, spec.dims, spec.itemsize,
                                       budget, target, top=top,
                                       stride=spec.stride)
    usable = [t for t in raw
              if divides(spec, t) and fits_vmem(spec, t, budget)]
    if not usable:
        return [Schedule(spec, raw[0], source="analytic")]
    scored = [Schedule(spec, t, source="analytic",
                       predicted_dram_accesses=predicted_dram_accesses(
                           spec, t, budget, target))
              for t in usable]
    # fewest predicted DRAM accesses first; break ties toward bigger
    # blocks (fewer grid steps -> less pipeline overhead) — EXCEPT for
    # flash_decode, where the KV stream touches every element once at any
    # block size (the model ties) and the tile doubles as the paged
    # cache's allocation granule: smaller pages waste fewer slots per
    # request and admit under a finer free-block budget.  The FUSED ops
    # rank byte-weighted (predicted_dram_bytes): their epilogue/joint
    # operands can carry different widths, and bytes — not element
    # counts — are what fusion eliminates.
    def tile_product(s: Schedule) -> int:
        prod = 1
        for t in s.tiles:
            prod *= t
        return prod
    page_like = spec.op in ATTN_OPS or spec.op == "flash_decode_oproj"
    sign = 1 if page_like else -1
    if spec.op in FUSED_OPS:
        scored.sort(key=lambda s: (predicted_dram_bytes(
            spec, s.tiles, budget, target), sign * tile_product(s)))
    else:
        scored.sort(key=lambda s: (s.predicted_dram_accesses,
                                   sign * tile_product(s)))
    return scored[:top]
