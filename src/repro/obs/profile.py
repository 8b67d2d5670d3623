"""Kernel-exact HBM byte counts.

Every kernel in ``repro.kernels`` exports ``hbm_bytes`` — the block
transfers its Pallas grid actually issues, DMA elision included.
:func:`kernel_hbm_bytes` prices one resolved schedule through the
matching formula.  On dividing tiles it equals the blocking model's
level-0 DRAM traffic exactly (``tests/test_property_profile.py``), and
``obs.energy`` prices the DRAM term on it.
"""

from __future__ import annotations

from repro.tune.schedule import OpSpec


def kernel_hbm_bytes(spec: OpSpec, tiles: tuple[int, ...]) -> int | None:
    """Per-dispatch HBM bytes of the kernel serving ``spec`` at ``tiles``
    — the grid's exact block transfers under DMA elision, from the
    kernel's own exported accounting.  ``None`` for tiles the kernel
    cannot run directly (it would take its oracle fallback, whose
    traffic is XLA's business, not ours).

    Decode-attention ops price one (batch=1, kv-head=1) nest instance,
    matching the per-resolution granularity ``best_schedule`` observes
    (one resolution per call site per trace, vmapped batch/head dims
    outside).
    """
    from repro.tune.lowering import divides
    if not divides(spec, tiles):
        return None
    bpe = spec.itemsize
    if spec.op in ("matmul", "matmul_dgrad"):
        from repro.kernels.matmul_blocked import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, bytes_per_elem=bpe)
    if spec.op == "matmul_w8":
        from repro.kernels.matmul_q import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, a_bytes=bpe, w_bytes=1)
    if spec.op == "matmul_fused":
        from repro.kernels.matmul_fused import hbm_bytes
        M, N, K = spec.dims
        return hbm_bytes(M, N, K, *tiles, bytes_per_elem=bpe)
    if spec.op == "qkv_fused":
        from repro.kernels.qkv_fused import hbm_bytes
        M, Nkv, K, G = spec.dims
        return hbm_bytes(M, Nkv, K, G, *tiles, bytes_per_elem=bpe)
    if spec.op in ("flash_decode", "flash_decode_fp8"):
        from repro.kernels.flash_decode import hbm_bytes
        G, S, D = spec.dims
        (bkv,) = tiles
        kvb = 1 if spec.op == "flash_decode_fp8" else None
        return hbm_bytes(1, 1, G, D, S, bkv, bytes_per_elem=bpe,
                         kv_bytes=kvb)
    if spec.op == "flash_decode_oproj":
        from repro.kernels.flash_decode import oproj_hbm_bytes
        G, S, D, E = spec.dims
        (bkv,) = tiles
        return oproj_hbm_bytes(1, 1, G, D, E, S, bkv, bytes_per_elem=bpe)
    if spec.op == "conv2d_wgrad":
        from repro.kernels.conv2d_bwd import hbm_bytes
    else:
        from repro.kernels.conv2d_blocked import hbm_bytes
    X, Y, C, K, Fw, Fh = spec.dims
    return hbm_bytes(X, Y, C, K, Fw, Fh, *tiles, bytes_per_elem=bpe,
                     stride=spec.stride)
