"""Measurement harness: time one candidate schedule end-to-end.

Runs the real ``kernels.ops`` entry points (so spatial halo slicing, vmap
over batch, etc. are all included) with the candidate's tiles pinned, and
returns the best-of-N wall time in microseconds.  On CPU the kernels run
in Pallas ``interpret=True`` mode — useful as a correctness-preserving
tie-breaker in tests and CI, but *not* a TPU performance proxy; the
analytic DRAM-access rank from ``tune.lowering`` carries that signal.
"""

from __future__ import annotations

import time

import numpy as np

from repro.tune.schedule import Schedule


def _block(x) -> None:
    np.asarray(x)  # host transfer forces completion in both modes


def make_inputs(schedule: Schedule, seed: int = 0):
    """Representative operand arrays for the schedule's OpSpec.

    Backward ops get the operands their kernels actually stream:
    ``matmul_dgrad`` a cotangent (M, K_red) plus the transposed-read
    operand (N_out, K_red); ``conv2d_wgrad`` an input image plus the
    output-space cotangent.  ``conv2d_dgrad`` *is* a forward conv after
    the host-side dilation, so it measures as one.
    """
    import jax.numpy as jnp
    from repro.kernels.flash_decode import page_pool_shape

    spec = schedule.spec
    rng = np.random.default_rng(seed)
    if spec.op == "flash_decode_oproj":
        # the flash_decode operands plus the per-head wo slab
        G, S, D, E = spec.dims
        (page,) = schedule.tiles
        n_blocks = -(-S // page)
        q = jnp.asarray(rng.normal(size=(1, 1, G, D)), spec.dtype)
        pool = page_pool_shape(n_blocks, 1, page, D)
        kp = jnp.asarray(rng.normal(size=pool), spec.dtype)
        vp = jnp.asarray(rng.normal(size=pool), spec.dtype)
        bt = jnp.asarray(rng.permutation(n_blocks)[None, :], jnp.int32)
        lengths = jnp.asarray([S], jnp.int32)
        wo = jnp.asarray(rng.normal(size=(1, G * D, E)) * 0.1,
                         spec.dtype)
        return q, kp, vp, bt, lengths, wo
    if spec.op == "qkv_fused":
        M, Nkv, K, G = spec.dims
        x = jnp.asarray(rng.normal(size=(M, K)), spec.dtype)
        wq = jnp.asarray(rng.normal(size=(K, G * Nkv)) * 0.1, spec.dtype)
        wk = jnp.asarray(rng.normal(size=(K, Nkv)) * 0.1, spec.dtype)
        wv = jnp.asarray(rng.normal(size=(K, Nkv)) * 0.1, spec.dtype)
        return x, wq, wk, wv
    if spec.op == "matmul_fused":
        # the MLP-block epilogue shape: bias + activation + residual
        M, N, K = spec.dims
        a = jnp.asarray(rng.normal(size=(M, K)), spec.dtype)
        w = jnp.asarray(rng.normal(size=(K, N)) * 0.1, spec.dtype)
        bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
        res = jnp.asarray(rng.normal(size=(M, N)), spec.dtype)
        return a, w, bias, res
    if spec.op in ("flash_decode", "flash_decode_fp8"):
        # one request, one kv head, paged cache laid out with THIS
        # schedule's block as the page size; a shuffled block table so
        # the gather is genuinely indirect.  The fp8 variant streams
        # 1-byte pages plus per-head dequant scales.
        G, S, D = spec.dims
        (page,) = schedule.tiles
        n_blocks = -(-S // page)
        page_dtype = (jnp.float8_e4m3fn if spec.op == "flash_decode_fp8"
                      else spec.dtype)
        q = jnp.asarray(rng.normal(size=(1, 1, G, D)), spec.dtype)
        pool = page_pool_shape(n_blocks, 1, page, D)
        kp = jnp.asarray(rng.normal(size=pool), page_dtype)
        vp = jnp.asarray(rng.normal(size=pool), page_dtype)
        bt = jnp.asarray(rng.permutation(n_blocks)[None, :], jnp.int32)
        lengths = jnp.asarray([S], jnp.int32)
        if spec.op == "flash_decode_fp8":
            ks = jnp.asarray(rng.uniform(0.5, 2.0, size=(1,)), jnp.float32)
            vs = jnp.asarray(rng.uniform(0.5, 2.0, size=(1,)), jnp.float32)
            return q, kp, vp, ks, vs, bt, lengths
        return q, kp, vp, bt, lengths
    if spec.op == "matmul_w8":
        M, N, K = spec.dims
        a = jnp.asarray(rng.normal(size=(M, K)), spec.dtype)
        w_q = jnp.asarray(rng.integers(-127, 128, size=(K, N)), jnp.int8)
        scale = jnp.asarray(rng.uniform(0.005, 0.05, size=(N,)),
                            jnp.float32)
        return a, w_q, scale
    if spec.op == "matmul_dgrad":
        M, N, K = spec.dims
        g = jnp.asarray(rng.normal(size=(M, K)), spec.dtype)
        b = jnp.asarray(rng.normal(size=(N, K)), spec.dtype)
        return g, b
    if spec.op == "matmul":
        M, N, K = spec.dims
        a = jnp.asarray(rng.normal(size=(M, K)), spec.dtype)
        b = jnp.asarray(rng.normal(size=(K, N)), spec.dtype)
        return a, b
    X, Y, C, K, Fw, Fh = spec.dims
    ih = (Y - 1) * spec.stride + Fh
    iw = (X - 1) * spec.stride + Fw
    x = jnp.asarray(rng.normal(size=(1, ih, iw, C)), spec.dtype)
    if spec.op == "conv2d_wgrad":
        g = jnp.asarray(rng.normal(size=(1, Y, X, K)) * 0.5, spec.dtype)
        return x, g
    w = jnp.asarray(rng.normal(size=(Fh, Fw, C, K)) * 0.5, spec.dtype)
    return x, w


def run_once(schedule: Schedule, inputs, interpret: bool | None = None):
    """Execute the schedule's op once and return the (blocked-on) result."""
    from repro.kernels import ops

    spec = schedule.spec
    interpret = ops.default_interpret() if interpret is None \
        else bool(interpret)
    if spec.op == "flash_decode_oproj":
        from repro.kernels.flash_decode import flash_decode_oproj
        q, kp, vp, bt, lengths, wo = inputs
        out = flash_decode_oproj(q, kp, vp, bt, lengths, wo,
                                 interpret=interpret)
    elif spec.op == "qkv_fused":
        from repro.kernels.qkv_fused import qkv_fused
        x, wq, wk, wv = inputs
        bm, bk, bn = schedule.tiles
        out = qkv_fused(x, wq, wk, wv, bm=bm, bk=bk, bn=bn,
                        interpret=interpret)[0]
    elif spec.op == "matmul_fused":
        a, w, bias, res = inputs
        out = ops.matmul_fused(a, w, bias=bias, act="gelu", residual=res,
                               tiles=schedule.tiles, use_kernel=True,
                               interpret=interpret)
    elif spec.op == "flash_decode":
        from repro.kernels.flash_decode import flash_decode
        q, kp, vp, bt, lengths = inputs
        out = flash_decode(q, kp, vp, bt, lengths, interpret=interpret)
    elif spec.op == "flash_decode_fp8":
        from repro.kernels.flash_decode import flash_decode_fp8
        q, kp, vp, ks, vs, bt, lengths = inputs
        out = flash_decode_fp8(q, kp, vp, ks, vs, bt, lengths,
                               interpret=interpret)
    elif spec.op == "matmul_w8":
        a, w_q, scale = inputs
        out = ops.matmul_w8(a, w_q, scale, tiles=schedule.tiles,
                            interpret=interpret)
    elif spec.op == "matmul_dgrad":
        from repro.kernels.matmul_bwd import matmul_dgrad_a
        g, b = inputs
        bm, br, bo = schedule.tiles
        out = matmul_dgrad_a(g, b, bm=bm, br=br, bo=bo,
                             interpret=interpret)
    elif spec.op == "matmul":
        a, b = inputs
        out = ops.matmul(a, b, tiles=schedule.tiles, interpret=interpret)
    elif spec.op == "conv2d_wgrad":
        from repro.kernels.conv2d_bwd import conv2d_wgrad
        x, g = inputs
        out = conv2d_wgrad(x, g, spec.dims[5], spec.dims[4],
                           stride=spec.stride, tiles=schedule.tiles,
                           interpret=interpret)
    else:  # conv2d and conv2d_dgrad (the latter is a forward nest)
        x, w = inputs
        out = ops.conv2d(x, w, stride=spec.stride, tiles=schedule.tiles,
                         interpret=interpret)
    _block(out)
    return out


def measure(schedule: Schedule, interpret: bool | None = None,
            iters: int = 3, warmup: int = 1, seed: int = 0) -> float:
    """Best-of-``iters`` latency (microseconds) for one schedule."""
    inputs = make_inputs(schedule, seed)
    for _ in range(warmup):
        run_once(schedule, inputs, interpret)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once(schedule, inputs, interpret)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def measure_top(schedules: list[Schedule], top_n: int = 3,
                interpret: bool | None = None, iters: int = 3,
                ) -> list[Schedule]:
    """Time the first ``top_n`` schedules; return ALL schedules re-ranked
    (measured ones first, by latency; unmeasured keep their analytic
    order behind them)."""
    import dataclasses

    timed = [dataclasses.replace(s, measured_us=measure(s, interpret,
                                                        iters=iters),
                                 source="measured")
             for s in schedules[:top_n]]
    timed.sort(key=lambda s: s.measured_us)
    return timed + schedules[top_n:]
