"""Public jit'd wrappers around the Pallas kernels — differentiable.

Each op (a) asks the schedule autotuner (``repro.tune.best_schedule``)
for its VMEM tiles — a tuned, persisted schedule when one is cached for
this (op, shapes, dtype, device), else the analytical blocking model's
winner — (b) runs the Pallas kernel when shapes tile cleanly, and
(c) falls back to the jnp oracle otherwise — so models can use these ops
unconditionally.  ``interpret`` defaults to True off-TPU (kernel body
executed in Python for correctness validation on CPU).

Every op carries a ``jax.custom_vjp``: the backward nests are Pallas
kernels too (``matmul_bwd`` / ``conv2d_bwd`` / ``flash_attention_bwd``),
each lowered through the same tune pipeline under its own schedule key
(``"matmul_dgrad"``, ``"conv2d_dgrad"``, ``"conv2d_wgrad"``), with jnp
oracle fallbacks for ragged shapes — so ``jax.grad`` through a model
built on these ops takes real training steps through blocked kernels.

``linear`` is the training-path entry: a plain ``x @ w`` unless blocked
linears are enabled (``blocked_linear(True)`` context or the
``REPRO_BLOCKED_LINEAR`` env var), in which case it routes through the
differentiable blocked GEMM.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os

import jax
import jax.numpy as jnp

from repro.core.tpu_adapter import flash_tiles
from repro.kernels import ref
from repro.kernels.conv2d_bwd import conv2d_dgrad, conv2d_wgrad
from repro.kernels.conv2d_blocked import conv2d_tiled
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul_blocked import matmul_blocked
from repro.kernels.matmul_bwd import matmul_dgrad_a, matmul_dgrad_b
from repro.tune import best_schedule


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------- matmul ------------------------------------


def _matmul_fwd_impl(a, b, tiles, interpret):
    m, k = a.shape
    _, n = b.shape
    bm, bk, bn = tiles or best_schedule("matmul", (m, n, k),
                                        a.dtype.name).tiles
    if m % bm or k % bk or n % bn:
        return ref.matmul_ref(a, b)
    return matmul_blocked(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret)


def _matmul_da(g, b, interpret):
    """dA[M,K] = g[M,N] @ B^T under the "matmul_dgrad" schedule."""
    m, n = g.shape
    k = b.shape[0]
    # dims in (M_out, N_out, K_reduce) convention of the dA nest
    bm, br, bo = best_schedule("matmul_dgrad", (m, k, n), g.dtype.name).tiles
    if m % bm or n % br or k % bo:
        return jnp.dot(g, b.T, preferred_element_type=jnp.float32)
    return matmul_dgrad_a(g, b, bm=bm, br=br, bo=bo, interpret=interpret)


def _matmul_db(a, g, interpret):
    """dB[K,N] = A^T @ g[M,N] under the "matmul_dgrad" schedule."""
    m, k = a.shape
    n = g.shape[1]
    bk, br, bn = best_schedule("matmul_dgrad", (k, n, m), g.dtype.name).tiles
    if k % bk or m % br or n % bn:
        return jnp.dot(a.T, g, preferred_element_type=jnp.float32)
    return matmul_dgrad_b(a, g, bk=bk, br=br, bn=bn, interpret=interpret)


@functools.lru_cache(maxsize=256)
def _matmul_vjp(tiles, interpret):
    @jax.custom_vjp
    def fn(a, b):
        return _matmul_fwd_impl(a, b, tiles, interpret)

    def fwd(a, b):
        return fn(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return (_matmul_da(g, b, interpret).astype(a.dtype),
                _matmul_db(a, g, interpret).astype(b.dtype))

    fn.defvjp(fwd, bwd)
    return fn


def matmul(a: jax.Array, b: jax.Array,
           tiles: tuple[int, int, int] | None = None,
           interpret: bool | None = None) -> jax.Array:
    """Blocked GEMM with tuned/model-derived tiles; oracle fallback.

    Differentiable: the VJP runs the NT/TN dgrad Pallas kernels with
    their own tuned schedules (explicit ``tiles`` pin the forward only).
    """
    interpret = default_interpret() if interpret is None else interpret
    return _matmul_vjp(tuple(tiles) if tiles else None, interpret)(a, b)


def matmul_w8(a: jax.Array, w_q: jax.Array, scale: jax.Array,
              tiles: tuple[int, int, int] | None = None,
              interpret: bool | None = None) -> jax.Array:
    """int8-weight GEMM ``A @ (Wq * scale)`` under the ``"matmul_w8"``
    schedule key — the dtype-aware blocking search sizes the weight tile
    at ONE byte per element, so its tiles differ from the bf16 GEMM's.

    ``scale`` is fp32 per-output-channel ``(N,)`` or a per-tensor
    scalar.  Inference-path op (no VJP); ragged shapes take the fp32
    dequant oracle.
    """
    from repro.kernels.matmul_q import matmul_w8 as _kernel, matmul_w8_ref
    m, k = a.shape
    _, n = w_q.shape
    interpret = default_interpret() if interpret is None else interpret
    bm, bk, bn = tiles or best_schedule("matmul_w8", (m, n, k),
                                        a.dtype.name).tiles
    if m % bm or k % bk or n % bn:
        return matmul_w8_ref(a, w_q, scale)
    return _kernel(a, w_q, scale, bm=bm, bk=bk, bn=bn, interpret=interpret)


# ----------------------------- fused ops -----------------------------------

_FUSED_OPS: contextvars.ContextVar[bool | None] = \
    contextvars.ContextVar("repro_fused_ops", default=None)


def fused_ops_enabled() -> bool:
    v = _FUSED_OPS.get()
    if v is None:
        return os.environ.get("REPRO_FUSED_OPS") == "1"
    return v


@contextlib.contextmanager
def fused_ops(enable: bool = True):
    """Route model hot paths through the cross-op fused kernels while
    tracing under this context (docs/fusion.md): the MLP block through
    the epilogue-fused GEMM (:func:`matmul_fused`), the attention
    front-end through the weight-stationary QKV pass
    (:func:`qkv_fused`), and — when the serving engine asks — paged
    decode through the oproj-fused flash decode.  The serving engines
    set this from their ``fuse`` config flag at trace time."""
    tok = _FUSED_OPS.set(bool(enable))
    try:
        yield
    finally:
        _FUSED_OPS.reset(tok)


def _kernels_on(use_kernel: bool | None) -> bool:
    """Fused kernels run on TPU by default; off-TPU the jnp oracle IS
    the fused semantics (XLA fuses the epilogue) without paying the
    Pallas interpreter — same policy as ``paged_attention``.

    ``REPRO_FORCE_KERNELS=1`` forces the kernel paths (interpret mode
    off-TPU) — the profiler sets it so every hot-path op resolves its
    schedule through the tuner and dispatches the grid whose transfers
    ``kernels.*.hbm_bytes`` accounts; forced runs are for attribution,
    not throughput.
    """
    if use_kernel is None:
        if os.environ.get("REPRO_FORCE_KERNELS") == "1":
            return True
        return jax.default_backend() == "tpu"
    return use_kernel


def _attn_kernels_on(use_kernel: bool | None) -> bool:
    """Attention-kernel gating: :func:`_kernels_on` plus the
    ``REPRO_REF_ATTENTION`` roofline override, which forces the
    reference path even when a caller asked for the kernel.  The single
    policy shared by ``paged_attention`` and ``paged_attention_oproj``.
    """
    if os.environ.get("REPRO_REF_ATTENTION"):
        return False
    return _kernels_on(use_kernel)


def matmul_fused(a: jax.Array, w, *, bias: jax.Array | None = None,
                 act: str = "none", mul: jax.Array | None = None,
                 residual: jax.Array | None = None,
                 tiles: tuple[int, int, int] | None = None,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """``act(a @ w + bias) * mul + residual`` with the epilogue fused
    into the GEMM — the output tile never round-trips through HBM
    between the reduction and its pointwise tail.

    ``a`` may have any leading shape; ``mul``/``residual`` must match
    the output shape.  ``w`` may be a
    :class:`repro.quant.QuantizedTensor` (int8): the w8 epilogue-fused
    kernel runs under the PR 4 ``"matmul_w8"`` schedule key, so
    quantization and fusion compose.  Inference-path op (no VJP);
    ragged shapes take the jnp oracle.
    """
    from repro.kernels.matmul_fused import (matmul_fused as _kernel,
                                            matmul_fused_ref)
    from repro.quant.quantize import QuantizedTensor
    scale = None
    if isinstance(w, QuantizedTensor):
        if w.q.ndim != 2 or w.q.dtype != jnp.int8:
            w2 = w.dequant(jnp.float32).astype(a.dtype)
            return matmul_fused(a, w2, bias=bias, act=act, mul=mul,
                                residual=residual, tiles=tiles,
                                use_kernel=use_kernel,
                                interpret=interpret)
        scale = w.scale.reshape(-1)
        w = w.q
    lead = a.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    a2 = a.reshape(m, a.shape[-1])
    n = w.shape[-1]
    mul2 = mul.reshape(m, n) if mul is not None else None
    res2 = residual.reshape(m, n) if residual is not None else None
    k = a2.shape[-1]
    if _kernels_on(use_kernel):
        op = "matmul_w8" if scale is not None else "matmul_fused"
        bm, bk, bn = tiles or best_schedule(op, (m, n, k),
                                            a.dtype.name).tiles
        fits = True
        if tiles is None and scale is not None:
            # a cached "matmul_w8" schedule was validated against the
            # UNFUSED kernel's footprint (tune.fits_vmem); re-check it
            # against the fused footprint — the streamed epilogue tiles
            # it never accounted for — before running it
            from repro.kernels.matmul_fused import vmem_bytes_required
            from repro.tune import vmem_budget
            fits = vmem_bytes_required(bm, bk, bn, a.dtype.itemsize,
                                       w_bytes=1) <= vmem_budget()
        if fits and m % bm == 0 and k % bk == 0 and n % bn == 0:
            interpret = default_interpret() if interpret is None \
                else interpret
            out = _kernel(a2, w, scale=scale, bias=bias, mul=mul2,
                          residual=res2, act=act, bm=bm, bk=bk, bn=bn,
                          interpret=interpret)
            return out.reshape(*lead, n)
        if scale is not None and not fits:
            # keep the 1-byte weight stream: the unfused w8 kernel under
            # its own validated schedule, epilogue composed outside
            from repro.kernels.matmul_fused import ACTIVATIONS
            y = matmul_w8(a2, w, scale,
                          interpret=interpret).astype(jnp.float32)
            if bias is not None:
                y = y + jnp.asarray(bias, jnp.float32).reshape(1, -1)
            y = ACTIVATIONS[act](y)
            if mul2 is not None:
                y = y * mul2.astype(jnp.float32)
            if res2 is not None:
                y = y + res2.astype(jnp.float32)
            return y.astype(a.dtype).reshape(*lead, n)
    out = matmul_fused_ref(a2, w, scale=scale, bias=bias, mul=mul2,
                           residual=res2, act=act)
    return out.reshape(*lead, n)


def qkv_fused(x: jax.Array, wq, wk, wv, *,
              tiles: tuple[int, int, int] | None = None,
              use_kernel: bool | None = None,
              interpret: bool | None = None):
    """The attention front-end's three projections in one
    weight-stationary pass: the activation streams from HBM once
    instead of three times.  Quantized (``QuantizedTensor``) weights
    fall back to three :func:`linear` calls, preserving the w8
    semantics exactly.  Returns ``(q, k, v)`` with the input's leading
    shape."""
    from repro.kernels.qkv_fused import qkv_fused as _kernel
    from repro.quant.quantize import QuantizedTensor
    if any(isinstance(w, QuantizedTensor) for w in (wq, wk, wv)):
        return (linear(x, wq, interpret), linear(x, wk, interpret),
                linear(x, wv, interpret))
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, x.shape[-1])
    k = x2.shape[-1]
    nq, nkv = wq.shape[-1], wk.shape[-1]
    if _kernels_on(use_kernel) and nq % nkv == 0:
        g = nq // nkv
        bm, bk, bn = tiles or best_schedule("qkv_fused", (m, nkv, k, g),
                                            x.dtype.name).tiles
        if m % bm == 0 and k % bk == 0 and nkv % bn == 0:
            interpret = default_interpret() if interpret is None \
                else interpret
            q2, k2, v2 = _kernel(x2, wq, wk, wv, bm=bm, bk=bk, bn=bn,
                                 interpret=interpret)
            return (q2.reshape(*lead, nq), k2.reshape(*lead, nkv),
                    v2.reshape(*lead, nkv))
    from repro.kernels.qkv_fused import qkv_fused_ref
    q2, k2, v2 = qkv_fused_ref(x2, wq, wk, wv)
    return (q2.reshape(*lead, nq), k2.reshape(*lead, nkv),
            v2.reshape(*lead, nkv))


def paged_attention_oproj(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          lengths: jax.Array, wo,
                          layer: jax.Array | int | None = None, *,
                          window: int | None = None,
                          logit_cap: float | None = None,
                          use_kernel: bool | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Paged decode attention with the output projection fused in.

    Same contract as :func:`paged_attention` (``layer`` included) plus
    ``wo`` — the dense ``(Hq*D, E)`` output-projection weight — and
    returns ``(B, E)``: the per-head attention outputs are reduced into
    the projection in VMEM and never exist in HBM.  An fp8 page pool or
    a quantized ``wo`` falls back to the unfused pair
    (``paged_attention`` + :func:`linear`), so ``--fuse`` composes with
    every ``--quantize`` mode.
    """
    from repro.kernels.flash_decode import (flash_decode_oproj,
                                            paged_attention_oproj_ref)
    from repro.quant.quantize import QuantizedTensor
    b, hq, d = q.shape
    hkv = k_pages.shape[-3]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    fp8 = jnp.dtype(k_pages.dtype).itemsize == 1
    if fp8 or isinstance(wo, QuantizedTensor):
        out = paged_attention(q, k_pages, v_pages, block_tables, lengths,
                              layer, window=window, logit_cap=logit_cap,
                              use_kernel=use_kernel, interpret=interpret)
        return linear(out.reshape(b, hq * d), wo, interpret)
    e = wo.shape[-1]
    qg = q.reshape(b, hkv, g, d)
    wo3 = wo.reshape(hkv, g * d, e)
    if _attn_kernels_on(use_kernel):
        interpret = default_interpret() if interpret is None else interpret
        return flash_decode_oproj(qg, k_pages, v_pages, block_tables,
                                  lengths, wo3, layer, window=window,
                                  logit_cap=logit_cap,
                                  interpret=interpret)
    return paged_attention_oproj_ref(qg, k_pages, v_pages, block_tables,
                                     lengths, wo3, layer, window=window,
                                     logit_cap=logit_cap)


# ------------------------------- linear ------------------------------------

_BLOCKED_LINEAR: contextvars.ContextVar[bool | None] = \
    contextvars.ContextVar("repro_blocked_linear", default=None)


def blocked_linear_enabled() -> bool:
    v = _BLOCKED_LINEAR.get()
    if v is None:
        return os.environ.get("REPRO_BLOCKED_LINEAR") == "1"
    return v


@contextlib.contextmanager
def blocked_linear(enable: bool = True):
    """Route model projections (``ops.linear``) through the blocked,
    custom-VJP GEMM while tracing under this context."""
    tok = _BLOCKED_LINEAR.set(bool(enable))
    try:
        yield
    finally:
        _BLOCKED_LINEAR.reset(tok)


def linear(x: jax.Array, w, interpret: bool | None = None) -> jax.Array:
    """Projection ``x @ w`` for any-rank x; blocked + differentiable when
    blocked linears are enabled (see :func:`blocked_linear`).

    ``w`` may be a :class:`repro.quant.QuantizedTensor` (int8/fp8
    payload + fp32 scale): on TPU — or whenever blocked linears are on —
    2-D int8 weights route through the ``matmul_w8`` Pallas kernel
    (in-kernel dequant, 1-byte weight stream); otherwise the fp32
    dequant matmul runs, which is the fake-quant reference semantics.
    """
    from repro.quant.quantize import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        return _quantized_linear(x, w, interpret)
    if not blocked_linear_enabled():
        return x @ w
    lead = x.shape[:-1]
    out = matmul(x.reshape(-1, x.shape[-1]), w, interpret=interpret)
    return out.reshape(*lead, w.shape[-1])


def _quantized_linear(x: jax.Array, w, interpret: bool | None):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    use_kernel = (blocked_linear_enabled()
                  or jax.default_backend() == "tpu")
    if use_kernel and w.q.ndim == 2 and w.q.dtype == jnp.int8:
        out = matmul_w8(x2, w.q, w.scale.reshape(-1), interpret=interpret)
    else:
        out = (x2 @ w.dequant(jnp.float32)).astype(x.dtype)
    return out.reshape(*lead, w.shape[-1])


# -------------------------------- conv2d -----------------------------------


def _conv2d_fwd_impl(x, w, stride, tiles, interpret):
    n, h, wd, c = x.shape
    fh, fw, _, k = w.shape
    oh = (h - fh) // stride + 1
    ow = (wd - fw) // stride + 1
    bx, by, bc, bk = tiles or best_schedule(
        "conv2d", (ow, oh, c, k, fw, fh), x.dtype.name, stride=stride).tiles
    if c % bc or k % bk:
        return ref.conv2d_ref(x, w, stride)
    per_image = functools.partial(conv2d_tiled, w=w, bx=bx, by=by, bc=bc,
                                  bk=bk, stride=stride, interpret=interpret)
    return jax.vmap(per_image)(x)


@functools.lru_cache(maxsize=256)
def _conv2d_vjp(stride, tiles, interpret):
    @jax.custom_vjp
    def fn(x, w):
        return _conv2d_fwd_impl(x, w, stride, tiles, interpret)

    def fwd(x, w):
        return fn(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        fh, fw = w.shape[0], w.shape[1]
        dx = conv2d_dgrad(g, w, x.shape, stride=stride, interpret=interpret)
        dw = conv2d_wgrad(x, g, fh, fw, stride=stride, interpret=interpret)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    fn.defvjp(fwd, bwd)
    return fn


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1,
           tiles: tuple[int, int, int, int] | None = None,
           interpret: bool | None = None) -> jax.Array:
    """Direct blocked conv, NHWC x HWIO -> NHWC (VALID padding).

    Level-1 spatial blocking (halo slices from HBM) happens outside the
    kernel; level-0 channel/kernel blocking inside.  Differentiable: the
    VJP runs the wgrad Pallas kernel and the transposed-conv dgrad under
    the ``"conv2d_wgrad"`` / ``"conv2d_dgrad"`` schedule keys.
    """
    interpret = default_interpret() if interpret is None else interpret
    return _conv2d_vjp(stride, tuple(tiles) if tiles else None,
                       interpret)(x, w)


# ------------------------------- attention ---------------------------------


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              logit_cap: float | None = None,
              tiles: tuple[int, int] | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Multi-head attention with GQA.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); Hq a multiple of Hkv.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0
    bq, bkv = tiles or flash_tiles(sq, skv, d, q.dtype.itemsize)
    interpret = default_interpret() if interpret is None else interpret
    use_kernel = sq % min(bq, sq) == 0 and skv % min(bkv, skv) == 0
    # roofline analysis variant: exact HLO flops without the Pallas
    # interpreter's while-loops.  "blocked" keeps flash-style O(S) memory.
    ref_mode = os.environ.get("REPRO_REF_ATTENTION")
    if ref_mode:
        use_kernel = False

    groups = hq // hkv
    # batch, kv-head and group lead, so the vmapped kernel's blocks keep
    # (seq, D) as their last two dims — the (sublane, lane) tile
    qg = q.reshape(b, sq, hkv, groups, d).transpose(0, 2, 3, 1, 4)

    def one_head(qh, kh, vh):  # (Sq, D), (Skv, D), (Skv, D)
        if use_kernel:
            return flash_attention(qh, kh, vh, causal=causal, window=window,
                                   logit_cap=logit_cap, block_q=bq,
                                   block_kv=bkv, interpret=interpret)
        if ref_mode == "blocked":
            from repro.kernels.flash_attention import _blocked_ref
            return _blocked_ref(qh, kh, vh, causal=causal, window=window,
                                logit_cap=logit_cap, block_kv=bkv)
        return ref.attention_ref(qh, kh, vh, causal=causal,
                                 logit_cap=logit_cap, window=window)

    def per_kvhead(qh, kh, vh):  # qh: (G, Sq, D); kh, vh: (Skv, D)
        return jax.vmap(lambda qx: one_head(qx, kh, vh))(qh)

    # vmap over groups (innermost), kv-heads and batch (outer)
    fn = jax.vmap(jax.vmap(per_kvhead))
    out = fn(qg,                            # (B, Hkv, G, Sq, D)
             k.transpose(0, 2, 1, 3),       # (B, Hkv, Skv, D)
             v.transpose(0, 2, 1, 3))       # -> (B, Hkv, G, Sq, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    layer: jax.Array | int | None = None, *,
                    window: int | None = None,
                    logit_cap: float | None = None,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None,
                    use_kernel: bool | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Single-token attention over a paged KV cache (decode path).

    q: (B, Hq, D) — the current token's query rows; k/v_pages:
    (n_pages, Hkv, page, D); block_tables: (B, n_blocks) physical page
    per logical KV block; lengths: (B,) cache length per request
    *including* the token being decoded.  Returns (B, Hq, D).  Stacked
    (n_layers, n_pages, Hkv, page, D) pools take the ``layer`` to read.

    A 4-D ``q`` of shape (B, S, Hq, D) is the multi-position form
    (speculative verify / chunked prefill): the S positions are
    consecutive, their K/V already scattered into the pages, and
    ``lengths`` counts the cache including the FIRST of them.  Rows fold
    into the kernel's GQA group dim (``q_span = S``) so all S positions
    score in ONE flash-decode call over the same streamed pages; each
    position gets a causal per-row mask.  Returns (B, S, Hq, D).

    The page size doubles as the flash-decode kernel's KV block; it is
    chosen by ``repro.tune`` under the ``"flash_decode"`` op key when the
    paged cache is built (``serve.kv_cache.choose_page_size``).  With
    ``use_kernel=None`` the Pallas kernel runs on TPU and the vectorized
    jnp oracle runs elsewhere (the interpret-mode kernel is a correctness
    harness, not a CPU fast path); pass ``use_kernel=True`` to force the
    kernel (tests run it with ``interpret=True``).

    A 1-byte page pool (fp8 KV cache) routes to the fp8 kernel variant,
    whose schedule — and therefore the pool's page size — comes from the
    fp8-aware ``"flash_decode_fp8"`` op key.  ``k_scale``/``v_scale``
    are optional per-kv-head fp32 dequant scales (default: pure cast,
    which is exactly the dense ``kv_cache_dtype=fp8`` semantics, keeping
    the paged path token-exact against the fp8 dense path).
    """
    from repro.kernels.flash_decode import (flash_decode, flash_decode_fp8,
                                            paged_attention_fp8_ref,
                                            paged_attention_ref)
    multi = q.ndim == 4
    if multi:
        b, span, hq, d = q.shape
    else:
        b, hq, d = q.shape
        span = 1
    hkv = k_pages.shape[-3]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    if multi:
        # (B, S, Hq, D) -> (B, Hkv, S*G, D) with rows position-major
        # inside each kv head: row r of head h is position offset r // G,
        # local group r % G — the layout flash_decode's q_span mask
        # expects.
        qg = (q.transpose(0, 2, 1, 3)
               .reshape(b, hkv, g, span, d)
               .transpose(0, 1, 3, 2, 4)
               .reshape(b, hkv, span * g, d))
    else:
        qg = q.reshape(b, hkv, g, d)
    fp8 = jnp.dtype(k_pages.dtype).itemsize == 1
    scaled = k_scale is not None or v_scale is not None
    if scaled and not fp8:
        raise ValueError("k_scale/v_scale require a 1-byte (fp8) page pool")
    if fp8:
        # unit scales = pure-cast semantics, shared by kernel and oracle
        ks = jnp.ones(hkv, jnp.float32) if k_scale is None else k_scale
        vs = jnp.ones(hkv, jnp.float32) if v_scale is None else v_scale
    if _attn_kernels_on(use_kernel):
        interpret = default_interpret() if interpret is None else interpret
        if fp8:
            out = flash_decode_fp8(qg, k_pages, v_pages, ks, vs,
                                   block_tables, lengths, layer,
                                   window=window, logit_cap=logit_cap,
                                   q_span=span, interpret=interpret)
        else:
            out = flash_decode(qg, k_pages, v_pages, block_tables, lengths,
                               layer, window=window, logit_cap=logit_cap,
                               q_span=span, interpret=interpret)
    elif fp8 and scaled:
        out = paged_attention_fp8_ref(qg, k_pages, v_pages, ks, vs,
                                      block_tables, lengths, layer,
                                      window=window, logit_cap=logit_cap,
                                      q_span=span)
    else:
        out = paged_attention_ref(qg, k_pages, v_pages, block_tables,
                                  lengths, layer, window=window,
                                  logit_cap=logit_cap, q_span=span)
    if multi:
        return (out.reshape(b, hkv, span, g, d)
                   .transpose(0, 2, 1, 3, 4)
                   .reshape(b, span, hq, d))
    return out.reshape(b, hq, d)


def write_kv(k_pages: jax.Array, v_pages: jax.Array, k: jax.Array,
             v: jax.Array, block_tables: jax.Array, pos: jax.Array,
             layer: jax.Array | int | None = None, *,
             max_seq: int | None = None,
             use_kernel: bool | None = None,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Write new K/V rows (B, S, Hkv, D) at positions ``pos[b] + t``
    into the page pools (one layer's, or the ``layer`` of stacked
    pools); returns the updated pools.

    The aliased Pallas writer (``kernels.kv_write``) runs on TPU, the
    one-scatter jnp oracle elsewhere — the same policy as
    :func:`paged_attention`, whose pools it writes.  The oracle is no TPU
    path: inside a hot program its scatter re-lays the pool.
    """
    from repro.kernels.kv_write import write_kv as _kernel, write_kv_ref
    if _kernels_on(use_kernel):
        interpret = default_interpret() if interpret is None else interpret
        return _kernel(k_pages, v_pages, k, v, block_tables, pos, layer,
                       max_seq=max_seq, interpret=interpret)
    return write_kv_ref(k_pages, v_pages, k, v, block_tables, pos, layer,
                        max_seq=max_seq)
