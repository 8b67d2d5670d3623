"""Paged flash-decode attention Pallas kernel (the serving nest).

One query row per request streams over a block-table-indexed paged KV
cache: the KV *pages* are the paper's kernel buffer (each page is fetched
from HBM exactly once per step), and the fp32 running (m, l, acc)
statistics are the output buffer held VMEM-resident across the whole KV
reduction.  The page size — which is simultaneously the kernel's KV block
— is tuned through ``repro.tune`` under the ``"flash_decode"`` op key, so
the paged cache layout (``serve/kv_cache.py``) and the kernel schedule
come from the same analytical blocking model.

Layouts (GQA-native: all G query heads of one KV head share its pages):

* ``q``:            (B, Hkv, G, D) — the current token's query rows;
* ``k/v_pages``:    (n_pages, Hkv, page, D) — the global page pool, head-
  major inside a page so one kernel block ``(1, 1, page, D)`` is a
  contiguous (sublane x lane) tile of one KV head;
* ``block_tables``: (B, n_blocks) int32 — physical page of each logical
  KV block; entries past a request's length must still be *valid* page
  indices (use 0) because the DMA runs before the mask is applied;
* ``lengths``:      (B,) int32 — tokens in the cache *including* the one
  being decoded (its K/V must already be scattered into the pages).

Grid is (B, Hkv, n_blocks) with the KV-block dim minor-most so the
accumulators persist across the reduction; block tables and lengths ride
in scalar-prefetch SMEM so the page DMA for block ``i`` of request ``b``
is issued straight from ``block_tables[b, i]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF


LANE, SUBLANE = 128, 8


def _pad(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def page_pool_shape(n_pages: int, hkv: int, page: int,
                    head_dim: int) -> tuple[int, int, int, int]:
    """Shape of a K or V page pool as every kernel here reads it."""
    return (n_pages, hkv, page, head_dim)


def vmem_bytes_required(block_kv: int, groups: int, head_dim: int,
                        bytes_per_elem: int = 2,
                        kv_bytes: int | None = None,
                        q_span: int = 1) -> int:
    """VMEM footprint of one grid step of :func:`flash_decode`.

    Counts what Mosaic allocates, not just the logical tiles: every
    pipelined block (K and V pages, the q block and the output block)
    is double-buffered across grid steps; the (rows, 1) fp32 running
    max/denominator scratch pads to a full 128-lane row each; the kernel
    body keeps fp32 copies of q, K and V next to the fp32 score block
    ``s`` and its exponentials ``p``.  Minor dims pad to lanes and row
    counts to sublanes.  Single source of truth for the
    ``"flash_decode"`` schedule-candidate filter in ``tune.lowering``.

    ``kv_bytes`` is the page element width when the cache is quantized
    (fp8: 1) — only the streamed pages narrow; q/out keep their dtype
    and the running statistics stay fp32.

    ``q_span`` is the number of query *positions* folded into the q
    block (speculative verify / chunked prefill): everything that scales
    with the query rows — q/o blocks, scores, running stats — multiplies
    by it, while the streamed pages do not.  That asymmetry is what lets
    ``serve.kv_cache.choose_prefill_chunk`` price a multi-page chunk
    against the same VMEM budget the page size was tuned under.
    """
    kvb = kv_bytes or bytes_per_elem
    rows = _pad(groups * q_span, SUBLANE)
    d = _pad(head_dim, LANE)
    kv_cols = _pad(block_kv, LANE)
    streamed = 2 * 2 * block_kv * d * kvb                 # K + V, 2 buffers
    q_o = 2 * 2 * rows * d * bytes_per_elem               # q + o, 2 buffers
    stats = 2 * rows * LANE * 4 + rows * d * 4            # m, l; acc
    f32_copies = (rows + 2 * block_kv) * d * 4            # q, K, V in fp32
    scores = 2 * rows * kv_cols * 4                       # s and p
    return streamed + q_o + stats + f32_copies + scores


def _block_mask(len_ref, b, i, block_kv: int, window: int | None,
                q_span: int = 1, groups: int = 1):
    """Validity mask for KV block ``i`` of request ``b``.

    With ``q_span == 1`` (plain decode) the mask is ``(1, block_kv)`` and
    broadcasts over the G query rows.  With ``q_span > 1`` the q block
    holds ``q_span`` consecutive *positions* of ``groups`` rows each
    (position-major: row r is position offset ``r // groups``), and the
    mask is per-row causal: position offset t sees ``kpos < length + t``
    — ``lengths`` counts the cache *including the first* spanned token,
    exactly the single-token convention extended row-wise.
    """
    length = len_ref[b]                                  # tokens incl. current
    kpos = i * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_kv), 1)                     # logical positions
    if q_span == 1:
        mask = kpos < length
        if window is not None:
            # same rule as the dense decode path: query position is
            # length-1, and it sees kpos > qpos - window
            mask &= kpos > (length - 1) - window
        return mask
    offs = jax.lax.broadcasted_iota(
        jnp.int32, (q_span * groups, 1), 0) // groups    # row -> position off
    mask = kpos < length + offs
    if window is not None:
        mask &= kpos > (length - 1 + offs) - window
    return mask


def _softmax_update(s, v, mask, m_ref, l_ref, acc_ref):
    """One streaming-softmax step over a masked score block — the shared
    core of the bf16 and fp8 decode kernels."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                                  # (G, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked blocks/rows (m == NEG_INF) against NaN
    p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp(jnp.minimum(m_prev - m_new, 0.0)))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _decode_init(i, m_ref, l_ref, acc_ref):
    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_finish(i, n_blocks, o_ref, m_ref, l_ref, acc_ref):
    @pl.when(i == n_blocks - 1)
    def _done():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe_l)[None, None].astype(o_ref.dtype)


def _decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float,
                   window: int | None, logit_cap: float | None,
                   block_kv: int, n_blocks: int, q_span: int = 1,
                   groups: int = 1):
    b = pl.program_id(0)
    i = pl.program_id(2)
    _decode_init(i, m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (q_span*G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)

    mask = _block_mask(len_ref, b, i, block_kv, window, q_span, groups)
    _softmax_update(s, v, mask, m_ref, l_ref, acc_ref)
    _decode_finish(i, n_blocks, o_ref, m_ref, l_ref, acc_ref)


def _decode_fp8_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       scale: float, window: int | None,
                       logit_cap: float | None, block_kv: int,
                       n_blocks: int, q_span: int = 1, groups: int = 1):
    """fp8-page variant: K/V stream in at 1 byte/elem and dequantize
    in-VMEM with the per-kv-head fp32 scales.  The scales are scalars
    within a grid step, so K's folds into the score block and V's into
    the accumulator update — no widened page tile is ever materialized.
    """
    b = pl.program_id(0)
    i = pl.program_id(2)
    _decode_init(i, m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D) fp8->f32
    v = v_ref[0, 0].astype(jnp.float32)
    h = pl.program_id(1)
    ks = ks_ref[h]                                       # this head's scales
    vs = vs_ref[h]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (scale * ks)
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)

    mask = _block_mask(len_ref, b, i, block_kv, window, q_span, groups)
    _softmax_update(s, v * vs, mask, m_ref, l_ref, acc_ref)
    _decode_finish(i, n_blocks, o_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("window", "logit_cap",
                                             "q_span", "interpret"))
def flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                 block_tables: jax.Array, lengths: jax.Array, *,
                 window: int | None = None,
                 logit_cap: float | None = None,
                 q_span: int = 1,
                 interpret: bool = False) -> jax.Array:
    """Paged attention over one q block per (batch, kv-head).

    ``q`` is (B, Hkv, q_span*G, D): with ``q_span == 1`` the classic
    single-token decode; with ``q_span > 1`` the rows hold ``q_span``
    consecutive positions (position-major — row r is position offset
    ``r // G``) whose K/V must already be scattered into the pages, and
    each position's rows get a causal per-row mask (``lengths`` still
    counts the cache including the FIRST spanned token).  This is the
    one kernel capability behind both speculative verify and chunked
    prefill: the GQA grouping already streams a multi-row q block, so
    spanning positions costs no extra KV traffic.  Returns the same
    shape as ``q``.
    """
    b, hkv, gtot, d = q.shape
    if gtot % q_span:
        raise ValueError(f"q rows {gtot} not divisible by q_span {q_span}")
    g = gtot // q_span
    page = k_pages.shape[2]
    n_blocks = block_tables.shape[1]
    scale = d ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, gtot, d),
                         lambda bi, h, i, bt, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gtot, d),
                               lambda bi, h, i, bt, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gtot, 1), jnp.float32),  # running max m
            pltpu.VMEM((gtot, 1), jnp.float32),  # running denom l
            pltpu.VMEM((gtot, d), jnp.float32),  # accumulator (OB)
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          logit_cap=logit_cap, block_kv=page,
                          n_blocks=n_blocks, q_span=q_span, groups=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gtot, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("window", "logit_cap",
                                             "q_span", "interpret"))
def flash_decode_fp8(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     k_scale: jax.Array, v_scale: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     window: int | None = None,
                     logit_cap: float | None = None,
                     q_span: int = 1,
                     interpret: bool = False) -> jax.Array:
    """Paged attention over an fp8-quantized page pool.

    Same contract as :func:`flash_decode` (including the multi-position
    ``q_span`` q block) except ``k_pages``/``v_pages`` are fp8
    (``float8_e4m3fn``) and ``k_scale``/``v_scale`` are fp32 per-kv-head
    dequantization scales of shape ``(Hkv,)`` (pass ones for a pure-cast
    cache).  The pages stream from HBM at one byte per element;
    dequantization happens in VMEM inside the kernel, so HBM traffic for
    the dominant decode operand is halved vs bf16 — which is why the
    page size comes from the ``"flash_decode_fp8"`` schedule key.
    Returns the same shape as ``q`` in ``q.dtype``.
    """
    b, hkv, gtot, d = q.shape
    if gtot % q_span:
        raise ValueError(f"q rows {gtot} not divisible by q_span {q_span}")
    g = gtot // q_span
    page = k_pages.shape[2]
    n_blocks = block_tables.shape[1]
    scale = d ** -0.5
    ks = jnp.asarray(k_scale, jnp.float32).reshape(hkv)
    vs = jnp.asarray(v_scale, jnp.float32).reshape(hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, gtot, d),
                         lambda bi, h, i, bt, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
            # the (Hkv,) scale vectors sit whole in SMEM: a per-head
            # (1, 1) VMEM block would split the sublane dim
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, gtot, d),
                               lambda bi, h, i, bt, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gtot, 1), jnp.float32),  # running max m
            pltpu.VMEM((gtot, 1), jnp.float32),  # running denom l
            pltpu.VMEM((gtot, d), jnp.float32),  # accumulator (OB)
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_fp8_kernel, scale=scale, window=window,
                          logit_cap=logit_cap, block_kv=page,
                          n_blocks=n_blocks, q_span=q_span, groups=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gtot, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages, ks, vs)


def hbm_bytes(batch: int, hkv: int, groups: int, head_dim: int,
              seq: int, block_kv: int, bytes_per_elem: int = 2,
              kv_bytes: int | None = None) -> int:
    """Exact HBM traffic of one :func:`flash_decode` call (the grid's
    actual block transfers; scalar-prefetch block tables and lengths are
    excluded, as in :func:`oproj_hbm_bytes`).

    The q and output blocks are (bi, h)-indexed — constant across the
    KV-block grid dim, so each moves once per (batch, kv-head) row; the
    K/V pages stream once per row.  ``kv_bytes`` gives the paged K/V
    streams their own width (fp8 cache: 1); the fp8 variant additionally
    loads the two (Hkv,) fp32 dequant-scale vectors into SMEM once.
    """
    nb = -(-seq // block_kv)
    kvb = bytes_per_elem if kv_bytes is None else kv_bytes
    q_bytes = batch * hkv * groups * head_dim * bytes_per_elem
    kv = 2 * batch * hkv * nb * block_kv * head_dim * kvb
    out = batch * hkv * groups * head_dim * bytes_per_elem
    total = q_bytes + kv + out
    if kv_bytes is not None:
        total += 2 * 4 * hkv              # whole scale vectors, once
    return total


def oproj_vmem_bytes_required(block_kv: int, groups: int, head_dim: int,
                              d_model: int,
                              bytes_per_elem: int = 2) -> int:
    """VMEM footprint of one grid step of :func:`flash_decode_oproj`:
    the base decode footprint plus the double-buffered per-head wo slab
    (G*D x E), the fp32 copy of one group's (D, E) slice, and the
    sublane-padded (1, E) fp32 accumulator and output block that stay
    resident across the head loop.  Single source of truth for the
    ``"flash_decode_oproj"`` schedule-candidate filter."""
    base = vmem_bytes_required(block_kv, groups, head_dim, bytes_per_elem)
    e = _pad(d_model, LANE)
    wo_slab = 2 * groups * head_dim * e * bytes_per_elem
    wo_f32 = head_dim * e * 4
    out = SUBLANE * e * 4 + 2 * SUBLANE * e * bytes_per_elem
    return base + wo_slab + wo_f32 + out


def oproj_hbm_bytes(batch: int, hkv: int, groups: int, head_dim: int,
                    d_model: int, seq: int, block_kv: int,
                    bytes_per_elem: int = 2) -> int:
    """Exact HBM traffic of one :func:`flash_decode_oproj` call (the
    grid's actual block transfers).  The unfused baseline additionally
    writes the (B, Hq, D) attention output and reads it back for the
    projection GEMM — that intermediate never exists here."""
    nb = -(-seq // block_kv)
    q_bytes = batch * hkv * groups * head_dim * bytes_per_elem
    kv = 2 * batch * hkv * nb * block_kv * head_dim * bytes_per_elem
    wo = batch * hkv * groups * head_dim * d_model * bytes_per_elem
    out = batch * d_model * bytes_per_elem
    return q_bytes + kv + wo + out


def _decode_oproj_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, wo_ref,
                         o_ref, m_ref, l_ref, acc_ref, oacc_ref, *,
                         scale: float, window: int | None,
                         logit_cap: float | None, block_kv: int,
                         n_blocks: int, n_heads: int, groups: int,
                         head_dim: int):
    """Flash-decode with the output projection's row tile fused in.

    Grid is (B, Hkv, n_blocks) with the KV block minor-most, exactly as
    :func:`flash_decode` — but the per-head attention output (G, D) is
    never written to HBM: at the last KV block of each head it is
    multiplied into that head's wo row slab and accumulated into the
    (1, E) output block, which ignores the head grid dim and therefore
    stays VMEM-resident across the whole head loop (the paper's OB rule
    applied to the *consumer* nest's reduction over heads).
    """
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    _decode_init(i, m_ref, l_ref, acc_ref)

    @pl.when((h == 0) & (i == 0))
    def _init_out():
        oacc_ref[...] = jnp.zeros_like(oacc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)

    mask = _block_mask(len_ref, b, i, block_kv, window)
    _softmax_update(s, v, mask, m_ref, l_ref, acc_ref)

    @pl.when(i == n_blocks - 1)
    def _project():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        attn = (acc_ref[...] / safe_l)                   # (G, D) fp32
        # row g of attn meets rows [g*D, (g+1)*D) of the (G*D, E) slab;
        # static per-group slices keep every operand a 2-D tile
        out = oacc_ref[...]
        for gi in range(groups):
            wo = wo_ref[0, gi * head_dim:(gi + 1) * head_dim, :]
            out += jnp.dot(attn[gi:gi + 1, :], wo.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        oacc_ref[...] = out

    @pl.when((h == n_heads - 1) & (i == n_blocks - 1))
    def _done():
        o_ref[0] = oacc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "logit_cap",
                                             "interpret"))
def flash_decode_oproj(q: jax.Array, k_pages: jax.Array,
                       v_pages: jax.Array, block_tables: jax.Array,
                       lengths: jax.Array, wo: jax.Array, *,
                       window: int | None = None,
                       logit_cap: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Paged single-token attention fused with the output projection.

    Same contract as :func:`flash_decode` plus ``wo``: the attention
    output projection reshaped per kv head, ``(Hkv, G*D, E)`` (rows of
    the dense ``(Hq*D, E)`` weight grouped by the kv head that produces
    them).  Returns ``(B, E)`` — the per-head (G, D) attention outputs
    are reduced into the projection inside VMEM and never round-trip
    through HBM.  Schedule key: ``"flash_decode_oproj"`` (the KV block
    is still the tunable, and still the paged cache's page size).

    Traffic caveat (docs/fusion.md, "when fusion loses"): the output
    block is resident across the head loop of ONE batch row, so the wo
    slabs are refetched per row — ``B * Hq * D * E`` weight bytes vs
    the unfused GEMM's single pass.  Per request (B=1, the paged
    engine's per-slot view) fusion strictly saves the attention
    output's round-trip; at large decode batches the wo refetch can
    outweigh it, which is exactly the arithmetic
    ``oproj_hbm_bytes`` exposes — leave ``fuse`` off there.
    """
    b, hkv, g, d = q.shape
    page = k_pages.shape[2]
    e = wo.shape[-1]
    assert wo.shape == (hkv, g * d, e), (wo.shape, (hkv, g * d, e))
    n_blocks = block_tables.shape[1]
    scale = d ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, h, i, bt, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, h, i, bt, ln: (bt[bi, i], h, 0, 0)),
            pl.BlockSpec((1, g * d, e), lambda bi, h, i, bt, ln: (h, 0, 0)),
        ],
        # (B, 1, E) so the block's last two dims are whole: a (1, E)
        # block over (B, E) would split the sublane dim
        out_specs=pl.BlockSpec((1, 1, e),
                               lambda bi, h, i, bt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),     # running max m
            pltpu.VMEM((g, 1), jnp.float32),     # running denom l
            pltpu.VMEM((g, d), jnp.float32),     # attention acc (OB)
            pltpu.VMEM((1, e), jnp.float32),     # projected-output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_oproj_kernel, scale=scale, window=window,
                          logit_cap=logit_cap, block_kv=page,
                          n_blocks=n_blocks, n_heads=hkv, groups=g,
                          head_dim=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, e), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages, wo).reshape(b, e)


def paged_attention_oproj_ref(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array,
                              block_tables: jax.Array,
                              lengths: jax.Array, wo: jax.Array, *,
                              window: int | None = None,
                              logit_cap: float | None = None,
                              ) -> jax.Array:
    """jnp oracle (and the unfused chain): paged attention, then the
    dense projection over the flattened heads.  wo: (Hkv, G*D, E)."""
    b, hkv, g, d = q.shape
    e = wo.shape[-1]
    attn = paged_attention_ref(q, k_pages, v_pages, block_tables,
                               lengths, window=window,
                               logit_cap=logit_cap)    # (B, Hkv, G, D)
    flat = attn.reshape(b, hkv * g * d).astype(jnp.float32)
    w2 = wo.reshape(hkv * g * d, e).astype(jnp.float32)
    return jnp.dot(flat, w2,
                   preferred_element_type=jnp.float32).astype(q.dtype)


def paged_attention_fp8_ref(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, k_scale: jax.Array,
                            v_scale: jax.Array, block_tables: jax.Array,
                            lengths: jax.Array, *,
                            window: int | None = None,
                            logit_cap: float | None = None,
                            q_span: int = 1) -> jax.Array:
    """jnp oracle for :func:`flash_decode_fp8`: dequantize the page pool
    in fp32, then the dense masked softmax of :func:`paged_attention_ref`.
    """
    hkv = k_pages.shape[1]
    ks = jnp.asarray(k_scale, jnp.float32).reshape(1, hkv, 1, 1)
    vs = jnp.asarray(v_scale, jnp.float32).reshape(1, hkv, 1, 1)
    return paged_attention_ref(q, k_pages.astype(jnp.float32) * ks,
                               v_pages.astype(jnp.float32) * vs,
                               block_tables, lengths, window=window,
                               logit_cap=logit_cap, q_span=q_span)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, *,
                        window: int | None = None,
                        logit_cap: float | None = None,
                        q_span: int = 1) -> jax.Array:
    """jnp oracle: gather pages by block table, dense masked softmax.

    Bit-comparable semantics to :func:`flash_decode` (same masking rules
    — including the per-position rows of a ``q_span > 1`` block — and
    fp32 math); the correctness oracle in tests and the fast vectorized
    path off-TPU.
    """
    b, hkv, gtot, d = q.shape
    g = gtot // q_span
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    # (b, nb, hkv, page, d) -> (b, hkv, nb * page, d)
    k = k_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, nb * page, d)
    v = v_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, nb * page, d)
    s = jnp.einsum("bhgd,bhld->bhgl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * d ** -0.5
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)
    kpos = jnp.arange(nb * page)
    offs = jnp.arange(gtot) // g                         # row -> position off
    lim = lengths[:, None] + offs[None, :]               # (b, gtot)
    valid = kpos[None, None, :] < lim[..., None]
    if window is not None:
        valid &= kpos[None, None, :] > (lim[..., None] - 1) - window
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgl,bhld->bhgd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
