"""Paged KV cache: fixed-size KV blocks + per-request block tables.

The serving analogue of the paper's buffer-sizing rule: instead of one
dense ``(B, max_seq, Hkv, D)`` ring buffer per request slot, every
attention layer owns a global *page pool* ``(n_pages, Hkv, page, D)`` and
each request holds a block table mapping its logical KV blocks to
physical pages.  The page size is not a heuristic — it is the KV block
of the flash-decode kernel, chosen by the analytical blocking optimizer
through ``repro.tune`` under the ``"flash_decode"`` op key
(:func:`choose_page_size`), so cache layout and kernel schedule are one
decision.

Layout properties:

* allocation granularity is one page — admission control is a free-page
  budget (``PageAllocator``), not a max-batch-times-max-seq reservation;
* pages are position-agnostic, so the layout admits prefix sharing: two
  block tables may point at the same physical page, and the allocator
  refcounts owners (:meth:`PageAllocator.share`).  :class:`PrefixCache`
  is the sharing layer — a radix tree over *full-page token spans*
  mapping each span to its physical page, so a new request's admission
  matches its longest cached prefix and only prefills the tail.  Only
  full, frozen blocks are ever shared, because decode writes into the
  page holding position ``lengths[b]``; when a shared page *would* be
  written (an exact full-page prefix hit must re-run its last token for
  the first-sample logits), the page is copy-on-write forked first;
* page 0 is a reserved scratch page: retired or inactive request slots
  keep all-zero block tables, so their (masked, ignored) decode writes
  land harmlessly in the scratch page instead of needing a branch.

Non-attention mixers (SSD, RG-LRU) keep their O(1) dense states, indexed
by batch slot — paging only ever applies to the linearly-growing KV.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_decode import page_pool_shape
from repro.models import layers as L
from repro.models.base import ParamDef, build, stack_defs
from repro.models.config import ModelConfig
from repro.obs.metrics import MetricsRegistry

SCRATCH_PAGE = 0


def choose_page_size(cfg: ModelConfig, max_seq: int,
                     cache=None, fused: bool = False,
                     reuse_rate: float | None = None) -> int:
    """KV page size from the analytical model (op key ``"flash_decode"``).

    The spec's dims are (G, S, D): G query heads per KV head stream over
    an S-long cache of head dim D.  A tuned entry in the schedule cache
    (``python -m repro.tune flash_decode ...``) wins; otherwise the
    analytic top candidate is used.

    An fp8 cache (``kv_cache_dtype`` of width 1) sizes its pages under
    the ``"flash_decode_fp8"`` key instead: the dtype-aware search sees
    the 1-byte page stream, so the fp8 pool's page size — and the fp8
    kernel's KV block — both come from the fp8 model, not the bf16 one.

    ``fused=True`` (the engine's ``fuse`` flag, wide caches only) sizes
    pages under ``"flash_decode_oproj"``: the fused kernel's resident
    wo slab + output accumulator squeeze the VMEM budget the KV block
    competes for, so the fusion-aware search may pick smaller pages.

    ``reuse_rate`` (prefix caching on) extends the tradeoff the page
    size arbitrates to hit-rate-vs-streaming: the prefix tree shares
    only *full* pages, so a cached hit re-prefills on average
    ``(page - 1) / 2`` boundary-slack tokens — small pages share
    better — while the decode kernel pays a fixed per-page cost
    (block-table fetch + DMA issue) for every page it streams — large
    pages stream better.  :func:`reuse_priced_page` re-prices the tuned
    block under that model; ``reuse_rate`` is the expected fraction of
    admissions that hit the cache.
    """
    from repro.tune import best_schedule
    g = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
    if kv_dtype.itemsize == 1:
        op, dtype_name = "flash_decode_fp8", jnp.dtype(cfg.dtype).name
        dims: tuple[int, ...] = (g, max_seq, cfg.head_dim)
    elif fused:
        op, dtype_name = "flash_decode_oproj", kv_dtype.name
        dims = (g, max_seq, cfg.head_dim, cfg.d_model)
    else:
        op, dtype_name = "flash_decode", kv_dtype.name
        dims = (g, max_seq, cfg.head_dim)
    sched = best_schedule(op, dims, dtype_name, cache=cache)
    page = max(1, min(sched.tiles[0], max_seq))
    if reuse_rate:
        return reuse_priced_page(page, max_seq, float(reuse_rate))
    return page


# per-page fixed streaming overhead, in token-equivalents: what one
# extra page boundary costs the decode kernel (block-table fetch + DMA
# issue) relative to streaming one more KV token.  Small by design —
# the analytical access counts tie across page sizes (every KV element
# streams exactly once), so this models the *constant* per-page work
# the access model cannot see.
PAGE_OVERHEAD_TOKENS = 0.25


def reuse_priced_page(tuned: int, max_seq: int, reuse_rate: float) -> int:
    """Share-vs-stream page pricing for the prefix cache.

    Candidates are the whole-page divisors of ``max_seq`` (the grid
    needs whole blocks) plus the tuned block.  Each candidate ``p``
    scores, in expected re-streamed tokens per request:

    * **sharing loss** ``reuse_rate * (p - 1) / 2`` — the tree shares
      full pages only, so a hit loses the matched prefix's boundary
      slack (uniform residue: ``(p - 1) / 2`` tokens re-prefilled);
    * **streaming loss** ``PAGE_OVERHEAD_TOKENS * max_seq / p`` — a
      full-length decode stream touches ``max_seq / p`` pages, each
      paying the fixed per-page cost.

    ``reuse_rate -> 0`` recovers the tuned kernel block (the streaming
    term dominates); higher reuse rates monotonically shrink the page.
    Ties break toward the larger page (closer to the tuned block).
    """
    tuned = max(1, min(tuned, max_seq))
    floor = min(8, max_seq)
    cands = {d for d in range(floor, max_seq + 1) if max_seq % d == 0}
    cands.add(tuned)

    def score(p: int) -> float:
        return (reuse_rate * (p - 1) / 2.0
                + PAGE_OVERHEAD_TOKENS * max_seq / p)

    return min(sorted(cands), key=lambda p: (score(p), -p))


def num_blocks(length: int, page_size: int) -> int:
    return -(-length // page_size)


def choose_prefill_chunk(cfg: ModelConfig, max_seq: int,
                         page_size: int) -> int:
    """Prefill chunk size from the same blocking model as the page size.

    A prefill chunk is processed as one multi-position q block of the
    flash-decode kernel (``q_span = chunk``), so its VMEM cost is priced
    by the kernel's own footprint model: the chunk is the largest
    power-of-two multiple of the page size (a whole number of pages, so
    chunk boundaries and page boundaries never disagree) whose q/score/
    accumulator rows still fit the VMEM budget the page size was tuned
    under, capped at ``max_seq``.  Growing the chunk amortizes the
    per-chunk KV stream over more query rows — the same
    arithmetic-intensity argument the paper makes for output blocking —
    until the row-proportional buffers hit the budget.
    """
    from repro.core.tpu_adapter import default_vmem_budget
    from repro.kernels.flash_decode import vmem_bytes_required
    g = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    kv_bytes = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype).itemsize
    act_bytes = jnp.dtype(cfg.dtype).itemsize
    budget = default_vmem_budget()
    chunk = min(page_size, max_seq)
    while chunk * 2 <= max_seq and vmem_bytes_required(
            page_size, g, cfg.head_dim, act_bytes, kv_bytes=kv_bytes,
            q_span=chunk * 2) <= budget:
        chunk *= 2
    return chunk


# ------------------------------ device side --------------------------------


def paged_attention_cache_defs(cfg: ModelConfig, n_pages: int,
                               page_size: int, model_ax: int) -> dict:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache_dtype = cfg.kv_cache_dtype or cfg.dtype
    skv = "model" if model_ax > 1 and hkv % model_ax == 0 else None
    spec = P(None, skv, None, None)
    shape = page_pool_shape(n_pages, hkv, page_size, hd)
    return {"k_pages": ParamDef(shape, spec, init="zeros",
                                dtype=cache_dtype),
            "v_pages": ParamDef(shape, spec, init="zeros",
                                dtype=cache_dtype)}


def paged_cache_defs(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, model_ax: int = 1) -> dict:
    """Decode-state tree with paged KV for every attention layer.

    Mirrors ``transformer.cache_defs`` so the scan structure is
    identical; only the attention entries change layout (pools are
    shared across the batch — no leading batch dim).
    """
    if cfg.is_encdec or cfg.prefix_tokens:
        raise NotImplementedError(
            "paged serving covers decoder-only token models")
    pattern = cfg.layer_pattern
    n_groups = cfg.n_layers // len(pattern)
    rem = cfg.n_layers % len(pattern)

    def one(mixer: str) -> dict:
        if mixer in ("global", "local"):
            return paged_attention_cache_defs(cfg, n_pages, page_size,
                                              model_ax)
        if mixer == "recurrent":
            return L.rglru_cache_defs(cfg, batch, model_ax)
        if mixer == "ssd":
            return L.ssd_cache_defs(cfg, batch, model_ax)
        raise ValueError(mixer)

    return {"layers": [stack_defs(one(m), n_groups) for m in pattern],
            "tail": [one(pattern[j]) for j in range(rem)]}


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, model_ax: int = 1):
    return build(paged_cache_defs(cfg, batch, n_pages, page_size, model_ax),
                 "init", jax.random.PRNGKey(0))


def write_prefill(cfg: ModelConfig, paged: dict, dense: dict,
                  slot: jax.Array, pages: jax.Array,
                  page_size: int) -> dict:
    """Scatter one request's dense prefill cache into the paged tree.

    ``dense`` is a batch-1 ``transformer.prefill(..., full_kv=True)``
    cache; ``pages`` is the request's physical page per logical block
    (length >= ceil(bucket / page_size); spill entries may point at the
    scratch page).  Attention K/V land in the pools; O(1) states land at
    batch ``slot``.  Traceable — the engine jits this together with the
    prefill itself, once per bucket length.
    """
    pattern = cfg.layer_pattern

    def attn_group(pc: dict, dc: dict, stacked: bool) -> dict:
        k, v = dc["k"], dc["v"]         # (..., 1, bucket, hkv, hd)
        bucket = k.shape[-3]
        nb = num_blocks(bucket, page_size)
        pad = nb * page_size - bucket

        def scatter(pool, kv):          # (n_pages, hkv, p, hd), (bucket,...)
            blocks = jnp.pad(kv, ((0, pad), (0, 0), (0, 0))).reshape(
                nb, page_size, *kv.shape[1:]).swapaxes(1, 2)
            return pool.at[pages[:nb]].set(blocks.astype(pool.dtype))

        if stacked:
            return {"k_pages": jax.vmap(scatter)(pc["k_pages"], k[:, 0]),
                    "v_pages": jax.vmap(scatter)(pc["v_pages"], v[:, 0])}
        return {"k_pages": scatter(pc["k_pages"], k[0]),
                "v_pages": scatter(pc["v_pages"], v[0])}

    def state_group(pc: dict, dc: dict, stacked: bool) -> dict:
        if stacked:   # (n_groups, B, ...) <- (n_groups, 1, ...)
            return {kk: pc[kk].at[:, slot].set(
                        dc[kk][:, 0].astype(pc[kk].dtype))
                    for kk in pc}
        return {kk: pc[kk].at[slot].set(dc[kk][0].astype(pc[kk].dtype))
                for kk in pc}

    def one(mixer: str, pc: dict, dc: dict, stacked: bool) -> dict:
        if mixer in ("global", "local"):
            return attn_group(pc, dc, stacked)
        return state_group(pc, dc, stacked)

    new = {"layers": [], "tail": []}
    for m, pc, dc in zip(pattern, paged["layers"], dense["layers"]):
        new["layers"].append(one(m, pc, dc, stacked=True))
    for j, (pc, dc) in enumerate(zip(paged["tail"], dense["tail"])):
        new["tail"].append(one(pattern[j], pc, dc, stacked=False))
    return new


def make_paged_attn_step(cfg: ModelConfig, block_tables: jax.Array,
                         page_size: int, use_kernel: bool | None = None,
                         interpret: bool | None = None,
                         fused: bool = False):
    """The ``attn_step`` the paged engine threads through
    ``transformer.decode_step``.

    ``pos`` arrives as the per-request cached-token count (B,): the new
    token sits at position ``pos[b]``, its K/V are scattered into page
    ``block_tables[b, pos // page]`` slot ``pos % page``, and attention
    runs over ``pos + 1`` positions through ``ops.paged_attention``
    (the flash-decode kernel / its oracle).

    ``fused=True`` (the engine's ``fuse`` flag) routes attention +
    output projection through ``ops.paged_attention_oproj`` — the
    per-head attention outputs never round-trip through HBM
    (docs/fusion.md); quantized wo / fp8 pools fall back inside the op.
    """
    from repro.kernels import ops

    def attn_step(p: dict, hn: jax.Array, cache: dict, pos: jax.Array,
                  window: int | None):
        b, _, _ = hn.shape
        hq, hd = cfg.n_heads, cfg.head_dim
        q, k, v = L.qkv_decode_proj(cfg, p, hn[:, 0], pos[:, None])

        rows = jnp.arange(b)
        page_idx = block_tables[rows, pos // page_size]
        slot_idx = pos % page_size
        kp = cache["k_pages"].at[page_idx, :, slot_idx].set(
            k.astype(cache["k_pages"].dtype))
        vp = cache["v_pages"].at[page_idx, :, slot_idx].set(
            v.astype(cache["v_pages"].dtype))

        if fused:
            out = ops.paged_attention_oproj(
                q, kp, vp, block_tables, pos + 1, p["wo"],
                window=window, logit_cap=cfg.attn_logit_cap,
                use_kernel=use_kernel, interpret=interpret)
            out = out[:, None, :].astype(hn.dtype)
            return out, {"k_pages": kp, "v_pages": vp}
        out = ops.paged_attention(q, kp, vp, block_tables, pos + 1,
                                  window=window,
                                  logit_cap=cfg.attn_logit_cap,
                                  use_kernel=use_kernel,
                                  interpret=interpret)
        out = out.reshape(b, 1, hq * hd).astype(hn.dtype)
        # ops.linear: wo may be a QuantizedTensor (quantized serving)
        return ops.linear(out, p["wo"]), {"k_pages": kp, "v_pages": vp}

    return attn_step


def make_paged_span_step(cfg: ModelConfig, block_tables: jax.Array,
                         page_size: int, max_seq: int,
                         use_kernel: bool | None = None,
                         interpret: bool | None = None):
    """The span-capable ``attn_step`` for multi-token
    ``transformer.decode_step`` — one definition behind both chunked
    prefill and speculative verify.

    ``hn`` is (B, S, D): S consecutive tokens starting at position
    ``pos[b]`` (= the cached length).  All S positions' K/V are
    scattered into the request's pages first, then ONE
    ``ops.paged_attention`` call with a (B, S, Hq, D) q block scores
    every position under its own causal mask — the kernel streams each
    KV page once for all S rows.  Positions at or past ``max_seq`` (the
    padded tail of a final prefill chunk, or draft rows past the token
    budget) scatter harmlessly into the scratch page; positions inside
    ``max_seq`` but past the span's accepted prefix are overwritten by
    the next span before the length mask ever exposes them.

    The fused oproj kernel is single-token (its output block is one
    (1, E) row), so spans always use the unfused attention + ``linear``
    pair; under ``fuse`` the QKV projection and the FFN still fuse.
    """
    from repro.kernels import ops

    def attn_step(p: dict, hn: jax.Array, cache: dict, pos: jax.Array,
                  window: int | None):
        b, s, _ = hn.shape
        hq, hd = cfg.n_heads, cfg.head_dim
        positions = pos[:, None] + jnp.arange(s, dtype=pos.dtype)[None, :]
        q, k, v = L.qkv_span_proj(cfg, p, hn, positions)

        rows = jnp.arange(b)[:, None]
        nb = block_tables.shape[1]
        safe = positions < max_seq
        blk = jnp.minimum(positions // page_size, nb - 1)
        page_idx = jnp.where(safe, block_tables[rows, blk], SCRATCH_PAGE)
        slot_idx = jnp.where(safe, positions % page_size, 0)
        kp = cache["k_pages"].at[page_idx, :, slot_idx].set(
            k.astype(cache["k_pages"].dtype))
        vp = cache["v_pages"].at[page_idx, :, slot_idx].set(
            v.astype(cache["v_pages"].dtype))

        out = ops.paged_attention(q, kp, vp, block_tables, pos + 1,
                                  window=window,
                                  logit_cap=cfg.attn_logit_cap,
                                  use_kernel=use_kernel,
                                  interpret=interpret)   # (B, S, Hq, hd)
        out = out.reshape(b, s, hq * hd).astype(hn.dtype)
        # ops.linear: wo may be a QuantizedTensor (quantized serving)
        return ops.linear(out, p["wo"]), {"k_pages": kp, "v_pages": vp}

    return attn_step


# ------------------------------- host side ---------------------------------


class PageAllocator:
    """Host-side refcounted free list over the page pool.

    Page 0 (``SCRATCH_PAGE``) is reserved and never handed out: it can
    never be allocated, shared, or owned, which is what lets the engine
    mask inactive block-table rows to it — and why :class:`PrefixCache`
    rejects it outright (a scratch page in the tree would hand decode
    garbage to every matching request).  :meth:`share` takes an extra
    reference for prefix sharing (one per owning request, plus one held
    by the prefix tree itself — see the module docstring for the
    full-frozen-blocks rule a sharer must follow); a page returns to
    the free list when its last owner releases it.  Every transition is
    checked, so a leak or double-free fails loudly — the serving
    hypothesis suite leans on that.
    """

    def __init__(self, n_pages: int, metrics=None):
        if n_pages < 2:
            raise ValueError("need at least one scratch + one real page")
        self.n_pages = n_pages
        self._refs = np.zeros(n_pages, np.int32)
        self._free = list(range(n_pages - 1, 0, -1))   # page 0 reserved
        m = metrics if metrics is not None else MetricsRegistry()
        m.gauge("pages.capacity").set(self.capacity)
        self._m_in_use = m.gauge("pages.in_use")

    @property
    def capacity(self) -> int:
        return self.n_pages - 1

    def available(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def refcount(self, page: int) -> int:
        """Current reference count (0 = free; scratch is always 0)."""
        return int(self._refs[page])

    def alloc(self) -> int:
        if not self._free:
            raise MemoryError("page pool exhausted")
        page = self._free.pop()
        assert self._refs[page] == 0, page
        self._refs[page] = 1
        self._m_in_use.set(self.in_use())
        return page

    def alloc_many(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: need {n}, have {len(self._free)}")
        return [self.alloc() for _ in range(n)]

    def share(self, page: int) -> int:
        """Take an extra reference (shared prompt prefix)."""
        if page == SCRATCH_PAGE or self._refs[page] <= 0:
            raise ValueError(f"cannot share unowned page {page}")
        self._refs[page] += 1
        return page

    def free(self, page: int) -> None:
        if page == SCRATCH_PAGE:
            return                       # scratch is never owned
        if self._refs[page] <= 0:
            raise ValueError(f"double free of page {page}")
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._free.append(page)
            self._m_in_use.set(self.in_use())

    def free_many(self, pages) -> None:
        for p in pages:
            self.free(int(p))


class _PrefixNode:
    """One full-page token span cached in the prefix tree."""

    __slots__ = ("key", "page", "parent", "children", "last_used")

    def __init__(self, key, page, parent):
        self.key = key                  # tuple of page_size token ids
        self.page = page                # physical page holding the span's KV
        self.parent = parent
        self.children: dict = {}        # key -> _PrefixNode
        self.last_used = 0


class PrefixCache:
    """Radix tree over full-page token spans -> physical KV pages.

    Each node caches one *page-aligned* span of prompt tokens and the
    physical page holding that span's K/V; a root-to-node path spells a
    cached prompt prefix.  The tree holds its own allocator reference on
    every cached page (``refcount == owning requests + 1``), so a page
    outlives the request that prefilled it and later requests can
    :meth:`match` it — admission bumps refcounts instead of
    re-prefilling.

    Invariants (enforced here, exercised by the serving hypothesis
    suite in ``tests/test_serve_invariants.py``):

    * spans are always exactly ``page_size`` tokens (page-aligned);
    * the scratch page can never enter the tree;
    * eviction (:meth:`evict`) only ever frees **LRU leaves whose sole
      reference is the tree's** — a page a live request owns has
      ``refcount >= 2`` and is skipped, so sharing can never free a
      page out from under a reader.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 metrics=None):
        self.allocator = allocator
        self.page_size = page_size
        self._root = _PrefixNode((), -1, None)
        self._pages: dict[int, _PrefixNode] = {}   # page -> node
        self._clock = 0
        m = metrics if metrics is not None else MetricsRegistry()
        self._m_cached = m.gauge("prefix_cache.cached_pages")
        self._m_evicted = m.counter("prefix_cache.evicted_pages")

    def __len__(self) -> int:
        return len(self._pages)

    def pages(self) -> set[int]:
        """The set of physical pages the tree currently references."""
        return set(self._pages)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    # -- lookup / registration ----------------------------------------------

    def match(self, prompt) -> list[int]:
        """Pages of the longest cached full-page prefix of ``prompt``,
        in block order (possibly the whole prompt when its length is an
        exact page multiple — the caller must then CoW-fork the last
        page before re-running the final token).  Bumps LRU on the
        matched path; takes no references — the caller shares each page
        it actually attaches."""
        p = self.page_size
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        node, out, t = self._root, [], self._tick()
        for i in range(0, len(toks) - len(toks) % p, p):
            child = node.children.get(tuple(toks[i:i + p]))
            if child is None:
                break
            child.last_used = t
            out.append(child.page)
            node = child
        return out

    def insert(self, tokens, pages) -> int:
        """Register full, frozen prompt pages; returns new nodes added.

        ``tokens`` must be page-aligned and ``pages`` its physical page
        per block.  Spans already cached keep their incumbent page (the
        duplicate prefill is the caller's loss, not a correctness
        issue); new nodes take the tree's own reference via
        :meth:`PageAllocator.share`."""
        p = self.page_size
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if len(toks) % p:
            raise ValueError(
                f"prefix spans must be page-aligned: {len(toks)} tokens "
                f"with page {p}")
        if len(toks) != len(pages) * p:
            raise ValueError(f"{len(toks)} tokens != {len(pages)} pages")
        node, added, t = self._root, 0, self._tick()
        for i, page in enumerate(pages):
            key = tuple(toks[i * p:(i + 1) * p])
            child = node.children.get(key)
            if child is None:
                page = int(page)
                if page == SCRATCH_PAGE:
                    raise ValueError(
                        "scratch page can never enter the prefix tree")
                if page in self._pages:
                    raise ValueError(
                        f"page {page} already cached under another span")
                self.allocator.share(page)     # the tree's own reference
                child = _PrefixNode(key, page, node)
                node.children[key] = child
                self._pages[page] = child
                added += 1
            child.last_used = t
            node = child
        self._m_cached.set(len(self._pages))
        return added

    # -- eviction ------------------------------------------------------------

    def evict(self, n_pages: int, protect=frozenset()) -> int:
        """Free up to ``n_pages`` pages from LRU leaves the tree is the
        sole owner of (``refcount == 1``); returns how many were freed.

        Pages in ``protect`` (a just-matched path the caller is about
        to attach) and pages any live request owns are never touched;
        an internal node only becomes evictable once its subtree is
        gone, so a cached span never loses the prefix context that
        gives it meaning."""
        freed = 0
        while freed < n_pages:
            victim = None
            for node in self._pages.values():
                if (node.children or node.page in protect
                        or self.allocator.refcount(node.page) != 1):
                    continue
                if victim is None or node.last_used < victim.last_used:
                    victim = node
            if victim is None:
                break
            del victim.parent.children[victim.key]
            del self._pages[victim.page]
            self.allocator.free(victim.page)
            freed += 1
        self._m_evicted.inc(freed)
        self._m_cached.set(len(self._pages))
        return freed
