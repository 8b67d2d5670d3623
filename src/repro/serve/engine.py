"""Serving engines: static-batch baseline and paged continuous batching.

``DecodeEngine`` is the static-batch baseline: left-padded prefill, dense
per-slot KV caches, one jitted token loop.  Its decode loop is a
``lax.scan`` with device-side sampling — tokens accumulate on device and
transfer to the host once per call, not once per token.

``PagedEngine`` is the production path (docs/serving.md): a paged KV
cache whose page size comes from the analytical blocking model
(``tune`` op key ``"flash_decode"``), a decode-priority continuous-
batching scheduler, and three mechanisms that keep steady-state decode
from ever stalling:

* **chunked prefill** — prompts are cached ``prefill_chunk`` tokens at a
  time (a whole number of KV pages, sized by
  ``kv_cache.choose_prefill_chunk`` under the same VMEM budget as the
  page size) through the multi-position form of the flash-decode kernel,
  interleaved with decode steps instead of monopolizing one;
* **speculative decode** — an n-gram self-drafted draft-verify step
  scores ``spec_decode`` draft tokens plus the current token in ONE
  flash-decode call (the kernel's GQA grouping carries the multi-row q
  block) and accepts the longest greedy-matching prefix, so accepted
  tokens amortize the per-step host overhead;
* **persistent device state** — block tables and lengths live on device
  and are updated incrementally at admission/eviction instead of being
  rebuilt and re-uploaded every step.

With ``prefix_cache=True`` a radix tree over full-page token spans
(``kv_cache.PrefixCache``) is threaded through admission: a request
whose prompt prefix is cached shares the matched pages (refcount bump,
no allocation, no model call) and chunk-prefills only the O(new tokens)
tail from the matched boundary; an exact full-page match CoW-forks its
final page before re-running the last prompt token for the first-sample
logits.  Completed prefills register their full prompt pages back into
the tree, and admission under page pressure reclaims LRU tree leaves —
never a page a live request owns.

The decode step remains fully jitted — paged flash-decode attention,
device-side sampling, and an on-device output buffer read back only when
a request finishes.

Every request leaves with a terminal :class:`~repro.serve.lifecycle.
RequestStatus` (docs/robustness.md): deadlines/TTLs expire it,
``cancel()`` truncates it, exhausted admission retries or the NaN/Inf
logit guard (``nan_guard=True`` — per-slot isfinite tracking inside the
jitted decode, failing only the poisoned slot) fail it, and under page
exhaustion the scheduler can preempt it and restore it later through
the prefix cache with byte-exact tokens.  A
:class:`~repro.serve.lifecycle.DegradationController` (``degrade=True``)
steps spec-decode off, shrinks the decode chunk, and finally enables
preemption as pressure mounts.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import Obs
from repro.obs.trace import null_span
from repro.serve import kv_cache as KV
from repro.serve.lifecycle import DegradationController, RequestStatus
from repro.serve.scheduler import Request, Scheduler


def sample_tokens(cfg: ModelConfig, logits: jax.Array, temperature: float,
                  key: jax.Array) -> jax.Array:
    """Greedy (temperature <= 0) or categorical sampling; masks the
    padded-vocab tail.  logits: (B, V_padded) -> (B,) int32."""
    logits = logits[:, :cfg.vocab]
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


# ========================= static-batch baseline ===========================


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 1024
    temperature: float = 0.0   # 0 -> greedy
    seed: int = 0
    fuse: bool = False         # cross-op fused kernels (docs/fusion.md)


class DecodeEngine:
    """Static batch: every request prefills together (left-padded to a
    common length) and decodes in lock-step for a fixed token budget."""

    def __init__(self, cfg: ModelConfig, params: Any, sc: ServeConfig,
                 obs: Obs | None = None):
        self.cfg, self.params, self.sc = cfg, params, sc
        self.obs = obs if obs is not None else Obs()
        reg = self.obs.registry
        self._m_prefill_tokens = reg.counter("engine.prefill_tokens")
        self._m_decode_tokens = reg.counter("engine.decode_tokens")

        def prefill(*a, **kw):
            # the fusion flag is read at TRACE time; each engine owns its
            # jit wrappers, so the flag is pinned per instance
            with ops.fused_ops(sc.fuse):
                return T.prefill(cfg, *a, **kw)

        self._prefill = jax.jit(prefill, static_argnames=("max_seq",))
        self._gen = jax.jit(self._gen_fn, static_argnames=("n_tokens",))

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 enc_embeds=None, prefix_embeds=None) -> np.ndarray:
        """prompts: (B, S0) int32 (right-aligned).  Returns (B, n_tokens)."""
        cfg, sc = self.cfg, self.sc
        b, s0 = prompts.shape
        extras = {}
        if enc_embeds is not None:
            extras["enc_embeds"] = enc_embeds
        if prefix_embeds is not None:
            extras["prefix_embeds"] = prefix_embeds
        tr = self.obs.tracer
        sp = tr.span if tr is not None else null_span
        with sp("prefill", cat="static"), \
                self.obs.dram.scope(f"static_prefill[{s0}]"):
            logits, cache = self._prefill(self.params, jnp.asarray(prompts),
                                          max_seq=sc.max_seq, **extras)
            if tr is not None:
                jax.block_until_ready(logits)
        pos = s0 + (cfg.prefix_tokens if prefix_embeds is not None else 0)
        rng = jax.random.PRNGKey(sc.seed)
        # the whole token loop runs on device (lax.scan, sampling
        # included) and transfers once — no per-token host sync
        with sp("decode", cat="static"), \
                self.obs.dram.scope(f"static_generate[{n_tokens}]"):
            out = self._gen(self.params, logits, cache, jnp.int32(pos), rng,
                            n_tokens=n_tokens)
            if tr is not None:
                jax.block_until_ready(out)
        with sp("readback", cat="static"):
            host = np.asarray(out)
        self._m_prefill_tokens.inc(b * s0)
        self._m_decode_tokens.inc(b * n_tokens)
        self.obs.dram.end_step(range(b))
        return host

    def _gen_fn(self, params, logits, cache, pos, rng, *, n_tokens: int):
        cfg, sc = self.cfg, self.sc
        tok0 = sample_tokens(cfg, logits, sc.temperature,
                             jax.random.fold_in(rng, 0))

        def body(carry, i):
            tok, cache, pos = carry
            logits, cache = T.decode_step(cfg, params, tok, cache, pos)
            t = sample_tokens(cfg, logits, sc.temperature,
                              jax.random.fold_in(rng, i))
            return (t, cache, pos + 1), t

        with ops.fused_ops(sc.fuse):
            (_, _, _), rest = jax.lax.scan(
                body, (tok0, cache, pos), jnp.arange(1, n_tokens))
        return jnp.concatenate([tok0[:, None], rest.T], axis=1)


# ======================== paged continuous batching ========================


@dataclasses.dataclass
class PagedServeConfig:
    max_seq: int = 1024            # per-request prompt + generation cap
    max_batch: int = 8             # decode batch slots
    page_size: int | None = None   # None -> tuned ("flash_decode" key)
    n_pages: int | None = None     # None -> max_batch full sequences + 1
    temperature: float = 0.0
    seed: int = 0
    fuse: bool = False             # cross-op fused kernels (docs/fusion.md)
    buckets: tuple[int, ...] | None = None   # prefill padding lengths
    decode_chunk: int = 8          # decode steps per scheduler visit
    prefill_chunk: int | None = None   # None -> auto-sized; 0 -> whole-
    #                                    prompt joins (legacy behavior)
    spec_decode: int = 0           # draft tokens per verify step (0 = off;
    #                                greedy only, attention-only stacks)
    prefix_cache: bool = False     # radix-tree prefix sharing across
    #                                requests (attention-only stacks with
    #                                chunked prefill; docs/serving.md)
    reuse_hint: float = 0.5        # expected prompt-reuse rate, used by
    #                                choose_page_size to price the
    #                                share-vs-stream page tradeoff when
    #                                the prefix cache is on
    age_limit: int = 8             # admission rounds before a waiting head
    #                                suspends backfill (anti-starvation)
    use_kernel: bool | None = None  # paged attention: None -> TPU only
    interpret: bool | None = None
    # -- lifecycle / robustness (docs/robustness.md) -------------------------
    nan_guard: bool = False        # per-slot non-finite logit detection:
    #                                fails only the poisoned request, at the
    #                                cost of one readback per decode chunk
    preempt: bool = False          # preempt-with-restore when the waiting
    #                                head starves (greedy only; rung 3 of
    #                                the degradation ladder enables it too)
    degrade: bool = False          # graceful-degradation ladder controller
    max_retries: int | None = None  # admission probe failures before a
    #                                 queued request is FAILED (None = never)


def default_buckets(cfg: ModelConfig, max_seq: int) -> tuple[int, ...] | None:
    """Prefill length buckets: powers of two for pure-attention stacks
    (bounded recompilation; right-padding is safe because causal
    attention ignores the tail, and although the pad positions' K/V are
    scattered into the request's reserved pages, they stay masked by the
    length until decode overwrites each slot in order).  Recurrent/SSD
    mixers fold *every* position into their O(1) state, so right-padding
    would corrupt it — those prefill at exact lengths (None), one
    compile per distinct prompt length."""
    if all(p in ("global", "local") for p in cfg.layer_pattern):
        out, b = [], 8
        while b < max_seq:
            out.append(b)
            b *= 2
        out.append(max_seq)
        return tuple(sorted(set(out)))
    return None


class PagedEngine:
    """Request/response serving over the paged cache.

    ``submit()`` enqueues a prompt; ``step()`` runs one scheduler
    iteration and returns the requests that finished; ``generate()`` is
    the batch-convenience wrapper used by the examples and benchmarks.

    A step executes the scheduler's :class:`~repro.serve.scheduler.
    StepPlan` in decode-priority order: admission first (chunk-prefilled
    requests only reserve state; legacy joins prefill whole prompts),
    then ONE jitted decode chunk covering every decode-ready slot, then
    prefill chunks backfilling the leftover token budget, then eviction.
    A decode chunk is up to ``decode_chunk`` steps fused into one
    ``lax.scan`` — per-slot activity is masked inside the scan, so
    chunking changes scheduling granularity, never results.  With
    ``spec_decode=k`` each scan step is a draft-verify call that can
    emit up to ``k+1`` tokens (greedy semantics preserved exactly:
    tokens are accepted only while they match the argmax chain).

    Page reservations are made in full at admission, which is what makes
    block tables stable across a chunk; the tables themselves live on
    device and are updated incrementally at admission/eviction — steady-
    state decode re-uploads nothing.

    Chunked prefill and speculative decode need every mixer to be
    attention (the rglru/ssd state updates are strictly one-token);
    hybrid stacks silently fall back to whole-prompt joins and plain
    decode, keeping one engine API across all architectures.
    """

    def __init__(self, cfg: ModelConfig, params: Any, sc: PagedServeConfig,
                 obs: Obs | None = None):
        if cfg.is_encdec or cfg.prefix_tokens:
            raise NotImplementedError(
                "paged serving covers decoder-only token models")
        self.cfg, self.params, self.sc = cfg, params, sc
        self.obs = obs if obs is not None else Obs()
        has_attn = any(p in ("global", "local") for p in cfg.layer_pattern)
        attn_only = has_attn and all(
            p in ("global", "local") for p in cfg.layer_pattern)
        reuse = (sc.reuse_hint or None) if (sc.prefix_cache
                                            and attn_only) else None
        with self.obs.dram.scope("setup"):
            # page-size / chunk selection resolves the flash-decode
            # schedule once, here — attributed to "setup", not a step
            self.page_size = sc.page_size or (
                KV.choose_page_size(cfg, sc.max_seq, fused=sc.fuse,
                                    reuse_rate=reuse) if has_attn
                else min(sc.max_seq, 128))   # attention-free: pages unused
        self.max_blocks = KV.num_blocks(sc.max_seq, self.page_size)
        n_pages = sc.n_pages or sc.max_batch * self.max_blocks + 1
        self.cache = KV.init_paged_cache(cfg, sc.max_batch, n_pages,
                                         self.page_size)
        self.buckets = (sc.buckets if sc.buckets is not None
                        else default_buckets(cfg, sc.max_seq))

        # resolve the span-based features against the stack's capability
        if sc.prefill_chunk is None:
            self.prefill_chunk = (KV.choose_prefill_chunk(
                cfg, sc.max_seq, self.page_size) if attn_only else 0)
        elif sc.prefill_chunk and attn_only:
            # snap an explicit chunk to a whole number of pages
            self.prefill_chunk = min(
                sc.max_seq,
                KV.num_blocks(sc.prefill_chunk, self.page_size)
                * self.page_size)
        else:
            self.prefill_chunk = 0
        self.spec = int(sc.spec_decode or 0) if attn_only else 0
        if self.spec and sc.temperature > 0:
            raise ValueError(
                "spec_decode is greedy-only: draft acceptance compares "
                "against the argmax chain, which sampling would break")
        if sc.preempt and sc.temperature > 0:
            raise ValueError(
                "preempt is greedy-only: restoring a preempted request "
                "replays its tail deterministically, which sampling "
                "would break (byte-exactness is the correctness bar)")

        # prefix caching needs the span machinery to resume prefill at
        # the matched boundary, so it gates exactly like chunked prefill
        # (attention-only stacks; explicit prefill_chunk=0 turns it off)
        self.prefix_caching = bool(sc.prefix_cache) and attn_only \
            and self.prefill_chunk > 0
        reg = self.obs.registry
        allocator = KV.PageAllocator(n_pages, metrics=reg)
        self.prefix_cache = (KV.PrefixCache(allocator, self.page_size,
                                            metrics=reg)
                             if self.prefix_caching else None)
        self.scheduler = Scheduler(sc.max_batch, self.page_size,
                                   allocator, sc.max_seq,
                                   age_limit=sc.age_limit,
                                   prefix_cache=self.prefix_cache,
                                   metrics=reg,
                                   max_retries=sc.max_retries)
        self.degrade = (DegradationController(reg, tracer=self.obs.tracer)
                        if sc.degrade else None)

        b = sc.max_batch
        self._block_tables = jnp.zeros((b, self.max_blocks), jnp.int32)
        self._lengths = jnp.zeros(b, jnp.int32)    # cached tokens per slot
        self._cur_tok = jnp.zeros(b, jnp.int32)
        self._out_buf = jnp.zeros((b, sc.max_seq), jnp.int32)
        self._hist = jnp.zeros((b, sc.max_seq), jnp.int32)  # prompt+tokens
        self._rng = jax.random.PRNGKey(sc.seed)
        self._step_count = 0
        self._next_rid = 0
        # chaos seam: added to every logit a slot produces (nan_guard
        # reads it; the host mirror skips no-op device updates)
        self._poison = jnp.zeros(b, jnp.float32)
        self._poison_host = np.zeros(b, np.float64)
        self._clock = time.monotonic_ns    # injectable for deterministic tests
        self._sched_steps = 0              # TTL / expiry step counter
        self._joins: dict[int, Any] = {}           # bucket -> jitted join
        self._chunk_fns: dict[int, Any] = {}       # span width -> chunk fn
        self._fork_fn: Any = None                  # jitted CoW page copy
        # every device fn donates the page pools (argument 1): the step
        # updates them in place instead of holding two copies
        self._decode = jax.jit(self._decode_fn,
                               static_argnames=("chunk",),
                               donate_argnums=(1,))
        self._decode_spec = jax.jit(self._decode_spec_fn,
                                    static_argnames=("chunk",),
                                    donate_argnums=(1,))
        self.last_step_tokens = 0                  # benchmark counter
        # registry-backed counters (spec_stats/prefix_stats are views)
        self._m_steps = reg.counter("engine.steps")
        self._m_step_us = reg.histogram("engine.step_us")
        self._m_decode_tokens = reg.counter("engine.decode_tokens")
        self._m_prefill_tokens = reg.counter("engine.prefill_tokens")
        self._m_spec_calls = reg.counter("spec.verify_calls")
        self._m_spec_tokens = reg.counter("spec.tokens")
        self._m_prefix_lookups = reg.counter("prefix_cache.lookups")
        self._m_prefix_hits = reg.counter("prefix_cache.hits")
        self._m_prefix_saved = reg.counter("prefix_cache.tokens_saved")
        self._m_status = {s: reg.counter(f"lifecycle.{s.value}")
                          for s in RequestStatus}
        self._m_nan_trips = reg.counter("lifecycle.nan_guard_trips")

    # -- request API ----------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int, *,
               priority: int = 0, deadline_s: float | None = None,
               ttl_steps: int | None = None) -> int:
        """Enqueue one prompt; returns the request id.

        ``deadline_s`` is a wall budget from now (engine clock);
        ``ttl_steps`` a deterministic budget in scheduler steps —
        whichever passes first expires the request to
        DEADLINE_EXCEEDED with whatever tokens it has.  ``priority``
        orders preemption victims (lower goes first)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        rid = self._next_rid
        self._next_rid += 1
        deadline_ns = (None if deadline_s is None
                       else self._clock() + int(deadline_s * 1e9))
        expire_step = (None if ttl_steps is None
                       else self._sched_steps + int(ttl_steps))
        self.scheduler.submit(Request(rid, prompt, int(max_new_tokens),
                                      priority=int(priority),
                                      deadline_ns=deadline_ns,
                                      expire_step=expire_step))
        return rid

    def cancel(self, rid: int) -> bool:
        """Cooperative cancel: the request finishes TRUNCATED (partial
        output) at the next step boundary.  False if rid is unknown."""
        return self.scheduler.cancel(rid)

    def preempt(self, rid: int) -> bool:
        """Force-preempt a running request (the pressure path calls
        this automatically; exposed for tests and the chaos harness).
        Its tokens so far are preserved and it will be re-admitted —
        through the prefix cache when one is attached — to finish with
        byte-exact output and status PREEMPTED_RETRIED."""
        for slot, r in self.scheduler.running.items():
            if r.rid == rid:
                self._preempt_slot(slot, self.obs.tracer)
                return True
        return False

    def inject_logit_fault(self, rid: int,
                           value: float = float("nan")) -> None:
        """Chaos seam: add ``value`` to every logit ``rid``'s slot
        produces from now on.  With ``nan_guard`` on, a non-finite
        ``value`` fails exactly this request and no other."""
        if not self.sc.nan_guard:
            raise RuntimeError(
                "inject_logit_fault needs PagedServeConfig(nan_guard="
                "True): without the guard a poisoned slot would decode "
                "garbage forever instead of failing fast")
        for slot, r in self.scheduler.running.items():
            if r.rid == rid:
                self._poison = self._poison.at[slot].set(value)
                self._poison_host[slot] = value
                return
        raise KeyError(f"rid {rid} is not running")

    def shutdown(self) -> list[Request]:
        """Cancel all in-flight work and drain to terminal statuses.

        Frees every request-owned page (prefix-tree references are
        dropped too, so the pool returns to empty) — the Ctrl-C path in
        ``launch/serve``.  Returns the requests finished by the drain.
        """
        for r in list(self.scheduler.waiting):
            r.cancelled = True
        for r in self.scheduler.running.values():
            r.cancelled = True
        out = []
        while self.has_work:
            out.extend(self.step())
        if self.prefix_cache is not None:
            while len(self.prefix_cache):
                if not self.prefix_cache.evict(len(self.prefix_cache)):
                    break
        return out

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def spec_stats(self) -> dict:
        """Draft-verify counters: total verify calls, tokens they
        emitted, and the mean accepted span (1.0 = plain decode).
        A thin view over the metrics registry (``spec.*``)."""
        calls, toks = self._m_spec_calls.value, self._m_spec_tokens.value
        return {"verify_calls": calls, "tokens": toks,
                "mean_accepted": toks / calls if calls else 0.0}

    def prefix_stats(self) -> dict:
        """Prefix-cache counters: admissions probed, admissions that
        matched, prompt tokens served from shared pages instead of
        being re-prefilled, and the tree's current page count.
        A thin view over the metrics registry (``prefix_cache.*``)."""
        lookups = self._m_prefix_lookups.value
        hits = self._m_prefix_hits.value
        return {"lookups": lookups, "hits": hits,
                "hit_rate": hits / lookups if lookups else 0.0,
                "tokens_saved": self._m_prefix_saved.value,
                "cached_pages": (len(self.prefix_cache)
                                 if self.prefix_cache is not None else 0)}

    def lifecycle_stats(self) -> dict:
        """Terminal-status counts plus the pressure/fault counters — a
        thin view over the ``lifecycle.*`` / ``sched.*`` registry
        entries (docs/robustness.md)."""
        reg = self.obs.registry
        out = {s.value: self._m_status[s].value for s in RequestStatus}
        out["preemptions"] = reg.counter("sched.preemptions").value
        out["admit_rollbacks"] = reg.counter("sched.admit_rollbacks").value
        out["nan_guard_trips"] = self._m_nan_trips.value
        if self.degrade is not None:
            out["degrade_level"] = self.degrade.level
            out["degrade_escalations"] = \
                reg.counter("degrade.escalations").value
        return out

    def step(self) -> list[Request]:
        """One continuous-batching iteration; returns finished requests
        (with ``.output`` filled).

        With a tracer attached, the step and its phases (host prep,
        ``plan_step``, device dispatches, readback) emit Chrome-trace
        spans, and each device dispatch is fenced with
        ``block_until_ready`` so span durations mean device time.  With
        no tracer, ``sp`` is the shared no-op span and NO fence runs —
        the hot path stays async (guarded by ``tests/test_obs.py``).
        """
        t0 = time.perf_counter_ns()
        tr = self.obs.tracer
        sp = tr.span if tr is not None else null_span
        self.last_step_tokens = 0
        step_rids: set[int] = set()
        with sp("step", cat="engine", args={"step": self._step_count}):
            finished = self._step_inner(sp, tr, step_rids)
        self._m_steps.inc()
        self._m_step_us.observe((time.perf_counter_ns() - t0) / 1000.0)
        self.obs.dram.end_step(sorted(step_rids))
        return finished

    def _step_inner(self, sp, tr, step_rids: set[int]) -> list[Request]:
        self._sched_steps += 1
        now = self._clock()
        finished: list[Request] = []
        # lifecycle sweep: queued deadline/TTL expiry and cancellation
        # drain before admission so a dead request never takes pages
        for req in self.scheduler.expire(now, self._sched_steps):
            self._finish(req, finished)
        # degradation ladder: one control tick per step, applied to THIS
        # step's spec/chunk/preemption decisions
        decode_chunk = self.sc.decode_chunk
        use_spec = self.spec
        allow_preempt = self.sc.preempt
        force_preempt = False
        if self.degrade is not None:
            self.degrade.update()
            if self.degrade.spec_disabled:
                use_spec = 0
            if self.degrade.shrink_chunk:
                decode_chunk = max(1, decode_chunk // 2)
            if self.degrade.allow_preempt and self.sc.temperature <= 0:
                allow_preempt = force_preempt = True
        if allow_preempt:
            victim = self.scheduler.preempt_candidate(force=force_preempt)
            if victim is not None:
                with sp("preempt", cat="sched"):
                    self._preempt_slot(victim, tr)
        with sp("host_prep", cat="engine"):
            for req in self.scheduler.admit():
                step_rids.add(req.rid)
                row = np.full(self.max_blocks, KV.SCRATCH_PAGE, np.int32)
                row[:len(req.pages)] = req.pages
                self._block_tables = self._block_tables.at[req.slot].set(
                    jnp.asarray(row))
                if self.prefix_caching:
                    self._m_prefix_lookups.inc()
                if req.cached_tokens:
                    # prefix hit: shared pages already hold the matched
                    # K/V; prefill resumes at the boundary through the
                    # chunk path, so only O(new tokens) run the model
                    self._m_prefix_hits.inc()
                    self._m_prefix_saved.inc(req.prefilled)
                    if req.cow_fork is not None:
                        src, dst = req.cow_fork
                        with sp("dispatch.fork", cat="device"), \
                                self.obs.dram.scope("cow_fork"):
                            self.cache = self._get_fork_fn()(
                                self.cache, jnp.int32(src), jnp.int32(dst))
                    # the spec-decode draft history must cover the cached
                    # prefix the chunk path will never feed
                    hist_row = np.zeros(self.sc.max_seq, np.int32)
                    L = min(req.prompt_len, self.sc.max_seq)
                    hist_row[:L] = req.prompt[:L]
                    self._hist = self._hist.at[req.slot].set(
                        jnp.asarray(hist_row))
                    # a tail that fits one chunk prefills inline, exactly
                    # where a miss would run its join — the hit request is
                    # decode-ready this very step instead of waiting a
                    # scheduling round (longer tails go through plan_step)
                    if req.prompt_len - req.prefilled <= self.prefill_chunk:
                        with sp("dispatch.prefill", cat="device"):
                            self._prefill_one_chunk(req)
                            if tr is not None:
                                jax.block_until_ready(self._cur_tok)
                    continue
                if (not self.prefill_chunk
                        or req.prompt_len <= self.prefill_chunk):
                    # whole-prompt join: chunking a prompt that fits in ONE
                    # chunk would pay the fixed-span chunk call (span =
                    # prefill_chunk, padded) where the bucketed join prices
                    # the prefill at the prompt's own pow2 bucket — chunked
                    # prefill only earns its keep on multi-chunk prompts
                    with sp("dispatch.join", cat="device"):
                        self._join(req)
                        if tr is not None:
                            jax.block_until_ready(self._cur_tok)
                    req.prefilled = req.prompt_len
                    if not req.failed:      # poisoned pages never cached
                        self.scheduler.register_prefix(req)
                        self.last_step_tokens += 1     # the prefill token
        for req in self.scheduler.take_rejected():
            self._finish(req, finished)
        with sp("plan_step", cat="sched"):
            plan = self.scheduler.plan_step(decode_chunk,
                                            self.prefill_chunk or 1)
        # plan entries are validated and deduped before dispatch: a
        # duplicated decode slot would double-count ``generated`` and a
        # stale/dropped entry is simply skipped (the next plan recomputes
        # from scheduler state, so nothing is lost) — chaos-harness seam
        running = self.scheduler.running
        decode_rs: list[Request] = []
        seen: set[int] = set()
        for s in plan.decode_slots:
            r = running.get(s)
            if r is None or s in seen or not r.decode_ready \
                    or r.cancelled or r.expired(now, self._sched_steps):
                continue            # dead slots stop decoding immediately
            seen.add(s)
            decode_rs.append(r)
        step_rids.update(r.rid for r in decode_rs)
        # decode first: decode-ready slots are never stalled by prefill
        if decode_rs:
            with sp("dispatch.decode", cat="device"):
                self._decode_once(decode_rs, decode_chunk, use_spec)
                if tr is not None:
                    jax.block_until_ready(self._out_buf)
        for slot in plan.prefill_slots:
            r = running.get(slot)
            if r is None or r.prefill_done or r.cancelled \
                    or r.expired(now, self._sched_steps):
                continue
            step_rids.add(r.rid)
            with sp("dispatch.prefill", cat="device"):
                self._prefill_one_chunk(r)
                if tr is not None:
                    jax.block_until_ready(self._cur_tok)
        done_slots = [s for s, r in self.scheduler.running.items()
                      if r.done or r.failed or r.cancelled
                      or r.expired(now, self._sched_steps)]
        if done_slots:
            # one host transfer covers every request finishing this step;
            # device state is NOT reset — the decode fns mask unoccupied
            # slots to scratch, and admission rewrites the row anyway
            with sp("readback", cat="engine"):
                host_out = np.asarray(self._out_buf)
            for slot in done_slots:
                req = self.scheduler.running[slot]
                tail = host_out[slot, :req.generated].copy()
                req.output = (tail if req.prior_tokens is None else
                              np.concatenate([req.prior_tokens, tail]))
                self._clear_poison(slot)
                self._finish(self.scheduler.evict(slot), finished)
        return finished

    def _finish(self, req: Request, out: list[Request]) -> None:
        """Assign the terminal status (docs/robustness.md), count it,
        and hand the request back.  Precedence: a tripped fault always
        FAILs; a request that finished its budget is OK (or
        PREEMPTED_RETRIED) even if a cancel/deadline raced the last
        step; otherwise cancel beats deadline."""
        if req.output is None:     # never ran: expired/rejected in queue
            req.output = (req.prior_tokens if req.prior_tokens is not None
                          else np.zeros(0, np.int32))
        if req.failed:
            status = RequestStatus.FAILED
        elif req.done:
            status = (RequestStatus.PREEMPTED_RETRIED if req.preempt_count
                      else RequestStatus.OK)
        elif req.cancelled:
            status = RequestStatus.TRUNCATED
        else:
            status = RequestStatus.DEADLINE_EXCEEDED
        req.status = status
        self._m_status[status].inc()
        out.append(req)

    def _preempt_slot(self, slot: int, tr=None) -> None:
        """Preempt one running slot: read back its sampled tokens (the
        rare sync preemption pays), hand them to the scheduler — which
        registers complete pages in the prefix tree and requeues the
        replacement — and clear any injected poison with the slot."""
        req = self.scheduler.running[slot]
        host_out = np.asarray(self._out_buf)
        emitted = host_out[slot, :req.generated].copy()
        new = self.scheduler.preempt(slot, emitted)
        self._clear_poison(slot)
        if tr is not None:
            tr.instant("preempt", cat="lifecycle",
                       args={"rid": req.rid, "slot": slot,
                             "kept_tokens": int(len(new.prior_tokens))})

    def _clear_poison(self, slot: int) -> None:
        if self._poison_host[slot]:
            self._poison = self._poison.at[slot].set(0.0)
            self._poison_host[slot] = 0.0

    def generate(self, prompts, n_tokens: int, *, priorities=None,
                 deadline_s: float | None = None,
                 ttl_steps: int | None = None,
                 return_requests: bool = False):
        """Batch convenience: submit all, run to completion, return
        (B, n_tokens) in submission order.  ``prompts`` may be a 2-D
        array or a list of 1-D arrays (ragged lengths welcome).

        With ``return_requests=True`` the finished
        :class:`~repro.serve.scheduler.Request` objects come back
        instead (``.output`` + terminal ``.status``, submission order)
        — the only safe form when deadlines/TTLs/faults can truncate
        outputs to ragged lengths."""
        pr = (list(priorities) if priorities is not None
              else [0] * len(prompts))
        rids = [self.submit(p, n_tokens, priority=q, deadline_s=deadline_s,
                            ttl_steps=ttl_steps)
                for p, q in zip(prompts, pr)]
        done: dict[int, Request] = {}
        while self.has_work:
            for req in self.step():
                done[req.rid] = req
        if return_requests:
            return [done[r] for r in rids]
        return np.stack([done[r].output for r in rids])

    # -- internals ------------------------------------------------------------

    def _bucket(self, length: int) -> int:
        if self.buckets is None:
            return length
        for b in self.buckets:
            if b >= length:
                return b
        return length

    def _next_key(self) -> jax.Array:
        self._step_count += 1
        return jax.random.fold_in(self._rng, self._step_count)

    def _join(self, req: Request) -> None:
        """Prefill an admitted request at its bucketed true length,
        scatter its KV into the reserved pages, sample its first token —
        all in one jitted call per bucket length."""
        slot, L = req.slot, req.prompt_len
        bucket = self._bucket(L)
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :L] = req.prompt
        nb = KV.num_blocks(bucket, self.page_size)
        pages = np.full(nb, KV.SCRATCH_PAGE, np.int32)
        pages[:min(nb, len(req.pages))] = req.pages[:nb]
        # the scope tag carries the jit variant (one trace per bucket),
        # so resolution bytes x execution count attributes correctly
        with self.obs.dram.scope(f"join[{bucket}]"):
            res = self._get_join(bucket)(
                self.params, self.cache, jnp.asarray(prompt),
                jnp.int32(L), jnp.int32(slot), jnp.asarray(pages),
                self._lengths, self._cur_tok, self._out_buf, self._hist,
                self._next_key(), self._poison)
        if self.sc.nan_guard:
            (self.cache, self._lengths, self._cur_tok, self._out_buf,
             self._hist, bad) = res
            self._m_prefill_tokens.inc(L)
            if bool(np.asarray(bad)):
                req.failed = True
                self._m_nan_trips.inc()
                return
        else:
            (self.cache, self._lengths, self._cur_tok, self._out_buf,
             self._hist) = res
            self._m_prefill_tokens.inc(L)
        req.generated = 1

    def _get_join(self, bucket: int):
        if bucket not in self._joins:
            cfg, sc = self.cfg, self.sc

            def join(params, cache, prompt, true_len, slot, pages,
                     lengths, cur_tok, out_buf, hist, key, poison):
                with ops.fused_ops(sc.fuse):
                    logits, dense = T.prefill(cfg, params, prompt,
                                              max_seq=bucket, full_kv=True,
                                              logits_at=true_len - 1)
                cache = KV.write_prefill(cfg, cache, dense, slot, pages,
                                         self.page_size)
                if sc.nan_guard:
                    logits = logits + poison[slot]
                tok = sample_tokens(cfg, logits, sc.temperature, key)[0]
                hist = jax.lax.dynamic_update_slice(
                    hist, prompt, (slot, jnp.int32(0)))
                hist = hist.at[slot, true_len].set(tok, mode="drop")
                out = (cache, lengths.at[slot].set(true_len),
                       cur_tok.at[slot].set(tok),
                       out_buf.at[slot, 0].set(tok), hist)
                if sc.nan_guard:
                    bad = ~jnp.all(jnp.isfinite(logits[..., :cfg.vocab]))
                    return out + (bad,)
                return out

            self._joins[bucket] = jax.jit(join, donate_argnums=(1,))
        return self._joins[bucket]

    # -- prefix cache ---------------------------------------------------------

    def _get_fork_fn(self):
        """Jitted copy-on-write page copy: duplicate page ``src`` into
        ``dst`` across every attention layer's pools (prefix caching is
        gated to attention-only stacks, so every group pages)."""
        if self._fork_fn is None:
            def fork(cache, src, dst):
                def cp(pc, stacked):
                    if stacked:     # (n_groups, n_pages, hkv, page, hd)
                        return {k: pc[k].at[:, dst].set(pc[k][:, src])
                                for k in ("k_pages", "v_pages")}
                    return {k: pc[k].at[dst].set(pc[k][src])
                            for k in ("k_pages", "v_pages")}
                return {"layers": [cp(pc, True) for pc in cache["layers"]],
                        "tail": [cp(pc, False) for pc in cache["tail"]]}

            # donate the pools: the fork updates one page slice in
            # place instead of copying the whole cache
            self._fork_fn = jax.jit(fork, donate_argnums=(0,))
        return self._fork_fn

    # -- chunked prefill ------------------------------------------------------

    def _prefill_one_chunk(self, req: Request) -> None:
        """Advance one request's prefill by one chunk.

        The chunk runs as a batch-1 multi-token ``decode_step`` over the
        paged cache (``make_paged_span_step``): K/V for all chunk
        positions scatter into the reserved pages and one q-span
        flash-decode call attends each position to everything before it
        — identical math to whole-prompt prefill, paid ``prefill_chunk``
        tokens at a time.  The final chunk samples the first token
        exactly as a join would.
        """
        start, L = req.prefilled, req.prompt_len
        c_real = min(self.prefill_chunk, L - start)
        # span width = pow2 bucket of the real remainder, not the full
        # prefill_chunk: the final partial chunk of any prompt — and the
        # short unshared tail after a prefix-cache hit — pays for the
        # tokens it actually carries
        C = 1
        while C < c_real:
            C *= 2
        final = start + c_real >= L
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :c_real] = req.prompt[start:start + c_real]
        take_at = (L - 1 - start) if final else -1
        with self.obs.dram.scope(f"prefill[{C}]"):
            res = self._get_chunk_fn(C)(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.int32(start), self._block_tables,
                self._lengths, jnp.int32(req.slot),
                jnp.int32(start + c_real), jnp.int32(take_at),
                self._cur_tok, self._out_buf, self._hist, self._next_key(),
                self._poison)
        if self.sc.nan_guard:
            (self.cache, self._lengths, self._cur_tok, self._out_buf,
             self._hist, bad) = res
            self._m_prefill_tokens.inc(c_real)
            req.prefilled = start + c_real
            if bool(np.asarray(bad)):   # guard sync: one scalar per chunk
                req.failed = True
                self._m_nan_trips.inc()
                return
        else:
            (self.cache, self._lengths, self._cur_tok, self._out_buf,
             self._hist) = res
            self._m_prefill_tokens.inc(c_real)
            req.prefilled = start + c_real
        if final:
            req.generated = 1
            self.scheduler.register_prefix(req)
            self.last_step_tokens += 1             # the prefill token

    def _get_chunk_fn(self, C: int):
        if C not in self._chunk_fns:
            cfg, sc = self.cfg, self.sc

            def chunk(params, cache, tokens, start, block_tables, lengths,
                      slot, new_len, take_at, cur_tok, out_buf, hist, key,
                      poison):
                bt_row = jax.lax.dynamic_slice_in_dim(block_tables,
                                                      slot, 1)
                with ops.fused_ops(sc.fuse):
                    attn = KV.make_paged_span_step(
                        cfg, bt_row, self.page_size, sc.max_seq,
                        sc.use_kernel, sc.interpret)
                    logits, cache = T.decode_step(
                        cfg, params, tokens, cache,
                        jnp.full((1,), start, jnp.int32), attn_step=attn)
                if sc.nan_guard:
                    logits = logits + poison[slot]
                lengths = lengths.at[slot].set(new_len)
                idx = start + jnp.arange(C)
                hist = hist.at[slot, jnp.where(idx < sc.max_seq, idx,
                                               sc.max_seq)].set(
                    tokens[0], mode="drop")
                # final chunk: the prompt's last logits seed generation
                tok = sample_tokens(cfg,
                                    logits[:, jnp.clip(take_at, 0, C - 1)],
                                    sc.temperature, key)[0]
                is_final = take_at >= 0
                cur_tok = cur_tok.at[slot].set(
                    jnp.where(is_final, tok, cur_tok[slot]))
                out_buf = out_buf.at[slot, 0].set(
                    jnp.where(is_final, tok, out_buf[slot, 0]))
                hist = hist.at[slot, new_len].set(
                    jnp.where(is_final, tok, hist[slot, new_len]),
                    mode="drop")
                if sc.nan_guard:
                    bad = ~jnp.all(jnp.isfinite(logits[..., :cfg.vocab]))
                    return cache, lengths, cur_tok, out_buf, hist, bad
                return cache, lengths, cur_tok, out_buf, hist

            self._chunk_fns[C] = jax.jit(chunk, donate_argnums=(1,))
        return self._chunk_fns[C]

    # -- decode ---------------------------------------------------------------

    def _decode_fn(self, params, cache, cur_tok, block_tables, lengths,
                   occupied, remaining, out_idx, out_buf, key, poison, *,
                   chunk: int):
        """``chunk`` fused decode steps (one device dispatch).

        ``remaining[b]`` is the slot's token budget at chunk start; a
        step is active for slot b while ``occupied[b]`` and its emitted
        count is under budget.  Inactive slots freeze their length,
        token and output row, and their block-table rows / lengths are
        masked to scratch/0 *here, inside the jit* — so eviction never
        has to reset device state (a stale row is harmless) and freeing
        a request costs zero device dispatches.

        With ``nan_guard`` on, ``poison`` (the chaos seam) is added to
        the logits and any slot producing a non-finite logit is frozen
        for the rest of the chunk — its sampled-so-far output stays
        intact — and reported in a per-slot ``(emitted, bad)`` stats
        array the host reads back once per chunk.  Guard off: no stats
        output, no readback, the hot path stays async."""
        cfg = self.cfg
        guard = self.sc.nan_guard
        lengths_in = lengths
        block_tables = jnp.where(occupied[:, None], block_tables,
                                 KV.SCRATCH_PAGE)
        lengths = jnp.where(occupied, lengths, 0)
        attn = KV.make_paged_attn_step(cfg, block_tables, self.page_size,
                                       self.sc.use_kernel,
                                       self.sc.interpret,
                                       fused=self.sc.fuse)
        rows = jnp.arange(cur_tok.shape[0])

        def body(carry, i):
            cur_tok, cache, lengths, out_idx, out_buf, emitted, bad = carry
            active = occupied & (emitted < remaining) & ~bad
            logits, cache = T.decode_step(cfg, params, cur_tok, cache,
                                          lengths, attn_step=attn)
            if guard:
                logits = logits + poison[:, None]
                finite = jnp.all(jnp.isfinite(logits[:, :cfg.vocab]),
                                 axis=-1)
                bad = bad | (active & ~finite)
                active = active & finite
            tok = sample_tokens(cfg, logits, self.sc.temperature,
                                jax.random.fold_in(key, i))
            tok = jnp.where(active, tok, cur_tok)
            keep = out_buf[rows, out_idx]
            out_buf = out_buf.at[rows, out_idx].set(
                jnp.where(active, tok, keep))
            out_idx = jnp.where(active, out_idx + 1, out_idx)
            lengths = jnp.where(active, lengths + 1, lengths)
            emitted = emitted + active.astype(jnp.int32)
            return (tok, cache, lengths, out_idx, out_buf, emitted,
                    bad), None

        with ops.fused_ops(self.sc.fuse):
            carry = (cur_tok, cache, lengths, out_idx, out_buf,
                     jnp.zeros_like(remaining),
                     jnp.zeros(cur_tok.shape[0], bool))
            (cur_tok, cache, lengths, _, out_buf, emitted,
             bad), _ = jax.lax.scan(body, carry, jnp.arange(chunk))
        # restore masked-out lengths (a still-prefilling slot keeps its)
        out = (cur_tok, cache,
               jnp.where(occupied, lengths, lengths_in), out_buf)
        if guard:
            return out + (jnp.stack([emitted, bad.astype(jnp.int32)]),)
        return out

    def _decode_spec_fn(self, params, cache, cur_tok, block_tables,
                        lengths, occupied, remaining, out_idx, out_buf,
                        hist, poison, *, chunk: int):
        """``chunk`` draft-verify steps (one device dispatch).

        Each step drafts ``k = spec_decode`` tokens by n-gram lookup
        over the slot's own history (prompt-lookup decoding: the latest
        earlier occurrence of the trailing 2-gram proposes its
        continuation; no match drafts -1, which can never be accepted),
        scores current + drafts in ONE span decode_step, and accepts the
        longest prefix matching the greedy argmax chain — so emitted
        tokens are bit-identical to plain greedy decode, just cheaper
        per token.  Draft rows past the accepted prefix leave garbage
        K/V above the new length; the next span overwrites every such
        position before the length mask can expose it.

        ``remaining`` bounds *emitted tokens*, not steps; a step that
        would overshoot the budget truncates its accepted span.  Returns
        per-slot emitted counts and the active-call total for the
        acceptance stats.
        """
        cfg = self.cfg
        k = self.spec
        span = k + 1
        max_seq = self.sc.max_seq
        b = cur_tok.shape[0]
        rows = jnp.arange(b)
        # inactive slots (free, evicted-stale, or still prefilling) are
        # masked to scratch here so eviction never resets device state
        lengths_in = lengths
        block_tables = jnp.where(occupied[:, None], block_tables,
                                 KV.SCRATCH_PAGE)
        lengths = jnp.where(occupied, lengths, 0)
        attn = KV.make_paged_span_step(cfg, block_tables, self.page_size,
                                       max_seq, self.sc.use_kernel,
                                       self.sc.interpret)

        def drafts_for(hist, lengths):
            hl = lengths + 1                     # tokens in hist per slot
            last = hist[rows, jnp.clip(hl - 1, 0, max_seq - 1)]
            prev = hist[rows, jnp.clip(hl - 2, 0, max_seq - 1)]
            m2 = ((hist[:, 1:] == last[:, None])
                  & (hist[:, :-1] == prev[:, None]))
            p = jnp.arange(1, max_seq)
            m2 &= p[None, :] < (hl - 1)[:, None]     # strictly earlier
            j = jnp.max(jnp.where(m2, p[None, :], -1), axis=1)
            gidx = j[:, None] + 1 + jnp.arange(k)[None, :]
            valid = (j >= 0)[:, None] & (gidx < hl[:, None])
            d = hist[rows[:, None], jnp.clip(gidx, 0, max_seq - 1)]
            return jnp.where(valid, d, -1)

        guard = self.sc.nan_guard

        def body(carry, i):
            (cur_tok, cache, lengths, out_idx, out_buf, hist, emitted,
             calls, bad) = carry
            active = occupied & (emitted < remaining) & ~bad
            d = drafts_for(hist, lengths)
            feed = jnp.concatenate(
                [cur_tok[:, None], jnp.maximum(d, 0)], axis=1)
            logits, cache = T.decode_step(cfg, params, feed, cache,
                                          lengths, attn_step=attn)
            if guard:
                # any non-finite logit in the slot's span freezes the
                # whole verify step for that slot (emits nothing): a
                # poisoned draft chain must never be accepted
                logits = logits + poison[:, None, None]
                finite = jnp.all(jnp.isfinite(logits[..., :cfg.vocab]),
                                 axis=(1, 2))
                bad = bad | (active & ~finite)
                active = active & finite
            a = jnp.argmax(logits[..., :cfg.vocab],
                           axis=-1).astype(jnp.int32)         # (B, span)
            prefix = jnp.cumprod((d == a[:, :k]).astype(jnp.int32), axis=1)
            m = jnp.sum(prefix, axis=1)          # accepted drafts in [0, k]
            n_emit = jnp.where(active,
                               jnp.minimum(m + 1, remaining - emitted), 0)
            t = jnp.arange(span)
            take = t[None, :] < n_emit[:, None]
            oidx = jnp.where(take, out_idx[:, None] + t[None, :], max_seq)
            out_buf = out_buf.at[rows[:, None], oidx].set(a, mode="drop")
            hidx = jnp.where(take, (lengths + 1)[:, None] + t[None, :],
                             max_seq)
            hist = hist.at[rows[:, None], hidx].set(a, mode="drop")
            new_cur = a[rows, jnp.clip(n_emit - 1, 0, k)]
            cur_tok = jnp.where(active, new_cur, cur_tok)
            return (cur_tok, cache, lengths + n_emit, out_idx + n_emit,
                    out_buf, hist, emitted + n_emit,
                    calls + jnp.sum(active.astype(jnp.int32)), bad), None

        with ops.fused_ops(self.sc.fuse):
            carry = (cur_tok, cache, lengths, out_idx, out_buf, hist,
                     jnp.zeros(b, jnp.int32), jnp.int32(0),
                     jnp.zeros(b, bool))
            (cur_tok, cache, lengths, _, out_buf, hist, emitted,
             calls, bad), _ = jax.lax.scan(body, carry, jnp.arange(chunk))
        out = (cur_tok, cache, jnp.where(occupied, lengths, lengths_in),
               out_buf, hist, emitted, calls)
        if guard:
            return out + (bad.astype(jnp.int32),)
        return out

    def _decode_once(self, running: list[Request],
                     decode_chunk: int | None = None,
                     use_spec: int | None = None) -> None:
        decode_chunk = (self.sc.decode_chunk if decode_chunk is None
                        else decode_chunk)
        use_spec = self.spec if use_spec is None else use_spec
        guard = self.sc.nan_guard
        occupied = np.zeros(self.sc.max_batch, bool)
        remaining = np.zeros(self.sc.max_batch, np.int32)
        out_idx = np.zeros(self.sc.max_batch, np.int32)
        for r in running:
            occupied[r.slot] = True
            remaining[r.slot] = r.max_new_tokens - r.generated
            out_idx[r.slot] = r.generated
        # chunk is a static jit arg: snap the tail to the next power of
        # two so the decode scan compiles O(log decode_chunk) times, not
        # once per distinct remaining-budget value (masking keeps any
        # over-length steps result-invariant)
        chunk = 1 << (int(remaining.max()) - 1).bit_length()
        chunk = int(min(decode_chunk, chunk))
        if use_spec:
            # each verify call emits 1..spec+1 tokens; size the scan for
            # the token budget at full acceptance — zero acceptance just
            # spreads a slot's budget over more scheduler visits instead
            # of burning idle full-span model calls here
            iters = -(-chunk // (self.spec + 1))
            with self.obs.dram.scope(f"spec_decode[{iters}]"):
                (self._cur_tok, self.cache, self._lengths, self._out_buf,
                 self._hist, emitted, calls, *badv) = self._decode_spec(
                    self.params, self.cache, self._cur_tok,
                    self._block_tables, self._lengths,
                    jnp.asarray(occupied), jnp.asarray(remaining),
                    jnp.asarray(out_idx), self._out_buf, self._hist,
                    self._poison, chunk=iters)
            # the one per-step readback: how far each slot actually got
            emitted = np.asarray(emitted)
            bad = np.asarray(badv[0]).astype(bool) if guard else None
            for r in running:
                n = int(emitted[r.slot])
                r.generated += n
                self.last_step_tokens += n
                if guard and bad[r.slot]:
                    r.failed = True
                    self._m_nan_trips.inc()
            self._m_spec_calls.inc(int(calls))
            self._m_spec_tokens.inc(int(emitted.sum()))
            self._m_decode_tokens.inc(int(emitted.sum()))
            return
        with self.obs.dram.scope(f"decode[{chunk}]"):
            res = self._decode(
                self.params, self.cache, self._cur_tok, self._block_tables,
                self._lengths, jnp.asarray(occupied),
                jnp.asarray(remaining), jnp.asarray(out_idx),
                self._out_buf, self._next_key(), self._poison, chunk=chunk)
        if guard:
            (self._cur_tok, self.cache, self._lengths, self._out_buf,
             stats) = res
            stats = np.asarray(stats)   # the guard's per-chunk readback
            emitted, bad = stats[0], stats[1].astype(bool)
            for r in running:
                n = int(emitted[r.slot])
                r.generated += n
                self.last_step_tokens += n
                self._m_decode_tokens.inc(n)
                if bad[r.slot]:
                    r.failed = True
                    self._m_nan_trips.inc()
            return
        (self._cur_tok, self.cache, self._lengths, self._out_buf) = res
        for r in running:
            steps = min(chunk, r.max_new_tokens - r.generated)
            r.generated += steps
            self.last_step_tokens += steps
            self._m_decode_tokens.inc(steps)
