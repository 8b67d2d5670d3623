"""Serving benchmark: paged continuous batching vs the static-batch
baseline on a mixed-length workload.

Workload: ``--requests`` prompts with lengths in [prompt_len/2,
prompt_len] and *heavy-tailed* generation budgets (75% short answers,
25% long ones up to ``--gen``) — the output-length skew real serving
traffic has.  The static engine processes requests in submission-order
batches, left-padding prompts to the batch max and decoding every batch
member to the batch's largest budget (tokens past a request's own budget
are discarded — the lock-step waste continuous batching removes).  The
paged engine streams the same requests through its decode slots,
admitting by free-page budget and evicting the moment a request
finishes.

Every run — ``--smoke`` included — uses a serving-scale reduced config
(d_model 256): on the tiny test config per-step compute is smaller than
a host dispatch and the comparison would measure dispatch counts, not
scheduling.  ``--smoke`` only shrinks the *workload* to CI size.

``--prefix-cache`` adds a *prompt-reuse* section on its own zipfian
workload (a small pool of shared prefixes with zipf(1.2) popularity,
unique ragged tails): the prefix-sharing paged engine against an
otherwise-identical engine with sharing off, pinned to the same page
size.  Its records carry ``cache_hit_rate`` and
``admitted_tokens_saved`` — and are *not* comparable to the
``serve_static`` baseline, which runs the mixed-length workload.

Reports decode tokens/sec (useful tokens only) and p50/p95/p99
per-token step latency.  CSV contract: ``name,us_per_call,derived``.
Every record embeds the engine's metrics snapshot (registry counters +
the modeled-vs-measured DRAM report) under a ``metrics`` field;
``check_bench.py`` ignores fields it doesn't guard, so snapshot schema
growth never forces an ``--update``.  ``--trace`` / ``--metrics-out`` /
``--miss-log`` wire a full :class:`repro.obs.Obs` into the measured
paged engine; ``--trace DIR`` records the run in a JAX profiler session
(a Perfetto file with host spans and device lanes on one clock; the
spans never wait for the device, see docs/observability.md).

    PYTHONPATH=src python -m benchmarks.serve_bench --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, latency_summary, write_json
from repro.configs import get_reduced
from repro.models import transformer as T
from repro.obs import Obs
from repro.serve.engine import (DecodeEngine, PagedEngine, PagedServeConfig,
                                ServeConfig)


def make_workload(cfg, n_requests: int, prompt_len: int, gen: int,
                  seed: int = 0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(max(1, prompt_len // 2), prompt_len + 1,
                        n_requests)
    # heavy-tailed budgets: mostly short answers, occasional stragglers
    short = rng.integers(2, max(3, gen // 8), n_requests)
    long = rng.integers(max(2, gen // 2), gen + 1, n_requests)
    gens = np.where(rng.random(n_requests) < 0.75, short, long)
    prompts = [rng.integers(0, cfg.vocab, (int(L),), dtype=np.int32)
               for L in lens]
    return prompts, [int(g) for g in gens]


def make_reuse_workload(cfg, n_requests: int, prompt_len: int, gen: int,
                        max_seq: int, seed: int = 1):
    """Zipfian prompt-reuse workload for the prefix-cache section.

    A small pool of shared prefixes (3/4 of ``prompt_len`` tokens) is
    drawn once; each request picks a prefix with zipf(1.2) popularity —
    a few prompts dominate, like templated system prompts do — and
    appends a unique ragged tail (possibly empty, which exercises the
    exact-full-match CoW fork).  Budgets are heavy-tailed like
    :func:`make_workload`, capped so prompt + generation fits
    ``max_seq``.
    """
    rng = np.random.default_rng(seed)
    pre_len = max(1, (3 * prompt_len) // 4)
    pool = [rng.integers(0, cfg.vocab, (pre_len,), dtype=np.int32)
            for _ in range(8)]
    ranks = np.minimum(rng.zipf(1.2, n_requests) - 1, len(pool) - 1)
    short = rng.integers(2, max(3, gen // 8), n_requests)
    long = rng.integers(max(2, gen // 2), gen + 1, n_requests)
    gens = np.where(rng.random(n_requests) < 0.75, short, long)
    prompts, capped = [], []
    for r, g in zip(ranks, gens):
        tail_len = int(rng.integers(0, prompt_len - pre_len + 1))
        tail = rng.integers(0, cfg.vocab, (tail_len,), dtype=np.int32)
        prompt = np.concatenate([pool[int(r)], tail])
        prompts.append(prompt)
        capped.append(int(max(2, min(int(g), max_seq - len(prompt)))))
    return prompts, capped


def run_static(engine, prompts, gens, max_batch: int):
    """Submission-order batches, padded prompts, lock-step decode."""
    useful = 0
    step_times = []
    t0 = time.perf_counter()
    for i in range(0, len(prompts), max_batch):
        chunk_p = prompts[i:i + max_batch]
        chunk_g = gens[i:i + max_batch]
        width = max(p.shape[0] for p in chunk_p)
        batch = np.zeros((len(chunk_p), width), np.int32)
        for j, p in enumerate(chunk_p):        # right-aligned (left pad)
            batch[j, width - p.shape[0]:] = p
        n_tok = max(chunk_g)
        tb = time.perf_counter()
        engine.generate(batch, n_tok)
        dt = time.perf_counter() - tb
        step_times += [dt / n_tok] * n_tok     # lock-step: uniform
        useful += sum(chunk_g)
    wall = time.perf_counter() - t0
    return wall, useful, step_times


def run_paged(engine, prompts, gens):
    for p, g in zip(prompts, gens):
        engine.submit(p, g)
    useful = 0
    step_times = []
    t0 = time.perf_counter()
    while engine.has_work:
        tb = time.perf_counter()
        for req in engine.step():
            useful += req.generated
        dt = time.perf_counter() - tb
        # one scheduler visit emits up to decode_chunk tokens per slot
        # (more with speculative decode); normalize to per-token latency
        step_times += [dt / max(engine.last_step_tokens, 1)] * \
            max(engine.last_step_tokens, 1)
    wall = time.perf_counter() - t0
    return wall, useful, step_times


def paged_fields(engine, spec_before=None, prefix_before=None):
    """Per-engine configuration + speculative-decode acceptance and
    prefix-cache stats for the JSON record (deltas against pre-warmup
    snapshots so warmup runs don't pollute the measured run).  Every
    paged record carries ``cache_hit_rate`` / ``admitted_tokens_saved``
    — zero for engines without prefix caching — so the trajectory file
    stays one schema."""
    fields = {"page_size": int(engine.page_size),
              "prefill_chunk": int(engine.prefill_chunk),
              "spec_decode": int(engine.spec)}
    if engine.spec:
        st = engine.spec_stats()
        calls = st["verify_calls"] - (spec_before or {}).get(
            "verify_calls", 0)
        toks = st["tokens"] - (spec_before or {}).get("tokens", 0)
        fields["spec_verify_calls"] = int(calls)
        fields["spec_mean_accepted"] = round(toks / calls, 3) if calls \
            else 0.0
    fields["prefix_cache"] = bool(engine.prefix_caching)
    if engine.prefix_caching:
        st = engine.prefix_stats()
        b = prefix_before or {}
        lookups = st["lookups"] - b.get("lookups", 0)
        hits = st["hits"] - b.get("hits", 0)
        fields["cache_hit_rate"] = round(hits / lookups, 3) if lookups \
            else 0.0
        fields["admitted_tokens_saved"] = int(
            st["tokens_saved"] - b.get("tokens_saved", 0))
    else:
        fields["cache_hit_rate"] = 0.0
        fields["admitted_tokens_saved"] = 0
    return fields


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=96)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized workload (same serving-scale model)")
    ap.add_argument("--fuse", action="store_true",
                    help="also run the paged engine with cross-op "
                         "fused kernels (docs/fusion.md) and report a "
                         "fused-vs-unfused section")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="also run a zipfian prompt-reuse section: "
                         "prefix-sharing paged engine vs an identical "
                         "engine with sharing off (docs/serving.md)")
    ap.add_argument("--reuse-hint", type=float, default=0.5,
                    help="reuse rate fed to the share-vs-stream "
                         "page-size pricing for the sharing engine")
    ap.add_argument("--preempt", action="store_true",
                    help="also run a preemption/restore section: a "
                         "starved high-priority arrival preempts a "
                         "low-priority hog, whose restore replays only "
                         "the unshared tail (docs/robustness.md)")
    ap.add_argument("--spec", type=int, default=2,
                    help="draft tokens per speculative decode step for "
                         "the paged engine (0 -> off)")
    ap.add_argument("--decode-chunk", type=int, default=4,
                    help="decode steps fused per scheduler visit; small "
                         "chunks turn slots over faster on heavy-tailed "
                         "budgets (finished slots leave, queued work "
                         "enters, between chunks)")
    ap.add_argument("--prefill-chunk", type=int, default=-1,
                    help="paged prefill chunk (-1 -> auto-sized from "
                         "the VMEM blocking model, 0 -> whole-prompt "
                         "joins)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every record as machine-readable "
                         "JSON (the BENCH_serve.json trajectory file)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record the run in a JAX profiler session under "
                         "DIR (a Perfetto file: engine spans and device "
                         "lanes on one clock); the session itself costs "
                         "some host time")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the measured paged engine's metrics "
                         "snapshot (registry + DRAM report) as JSON")
    ap.add_argument("--miss-log", default=None, metavar="PATH",
                    help="append schedule-cache misses as JSONL targets "
                         "for python -m repro.tune --from-telemetry")
    args = ap.parse_args()
    if args.smoke:
        # large enough that per-step latency percentiles are taken over
        # dozens of steps, the heavy-tailed budget draw can't collapse
        # the whole workload to a handful of useful tokens (the old
        # 6-request/gen-8 draw bottomed out at useful=23), and the
        # batch is wide enough that lock-step padding waste — the thing
        # continuous batching exists to remove — actually shows up
        args.requests, args.gen, args.prompt_len = 16, 48, 16
        args.max_seq, args.max_batch = 64, 4

    cfg = dataclasses.replace(get_reduced(args.arch), dtype=jnp.float32)
    # serving-scale reduced model: per-step compute must dominate host
    # dispatch for the throughput comparison to mean anything
    cfg = dataclasses.replace(cfg, d_model=256, n_layers=4,
                              n_heads=8, n_kv_heads=4, d_ff=1024,
                              vocab=4096)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts, gens = make_workload(cfg, args.requests, args.prompt_len,
                                  args.gen)

    chunk = None if args.prefill_chunk < 0 else args.prefill_chunk
    static = DecodeEngine(cfg, params, ServeConfig(max_seq=args.max_seq))
    obs = Obs(trace=args.trace, miss_log=args.miss_log)
    paged = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=args.max_seq, max_batch=args.max_batch,
        page_size=args.page_size or None, prefill_chunk=chunk,
        spec_decode=args.spec, decode_chunk=args.decode_chunk), obs=obs)

    # warm the compile caches outside the timed region: one full pass of
    # the same workload per engine (compiles are keyed by batch width,
    # token budget and prefill bucket — the workload exercises them all)
    run_static(static, prompts, gens, args.max_batch)
    run_paged(paged, prompts, gens)
    spec0 = paged.spec_stats() if paged.spec else None

    s_wall, s_useful, s_steps = run_static(static, prompts, gens,
                                           args.max_batch)
    p_wall, p_useful, p_steps = run_paged(paged, prompts, gens)
    page = paged.page_size
    assert p_useful == sum(gens), (p_useful, sum(gens))

    s_tps = s_useful / s_wall
    p_tps = p_useful / p_wall
    s_lat, s_lat_f = latency_summary(s_steps)
    p_lat, p_lat_f = latency_summary(p_steps)
    emit("serve_static", s_wall / max(s_useful, 1) * 1e6,
         f"{s_tps:.1f} tok/s {s_lat} useful={s_useful}",
         tok_s=round(s_tps, 2), **s_lat_f,
         useful_tokens=int(s_useful),
         metrics=static.obs.snapshot())
    emit("serve_paged", p_wall / max(p_useful, 1) * 1e6,
         f"{p_tps:.1f} tok/s {p_lat} "
         f"useful={p_useful} page={page} chunk={paged.prefill_chunk} "
         f"spec={paged.spec} speedup={p_tps / max(s_tps, 1e-9):.2f}x",
         tok_s=round(p_tps, 2), **p_lat_f,
         useful_tokens=int(p_useful),
         metrics=paged.obs.snapshot(),
         **paged_fields(paged, spec0))

    if args.fuse:
        # fused-vs-unfused paged section: same workload, same slots,
        # cross-op fused kernels on the hot path; greedy decoding makes
        # the outputs comparable token-for-token with the run above
        fused = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=args.max_seq, max_batch=args.max_batch,
            page_size=args.page_size or None, fuse=True,
            prefill_chunk=chunk, spec_decode=args.spec,
            decode_chunk=args.decode_chunk))
        run_paged(fused, prompts, gens)          # warm compiles
        fspec0 = fused.spec_stats() if fused.spec else None
        f_wall, f_useful, f_steps = run_paged(fused, prompts, gens)
        assert f_useful == sum(gens), (f_useful, sum(gens))
        f_tps = f_useful / f_wall
        f_lat, f_lat_f = latency_summary(f_steps)
        emit("serve_paged_fused", f_wall / max(f_useful, 1) * 1e6,
             f"{f_tps:.1f} tok/s {f_lat} "
             f"useful={f_useful} page={fused.page_size} "
             f"vs-unfused={f_tps / max(p_tps, 1e-9):.2f}x",
             tok_s=round(f_tps, 2), **f_lat_f,
             useful_tokens=int(f_useful),
             metrics=fused.obs.snapshot(),
             **paged_fields(fused, fspec0))

    if args.prefix_cache:
        # prompt-reuse section: zipf-popular shared prefixes on a
        # separate workload (NOT comparable to serve_static above).
        # The sharing engine prices its page size under the reuse hint;
        # the no-sharing engine is pinned to the SAME page size, so the
        # delta is purely the sharing machinery — hit admissions skip
        # the shared prefix's prefill and only stream the tail
        # long prompts, short answers — the templated-system-prompt
        # regime sharing targets; a miss pays a near-max_seq join, a
        # hit streams only its ragged tail
        r_plen = 3 * args.max_seq // 4
        r_prompts, r_gens = make_reuse_workload(
            cfg, args.requests, r_plen, args.gen, args.max_seq)
        share = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=args.max_seq, max_batch=args.max_batch,
            page_size=args.page_size or None, prefill_chunk=chunk,
            spec_decode=args.spec, decode_chunk=args.decode_chunk,
            prefix_cache=True, reuse_hint=args.reuse_hint))
        noshare = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=args.max_seq, max_batch=args.max_batch,
            page_size=share.page_size, prefill_chunk=chunk,
            spec_decode=args.spec, decode_chunk=args.decode_chunk))
        run_paged(noshare, r_prompts, r_gens)    # warm compiles
        nspec0 = noshare.spec_stats() if noshare.spec else None
        n_wall, n_useful, n_steps = run_paged(noshare, r_prompts, r_gens)
        assert n_useful == sum(r_gens), (n_useful, sum(r_gens))
        # the sharing engine's warmup also brings the radix tree to
        # steady state — the measured run sees a warm cache, which is
        # the regime prefix caching exists for; the second pass repeats
        # the workload against the now-warm tree so every all-hit
        # admission path (and its span-width compile) runs before the
        # clock starts; stats are deltas
        run_paged(share, r_prompts, r_gens)
        run_paged(share, r_prompts, r_gens)
        sspec0 = share.spec_stats() if share.spec else None
        spfx0 = share.prefix_stats()
        sh_wall, sh_useful, sh_steps = run_paged(share, r_prompts,
                                                 r_gens)
        assert sh_useful == sum(r_gens), (sh_useful, sum(r_gens))
        n_tps = n_useful / n_wall
        sh_tps = sh_useful / sh_wall
        n_lat, n_lat_f = latency_summary(n_steps)
        h_lat, h_lat_f = latency_summary(sh_steps)
        emit("serve_paged_noshare", n_wall / max(n_useful, 1) * 1e6,
             f"{n_tps:.1f} tok/s {n_lat} "
             f"useful={n_useful} page={noshare.page_size} "
             f"(reuse workload, sharing off)",
             tok_s=round(n_tps, 2), **n_lat_f,
             useful_tokens=int(n_useful),
             metrics=noshare.obs.snapshot(),
             **paged_fields(noshare, nspec0))
        pf = paged_fields(share, sspec0, spfx0)
        emit("serve_paged_prefix", sh_wall / max(sh_useful, 1) * 1e6,
             f"{sh_tps:.1f} tok/s {h_lat} "
             f"useful={sh_useful} page={share.page_size} "
             f"hit={pf['cache_hit_rate']:.0%} "
             f"saved={pf['admitted_tokens_saved']}tok "
             f"vs-noshare={sh_tps / max(n_tps, 1e-9):.2f}x",
             tok_s=round(sh_tps, 2), **h_lat_f,
             useful_tokens=int(sh_useful),
             metrics=share.obs.snapshot(), **pf)

    if args.preempt:
        # preemption/restore section (docs/robustness.md): its own tiny
        # fixed workload — two low-priority hogs saturate both slots and
        # most of a deliberately small page pool, then a high-priority
        # arrival starves until the aging rule fires preemption.  The
        # victim's complete pages go into the prefix tree, so its
        # restore prefix-matches them and replays only the unshared
        # tail; every counter below is host-side deterministic (exact
        # in check_bench), and the outputs must be byte-identical to an
        # unpressured engine.  NOT comparable to serve_static.
        rng = np.random.default_rng(5)
        pe_prompts = [rng.integers(0, cfg.vocab, (12,), dtype=np.int32)
                      for _ in range(2)]
        pe_prompts.append(rng.integers(0, cfg.vocab, (17,),
                                       dtype=np.int32))
        pe_gens, pe_prios = [40, 40, 7], [0, 0, 1]

        def run_prio(engine):
            for p, g, pr in zip(pe_prompts, pe_gens, pe_prios):
                engine.submit(p, g, priority=pr)
            done, useful = {}, 0
            t0 = time.perf_counter()
            while engine.has_work:
                for req in engine.step():
                    done[req.rid] = req
                    useful += req.emitted_total
            return time.perf_counter() - t0, useful, done

        ref_eng = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=64, max_batch=2, page_size=8, decode_chunk=4,
            spec_decode=0))
        _, _, ref_done = run_prio(ref_eng)
        pre = PagedEngine(cfg, params, PagedServeConfig(
            max_seq=64, max_batch=2, page_size=8, decode_chunk=4,
            spec_decode=0, n_pages=17, prefix_cache=True, preempt=True))
        pe_wall, pe_useful, pe_done = run_prio(pre)
        assert pe_useful == sum(pe_gens), (pe_useful, sum(pe_gens))
        for rid, req in pe_done.items():
            np.testing.assert_array_equal(
                req.output, ref_done[rid].output,
                err_msg=f"rid {rid} diverged under preemption")
        reg = pre.obs.registry
        n_pre = reg.counter("sched.preemptions").value
        restored = reg.counter("lifecycle.preempted_retried").value
        saved = pre.prefix_stats()["tokens_saved"]
        assert n_pre > 0, "preemption section never preempted"
        assert saved > 0, "restore never matched the registered pages"
        pe_tps = pe_useful / pe_wall
        emit("serve_paged_preempt", pe_wall / max(pe_useful, 1) * 1e6,
             f"{pe_tps:.1f} tok/s useful={pe_useful} "
             f"preemptions={n_pre} restored={restored} "
             f"saved={saved}tok (pressure workload, byte-exact)",
             tok_s=round(pe_tps, 2), useful_tokens=int(pe_useful),
             preemptions=int(n_pre), restored_requests=int(restored),
             admitted_tokens_saved=int(saved),
             metrics=pre.obs.snapshot())

    if args.metrics_out:
        paged.obs.write_metrics(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    paged.obs.close()

    if args.json:
        write_json(args.json)


if __name__ == "__main__":
    main()
