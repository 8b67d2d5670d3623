"""The serving driver: a traffic file's load through
``PagedEngine.submit`` and ``PagedEngine.step``, timed from the client's
side.

Set-up builds the weights from the seed, builds the engine as the
configuration says (program knobs at the program's defaults), runs one
request per program shape that the traffic can reach, and then offers
the traffic's lead-in, so that the window opens in steady state.  In
the window every request is timed from its due time.  After each step
the harness waits for the step's device work and reads each request's
progress (``adapter``): a token counts as seen at the end of the step
that made it.  Once the window has closed, the harness keeps serving
until every request the run answers for (``counted``: those due in
the window, or in a backlog those admitted before the close) has its
first token, at most ``DRAIN_S`` more.  Then it frees the engine,
draws a sample of the finished requests from the seed, and scores
their served tokens with the plain reference.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

import adapter
import configmap
import traffic
import weights

DRAIN_S = 60.0          # past the close, wait this long for first tokens
SAMPLE_TOKENS = 256     # served tokens the correctness sample holds ...
SAMPLE_MAX = 8          # ... in at most this many requests
TRACE_S = 6.0           # the traced part of a --trace 1 window
# host annotations and runtime events, no Python function calls
TRACE_OPTIONS = jax.profiler.ProfileOptions()
TRACE_OPTIONS.python_tracer_level = 0


class CompileClock:
    """Backend compilations and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclasses.dataclass
class Rec:
    due: float
    prompt: np.ndarray
    max_new: int
    submit: float = 0.0
    admit: float | None = None
    first: float | None = None
    seen: list = dataclasses.field(default_factory=list)  # (t, tokens)
    output: np.ndarray | None = None
    status: str | None = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode: list            # (keys its first token attends, tokens)
    prefill: list           # (cached before, tokens, prompt length)


def serve_config(sv: dict, cfg):
    """The engine's configuration: every key of the file's ``serve``
    passes through to ``PagedServeConfig``, but ``pool_tokens``, which
    sets the page count at the page size the engine will choose."""
    import dataclasses
    from repro.serve.engine import PagedServeConfig
    sc = PagedServeConfig(**{k: v for k, v in sv.items()
                             if k != "pool_tokens"})
    if "pool_tokens" in sv:
        sc = dataclasses.replace(
            sc, n_pages=sv["pool_tokens"] // adapter.page_size(cfg, sc) + 1)
    return sc


def warm_prompts(buckets, prefill_chunk: int, lo: int, hi: int) -> list:
    """One prompt length for each prefill program that prompts of
    ``lo``..``hi`` tokens reach: a whole-prompt join at each bucket up
    to the prefill chunk, and the shortest chunked prompt from ``lo``
    whose last chunk has each power-of-two width."""
    out, prev = [], 0
    for b in buckets or ():
        if b > prefill_chunk:
            break
        n = max(lo, prev + 1)
        if n <= min(b, hi):
            out.append(n)
        prev = b
    c = 1
    while prefill_chunk and c <= prefill_chunk:
        n = prefill_chunk + c // 2 + 1
        while n < lo:
            n += prefill_chunk
        if n <= hi:
            out.append(n)
        c *= 2
    return sorted(set(out))


def warm_up(engine, vocab: int, t: dict, rng) -> int:
    """Run every program the traffic can reach once: each prefill shape,
    then decode visits of each power-of-two length up to the engine's
    decode chunk.  Returns the requests run."""
    chunk = engine.sc.decode_chunk
    hi = min(t["prompt_tokens"]["max"], engine.sc.max_seq - chunk - 1)
    lens = warm_prompts(engine.buckets, engine.prefill_chunk,
                        t["prompt_tokens"]["min"], hi)
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in lens]
    engine.generate(prompts, chunk + 1, return_requests=True)
    n, k = len(prompts), chunk // 2
    if engine.prefix_caching:
        # a prefix hit prefills its unshared tail as one chunk of the
        # tail's power-of-two width: one of each, behind a cached page
        page = engine.page_size
        head = rng.integers(0, vocab, page + 1, dtype=np.int32)
        engine.generate([head], 2, return_requests=True)
        longest, w = min(engine.prefill_chunk, hi - page), 1
        while w // 2 < longest:
            tail = rng.integers(0, vocab, min(w, longest), dtype=np.int32)
            engine.generate([np.concatenate([head[:page], tail])], 2,
                            return_requests=True)
            n, w = n + 1, w * 2
        n += 1
    while k >= 1:
        engine.generate(prompts[:1], k + 1, return_requests=True)
        n, k = n + 1, k // 2
    adapter.sync(engine)
    return n


def percentile(xs, q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


class Server:
    """The engine under test, built and warmed as the configuration and
    the traffic file say."""

    def __init__(self, cfg_json: dict, traffic_path: str, seed: int,
                 t_proc: float, log):
        from repro.serve.engine import PagedEngine
        self.clock = CompileClock()
        self.cfg_json = cfg_json
        self.traffic_path = traffic_path
        self.traffic = traffic.load(traffic_path)
        self.sv = sv = cfg_json["serve"]
        self.cfg = configmap.model_config(cfg_json)
        self.params = self.draw(seed)
        log(f"weights drawn from seed {seed} at "
            f"{time.perf_counter() - t_proc:.3f}s")
        self.engine = PagedEngine(self.cfg, self.params,
                                  serve_config(sv, self.cfg))
        e = self.engine
        n = warm_up(e, self.cfg.vocab, self.traffic,
                    np.random.default_rng(seed))
        log(f"engine: page {e.page_size}, prefill chunk {e.prefill_chunk}, "
            f"decode chunk {e.sc.decode_chunk}, {sv['max_batch']} slots; "
            f"{n} warm-up requests; compile {self.clock.seconds:.3f}s in "
            f"{self.clock.compiles} compilations, {self.clock.hits} cache "
            f"hits, at {time.perf_counter() - t_proc:.3f}s")

    def draw(self, seed: int):
        from repro.models import transformer as T
        p = weights.make_weights(T.param_shapes(self.cfg), self.cfg_json,
                                 seed)
        return jax.block_until_ready(p)

    def arrivals(self, seed: int, seconds: float,
                 rate: float | None = None) -> list:
        return traffic.schedule(self.traffic_path, seed, seconds + DRAIN_S,
                                self.cfg.vocab, self.sv["max_seq"], rate)

    def drive(self, arrivals: list, seconds: float,
              trace_dir: str | None = None) -> dict:
        """Offer ``arrivals`` from ``-lead_in_s``; the window is
        ``[0, seconds)``.  Returns what every request and step did."""
        engine = self.engine
        t_open = time.perf_counter() + float(self.traffic["lead_in_s"])

        def now() -> float:
            return time.perf_counter() - t_open

        backlog = self.traffic["arrivals"]["process"] == "backlog"
        recs: dict[int, Rec] = {}
        steps: list[Step] = []
        late, queue = [], []
        trace_at = seconds / 2 - TRACE_S / 2
        trace_span = None
        compiles_at_open = None
        i = 0
        while True:
            tn = now()
            if compiles_at_open is None and tn >= 0:
                compiles_at_open = self.clock.compiles
                queue.append(adapter.waiting(engine))
            if trace_dir and trace_span is None and tn >= trace_at:
                jax.profiler.start_trace(
                    trace_dir, profiler_options=TRACE_OPTIONS)
                trace_span = [tn, None]
            elif trace_span and trace_span[1] is None \
                    and tn >= trace_at + TRACE_S:
                jax.profiler.stop_trace()
                trace_span[1] = tn
            while i < len(arrivals) and arrivals[i].due <= tn:
                a = arrivals[i]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    rid = engine.submit(a.prompt, a.max_new)
                recs[rid] = Rec(a.due, a.prompt, a.max_new, submit=now())
                late.append(recs[rid].submit - a.due)
                i += 1
            if engine.has_work:
                before = adapter.progress(engine, recs)
                t0 = now()
                with jax.profiler.TraceAnnotation("bench.step"):
                    done = engine.step()
                    adapter.sync(engine)
                t1 = now()
                after = adapter.progress(engine, recs)
                for r in (r for r in done if r.rid in recs):
                    after[r.rid] = (-1, r.prompt_len, len(r.output))
                    recs[r.rid].output = r.output
                    recs[r.rid].status = r.status.value
                steps.append(account(recs, before, after, t0, t1))
            elif i < len(arrivals):
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, arrivals[i].due - now()))
                continue
            tn = now()
            if len(queue) == 1 and tn >= seconds:
                queue.append(adapter.waiting(engine))
            if tn >= seconds and (trace_span is None or trace_span[1]) \
                    and (all(r.first is not None
                             for r in counted(recs, seconds, backlog))
                         or tn >= seconds + DRAIN_S):
                break
        return {"recs": recs, "steps": steps, "late": late,
                "t_open": t_open, "t_end": now(), "trace_span": trace_span,
                "queue": queue, "seconds": seconds, "backlog": backlog,
                "compiles_in_window": self.clock.compiles
                - (compiles_at_open or 0)}

    def finish(self) -> None:
        """Cancel whatever is still in flight."""
        self.engine.shutdown()


def counted(recs: dict, seconds: float, backlog: bool) -> list:
    """The requests a run answers for: those due in the window or, where
    the traffic is a backlog due before the window opens, those admitted
    to a slot before it closes.  Each must give its first token, at most
    ``DRAIN_S`` past the close, and finish ``ok`` if it finishes."""
    if backlog:
        return [r for r in recs.values()
                if r.admit is not None and r.admit < seconds]
    return [r for r in recs.values() if 0 <= r.due < seconds]


def summary(d: dict) -> dict:
    """End-to-end metrics and counts of one ``Server.drive``."""
    recs, seconds = d["recs"], d["seconds"]
    window = [r for r in recs.values() if 0 <= r.due < seconds]
    res = metrics(recs, window, seconds, d["t_end"])
    answers = counted(recs, seconds, d["backlog"])
    never = sum(r.first is None for r in answers)
    info = {"requests_due_in_window": len(window),
            "requests_submitted": len(recs),
            "requests_finished": sum(r.output is not None
                                     for r in recs.values()),
            "queue_at_open": d["queue"][0] if d["queue"] else None,
            "queue_at_close": d["queue"][-1] if d["queue"] else None,
            "late_p50_ms": percentile(d["late"], 50) * 1e3,
            "late_max_ms": max(d["late"]) * 1e3,
            "compiles_in_window": d["compiles_in_window"],
            "steps": len(d["steps"]),
            "served_after_close_s": d["t_end"] - seconds}
    failed = never + sum(r.status not in (None, "ok") for r in answers)
    return {"metrics": res, "info": info, "attempted": len(answers),
            "failed": failed, "never": never}


def score(cfg_json: dict, params, recs: dict, seed: int,
          control: str | None = None) -> dict:
    """Score a sample of the finished requests with the plain
    reference: the widest gap below the reference's best logit of a
    served token (and, with ``control``, of the control's pick)."""
    from references import load as load_reference
    finished = [r for r in recs.values()
                if r.output is not None and len(r.output)]
    sample = draw_sample(finished, seed)
    ref = load_reference(cfg_json["reference"])
    t0 = time.perf_counter()
    g = ref.gaps(cfg_json, params,
                 [np.concatenate([r.prompt, r.output]) for r in sample],
                 [len(r.prompt) for r in sample], control=control)
    # nothing finished to score: no reading, and the run is not correct
    out = {"logit_gap": max((float(x["served"].max()) for x in g),
                            default=None),
           "reference_s": time.perf_counter() - t0,
           "sample_requests": len(sample),
           "sample_tokens": int(sum(len(r.output) for r in sample)),
           "not_ok": sum(r.status != "ok" for r in finished)}
    if control:
        out["control_gap"] = max((float(x["control"].max()) for x in g),
                                 default=None)
    return out


def run(cfg_json: dict, traffic_path: str, seed: int, seconds: float,
        trace_dir: str | None, t_proc: float, log) -> dict:
    """One run of a serving cell, as ``run.py`` reports it."""
    srv = Server(cfg_json, traffic_path, seed, t_proc, log)
    arrivals = srv.arrivals(seed, seconds)
    d = srv.drive(arrivals, seconds, trace_dir)
    setup_s = d["t_open"] - t_proc
    s = summary(d)
    s["metrics"]["setup_s"] = setup_s
    s["info"].update(compile_s=srv.clock.seconds,
                     cache_hits=srv.clock.hits)
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in jax.local_devices())
    prefill_chunk = srv.engine.prefill_chunk
    adapter.release(srv.engine)
    params = srv.params
    del srv
    sc = score(cfg_json, params, d["recs"], seed)
    s["info"].update({k: sc[k] for k in ("reference_s", "sample_requests",
                                          "sample_tokens")})
    s["checks"] = {"logit_gap": (sc["logit_gap"],
                                 cfg_json["limits"]["logit_gap"]),
                   "never_served": (s["never"], 0),
                   "not_ok": (sc["not_ok"], 0)}
    s.update(memory_peak_bytes=peak, steps=d["steps"], recs=d["recs"],
             window_s=seconds, trace_span=d["trace_span"],
             t_end=d["t_end"], prefill_chunk=prefill_chunk)
    return s


def account(recs, before: dict, after: dict, t0: float, t1: float) -> Step:
    """What one step did, from each request's progress before and
    after it; a token counts as seen at ``t1``.  The token that ends a
    prefill is the prefill's; the rest were decoded, the first of them
    attending the prompt and every token sampled before it."""
    decode, prefill = [], []
    for rid, (slot, pre1, gen1) in after.items():
        r = recs[rid]
        _, pre0, gen0 = before.get(rid, (-1, 0, 0))
        if r.admit is None and (slot >= 0 or gen1 > 0):
            r.admit = t0
        if pre1 > pre0:
            prefill.append((pre0, pre1 - pre0, len(r.prompt)))
        n_dec = gen1 - gen0 - (1 if gen0 == 0 and gen1 > 0 else 0)
        if n_dec > 0:
            decode.append((len(r.prompt) + max(gen0, 1), n_dec))
        if gen1 > gen0:
            if r.first is None:
                r.first = t1
            r.seen.append((t1, gen1))
    return Step(t0, t1, decode, prefill)


def metrics(recs, window, seconds: float, t_end: float) -> dict:
    """End-to-end metrics over every request (TTFT: those due in the
    window) and all the window's time."""
    ttft = [((r.first if r.first is not None else t_end) - r.due) * 1e3
            for r in window]
    tpot, tokens = [], 0
    for r in recs.values():
        inside = [(t, n) for t, n in r.seen if t < seconds]
        if inside:
            before = [n for t, n in r.seen if t < 0]
            tokens += inside[-1][1] - (before[-1] if before else 0)
            t_first, n_first = r.seen[0]
            t_last, n_last = inside[-1]
            if n_last > n_first:
                tpot.append((t_last - t_first) / (n_last - n_first) * 1e3)
    return {"ttft_mean_ms": float(np.mean(ttft)) if ttft else None,
            "ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(tpot, 95),
            "serve_tok_s": tokens / seconds}


def draw_sample(finished: list, seed: int) -> list:
    """The longest finished request (prompt + output), then others drawn
    from the seed until the sample holds ``SAMPLE_TOKENS`` served
    tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.output)))
    rng = np.random.default_rng([seed, 7])
    rest = [order[k] for k in rng.permutation(len(order) - 1) + 1]
    out = [order[0]]
    for r in rest:
        if sum(len(x.output) for x in out) >= SAMPLE_TOKENS \
                or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
    return out
