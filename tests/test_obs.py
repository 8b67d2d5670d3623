"""Observability subsystem: metrics registry, host spans on the JAX
profiler's trace, compile counts, modeled-vs-measured DRAM accounting,
and the guarantee that tracing never waits for the device
(docs/observability.md)."""

import dataclasses
import functools
import glob
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.obs import (DramLedger, MetricsRegistry, Obs, format_metrics,
                       hist_quantile, instant, read_miss_log, span)
from repro.obs.metrics import Histogram
from repro.serve.engine import PagedEngine, PagedServeConfig

# the engines' span names (docs/observability.md)
ENGINE_SPANS = ("engine.step", "engine.expire", "engine.admit",
                "engine.join", "engine.fork", "engine.prefill",
                "engine.decode", "engine.readback", "engine.finish",
                "engine.preempt", "sched.plan")


def _cfg(arch: str):
    return dataclasses.replace(get_reduced(arch), dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _model(arch: str):
    cfg = _cfg(arch)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _run_paged(arch: str, obs=None):
    """The shared tiny workload: two ragged prompts, 6 generated tokens."""
    cfg, params = _model(arch)
    engine = PagedEngine(cfg, params,
                         PagedServeConfig(max_seq=64, max_batch=2),
                         obs=obs)
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(3, 15, dtype=np.int32)]
    out = engine.generate(prompts, 6)
    return engine, out


# ========================== metrics registry ================================


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("engine.steps")
    assert reg.counter("engine.steps") is c
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = reg.gauge("pages.in_use")
    g.set(7)
    assert g.value == 7
    # a registered name cannot change type...
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("engine.steps")
    # ...and cannot be both a leaf and a group
    with pytest.raises(ValueError, match="leaf and group"):
        reg.counter("engine.steps.retries")
    with pytest.raises(ValueError, match="leaf and group"):
        reg.counter("engine")


def test_registry_snapshot_nests_by_dots_and_is_json():
    reg = MetricsRegistry()
    reg.counter("a.b.c").inc(2)
    reg.gauge("a.g").set(1)
    reg.counter("top").inc()
    snap = reg.snapshot()
    assert snap == {"a": {"b": {"c": 2}, "g": 1}, "top": 1}
    assert json.loads(reg.to_json()) == snap


def test_histogram_buckets_and_quantiles():
    h = Histogram(bounds=(10.0, 20.0, 40.0))
    for v in (5, 15, 15, 35, 1000):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["buckets"] == {"10": 1, "20": 2, "40": 1, "+inf": 1}
    assert snap["sum"] == pytest.approx(1070.0)
    # p50 interpolates inside the (10, 20] bucket
    assert 10.0 <= h.quantile(0.5) <= 20.0
    # the open +inf tail reports its lower bound, not infinity
    assert h.quantile(0.99) == pytest.approx(40.0)
    assert hist_quantile({"count": 0, "sum": 0, "buckets": {}}, 0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram(bounds=(10.0, 10.0))


def test_format_metrics_one_formatter():
    tree = {
        "spec": {"verify_calls": 4, "mean_accepted": 2.5},
        "prefix_cache": {"hit_rate": 0.25, "hits": 1},
        "engine": {"step_us": {"count": 2, "sum": 30.0,
                               "buckets": {"10": 1, "20": 1, "+inf": 0}}},
    }
    text = format_metrics(tree)
    assert "spec.verify_calls" in text
    assert "25.0%" in text                     # *rate floats as percents
    assert "p50=" in text and "p99=" in text   # histograms as quantiles
    # sections filter + order
    only = format_metrics(tree, sections=("prefix_cache",))
    assert "spec." not in only and "prefix_cache.hits" in only


# ================== spans on the JAX profiler's trace =======================


def _profile(trace_dir) -> list:
    """(name, start_ns, end_ns, args) of every host event in the
    profiler trace a session wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    (pb,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)))
    return out


def _named(events, prefixes=("engine.", "sched.", "static.", "degrade.",
                             "train.")) -> list:
    return [e for e in events if e[0].startswith(prefixes)]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_tracer_emits_valid_nested_chrome_trace(tmp_path):
    """The session's Perfetto file is a Chrome-trace JSON document whose
    complete events nest by interval containment, arguments included."""
    with Obs(trace=tmp_path) as obs:
        with span("outer", step=0):
            with span("inner_a"):
                pass
            with span("inner_b"):
                pass
        instant("marker", k=1)
    with gzip.open(obs.session.perfetto_path) as f:
        events = json.load(f)["traceEvents"]
    by_name = {e["name"]: e for e in events
               if e.get("name") in ("outer", "inner_a", "inner_b", "marker")}
    assert set(by_name) == {"outer", "inner_a", "inner_b", "marker"}
    outer, a, b = by_name["outer"], by_name["inner_a"], by_name["inner_b"]
    for e in by_name.values():
        assert e["ph"] == "X" and e["ts"] >= 0.0
    for inner in (a, b):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= \
            outer["ts"] + outer["dur"] + 1e-3
    assert a["ts"] + a["dur"] <= b["ts"] + 1e-3   # siblings in order
    assert outer["args"] == {"step": "0"}
    assert by_name["marker"]["args"] == {"k": "1"}
    obs.close()                                   # idempotent


def test_span_args_only_while_session_active(monkeypatch, tmp_path):
    """With no session a span is built from its name alone; arguments
    reach the annotation only while a session records."""
    from repro.obs import trace
    built = []
    real = trace._Annotation
    monkeypatch.setattr(
        trace, "_Annotation",
        lambda name, **kw: built.append(kw) or real(name, **kw))
    with span("engine.admit", rid=4):
        pass
    assert built == [{}]
    with Obs(trace=tmp_path):
        with span("engine.admit", rid=4):
            pass
    assert built[1] == {"rid": 4}
    (ev,) = [e for e in _profile(tmp_path) if e[0] == "engine.admit"]
    assert ev[3] == {"rid": 4}


def test_engine_trace_covers_plan_prefill_decode_spans(tmp_path):
    with Obs(trace=tmp_path) as obs:
        _run_paged("granite-3-8b", obs=obs)
    events = _named(_profile(tmp_path))
    names = {e[0] for e in events}
    assert {"engine.step", "engine.expire", "engine.admit", "sched.plan",
            "engine.decode", "engine.readback", "engine.finish"} <= names
    assert names & {"engine.join", "engine.prefill"}   # prompt ingest
    assert names <= set(ENGINE_SPANS), names - set(ENGINE_SPANS)
    steps = sorted(e for e in events if e[0] == "engine.step")
    assert [e[3]["step"] for e in steps] == list(range(1, len(steps) + 1))
    # engine steps are serial: monotonic and non-overlapping
    for prev, cur in zip(steps, steps[1:]):
        assert prev[2] <= cur[1]
    # every other span nests inside some engine step
    for e in events:
        if e[0] != "engine.step":
            assert any(_inside(e, s) for s in steps), e
    finishes = [e[3] for e in events if e[0] == "engine.finish"]
    assert sorted(f["rid"] for f in finishes) == [0, 1]
    assert {f["status"] for f in finishes} == {"ok"}


def test_engine_prefill_and_join_spans_carry_rid(tmp_path):
    """Each request's spans share its rid: joins for one-chunk prompts,
    prefill chunks (start, tokens) for longer ones."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, page_size=8, prefill_chunk=8))
    with Obs(trace=tmp_path):
        engine.generate([np.arange(1, 7, dtype=np.int32),     # one chunk
                         np.arange(3, 23, dtype=np.int32)],   # three
                        3)
    events = _named(_profile(tmp_path))
    joins = [e[3] for e in events if e[0] == "engine.join"]
    assert joins == [{"rid": 0, "bucket": 8}]
    chunks = [e[3] for e in events if e[0] == "engine.prefill"]
    assert chunks == [{"rid": 1, "start": 0, "tokens": 8},
                      {"rid": 1, "start": 8, "tokens": 8},
                      {"rid": 1, "start": 16, "tokens": 4}]
    admits = {e[3]["rid"]: e for e in events if e[0] == "engine.admit"}
    (join,) = [e for e in events if e[0] == "engine.join"]
    assert _inside(join, admits[0])       # a join runs at admission
    decode = [e[3] for e in events if e[0] == "engine.decode"]
    assert decode and all(d["spec"] == 0 and d["chunk"] >= 1
                          for d in decode)


def test_static_engine_spans_and_no_fences(monkeypatch, tmp_path):
    from repro.serve.engine import DecodeEngine, ServeConfig
    cfg, params = _model("granite-3-8b")
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    engine = DecodeEngine(cfg, params, ServeConfig(max_seq=32))
    with Obs(trace=tmp_path):
        out = engine.generate(np.ones((2, 4), np.int32), 3)
    assert out.shape == (2, 3) and not calls
    names = [e[0] for e in _named(_profile(tmp_path))]
    assert names.count("static.prefill") == 1
    assert {"static.decode", "static.readback"} <= set(names)


# ================== tracing is observation, not perturbation ================


@pytest.mark.parametrize("arch", ["granite-3-8b", "recurrentgemma-9b"])
def test_tokens_identical_with_tracing_on(arch, tmp_path):
    _, out_off = _run_paged(arch)
    with Obs(trace=tmp_path) as obs:
        _, out_on = _run_paged(arch, obs=obs)
    assert np.array_equal(out_off, out_on)
    assert any(e[0] == "engine.step" for e in _profile(tmp_path))


def test_no_host_syncs_when_tracing_off(monkeypatch, tmp_path):
    """``PagedEngine.step`` never calls ``block_until_ready``: not with
    tracing off, and not inside a profiler session either."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    engine, _ = _run_paged("granite-3-8b")          # no session
    assert engine.obs.session is None
    assert not calls, "engine fenced the device with tracing off"
    with Obs(trace=tmp_path) as obs:
        _run_paged("granite-3-8b", obs=obs)          # session open
    assert not calls, "engine fenced the device inside a session"


# ===================== program names and counters ===========================


class _Capture:
    """Wraps a jitted function; keeps the shapes of its last call so the
    program can be lowered again afterwards."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *a, **kw):
        self.args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if isinstance(x, jax.Array) else x, (a, kw))
        return self.fn(*a, **kw)

    def module_name(self) -> str:
        a, kw = self.args
        first = self.fn.lower(*a, **kw).as_text().splitlines()[0]
        return first.split()[1].lstrip("@")


def test_program_names_the_benchmark_finds():
    """The engine's decode, prefill-chunk and join programs keep the
    module names a profiler trace shows them by."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, page_size=8, prefill_chunk=8))
    caps = {}

    def wrap(getter):
        def get(key):
            fn = getter(key)
            return caps.setdefault(fn, _Capture(fn))
        return get

    engine._decode = caps["decode"] = _Capture(engine._decode)
    engine._get_chunk_fn = wrap(engine._get_chunk_fn)
    engine._get_join = wrap(engine._get_join)
    engine.generate([np.arange(1, 7, dtype=np.int32),
                     np.arange(3, 23, dtype=np.int32)], 3)
    names = {c.module_name() for c in caps.values()}
    assert names == {"jit__decode_fn", "jit_chunk", "jit_join"}


def test_queue_wait_us_from_submit_to_admission():
    """``sched.queue_wait_us`` observes engine-clock submit -> admission
    once per admission; ``Request.t_submit``/``t_admit`` carry it."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params,
                         PagedServeConfig(max_seq=64, max_batch=1))
    now = [1_000_000]
    engine._clock = lambda: now[0]
    engine.submit(np.arange(1, 9, dtype=np.int32), 2)
    engine.submit(np.arange(1, 9, dtype=np.int32), 2)
    now[0] += 3_000_000                  # 3 ms later the first gets in
    done = engine.step()
    while engine.has_work:
        now[0] += 2_000_000
        done += engine.step()
    r0, r1 = sorted(done, key=lambda r: r.rid)
    assert (r0.t_submit, r0.t_admit) == (1_000_000, 4_000_000)
    assert r1.t_submit == 1_000_000 and r1.t_admit > r0.t_admit
    h = engine.obs.snapshot()["sched"]["queue_wait_us"]
    assert h["count"] == 2
    assert h["sum"] == pytest.approx(
        (3_000_000 + r1.t_admit - r1.t_submit) / 1e3)


def test_engine_compiles_count_new_chunk_widths_only():
    """A step that needs a new prefill-chunk width compiles once; a
    step repeating a known shape compiles nothing."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=1, page_size=8, prefill_chunk=8,
        decode_chunk=2))
    reg = engine.obs.registry
    n = reg.counter("engine.compiles")
    # warm every program a 16-token prompt reaches (two full chunks)
    engine.generate([np.arange(1, 17, dtype=np.int32)], 2)
    assert n.value > 0 and reg.counter("engine.compile_s").value > 0
    base = n.value
    engine.generate([np.arange(2, 18, dtype=np.int32)], 2)
    assert n.value == base                      # repeated shapes
    engine.generate([np.arange(1, 13, dtype=np.int32)], 2)
    assert n.value == base + 1                  # a 4-wide final chunk
    engine.generate([np.arange(1, 11, dtype=np.int32)], 2)
    assert n.value == base + 2                  # a 2-wide final chunk
    engine.generate([np.arange(5, 15, dtype=np.int32)], 2)
    assert n.value == base + 2


def test_progress_agrees_with_scheduler_internals():
    """``progress`` reads what the harness reads through the scheduler:
    slot, prompt tokens cached, tokens sampled."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, page_size=8, prefill_chunk=8))
    rids = {engine.submit(np.arange(1, 21, dtype=np.int32), 4),
            engine.submit(np.arange(1, 6, dtype=np.int32), 4)}
    seen = 0
    while engine.has_work:
        engine.step()
        want = {r.rid: (r.slot, r.prefilled, r.generated)
                for r in engine.scheduler.running.values() if r.rid in rids}
        got = engine.progress(rids)
        assert got == want
        seen += bool(got)
        assert engine.progress(set()) == {}
    assert seen >= 2


def test_sync_waits_for_last_step():
    """After ``sync`` the device work of the last step has run: the
    buffers the harness waited on are ready, and the tokens sampled so
    far are the ones the request finishes with."""
    cfg, params = _model("granite-3-8b")
    engine = PagedEngine(cfg, params, PagedServeConfig(
        max_seq=64, max_batch=2, decode_chunk=2))
    rid = engine.submit(np.arange(1, 9, dtype=np.int32), 6)
    engine.step()
    engine.sync()
    assert engine._out_buf.is_ready()
    assert all(x.is_ready() for x in jax.tree.leaves(engine.cache))
    ((slot, prefilled, generated),) = engine.progress({rid}).values()
    assert (prefilled, generated) == (8, 3)
    early = np.asarray(engine._out_buf)[slot, :generated]
    done = None
    while engine.has_work:
        for r in engine.step():
            done = r
    assert np.array_equal(done.output[:generated], early)


def test_degrade_transitions_are_trace_instants(tmp_path):
    from repro.serve.lifecycle import DegradationController, \
        DegradeThresholds
    reg = MetricsRegistry()
    ctl = DegradationController(
        reg, DegradeThresholds(queue_depth=1, sustain=1, recover=1))
    with Obs(trace=tmp_path):
        reg.gauge("sched.queue_depth").set(3)
        ctl.update()
        reg.gauge("sched.queue_depth").set(0)
        ctl.update()
    ev = [(e[0], e[3]) for e in _profile(tmp_path)
          if e[0].startswith("degrade.")]
    assert ev == [("degrade.up", {"from": "normal", "to": "no_spec",
                                  "signal": "queue_depth"}),
                  ("degrade.down", {"from": "no_spec", "to": "normal",
                                    "signal": "recovered"})]


# ==================== modeled-vs-measured DRAM ledger =======================


def test_dram_ledger_records_resolutions_and_misses(tmp_path):
    from repro import tune
    miss_log = tmp_path / "miss.jsonl"
    reg = MetricsRegistry()
    led = DramLedger(registry=reg, miss_log=str(miss_log))
    with led.scope("gemm[64]"):
        tune.best_schedule("matmul", (64, 64, 64))
    with led.scope("gemm[64]"):                     # memoized: no new miss
        tune.best_schedule("matmul", (64, 64, 64))
    led.end_step([0, 1])
    rep = led.report()
    (key,) = rep["per_op"]
    assert key.startswith("matmul/")
    ent = rep["per_op"][key]
    # analytic fallback: the used tiles ARE the model's top candidate
    assert ent["source"] == "analytic"
    assert ent["modeled_bytes"] == ent["used_bytes"] > 0
    assert ent["ratio"] == pytest.approx(1.0)
    tag = rep["per_tag"]["gemm[64]"]
    assert tag["executions"] == 2 and tag["ops"] == [key]
    assert rep["total_bytes"] == 2 * tag["bytes_per_execution"]
    assert rep["per_step"]["steps"] == 1
    assert rep["per_request"]["requests"] == 2
    assert reg.snapshot()["schedule_cache"]["misses"] >= 1
    led.close()
    # miss log round-trips into deduplicated tuning targets
    targets = read_miss_log(str(miss_log))
    assert targets == [{"op": "matmul", "dims": [64, 64, 64],
                        "dtype": "float32", "stride": 1}]


def test_read_miss_log_tolerates_corrupt_lines(tmp_path):
    p = tmp_path / "miss.jsonl"
    p.write_text('{"op": "matmul", "dims": [8, 8, 8]}\n'
                 "not json\n"
                 "\n"
                 '{"op": "matmul", "dims": [8, 8, 8]}\n'     # duplicate
                 '{"dims": [1]}\n')                          # no op key
    assert read_miss_log(str(p)) == [
        {"op": "matmul", "dims": [8, 8, 8], "dtype": "float32",
         "stride": 1}]


def test_tune_cli_replays_telemetry_dry_run(tmp_path, capsys):
    from repro.tune.__main__ import main as tune_main
    p = tmp_path / "miss.jsonl"
    p.write_text('{"op": "matmul", "dims": [64, 64, 64], '
                 '"dtype": "float32", "stride": 1}\n')
    tune_main(["--from-telemetry", str(p), "--dry-run"])
    out = capsys.readouterr().out
    assert "1 distinct miss target(s)" in out
    assert "would tune matmul/" in out
    # an empty log is a clean no-op (CI runs this unconditionally)
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    tune_main(["--from-telemetry", str(empty), "--dry-run"])
    assert "0 distinct miss target(s)" in capsys.readouterr().out
    # without --from-telemetry, op and dims stay required
    with pytest.raises(SystemExit):
        tune_main([])


# ===================== engine integration snapshot ==========================


def test_engine_snapshot_sections_and_stat_views():
    engine, _ = _run_paged("granite-3-8b")
    snap = engine.obs.snapshot()
    assert snap["engine"]["decode_tokens"] > 0
    assert snap["engine"]["steps"] > 0
    assert snap["engine"]["step_us"]["count"] == snap["engine"]["steps"]
    assert snap["sched"]["admitted"] == 2
    assert snap["pages"]["capacity"] > 0
    # the tuner was consulted: schedule-cache section is non-empty...
    sc = snap["schedule_cache"]
    assert sc["hits"] + sc["misses"] > 0
    # ...and every resolved op key carries the modeled-vs-measured triple
    assert snap["dram"]["per_op"]
    for ent in snap["dram"]["per_op"].values():
        assert {"modeled_bytes", "used_bytes", "ratio"} <= set(ent)
    assert snap["dram"]["per_tag"]
    # stats views are thin reads over the same registry (one source of
    # truth — the dict shapes are the pre-registry contract)
    assert set(engine.spec_stats()) == {"verify_calls", "tokens",
                                        "mean_accepted"}
    assert set(engine.prefix_stats()) == {"lookups", "hits", "hit_rate",
                                          "tokens_saved", "cached_pages"}
    # snapshot is JSON-safe end to end
    json.dumps(snap)
