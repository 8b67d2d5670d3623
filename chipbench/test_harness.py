"""The harness's window arithmetic and data-driven cells, rehearsed on
the CPU at a tiny width with the program's own engine."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import run
import serve
import testkit

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(str(tmp_path_factory.mktemp("checkout")))


def test_new_cell_from_data_files(root):
    line = run.run_cell(root, "tiny-chat", SEED, 3.0, False,
                        time.perf_counter())
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p95_ms",
                                    "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["info"]["compiles_in_window"] == 0
    json.dumps(line)


def test_bursty_shared_prefix_cell_from_data_files(root):
    line = run.run_cell(root, "tiny-sessions", SEED + 2, 3.0, False,
                        time.perf_counter())
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["compiles_in_window"] == 0


def test_backlog_cell_counts_the_requests_it_admitted(root):
    line = run.run_cell(root, "tiny-backlog", SEED + 3, 3.0, False,
                        time.perf_counter())
    assert line["correct"], line["checks"]
    # nothing is due in the window, so no time to first token
    assert {"serve_tok_s", "setup_s"} <= set(line["metrics"])
    assert "ttft_mean_ms" not in line["metrics"]
    # every request is due before the window opens: a run answers for
    # those it admitted before the close, and more are left waiting
    assert line["info"]["requests_due_in_window"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["queue_at_close"] > 0
    assert line["info"]["compiles_in_window"] == 0


def test_backlog_failures_count():
    """In a backlog, a request admitted before the close that ends other
    than ``ok``, or never gives a token, is failed; one admitted after
    the close, or never, is not attempted."""
    recs = {0: rec(-20.0, [(1.0, 1)], out=np.zeros(3, np.int32)),
            1: rec(-20.0, [(2.0, 1)], out=np.zeros(3, np.int32)),
            2: rec(-20.0, []),                  # admitted, no token
            3: rec(-20.0, [(12.0, 1)]),         # admitted after the close
            4: rec(-20.0, [])}                  # still waiting
    for r, admit in zip(recs.values(), (0.5, 1.5, 9.0, 10.5, None)):
        r.admit = admit
    recs[0].status, recs[1].status = "ok", "failed"
    d = {"recs": recs, "seconds": 10.0, "t_end": 13.0, "backlog": True,
         "queue": [5, 1], "late": [0.0], "steps": [],
         "compiles_in_window": 0}
    s = serve.summary(d)
    assert (s["attempted"], s["failed"], s["never"]) == (3, 2, 1)
    assert serve.summary(dict(d, backlog=False))["attempted"] == 0


def test_traced_run_reads_what_the_cpu_has(root):
    line = run.run_cell(root, "tiny-chat", SEED + 1, 5.0, True,
                        time.perf_counter())
    assert line["correct"], line["checks"]
    # no TPU plane: the device metrics find nothing and are left out
    assert set(line["metrics"]) == {"queue_wait_p95_ms.chat",
                                    "ttft_p95_ms.chat"}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def tiny_server():
    return serve.Server(testkit.TINY, os.path.join(
        testkit.HERE, "traffic", "chat_steady.json"), SEED,
        time.perf_counter(), lambda m: None)


def test_stall_raises_ttft_behind_it(monkeypatch):
    """The host stalls for 3 s, 1 s into the window: every request due
    during the stall is served after it, and its time to first token,
    counted from its due time, carries the wait into the tail."""
    srv = tiny_server()
    t = dict(testkit.TINY_CHAT,
             arrivals={"process": "poisson", "rate_per_s": 2.0})
    monkeypatch.setattr(srv, "traffic", t)
    arrivals = serve.traffic.requests(t, SEED, 5.0 + serve.DRAIN_S,
                                      16000, 512)
    step, stalled = srv.engine.step, []

    def stall():
        if not stalled and time.perf_counter() > t_stall:
            stalled.append(time.perf_counter() + 3.0 - t_open)
            time.sleep(3.0)
        return step()

    monkeypatch.setattr(srv.engine, "step", stall)
    t_open = time.perf_counter() + t["lead_in_s"]
    t_stall = t_open + 1.0
    d = srv.drive(arrivals, 5.0)
    hit = serve.summary(d)["metrics"]
    assert stalled
    end = stalled[0]
    behind = [r for r in d["recs"].values() if 1.1 < r.due < end - 0.1]
    assert len(behind) >= 2
    assert all(r.first >= end for r in behind)
    assert hit["ttft_p95_ms"] >= 1e3 * min(end - r.due for r in behind)
    assert hit["ttft_mean_ms"] >= 1e3 * sum(end - r.due for r in behind) \
        / len([r for r in d["recs"].values() if 0 <= r.due < 5.0])


def rec(due, seen, out=None):
    r = serve.Rec(due, np.zeros(4, np.int32), 10)
    r.seen = seen
    r.first = seen[0][0] if seen else None
    r.output = out
    return r


def test_rates_take_the_whole_window_and_tails_every_request():
    recs = {0: rec(-1.0, [(-0.5, 1), (0.5, 5), (1.0, 9)]),
            1: rec(0.2, [(0.3, 1), (0.4, 3)]),
            2: rec(1.5, [])}                      # never served
    window = [r for r in recs.values() if 0 <= r.due < 10.0]
    m = serve.metrics(recs, window, 10.0, t_end=12.0)
    # 8 tokens of request 0 and 3 of request 1 seen inside, over 10 s
    assert m["serve_tok_s"] == pytest.approx(11 / 10.0)
    # the request never served counts with its whole wait
    assert m["ttft_p95_ms"] == pytest.approx(
        np.percentile([100.0, 10500.0], 95))
    assert m["ttft_mean_ms"] == pytest.approx((100.0 + 10500.0) / 2)
    # per-token time from the first token seen to the last in the window
    assert sorted([m["tpot_p95_ms"]]) == [pytest.approx(np.percentile(
        [1.5 / 8 * 1e3, 0.1 / 2 * 1e3], 95))]


# chat's list is the one from before the warm-up took the clip's low end
@pytest.mark.parametrize("lo,hi,want", [
    (64, 3072, [64, 65, 129, 257, 513, 514, 515, 517, 521, 529, 545, 577,
                641, 769]),
    (1024, 3584, [1025, 1026, 1027, 1029, 1033, 1041, 1057, 1089, 1153,
                  1281]),
    (1024, 1100, [1025, 1026, 1027, 1029, 1033, 1041, 1057, 1089]),
])
def test_warm_prompts_reach_every_chunk_width(lo, hi, want):
    """One prompt for each join bucket and each last-chunk width that
    prompts of lo..hi tokens reach, each the shortest from lo."""
    assert serve.warm_prompts([64, 128, 256, 512], 512, lo, hi) == want


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(testkit.HERE, "run.py"),
                        "--workload", "granite8b-chat-steady", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=testkit.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
