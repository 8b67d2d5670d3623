"""The engine's spans in a profiler trace and the two engine-host
readers, on a trace recorded on one TPU v5e: eight ``PagedEngine.step``
calls of the CPU tests' tiny dense model (8 layers, d_model 256; page
64, prefill chunk 128, decode chunk 2) serving prompts of 40, 300 and
100 tokens, under ``bench.submit`` / ``bench.step`` spans as
``serve.py`` opens them.  The expected values were worked out from the
file with ``jax.profiler.ProfileData`` alone: the eight ``engine.step`` spans
last 46614.209 us together, their three ``engine.readback`` spans
3893.980 us, and the device ran no operation for 43223.199 us of them.
"""

import os
import shutil

import pytest

import layerkit
import spans
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "engine_steps.xplane.pb")
PARENT_TRACE = os.path.join(HERE, "testdata", "decode_step.xplane.pb")


reader = layerkit.reader


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A trace directory as ``run.py`` leaves it, holding ``path``."""
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    spans._cache.clear()

    def put(path):
        d = tmp_path / "cell" / "plugins" / "profile" / "run"
        d.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, d / "host.xplane.pb")
    return put


def test_engine_steps_in_the_window():
    steps = spans.engine_steps(*trace_reduce.load(TRACE))
    assert len(steps) == 8
    lengths = [s["t1"] - s["t0"] for s in steps]
    assert lengths[0] == pytest.approx(18785.079e-6, rel=1e-9)
    assert lengths[-1] == pytest.approx(3801.860e-6, rel=1e-9)
    assert sum(lengths) == pytest.approx(46614.209e-6, rel=1e-9)
    # readbacks in steps 2, 3 and 8, where requests finish
    assert [s["readback_s"] > 0 for s in steps] == [
        False, True, True, False, False, False, False, True]
    assert sum(s["readback_s"] for s in steps) == pytest.approx(
        3893.980e-6, rel=1e-9)
    assert all(0 <= s["idle_s"] <= s["t1"] - s["t0"] for s in steps)
    assert steps[0]["idle_s"] == pytest.approx(17783.360e-6, rel=1e-9)


def test_engine_host_ms(run_dir):
    run_dir(TRACE)
    v = reader("engine_host_ms.chat")({"trace": {"steps": []}})
    assert v == pytest.approx((46614.209 - 3893.980) / 8 / 1e3, rel=1e-9)


def test_engine_gap_ms(run_dir):
    run_dir(TRACE)
    v = reader("engine_gap_ms.chat")({"trace": {"steps": []}})
    assert v == pytest.approx(43223.199 / 8 / 1e3, rel=1e-9)


def test_no_step_wholly_in_the_window_reads_none(run_dir, monkeypatch):
    """With only the ``bench.submit`` span left, the window ends before
    the first engine step does: nothing to read."""
    run_dir(TRACE)
    load = trace_reduce.load

    def submit_only(path):
        devices, host = load(path)
        return devices, [e for e in host if e[2] != "bench.step"]
    monkeypatch.setattr(trace_reduce, "load", submit_only)
    for name in ("engine_host_ms.chat", "engine_gap_ms.chat"):
        assert reader(name)({"trace": {"steps": []}}) is None


def test_program_without_engine_spans_reads_none(run_dir):
    """A trace of a program that emits no engine spans (one recorded
    before they existed) reads nothing, and raises nothing."""
    run_dir(PARENT_TRACE)
    for name in ("engine_host_ms.chat", "engine_gap_ms.chat"):
        assert reader(name)({"trace": {"steps": []}}) is None


def test_no_trace_reads_none(run_dir):
    for name in ("engine_host_ms.chat", "engine_gap_ms.chat"):
        assert reader(name)({"trace": None}) is None
        assert reader(name)({"trace": {"steps": []}}) is None
