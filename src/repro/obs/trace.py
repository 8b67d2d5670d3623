"""Host spans on the JAX profiler's clock, and the compilations an
engine step triggers.

:func:`span` returns a ``jax.profiler.TraceAnnotation``: with no
profiler session active it costs about a microsecond and records
nothing; inside a session (``jax.profiler.start_trace``, or
:class:`ProfilerSession`) it lands on the calling thread's line of the
profiler's own trace, on the device's clock, with its keyword arguments
as event stats.  Arguments are passed on only while a session is
active.  Spans never wait for the device: a span around a dispatch
times the host's part, and the device lanes of the same trace show the
device's.

:class:`CompileWatch` counts the backend compilations that fire while
it is open (one process-wide ``jax.monitoring`` listener, registered on
first use), so an engine can say which of its steps compiled.

The span names the engines emit are a fixed contract, listed in
docs/observability.md.
"""

from __future__ import annotations

import glob
import os

import jax
from jax._src.lib import _profiler

_session_active = _profiler.TraceMe.is_enabled
_Annotation = jax.profiler.TraceAnnotation

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def span(name: str, **args) -> _Annotation:
    """``with span("engine.prefill", rid=3): ...`` — one host span."""
    if args and _session_active():
        return _Annotation(name, **args)
    return _Annotation(name)


def instant(name: str, **args) -> None:
    """A zero-length span: a marker at this point of the host thread."""
    with span(name, **args):
        pass


class ProfilerSession:
    """One JAX profiler session writing under ``log_dir``: the
    ``.xplane.pb`` and a Perfetto ``perfetto_trace.json.gz`` that shows
    the host spans and the device lanes on one clock.  Host Python
    calls are not traced (only annotations and runtime events)."""

    def __init__(self, log_dir: str | os.PathLike):
        self.log_dir = os.fspath(log_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, create_perfetto_trace=True,
                                 profiler_options=opts)
        self._open = True

    def close(self) -> None:
        """Stop the session.  Idempotent."""
        if self._open:
            self._open = False
            jax.profiler.stop_trace()

    @property
    def perfetto_path(self) -> str | None:
        """The newest Perfetto file the session wrote (after close)."""
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "**", "perfetto_trace.json.gz"), recursive=True))
        return found[-1] if found else None


_watches: list = []          # the CompileWatches open, innermost last
_listening = False


def _on_duration(event: str, secs: float, **_) -> None:
    if event == BACKEND_COMPILE and _watches:
        w = _watches[-1]
        w.compiles.inc()
        w.compile_s.inc(secs)


class CompileWatch:
    """Counts backend compilations fired while open into ``registry`` as
    ``engine.compiles`` and ``engine.compile_s``.  Reentrant across
    engines: the innermost open watch takes the count."""

    def __init__(self, registry):
        global _listening
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
        self.compiles = registry.counter("engine.compiles")
        self.compile_s = registry.counter("engine.compile_s")

    def __enter__(self):
        _watches.append(self)
        return self

    def __exit__(self, *exc):
        _watches.pop()
        return False
