"""Persistent JSON schedule cache.

One file holds every tuned schedule, keyed by
``op/shape/dtype/device-kind`` (see :meth:`repro.tune.schedule.OpSpec.key`).
The default location is ``$REPRO_TUNE_CACHE`` if set, else
``~/.cache/repro/schedules.json``; pass an explicit path to keep per-project
caches (e.g. one checked into a deployment repo and pre-populated offline
with ``python -m repro.tune``).

File format (version 1)::

    {"version": 1,
     "schedules": {"matmul/m4096n4096k4096/bfloat16/tpu": {...Schedule...}}}

Writes are read-modify-write through an adjacent temp file + ``os.replace``
so concurrent tuners cannot truncate each other's entries.
"""

from __future__ import annotations

import json
import os
import tempfile
import warnings

from repro.tune.schedule import OpSpec, Schedule

SCHEMA_VERSION = 1


def default_cache_path() -> str:
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "schedules.json")


def device_kind() -> str:
    """Backend tag used in cache keys; interpret-mode results are tagged
    ``cpu`` so they never masquerade as real-device timings.  A backend
    that fails to start raises here rather than passing for the CPU."""
    import jax
    return jax.default_backend()


class ScheduleCache:
    """Dict-of-Schedules with lazy load and atomic persistence."""

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self._loaded: dict[str, Schedule] | None = None

    # -- IO -------------------------------------------------------------------

    def _quarantine(self, why: str) -> None:
        """Move the unreadable file aside to ``<path>.corrupt`` so the
        next flush rebuilds a clean cache without destroying the
        evidence (a second corrupt file overwrites the first — the
        newest specimen is the one worth inspecting)."""
        quarantined = self.path + ".corrupt"
        try:
            os.replace(self.path, quarantined)
        except OSError:
            return              # raced away or unwritable dir: nothing to do
        warnings.warn(
            f"schedule cache {self.path} is corrupt ({why}); quarantined "
            f"to {quarantined} and rebuilding — retune with "
            f"`python -m repro.tune` to repopulate")

    def _read_file(self) -> dict[str, Schedule]:
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except OSError:
            return {}           # no cache yet: cold start, not corruption
        except json.JSONDecodeError as e:
            self._quarantine(f"invalid JSON: {e}")
            return {}
        if not isinstance(raw, dict):
            self._quarantine(f"expected an object, got {type(raw).__name__}")
            return {}
        if raw.get("version") != SCHEMA_VERSION:
            return {}
        out: dict[str, Schedule] = {}
        for key, entry in raw.get("schedules", {}).items():
            try:
                # keep on-disk provenance (measured/analytic) intact;
                # lookup() tags what it hands out as "cache"
                out[key] = Schedule.from_json(entry)
            except (KeyError, ValueError, TypeError):
                continue  # skip corrupt entries, keep the rest usable
        return out

    def _entries(self) -> dict[str, Schedule]:
        if self._loaded is None:
            self._loaded = self._read_file()
        return self._loaded

    def _flush(self, entries: dict[str, Schedule]) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        payload = {"version": SCHEMA_VERSION,
                   "schedules": {k: s.to_json()
                                 for k, s in sorted(entries.items())}}
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(os.path.abspath(self.path)),
            suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- API ------------------------------------------------------------------

    def lookup(self, spec: OpSpec, device: str | None = None
               ) -> Schedule | None:
        hit = self._entries().get(spec.key(device or device_kind()))
        return hit.with_source("cache") if hit is not None else None

    def store(self, schedule: Schedule, device: str | None = None) -> str:
        """Persist (merging with whatever is on disk) and return the key."""
        key = schedule.spec.key(device or device_kind())
        entries = self._read_file()   # re-read: merge concurrent writers
        entries[key] = schedule
        self._flush(entries)
        self._loaded = entries
        return key

    def keys(self) -> list[str]:
        return sorted(self._entries())

    def invalidate(self) -> None:
        """Drop the in-memory view (next lookup re-reads the file)."""
        self._loaded = None
