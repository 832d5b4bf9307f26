"""Process-wide EventLog: JSONL sink + in-memory ring of typed events.

TPU-native analogue of the reference's runtime introspection spine: the
Legion profiler log that every FlexFlow analysis reads becomes one
append-only JSONL stream of schema-checked events (``schema.py``), and
the device-side ``PerfMetrics`` fold's host view rides the same stream
as ``step`` events.  One log is process-wide "active" at a time
(``set_event_log`` / the ``event_log`` context manager); producers all
over the framework (``FFModel.fit``/``train_epoch``, ``sim/search.py``,
``profiling.OpTimer``, ``bench.py``, the jax.monitoring compile hooks)
look it up with ``active_log()`` and no-op when telemetry is off — the
hot paths pay one None-check.

Emission validates against the schema and raises on drift; the cost per
event (a dict, a validation sweep, one buffered line write) is
microseconds, negligible at the intended rates (per-epoch / per-window /
per-search-iteration, never per-sample).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from .schema import validate_event


def _jsonable(v):
    """Coerce numpy/jax scalars and arrays to plain JSON types so the
    schema's isinstance checks and ``json.dumps`` both see native
    Python values."""
    kind = type(v)
    if kind is str or kind is int or kind is bool or v is None:
        return v  # what most fields are: skip the isinstance ladder
    if kind is float:
        return v if v - v == 0.0 else None  # NaN/Inf, as below
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        v = float(v)
    if isinstance(v, float) and not np.isfinite(v):
        # NaN/Inf serialize as spec-INVALID JSON tokens; None round-trips
        # (dropped as a top-level field, null inside dicts/lists)
        return None
    if isinstance(v, np.ndarray):
        return _jsonable(v.tolist())  # recurse: NaN/Inf elements -> None
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if hasattr(v, "__array__") and not isinstance(v, (str, bytes)):
        arr = np.asarray(v)  # jax device arrays of ANY rank
        return _jsonable(arr.item() if arr.ndim == 0 else arr.tolist())
    return v


class EventLog:
    """Typed event log: every ``emit`` validates against the schema,
    lands in a bounded in-memory ring, and (when ``path`` is set)
    appends one JSON line to the sink.

    ``mode="w"`` truncates (one file per run — what bench.py wants);
    the default ``"a"`` appends across restarts.

    ``stamp`` (a dict of schema COMMON_OPTIONAL fields, e.g.
    ``{"pidx": 2, "slice": 1}``) is merged into every emitted event
    that does not already carry those fields — how multi-host runs
    mark which process produced each line so ``report --fleet`` can
    merge per-process sinks (telemetry/fleet.py).
    """

    def __init__(self, path: Optional[str] = None, ring: int = 4096,
                 mode: str = "a", stamp: Optional[Dict[str, Any]] = None):
        self.path = path
        self.stamp = dict(stamp) if stamp else None
        self._ring: deque = deque(maxlen=ring)
        self._valid_shapes: set = set()
        self._lock = threading.Lock()
        self._fh = open(path, mode) if path else None

    # ------------------------------------------------------------- emission
    def emit(self, type: str, **fields) -> Dict[str, Any]:
        """Emit one event; None-valued fields are dropped (so callers can
        pass optional data unconditionally).  Raises ValueError when the
        event does not match the schema — producers and the report CLI
        must not drift apart silently.  Sink I/O is BEST-EFFORT: a write
        failure (disk full, vanished tmpfile) must never abort the
        training/search/bench run that emitted — the sink is dropped
        with one stderr warning and events keep landing in the ring."""
        ev: Dict[str, Any] = {"type": type, "ts": time.time()}
        for k, v in fields.items():
            v = _jsonable(v)  # may yield None (e.g. a NaN float): drop
            if v is not None:
                ev[k] = v
        if self.stamp:
            for k, v in self.stamp.items():
                ev.setdefault(k, v)
        # the schema's verdict depends on the event's type, its phase
        # and each field's name and runtime type, nothing else: sweep
        # once per such shape (a per-step producer repeats one shape)
        phase = ev.get("phase")
        shape = (type, phase if phase.__class__ is str else None,
                 *[(k, v.__class__) for k, v in ev.items()])
        if shape not in self._valid_shapes:
            errs = validate_event(ev)
            if errs:
                raise ValueError(f"invalid telemetry event: "
                                 f"{'; '.join(errs)} — event {ev!r}")
            if len(self._valid_shapes) < 4096:
                self._valid_shapes.add(shape)
        with self._lock:
            self._ring.append(ev)
            if self._fh is not None:
                try:
                    # default=str: a value _jsonable could not coerce
                    # degrades to its repr instead of aborting the run
                    self._fh.write(json.dumps(ev, default=str) + "\n")
                    self._fh.flush()
                except (OSError, ValueError) as e:
                    # OSError: disk full / sink vanished; ValueError:
                    # writing a closed file.  Schema errors raised above
                    # never reach this block.
                    import sys
                    print(f"# telemetry sink failed, dropping "
                          f"{self.path!r}: {e!r}", file=sys.stderr)
                    try:
                        self._fh.close()
                    except OSError:
                        pass
                    self._fh = None
        return ev

    # --------------------------------------------------------------- access
    def events(self, type: Optional[str] = None) -> List[Dict[str, Any]]:
        """Snapshot of the ring (optionally one type only), oldest first."""
        with self._lock:
            evs = list(self._ring)
        if type is not None:
            evs = [e for e in evs if e.get("type") == type]
        return evs

    def last(self, type: str) -> Optional[Dict[str, Any]]:
        """The newest event of ``type`` still in the ring, or None —
        the one-liner recovery tests use to assert "this run emitted a
        checkpoint/anomaly/fault event"."""
        evs = self.events(type)
        return evs[-1] if evs else None

    def close(self):
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------- active log
_active: Optional[EventLog] = None


def set_event_log(log: Optional[EventLog]) -> Optional[EventLog]:
    """Install ``log`` as the process-wide active log (None deactivates).
    Activating a log also installs the jax.monitoring compile hooks —
    they are global and idempotent, and no-op while no log is active.
    Returns the PREVIOUS active log so callers can restore it."""
    global _active
    prev = _active
    _active = log
    if log is not None:
        from .jax_hooks import install_compile_hooks
        install_compile_hooks()
    return prev


def active_log() -> Optional[EventLog]:
    """The producers' one-liner: the active log or None (telemetry off)."""
    return _active


def emit(type: str, **fields) -> Optional[Dict[str, Any]]:
    """Emit into the active log, or no-op when telemetry is off."""
    log = _active
    if log is None:
        return None
    return log.emit(type, **fields)


@contextlib.contextmanager
def suppressed():
    """Silence all producers for the block (timed measurement windows:
    an emit+flush between a timer start and its fence perturbs the wall
    it is recording), restoring the previous active log on exit."""
    prev = set_event_log(None)
    try:
        yield
    finally:
        set_event_log(prev)


@contextlib.contextmanager
def event_log(path: Optional[str] = None, ring: int = 4096, mode: str = "a",
              stamp: Optional[Dict[str, Any]] = None):
    """Scoped telemetry: activate a fresh EventLog for the block, restore
    the previous active log (and close this one) on exit."""
    log = EventLog(path=path, ring=ring, mode=mode, stamp=stamp)
    prev = set_event_log(log)
    try:
        yield log
    finally:
        set_event_log(prev)
        log.close()


# ------------------------------------------------------------ memory events
def sample_memory(phase: Optional[str] = None,
                  log: Optional[EventLog] = None) -> int:
    """Emit one ``memory`` event per local device with allocator stats
    (TPU ``memory_stats``), or one aggregate host-side fallback event
    summing live jax array bytes (CPU test meshes, where the allocator
    exposes nothing).  Returns the number of events emitted; no-op when
    telemetry is off."""
    log = log or _active
    if log is None:
        return 0
    import jax

    emitted = 0
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            log.emit("memory", device=str(d),
                     bytes_in_use=int(ms.get("bytes_in_use", 0)),
                     peak_bytes=(int(ms["peak_bytes_in_use"])
                                 if "peak_bytes_in_use" in ms else None),
                     source="memory_stats", phase=phase)
            emitted += 1
    if emitted == 0:
        live = sum(int(a.nbytes) for a in jax.live_arrays())
        log.emit("memory", device="all", bytes_in_use=live,
                 source="live_arrays", phase=phase)
        emitted = 1
    return emitted
