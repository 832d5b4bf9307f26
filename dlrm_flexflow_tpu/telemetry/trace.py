"""Dapper-style span tracing over the EventLog (docs/telemetry.md).

The EventLog records *flat* events; production triage needs *causal
chains*: a serving request that waits in the DynamicBatcher queue,
rides a padded bucket through the AOT forward, and is replied to (or
shed, or deadline-missed) is one trace of parented spans, and a
training run is a ``fit → epoch → dispatch → checkpoint/rollback``
chain.  A :class:`Span` is a timed, attributed region with identity
(``trace_id``/``span_id``/``parent_id``); closing it emits ONE
schema-checked ``span`` event into the active EventLog, so traces ride
the same JSONL as every other event and the ``export-trace`` CLI
(telemetry/exporter.py) renders them on per-thread Perfetto tracks.

Two APIs, both thread-safe:

* implicit — ``with span("name"):`` parents to the per-thread current
  span (a thread-local stack), the right tool for nested regions on
  one thread;
* explicit — ``start_span(...)`` / ``Span.end(status)`` for regions
  that OPEN on one thread and CLOSE on another (a serving request's
  root span opens at ``submit`` on the client thread and closes on the
  dispatcher thread), plus ``record_span`` for synthesizing an
  already-timed child (the per-request ``serve.forward`` span shares
  the batch's one engine wall).

Tracing is OFF unless an EventLog is active: every entry point checks
``active_log()`` once and returns the :data:`NULL_SPAN` no-op, so
traced code paths pay one global read when telemetry is off.  A span
ends EXACTLY once — the first ``end`` wins (lock-guarded), later calls
no-op — which is what lets shutdown races (drain vs. cancel vs. a
racing dispatcher) double-close safely.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Union

from .events import active_log, emit

_tls = threading.local()

# Open-span registry for the flight recorder (telemetry/fleet.py): when
# a run dies, the spans still open at death are the regions it died
# INSIDE — exactly what a post-mortem wants.  LOCK-FREE BY CONSTRUCTION:
# the recorder's crash-path read may run while arbitrary other threads
# hold arbitrary locks (it fires inside exception handling), so the
# registry is a plain dict of weakrefs mutated only through atomic
# single-bytecode dict ops (item assignment / ``pop``) and read through
# a ``list()`` snapshot — no lock to deadlock on, and weakrefs mean an
# abandoned span (never ended, log deactivated) cannot leak.
_open_spans: Dict[str, "weakref.ref[Span]"] = {}


def _register_open(sp: "Span") -> None:
    if len(_open_spans) > 8192:  # prune dead refs, bound the table
        for key in [k for k, r in list(_open_spans.items())
                    if r() is None]:
            _open_spans.pop(key, None)
    _open_spans[sp.span_id] = weakref.ref(sp)


def open_span_records() -> List[Dict[str, Any]]:
    """Snapshot of every span opened but not yet ended, as plain dicts
    (ready for the flight-recorder JSON).  ``age_us`` is how long each
    has been open.  Safe to call from an exception handler on any
    thread: no locks taken, a span ending concurrently is simply
    skipped or included with its last-known attrs."""
    now = time.perf_counter()
    out: List[Dict[str, Any]] = []
    for ref in list(_open_spans.values()):
        sp = ref()
        if sp is None or sp.ended:
            continue
        out.append({"name": sp.name, "trace_id": sp.trace_id,
                    "span_id": sp.span_id, "parent_id": sp.parent_id,
                    "start_s": sp._start_s,
                    "age_us": (now - sp._t0) * 1e6,
                    "thread": sp._thread, "tid": sp._tid,
                    "attrs": (dict(sp.attrs) if sp.attrs else None)})
    return out


# Span/trace ids: 4 random bytes drawn once per process (again in a
# forked child) + a 4-byte counter.  Unique within a process by
# construction and across a fleet's processes as far as 32 random bits
# go; a per-span os.urandom bought nothing more and was a syscall on
# the per-step path.
_id_base = os.urandom(4).hex()
_id_count = itertools.count(1)


def _reseed_ids() -> None:
    global _id_base, _id_count
    _id_base, _id_count = os.urandom(4).hex(), itertools.count(1)


os.register_at_fork(after_in_child=_reseed_ids)


def _rand_id() -> str:
    return f"{_id_base}{next(_id_count) & 0xffffffff:08x}"


_TraceAnnotation = None


def _annotation(name: str):
    """A started ``jax.profiler.TraceAnnotation`` (constructing one
    stamps its start; ``__exit__`` records the slice).  jax is imported
    on first use, like everywhere in this package."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


class _NullSpan:
    """The no-op span every API returns while tracing is off: swallows
    attrs and ends, is falsy, and parents nothing."""

    __slots__ = ()
    name = None
    trace_id = None
    span_id = None
    parent_id = None

    def set_attr(self, key, value):
        return self

    def end(self, status: str = "ok", dur_us: Optional[float] = None):
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


NULL_SPAN = _NullSpan()
SpanLike = Union["Span", _NullSpan]


class Span:
    """One timed region.  Construct via :func:`start_span` /
    :func:`span` (they handle the tracing-off no-op and parenting);
    close with :meth:`end` — idempotent, first close wins and emits the
    ``span`` event.  ``thread``/``tid`` record the OPENING thread (the
    region's origin — a request span that closes on the dispatcher
    still belongs to its client's track).

    ``annotate=True`` — for a span that opens and closes on ONE thread
    (the scoped ``span()`` always; ``start_span`` callers that own both
    ends, as the training loops do) — also holds a
    ``jax.profiler.TraceAnnotation`` of the span's name for its
    lifetime, so a profiler trace shows the span on the host thread's
    track, on the trace's own clock, beside the device's gaps.  It
    costs under a microsecond while no profiler runs."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "status", "_start_s", "_t0", "_thread", "_tid",
                 "_lock", "_ended", "_annotation", "__weakref__")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None,
                 start_s: Optional[float] = None,
                 t0: Optional[float] = None, annotate: bool = False):
        self.name = str(name)
        self.trace_id = trace_id or _rand_id()
        self.span_id = _rand_id()
        self.parent_id = parent_id
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.status: Optional[str] = None
        self._start_s = time.time() if start_s is None else float(start_s)
        self._t0 = time.perf_counter() if t0 is None else float(t0)
        th = threading.current_thread()
        self._thread = th.name
        self._tid = int(th.ident or 0)
        self._lock = threading.Lock()
        self._ended = False
        self._annotation = _annotation(self.name) if annotate else None
        _register_open(self)

    def set_attr(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    @property
    def ended(self) -> bool:
        return self._ended

    def end(self, status: str = "ok",
            dur_us: Optional[float] = None) -> Optional[dict]:
        """Close the span and emit its event (into whatever log is
        active NOW — a span outliving its log is silently dropped, like
        every producer).  Exactly-once: only the first call emits;
        later calls return None."""
        with self._lock:
            if self._ended:
                return None
            self._ended = True
        _open_spans.pop(self.span_id, None)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if dur_us is None:
            dur_us = (time.perf_counter() - self._t0) * 1e6
        self.status = status
        return emit("span", name=self.name, trace_id=self.trace_id,
                    span_id=self.span_id, parent_id=self.parent_id,
                    start_s=self._start_s, start_mono_s=self._t0,
                    dur_us=float(dur_us),
                    status=status, attrs=(self.attrs or None),
                    thread=self._thread, tid=self._tid)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        self.end(status="error" if exc_type is not None else "ok")
        return False

    def __bool__(self):
        return True

    def __repr__(self):
        return (f"Span({self.name!r}, trace={self.trace_id}, "
                f"span={self.span_id}, ended={self._ended})")


# ------------------------------------------------------- per-thread current
def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span() -> Optional[Span]:
    """This thread's innermost open span (the implicit parent), or
    None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def push_span(sp: SpanLike) -> SpanLike:
    """Make ``sp`` this thread's current span (explicit-API callers
    that cannot use the ``span()`` context manager without reindenting
    a whole loop body pair this with :func:`pop_span` in a
    try/finally).  No-op for the null span."""
    if sp:
        _stack().append(sp)
    return sp


def pop_span(sp: SpanLike) -> None:
    """Undo :func:`push_span` (tolerant: pops ``sp`` wherever it sits,
    no-ops when absent)."""
    if not sp:
        return
    st = _stack()
    if st and st[-1] is sp:
        st.pop()
    elif sp in st:
        st.remove(sp)


# ------------------------------------------------------------------ opening
def start_span(name: str, parent: Optional[SpanLike] = None,
               attrs: Optional[Dict[str, Any]] = None,
               annotate: bool = False) -> SpanLike:
    """Open a span (tracing off -> :data:`NULL_SPAN`).  ``parent``
    defaults to this thread's current span; a parentless span roots a
    fresh trace.  The caller owns closing it (``end``) — use
    :func:`span` for scoped regions.  ``annotate``: the caller closes
    it on this same thread (see :class:`Span`)."""
    if active_log() is None:
        return NULL_SPAN
    if parent is None:
        parent = current_span()
    if not parent:
        return Span(name, attrs=attrs, annotate=annotate)
    return Span(name, trace_id=parent.trace_id, parent_id=parent.span_id,
                attrs=attrs, annotate=annotate)


@contextlib.contextmanager
def span(name: str, attrs: Optional[Dict[str, Any]] = None,
         parent: Optional[SpanLike] = None):
    """Scoped span: opens, becomes the thread's current span for the
    block (children parent to it implicitly), and closes on exit —
    ``status="error"`` when the block raised, ``"ok"`` otherwise unless
    the body already ended it with its own status."""
    sp = start_span(name, parent=parent, attrs=attrs, annotate=True)
    if not sp:
        yield sp
        return
    push_span(sp)
    try:
        yield sp
    except BaseException:
        pop_span(sp)
        sp.end(status="error")
        raise
    else:
        pop_span(sp)
        sp.end()


def record_span(name: str, start_s: float, dur_us: float,
                parent: Optional[SpanLike] = None,
                status: str = "ok",
                attrs: Optional[Dict[str, Any]] = None) -> Optional[dict]:
    """Emit one already-timed span (opened and closed in the past) —
    how the batcher gives EVERY request of a micro-batch its own
    ``serve.forward`` child sharing the batch's single engine wall.
    No-op when tracing is off or ``parent`` is the null span (the
    request was submitted while tracing was off: there is no trace to
    join)."""
    if active_log() is None:
        return None
    if parent is not None and not parent:
        return None
    th = threading.current_thread()
    # no start_mono_s: the span was timed elsewhere, on the wall clock
    return emit("span", name=str(name),
                trace_id=(parent.trace_id if parent else _rand_id()),
                span_id=_rand_id(),
                parent_id=(parent.span_id if parent else None),
                start_s=float(start_s), dur_us=float(dur_us),
                status=status, attrs=(dict(attrs) if attrs else None),
                thread=th.name, tid=int(th.ident or 0))
