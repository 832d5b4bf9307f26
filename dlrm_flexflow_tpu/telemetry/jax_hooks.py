"""jax.monitoring hooks -> the set-up timeline, ``compile`` events and
running counters.

The reference memoizes its per-iteration task graph with Legion tracing
(``-dm:memoize``) and a recompilation there is visible as a trace
re-capture; here the analogous event is an XLA backend compile (a jit
cache MISS — cache hits take the C++ fast path and emit no monitoring
event).  ``install_compile_hooks`` registers process-global listeners
once.  From then on every compile stage JAX reports becomes one record
of the **set-up timeline** (docs/telemetry.md, "Set-up timeline"),
whether or not an EventLog is active:

  * ``trace``      — ``jaxpr_trace_duration`` (Python -> jaxpr)
  * ``lower``      — ``jaxpr_to_mlir_module_duration`` (jaxpr -> MLIR,
                     the Mosaic lowering of Pallas kernels included)
  * ``compile``    — ``backend_compile_duration`` (XLA; wraps the
                     persistent cache's read where there is one)
  * ``cache_load`` — ``cache_retrieval_time_sec`` (an executable read
                     back from the persistent cache)

each with its program (``fun_name`` with ``jit(...)`` stripped; a cache
load, hit or write belongs to the ``compile`` that encloses it) and
``start`` / ``end`` on ``time.perf_counter``, the clock of the
benchmark's ``T_START`` and of the window's ``t0`` (JAX's own spans are
on ``time.time``).  The program adds its own marks (``setup_mark``:
``FFModel.__init__``'s ``setup.model``) and spans (``setup_span``:
``FFModel.compile``'s ``setup.graph``).  ``setup_breakdown(until)``
reads one set-up from the timeline.  A steady window traces and
compiles nothing, so the timeline costs it nothing.

Each backend compile is also one ``compile`` event in the active
EventLog (no-op while telemetry is off), and ``compile_stats`` keeps
the running counters that ``benchmarks/run.py`` prints and
``compile_s`` reads.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_installed = False
_lock = threading.Lock()
_tls = threading.local()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: monitoring event -> stage of the set-up timeline
_STAGES = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: stage -> the name of its running counter in ``compile_stats``
_COUNTERS = {"trace": "jaxpr_trace", "lower": "jaxpr_to_mlir",
             "compile": "backend_compile"}

_counters: Dict[str, float] = {}

#: records kept; past it the oldest is evicted (``_evict``)
MAX_RECORDS = 1 << 16


class _Record:
    """One stage, mark or span of the timeline.  ``cache``: on a
    ``compile``, ``"hit"`` (read from the persistent cache) or
    ``"written"`` (compiled and written to it); None where no persistent
    cache was asked."""

    __slots__ = ("stage", "program", "start", "end", "cache")

    def __init__(self, stage, program, start, end, cache=None):
        self.stage, self.program = stage, program
        self.start, self.end, self.cache = start, end, cache


_records: deque = deque()
_model_marks: List[float] = []  # newest last; bounded like the records
#: what eviction took from the set-up of the newest ``setup.model``
#: mark: {stage: [seconds summed (not united), compiled programs]}
_folded: Dict[str, list] = {}
_overflow = 0  # records evicted since the newest mark


def _program(fun_name) -> Optional[str]:
    if fun_name is None:
        return None
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _evict():
    global _overflow
    old = _records.popleft()
    if _model_marks and old.end >= _model_marks[-1]:
        _overflow += 1
        entry = _folded.setdefault(old.stage, [0.0, 0])
        entry[0] += old.end - old.start
        entry[1] += old.stage == "compile" and old.cache != "hit"


def _append(rec: _Record):
    with _lock:
        _records.append(rec)
        if len(_records) > MAX_RECORDS:
            _evict()


def _open_compile():
    """This thread's cache loads and cache outcome since its last
    ``compile`` record: the compile that closes next holds them."""
    if not hasattr(_tls, "loads"):
        _tls.loads, _tls.cache = [], None
    return _tls


def _on_scalar(event: str, value, **_kw):
    # LogElapsedTimeContextManager reports each stage's start as a
    # scalar: count open traces so only the outermost one is counted
    if event == _TRACE_EVENT:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def _on_duration(event: str, duration: float, fun_name=None, **_kw):
    stage = _STAGES.get(event)
    if stage is None:
        return
    end = time.perf_counter()
    duration = float(duration)
    rec = _Record(stage, _program(fun_name), end - duration, end)
    counter = _COUNTERS.get(stage)
    if stage == "trace":
        _tls.depth = depth = max(getattr(_tls, "depth", 0) - 1, 0)
        if depth:  # nested in an open trace: that one counts it
            counter = None
    elif stage == "cache_load":
        _open_compile().loads.append(rec)
    elif stage == "compile":
        tls = _open_compile()
        for load in tls.loads:
            load.program = rec.program
        rec.cache = tls.cache
        tls.loads, tls.cache = [], None
    if counter is not None:
        with _lock:
            _counters[counter] = _counters.get(counter, 0) + 1
            _counters[counter + "_s"] = (_counters.get(counter + "_s", 0.0)
                                         + duration)
    _append(rec)
    if stage != "compile":
        return
    from .events import active_log
    log = active_log()
    if log is not None:
        import jax
        log.emit("compile", kind="backend_compile", duration_s=duration,
                 fn=rec.program or "?",
                 stage="cache_load" if rec.cache == "hit" else "compile",
                 backend=jax.default_backend())


def _on_event(event: str, **_kw):
    if event.startswith("/jax/compilation_cache/"):
        # "cache_hits": an executable read back from the persistent
        # cache; "cache_misses": one compiled and written to it.  Both
        # fire inside the compile that the next record closes
        leaf = event.rsplit("/", 1)[1]
        if leaf in ("cache_hits", "cache_misses"):
            with _lock:
                _counters[leaf] = _counters.get(leaf, 0) + 1
            _open_compile().cache = ("hit" if leaf == "cache_hits"
                                     else "written")


def install_compile_hooks() -> bool:
    """Register the jax.monitoring listeners (idempotent; listeners are
    process-global and cannot be unregistered individually, so they stay
    installed).  Returns True when this call did the installation."""
    global _installed
    with _lock:
        if _installed:
            return False
        _installed = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    return True


def compile_stats() -> Dict[str, float]:
    """Snapshot of the running counters: per-stage counts and total
    seconds (``backend_compile``, ``jaxpr_trace`` — outermost traces
    only, a trace inside another is that one's time — and
    ``jaxpr_to_mlir``), and the persistent cache's ``cache_hits`` and
    ``cache_misses`` (compiled and written).  ``backend_compile_s``
    holds every backend compile's wall, cache reads included."""
    with _lock:
        return dict(_counters)


# ------------------------------------------------------------ the program
def setup_mark(name: str) -> None:
    """An instant of the program's set-up on the timeline.
    ``setup.model`` (``FFModel.__init__``) starts one set-up: that is
    where ``setup_breakdown`` begins."""
    global _overflow
    now = time.perf_counter()
    with _lock:
        if name == "setup.model":
            _model_marks.append(now)
            if len(_model_marks) > MAX_RECORDS:
                del _model_marks[0]
            _folded.clear()
            _overflow = 0
    _append(_Record(name, None, now, now))


@contextlib.contextmanager
def setup_span(name: str):
    """A region of the program's set-up on the timeline, and, while an
    EventLog is active, a ``span`` event of the same name (annotated,
    so a profiler trace shows it too).  Usable as a decorator."""
    from .trace import span

    start = time.perf_counter()
    try:
        with span(name):
            yield
    finally:
        _append(_Record(name, None, start, time.perf_counter()))


def timeline() -> List[dict]:
    """The records kept, oldest first, as dicts."""
    with _lock:
        return [{k: getattr(r, k) for k in _Record.__slots__}
                for r in _records]


def _merged(spans) -> list:
    out: list = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(spans) -> float:
    return sum(e - s for s, e in spans)


def _covered(a: list, b: list) -> float:
    """Seconds of the merged intervals ``a`` that the merged ``b``
    cover."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def setup_breakdown(until: Optional[float] = None) -> Optional[dict]:
    """One set-up, read from the timeline: from the newest
    ``setup.model`` mark before ``until`` (``time.perf_counter``;
    default now) up to ``until``.  None where no mark precedes it.

    A stage's seconds are the union of its intervals (nested traces
    count once), less what a later stage holds inside them (a compile
    during a trace is compile time), so ``trace_s + lower_s +
    compile_s <= own_s``; ``cache_load_s`` lies inside ``compile_s``.
    ``compiled_programs`` counts the compiles the persistent cache did
    not serve, ``loaded_programs`` those it did; ``programs`` is
    ``{program: {stage seconds, compiles, loaded}}``, each stage's
    seconds summed (a nested trace also under its outer program);
    ``graph_s`` the ``setup.graph`` spans; ``overflow`` the records of
    this set-up the bound evicted, whose seconds are summed in."""
    until = time.perf_counter() if until is None else float(until)
    with _lock:
        marks = [t for t in _model_marks if t <= until]
        if not marks:
            return None
        start = marks[-1]
        recs = [r for r in _records if r.end >= start and r.start <= until]
        current = start == _model_marks[-1]  # eviction folds only this one
        folded = {k: list(v) for k, v in _folded.items()} if current else {}
        overflow = _overflow if current else 0
    spans: Dict[str, list] = {}
    programs: Dict[str, dict] = {}
    compiled = loaded = 0
    for r in recs:
        s, e = max(r.start, start), min(r.end, until)
        spans.setdefault(r.stage, []).append((s, e))
        if r.stage not in _COUNTERS and r.stage != "cache_load":
            continue
        row = programs.setdefault(r.program or "?", {
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_load_s": 0.0, "compiles": 0, "loaded": 0})
        row[r.stage + "_s"] += e - s
        if r.stage == "compile":
            hit = r.cache == "hit"
            row["loaded" if hit else "compiles"] += 1
            loaded += hit
            compiled += not hit
    compile_ = _merged(spans.get("compile", []))
    lower = _merged(spans.get("lower", []))
    later = _merged(spans.get("compile", []) + spans.get("lower", []))
    trace = _merged(spans.get("trace", []))
    out = {"start": start, "until": until, "own_s": until - start,
           "trace_s": _length(trace) - _covered(trace, later),
           "lower_s": _length(lower) - _covered(lower, compile_),
           "compile_s": _length(compile_),
           "cache_load_s": _length(_merged(spans.get("cache_load", []))),
           "compiled_programs": compiled, "loaded_programs": loaded,
           "graph_s": _length(_merged(spans.get("setup.graph", []))),
           "programs": programs, "overflow": overflow}
    for stage, (seconds, programs_compiled) in folded.items():
        if stage + "_s" in out:
            out[stage + "_s"] += seconds
        out["compiled_programs"] += programs_compiled
    return out
