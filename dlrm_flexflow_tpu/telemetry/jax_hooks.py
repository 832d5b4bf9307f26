"""jax.monitoring hooks -> ``compile`` telemetry events.

The reference memoizes its per-iteration task graph with Legion tracing
(``-dm:memoize``) and a recompilation there is visible as a trace
re-capture; here the analogous event is an XLA backend compile (a jit
cache MISS — cache hits take the C++ fast path and emit no monitoring
event, so "hit counts" are not observable from Python; what IS
observable, and what matters for perf triage, is every miss and its
wall time).  ``install_compile_hooks`` registers process-global
listeners once; each observed backend compile becomes one ``compile``
event in the active EventLog (no-op while telemetry is off), and
``compile_stats`` exposes the running counters (all trace/lower/compile
stages, plus compilation-cache activity) for report summaries.
"""

from __future__ import annotations

import threading
from typing import Dict

_installed = False
_lock = threading.Lock()

#: monitoring event name -> short kind.  Only "backend_compile" becomes
#: an EventLog event (it is the actual XLA compile — the costly miss);
#: the trace/lower stages fire on every trace and are only counted.
_DURATION_KINDS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
}

_counters: Dict[str, float] = {}


def _bump(key: str, dur: float):
    with _lock:
        _counters[key] = _counters.get(key, 0) + 1
        _counters[key + "_s"] = _counters.get(key + "_s", 0.0) + dur


def _on_duration(event: str, duration: float, **_kw):
    kind = _DURATION_KINDS.get(event)
    if kind is None:
        return
    _bump(kind, float(duration))
    if kind != "backend_compile":
        return
    from .events import active_log
    log = active_log()
    if log is not None:
        import jax
        log.emit("compile", kind=kind, duration_s=float(duration),
                 backend=jax.default_backend())


def _on_event(event: str, **_kw):
    if event.startswith("/jax/compilation_cache/"):
        # "cache_hits": an executable read back from the persistent
        # cache; "cache_misses": one compiled and written to it
        leaf = event.rsplit("/", 1)[1]
        with _lock:
            _counters["cache_events"] = _counters.get("cache_events", 0) + 1
            if leaf in ("cache_hits", "cache_misses"):
                _counters[leaf] = _counters.get(leaf, 0) + 1


def install_compile_hooks() -> bool:
    """Register the jax.monitoring listeners (idempotent; listeners are
    process-global and cannot be unregistered individually, so they stay
    installed and no-op while no EventLog is active).  Returns True when
    this call did the installation."""
    global _installed
    with _lock:
        if _installed:
            return False
        _installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    return True


def compile_stats() -> Dict[str, float]:
    """Snapshot of the running counters: per-stage counts and total
    seconds (``backend_compile``, ``jaxpr_trace``, ``jaxpr_to_mlir``)
    plus ``cache_events`` (persistent-compilation-cache activity) and,
    of those, ``cache_hits`` and ``cache_misses``."""
    with _lock:
        return dict(_counters)
