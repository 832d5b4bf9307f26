"""Unified training telemetry (docs/telemetry.md).

One process-wide ``EventLog`` (JSONL sink + in-memory ring) records
typed, schema-checked events from every layer of the framework:

  * ``step``    — epoch/window wall time, samples/s, loss, metric means
                  (FFModel.fit / train_epoch / bench.py windows)
  * ``compile`` — XLA compiles (jit cache misses) observed through
                  jax.monitoring hooks, each with its program and
                  whether the persistent cache served it, plus fit's
                  AOT compiles with their donated-argument counts
  * ``memory``  — per-device live-bytes watermarks sampled around steps
  * ``search``  — MCMC strategy-search trajectory and simulator
                  calibration fits (sim/search.py, sim/simulator.py)
  * ``op_time`` — per-op measured forward/backward next to the analytic
                  simulator's prediction (profiling.OpTimer)
  * ``serve``   — online-serving dispatches, shed requests, and latency
                  summaries (serving/, docs/serving.md)
  * ``elastic`` — topology changes absorbed at runtime: cross-mesh
                  checkpoint reshards, live replica resizes, incumbent
                  re-gates (elastic/, docs/elastic.md)
  * ``span``    — Dapper-style causal spans: serving request chains
                  (submit → queue-wait → forward → reply) and training
                  chains (fit → epoch → dispatch → checkpoint)
                  (telemetry/trace.py)
  * ``phase_time`` — per-phase step walls (data wait / dispatch /
                  grad-sync wait) and the measured exposed-comm share
                  next to the cost model's prediction (the fit loops)
  * ``row_freq`` — per-table embedding row-access frequency summaries
                  (telemetry/rowfreq.py — LFU admission input)
  * ``storage`` — tiered embedding store admissions, evictions, and
                  miss-stream stalls (storage/tiered.py,
                  docs/storage.md)
  * ``slo``     — serving SLO evaluations, multi-window burn-rate
                  breaches, and recoveries (telemetry/slo.py,
                  docs/slo.md)

The set-up has a timeline of its own, kept whether or not a log is
active (``jax_hooks``): every trace, lowering, compile and cache load
JAX reports, by program, on ``time.perf_counter``, beside the
program's ``setup.model`` mark and ``setup.graph`` span;
``setup_breakdown(until)`` reads one set-up from it.

Multi-host runs write one ``telemetry_pNNN.jsonl`` sink per process,
stamped with ``pidx``/``slice`` (``fleet_event_log``); ``report`` on
the directory (or ``--fleet DIR``) merges them and renders straggler
skew, per-slice throughput, and the exposed-comm fraction.  A dying
``resilient_fit`` dumps its EventLog ring + open spans to
``artifacts/flightrecorder_<ts>.json`` (``dump_flight_record``;
``report --flight PATH`` renders it).

Activate with ``set_event_log(EventLog(path=...))`` or the scoped
``event_log(...)`` context manager; producers no-op when telemetry is
off.  ``python -m dlrm_flexflow_tpu.telemetry report run.jsonl``
(``--format json`` for the machine-readable object) prints the per-op
time table, compile timeline, throughput summary, sim-vs-measured
calibration error, and span roll-up; ``export-trace`` renders the run
for https://ui.perfetto.dev; ``regress`` gates a fresh BENCH result
against a baseline.  Live metrics (telemetry/metrics.py) are exposed
as Prometheus text at ``/metrics`` by ``telemetry/exporter.py`` —
opt-in via ``FFConfig.metrics_port`` / ``--metrics-port``.
"""

from .events import (EventLog, active_log, emit, event_log,
                     sample_memory, set_event_log, suppressed)
from .fleet import (dump_flight_record, find_flight_records,
                    fleet_data, fleet_event_log, fleet_stamp,
                    load_fleet_events, load_flight_record,
                    process_sink_path)
from .jax_hooks import (compile_stats, install_compile_hooks,
                        setup_breakdown, setup_mark, setup_span, timeline)
from .rowfreq import RowFreqCounter, hot_rows
from .schema import SCHEMA, SCHEMA_VERSION, validate_event
from .slo import SLO, SLOMonitor, parse_slos
from .trace import (NULL_SPAN, Span, current_span, open_span_records,
                    record_span, span, start_span)

__all__ = [
    "EventLog", "active_log", "emit", "event_log",
    "sample_memory", "set_event_log", "suppressed", "compile_stats",
    "install_compile_hooks", "setup_breakdown", "setup_mark",
    "setup_span", "timeline", "SCHEMA", "SCHEMA_VERSION", "validate_event",
    "NULL_SPAN", "Span", "current_span", "open_span_records",
    "record_span", "span", "start_span",
    "dump_flight_record", "find_flight_records", "fleet_data",
    "fleet_event_log", "fleet_stamp", "load_fleet_events",
    "load_flight_record", "process_sink_path", "RowFreqCounter",
    "hot_rows", "SLO", "SLOMonitor", "parse_slos",
]
