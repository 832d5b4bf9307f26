"""Telemetry schema + metrics-registry lint (tier-1:
tests/test_telemetry.py runs it).

Guards the three-way contract between the event producers (model.py,
bench.py, sim/search.py, sim/simulator.py, profiling.OpTimer, the
jax.monitoring hooks, telemetry/trace.py spans),
``telemetry/schema.py``, and the documented schema in
``docs/telemetry.md`` — so a producer cannot add, rename, or retype a
field without the schema and the report CLI seeing it:

  1. self-consistency — a maximal example event of every type (all
     required + optional fields) must pass ``validate_event`` through
     the real ``EventLog.emit`` path;
  2. doc sync — every event type and every field named in the schema
     must appear in docs/telemetry.md, and every ```` `type` ````-headed
     event section in the doc must exist in the schema;
  3. producer scan — every ``*.emit("<type>", field=...)`` call in the
     package (AST walk, no regex guessing) must name a known event type
     and only known fields for it;
  4. metrics-name registry — every family the default
     ``telemetry.metrics.REGISTRY`` exposes must be declared in
     ``metrics.FAMILIES`` (and vice versa: no dead declarations), names
     must be valid Prometheus identifiers with counter families ending
     ``_total``, the rendered exposition must carry each family exactly
     once (no duplicates), and every family must be documented in
     docs/telemetry.md;
  5. tuning-artifact contract — every field of the calibration and
     strategy artifact schemas (``sim/tune.py``) must be documented in
     docs/tuning.md, the example artifacts must validate, and the
     promotion gate's metric name must gate UPWARD
     (``regress.lower_is_better``) so a slower candidate can never
     read as an improvement;
  6. input-pipeline contract — the pipelined hot loop's step-event
     fields (``data_stall_ms``/``dispatch_ms``/``host_overhead_pct``)
     must be declared in the step schema, the ``dlrm_data_stall_pct``
     family must be declared, both must be documented in
     docs/pipeline.md (next to the ``prefetch_depth``/``--prefetch``
     knobs), and the overhead/stall names must gate UPWARD in the
     regress CLI so a host-path regression reads as a regression;
  7. elastic contract — the ``elastic`` event type must carry the
     reshard/scale/regate phases, its metric families
     (``dlrm_elastic_reshard_total``, ``dlrm_serve_replicas``) must be
     declared, docs/elastic.md must document the subsystem's entry
     points next to them, and the regress anchor keys must keep the
     ``:mesh=``/``:replicas=`` topology suffixes so an elastic run can
     never gate against a different topology's baseline;
  8. exchange-overlap contract — the overlapped-exchange knobs
     (``exchange_overlap``/``--exchange-overlap``/``BENCH_OVERLAP``,
     the ``FF_EXCHANGE_OVERLAP`` dispatch override, the microbatch
     count) must be documented in docs/pipeline.md next to the
     host-side pipeline they mirror, and the regress anchor keys must
     keep the ``:overlap=`` suffix (the pipeline reorders collective
     reductions, so an overlapped run must never gate a serial
     baseline);
  9. pod-scale contract — the multi-host knobs and layouts
     (``host_local_batch``/``make_global_array``/``HostShardLoader``,
     the ``PodTopology`` two-level cost model, the ``multihost``
     checkpoint mode's ``shard-p*`` layout) must be documented in
     docs/distributed.md, the per-process metric families
     (``dlrm_process_index``/``dlrm_process_count``) declared, the
     ``distributed`` bootstrap event present, and the regress anchor
     keys must keep the ``:hosts=``/``:slices=`` topology suffixes so
     a multi-host run never gates a single-host baseline;
 10. fleet-observability contract — the ``phase_time``/``row_freq``
     event types must carry their attribution fields, the optional
     ``pidx``/``slice`` stamp must be accepted on every event type,
     the straggler/exposed-comm gauges (``dlrm_step_skew_ms``,
     ``dlrm_exposed_comm_pct``) must be declared, skew must gate
     UPWARD in the regress CLI (lower is better), and the per-process
     sink naming + ``--fleet``/``--flight`` report modes must be
     documented in docs/telemetry.md;
 11. recovery contract — the ``recovery`` event type must carry every
     failure-domain phase (heartbeat death, barrier timeout, stall,
     survivor resume, replica ejection, dispatcher death), the
     watchdog gauge (``dlrm_host_heartbeat_age_s``) and ejection
     counter (``dlrm_serve_replica_ejected_total``) must be declared,
     the host-loss fault kinds must parse (including the ``barrier``
     injection point), and docs/resilience.md, docs/distributed.md,
     and docs/serving.md must document the watchdog/recovery/ejection
     entry points next to each other;
 12. tiered-storage contract — the ``storage`` event type must carry
     the admit/evict/miss phases, the cache gauges
     (``dlrm_embed_cache_hit_pct``,
     ``dlrm_embed_cache_miss_stall_us``) must be declared with the
     stall gating UPWARD and the hit rate NOT, docs/storage.md must
     document the subsystem's knobs and entry points, and the regress
     anchor keys must keep the ``:storage=`` suffix so a hot-cache
     run (which pays miss stalls by design) can never gate the
     fully-resident baseline;
 13. SLO contract — the ``slo`` event type must carry the
     eval/breach/recover phases, the objective gauge families
     (``dlrm_slo_error_budget_pct``, ``dlrm_slo_burn_rate``) and the
     cause-split shed counter (``dlrm_serve_shed_total``) must be
     declared, the burn rate must gate UPWARD in the regress CLI (a
     rising burn spends budget faster, so it must never read as an
     improvement), and docs/slo.md must document the spec
     mini-language, the burn-rate windows, the tail exemplars, and the
     breach → flight-record flow;
 14. phase contract — every ``jax.named_scope`` literal that
     ``model.py`` opens must be a phase by ``profiling.phase_of``'s
     naming rule (and classify as itself) and be documented in
     docs/telemetry.md, the ``program`` event type and the span's
     ``start_mono_s`` must be in the schema, and the training path's
     span names (``train.fit`` … ``train.launch``) documented.

Exit 0 when clean; prints one line per violation and exits 1 otherwise.
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dlrm_flexflow_tpu.telemetry.events import EventLog  # noqa: E402
from dlrm_flexflow_tpu.telemetry.schema import (COMMON_REQUIRED,  # noqa: E402
                                                SCHEMA)

#: example value per declared type, rich enough to satisfy validation
_EXAMPLE = {float: 0.5, int: 3, str: "x", bool: True,
            dict: {"k": 1.0}, list: [1, 2]}

#: files whose ``emit(...)`` calls the producer scan covers (loaded
#: through the shared analysis-engine walker — ONE loader for every
#: AST-based lint, see dlrm_flexflow_tpu/analysis/engine.py)
_SCAN = ["bench.py", "dlrm_flexflow_tpu"]


def _example_event(etype: str, spec: dict) -> dict:
    ev = {}
    for name, decl in {**spec["required"], **spec["optional"]}.items():
        ev[name] = _EXAMPLE[decl]
    phases = spec.get("phases")
    if phases is not None:
        # pick the phase whose extra requirements the example satisfies
        # (all optional fields are present, so any phase works)
        ev["phase"] = sorted(phases)[0]
    return ev


def check_self_consistency() -> list:
    errs = []
    log = EventLog()  # ring only, no sink
    for etype, spec in sorted(SCHEMA.items()):
        for field in ("required", "optional"):
            if not isinstance(spec.get(field), dict):
                errs.append(f"schema[{etype}].{field} is not a dict")
                return errs
        overlap = set(spec["required"]) & set(spec["optional"])
        if overlap:
            errs.append(f"schema[{etype}]: fields both required and "
                        f"optional: {sorted(overlap)}")
        clash = (set(spec["required"]) | set(spec["optional"])) \
            & set(COMMON_REQUIRED)
        if clash:
            errs.append(f"schema[{etype}]: redefines common fields "
                        f"{sorted(clash)}")
        try:
            log.emit(etype, **_example_event(etype, spec))
        except ValueError as e:
            errs.append(f"schema[{etype}]: maximal example rejected by "
                        f"EventLog.emit: {e}")
    return errs


def check_doc_sync(doc_path: str) -> list:
    if not os.path.exists(doc_path):
        return [f"missing {doc_path} (the documented schema)"]
    with open(doc_path) as f:
        doc = f.read()
    errs = []
    for etype, spec in sorted(SCHEMA.items()):
        if f"`{etype}`" not in doc:
            errs.append(f"docs/telemetry.md does not document event "
                        f"type `{etype}`")
            continue
        for name in {**spec["required"], **spec["optional"]}:
            if f"`{name}`" not in doc:
                errs.append(f"docs/telemetry.md does not document "
                            f"{etype} field `{name}`")
        for ph in spec.get("phases") or ():
            if f'"{ph}"' not in doc and f"`{ph}`" not in doc:
                errs.append(f"docs/telemetry.md does not document "
                            f"{etype} phase {ph!r}")
    return errs


def _emit_calls(tree: ast.AST):
    """(lineno, type_literal, keyword_names, has_starstar) for every
    ``emit("...")`` / ``<x>.emit("...")`` call with a literal type."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else \
            fn.id if isinstance(fn, ast.Name) else None
        if name != "emit" or not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        kws = [k.arg for k in node.keywords if k.arg is not None]
        starstar = any(k.arg is None for k in node.keywords)
        yield node.lineno, first.value, kws, starstar


def check_producers() -> list:
    from dlrm_flexflow_tpu.analysis.engine import load_modules

    errs = []
    parse_errors: list = []
    modules = load_modules(roots=_SCAN, repo=REPO, errors=parse_errors)
    errs.extend(f"{rel}: unparseable: {e}" for rel, e in parse_errors)
    for mod in modules:
        rel = mod.relpath
        for lineno, etype, kws, starstar in _emit_calls(mod.tree):
            if etype not in SCHEMA:
                errs.append(f"{rel}:{lineno}: emit of unknown event "
                            f"type {etype!r}")
                continue
            spec = SCHEMA[etype]
            known = set(spec["required"]) | set(spec["optional"])
            for kw in kws:
                if kw not in known:
                    errs.append(f"{rel}:{lineno}: emit(\"{etype}\") "
                                f"passes unknown field {kw!r}")
            if not starstar:
                missing = set(spec["required"]) - set(kws)
                if missing:
                    errs.append(f"{rel}:{lineno}: emit(\"{etype}\") "
                                f"misses required {sorted(missing)}")
    return errs


def check_metrics_registry(doc_path: str) -> list:
    """The metric-name registry contract (telemetry/metrics.py): the
    declared FAMILIES table, the default REGISTRY, the rendered
    exposition, and docs/telemetry.md must all agree."""
    import re

    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics

    errs = []
    registered = set(tmetrics.REGISTRY.names())
    declared = set(tmetrics.FAMILIES)
    for name in sorted(registered - declared):
        errs.append(f"metric {name!r} registered but not declared in "
                    f"telemetry.metrics.FAMILIES")
    for name in sorted(declared - registered):
        errs.append(f"metric {name!r} declared in FAMILIES but never "
                    f"registered in the default REGISTRY")
    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    for name, (mtype, help_) in sorted(tmetrics.FAMILIES.items()):
        if not name_re.match(name):
            errs.append(f"metric {name!r}: not a valid Prometheus "
                        f"metric name")
        if mtype not in ("counter", "gauge", "histogram"):
            errs.append(f"metric {name!r}: unknown type {mtype!r}")
        if mtype == "counter" and not name.endswith("_total"):
            errs.append(f"metric {name!r}: counter families must end "
                        f"'_total'")
        if not help_.strip():
            errs.append(f"metric {name!r}: empty help text")
    try:
        rendered = tmetrics.REGISTRY.render()
    except Exception as e:
        return errs + [f"REGISTRY.render() raised {e!r}"]
    for name in sorted(declared):
        n = rendered.count(f"# TYPE {name} ")
        if n != 1:
            errs.append(f"metric {name!r}: {n} TYPE lines in the "
                        f"exposition (want exactly 1)")
    if os.path.exists(doc_path):
        with open(doc_path) as f:
            doc = f.read()
        for name in sorted(declared):
            if f"`{name}`" not in doc:
                errs.append(f"docs/telemetry.md does not document "
                            f"metric family `{name}`")
    return errs


def check_tuning_artifacts(doc_path: str) -> list:
    """The tuning-artifact contract (sim/tune.py, docs/tuning.md):
    artifact field tables documented, example artifacts valid, and the
    gate metric latency-shaped."""
    from dlrm_flexflow_tpu.sim import tune
    from dlrm_flexflow_tpu.telemetry.regress import lower_is_better

    errs = []
    if not os.path.exists(doc_path):
        return [f"missing {doc_path} (the documented tuning-artifact "
                f"schema)"]
    with open(doc_path) as f:
        doc = f.read()
    for table, fields in (("calibration", tune.CALIBRATION_FIELDS),
                          ("strategy", tune.STRATEGY_FIELDS),
                          ("provenance", tune.PROVENANCE_FIELDS)):
        for name in fields:
            if f"`{name}`" not in doc:
                errs.append(f"docs/tuning.md does not document "
                            f"{table} artifact field `{name}`")
    for kind, example, validate in (
            ("calibration", tune.example_calibration_artifact,
             tune.validate_calibration_artifact),
            ("strategy", tune.example_strategy_artifact,
             tune.validate_strategy_artifact)):
        for e in validate(example()):
            errs.append(f"{kind} example artifact invalid: {e}")
    if not lower_is_better(tune.TUNE_METRIC):
        errs.append(f"tune.TUNE_METRIC {tune.TUNE_METRIC!r} is not "
                    f"latency-shaped — the promotion gate would let a "
                    f"slower candidate pass as an improvement")
    if f"`{tune.TUNE_METRIC}`" not in doc:
        errs.append(f"docs/tuning.md does not document the gate metric "
                    f"`{tune.TUNE_METRIC}`")
    return errs


PIPELINE_STEP_FIELDS = ("data_stall_ms", "dispatch_ms",
                        "host_overhead_pct")
PIPELINE_GAUGE = "dlrm_data_stall_pct"


def check_pipeline_contract(doc_path: str) -> list:
    """The input-pipeline observability contract (docs/pipeline.md):
    the fields the pipelined training loop reports exist in the schema
    and metric registry, are documented next to the knobs that move
    them, and regress in the right direction."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry.regress import lower_is_better

    errs = []
    step_fields = {**SCHEMA["step"]["required"],
                   **SCHEMA["step"]["optional"]}
    for name in PIPELINE_STEP_FIELDS:
        if name not in step_fields:
            errs.append(f"pipeline: step event field {name!r} missing "
                        f"from telemetry/schema.py")
    if PIPELINE_GAUGE not in tmetrics.FAMILIES:
        errs.append(f"pipeline: metric family {PIPELINE_GAUGE!r} not "
                    f"declared in telemetry.metrics.FAMILIES")
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented input "
                    f"pipeline)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in PIPELINE_STEP_FIELDS + (PIPELINE_GAUGE,
                                              "prefetch_depth",
                                              "--prefetch"):
            if f"`{needle}`" not in doc:
                errs.append(f"docs/pipeline.md does not document "
                            f"`{needle}`")
    for name in ("host_overhead_pct", PIPELINE_GAUGE):
        if not lower_is_better(name):
            errs.append(f"pipeline: {name!r} is not overhead-shaped in "
                        f"regress.lower_is_better — a host-path "
                        f"regression would read as an improvement")
    return errs


ELASTIC_PHASES = ("reshard", "scale", "regate")
ELASTIC_FAMILIES = ("dlrm_elastic_reshard_total", "dlrm_serve_replicas")


def check_elastic_contract(doc_path: str) -> list:
    """The elastic-topology observability contract (docs/elastic.md):
    the event phases, metric families, and topology-scoped regress
    anchors the subsystem documents must actually exist."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry.regress import _history_metrics

    errs = []
    phases = SCHEMA.get("elastic", {}).get("phases") or {}
    for ph in ELASTIC_PHASES:
        if ph not in phases:
            errs.append(f"elastic: phase {ph!r} missing from the "
                        f"elastic event schema")
    for name in ELASTIC_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"elastic: metric family {name!r} not declared "
                        f"in telemetry.metrics.FAMILIES")
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented elastic "
                    f"subsystem)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in ELASTIC_FAMILIES + (
                "reshard_restore", "scale_to", "rebuild",
                "preempt+reshape", "partition_rules"):
            if f"`{needle}" not in doc:
                errs.append(f"docs/elastic.md does not document "
                            f"`{needle}`")
    # elastic runs gate per-topology: the regress anchor keys must keep
    # the :mesh=/:replicas= suffixes, or a resharded run's headline
    # would gate against a different topology's baseline
    anchors = _history_metrics([
        {"metric": "m", "value": 1.0, "fenced": True},
        {"metric": "m", "value": 1.0, "fenced": True, "replicas": 4},
        {"metric": "m", "value": 1.0, "fenced": True,
         "mesh": "2x2"}])
    for key in ("m", "m:replicas=4", "m:mesh=2x2"):
        if key not in anchors:
            errs.append(f"elastic: regress anchor key {key!r} missing — "
                        f"topology-scoped gating broke "
                        f"(telemetry/regress.py _history_metrics)")
    return errs


OVERLAP_DOC_NEEDLES = ("exchange_overlap", "--exchange-overlap",
                       "BENCH_OVERLAP", "FF_EXCHANGE_OVERLAP",
                       "exchange_microbatches")


def check_overlap_contract(doc_path: str) -> list:
    """The exchange-overlap observability contract (docs/pipeline.md):
    every knob of the device-side microbatched pipeline documented
    next to the host-side pipeline, and overlapped runs anchored
    separately in the regress gate."""
    from dlrm_flexflow_tpu.telemetry.regress import _history_metrics

    errs = []
    if not os.path.exists(doc_path):
        return [f"missing {doc_path} (the documented pipelines)"]
    with open(doc_path) as f:
        doc = f.read()
    for needle in OVERLAP_DOC_NEEDLES:
        if f"`{needle}" not in doc:
            errs.append(f"docs/pipeline.md does not document "
                        f"`{needle}`")
    anchors = _history_metrics([
        {"metric": "m", "value": 1.0, "fenced": True},
        {"metric": "m", "value": 1.0, "fenced": True, "overlap": "on"}])
    for key in ("m", "m:overlap=on"):
        if key not in anchors:
            errs.append(f"overlap: regress anchor key {key!r} missing — "
                        f"an overlapped run could gate a serial "
                        f"baseline (telemetry/regress.py "
                        f"_history_metrics)")
    return errs


POD_DOC_NEEDLES = ("host_local_batch", "make_global_array",
                   "HostShardLoader", "PodTopology", "pod_topology",
                   "multihost", "shard-p", "dlrm_process_index",
                   "dlrm_process_count", ":hosts=", ":slices=")
POD_FAMILIES = ("dlrm_process_index", "dlrm_process_count")


def check_pod_contract(doc_path: str) -> list:
    """The pod-scale contract (docs/distributed.md): the multi-host
    knobs and the two-level cost model documented together, the
    per-process metric families declared, the ``distributed``
    bootstrap event present, and multi-host/slice runs anchored
    separately in the regress gate so a pod run can never gate a
    single-host baseline."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry.regress import _history_metrics

    errs = []
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented multi-host "
                    f"subsystem)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in POD_DOC_NEEDLES:
            if f"`{needle}" not in doc:
                errs.append(f"docs/distributed.md does not document "
                            f"`{needle}`")
    for name in POD_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"pod: metric family {name!r} not declared in "
                        f"telemetry.metrics.FAMILIES")
    phases = SCHEMA.get("distributed", {}).get("phases") or {}
    if "init" not in phases:
        errs.append("pod: the 'distributed' event type has no 'init' "
                    "phase — the bootstrap identity event is gone")
    anchors = _history_metrics([
        {"metric": "m", "value": 1.0, "fenced": True},
        {"metric": "m", "value": 1.0, "fenced": True, "hosts": 2},
        {"metric": "m", "value": 1.0, "fenced": True, "slices": 2}])
    for key in ("m", "m:hosts=2", "m:slices=2"):
        if key not in anchors:
            errs.append(f"pod: regress anchor key {key!r} missing — a "
                        f"multi-host run could gate a single-host "
                        f"baseline (telemetry/regress.py "
                        f"_history_metrics)")
    return errs


FLEET_DOC_NEEDLES = ("telemetry_pNNN", "flightrecorder_", "--fleet",
                     "--flight", "dlrm_step_skew_ms",
                     "dlrm_exposed_comm_pct", "pidx", "slice",
                     "row_freq", "phase_time")
PHASE_TIME_REQUIRED = ("step", "step_wall_ms")
PHASE_TIME_FIELDS = ("data_wait_ms", "dispatch_ms", "sync_wait_ms",
                     "exposed_comm_pct", "predicted_sync_ms")
ROW_FREQ_REQUIRED = ("table", "rows_seen", "unique_ids")
FLEET_FAMILIES = ("dlrm_step_skew_ms", "dlrm_exposed_comm_pct")


def check_fleet_contract(doc_path: str) -> list:
    """The fleet-observability contract (docs/telemetry.md): step-phase
    attribution and row-frequency events declared with their fields,
    the common ``pidx``/``slice`` stamp accepted everywhere, the skew
    and exposed-comm gauges registered, skew gating downward-is-better
    in regress, and the merge/flight CLI surface documented."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry.regress import lower_is_better
    from dlrm_flexflow_tpu.telemetry.schema import COMMON_OPTIONAL

    errs = []
    pt = SCHEMA.get("phase_time")
    if pt is None:
        errs.append("fleet: event type 'phase_time' missing from the "
                    "schema — step-phase attribution is gone")
    else:
        for f in PHASE_TIME_REQUIRED:
            if f not in pt["required"]:
                errs.append(f"fleet: phase_time required field {f!r} "
                            f"missing")
        for f in PHASE_TIME_FIELDS:
            if f not in pt["optional"]:
                errs.append(f"fleet: phase_time attribution field "
                            f"{f!r} missing")
    rf = SCHEMA.get("row_freq")
    if rf is None:
        errs.append("fleet: event type 'row_freq' missing from the "
                    "schema — LFU-admission input is gone")
    else:
        for f in ROW_FREQ_REQUIRED:
            if f not in rf["required"]:
                errs.append(f"fleet: row_freq required field {f!r} "
                            f"missing")
    for f in ("pidx", "slice"):
        if f not in COMMON_OPTIONAL:
            errs.append(f"fleet: common stamp field {f!r} missing from "
                        f"schema.COMMON_OPTIONAL — merged per-process "
                        f"events would be rejected")
    for name in FLEET_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"fleet: metric family {name!r} not declared "
                        f"in telemetry.metrics.FAMILIES")
    if not lower_is_better("dlrm_step_skew_ms"):
        errs.append("fleet: regress treats dlrm_step_skew_ms as "
                    "higher-is-better — a straggler regression would "
                    "read as an improvement")
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented fleet "
                    f"surface)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in FLEET_DOC_NEEDLES:
            if f"`{needle}" not in doc:
                errs.append(f"docs/telemetry.md does not document "
                            f"`{needle}`")
    return errs


RECOVERY_PHASES = ("dead_peer", "barrier_timeout", "stall", "resume",
                   "eject", "dispatcher_died")
RECOVERY_FAMILIES = ("dlrm_host_heartbeat_age_s",
                     "dlrm_serve_replica_ejected_total")
#: (doc path relative to docs/, needles that must appear backticked)
RECOVERY_DOC_NEEDLES = (
    ("resilience.md", ("HostWatchdog", "heartbeat-p", "StallWatchdog",
                       "FleetBarrierTimeout", "recover_and_resume",
                       "host_crash", "host_hang",
                       "dlrm_host_heartbeat_age_s")),
    ("distributed.md", ("barrier_timeout_s", "FleetBarrierTimeout")),
    ("serving.md", ("check_health", "ReplicaDead", "dispatcher_dead",
                    "consecutive_engine_failures",
                    "dlrm_serve_replica_ejected_total")),
)


def check_recovery_contract() -> list:
    """The failure-domain recovery contract (docs/resilience.md,
    docs/serving.md): the ``recovery`` event phases, the watchdog
    gauge + ejection counter, the host-loss fault specs, and the
    documented entry points must all exist."""
    from dlrm_flexflow_tpu.resilience import faultinject
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics

    errs = []
    phases = SCHEMA.get("recovery", {}).get("phases") or {}
    if not phases:
        errs.append("recovery: event type 'recovery' missing from the "
                    "schema (or has no phases) — failure-domain "
                    "telemetry is gone")
    for ph in RECOVERY_PHASES:
        if ph not in phases:
            errs.append(f"recovery: phase {ph!r} missing from the "
                        f"recovery event schema")
    for name in RECOVERY_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"recovery: metric family {name!r} not "
                        f"declared in telemetry.metrics.FAMILIES")
    # the host-loss fault kinds must parse (barrier point included) —
    # without them the recovery paths are unprovable
    for spec in ("host_crash@step=3", "host_hang@step=3",
                 "host_hang@barrier"):
        try:
            faultinject.parse(spec)
        except Exception as e:
            errs.append(f"recovery: fault spec {spec!r} no longer "
                        f"parses: {e}")
    for doc_name, needles in RECOVERY_DOC_NEEDLES:
        path = os.path.join(REPO, "docs", doc_name)
        if not os.path.exists(path):
            errs.append(f"missing docs/{doc_name} (documented recovery "
                        f"surface)")
            continue
        with open(path) as f:
            doc = f.read()
        for needle in needles:
            if f"`{needle}" not in doc:
                errs.append(f"docs/{doc_name} does not document "
                            f"`{needle}`")
    return errs


STORAGE_PHASES = ("admit", "evict", "miss")
STORAGE_FAMILIES = ("dlrm_embed_cache_hit_pct",
                    "dlrm_embed_cache_miss_stall_us")
STORAGE_DOC_NEEDLES = ("TieredEmbeddingTable", "hot_rows",
                       "tiered_storage_wins", ":storage=",
                       "BENCH_STORAGE", "--storage", "--id-dist",
                       "--zipf-alpha", "serve_storage",
                       "storage_hot_rows", "FF_TIERED_STORAGE",
                       "dlrm_embed_cache_hit_pct",
                       "dlrm_embed_cache_miss_stall_us",
                       "save_tiered", "load_tiered", "lfu", "lru",
                       "clock")


def check_storage_contract(doc_path: str) -> list:
    """The tiered-storage contract (docs/storage.md): the ``storage``
    event phases, the cache gauges with their gating directions, the
    documented knob surface, and the ``:storage=`` regress anchor."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry.regress import (_history_metrics,
                                                     lower_is_better)

    errs = []
    phases = SCHEMA.get("storage", {}).get("phases") or {}
    if not phases:
        errs.append("storage: event type 'storage' missing from the "
                    "schema (or has no phases) — tier telemetry is "
                    "gone")
    for ph in STORAGE_PHASES:
        if ph not in phases:
            errs.append(f"storage: phase {ph!r} missing from the "
                        f"storage event schema")
    for name in STORAGE_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"storage: metric family {name!r} not declared "
                        f"in telemetry.metrics.FAMILIES")
    if not lower_is_better("dlrm_embed_cache_miss_stall_us"):
        errs.append("storage: regress treats the miss stall as "
                    "higher-is-better — a streaming regression would "
                    "read as an improvement")
    if lower_is_better("dlrm_embed_cache_hit_pct"):
        errs.append("storage: regress treats the hit rate as "
                    "lower-is-better — a cache-thrash regression "
                    "would read as an improvement")
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented tiered "
                    f"storage subsystem)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in STORAGE_DOC_NEEDLES:
            if f"`{needle}" not in doc:
                errs.append(f"docs/storage.md does not document "
                            f"`{needle}`")
    anchors = _history_metrics([
        {"metric": "m", "value": 1.0, "fenced": True},
        {"metric": "m", "value": 2.0, "fenced": True,
         "storage": "resident"},
        {"metric": "m", "value": 3.0, "fenced": True,
         "storage": "tiered"}])
    if "m:storage=tiered" not in anchors:
        errs.append("storage: regress anchor key 'm:storage=tiered' "
                    "missing — a tiered run could gate the resident "
                    "baseline (telemetry/regress.py _history_metrics)")
    if anchors.get("m") != 2.0:
        errs.append("storage: an explicit storage='resident' entry "
                    "must anchor the BARE metric key (same anchor as "
                    "entries predating the field)")
    return errs


SLO_PHASES = ("eval", "breach", "recover")
SLO_FAMILIES = ("dlrm_slo_error_budget_pct", "dlrm_slo_burn_rate",
                "dlrm_serve_shed_total")
SLO_DOC_NEEDLES = ("SLO", "SLOMonitor", "parse_slos", "--slo",
                   "p99_ms", "availability", "freshness",
                   "burn_fast", "burn_slow", "fast_window_s",
                   "slow_window_s", "dump_flight_record", "/healthz",
                   "queue_wait", "engine_forward", "miss_stall",
                   "dominant", "trace_id",
                   "dlrm_slo_error_budget_pct", "dlrm_slo_burn_rate",
                   "dlrm_serve_shed_total")
SLO_SHED_CAUSES = ("queue_full", "deadline", "shutdown", "saturated")


def check_slo_contract(doc_path: str) -> list:
    """The serving-SLO contract (docs/slo.md): the ``slo`` event
    phases, the budget/burn gauge families + cause-split shed counter,
    the burn rate's regress direction, and the documented spec
    mini-language / exemplar / breach-response surface."""
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    from dlrm_flexflow_tpu.telemetry import slo as tslo
    from dlrm_flexflow_tpu.telemetry.regress import lower_is_better

    errs = []
    phases = SCHEMA.get("slo", {}).get("phases") or {}
    if not phases:
        errs.append("slo: event type 'slo' missing from the schema "
                    "(or has no phases) — objective telemetry is gone")
    for ph in SLO_PHASES:
        if ph not in phases:
            errs.append(f"slo: phase {ph!r} missing from the slo "
                        f"event schema")
    for name in SLO_FAMILIES:
        if name not in tmetrics.FAMILIES:
            errs.append(f"slo: metric family {name!r} not declared in "
                        f"telemetry.metrics.FAMILIES")
    if not lower_is_better("dlrm_slo_burn_rate"):
        errs.append("slo: regress treats dlrm_slo_burn_rate as "
                    "higher-is-better — a budget-burning regression "
                    "would read as an improvement")
    # the spec mini-language serve_bench documents must keep parsing
    try:
        parsed = tslo.parse_slos("p99_ms=5,availability=99.9,"
                                 "freshness=600")
        kinds = [s.kind for s in parsed]
        if kinds != ["latency", "availability", "freshness"]:
            errs.append(f"slo: parse_slos kinds drifted: {kinds}")
    except Exception as e:
        errs.append(f"slo: the documented --slo spec no longer "
                    f"parses: {e}")
    if not os.path.exists(doc_path):
        errs.append(f"missing {doc_path} (the documented SLO engine)")
    else:
        with open(doc_path) as f:
            doc = f.read()
        for needle in SLO_DOC_NEEDLES:
            if f"`{needle}" not in doc:
                errs.append(f"docs/slo.md does not document "
                            f"`{needle}`")
        for cause in SLO_SHED_CAUSES:
            if cause not in doc:
                errs.append(f"docs/slo.md does not document shed "
                            f"cause {cause!r}")
    return errs


#: spans of the training path, each held as a profiler annotation too
TRAIN_SPANS = ("train.fit", "train.epoch", "train.dispatch",
               "train.shard", "train.launch")


def check_phase_contract(doc_path: str) -> list:
    from dlrm_flexflow_tpu.profiling import UNATTRIBUTED, phase_of

    errs = []
    with open(doc_path) as f:
        doc = f.read()
    scopes = set()
    for source in ("model.py", "row_cache.py"):   # the step's, the cache's
        with open(os.path.join(REPO, "dlrm_flexflow_tpu", source)) as f:
            tree = ast.parse(f.read())
        found = {node.args[0].value for node in ast.walk(tree)
                 if (isinstance(node, ast.Call)
                     and isinstance(node.func, ast.Attribute)
                     and node.func.attr == "named_scope" and node.args
                     and isinstance(node.args[0], ast.Constant))}
        if not found:
            errs.append(f"{source} opens no jax.named_scope literal: "
                        f"its phase scopes are gone")
        scopes |= found
    for scope in sorted(scopes):
        if phase_of(f"jit(f)/{scope}/add") != scope:
            errs.append(f"scope {scope!r} is not a phase by "
                        f"profiling.phase_of's naming rule")
        if f"`{scope}`" not in doc:
            errs.append(f"docs/telemetry.md does not document the phase "
                        f"scope `{scope}`")
    if phase_of("jit(f)/jit(_where)/select_n") != UNATTRIBUTED:
        errs.append("phase_of names a phase where the stack holds none")
    if "program" not in SCHEMA:
        errs.append("schema lacks the `program` event type")
    if "start_mono_s" not in SCHEMA["span"]["optional"]:
        errs.append("span schema lacks `start_mono_s`")
    for name in TRAIN_SPANS + ("phase_of", "program_phases",
                               "parse_device_trace_phases"):
        if f"`{name}`" not in doc and f"`profiling.{name}" not in doc:
            errs.append(f"docs/telemetry.md does not document `{name}`")
    return errs


def main() -> int:
    doc = os.path.join(REPO, "docs", "telemetry.md")
    errs = (check_self_consistency()
            + check_doc_sync(doc)
            + check_producers()
            + check_metrics_registry(doc)
            + check_tuning_artifacts(os.path.join(REPO, "docs",
                                                  "tuning.md"))
            + check_pipeline_contract(os.path.join(REPO, "docs",
                                                   "pipeline.md"))
            + check_elastic_contract(os.path.join(REPO, "docs",
                                                  "elastic.md"))
            + check_overlap_contract(os.path.join(REPO, "docs",
                                                  "pipeline.md"))
            + check_pod_contract(os.path.join(REPO, "docs",
                                              "distributed.md"))
            + check_fleet_contract(doc)
            + check_recovery_contract()
            + check_storage_contract(os.path.join(REPO, "docs",
                                                  "storage.md"))
            + check_slo_contract(os.path.join(REPO, "docs",
                                              "slo.md"))
            + check_phase_contract(doc))
    for e in errs:
        print(f"check_telemetry_schema: {e}")
    if errs:
        return 1
    from dlrm_flexflow_tpu.telemetry import metrics as tmetrics
    print(f"check_telemetry_schema: OK ({len(SCHEMA)} event types, "
          f"{len(tmetrics.FAMILIES)} metric families)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
