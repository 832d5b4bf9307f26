"""The telemetry event schema — the single contract between event
producers (model.py, bench.py, sim/search.py, profiling.OpTimer, the
jax.monitoring compile hooks) and the report CLI.

Every emitted event is a flat JSON object with two common fields
(``type``, ``ts``), an optional fleet identity stamp (``pidx``,
``slice`` — multi-host runs only, see telemetry/fleet.py), plus
per-type fields listed here.  ``EventLog.emit``
validates against this table at emission time and
``scripts/check_telemetry_schema.py`` lints it in tier-1 tests, so a
producer cannot add or rename a field without the schema (and therefore
the report CLI) seeing it — the drift this module exists to prevent.

The documented form of this schema lives in ``docs/telemetry.md``; keep
the two in sync (the lint checks the doc names every type).
"""

from __future__ import annotations

from typing import Dict, List

SCHEMA_VERSION = 1

#: declared type -> accepted runtime types.  ``float`` fields accept ints
#: (JSON round-trips 1.0 as 1) but never bools; ``int`` fields reject
#: bools too (bool subclasses int in Python).
_ACCEPT = {
    float: (int, float),
    int: (int,),
    str: (str,),
    bool: (bool,),
    dict: (dict,),
    list: (list, tuple),
}

COMMON_REQUIRED = {"type": str, "ts": float}

#: fleet identity stamp, accepted on EVERY event type: which host
#: process (``pidx`` = jax.process_index) of which DCN slice produced
#: the event.  ``EventLog(stamp=...)`` injects these on emission under
#: ``process_count() > 1`` (telemetry/fleet.py) so ``report --fleet``
#: can merge per-process sinks and attribute stragglers; single-process
#: runs never carry them, keeping single-file output bit-identical.
COMMON_OPTIONAL = {"pidx": int, "slice": int}

SCHEMA: Dict[str, dict] = {
    # one timed stretch of training: an epoch, a fused multi-epoch
    # dispatch, or a fenced bench window.  ``fenced`` distinguishes
    # device-complete walls (closed by ``device_fence``) from
    # dispatch-only walls, which end when the host has enqueued the work.
    "step": {
        "required": {"wall_s": float, "samples": int},
        "optional": {"samples_per_s": float, "steps": int,
                     "epochs": int, "loss": float, "metrics": dict,
                     "fenced": bool, "phase": str,
                     # input-pipeline decomposition of the per-batch
                     # loops (docs/pipeline.md): host ms spent waiting
                     # for the next batch / issuing dispatches across
                     # the whole stretch, and the derived host share of
                     # the wall (100*(wall-busy)/wall for bench windows)
                     "data_stall_ms": float, "dispatch_ms": float,
                     "host_overhead_pct": float},
    },
    # one XLA compilation (jit cache miss).  ``kind`` is
    # "backend_compile" for hook-observed compiles and "aot" for
    # FFModel.fit's explicit lower().compile() calls (which also know
    # the donated-argument count).  A backend compile names its program
    # (``fn``) and its ``stage``: "compile", or "cache_load" where the
    # persistent cache served the executable.
    "compile": {
        "required": {"kind": str, "duration_s": float},
        "optional": {"fn": str, "stage": str, "donated_args": int,
                     "backend": str},
    },
    # per-device live-bytes watermark sampled around a step.  ``source``
    # is "memory_stats" on backends that expose allocator stats (TPU) or
    # "live_arrays" for the host-side fallback (CPU test meshes).
    "memory": {
        "required": {"device": str, "bytes_in_use": int},
        "optional": {"peak_bytes": int, "source": str, "phase": str},
    },
    # MCMC strategy-search trajectory (sim/search.py), simulator
    # calibration (sim/simulator.py), and gated strategy promotion
    # (sim/tune.py).  ``phase`` selects the sub-shape: per-iteration
    # proposals, the end-of-search summary, one sim-vs-measured
    # calibration fit, or one candidate-vs-incumbent promotion verdict.
    "search": {
        "required": {"phase": str},
        "optional": {"it": int, "op": str, "dims": list, "devices": list,
                     "current_s": float, "best_s": float, "start_s": float,
                     "accepted": bool,
                     "iterations": int, "accepted_count": int,
                     "acceptance_rate": float, "backend": str,
                     "simulated_s": float, "measured_s": float,
                     "scale": float, "verdict": str, "version": int,
                     "incumbent_version": int, "candidate_s": float,
                     "incumbent_s": float, "tolerance_pct": float,
                     "metric": str, "app": str, "num_devices": int},
        "phases": {
            "iteration": ("it", "accepted", "current_s", "best_s"),
            "summary": ("iterations", "best_s"),
            "calibrate": ("simulated_s", "measured_s", "scale"),
            "promote": ("verdict", "version", "candidate_s"),
        },
    },
    # cost-model calibration against recorded reality (sim/tune.py,
    # scripts/calibrate_sim.py — docs/tuning.md).  ``phase`` selects
    # the sub-shape: one per-op-class fit from op_time telemetry, one
    # whole-step real-vs-sim measurement, or one persisted calibration
    # artifact.
    "calibration": {
        "required": {"phase": str},
        "optional": {"source": str, "ops": int, "op_classes": int,
                     "mae_pct_before": float, "mae_pct_after": float,
                     "artifact": str, "real_ms": float, "sim_ms": float,
                     "ratio": float, "rows": int, "batch": int,
                     "scale": float},
        "phases": {
            "fit": ("ops", "mae_pct_before", "mae_pct_after"),
            "measure": ("real_ms", "sim_ms", "ratio"),
            "persist": ("artifact",),
        },
    },
    # one op's isolated forward/backward wall time (profiling.OpTimer)
    # next to the analytic simulator's prediction for the same op — the
    # report's sim-vs-measured calibration table reads these.
    "op_time": {
        "required": {"op": str, "forward_s": float},
        "optional": {"backward_s": float, "sim_forward_s": float,
                     "sim_backward_s": float},
    },
    # one checkpoint-manager action (resilience/manager.py).  ``action``
    # is "save" (atomic commit), "retry" (transient I/O error, backed
    # off), "save_failed" (all attempts exhausted — the run CONTINUES),
    # "restore", or "gc" (retention sweep / killed-save debris).
    "checkpoint": {
        "required": {"action": str},
        "optional": {"step": int, "path": str, "duration_s": float,
                     "attempt": int, "error": str, "files": int,
                     "kept": int, "removed_ckpts": int,
                     "removed_tmp": int},
    },
    # one anomalous training dispatch the NaN sentinel rejected
    # (resilience/sentinel.py).  ``kind``: "nan_loss" | "inf_loss" |
    # "nonfinite_params"; ``action``: "rollback_skip" |
    # "rollback_lr_backoff".  ``loss`` is absent for NaN (JSON cannot
    # carry it); ``lr`` is the rate BEFORE any backoff.
    "anomaly": {
        "required": {"kind": str},
        "optional": {"step": int, "action": str, "rollbacks": int,
                     "policy": str, "loss": float, "lr": float},
    },
    # online serving (serving/, docs/serving.md).  ``phase`` selects the
    # sub-shape: one engine dispatch (a padded bucket run), one shed or
    # deadline-missed request, the run's latency summary the report
    # CLI's "== serving ==" section reads, or one tail exemplar (a
    # top-K slowest request with its span-derived phase decomposition —
    # the "== tail ==" section and docs/slo.md read these; ``dominant``
    # names the phase that contributed the most wall).
    "serve": {
        "required": {"phase": str},
        "optional": {"batch": int, "bucket": int, "padded": int,
                     "fill": float, "queue_wait_us": float,
                     "compute_us": float, "reason": str,
                     "requests": int, "dispatches": int,
                     "rejected": int, "deadline_misses": int,
                     "wall_s": float, "qps": float, "p50_us": float,
                     "p95_us": float, "p99_us": float, "mean_us": float,
                     "replicas": int, "router_shed": int,
                     "lat_us": float, "trace_id": str, "pad_us": float,
                     "stall_us": float, "dominant": str},
        "phases": {
            "dispatch": ("batch", "bucket", "queue_wait_us",
                         "compute_us"),
            "reject": ("reason",),
            "summary": ("requests", "qps"),
            "tail": ("bucket", "lat_us", "trace_id", "dominant"),
        },
    },
    # one elastic-topology action (elastic/, docs/elastic.md).
    # ``phase`` selects the sub-shape: one cross-topology checkpoint
    # restore ("reshard" — saved shards gathered to host-logical arrays
    # and re-placed under the new mesh's partition rules), one live
    # replica resize ("scale" — ReplicaRouter.scale_to/rebuild), or one
    # incumbent-strategy re-gate for the new topology ("regate" —
    # through sim/tune.py's promotion machinery; ``verdict`` is
    # "incumbent" / "none" / a gate_candidate verdict).
    "elastic": {
        "required": {"phase": str},
        "optional": {"from_mesh": str, "to_mesh": str, "step": int,
                     "leaves": int, "duration_s": float,
                     "replicas_from": int, "replicas_to": int,
                     "drained": int, "verdict": str, "app": str,
                     "num_devices": int, "version": int},
        "phases": {
            "reshard": ("from_mesh", "to_mesh"),
            "scale": ("replicas_from", "replicas_to"),
            "regate": ("verdict",),
        },
    },
    # one multi-host bootstrap (distributed.initialize,
    # docs/distributed.md): which process of how many produced this
    # run's telemetry, over how many global/local devices and DCN
    # slices — the report CLI's "== distributed ==" section and the
    # dlrm_process_index/dlrm_process_count gauges carry the same
    # identity.
    "distributed": {
        "required": {"phase": str},
        "optional": {"process_index": int, "process_count": int,
                     "global_devices": int, "local_devices": int,
                     "slices": int},
        "phases": {
            "init": ("process_index", "process_count"),
        },
    },
    # one injected fault firing (resilience/faultinject.py) — recovery
    # tests read these next to the checkpoint/anomaly events the fault
    # provoked.  ``point``: "step" | "save" | "restore"; ``remaining``:
    # firings this fault has left.
    "fault": {
        "required": {"kind": str, "point": str},
        "optional": {"step": int, "remaining": int},
    },
    # one failure-domain action (resilience/watchdog.py,
    # elastic/recovery.py, serving/router.py — docs/resilience.md).
    # ``phase`` selects the sub-shape: a peer whose heartbeat aged past
    # the deadline ("dead_peer"), a podshard commit barrier that timed
    # out naming its absentees ("barrier_timeout"), the step-level
    # stall watchdog firing ("stall"), a survivor resuming at reduced
    # fleet shape ("resume" — recover_and_resume), a replica ejected
    # from dispatch ("eject"), or a serving dispatcher thread that died
    # with its pending futures failed loudly ("dispatcher_died").
    "recovery": {
        "required": {"phase": str},
        "optional": {"peer": str, "age_s": float, "deadline_s": float,
                     "tag": str, "missing": list, "arrived": int,
                     "expected": int, "stall_s": float, "limit_s": float,
                     "step": int, "process_count": int, "path": str,
                     "replica": str, "reason": str, "error": str,
                     "failed": int, "duration_s": float},
        "phases": {
            "dead_peer": ("peer", "age_s", "deadline_s"),
            "barrier_timeout": ("tag", "missing"),
            "stall": ("stall_s", "limit_s"),
            "resume": ("process_count", "path"),
            "eject": ("replica", "reason"),
            "dispatcher_died": ("error", "failed"),
        },
    },
    # per-phase wall attribution of one training step (or a whole fit
    # stretch when ``phase`` is a loop name) — the measured column next
    # to the cost model's DCN-exposed prediction (PERF.md).  Producers:
    # the per-batch fit loop and resilient_fit's lag-1 pipeline.
    # ``step`` is the global step the walls belong to (fleet merge
    # aligns on it); ``sync_wait_ms`` is the host wall blocked on
    # device completion beyond the overlapped window (grad-sync /
    # collective wait on comm-bound steps); ``exposed_comm_pct`` =
    # 100*sync_wait/step_wall; ``predicted_sync_ms`` is the two-level
    # cost model's hierarchical grad all-reduce price for comparison.
    # ``forward_ms``/``backward_ms`` are only host-separable where the
    # step runs unfused — the jitted path reports dispatch+sync and
    # leaves per-op walls to ``op_time`` events.
    "phase_time": {
        "required": {"step": int, "step_wall_ms": float},
        "optional": {"data_wait_ms": float, "dispatch_ms": float,
                     "forward_ms": float, "backward_ms": float,
                     "sync_wait_ms": float, "exposed_comm_pct": float,
                     "predicted_sync_ms": float, "samples": int,
                     "steps": int, "phase": str},
    },
    # per-table embedding row-access frequency summary
    # (telemetry/rowfreq.py): host-side, off the traced graph, sampled
    # every Nth batch so the hot path pays ~0.  ``bucket_counts[b]`` is
    # the number of distinct ids whose access count falls in
    # [2^b, 2^(b+1)) — the power-of-two histogram ROADMAP item 4's LFU
    # admission policy reads; ``top_ids``/``top_counts`` rank the
    # hottest rows first.  ``evicted`` counts cold ids pruned when the
    # counter exceeded twice its ``capacity``.
    "row_freq": {
        "required": {"table": str, "rows_seen": int, "unique_ids": int},
        "optional": {"top_ids": list, "top_counts": list,
                     "bucket_counts": list, "sampled_batches": int,
                     "sample_every": int, "capacity": int,
                     "evicted": int},
    },
    # one tiered-embedding-store action (storage/tiered.py —
    # docs/storage.md).  ``phase`` selects the sub-shape: a warm-start
    # / checkpoint-reload admission batch ("admit" — how many rows
    # entered the hot tier under which policy), an eviction batch
    # ("evict" — rows displaced to make room, dirty ones written back
    # to the cold tier first), or one remap's miss block ("miss" — the
    # lookups that left the hot tier, with the start-all-then-wait
    # host->device stall they paid).  ``table`` is the store name (the
    # sparse input it backs); ``hit_pct`` mirrors the
    # dlrm_embed_cache_hit_pct gauge at emit time.
    "storage": {
        "required": {"phase": str, "table": str},
        "optional": {"rows": int, "slots": int, "hit_pct": float,
                     "hits": int, "misses": int, "evicted": int,
                     "admitted": int, "stall_us": float, "policy": str,
                     "dirty": int, "writebacks": int},
        "phases": {
            "admit": ("admitted", "policy"),
            "evict": ("evicted",),
            "miss": ("misses", "stall_us"),
        },
    },
    # one SLO evaluation tick (telemetry/slo.py — docs/slo.md).
    # ``phase`` selects the sub-shape: one multi-window burn-rate
    # evaluation of one declared objective ("eval" — every monitor
    # tick), a breach verdict ("breach" — a burn window crossed its
    # threshold; names the objective, the measured windowed bad
    # fraction, the dominant tail phase, and the flight-record path
    # when one was dumped), or the return below threshold ("recover").
    # ``value`` is the windowed bad fraction (latency: share of
    # requests over threshold; availability: shed share; freshness:
    # share of stale samples); ``burn_fast``/``burn_slow`` are the
    # Google-SRE burn rates over the fast/slow windows (observed error
    # rate over budgeted error rate); ``budget_pct`` is the error
    # budget remaining since monitor start.
    "slo": {
        "required": {"phase": str, "slo": str},
        "optional": {"kind": str, "value": float, "objective": float,
                     "burn_fast": float, "burn_slow": float,
                     "budget_pct": float, "window_s": float,
                     "dominant": str, "flight": str,
                     "good": int, "bad": int},
        "phases": {
            "eval": ("value", "burn_fast", "burn_slow", "budget_pct"),
            "breach": ("value", "burn_fast", "budget_pct", "dominant"),
            "recover": ("value", "burn_fast", "burn_slow",
                        "budget_pct"),
        },
    },
    # one closed span (telemetry/trace.py) — a Dapper-style timed,
    # attributed region of a request or training run, emitted at span
    # END.  ``start_s`` is the wall-clock start (time.time());
    # ``dur_us`` comes from a monotonic clock.  ``parent_id`` links the
    # causal chain within one ``trace_id`` (serving: submit →
    # queue-wait → dispatch → pad → forward → reply; training: fit →
    # epoch → dispatch → checkpoint/rollback).  ``status`` is "ok" or
    # the reason the region ended otherwise ("error", "shed",
    # "deadline", "cancelled", "rejected"); ``thread``/``tid`` name the
    # thread that OPENED the span (the export-trace CLI's per-thread
    # tracks).
    # ``start_mono_s`` is the same start on ``time.perf_counter()``,
    # the clock ``dur_us`` is taken on and the one a profiler trace's
    # host events can be lined up with (absent on ``record_span``'s
    # already-timed spans).  Spans of the training path
    # (``train.fit`` / ``train.epoch`` / ``train.dispatch`` /
    # ``train.shard`` / ``train.launch``) also hold a
    # ``jax.profiler.TraceAnnotation`` of their name while open.
    "span": {
        "required": {"name": str, "trace_id": str, "span_id": str,
                     "start_s": float, "dur_us": float},
        "optional": {"parent_id": str, "status": str, "attrs": dict,
                     "thread": str, "tid": int, "start_mono_s": float},
    },
    # one jitted training program named at its first dispatch under
    # this log (FFModel.train_step / train_epoch / train_epochs through
    # profiling.note_program): ``name`` is the key
    # ``profiling.program_phases`` takes to say which phase scope of
    # ``model.py::_compile_body`` / ``row_cache.py`` each HLO instruction
    # of that program belongs to; ``fn`` the wrapper's jitted function.
    # One event per program and log, not per dispatch.
    # ``attention_core``: only for a program with ``LatentAttention``
    # or ``GatedAttention`` ops: how many of them run the fused Pallas
    # core and how many the plain one, ``{"pallas": 6, "plain": 0}``
    # (ops/attention.py ``core_form``: the backend and the shapes decide
    # as the program is built, so the step pays nothing).
    # ``gdn_core``: the like count for ``GatedDeltaNet`` ops: how many
    # run the gated delta rule as the two Pallas kernels of
    # ops/pallas_deltanet.py and how many in the chunked ``jax.numpy``
    # form, ``{"pallas": 3, "chunked": 0}`` on a TPU at 16,384 tokens
    # and 128-wide heads, ``{"pallas": 0, "chunked": 3}`` on the CPU
    # (ops/deltanet.py ``core_form``).  ``kda_core``: the like count for
    # ``KimiDeltaAttention`` ops (a decay per key channel): how many run
    # the rule as the two Pallas kernels of ops/pallas_kda.py and how
    # many in the chunked form, ``{"pallas": 6, "chunked": 0}`` on a TPU
    # in the Ling-style cell, ``{"pallas": 0, "chunked": 6}`` on the CPU
    # (ops/deltanet.py ``kimi_core_form``).  ``core_kept``: for each of
    # those fields, how many of its ops run their kernel form inside a
    # recomputed run that keeps what the kernels name
    # (``Op.saved_in_recompute``: the output and the boundary states or
    # log-sum-exp), so that the recomputation never runs their forward
    # kernel again: ``{"attention_core": 1, "gdn_core": 3}`` on a TPU in
    # the hybrid cell, ``{"attention_core": 1, "kda_core": 6}`` in the
    # Ling-style one, every count 0 on the CPU or with recomputation off.
    # ``route_kept``: only for a program with ``HeldExpertsMoE`` ops: how
    # many of them sit in a recomputed run and keep their routing there
    # (the scores, the selection and its scores, the sorted assignments
    # and the groups' sizes, ``ops/moe.py::ROUTE_SAVED``), so that the
    # recomputation never routes again: 4 / 6 / 5 / 4 in the hybrid,
    # Ling-style, JoyAI and ZAYA1-style cells, 0 with recomputation off.
    "program": {
        "required": {"name": str},
        "optional": {"fn": str, "attention_core": dict, "gdn_core": dict,
                     "kda_core": dict, "core_kept": dict, "route_kept": int},
    },
    # what one dispatch of FFModel.train_epoch / train_epochs counted
    # inside one op that keeps counters in its state (ops/moe.py
    # HeldExpertsMoE: ``tokens_per_expert`` over all experts,
    # ``held_assignments``, ``padded_rows`` (assignments to no held
    # expert), ``buffer_rows`` (rows the grouped matmuls were given:
    # slabs x the slab's rows), ``overflow_steps`` (steps that took more
    # than one slab), ``bias_abs_max``; with a skip slot
    # ``skipped_tokens``, the assignments to it), summed over the dispatch's
    # steps (``*_max``: the largest).  Emitting it reads device values:
    # the host waits for the dispatch.
    "op_counters": {
        "required": {"op": str, "counters": dict},
        "optional": {"fn": str},
    },
}


def _type_ok(val, declared) -> bool:
    ok = _ACCEPT[declared]
    if isinstance(val, bool):
        return declared is bool
    return isinstance(val, ok)


def validate_event(ev: dict) -> List[str]:
    """Errors for one event dict against the schema (empty list = valid).

    Checks: common fields, known type, required fields present with the
    right runtime types, NO unknown fields (an unknown field means a
    producer drifted from the schema — exactly what the lint catches),
    and the per-phase required fields of ``search`` events.
    """
    errs: List[str] = []
    if not isinstance(ev, dict):
        return [f"event is not a dict: {type(ev).__name__}"]
    for name, decl in COMMON_REQUIRED.items():
        if name not in ev:
            errs.append(f"missing common field {name!r}")
        elif not _type_ok(ev[name], decl):
            errs.append(f"common field {name!r} has type "
                        f"{type(ev[name]).__name__}, want {decl.__name__}")
    etype = ev.get("type")
    if etype not in SCHEMA:
        errs.append(f"unknown event type {etype!r} "
                    f"(known: {sorted(SCHEMA)})")
        return errs
    spec = SCHEMA[etype]
    known = {**spec["required"], **spec["optional"]}
    for name, decl in spec["required"].items():
        if name not in ev:
            errs.append(f"{etype}: missing required field {name!r}")
        elif not _type_ok(ev[name], decl):
            errs.append(f"{etype}.{name}: type {type(ev[name]).__name__}, "
                        f"want {decl.__name__}")
    for name, val in ev.items():
        if name in COMMON_REQUIRED:
            continue
        if name in COMMON_OPTIONAL:
            if not _type_ok(val, COMMON_OPTIONAL[name]):
                errs.append(
                    f"common field {name!r} has type "
                    f"{type(val).__name__}, "
                    f"want {COMMON_OPTIONAL[name].__name__}")
            continue
        if name not in known:
            errs.append(f"{etype}: unknown field {name!r} "
                        f"(schema drift — update telemetry/schema.py "
                        f"and docs/telemetry.md together)")
        elif name in spec["optional"] and not _type_ok(val, known[name]):
            errs.append(f"{etype}.{name}: type {type(val).__name__}, "
                        f"want {known[name].__name__}")
    phases = spec.get("phases")
    if phases is not None and "phase" in ev:
        ph = ev["phase"]
        if ph not in phases:
            errs.append(f"{etype}: unknown phase {ph!r} "
                        f"(known: {sorted(phases)})")
        else:
            for name in phases[ph]:
                if name not in ev:
                    errs.append(f"{etype}[phase={ph}]: missing {name!r}")
    return errs
