"""Headline benchmark: DLRM synthetic training throughput (samples/s).

Mirrors the reference's synthetic benchmark configuration
(reference: examples/cpp/DLRM/run_random.sh — 8 tables x 1M rows,
sparse-feature 64, MLP bot 64-512-512-64, top 576-1024-1024-1024-1,
batch 256/GPU).  Timing follows the reference's fenced wall-clock
(dlrm.cc:154-198) over BENCH_REPS windows (each = `epochs` scanned
epochs dispatched asynchronously, one device fence at the end); the best
window is reported.

The epoch runs as one on-device ``lax.scan`` (the analogue of Legion
tracing with ``-dm:memoize``), so host dispatch is off the critical path.
Default precision is mixed: bf16 MXU matmuls with f32 accumulation and
f32 master weights (BENCH_DTYPE=float32 for full fp32).

The run prints the device it found first and refuses a backend that is
not a TPU unless the caller set JAX_PLATFORMS=cpu themselves; every
history entry and result line carries the device.  On a TPU the
provenance phases (device-busy trace, OpTimer, cost-analysis bytes,
simulator calibration) are part of the result: one that fails, fails the
run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device"}.  The reference repo publishes no numbers (BASELINE.md) —
vs_baseline is computed against the FIRST *fenced* bench_history.json
entry whose shape config (batch/num_batches/epochs/rows/emb_dtype, plus
act_dtype for the conv apps) matches this run; table and activation
STORAGE dtypes change numerics, so fp32 and bf16 runs anchor separately
(entries predating the fields count as float32).  The earliest entries
were taken before every window was closed by ``device_fence``; they are
kept for the record but never used as the anchor.  The COMPUTE precision
default (bf16 MXU, f32 accumulation/master weights) is credited as a
framework optimization, so "dtype" is intentionally NOT part of the
match key.  No matching anchor -> 1.0.
"""

import contextlib
import json
import os
import sys
import time

import numpy as np


def _emit(metric, thpt, key, extra=None, unit="samples/s"):
    """Shared tail of every benchmark: anchor ``thpt`` against the FIRST
    fenced history entry matching ``key`` (entries predating the "app"
    field count as app=="dlrm"), append this run (plus ``extra``
    provenance fields like dtype, excluded from matching), and print the
    one-line JSON protocol.  ``vs_baseline`` always reads >1 = BETTER:
    for latency-style metrics (regress.lower_is_better, e.g.
    dlrm_serving_p99_ms) the ratio is baseline/new, for throughput
    new/baseline."""
    from dlrm_flexflow_tpu.telemetry.regress import lower_is_better
    hist_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_history.json")
    vs = 1.0
    try:
        with open(hist_path) as f:
            hist = json.load(f)
        if not isinstance(hist, list):
            hist = []

        def matches(h):
            for k, v in key.items():
                hv = h.get(k)
                if k == "app" and hv is None:
                    hv = "dlrm"  # records written before the app field
                if k == "overlap" and hv is None:
                    hv = "off"  # records written before exchange overlap
                if k == "emb_dtype" and hv is None:
                    hv = "float32"  # records written before emb_dtype
                if k == "act_dtype" and hv is None:
                    hv = "float32"  # records written before act_dtype
                if k == "quantize" and hv is None:
                    hv = "off"  # records written before serve quantize
                if k == "storage" and hv is None:
                    hv = "resident"  # records written before tiering
                if k == "replicas" and hv is None:
                    hv = 1  # records written before the replica router
                if k == "hosts" and hv is None:
                    hv = 1  # records written before multi-host keys
                if k == "slices" and hv is None:
                    hv = 1  # records written before pod topology keys
                if k == "mesh" and hv is None:
                    hv = ""  # records written before mesh-native serving
                if k == "metric" and hv is None:
                    # records written before the metric field carry the
                    # app's ONE historical headline — THE mapping lives
                    # in telemetry/regress.py, used here verbatim
                    from dlrm_flexflow_tpu.telemetry.regress import (
                        _history_metric_name)
                    hv = _history_metric_name(h)
                if hv != v:
                    return False
            return True

        for h in hist:
            if h.get("fenced") and h.get("value") and matches(h):
                if lower_is_better(metric):
                    vs = float(h["value"]) / thpt if thpt else 1.0
                else:
                    vs = thpt / float(h["value"])
                break
    except (OSError, ValueError, TypeError, AttributeError):
        hist = []
    from dlrm_flexflow_tpu.entrypoint import device_info
    device = device_info()
    entry = {**key, **(extra or {}), "ts": time.time(), "value": thpt,
             "fenced": True, "device": device}
    hist.append(entry)
    # the layer metrics ride the history entry, which stays on whatever
    # machine ran this; the run's own log carries them too
    print(f"# history entry: {json.dumps(entry)}", file=sys.stderr)
    try:
        with open(hist_path, "w") as f:
            json.dump(hist, f, indent=1)
    except OSError:
        pass
    print(json.dumps({
        "metric": metric,
        "value": round(thpt, 2),
        "unit": unit,
        "vs_baseline": round(vs, 4),
        "device": device,
    }))


def _telemetry_ctx(app):
    """Scoped EventLog for one bench run, written under ``artifacts/``
    as ``telemetry_<app>.jsonl`` (mode="w": one file per run — run
    artifacts live in artifacts/, never at the repo root where they
    dirty the tree).  ``BENCH_TELEMETRY`` overrides the path
    ("0"/"off"/"none"/"false"/"no" disables and yields a null context;
    "1"/"on"/"true"/"yes" just enables the default path — switches, not
    filenames)."""
    p = os.environ.get("BENCH_TELEMETRY", "")
    if p.strip().lower() in ("0", "off", "none", "false", "no"):
        return contextlib.nullcontext()
    if p.strip().lower() in ("1", "on", "true", "yes"):
        p = ""
    if not p:
        d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts")
        os.makedirs(d, exist_ok=True)
        p = os.path.join(d, f"telemetry_{app}.jsonl")
    # fleet_event_log: single-process this IS event_log(path, mode="w");
    # under process_count() > 1 each process writes its own
    # telemetry_<app>_pNNN.jsonl stamped with pidx/slice, and
    # `telemetry report <artifacts dir>` (or --fleet) merges them
    from dlrm_flexflow_tpu.telemetry import fleet_event_log

    return fleet_event_log(path=p, mode="w")


@contextlib.contextmanager
def _provenance(what):
    """A phase after the timed windows that yields a layer metric
    (device-busy time, per-op times, bytes moved, the calibration fit).
    On a TPU those metrics are what the run is for, so a failure fails
    the run; on a CPU the caller asked for, it is a comment on stderr and
    the measurement (history append + JSON line) still lands."""
    import jax

    try:
        yield
    except Exception as e:
        if jax.default_backend() == "tpu":
            raise
        print(f"# {what} failed: {e!r}", file=sys.stderr)


def _telemetry_tail(model, state, inputs, thpt, batch, nb, epochs):
    """Post-timing telemetry: the best fenced window as one ``step``
    event, per-op measured-vs-analytic times (``op_time`` via OpTimer),
    and one simulator calibration fit against the measured per-step
    time — the report CLI's per-op table and sim-vs-measured summary.
    Everything runs AFTER the timed windows (it cannot perturb the
    measurement) and no-ops when telemetry is off."""
    from dlrm_flexflow_tpu.telemetry import active_log, sample_memory

    log = active_log()
    if log is None:
        return
    best_t = epochs * nb * batch / float(thpt)
    with _provenance("window/memory telemetry"):
        log.emit("step", wall_s=best_t, samples=epochs * nb * batch,
                 samples_per_s=float(thpt), steps=nb, epochs=epochs,
                 fenced=True, phase="bench_window")
        sample_memory(phase="bench")
    with _provenance("op-time telemetry"):
        from dlrm_flexflow_tpu.profiling import OpTimer

        OpTimer(model, iters=int(os.environ.get("BENCH_OPTIMER_ITERS",
                                                3))).profile(state, inputs)
    with _provenance("sim-calibration telemetry"):
        import jax

        from dlrm_flexflow_tpu.sim.search import data_parallel_strategy
        from dlrm_flexflow_tpu.sim.simulator import Simulator

        n = jax.device_count()
        Simulator(model, n).calibrate(data_parallel_strategy(model, n),
                                      best_t / float(epochs * nb))


def _checkpoint_tail(model, state, app):
    """Optional provenance checkpoint: ``BENCH_CHECKPOINT=<dir>`` commits
    the benched final state atomically (resilience.CheckpointManager —
    SHA-256 manifest, tmp+rename) under ``<dir>/<app>/`` after the timed
    windows, so a measured configuration is restorable for later
    regression hunts.  The save's ``checkpoint`` telemetry events land
    in the run's JSONL.  Best-effort like all bench telemetry — and the
    manager itself never raises on I/O failure."""
    d = os.environ.get("BENCH_CHECKPOINT", "").strip()
    if not d or d.lower() in ("0", "off", "none", "false", "no"):
        return
    try:
        from dlrm_flexflow_tpu.resilience import CheckpointManager

        CheckpointManager(os.path.join(d, app), keep_n=2).save(
            state, model=model)
    except Exception as e:
        print(f"# bench checkpoint failed: {e!r}", file=sys.stderr)


def _exposed_comm_extra():
    """Measured exposed-comm share of the run as extra provenance —
    like ``strategy_version``: remaps nothing numeric and is NOT part
    of the anchor key.  Read from the run's ``phase_time`` summary
    events (the fit loops emit them; the scanned bench windows have no
    host loop to attribute, so the field is simply absent there)."""
    try:
        from dlrm_flexflow_tpu.telemetry import active_log

        log = active_log()
        if log is None:
            return {}
        sums = [e for e in log.events("phase_time")
                if e.get("phase") != "step" and "exposed_comm_pct" in e]
        if not sums:
            return {}
        return {"exposed_comm_pct":
                round(float(sums[-1]["exposed_comm_pct"]), 2)}
    except Exception:
        return {}


def _model_flops_per_step(model, batch):
    """Forward+backward FLOPs for one train step: each op exposes
    forward FLOPs (``Op.flops``, the simulator's analytic hook), and the
    backward pass costs ~2x forward (dgrad+wgrad — the same convention
    as sim/cost_model._analytic_op)."""
    total = 0.0
    for op in model.layers:
        total += float(op.flops(batch) or 0)
    return 3.0 * total


def _mfu_extras(model, batch, steps_per_window, prov):
    """Derived per-entry utilization metrics: from the trace-derived
    ``device_busy_ms`` and the model's analytic FLOPs, record achieved
    TFLOP/s and MFU vs the chip's peak for the COMPUTE dtype; from the
    compiled program's cost-analysis bytes, HBM bandwidth utilization.
    Absent inputs yield absent fields, never fake numbers.  The peaks are
    ``TPUMachineModel()``'s — ``_require_peaks_device`` (run by
    ``__main__``) refuses any other TPU before a number is divided by
    them."""
    busy_ms = prov.get("device_busy_ms")
    if not busy_ms:
        return {}
    from dlrm_flexflow_tpu.sim.cost_model import TPUMachineModel

    m = TPUMachineModel()
    out = {}
    flops = _model_flops_per_step(model, batch) * steps_per_window
    if flops > 0:
        tfs = flops / (busy_ms * 1e-3) / 1e12
        dt = str(getattr(model.config, "compute_dtype", "float32"))
        peak = m.peak_flops_bf16 if "bf" in dt else m.peak_flops_f32
        out["model_tflops"] = round(tfs, 3)
        out["mfu_pct"] = round(100.0 * tfs * 1e12 / peak, 2)
    gb = prov.get("window_bytes_gb")
    if gb:
        out["hbm_util_pct"] = round(
            100.0 * gb * 1e9 / (busy_ms * 1e-3) / m.hbm_bandwidth, 2)
    return out


def _require_peaks_device(info):
    """The MFU and HBM-utilization fields divide by the peaks of ONE
    chip (``TPUMachineModel()`` defaults); on any other TPU they would be
    wrong under a right-looking name, so the run stops instead.  (A CPU
    run the caller asked for records no ``device_busy_ms`` and therefore
    no utilization field at all.)"""
    from dlrm_flexflow_tpu.sim.cost_model import TPUMachineModel

    m = TPUMachineModel()
    if info["platform"] == "tpu" and info["kind"] != m.device_kind:
        raise SystemExit(
            f"bench.py's utilization peaks describe {m.device_kind!r} "
            f"({m.name}); this is {info['kind']!r} — add its peaks "
            f"before measuring on it")


def _windows(model, state, inputs, labels, batch, num_batches, epochs, reps,
             place=True):
    """Fenced best-window timing over scanned epochs.

    The shared timing protocol: warmup/compile epoch, then ``reps``
    windows of ``epochs`` chained epochs, each closed by a device fence;
    the best window is reported.  Returns (samples_per_sec, prov) where
    ``prov`` carries trace/cost provenance for the history entry:
    ``device_busy_ms`` (one traced window) and ``window_bytes_gb`` (XLA
    cost-analysis bytes of the compiled window program).
    """
    from dlrm_flexflow_tpu.profiling import device_fence

    if place:
        # dataset placed once with the sharding train_epoch expects (the
        # analogue of the reference's zero-copy attached dataset regions,
        # dlrm.cc:266-382); place=False keeps host inputs for
        # apples-to-apples re-measurement of old anchors
        inputs, labels = model.place_dataset(inputs, labels)
    # the whole window runs as ONE dispatch when the epoch is unchunked
    # (train_epochs: launch overhead + row-cache sweeps amortize over all
    # epochs); chunked epochs keep per-epoch dispatches inside
    chunk_bounds = model._epoch_chunk_bounds(labels.shape[0])
    fused = epochs > 1 and chunk_bounds is None

    def window(state):
        if fused:
            state, _ = model.train_epochs(state, inputs, labels, epochs)
            return state
        for _ in range(epochs):
            state, _ = model.train_epoch(state, inputs, labels)
        return state

    # warmup/compile runs with the log ACTIVE: this is where the window
    # program's XLA compiles happen — the dominant compile events the
    # telemetry JSONL exists to record ("every compile the run paid")
    state = window(state)
    device_fence(state.step)

    # producers silent INSIDE the timed windows: the train_epoch(s)
    # wrappers would otherwise emit+flush step/memory events between t0
    # and the fence, perturbing the measurement the telemetry exists to
    # record (the window summary is emitted by _telemetry_tail; compiles
    # already happened in the unsuppressed warmup above)
    from dlrm_flexflow_tpu.telemetry import suppressed

    best_t = float("inf")
    with suppressed():
        for _ in range(reps):
            t0 = time.perf_counter()
            state = window(state)
            device_fence(state.step)
            best_t = min(best_t, time.perf_counter() - t0)
    # Trace-derived device-busy time for ONE window: the wall-clock above
    # includes every host-side gap, so each history entry also carries
    # the time the device was occupied.  One traced window after timing
    # (tracing perturbs wall, not device-op durations).  BENCH_TRACE=0
    # disables.
    busy_ms = None
    if os.environ.get("BENCH_TRACE", "1") != "0":
        from dlrm_flexflow_tpu.profiling import traced_device_busy_ms

        def _traced():
            device_fence(window(state).step)

        with suppressed(), _provenance("device-busy trace"):
            busy_ms = round(traced_device_busy_ms(_traced), 3)
    prov = {"device_busy_ms": busy_ms}
    # host share of the best wall window (docs/pipeline.md): how far
    # the wall headline sits above the busy-equivalent ceiling because
    # of host-side work/queueing.  Rides the history entry (and the
    # regress CLI's ":host_overhead_pct" lower-is-better gate) so a
    # host-path regression can't hide behind an unchanged busy number.
    if busy_ms:
        wall_ms = best_t * 1e3
        prov["host_overhead_pct"] = round(
            max(0.0, 100.0 * (wall_ms - busy_ms) / wall_ms), 2)
    # XLA cost-analysis bytes of the window program (feeds hbm_util_pct).
    # Lowering does not execute, so donated buffers are untouched;
    # per-epoch (non-fused) programs scale by `epochs`.  Chunked-epoch
    # dispatch runs chunk-shaped programs this lowering would NOT match
    # — skip rather than misattribute; and the AOT compile is a second
    # XLA compilation of the window, so BENCH_COST_BYTES=0 opts out (the
    # tracing flag's sibling).
    if (os.environ.get("BENCH_COST_BYTES", "1") != "0"
            and chunk_bounds is None):
        with _provenance("cost-analysis bytes"):
            if fused:
                ca = (model._train_epochs
                      .lower(state, inputs, labels, epochs)
                      .compile().cost_analysis())
                mult = 1.0
            else:
                ca = (model._train_epoch.lower(state, inputs, labels)
                      .compile().cost_analysis())
                mult = float(epochs)
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            nbytes = float(ca.get("bytes accessed", 0.0))
            if nbytes > 0:
                prov["window_bytes_gb"] = round(mult * nbytes / 1e9, 3)
    return epochs * num_batches * batch / float(best_t), prov


def main():
    import jax
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

    batch = int(os.environ.get("BENCH_BATCH", 256))
    num_batches = int(os.environ.get("BENCH_BATCHES", 512))
    epochs = int(os.environ.get("BENCH_EPOCHS", 3))
    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    # Mixed precision is the TPU-idiomatic default: bf16 MXU matmuls with
    # f32 accumulation (preferred_element_type) and f32 master weights —
    # the MXU analogue of the reference's fp32 cublasSgemm path.
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")

    cfg = DLRMConfig()  # run_random.sh architecture
    cfg.embedding_size = [rows] * 8
    # BENCH_FUSED={off,auto,on}: build the gather->pool->interact chain
    # as the ONE FusedEmbedInteract op (cost-model kernel dispatch
    # inside; bit-exact vs the classic graph, so like compute dtype it
    # is provenance, not part of the anchor key)
    cfg.fused_interaction = (os.environ.get("BENCH_FUSED", "off")
                             .strip().lower() or "off")
    # fp32 table storage is the default: like-for-like with the
    # reference's fp32 tables and with the fp32 anchor entry (emb_dtype
    # is part of the history key — advisor r1).  BENCH_EMB_DTYPE=bfloat16
    # measures the halved-sweep variant, anchored separately.
    emb_dtype = os.environ.get("BENCH_EMB_DTYPE", "float32")
    ffconfig = ff.FFConfig(batch_size=batch, compute_dtype=dtype,
                           embedding_dtype=emb_dtype)
    # BENCH_PREFETCH=N: async input-pipeline depth (FFConfig.
    # prefetch_depth, docs/pipeline.md).  The headline windows dispatch
    # scanned epochs (no per-batch loader on the hot path), so like
    # BENCH_FUSED this is graph-shape-neutral provenance, NOT part of
    # the anchor key — numerics are bit-exact prefetch on/off (pinned
    # by tests/test_pipeline.py).
    prefetch = int(os.environ.get("BENCH_PREFETCH", "0") or 0)
    ffconfig.prefetch_depth = prefetch
    # BENCH_OVERLAP={off,auto,on}: build bottom-MLP + stacked embedding
    # as ONE OverlappedEmbedBottom op so the manual table exchange
    # (BENCH_EXCHANGE={allgather,all_to_all}) pipelines each
    # microbatch's ICI collective under its dense slice
    # (parallel/overlap.py, docs/pipeline.md).  Overlap REORDERS
    # collective reductions, so unlike BENCH_FUSED it IS part of the
    # anchor key (the regress CLI suffixes ":overlap=" the same way);
    # BENCH_OVERLAP_K is the pipeline depth (provenance), BENCH_MESH
    # ("data=2,model=2") the mesh the run shards over (the mesh string
    # rides the anchor key like serving entries).
    overlap = (os.environ.get("BENCH_OVERLAP", "off")
               .strip().lower() or "off")
    overlap_k = int(os.environ.get("BENCH_OVERLAP_K", "2") or 2)
    exchange = (os.environ.get("BENCH_EXCHANGE", "off")
                .strip().lower() or "off")
    cfg.exchange_overlap = overlap
    cfg.exchange_microbatches = overlap_k
    ffconfig.table_exchange = exchange
    mesh_env = os.environ.get("BENCH_MESH", "").strip()
    if mesh_env:
        ffconfig.mesh_shape = {
            a: int(s) for a, s in
            (kv.split("=") for kv in mesh_env.split(","))}
    # table_parallel follows the EXCHANGE knob alone: BENCH_OVERLAP
    # without an exchange is a documented no-op for the graph shape
    # ("auto" engages only with a manual exchange), and silently
    # flipping the classic graph's sharding would confound the
    # serial-vs-overlap A/B the ":overlap=" anchors exist to keep clean
    model = build_dlrm(cfg, ffconfig, table_parallel=exchange != "off")
    # BENCH_STRATEGY=<strategy artifact>: run the headline under a
    # search-tune winner (sim/tune.py, docs/tuning.md).  The artifact is
    # schema-checked before it can steer a measurement; its version is
    # recorded as provenance (a strategy remaps execution, it does not
    # change numerics — like BENCH_FUSED it is not part of the anchor
    # key).
    strategy, strategy_version = None, None
    sp = os.environ.get("BENCH_STRATEGY", "").strip()
    if sp and sp.lower() not in ("0", "off", "none", "false", "no"):
        from dlrm_flexflow_tpu.sim.tune import (load_strategy_artifact,
                                                strategy_from_artifact)
        sdoc = load_strategy_artifact(sp)
        if sdoc["app"] != "dlrm" \
                or sdoc["num_devices"] != jax.device_count():
            # strategies are scoped per (app, device count) — the
            # reason sim/tune.py topology-scopes incumbents; refusing a
            # mismatch here keeps strategy_version provenance honest: a
            # recorded version really steered the measurement it
            # annotates (a foreign app's op names would silently match
            # nothing)
            raise SystemExit(
                f"BENCH_STRATEGY {sp} targets "
                f"{sdoc['app']}/{sdoc['num_devices']}dev but this "
                f"bench runs dlrm on {jax.device_count()} device(s) — "
                f"re-tune for this topology")
        strategy = strategy_from_artifact(sdoc)
        strategy_version = sdoc["version"]
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"),
                  mesh=False if jax.device_count() == 1 else None,
                  strategy=strategy)
    state = model.init(seed=0)

    rng = np.random.default_rng(0)
    inputs = {
        "dense": rng.standard_normal(
            (num_batches, batch, cfg.mlp_bot[0])).astype(np.float32),
        "sparse": rng.integers(
            0, rows, size=(num_batches, batch, 8, cfg.embedding_bag_size),
            dtype=np.int64),
    }
    labels = rng.integers(0, 2,
                          size=(num_batches, batch, 1)).astype(np.float32)
    reps = int(os.environ.get("BENCH_REPS", 5))
    thpt, prov = _windows(
        model, state, inputs, labels, batch, num_batches, epochs, reps,
        place=not os.environ.get("BENCH_HOST_INPUTS"))
    _telemetry_tail(model, state, inputs, thpt, batch, num_batches, epochs)
    _checkpoint_tail(model, state, "dlrm")
    # vs_baseline: FIRST fenced history entry of the same config is the
    # anchor, so improvements accumulate instead of drifting with the
    # previous run's noise (the reference publishes no numbers,
    # BASELINE.md).  "emb_dtype" IS part of the key (fp32 and bf16 table
    # storage change the numerics, so their speedup ratios must not mix —
    # advisor r1); compute "dtype" is not: bf16 MXU matmuls with f32
    # accumulation and f32 master weights track the fp32 loss trajectory
    # (pinned by test) and are credited as a framework optimization.
    # the mesh shape rides the anchor key whenever one is active: a
    # sharded training run and the single-device headline must never
    # share an anchor (the serving entries' "mesh" convention)
    mesh_str = ("" if model.mesh is None else
                ",".join(f"{a}={s}" for a, s in
                         zip(model.mesh.axis_names,
                             model.mesh.devices.shape)))
    # the multi-host / pod shape rides the anchor key (the PR 9
    # :replicas=/:mesh= pattern): a 2-host or 2-slice run trains a
    # different physical topology — different collectives on different
    # links — and must never gate the single-host baseline
    # (telemetry/regress.py suffixes ":hosts="/":slices=" the same
    # way; entries predating the fields count as 1 in matches())
    from dlrm_flexflow_tpu.distributed import pod_topology
    hosts = jax.process_count()
    slices = pod_topology().num_slices
    _emit("dlrm_synthetic_samples_per_sec", thpt,
          {"app": "dlrm", "batch": batch, "num_batches": num_batches,
           "epochs": epochs, "rows": rows, "emb_dtype": emb_dtype,
           "overlap": overlap, "mesh": mesh_str, "hosts": hosts,
           "slices": slices},
          extra={"dtype": dtype, "fused": cfg.fused_interaction,
                 "prefetch": prefetch, "exchange": exchange,
                 "overlap_k": overlap_k, **prov,
                 **({"strategy_version": strategy_version}
                    if strategy_version is not None else {}),
                 **_exposed_comm_extra(),
                 **_mfu_extras(model, batch, epochs * num_batches, prov)})


# --------------------------------------------------------------------------
# Additional headline configs (BASELINE.json "configs"): BENCH_APP selects
# one; the default "dlrm" is the synthetic run_random.sh workload above.
# Each prints the same one-line JSON protocol.

def kaggle_model(batch: int, dtype: str = "bfloat16"):
    """The anchored dlrm_kaggle bench model — the one shared
    criteo_kaggle_config() shape (apps/dlrm.py), so this benchmark,
    scripts/bench_kaggle_windows.py, and examples/dlrm_criteo.py always
    measure the identical architecture."""
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import build_dlrm, criteo_kaggle_config

    cfg = criteo_kaggle_config()
    model = build_dlrm(cfg, ff.FFConfig(batch_size=batch,
                                        compute_dtype=dtype))
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"),
                  mesh=False if jax.device_count() == 1 else None)
    return cfg, model


def kaggle_inputs(cfg, batch: int, nb: int, seed: int = 0):
    """Stacked synthetic batches for the kaggle model (per-column id
    ranges)."""
    rng = np.random.default_rng(seed)
    inputs = {"dense": rng.standard_normal(
        (nb, batch, cfg.mlp_bot[0])).astype(np.float32),
        "sparse": np.stack([rng.integers(0, r,
                                         size=(nb, batch,
                                               cfg.embedding_bag_size),
                                         dtype=np.int64)
                            for r in cfg.embedding_size], axis=2)}
    labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
    return inputs, labels


# conv apps and their default activation STORAGE dtype (one constant so
# the config mutation and the act_dtype anchor-key emit can't drift
# apart).  Defaults are the paired-A/B winners, trace-busy measured:
# bf16 activations win 21% on Inception (big spatial activations ->
# bandwidth dominates, PERF.md round 4) and — since the round-5 bf16
# conv epilogues removed the f32 activation round-trips — now also win
# 4.4% on AlexNet (busy 128.2 f32 vs 122.6 bf16; the round-4 f32 win
# was the cost of the inserted converts, which no longer exist).
CONV_APPS = {"alexnet": "bfloat16", "inception": "bfloat16"}


def build_conv_app(app: str, batch: int, nb: int,
                   dtype: str | None = None, act_dtype: str | None = None):
    """THE conv-app bench construction, shared by ``bench_app`` and
    ``scripts/profile_app.py`` so profiles always attribute the exact
    configuration the bench anchors (advisor r4): same config mutations
    (incl. the per-app activation-storage default from CONV_APPS), same
    compile arguments, same synthetic data.  Returns
    ``(model, inputs, labels)`` with HOST inputs."""
    import jax
    import dlrm_flexflow_tpu as ff

    if app not in CONV_APPS:
        raise ValueError(f"not a conv app: {app!r}")
    if dtype is None:
        dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    rng = np.random.default_rng(0)
    fc = ff.FFConfig(batch_size=batch, compute_dtype=dtype)
    mesh = False if jax.device_count() == 1 else None
    # per-app activation-storage default (see CONV_APPS); loss
    # trajectory pinned by tests/test_ops.py either way
    fc.activation_dtype = (act_dtype
                           or os.environ.get("BENCH_ACT_DTYPE",
                                             CONV_APPS[app]))
    if app == "alexnet":
        # "AlexNet single-device, synthetic data, default data-parallel"
        from dlrm_flexflow_tpu.apps.alexnet import build_alexnet
        model = build_alexnet(fc)
        strategy, side = None, 229
    elif app == "inception":
        # "InceptionV3 with SOAP auto-searched op/attr-parallel strategy"
        from dlrm_flexflow_tpu.apps.inception import build_inception
        model = build_inception(fc)
        strategy, side = None, 299
        if jax.device_count() > 1:
            # a searched strategy only changes execution when there is a
            # mesh to shard over; on one chip skip the search rather than
            # discard its result
            from dlrm_flexflow_tpu.sim.search import mcmc_search
            strategy = mcmc_search(model, jax.device_count(),
                                   budget=int(os.environ.get("BENCH_BUDGET",
                                                             100)))
    else:
        raise ValueError(f"not a conv app: {app!r}")
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=("accuracy",), mesh=mesh, strategy=strategy)
    inputs = {"input": rng.standard_normal(
        (nb, batch, 3, side, side)).astype(np.float32)}
    labels = rng.integers(0, 10, size=(nb, batch, 1)).astype(np.int32)
    return model, inputs, labels


def bench_app(app: str):
    import jax
    import dlrm_flexflow_tpu as ff

    batch = int(os.environ.get("BENCH_BATCH", 64))
    nb = int(os.environ.get("BENCH_BATCHES", 16))
    epochs = int(os.environ.get("BENCH_EPOCHS", 2))
    reps = int(os.environ.get("BENCH_REPS", 3))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    if app in CONV_APPS:
        # build_conv_app owns the conv-app config/rng/mesh (shared with
        # scripts/profile_app.py) — nothing else is constructed here so
        # the two paths cannot drift
        model, inputs, labels = build_conv_app(app, batch, nb, dtype)
        rng = fc = mesh = None
    else:
        rng = np.random.default_rng(0)
        fc = ff.FFConfig(batch_size=batch, compute_dtype=dtype)
        mesh = False if jax.device_count() == 1 else None
    if app in CONV_APPS:
        pass
    elif app == "nmt":
        # "NMT LSTM seq2seq (nmt/), attribute-parallel RNN layers" at the
        # REFERENCE scale (nmt/nmt.cc:36-50: vocab 20480, embed/hidden
        # 2048, 2 layers) — the toy override benched through round 2
        # evidenced nothing about the real workload (VERDICT r2 item 6);
        # the key carries the scale so the two never share an anchor
        from dlrm_flexflow_tpu.apps.nmt import NMTConfig, build_nmt
        cfg = NMTConfig()
        model = build_nmt(cfg, fc, seq_shards=2)
        model.compile(optimizer=ff.SGDOptimizer(lr=0.1),
                      loss_type="sparse_categorical_crossentropy",
                      metrics=("sparse_categorical_crossentropy",),
                      mesh=mesh)
        inputs = {
            "src": rng.integers(0, cfg.vocab_size,
                                size=(nb, batch, cfg.src_len),
                                dtype=np.int32),
            "tgt_in": rng.integers(0, cfg.vocab_size,
                                   size=(nb, batch, cfg.tgt_len),
                                   dtype=np.int32),
        }
        labels = rng.integers(0, cfg.vocab_size,
                              size=(nb, batch, cfg.tgt_len, 1)).astype(
                                  np.int32)
    elif app in ("dlrm_kaggle", "dlrm_hybrid", "dlrm_criteo"):
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        if app in ("dlrm_kaggle", "dlrm_criteo"):
            # "DLRM small (Criteo-Kaggle), data-parallel embeddings + MLP";
            # dlrm_criteo is the same model on Zipf(1.05)-skewed ids — the
            # realistic stand-in for real Criteo columns (the reference's
            # flagship real-data path, dlrm.cc:266-382): far fewer
            # distinct rows than lookups, the epoch row-cache's regime
            cfg, model = kaggle_model(batch, dtype)  # compiles internally
        else:
            # "DLRM Criteo-Terabyte, SOAP hybrid (table-parallel
            # embeddings, DP MLP)" — TB-scale tables, hybrid strategy
            cfg = DLRMConfig()
            cfg.embedding_size = [int(os.environ.get("BENCH_ROWS",
                                                     1_000_000))] * 8
            model = build_dlrm(cfg, fc, table_parallel=True)
            model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                          loss_type="mean_squared_error",
                          metrics=("accuracy", "mean_squared_error"),
                          mesh=mesh)
        dense = rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32)
        if app == "dlrm_criteo":
            from dlrm_flexflow_tpu.data.loader import zipf_ids
            inputs = {"dense": dense,
                      "sparse": np.stack(
                          [zipf_ids(rng, rows_i,
                                    (nb, batch, cfg.embedding_bag_size))
                           for rows_i in cfg.embedding_size], axis=2)}
        elif model._dlrm_stacked:
            # per-column ranges (column t < rows_t) — serves both the
            # uniform stacked and the ragged (Kaggle) table sets
            inputs = {"dense": dense,
                      "sparse": np.stack(
                          [rng.integers(0, rows_i,
                                        size=(nb, batch,
                                              cfg.embedding_bag_size),
                                        dtype=np.int64)
                           for rows_i in cfg.embedding_size], axis=2)}
        else:
            inputs = {"dense": dense}
            for i, rows_i in enumerate(cfg.embedding_size):
                inputs[f"sparse_{i}"] = rng.integers(
                    0, rows_i, size=(nb, batch, cfg.embedding_bag_size),
                    dtype=np.int64)
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
    else:
        raise SystemExit(f"unknown BENCH_APP {app!r}")

    # provenance of the input-pipeline knob (see main(): graph-shape-
    # neutral, never part of the anchor key)
    prefetch = int(os.environ.get("BENCH_PREFETCH", "0") or 0)
    model.config.prefetch_depth = prefetch
    state = model.init(seed=0)
    thpt, prov = _windows(model, state, inputs, labels, batch, nb, epochs,
                          reps)
    _telemetry_tail(model, state, inputs, thpt, batch, nb, epochs)
    _checkpoint_tail(model, state, app)
    key = {"app": app, "batch": batch, "num_batches": nb, "epochs": epochs}
    extra = {"dtype": dtype, "prefetch": prefetch, **prov,
             **_exposed_comm_extra(),
             **_mfu_extras(model, batch, epochs * nb, prov)}
    if app in CONV_APPS:
        # activation STORAGE dtype changes numerics (loss pinned only to
        # within 0.05), so like emb_dtype it is part of the anchor key:
        # f32- and bf16-activation runs never share an anchor (advisor
        # r3).  Records predating the field count as float32 in
        # matches().  Cross-precision trajectory lives in PERF.md.
        key["act_dtype"] = str(
            getattr(model.config, "activation_dtype", "float32"))
    if app == "nmt":
        # the FULL scale tuple anchors the entry: any dimension change
        # (vocab/embed/hidden/layers/lengths) is a different workload
        # and must never share an anchor with this one
        key["vocab"] = cfg.vocab_size
        key["embed"] = cfg.embed_size
        key["hidden"] = cfg.hidden_size
        key["layers"] = cfg.num_layers
        key["seq"] = [cfg.src_len, cfg.tgt_len]  # json round-trips lists
    if app in ("dlrm_kaggle", "dlrm_hybrid", "dlrm_criteo"):
        key["rows"] = max(cfg.embedding_size)
        # table-storage dtype is numerics-relevant, so it is part of the
        # anchor key here exactly as in main() (advisor r2); entries
        # predating the field count as float32 in matches()
        key["emb_dtype"] = str(
            np.dtype(model.config.embedding_dtype
                     if hasattr(model.config, "embedding_dtype")
                     else "float32"))
        # provenance: since round 2 the kaggle config runs the 26
        # non-uniform tables as ONE fused RaggedStackedEmbedding row
        # space (ops/embedding.py), not 26 separate Embedding ops
        extra["arch"] = ("stacked_hybrid" if app == "dlrm_hybrid"
                         else "ragged_fused")
    _emit(f"{app}_samples_per_sec", thpt, key, extra=extra)


def bench_serving():
    """Serving headline: the synthetic run_random.sh DLRM behind an
    InferenceEngine + DynamicBatcher under closed-loop load
    (docs/serving.md) — ``dlrm_serving_qps`` next to the training
    samples/s metric.  BENCH_CLIENTS threads each fire BENCH_REQUESTS
    requests of BENCH_REQ_ROWS rows back-to-back; buckets come from
    BENCH_BUCKETS.  The engine AOT-compiles every bucket at warmup
    (untimed, like the training windows' AOT epoch builds), so the
    measured window never recompiles; its ``serve`` telemetry events
    land in the run's JSONL for the report CLI's ``== serving ==``
    section."""
    import jax
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.serving import (DynamicBatcher, InferenceEngine,
                                           parse_buckets)
    from scripts.serve_bench import closed_loop

    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    clients = int(os.environ.get("BENCH_CLIENTS", 8))
    requests = int(os.environ.get("BENCH_REQUESTS", 64))
    req_rows = int(os.environ.get("BENCH_REQ_ROWS", 1))
    buckets = os.environ.get("BENCH_BUCKETS", "1,8,64,256")
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    # BENCH_QUANTIZE={off,int8,bf16}: row-quantized serving tables
    # (docs/serving.md).  Quantization changes numerics (tolerance-
    # pinned), so like emb_dtype it is part of the anchor key — f32 and
    # quantized runs never share an anchor.
    quantize = (os.environ.get("BENCH_QUANTIZE", "off")
                .strip().lower() or "off")
    # BENCH_REPLICAS: batcher replicas behind the least-loaded
    # ReplicaRouter (docs/serving.md).  A 4-replica run measures a
    # different serving topology, so like quantize it is PART of the
    # anchor key — an N-replica QPS entry never gates against the
    # single-replica baseline (regress keys ":replicas=N" the same way)
    replicas = int(os.environ.get("BENCH_REPLICAS", 1))
    # BENCH_STORAGE={resident,tiered}: tiered embedding storage
    # (docs/storage.md).  A tiered run pays hot-cache miss stalls by
    # design, so like quantize it is PART of the anchor key — a tiered
    # entry never gates the fully-resident baseline (regress keys
    # ":storage=tiered" the same way).  BENCH_HOT_ROWS is the
    # per-table device budget; BENCH_ID_DIST/BENCH_ZIPF_ALPHA shape
    # the request-pool id traffic (power-law skew is what makes the
    # cache win — and what the dispatch gate demands evidence of).
    storage = (os.environ.get("BENCH_STORAGE", "resident")
               .strip().lower() or "resident")
    hot_rows = int(os.environ.get("BENCH_HOT_ROWS", 4096))
    id_dist = (os.environ.get("BENCH_ID_DIST", "uniform")
               .strip().lower() or "uniform")
    zipf_alpha = float(os.environ.get("BENCH_ZIPF_ALPHA", 1.05))
    cfg = DLRMConfig()  # run_random.sh architecture — same as main()
    cfg.embedding_size = [rows] * 8
    cfg.fused_interaction = (os.environ.get("BENCH_FUSED", "off")
                             .strip().lower() or "off")
    fc = ff.FFConfig(batch_size=parse_buckets(buckets)[-1],
                     compute_dtype=dtype, serve_buckets=buckets,
                     serve_storage=storage, storage_hot_rows=hot_rows)
    model = build_dlrm(cfg, fc)
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="mean_squared_error", metrics=(),
                  mesh=False if jax.device_count() == 1 else None)
    # the mesh shape (if any) rides the anchor key too: mesh-native
    # serving shards the forward differently per topology, and an
    # 8-chip entry must never anchor a 1-chip run
    mesh_str = ("" if model.mesh is None else
                ",".join(f"{a}={s}" for a, s in
                         zip(model.mesh.axis_names, model.mesh.devices.shape)))
    rng = np.random.default_rng(0)
    # request pool in main()'s input convention: uniform tables, one
    # (rows, T, bag) id block — NOT the per-table ragged stacking the
    # tiny serve_bench/check_serving models use
    def _ids(size):
        if id_dist == "zipf":
            from dlrm_flexflow_tpu.data.loader import zipf_ids
            return zipf_ids(rng, rows, int(np.prod(size)),
                            a=zipf_alpha).reshape(size)
        return rng.integers(0, rows, size=size, dtype=np.int64)
    pool = [{"dense": rng.standard_normal(
                 (req_rows, cfg.mlp_bot[0])).astype(np.float32),
             "sparse": _ids((req_rows, 8, cfg.embedding_bag_size))}
            for _ in range(128)]
    if storage == "tiered":
        # feed the pool's id traffic to the row-frequency counters the
        # LFU admission warm start and the dispatch gate's predicted
        # hit rate read — the bench's stand-in for a prior run's
        # observed traffic (docs/storage.md)
        from dlrm_flexflow_tpu.telemetry import rowfreq
        for r in pool:
            for t in range(r["sparse"].shape[1]):
                rowfreq.counter(f"sparse[{t}]").observe(r["sparse"][:, t])
    engine = InferenceEngine(model, model.init(seed=0),
                             quantize=quantize,  # warmup: AOT all
                             storage=storage)
    # anchor the mode that actually RAN: the dispatch gate may refuse
    # tiering (no skew evidence, budget >= table) and fall back to
    # resident — that run must share the resident anchor
    storage = engine.storage.get("mode", storage)
    if replicas > 1:
        from dlrm_flexflow_tpu.serving import ReplicaRouter

        batcher = ReplicaRouter([engine] * replicas)
    else:
        batcher = DynamicBatcher(engine)
    wall, _rejected = closed_loop(batcher, pool, clients, requests)
    summary = batcher.close()  # drains + emits the serve summary event
    # SERVED requests only — shed (Rejected) submissions must not
    # inflate the headline or its history anchor
    qps = summary["requests"] / max(wall, 1e-9)
    extra = {"dtype": dtype, "fused": cfg.fused_interaction,
             **{k: round(summary[k], 1) for k in
                ("p50_us", "p95_us", "p99_us") if k in summary}}
    if engine.storage.get("mode") == "tiered":
        # provenance (excluded from matching): the live cache numbers
        # behind the dlrm_embed_cache_* gauges this run exported
        sst = engine.storage_stats()
        extra.update(id_dist=id_dist,
                     hot_rows=hot_rows,
                     hit_pct=round(sst.get("hit_pct", 0.0), 2),
                     miss_stall_us=round(sst.get("stall_us_last", 0.0), 1))
    _emit("dlrm_serving_qps", qps,
          {"app": "dlrm_serving", "metric": "dlrm_serving_qps",
           "rows": rows, "clients": clients, "req_rows": req_rows,
           "buckets": buckets, "quantize": quantize,
           "replicas": replicas, "mesh": mesh_str, "storage": storage},
          extra=extra, unit="requests/s")
    # second serving headline: engine-forward p99 at the LARGEST bucket
    # the run dispatched (per-bucket histograms, LatencyStats) — the
    # tail-latency number the quantized tables exist to cut.  LOWER is
    # better; the regress CLI knows (latency metrics invert the gate).
    dispatched = engine.stats.bucket_histograms()  # locked snapshot
    if dispatched:
        top_bucket = max(dispatched)
        p99_us = engine.stats.bucket_percentile(top_bucket, 99)
        if p99_us is not None:
            # "bucket" is PART of the anchor key: which bucket ends up
            # largest is load/timing-dependent, and a bucket-8 p99 must
            # never gate against a bucket-64 anchor
            _emit("dlrm_serving_p99_ms", p99_us / 1e3,
                  {"app": "dlrm_serving", "metric": "dlrm_serving_p99_ms",
                   "rows": rows, "clients": clients, "req_rows": req_rows,
                   "buckets": buckets, "quantize": quantize,
                   "bucket": top_bucket, "replicas": replicas,
                   "mesh": mesh_str, "storage": storage},
                  extra={"dtype": dtype, "fused": cfg.fused_interaction},
                  unit="ms")


if __name__ == "__main__":
    from dlrm_flexflow_tpu.entrypoint import (enable_compile_cache,
                                              require_tpu)

    enable_compile_cache()
    _require_peaks_device(require_tpu(allow_requested_cpu=True))
    app = os.environ.get("BENCH_APP", "dlrm")
    # the EventLog scopes the WHOLE run so the jax.monitoring hooks see
    # every compile (warmup, AOT window builds, OpTimer's isolated jits)
    with _telemetry_ctx(app):
        sys.exit(main() if app == "dlrm"
                 else bench_serving() if app == "dlrm_serving"
                 else bench_app(app))
