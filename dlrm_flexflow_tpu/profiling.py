"""Profiling / tracing utilities.

TPU-native equivalent of the reference's profiling stack (SURVEY §5.1):
  Legion tracing (-dm:memoize)        -> jit compilation cache +
                                         FFModel.train_epoch scan
  Legion profiler (-lg:prof)          -> jax.profiler traces (XPlane,
                                         viewable in TensorBoard/Perfetto)
  per-op cudaEvent timing (--profiling,
    linear.cu:499-531)               -> per-op wall-clock via OpTimer
  execution fence + TimingLauncher    -> device_fence + perf_counter
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict

import jax


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a jax.profiler trace for the enclosed block
    (the -lg:prof analogue)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_fence(x):
    """Execution fence by read-back: force a device->host read of one
    element of every leaf (every addressable shard) of ``x`` — the
    transfer cannot complete until the program that produced it has.

    History and status (PERF.md, PR 21): this exists because
    ``jax.block_until_ready`` returned early on a previous installation
    (donated-buffer ready events).  On the current one (TPU v5e, jax
    0.9.0) it does not: a window of chained, state-donating
    ``train_epoch`` dispatches closed by ``block_until_ready`` lasts as
    long as the device is busy (53.3 ms against 51.6 ms traced busy),
    and a ``device_fence`` after it only adds its own cost — one small
    program launch plus one transfer per leaf, about 1.1 ms per leaf:
    22 ms for the DLRM TrainState's ~19 leaves, which turns that 53 ms
    window into 77 ms.  So fence on one small leaf of the last
    program's output (``state.step``), as ``fit`` and ``bench.py`` do.
    Whether the 14 callers move to ``block_until_ready`` is ROADMAP
    D7."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(x)
    if not leaves:
        return x
    for leaf in leaves:
        try:
            # read one element from EVERY addressable shard so a sharded
            # or replicated array waits for all participating devices, not
            # just the shard that happens to back element 0 — and do it
            # for every leaf, since leaves may come from separate
            # dispatches
            shards = getattr(leaf, "addressable_shards", None)
            datas = [s.data for s in shards] if shards else [leaf]
            for d in datas:
                if getattr(d, "ndim", None) == 0:
                    np.asarray(d)
                elif getattr(d, "size", 0):
                    # index the first element — NOT d.ravel()[0]: ravel
                    # of a tiled (R, 128) device array compiles to a
                    # full-array re-tiling copy (1.25 ms device busy
                    # for the kaggle table, ~7 ms for the 2 GB headline
                    # table — round-5 trace, jit_ravel module), while a
                    # first-element index is a ~2 us dynamic-slice with
                    # the same fencing semantics (its transfer cannot
                    # complete before d's producer has)
                    np.asarray(d[(0,) * d.ndim])
                else:  # zero-size shard: nothing to read, fall back
                    jax.block_until_ready(d)
        except (AttributeError, TypeError):
            jax.block_until_ready(leaf)
    return x


#: what a TPU trace calls things (jax 0.9.0 / libtpu 0.0.34 on a v5e,
#: read by hand — PERF.md, PR 21): one process per chip and, in it, one
#: thread per track.  The other tracks of the process ("Steps" in the
#: trace.json; "Async XLA Ops" and "TC Overlay" in the xplane only)
#: mirror the same wall time and are never summed.
DEVICE_PROCESS_PREFIX = "/device:TPU:"
MODULES_TRACK = "XLA Modules"
OPS_TRACK = "XLA Ops"


def parse_device_trace(logdir: str):
    """Parse the NEWEST ``*.trace.json.gz`` under ``logdir``.

    Returns ``(trace_path, process_names, {op_name: self_us}, busy_ms)``.

    ``busy_ms`` is the "XLA Modules" track total of a ``/device:TPU:<n>``
    process — the wall time that chip was occupied by a program, the
    number the bench records as ``device_busy_ms``.  With several chips
    in the trace it is the BUSIEST chip's (an SPMD program occupies
    every chip for about the same time; a sum would count it once per
    chip), and ``self_us`` is that same chip's.

    ``self_us`` is per-op SELF time on the "XLA Ops" track: op slices
    NEST (a scan's ``while`` slice spans every op executed inside it —
    Ops-track raw sum 4.8 ms against 2.6 ms of module time in a 16-step
    epoch), so each slice's children are subtracted before accumulating.
    A trace with a Modules track but no Ops track attributes at module
    granularity.

    Nothing else is substituted: a trace with no TPU process, or whose
    TPU process has no "XLA Modules" track, raises ``ValueError`` (a CPU
    trace has only ``/host:CPU``).  Shared by
    ``scripts/profile_headline.py`` and ``bench.py``."""
    import gzip
    import json
    import os

    paths = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if f.endswith(".trace.json.gz"):
                paths.append(os.path.join(root, f))
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {logdir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pnames = {}
    tnames = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pnames[e["pid"]] = e["args"].get("name", "")
        elif e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    dev_pids = {p for p, n in pnames.items()
                if n.startswith(DEVICE_PROCESS_PREFIX)}
    if not dev_pids:
        raise ValueError(
            f"no {DEVICE_PROCESS_PREFIX}<n> process in {path} "
            f"(processes: {sorted(pnames.values())})")

    def _track(pid, name):
        return {pt for pt, n in tnames.items() if pt[0] == pid and n == name}

    def _slices(keep_tids):
        for e in events:
            if (e.get("ph") == "X"
                    and (e.get("pid"), e.get("tid")) in keep_tids):
                yield e

    busy_us = {pid: sum(e.get("dur", 0.0)
                        for e in _slices(_track(pid, MODULES_TRACK)))
               for pid in dev_pids}
    pid = max(busy_us, key=busy_us.get)
    if not busy_us[pid]:
        tracks = sorted(n for pt, n in tnames.items() if pt[0] in dev_pids)
        raise ValueError(
            f'no "{MODULES_TRACK}" slices on a TPU process in {path} '
            f"(tracks: {tracks})")

    # self time per op: sort by (ts, -dur) so a parent precedes the
    # children it contains; a stack tracks open slices per track
    tot = {}
    by_tid = {}
    for e in _slices(_track(pid, OPS_TRACK) or _track(pid, MODULES_TRACK)):
        by_tid.setdefault((e["pid"], e.get("tid")), []).append(e)
    for track in by_tid.values():
        track.sort(key=lambda e: (e["ts"], -e.get("dur", 0.0)))
        stack = []  # [end_ts, children_dur, name, dur]
        for e in track:
            ts, dur = e["ts"], e.get("dur", 0.0)
            while stack and stack[-1][0] <= ts:
                _end, kids, nm, d = stack.pop()
                tot[nm] = tot.get(nm, 0.0) + (d - kids)
            if stack:
                stack[-1][1] += dur
            stack.append([ts + dur, 0.0, e["name"], dur])
        while stack:
            _end, kids, nm, d = stack.pop()
            tot[nm] = tot.get(nm, 0.0) + (d - kids)
    return path, pnames, tot, busy_us[pid] / 1e3


def traced_device_busy_ms(fn, logdir: str | None = None) -> float:
    """Run ``fn()`` under a profiler trace and return the device-busy
    time in ms (``parse_device_trace``).  ``fn`` must wait for its own
    work so the trace covers it.  Temp trace dirs are cleaned up
    afterwards."""
    import shutil
    import tempfile

    own = logdir is None
    if own:
        logdir = tempfile.mkdtemp(prefix="ff_bench_trace_")
    try:
        with trace(logdir):
            fn()
        _path, _pnames, _tot, busy_ms = parse_device_trace(logdir)
        return busy_ms
    finally:
        if own:
            shutil.rmtree(logdir, ignore_errors=True)


class Timer:
    """Fenced wall-clock timing (reference dlrm.cc:154-198 protocol)."""

    def __init__(self):
        self.elapsed = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self._t0
        return False

    @staticmethod
    def fence(x):
        device_fence(x)


class OpTimer:
    """Per-op forward timing (reference --profiling flag wrapping kernels
    with cudaEvents, linear.cu:499-531).  Times each op's jitted forward
    in isolation — useful for cost-model calibration and hot-spot lists.

    When a telemetry EventLog is active, each op also lands as one
    ``op_time`` event carrying the measured times NEXT TO the analytic
    simulator's prediction for the same op — the pairing the report
    CLI's sim-vs-measured calibration table reads (docs/telemetry.md;
    the way FlexFlow validates its simulator against measured per-op
    cost, MLSys'19 §5)."""

    def __init__(self, model, iters: int = 10):
        self.model = model
        self.iters = iters

    def profile(self, state, inputs) -> Dict[str, float]:
        from .sim.cost_model import CostModel
        from .telemetry import active_log

        cm = CostModel(measure=True, measure_iters=self.iters)
        sim_cm = CostModel()  # analytic roofline — the simulator's view
        log = active_log()
        out = {}
        for op in self.model.layers:
            fwd, bwd = cm.op_times(op, 1)
            sf, sb = sim_cm.op_times(op, 1)
            out[op.name] = {"forward_s": fwd, "backward_s": bwd,
                            "sim_forward_s": sf, "sim_backward_s": sb}
            if log is not None:
                log.emit("op_time", op=op.name, forward_s=fwd,
                         backward_s=bwd, sim_forward_s=sf,
                         sim_backward_s=sb)
        return out

    def report(self, times: Dict[str, dict]) -> str:
        lines = ["op                        forward(us)  backward(us)"]
        for name, t in sorted(times.items(),
                              key=lambda kv: -kv[1]["forward_s"]):
            lines.append(f"{name:24s} {t['forward_s']*1e6:12.1f} "
                         f"{t['backward_s']*1e6:12.1f}")
        return "\n".join(lines)
