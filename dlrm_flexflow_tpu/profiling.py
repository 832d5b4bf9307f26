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
import itertools
import os
import re
import time
import weakref
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


def _closed_slices(track):
    """Walk one track's nested slices and yield ``(slice, self_us, name
    stack)`` as each closes.  Sorted by (start, longest first) a parent
    precedes the children it contains; their durations are subtracted
    from it.  The name stack is the slice's ``args.tf_op`` (the
    instruction's ``op_name`` as the profiler kept it, with a trailing
    ``:``) or, where the profiler kept none though the instruction has
    one — every ``while`` — the longest common prefix of its children's:
    a loop body's instructions all start with the loop's own stack."""
    open_ = []  # [end_ts, slice, children's dur, their common prefix]

    def close():
        _end, e, kids, prefix = open_.pop()
        own = e.get("args", {}).get("tf_op", "").rstrip(":")
        stack = own or "/".join(prefix or ())
        if open_ and stack:
            parts, seen = stack.split("/"), open_[-1][3]
            open_[-1][3] = parts if seen is None else \
                os.path.commonprefix([seen, parts])
        return e, e.get("dur", 0.0) - kids, stack

    for e in sorted(track, key=lambda e: (e["ts"], -e.get("dur", 0.0))):
        ts, dur = e["ts"], e.get("dur", 0.0)
        while open_ and open_[-1][0] <= ts:
            yield close()
        if open_:
            open_[-1][2] += dur
        open_.append([ts + dur, e, 0.0, None])
    while open_:
        yield close()


def _self_times(tracks, key) -> Dict[str, float]:
    """Self time in us per ``key(slice, name stack)`` over some tracks."""
    tot: Dict[str, float] = {}
    for track in tracks:
        for e, us, stack in _closed_slices(track):
            k = key(e, stack)
            tot[k] = tot.get(k, 0.0) + us
    return tot


def _busiest_chip(logdir: str):
    """``(trace_path, process_names, op tracks, busy_us)`` of the NEWEST
    ``*.trace.json.gz`` under ``logdir``: the busiest chip's "XLA Ops"
    slices, one list per track (its "XLA Modules" slices where the
    trace has no Ops track), and that chip's "XLA Modules" total."""
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
    by_tid = {}
    for e in _slices(_track(pid, OPS_TRACK) or _track(pid, MODULES_TRACK)):
        by_tid.setdefault((e["pid"], e.get("tid")), []).append(e)
    return path, pnames, list(by_tid.values()), busy_us[pid]


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
    path, pnames, tracks, busy_us = _busiest_chip(logdir)
    return path, pnames, \
        _self_times(tracks, lambda e, _stack: e["name"]), busy_us / 1e3


def parse_device_trace_phases(logdir: str):
    """``parse_device_trace``'s sibling: ``(trace_path, {phase: self_us},
    busy_ms)`` of the same trace and chip, each op slice counted under
    ``phase_of`` its name stack (``_closed_slices``; a slice with none
    is ``unattributed``).  The operator's route to what
    ``program_phases`` gives the benchmark: on one trace the two agree
    (tests/test_phases.py)."""
    path, _pnames, tracks, busy_us = _busiest_chip(logdir)
    return path, _self_times(tracks, lambda _e, stack: phase_of(stack)), \
        busy_us / 1e3


# --------------------------------------------------------------- phases
#: the phase of an instruction whose name stack holds no phase scope
UNATTRIBUTED = "unattributed"
#: a phase scope: model.py::_compile_body and row_cache.py open them, all
#: under the one prefix ``ff.`` so that a graph op's own scope is never
#: taken for one
_PHASE = re.compile(r"(?<![\w.])ff\.[a-z_]+(?:\.[a-z_]+)*")
_WRAPPER = re.compile(r"([A-Za-z_]\w*)?\(|\)")


def phase_of(op_name: str) -> str:
    """The phase an HLO instruction belongs to, from its ``op_name``
    name stack: the INNERMOST ``ff.*`` scope, seen through ``jvp(...)``
    / ``transpose(...)`` wrappers; inside a ``transpose(`` it is the
    scope's backward and reads ``<scope>.bwd``; ``unattributed`` when
    the stack names no phase.  The one place that knows the naming
    rule (PERF.md §3 lists the scopes)::

        jit(f)/ff.ladder/while/body/ff.step.gather/gather -> ff.step.gather
        .../transpose(jvp(ff.step.model))/top_1/dot_general
                                                    -> ff.step.model.bwd
        jit(f)/jit(_where)/select_n                 -> unattributed

    A run under ``jax.checkpoint`` (``FFModel.scope(recompute=...)``)
    is differentiated apart: in the backward pass its own stack follows
    the *closed* ``transpose(...)`` of the enclosing scope.  There the
    forward computed again reads ``<scope>.remat`` and the rest
    ``<scope>.bwd``::

        .../transpose(jvp(ff.step.model))/jvp(ff.step.model)/checkpoint/
            rematted_computation/ff.lm.ffn/dot_general -> ff.lm.ffn.remat
        .../transpose(jvp(ff.step.model))/jvp(ff.step.model)/checkpoint/
            ff.lm.ffn/mul                              -> ff.lm.ffn.bwd
        jit(f)/jvp(ff.step.model)/checkpoint/ff.lm.ffn/tanh -> ff.lm.ffn

    XLA joins the stacks of instructions it merged with ``;``: the
    first is the full one and decides."""
    stack = op_name.split(";", 1)[0]
    found = None
    for found in _PHASE.finditer(stack):
        pass
    if found is None:
        return UNATTRIBUTED
    wrappers, closed_transpose_at = [], None
    for tok in _WRAPPER.finditer(stack, 0, found.start()):
        if tok.group(0) == ")":
            if wrappers and wrappers.pop() == "transpose" \
                    and "transpose" not in wrappers:
                closed_transpose_at = tok.end()
        else:
            wrappers.append(tok.group(1))
    if "transpose" in wrappers:
        return found.group(0) + ".bwd"
    if closed_transpose_at is not None:
        frames = stack[closed_transpose_at:found.start()].split("/")
        if "checkpoint" in frames:
            return found.group(0) + (".remat" if "rematted_computation"
                                     in frames else ".bwd")
    return found.group(0)


_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_FUSED = re.compile(r"\sfusion\(.*\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)"
                     r"|\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


def hlo_phases(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` of an optimized HLO module's text:
    every instruction of every computation but the fused ones (a
    profiler trace has one slice per instruction that runs on its own;
    what sits inside a fusion never shows), through ``phase_of`` its
    ``metadata={op_name=...}``.  An instruction the compiler made and
    gave no name (a copy, a converted constant) takes the phase of the
    instruction that calls its computation — a ``while`` its body and
    condition, a conditional its branches — as the profiler's
    ``tf_op`` does; in the entry computation it is ``unattributed``."""
    lines = hlo_text.splitlines()
    fused = {m.group(1) for m in map(_FUSED.search, lines) if m}
    named: Dict[str, str] = {}      # instruction -> its own phase
    unnamed: Dict[str, str] = {}    # instruction -> its computation
    callers: Dict[str, set] = {}    # computation -> calling instructions
    computation = None
    for line in lines:
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = None if c.group(1) in fused else c.group(1)
            continue
        if computation is None:
            continue
        op_name = _OP_NAME.search(line)
        if op_name:
            named[m.group(1)] = phase_of(op_name.group(1))
        else:
            unnamed[m.group(1)] = computation
        for one, several in _CALLED.findall(line):
            for callee in [one] if one else re.findall(r"[\w.\-]+", several):
                callers.setdefault(callee, set()).add(m.group(1))

    def resolve(name):  # HLO's call graph has no cycle
        if name in named:
            return named[name]
        got = {resolve(c) for c in callers.get(unnamed[name], ())}
        return got.pop() if len(got) == 1 else UNATTRIBUTED

    return {name: resolve(name) for name in [*named, *unnamed]}


class _Program:
    """One jitted program a training wrapper dispatched under an active
    EventLog: the function (weakly: the registry must not keep a model
    alive), its abstract arguments, the log it was last named in, and
    its phase map once asked for."""

    __slots__ = ("name", "fn", "args", "log", "phases")

    def __init__(self, name, fn, args):
        self.name, self.fn, self.args = name, weakref.ref(fn), args
        self.log = self.phases = None


#: programs by signature and by name.  Filled only under an active
#: EventLog (FFModel's wrappers call ``note_program`` inside the ``log
#: is not None`` check they already make).
_by_signature: Dict[tuple, _Program] = {}
_programs: Dict[str, _Program] = {}
_MAX_PROGRAMS = 256
_program_ids = itertools.count(1)


def _abstract(x):
    """What jit keys its own cache on: shape, dtype and, for a
    COMMITTED array, its sharding (an uncommitted one leaves the
    placement to jit; naming its device would lower another program
    than the one that ran).  Anything else is a static argument."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return x


def note_program(log, fn, args: tuple, **fields) -> str:
    """Remember that the jitted ``fn`` was dispatched with ``args`` and
    name it in ``log``: one ``program`` event per program and log (so
    the per-step path pays a signature lookup, not an event).  Keeps
    shapes, dtypes, shardings and static arguments; reads no device
    value.  ``fields`` join the event (``attention_core``: what the
    model knew of the program when it compiled).  Returns the program's
    name, ``<fn's name>#<n>``."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = (id(fn), treedef,
           tuple((x.shape, x.dtype, x.sharding if x.committed else None)
                 if isinstance(x, jax.Array) else x for x in leaves))
    prog = _by_signature.get(sig)
    if prog is None or prog.fn() is not fn:
        if len(_programs) >= _MAX_PROGRAMS:  # drop what died, else oldest
            dead = [k for k, p in _by_signature.items() if p.fn() is None]
            for k in dead or list(_by_signature)[:1]:
                _programs.pop(_by_signature.pop(k).name, None)
        prog = _Program(f"{fn.__name__}#{next(_program_ids)}", fn,
                        treedef.unflatten([_abstract(x) for x in leaves]))
        _by_signature[sig] = _programs[prog.name] = prog
    if prog.log is None or prog.log() is not log:
        prog.log = weakref.ref(log)
        log.emit("program", name=prog.name, fn=fn.__name__, **fields)
    return prog.name


def program_phases(name: str) -> Dict[str, str]:
    """``{HLO instruction name: phase}`` of the program a ``program``
    event named: ``hlo_phases`` of its optimized HLO, from
    ``.lower(<noted abstract arguments>).compile().as_text()``.  Built
    when first asked and memoised; ask AFTER a measured window (the
    second ``lower().compile()`` is served by the lowering JAX holds or
    by the persistent cache — PERF.md §6 has the measured cost — but it
    is host work).  The executable is dropped once parsed.  Raises
    ``KeyError`` for a name never noted and ``RuntimeError`` when the
    program's function is gone."""
    prog = _programs[name]
    if prog.phases is None:
        fn = prog.fn()
        if fn is None:
            raise RuntimeError(f"the function of program {name!r} is gone")
        prog.phases = hlo_phases(fn.lower(*prog.args).compile().as_text())
    return prog.phases


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
