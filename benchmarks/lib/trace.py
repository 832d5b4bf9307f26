"""From a profiler trace to numbers: the benchmark's own reduction.

A copy of what ``dlrm_flexflow_tpu/profiling.py::parse_device_trace``
does (sound since PR 21), plus the per-chip mean, the idle gaps and the
collective share, kept here so that a PR which edits ``profiling.py``
cannot move the yardstick.  Checked on the recorded v5e trace
``tests/data/v5e_train_epoch.trace.json.gz`` (tests/benchmark/).

What a TPU trace calls things (jax 0.9.0 / libtpu 0.0.34, read by hand
in PR 21): one process ``/device:TPU:<n>`` per chip with one thread per
track; "XLA Modules" has one slice per executed program, "XLA Ops" one
per HLO op, nested (a ``while`` spans its body).  Host threads live in
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there.  The
device's clock and the host's differ by about a millisecond in that
trace, so a label from a host span is a hint, not a measurement.
"""

from __future__ import annotations

import gzip
import json
import os
import re

DEVICE_PROCESS_PREFIX = "/device:TPU:"
MODULES_TRACK = "XLA Modules"
OPS_TRACK = "XLA Ops"
#: the benchmark's own host spans (drivers open them around each call)
SPAN_PREFIX = "bench."
#: the converter that writes trace.json.gz drops what exceeds this
MAX_TRACE_EVENTS = 1_000_000
COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)")


def load_newest_trace(logdir: str) -> list:
    """The ``traceEvents`` of the newest ``*.trace.json.gz`` under
    ``logdir``."""
    paths = [os.path.join(root, f) for root, _dirs, files in os.walk(logdir)
             for f in files if f.endswith(".trace.json.gz")]
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {logdir}")
    with gzip.open(max(paths, key=os.path.getmtime), "rt") as f:
        return json.load(f).get("traceEvents", [])


def _self_times(slices) -> dict:
    """Per-name self time in us of nested slices on one track: sorted by
    (start, longest first) a parent precedes its children, whose
    durations are subtracted from it."""
    total = {}
    stack = []  # [end, children_dur, name, dur]

    def close():
        _end, kids, name, dur = stack.pop()
        total[name] = total.get(name, 0.0) + dur - kids

    for e in sorted(slices, key=lambda e: (e["ts"], -e.get("dur", 0.0))):
        ts, dur = e["ts"], e.get("dur", 0.0)
        while stack and stack[-1][0] <= ts:
            close()
        if stack:
            stack[-1][1] += dur
        stack.append([ts + dur, 0.0, e["name"], dur])
    while stack:
        close()
    return total


def reduce_trace(events: list) -> dict:
    """Reduce one trace to::

        {"chips": n, "busy_us_by_chip": {process name: us},
         "busy_us": the busiest chip's, "busy_us_mean": over the chips,
         "modules": slices on the busiest chip's Modules track,
         "self_us": {op name: self us} on that chip's Ops track,
         "gaps": [(start_us, dur_us)] between its module slices,
         "spans": [(name, start_us, dur_us)] the benchmark's host spans}

    Busy is the sum of a chip's "XLA Modules" slices: the time a program
    occupied it.  A trace with no TPU process or no module slice raises
    ``ValueError``; nothing is substituted."""
    if sum(1 for e in events if e.get("ph") == "X") >= MAX_TRACE_EVENTS:
        raise ValueError(
            f"trace holds {MAX_TRACE_EVENTS} slices or more: the converter "
            f"has dropped the rest, trace a shorter window")
    pnames, tnames = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pnames[e["pid"]] = e["args"].get("name", "")
        elif e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    dev_pids = [p for p, n in pnames.items()
                if n.startswith(DEVICE_PROCESS_PREFIX)]
    if not dev_pids:
        raise ValueError(f"no {DEVICE_PROCESS_PREFIX}<n> process in the "
                         f"trace (processes: {sorted(pnames.values())})")
    by_track = {}
    spans = []
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if key[0] in dev_pids:
            by_track.setdefault((key[0], tnames.get(key)), []).append(e)
        elif str(e.get("name", "")).startswith(SPAN_PREFIX):
            spans.append((e["name"], e["ts"], e.get("dur", 0.0)))
    busy = {p: sum(e.get("dur", 0.0)
                   for e in by_track.get((p, MODULES_TRACK), []))
            for p in dev_pids}
    top = max(busy, key=busy.get)
    if not busy[top]:
        raise ValueError(f'no "{MODULES_TRACK}" slice on a TPU process '
                         f"(tracks: {sorted(str(t) for _p, t in by_track)})")
    modules = sorted(by_track[(top, MODULES_TRACK)], key=lambda e: e["ts"])
    gaps, end = [], None
    for e in modules:
        if end is not None and e["ts"] > end:
            gaps.append((end, e["ts"] - end))
        end = max(end or 0.0, e["ts"] + e.get("dur", 0.0))
    return {"chips": len(dev_pids),
            "busy_us_by_chip": {pnames[p]: busy[p] for p in dev_pids},
            "busy_us": busy[top],
            "busy_us_mean": sum(busy.values()) / len(busy),
            "modules": len(modules),
            "self_us": _self_times(by_track.get((top, OPS_TRACK), modules)),
            "gaps": gaps, "spans": spans}


def collective_us(self_us: dict) -> float:
    """Self time of the collective ops (matched on the names the Ops
    track prints: ``all-reduce.3``, ``all-gather-start.1``, ...)."""
    return sum(us for name, us in self_us.items() if COLLECTIVE.match(name))


def top_ops(self_us: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the ``n`` ops with most self time."""
    ranked = sorted(self_us.items(), key=lambda kv: -kv[1])[:n]
    return [[name, us / 1e6] for name, us in ranked]


def longest_gaps(gaps: list, spans: list, n: int = 5) -> list:
    """``[[label, seconds], ...]``: the ``n`` longest idle gaps, each
    labelled with the innermost benchmark span that covers its middle,
    or ``unattributed``."""
    out = []
    for start, dur in sorted(gaps, key=lambda g: -g[1])[:n]:
        mid = start + dur / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        label = min(cover, key=lambda s: s[2])[0] if cover else "unattributed"
        out.append([label, dur / 1e6])
    return out
