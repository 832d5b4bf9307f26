"""The phase instrument of the compiled training program, on the CPU:
``profiling.phase_of`` (the one naming rule), ``hlo_phases``,
``note_program`` / ``program_phases`` behind the training wrappers'
``program`` event, spans as profiler annotations, and the fixture the
chip recorded (``scripts/record_phase_fixture.py``): on one trace the
operator's route (``args.tf_op``) and the benchmark's route (the
program's own map) give the same time per phase."""

import glob
import gzip
import json
import os
import shutil

import jax
import numpy as np
import pytest

from dlrm_flexflow_tpu import profiling
from dlrm_flexflow_tpu.profiling import (UNATTRIBUTED, hlo_phases,
                                         parse_device_trace,
                                         parse_device_trace_phases,
                                         phase_of, program_phases)
from dlrm_flexflow_tpu.telemetry import event_log, span, start_span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: every scope model.py::_compile_body and row_cache.py open for the
#: tiny DLRM (PERF.md §3), as phases
SCOPES = {"ff.cache.prologue", "ff.cache.plan", "ff.cache.epilogue",
          "ff.ladder", "ff.ladder.fetch", "ff.ladder.writeback",
          "ff.step.gather", "ff.step.model", "ff.step.model.bwd",
          "ff.step.row_update", "ff.step.dense_update", "ff.step.metrics"}
#: the single-level region layout's leaf fetch runs wholly inside its
#: two sub-scopes (the streamed own region, the gathered foreign rows)
REGION_FETCH = {"ff.ladder.fetch.own", "ff.ladder.fetch.foreign"}


@pytest.mark.parametrize("stack, phase", [
    ("jit(f)/ff.ladder/while/body/closed_call/ff.step.gather/gather",
     "ff.step.gather"),                      # nested: the innermost wins
    ("jit(f)/ff.cache.prologue/ff.ladder.fetch/jit(_take)/gather:",
     "ff.ladder.fetch"),                     # a tf_op's trailing colon
    ("jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.own/"
     "dynamic_slice", "ff.ladder.fetch.own"),  # a sub-scope is a phase
    ("jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.foreign/"
     "while/body/jit(_take)/gather:", "ff.ladder.fetch.foreign"),
    ("jit(f)/ff.ladder/while/body/jvp(ff.step.model)/top_1/dot_general",
     "ff.step.model"),                       # forward, seen through jvp
    ("jit(f)/ff.ladder/while/body/transpose(jvp(ff.step.model))/top_1/mul",
     "ff.step.model.bwd"),                   # backward, by the wrapper
    ("a/transpose(jvp(ff.step.model))/mul;transpose(jvp(x))/broadcast",
     "ff.step.model.bwd"),                   # merged: the first decides
    ("jit(f)/transpose(jvp(top_0))/ff.step.dense_update/sub",
     "ff.step.dense_update"),                # a closed wrapper is behind us
    ("jit(train_epoch)/jit(_where)/select_n", UNATTRIBUTED),
    ("jit(f)/diff.cache/stuff.step/x", UNATTRIBUTED),  # no lookalikes
    ("", UNATTRIBUTED),
])
def test_phase_of(stack, phase):
    assert phase_of(stack) == phase


HLO = '''HloModule jit_f, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/ff.step.gather/add"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/ff.ladder/while/body/ff.step.gather/add" source_file="m.py" source_line=3}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.1)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/ff.ladder/while"}
  ROOT %copy.2 = f32[8]{0} copy(%gte.5), metadata={op_name="jit(f)/jit(_where)/select_n"}
}
'''


def test_hlo_phases_reads_every_computation_but_the_fused_ones():
    # an instruction without a name takes its computation's caller's
    # phase (the while's, in its body); in the entry it has none
    assert hlo_phases(HLO) == {
        "t": "ff.ladder", "gte.1": "ff.ladder",
        "fusion.1": "ff.step.gather", "tuple.1": "ff.ladder",
        "x": UNATTRIBUTED, "while.1": "ff.ladder",
        "copy.2": UNATTRIBUTED}


def meta(tid, tname):
    return [{"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
             "args": {"name": tname}}]


def op(name, ts, dur, tf_op=None):
    return {"ph": "X", "pid": 1, "tid": 3, "name": name, "ts": ts,
            "dur": dur, "args": {"tf_op": tf_op} if tf_op else {}}


def test_trace_route_gives_a_while_the_stack_its_children_share(tmp_path):
    body = "jit(f)/ff.ladder/while/body/closed_call/"
    events = meta(2, "XLA Modules") + meta(3, "XLA Ops") + [
        {"ph": "X", "pid": 1, "tid": 2, "name": "jit_f(1)", "ts": 0,
         "dur": 120},
        op("while.1", 0, 100),                     # the profiler keeps none
        op("fusion.1", 10, 30, body + "ff.step.gather/gather:"),
        op("fusion.2", 50, 40, body + "transpose(jvp(ff.step.model))/mul:"),
        op("copy.9", 100, 15)]                     # no name, no children
    with gzip.open(tmp_path / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    _path, by_phase, busy_ms = parse_device_trace_phases(str(tmp_path))
    assert busy_ms == pytest.approx(0.120)
    assert by_phase == {"ff.ladder": 30.0, "ff.step.gather": 30.0,
                        "ff.step.model.bwd": 40.0, UNATTRIBUTED: 15.0}


FETCH_HLO = '''HloModule jit_f, is_scheduled=true

%fetch_body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %fusion.144 = f32[8]{0} fusion(%t), kind=kCustom, calls=%fc, metadata={op_name="jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.foreign/while/body/jit(_take)/gather"}
  ROOT %dynamic_update_slice.129 = f32[8]{0} dynamic-update-slice(%t, %fusion.144), metadata={op_name="jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.foreign/while/body/dynamic_update_slice"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  %dynamic_slice.124 = f32[8]{0} dynamic-slice(%t.1), metadata={op_name="jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.own/dynamic_slice"}
  %while.228 = (s32[], f32[8]{0}) while(%dynamic_slice.124), condition=%fetch_cond, body=%fetch_body, metadata={op_name="jit(f)/ff.ladder/while/body/ff.ladder.fetch/ff.ladder.fetch.foreign/while"}
  %copy.7 = f32[8]{0} copy(%while.228)
  ROOT %dynamic_update_slice.95 = f32[8]{0} dynamic-update-slice(%t.1, %copy.7), metadata={op_name="jit(f)/ff.ladder/while/body/ff.ladder.writeback/dynamic_update_slice"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/ff.ladder/while"}
}
'''


def test_region_fetch_sub_scopes_are_ladder_time_on_both_routes(tmp_path):
    """``ff.ladder.fetch.own`` / ``.foreign`` are phases of their own
    and fall into the benchmark's ladder group by their prefix, read
    off the program's map (the chunk loop's ``while`` and what runs in
    its body included) and off a trace's ``tf_op`` alike."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import phases as bench_phases
    from benchmarks.models.dlrm import PHASE_GROUPS

    for scope in REGION_FETCH:
        assert bench_phases.group_of(scope, PHASE_GROUPS) == "ladder"
    # the map route
    by_map = hlo_phases(FETCH_HLO)
    assert by_map["dynamic_slice.124"] == "ff.ladder.fetch.own"
    assert {by_map[n] for n in ("while.228", "fusion.144", "t",
                                "dynamic_update_slice.129")} \
        == {"ff.ladder.fetch.foreign"}
    assert by_map["dynamic_update_slice.95"] == "ff.ladder.writeback"
    assert by_map["copy.7"] == "ff.ladder"   # unnamed: its caller's
    self_us = {"dynamic_slice.124": 40.0, "fusion.144": 30.0,
               "dynamic_update_slice.129": 2.0, "while.228": 1.0,
               "dynamic_update_slice.95": 35.0}
    parts = bench_phases.split(self_us, by_map, 110.0, PHASE_GROUPS)
    assert parts["ladder"] == 108.0 and parts[UNATTRIBUTED] == 2.0
    # the trace route
    stack = "jit(f)/ff.ladder/while/body/ff.ladder.fetch/"
    events = meta(2, "XLA Modules") + meta(3, "XLA Ops") + [
        {"ph": "X", "pid": 1, "tid": 2, "name": "jit_f(1)", "ts": 0,
         "dur": 120},
        op("dynamic_slice.124", 0, 40,
           stack + "ff.ladder.fetch.own/dynamic_slice:"),
        op("while.228", 40, 33),                   # the profiler keeps none
        op("fusion.144", 41, 30, stack + "ff.ladder.fetch.foreign/while/"
           "body/jit(_take)/gather:"),
        op("dynamic_update_slice.129", 71, 2, stack
           + "ff.ladder.fetch.foreign/while/body/dynamic_update_slice:")]
    with gzip.open(tmp_path / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    _path, by_phase, _busy = parse_device_trace_phases(str(tmp_path))
    assert by_phase == {"ff.ladder.fetch.own": 40.0,
                        "ff.ladder.fetch.foreign": 33.0}
    assert {bench_phases.group_of(p, PHASE_GROUPS)
            for p in by_phase} == {"ladder"}


# ------------------------------------------------------- the tiny model
def _tiny(mesh=False, **ffconfig):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

    cfg = DLRMConfig()
    cfg.sparse_feature_size = 8
    cfg.embedding_size = [50_000] * 4
    cfg.mlp_bot = [8, 16, 8]
    cfg.mlp_top = [40, 16, 1]
    fc = ff.FFConfig(batch_size=32, epoch_row_cache="on",
                     packed_tables="on", epoch_cache_regions="on",
                     **ffconfig)
    model = build_dlrm(cfg, fc)
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"), mesh=mesh)
    rng = np.random.default_rng(0)
    nb = 32
    inputs = {"dense": rng.standard_normal((nb, 32, 8)).astype(np.float32),
              "sparse": rng.integers(0, 50_000, size=(nb, 32, 4, 1),
                                     dtype=np.int64)}
    labels = rng.integers(0, 2, size=(nb, 32, 1)).astype(np.float32)
    return model, *model.place_dataset(inputs, labels)


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def test_no_event_and_no_registry_entry_with_telemetry_off(tiny):
    model, inputs, labels = tiny
    # the names are metadata: compile() keys the persistent cache on it
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    state = model.init(seed=0)  # each test donates its own
    known = set(profiling._programs)
    with event_log() as log:
        pass
    state, _ = model.train_epochs(state, inputs, labels, 2)
    state, _ = model.train_epoch(state, inputs, labels)
    state, _ = model.train_step(state, {k: v[0] for k, v in inputs.items()},
                                labels[0])
    assert set(profiling._programs) == known
    assert log.events() == []
    with pytest.raises(KeyError):
        program_phases("train_epochs#never")


def test_program_phases_of_the_tiny_train_epochs(tiny):
    model, inputs, labels = tiny
    state = model.init(seed=0)  # each test donates its own
    with event_log() as log:
        for _ in range(3):
            state, _ = model.train_epochs(state, inputs, labels, 2)
        jax.block_until_ready(state.step)
        named = log.events("program")
        spans = [e for e in log.events("span")]
    # one event per program and log, however many dispatches
    assert [e["fn"] for e in named] == ["train_epochs"]
    assert [s["name"] for s in spans] == ["train.dispatch"] * 3
    assert all(s["start_mono_s"] > 0 and s["attrs"]["epochs"] == 2
               for s in spans)
    phases = program_phases(named[0]["name"])
    # regions are on: the leaf fetch is its two sub-scopes and nothing
    # is left under ``ff.ladder.fetch`` itself
    assert (SCOPES - {"ff.ladder.fetch"}) | REGION_FETCH \
        <= set(phases.values())
    assert program_phases(named[0]["name"]) is phases  # memoised
    # a second log names the program again, under the same name
    with event_log() as log2:
        state, _ = model.train_epochs(state, inputs, labels, 2)
        assert [e["name"] for e in log2.events("program")] \
            == [named[0]["name"]]


def test_train_step_names_its_program_and_splits_its_host_time(tiny):
    model, inputs, labels = tiny
    state = model.init(seed=0)  # each test donates its own
    batch = {k: np.asarray(v[0]) for k, v in inputs.items()}
    with event_log() as log:
        with span("outer") as outer:
            for _ in range(2):
                state, _ = model.train_step(state, batch,
                                            np.asarray(labels[0]))
        # a bare call names its program too, but roots no trace
        state, _ = model.train_step(state, batch, np.asarray(labels[0]))
        spans = log.events("span")
        (named,) = log.events("program")
    assert [s["name"] for s in spans] == ["train.shard", "train.launch"] * 2 \
        + ["outer"]
    assert all(s["parent_id"] == outer.span_id for s in spans[:4])
    got = set(program_phases(named["name"]).values())
    assert {"ff.step.gather", "ff.step.model", "ff.step.model.bwd",
            "ff.step.row_update", "ff.step.dense_update"} <= got
    assert not any(p.startswith(("ff.cache", "ff.ladder")) for p in got)


def test_fit_names_the_program_it_compiled_ahead(tiny):
    from dlrm_flexflow_tpu.data.loader import ArrayDataLoader

    model, inputs, labels = tiny
    state = model.init(seed=0)  # each test donates its own
    flat = {k: np.asarray(v).reshape((-1,) + v.shape[2:])
            for k, v in inputs.items()}
    loader = ArrayDataLoader(flat, np.asarray(labels).reshape(-1, 1),
                             batch_size=32)
    with event_log() as log:
        state, _ = model.fit(state, loader, epochs=2, verbose=False,
                             warmup=False, show_throughput=False)
        named = log.events("program")
    assert [e["fn"] for e in named] == ["train_epochs"]
    assert "ff.cache.prologue" in set(
        program_phases(named[0]["name"]).values())


# ------------------------------------------------------------- spans
def _host_slices(logdir):
    (path,) = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                        recursive=True)
    with gzip.open(path, "rt") as f:
        return [e for e in json.load(f)["traceEvents"]
                if e.get("ph") == "X"]


def test_a_span_shows_up_as_an_annotation_in_a_profiler_trace(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with event_log() as log:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with span("test.scoped"):
            sp = start_span("test.annotated", annotate=True)
            sp.end()
            sp = start_span("test.plain")  # may close on another thread
            sp.end()
        jax.profiler.stop_trace()
        spans = {e["name"]: e for e in log.events("span")}
    slices = {e["name"]: e for e in _host_slices(str(tmp_path))}
    assert "test.scoped" in slices and "test.annotated" in slices
    assert "test.plain" not in slices and "test.plain" in spans
    inner, outer = slices["test.annotated"], slices["test.scoped"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # the event's monotonic start is on perf_counter, like its duration
    mono = spans["test.annotated"]["start_mono_s"] \
        - spans["test.scoped"]["start_mono_s"]
    assert mono * 1e6 == pytest.approx(inner["ts"] - outer["ts"], abs=500)


def test_span_ids_are_unique_and_a_drifted_event_still_raises():
    with event_log() as log:
        for _ in range(200):
            start_span("x").end()
        ids = [e["span_id"] for e in log.events("span")]
        assert len(set(ids)) == 200 and all(len(i) == 16 for i in ids)
        log.emit("phase_time", step=1, step_wall_ms=1.0, phase="step")
        # the same producer shape passes again; a drifted one is swept
        log.emit("phase_time", step=2, step_wall_ms=1.0, phase="step")
        with pytest.raises(ValueError, match="unknown field"):
            log.emit("phase_time", step=3, step_wall_ms=1.0, walls=2.0)
        with pytest.raises(ValueError, match="type"):
            log.emit("phase_time", step="3", step_wall_ms=1.0, phase="step")
        with pytest.raises(ValueError, match="unknown event type"):
            log.emit("phase_times", step=3, step_wall_ms=1.0, phase="step")


# ---------------------------------------------- the fixture from the chip
def _fixture(tmp_path):
    # the parsers take the newest *.trace.json.gz of a directory
    shutil.copy(os.path.join(DATA, "v5e_train_epoch_phases_trace.json.gz"),
                tmp_path / "recorded.trace.json.gz")
    with open(os.path.join(DATA, "v5e_train_epoch_phases_map.json")) as f:
        return str(tmp_path), json.load(f)


def test_trace_route_and_map_route_agree_on_the_recorded_trace(tmp_path):
    logdir, phases = _fixture(tmp_path)
    _p, _names, self_us, busy_ms = parse_device_trace(logdir)
    _p, by_tf_op, busy_ms2 = parse_device_trace_phases(logdir)
    assert busy_ms2 == busy_ms
    by_map = {}
    for name, us in self_us.items():
        assert name in phases, name   # every slice is an instruction
        by_map[phases[name]] = by_map.get(phases[name], 0.0) + us
    assert set(by_map) == set(by_tf_op)
    for phase, us in by_tf_op.items():
        assert by_map[phase] == pytest.approx(us, abs=1e-6), phase
    # every scope of a train_epoch ran, and the phases are the busy time
    # but for the moments inside the program between two instructions
    assert SCOPES <= set(by_map)
    total = sum(by_map.values())
    assert 0.99 * busy_ms * 1e3 < total <= busy_ms * 1e3
    assert by_map[UNATTRIBUTED] < 0.10 * total


def test_the_program_s_spans_are_in_the_recorded_trace(tmp_path):
    logdir, _phases = _fixture(tmp_path)
    names = {e["name"] for e in _host_slices(logdir)}
    assert {"train.dispatch", "fixture.fence"} <= names
