"""Unit tests for the unified telemetry subsystem (docs/telemetry.md):
EventLog round-trip through the JSONL sink and the report CLI, schema
drift rejection, named_scope trace attribution in compiled HLO, compile
event counting across a forced retrace, search-trajectory recording
from a short MCMC run, and the producer integrations in FFModel /
OpTimer / Simulator.  All CPU, all fast.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
from dlrm_flexflow_tpu.data.loader import SyntheticDLRMLoader
from dlrm_flexflow_tpu.telemetry import (EventLog, active_log, emit,
                                         event_log, set_event_log,
                                         validate_event)
from dlrm_flexflow_tpu.telemetry.report import format_report, load_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mlp_model(batch=16, widths=(16, 32, 8)):
    m = ff.FFModel(ff.FFConfig(batch_size=batch))
    t = m.create_tensor((batch, widths[0]), name="x")
    for i, w in enumerate(widths[1:]):
        t = m.dense(t, w, activation="relu", name=f"fc{i}")
    return m


def small_dlrm(batch=16):
    cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[50] * 2,
                     embedding_bag_size=2, mlp_bot=[13, 16, 8],
                     mlp_top=[8 * 2 + 8, 16, 1])
    m = build_dlrm(cfg, ff.FFConfig(batch_size=batch))
    m.compile(optimizer=ff.SGDOptimizer(lr=0.01),
              loss_type="mean_squared_error", metrics=("accuracy",))
    return cfg, m


def stacked_batches(cfg, nb, batch, seed=0):
    rng = np.random.default_rng(seed)
    inputs = {
        "dense": rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32),
        "sparse": rng.integers(
            0, min(cfg.embedding_size),
            size=(nb, batch, len(cfg.embedding_size),
                  cfg.embedding_bag_size), dtype=np.int64),
    }
    labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
    return inputs, labels


# ------------------------------------------------------------ EventLog core

class TestEventLog:
    def test_roundtrip_emit_jsonl_report(self, tmp_path):
        """emit -> JSONL -> load_events -> report covers every section."""
        path = str(tmp_path / "run.jsonl")
        with event_log(path, mode="w") as log:
            log.emit("step", wall_s=0.5, samples=1024,
                     samples_per_s=2048.0, fenced=True, phase="fit",
                     metrics={"train_all": 1024.0})
            log.emit("compile", kind="aot", duration_s=1.5,
                     fn="train_epoch", donated_args=1)
            log.emit("memory", device="cpu:0", bytes_in_use=1 << 20,
                     source="live_arrays", phase="fit")
            log.emit("search", phase="iteration", it=0, accepted=True,
                     current_s=0.01, best_s=0.01, op="fc0", dims=[2, 1])
            log.emit("search", phase="summary", iterations=1, best_s=0.01,
                     acceptance_rate=1.0, backend="python")
            log.emit("search", phase="calibrate", simulated_s=0.01,
                     measured_s=0.02, scale=2.0)
            log.emit("op_time", op="fc0", forward_s=1e-4, backward_s=2e-4,
                     sim_forward_s=1.5e-4, sim_backward_s=3e-4)
        events = load_events(path, strict=True)
        assert len(events) == 7
        rep = format_report(events)
        for section in ("throughput", "per-op time table",
                        "sim-vs-measured calibration", "compile events",
                        "memory watermarks", "strategy search"):
            assert section in rep, rep
        assert "2,048 samples/s" in rep
        assert "fc0" in rep

    def test_ring_and_type_filter(self):
        log = EventLog(ring=4)
        for i in range(6):
            log.emit("memory", device=f"d{i}", bytes_in_use=i)
        evs = log.events("memory")
        assert len(evs) == 4  # bounded ring keeps the newest
        assert evs[-1]["device"] == "d5"
        assert log.events("step") == []

    def test_emit_rejects_schema_drift(self):
        log = EventLog()
        with pytest.raises(ValueError, match="unknown event type"):
            log.emit("nope", x=1)
        with pytest.raises(ValueError, match="missing required"):
            log.emit("step", wall_s=1.0)  # no samples
        with pytest.raises(ValueError, match="unknown field"):
            log.emit("step", wall_s=1.0, samples=2, zzz=3)
        with pytest.raises(ValueError, match="phase"):
            log.emit("search", phase="iteration", it=1)  # phase fields

    def test_none_fields_dropped_and_numpy_coerced(self):
        log = EventLog()
        ev = log.emit("memory", device="d", bytes_in_use=np.int64(7),
                      peak_bytes=None, source="memory_stats")
        assert "peak_bytes" not in ev
        assert ev["bytes_in_use"] == 7
        assert type(ev["bytes_in_use"]) is int
        json.dumps(ev)  # JSON-clean

    def test_device_arrays_in_nested_fields_coerced(self, tmp_path):
        """A producer passing jax device values (any rank) inside a
        dict/list field must round-trip, not abort the run."""
        path = str(tmp_path / "arr.jsonl")
        # arrays built OUTSIDE the log scope (jnp.ones is a jitted fill
        # whose compile event would otherwise land in the sink too)
        vec, sc = jnp.ones(4), jnp.float32(0.5)
        with event_log(path, mode="w") as log:
            ev = log.emit("step", wall_s=1.0, samples=4,
                          metrics={"loss": vec, "acc": sc})
        assert ev["metrics"]["loss"] == [1.0, 1.0, 1.0, 1.0]
        assert ev["metrics"]["acc"] == 0.5
        evs = load_events(path, strict=True)
        assert [e for e in evs if e["type"] == "step"] == [ev]

    def test_nonfinite_floats_never_break_the_jsonl(self, tmp_path):
        """NaN/Inf would serialize as spec-invalid JSON tokens; they are
        coerced to None (dropped at top level, null nested) so strict
        consumers can always parse the sink."""
        path = str(tmp_path / "nan.jsonl")
        with event_log(path, mode="w") as log:
            ev = log.emit("step", wall_s=1.0, samples=4,
                          loss=float("nan"),
                          metrics={"mse": float("inf"), "acc": 0.5,
                                   "arr": np.array([np.inf, 1.0])})
        assert "loss" not in ev
        assert ev["metrics"] == {"mse": None, "acc": 0.5,
                                 "arr": [None, 1.0]}
        with open(path) as f:
            line = f.read()
        assert "NaN" not in line and "Infinity" not in line
        assert len(load_events(path, strict=True)) == 1

    def test_active_log_scoping(self):
        assert active_log() is None
        assert emit("step", wall_s=1.0, samples=1) is None  # off: no-op
        outer = EventLog()
        prev = set_event_log(outer)
        try:
            assert prev is None
            with event_log() as inner:
                assert active_log() is inner
            assert active_log() is outer  # restored
        finally:
            set_event_log(None)

    def test_report_skips_malformed_lines(self, tmp_path):
        path = str(tmp_path / "bad.jsonl")
        good = {"type": "step", "ts": 1.0, "wall_s": 1.0, "samples": 8}
        with open(path, "w") as f:
            f.write("not json\n")
            f.write(json.dumps({"type": "step", "ts": 1.0}) + "\n")
            f.write(json.dumps(good) + "\n")
        assert len(load_events(path)) == 1
        with pytest.raises(ValueError):
            load_events(path, strict=True)

    def test_sink_failure_is_best_effort(self, tmp_path, capsys):
        """A sink I/O failure must never abort the producer's run: the
        broken sink is dropped (one stderr warning) and events keep
        landing in the ring."""
        path = str(tmp_path / "sink.jsonl")
        log = EventLog(path, mode="w")
        log.emit("memory", device="d", bytes_in_use=1)
        log._fh.close()  # break the sink out from under emit
        log.emit("memory", device="d", bytes_in_use=2)  # must not raise
        assert log._fh is None  # dropped, not retried
        log.emit("memory", device="d", bytes_in_use=3)
        assert len(log.events("memory")) == 3  # ring unaffected
        assert "telemetry sink failed" in capsys.readouterr().err

    def test_suppressed_scopes_and_restores(self):
        from dlrm_flexflow_tpu.telemetry import suppressed

        with event_log() as log:
            with suppressed():
                assert active_log() is None
                assert emit("step", wall_s=1.0, samples=1) is None
            assert active_log() is log

    def test_validate_event_direct(self):
        assert validate_event({"type": "step", "ts": 1.0, "wall_s": 0.1,
                               "samples": 4}) == []
        # bool must not satisfy int/float fields
        errs = validate_event({"type": "step", "ts": 1.0, "wall_s": True,
                               "samples": 4})
        assert errs


# -------------------------------------------------------- trace attribution

class TestNamedScope:
    def test_forward_wrapped_once(self):
        from dlrm_flexflow_tpu.ops.base import Op
        for cls in [Op] + Op.__subclasses__():
            fwd = cls.__dict__.get("forward")
            if fwd is not None and cls is not Op:
                assert getattr(fwd, "__named_scope_wrapped__", False), cls

    def test_named_scope_in_compiled_hlo(self):
        """Framework op names must appear in XLA op metadata — that is
        the whole attribution story (profiler traces read it)."""
        m = mlp_model()
        m.compile(loss_type="mean_squared_error", metrics=())
        state = m.init(seed=0)
        x = np.zeros((16, 16), np.float32)
        y = np.zeros((16, 8), np.float32)
        txt = m._train_step.lower(state, {"x": x}, y).compile().as_text()
        assert "fc0" in txt
        assert "fc1" in txt

    def test_named_scope_in_jaxpr_name_stack(self):
        """The scope is also visible pre-compile via eqn source names in
        the lowered module (named_scope feeds the mlir location path)."""
        m = mlp_model()
        m.compile(loss_type="mean_squared_error", metrics=())
        state = m.init(seed=0)

        def fwd(params, x):
            return m._forward_fn(params, {"x": x}, state.bn_state)

        hlo = jax.jit(fwd).lower(
            state.params, np.zeros((16, 16), np.float32)).compile().as_text()
        assert "fc0" in hlo


# ----------------------------------------------------------- compile events

class TestCompileEvents:
    def test_retrace_emits_compile_events(self):
        @jax.jit
        def f(v):
            return v * 2 + 1

        # build inputs OUTSIDE the log scope: jnp.ones is itself a
        # jitted fill whose compile must not pollute the counts
        a, b = jnp.ones((3,)), jnp.ones((5,))
        with event_log() as log:
            f(a)                        # miss: shape (3,)
            before = len(log.events("compile"))
            f(a)                        # hit: no new event
            assert len(log.events("compile")) == before
            f(b)                        # forced retrace: new shape
            evs = log.events("compile")
            assert len(evs) == before + 1
        assert before >= 1
        for e in evs:
            assert e["kind"] == "backend_compile"
            assert e["duration_s"] > 0
            assert e["backend"] == "cpu"

    def test_compile_stats_counters(self):
        from dlrm_flexflow_tpu.telemetry import compile_stats

        @jax.jit
        def g(v):
            return v - 1

        with event_log():
            g(jnp.ones((7,)))
        stats = compile_stats()
        assert stats.get("backend_compile", 0) >= 1
        assert stats.get("backend_compile_s", 0.0) > 0

    def test_backend_compile_s_is_every_compile_wall_and_names_it(self):
        """What ``compile_s`` reads keeps its meaning: the sum of the
        backend compiles' walls, one per ``compile`` event, which now
        names its program and stage."""
        from dlrm_flexflow_tpu.telemetry import compile_stats

        def h(v):
            return v * 3 - 1

        x = jnp.ones((11,))
        before = compile_stats()
        with event_log() as log:
            jax.jit(h)(x)
        after = compile_stats()
        comps = log.events("compile")
        assert [(e["fn"], e["stage"]) for e in comps] == [("h", "compile")]
        assert after["backend_compile"] - before["backend_compile"] == 1
        assert after["backend_compile_s"] - before["backend_compile_s"] \
            == pytest.approx(comps[0]["duration_s"])
        assert "fn=h stage=compile" in format_report(log.events())


# ------------------------------------------------------ the set-up timeline

class TestSetupTimeline:
    """``jax_hooks``' set-up timeline and ``setup_breakdown``."""

    @pytest.fixture(autouse=True)
    def _hooks(self):
        from dlrm_flexflow_tpu.telemetry import install_compile_hooks
        install_compile_hooks()

    def test_a_nested_trace_counts_once(self):
        import time

        from dlrm_flexflow_tpu.telemetry import (compile_stats,
                                                 setup_breakdown, setup_mark)

        @jax.jit
        def inner(v):
            time.sleep(0.2)
            return v + 1

        @jax.jit
        def outer(v):
            return inner(v) * 2

        x = jnp.ones((13,))
        before = compile_stats()
        setup_mark("setup.model")
        outer(x)
        b = setup_breakdown()
        traced = compile_stats()["jaxpr_trace_s"] - before["jaxpr_trace_s"]
        assert 0.2 <= b["trace_s"] < 0.3
        assert 0.2 <= traced < 0.3          # was 0.4: inner twice
        # the per-program table keeps both names, each with its own wall
        assert b["programs"]["inner"]["trace_s"] >= 0.2
        assert b["programs"]["outer"]["trace_s"] >= 0.2
        assert b["programs"]["outer"]["compiles"] == 1
        assert b["trace_s"] + b["lower_s"] + b["compile_s"] <= b["own_s"]

    def test_a_persistent_cache_load_is_loaded_not_compiled(self, tmp_path):
        from jax._src import compilation_cache

        from dlrm_flexflow_tpu.telemetry import setup_breakdown, setup_mark

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        compilation_cache.reset_cache()
        try:
            def cached(v):
                return jnp.sin(v) @ v

            x = jnp.ones((17,))
            seen = []
            for _ in range(2):  # one call site: the cache key holds it
                jax.clear_caches()
                setup_mark("setup.model")
                with event_log() as log:
                    jax.jit(cached)(x)
                seen.append(setup_breakdown())
            first, again = seen
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
        assert first["programs"]["cached"]["compiles"] == 1
        assert first["cache_load_s"] == 0
        row = again["programs"]["cached"]
        assert (row["compiles"], row["loaded"]) == (0, 1)
        assert again["compiled_programs"] == 0
        assert again["loaded_programs"] == 1
        assert 0 < again["cache_load_s"] <= again["compile_s"]
        assert row["cache_load_s"] == pytest.approx(again["cache_load_s"])
        (event,) = [e for e in log.events("compile") if e["fn"] == "cached"]
        assert event["stage"] == "cache_load"

    def test_the_breakdown_starts_at_the_newest_model(self):
        import time

        from dlrm_flexflow_tpu.telemetry import setup_breakdown, timeline

        first = mlp_model()
        first.compile()
        first.init(seed=0)
        between = time.perf_counter()
        second = mlp_model(widths=(16, 24, 8))
        second.compile()
        second.init(seed=0)
        now = setup_breakdown()
        marks = [r["start"] for r in timeline()
                 if r["stage"] == "setup.model"]
        assert now["start"] == marks[-1] > between
        assert setup_breakdown(between)["start"] == marks[-2] < between
        assert now["graph_s"] > 0 and now["compiled_programs"] >= 1
        assert now["own_s"] < setup_breakdown(between)["own_s"] \
            + now["own_s"]

    def test_setup_graph_is_a_span_only_while_a_log_is_on(self):
        from dlrm_flexflow_tpu.telemetry import timeline

        m = mlp_model()
        m.compile()
        assert timeline()[-1]["stage"] == "setup.graph"
        with event_log() as log:
            m = mlp_model()
            m.compile()
        (sp,) = log.events("span")
        assert sp["name"] == "setup.graph" and sp["status"] == "ok"

    def test_the_bound_folds_what_it_evicts(self, monkeypatch):
        from dlrm_flexflow_tpu.telemetry import (jax_hooks, setup_breakdown,
                                                 setup_mark)

        import time

        monkeypatch.setattr(jax_hooks, "_records", jax_hooks.deque())
        monkeypatch.setattr(jax_hooks, "MAX_RECORDS", 4)
        setup_mark("setup.model")
        t = time.perf_counter()
        for i in range(6):   # six compiles of 10 ms, written to no cache
            jax_hooks._append(jax_hooks._Record(
                "compile", f"p{i}", t + 0.01 * i, t + 0.01 * (i + 1)))
        b = setup_breakdown(t + 1.0)
        assert len(jax_hooks.timeline()) == 4
        assert b["overflow"] == 3    # the mark and two compiles
        assert b["compiled_programs"] == 6
        assert b["compile_s"] == pytest.approx(0.06)
        time.sleep(0.07)
        setup_mark("setup.model")    # a new set-up: nothing of it lost
        assert setup_breakdown()["overflow"] == 0


# --------------------------------------------------------- search recording

class TestSearchEvents:
    def test_mcmc_emits_trajectory_and_summary(self):
        from dlrm_flexflow_tpu.sim.search import mcmc_search

        model = mlp_model(batch=64, widths=(64, 128, 8))
        with event_log() as log:
            best = mcmc_search(model, 8, budget=12, seed=0,
                               backend="python", measure=False)
        its = [e for e in log.events("search")
               if e["phase"] == "iteration"]
        sums = [e for e in log.events("search") if e["phase"] == "summary"]
        assert len(its) == 12
        assert len(sums) == 1
        s = sums[0]
        assert s["iterations"] == 12
        assert s["backend"] == "python"
        assert 0.0 <= s["acceptance_rate"] <= 1.0
        assert s["accepted_count"] == sum(1 for e in its if e["accepted"])
        # the trajectory's best-cost is monotone non-increasing and ends
        # at the summary's best
        bests = [e["best_s"] for e in its]
        assert all(b2 <= b1 + 1e-15 for b1, b2 in zip(bests, bests[1:]))
        assert abs(bests[-1] - s["best_s"]) < 1e-15
        assert abs(best.best_simulated_time - s["best_s"]) < 1e-15

    def test_calibrate_emits_fit(self):
        from dlrm_flexflow_tpu.sim.search import data_parallel_strategy
        from dlrm_flexflow_tpu.sim.simulator import Simulator

        model = mlp_model(batch=64, widths=(64, 128, 8))
        sim = Simulator(model, 4)
        dp = data_parallel_strategy(model, 4)
        with event_log() as log:
            scale = sim.calibrate(dp, 0.25)
        cal = [e for e in log.events("search") if e["phase"] == "calibrate"]
        assert len(cal) == 1
        assert cal[0]["measured_s"] == 0.25
        assert cal[0]["scale"] == pytest.approx(scale)
        assert cal[0]["simulated_s"] * scale == pytest.approx(0.25)


# ------------------------------------------------------ producer integration

class TestModelIntegration:
    def test_fit_emits_step_memory_and_aot_compile(self, tmp_path):
        cfg, m = small_dlrm()
        state = m.init(seed=0)
        loader = SyntheticDLRMLoader(64, 13, cfg.embedding_size, 2, 16,
                                     seed=1)
        path = str(tmp_path / "fit.jsonl")
        with event_log(path, mode="w") as log:
            m.fit(state, loader, epochs=1, verbose=False)
            steps = [e for e in log.events("step") if e["phase"] == "fit"]
            assert len(steps) == 1
            assert steps[0]["fenced"] is True
            assert steps[0]["samples"] > 0
            assert steps[0]["samples_per_s"] > 0
            assert steps[0]["metrics"].get("train_all", 0) > 0
            assert np.isfinite(steps[0]["loss"])  # final epoch's loss
            assert log.events("memory")
        # the JSONL sink holds the same run and reports cleanly
        rep = format_report(load_events(path, strict=True))
        assert "throughput" in rep

    def test_train_epoch_emits_dispatch_step(self):
        cfg, m = small_dlrm()
        state = m.init(seed=0)
        inputs, labels = stacked_batches(cfg, nb=4, batch=16)
        with event_log() as log:
            m.train_epoch(state, inputs, labels)
            evs = [e for e in log.events("step")
                   if e["phase"] == "train_epoch"]
            assert len(evs) == 1
            assert evs[0]["fenced"] is False  # dispatch-only wall
            assert evs[0]["samples"] == 4 * 16
            assert evs[0]["steps"] == 4

    def test_telemetry_off_is_silent(self, capsys):
        """With no active log, training emits nothing and changes no
        behavior (the producers' one None-check contract)."""
        assert active_log() is None
        cfg, m = small_dlrm()
        state = m.init(seed=0)
        inputs, labels = stacked_batches(cfg, nb=2, batch=16)
        state2, mets = m.train_epoch(state, inputs, labels)
        assert np.isfinite(float(mets["loss"]))

    def test_optimer_emits_op_time_with_sim_prediction(self):
        from dlrm_flexflow_tpu.profiling import OpTimer

        m = mlp_model()
        m.compile(loss_type="mean_squared_error", metrics=())
        state = m.init(seed=0)
        with event_log() as log:
            times = OpTimer(m, iters=1).profile(state, None)
            evs = log.events("op_time")
        assert len(evs) == len(m.layers)
        for e in evs:
            assert e["forward_s"] >= 0
            assert e["sim_forward_s"] > 0  # analytic prediction rides along
            assert times[e["op"]]["sim_forward_s"] == e["sim_forward_s"]
        rep = format_report(evs)
        assert "sim-vs-measured calibration" in rep
        assert "sim/meas" in rep


# ------------------------------------------------------------------ tooling

class TestSchemaLint:
    def test_lint_passes(self):
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_telemetry_schema.py")],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr

    def test_report_cli_runs(self, tmp_path):
        path = str(tmp_path / "cli.jsonl")
        with event_log(path, mode="w") as log:
            log.emit("step", wall_s=1.0, samples=256, fenced=True,
                     phase="fit")
        r = subprocess.run(
            [sys.executable, "-m", "dlrm_flexflow_tpu.telemetry",
             "report", path],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "throughput" in r.stdout
