"""Observability tests (telemetry/trace.py, metrics.py, exporter.py,
regress.py — docs/telemetry.md): span API semantics, span propagation
on every serving edge path (shed / deadline / drain / cancel close
exactly once with the right status), concurrent /metrics scrapes under
traffic, the fixed-bucket latency histogram, Chrome-trace export, the
report's ``--format json`` round-trip, the regress gate, and the
tier-1 smoke matrix."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
from dlrm_flexflow_tpu.serving import (DeadlineExceeded, DynamicBatcher,
                                       InferenceEngine, LatencyStats,
                                       Rejected)
from dlrm_flexflow_tpu.telemetry import (NULL_SPAN, current_span, event_log,
                                         record_span, span, start_span)
from dlrm_flexflow_tpu.telemetry.exporter import MetricsServer, chrome_trace
from dlrm_flexflow_tpu.telemetry.metrics import (LATENCY_BUCKETS_US,
                                                 REGISTRY)
from dlrm_flexflow_tpu.telemetry.regress import compare, load_metrics
from dlrm_flexflow_tpu.telemetry.regress import main as regress_main
from dlrm_flexflow_tpu.telemetry.report import (format_report, load_events,
                                                main as report_main,
                                                report_data)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served():
    """(cfg, model, state, engine) — one compile for the whole module."""
    cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[64, 48],
                     embedding_bag_size=2, mlp_bot=[4, 8, 8],
                     mlp_top=[8 * 2 + 8, 8, 1])
    m = build_dlrm(cfg, ff.FFConfig(batch_size=8, serve_buckets="2,4,8"))
    m.compile(optimizer=ff.SGDOptimizer(0.01),
              loss_type="mean_squared_error", metrics=(), mesh=False)
    state = m.init(seed=0)
    engine = InferenceEngine(m, state)
    return cfg, m, state, engine


def make_request(cfg, rng, n=1):
    return {"dense": rng.standard_normal((n, cfg.mlp_bot[0])).astype(
                np.float32),
            "sparse": np.stack(
                [rng.integers(0, r, size=(n, cfg.embedding_bag_size),
                              dtype=np.int64)
                 for r in cfg.embedding_size], axis=1)}


def spans_named(log, name):
    return [e for e in log.events("span") if e["name"] == name]


# ------------------------------------------------------------------ span API

class TestSpanAPI:
    def test_off_by_default_null(self):
        sp = start_span("x")
        assert sp is NULL_SPAN and not sp
        assert sp.end() is None
        with span("y") as s:
            assert not s

    def test_nesting_and_parenting(self):
        with event_log() as log:
            with span("outer") as out_sp:
                assert current_span() is out_sp
                with span("inner"):
                    pass
            assert current_span() is None
            inner, outer = log.events("span")
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert "parent_id" not in outer

    def test_end_exactly_once(self):
        with event_log() as log:
            sp = start_span("once")
            assert sp.end(status="deadline") is not None
            assert sp.end() is None
            assert sp.end(status="ok") is None
            evs = log.events("span")
        assert len(evs) == 1
        assert evs[0]["status"] == "deadline"

    def test_error_status_on_raise(self):
        with event_log() as log:
            with pytest.raises(RuntimeError):
                with span("boom"):
                    raise RuntimeError("x")
            (ev,) = log.events("span")
        assert ev["status"] == "error"

    def test_record_span_synthesized_child(self):
        with event_log() as log:
            root = start_span("root")
            record_span("child", time.time(), 123.0, parent=root,
                        attrs={"rows": 2})
            root.end()
            child, rootev = log.events("span")
        assert child["parent_id"] == rootev["span_id"]
        assert child["dur_us"] == 123.0 and child["attrs"]["rows"] == 2
        # a null parent means the request never had a trace: no event
        assert record_span("c", time.time(), 1.0, parent=NULL_SPAN) is None

    def test_span_event_is_schema_valid(self):
        from dlrm_flexflow_tpu.telemetry import validate_event
        with event_log() as log:
            with span("s", attrs={"k": 1}):
                pass
            (ev,) = log.events("span")
        assert validate_event(ev) == []

    def test_cross_thread_close(self):
        with event_log() as log:
            sp = start_span("xthread")
            t = threading.Thread(target=lambda: sp.end(status="ok"))
            t.start()
            t.join()
            (ev,) = log.events("span")
        # thread/tid name the OPENING thread, not the closer
        assert ev["thread"] == threading.current_thread().name


# --------------------------------------------- serving edge-path propagation

class TestServingSpanEdges:
    """Each edge path closes its request spans EXACTLY once with the
    right status (the acceptance contract for shutdown races)."""

    def test_shed_queue_full(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        with event_log() as log:
            b = DynamicBatcher(engine, queue_depth=2, autostart=False)
            for _ in range(2):
                b.submit(make_request(cfg, rng))
            with pytest.raises(Rejected):
                b.submit(make_request(cfg, rng))
            shed = [e for e in spans_named(log, "serve.request")
                    if e["status"] == "shed"]
            assert len(shed) == 1
            assert shed[0]["attrs"]["reason"] == "queue_full"
            b.close()
            roots = spans_named(log, "serve.request")
        # 2 served ok + 1 shed; every span_id unique (closed once)
        assert sorted(e["status"] for e in roots) == ["ok", "ok", "shed"]
        ids = [e["span_id"] for e in log.events("span")]
        assert len(ids) == len(set(ids))

    def test_shed_after_shutdown(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        b = DynamicBatcher(engine)
        b.close()
        with event_log() as log:
            with pytest.raises(Rejected):
                b.submit(make_request(cfg, rng))
            (root,) = spans_named(log, "serve.request")
        assert root["status"] == "shed"
        assert root["attrs"]["reason"] == "shutdown"

    def test_deadline_at_pop(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        with event_log() as log:
            b = DynamicBatcher(engine, autostart=False)
            fut = b.submit(make_request(cfg, rng), timeout_us=1000.0)
            time.sleep(0.02)
            b.start()
            with pytest.raises(DeadlineExceeded):
                fut.result(10)
            b.close()
            roots = spans_named(log, "serve.request")
            waits = spans_named(log, "serve.queue_wait")
        assert [e["status"] for e in roots] == ["deadline"]
        assert [e["status"] for e in waits] == ["deadline"]
        ids = [e["span_id"] for e in log.events("span")]
        assert len(ids) == len(set(ids))

    def test_graceful_drain_closes_ok(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        with event_log() as log:
            b = DynamicBatcher(engine, queue_depth=32, autostart=False)
            futs = [b.submit(make_request(cfg, rng)) for _ in range(6)]
            b.close()  # drain: every queued request served
            for f in futs:
                f.result(0)
            roots = spans_named(log, "serve.request")
            forwards = spans_named(log, "serve.forward")
        assert len(roots) == 6
        assert all(e["status"] == "ok" for e in roots)
        assert len(forwards) == 6  # one per request, batch-shared wall
        ids = [e["span_id"] for e in log.events("span")]
        assert len(ids) == len(set(ids))

    def test_cancel_close_without_drain(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        with event_log() as log:
            b = DynamicBatcher(engine, queue_depth=8, autostart=False)
            for _ in range(4):
                b.submit(make_request(cfg, rng))
            b.close(drain=False)
            roots = spans_named(log, "serve.request")
            waits = spans_named(log, "serve.queue_wait")
        assert len(roots) == 4
        assert all(e["status"] == "cancelled" for e in roots)
        assert all(e["attrs"]["reason"] == "shutdown" for e in roots)
        assert all(e["status"] == "cancelled" for e in waits)
        ids = [e["span_id"] for e in log.events("span")]
        assert len(ids) == len(set(ids))

    def test_complete_chain_on_served_request(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(3)
        with event_log() as log:
            with DynamicBatcher(engine, max_wait_us=200) as b:
                b.predict(make_request(cfg, rng), result_timeout_s=30)
            (root,) = spans_named(log, "serve.request")
            names_in_trace = {e["name"] for e in log.events("span")
                              if e["trace_id"] == root["trace_id"]}
            dispatch = spans_named(log, "serve.dispatch")
            engine_fwd = spans_named(log, "serve.engine_forward")
        assert {"serve.request", "serve.queue_wait",
                "serve.forward"} <= names_in_trace
        # engine spans nest under the dispatcher's serve.dispatch span
        assert len(dispatch) == 1
        assert any(e.get("parent_id") == dispatch[0]["span_id"]
                   for e in engine_fwd)


# ------------------------------------------------------------ latency buckets

class TestLatencyHistogram:
    def test_cumulative_buckets(self):
        s = LatencyStats()
        s.record_many([50.0, 150.0, 800.0, 2_000_000.0])
        cum, total, n = s.histogram()
        assert n == 4 and total == pytest.approx(2_000_000.0 + 1000.0)
        assert len(cum) == len(LATENCY_BUCKETS_US) + 1
        assert cum[0] == 1          # <= 100us
        assert cum[1] == 2          # <= 250us
        assert cum[-2] == 3         # <= 1s
        assert cum[-1] == 4         # +Inf catches the 2s outlier
        # edge value lands in its own bucket (le is inclusive)
        s2 = LatencyStats()
        s2.record(100.0)
        cum2, _, _ = s2.histogram()
        assert cum2[0] == 1

    def test_dispatch_bucket_counts(self):
        s = LatencyStats()
        s.record_dispatch(bucket=8)
        s.record_dispatch(bucket=8)
        s.record_dispatch(bucket=64)
        s.record_dispatch()  # bucketless (batcher-level) still counts
        assert s.dispatches == 4
        assert s.dispatch_buckets == {8: 2, 64: 1}

    def test_summary_unchanged(self):
        s = LatencyStats()
        s.record_many([1000.0] * 10)
        out = s.summary(wall_s=2.0)
        assert out["requests"] == 10 and out["qps"] == pytest.approx(5.0)
        assert out["p50_us"] == 1000.0


# ---------------------------------------------------------- metrics folding

class TestMetricsFolding:
    def test_shed_after_fold_lands_in_retained_base(self):
        from dlrm_flexflow_tpu.telemetry import metrics as tm
        s = LatencyStats()
        s._metrics_folded = True  # as if its batcher already retired
        before = tm._retired["rejected"]
        tm.record_shed_late(s)
        assert tm._retired["rejected"] == before + 1
        assert s.rejected == 0  # not double-counted on the folded object
        s2 = LatencyStats()
        tm.record_shed_late(s2)  # pre-fold: rides the stats as usual
        assert s2.rejected == 1
        assert tm._retired["rejected"] == before + 1

    def test_gc_without_close_keeps_counters_monotone(self):
        import gc
        from dlrm_flexflow_tpu.telemetry import metrics as tm

        class FakeBatcher:
            def __init__(self):
                self.stats = LatencyStats()

                class Q:
                    def qsize(self):
                        return 0
                self._q = Q()

        b = FakeBatcher()
        tm.track_batcher(b)
        b.stats.record(123.0)
        before = tm.SERVE_REQUESTS.value
        stats = b.stats
        del b
        gc.collect()  # finalizer queues the fold lock-free
        assert tm.SERVE_REQUESTS.value == before  # scrape drains + folds
        assert getattr(stats, "_metrics_folded", False)
        assert stats not in tm._live_stats  # strong registry released


# ----------------------------------------------------------- /metrics server

class TestMetricsExporter:
    def test_render_well_formed(self):
        body = REGISTRY.render()
        assert "# TYPE dlrm_serve_latency_us histogram" in body
        assert "# TYPE dlrm_serve_requests_total counter" in body
        assert 'le="+Inf"' in body

    def test_healthz_and_404(self):
        with MetricsServer(port=0, host="127.0.0.1") as srv:
            hz = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5)
            assert json.load(hz)["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5)

    def test_concurrent_scrape_under_traffic(self, served):
        cfg, _, _, engine = served
        rng = np.random.default_rng(0)
        reqs = [make_request(cfg, rng, 1 + i % 2) for i in range(16)]
        with MetricsServer(port=0, host="127.0.0.1") as srv:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            before = urllib.request.urlopen(url, timeout=5).read().decode()
            bodies = []

            def scraper():
                for _ in range(8):
                    bodies.append(urllib.request.urlopen(
                        url, timeout=5).read().decode())

            with DynamicBatcher(engine, max_wait_us=300) as b:
                t = threading.Thread(target=scraper)
                clients = [threading.Thread(
                    target=lambda r=r: b.predict(r, result_timeout_s=30))
                    for r in reqs]
                t.start()
                for c in clients:
                    c.start()
                for c in clients:
                    c.join()
                t.join()
            after = urllib.request.urlopen(url, timeout=5).read().decode()
        for body in bodies + [before, after]:
            assert "dlrm_serve_queue_depth" in body
            for line in body.splitlines():
                if line and not line.startswith("#"):
                    name, _, val = line.rpartition(" ")
                    assert name and val  # every sample line well-formed
                    float(val)

        def counter(body, name):
            for line in body.splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[-1])
            return 0.0

        assert (counter(after, "dlrm_serve_requests_total")
                >= counter(before, "dlrm_serve_requests_total") + 16)


# ------------------------------------------------------------- chrome trace

class TestChromeTrace:
    def test_spans_and_events_render(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with event_log(path, mode="w") as log:
            with span("outer"):
                with span("inner"):
                    pass
            log.emit("step", wall_s=0.5, samples=64, fenced=True,
                     phase="fit")
            log.emit("compile", kind="aot", duration_s=0.1, fn="f")
            log.emit("op_time", op="dense", forward_s=0.001)
        doc = chrome_trace(load_events(path))
        evs = doc["traceEvents"]
        xs = {e["name"] for e in evs if e["ph"] == "X"}
        assert {"outer", "inner", "step:fit", "compile:f",
                "op:dense"} <= xs
        assert all(e["ts"] >= 0 for e in evs if e["ph"] == "X")
        metas = [e for e in evs if e["ph"] == "M"]
        assert any(e["args"]["name"] == "compiles" for e in metas)

    def test_export_trace_cli(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with event_log(path, mode="w"):
            with span("s"):
                pass
        out = str(tmp_path / "t.trace.json")
        rc = report_main(["export-trace", path, "-o", out])
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        assert any(e["name"] == "s" for e in doc["traceEvents"])


# --------------------------------------------------------- report --format json

class TestReportJson:
    def _events(self, tmp_path):
        path = str(tmp_path / "r.jsonl")
        with event_log(path, mode="w") as log:
            log.emit("step", wall_s=1.0, samples=256, samples_per_s=256.0,
                     fenced=True, phase="fit")
            log.emit("serve", phase="summary", requests=5, qps=10.0,
                     p50_us=100.0)
            with span("serve.request"):
                pass
        return path

    def test_sections_match_text(self, tmp_path):
        path = self._events(tmp_path)
        events = load_events(path)
        data = report_data(events)
        text = format_report(events)
        # section presence identical between the two renderings
        assert ("throughput" in data) == ("== throughput ==" in text)
        assert ("serving" in data) == ("== serving ==" in text)
        assert ("spans" in data) == ("== spans ==" in text)
        assert "per_op" not in data and "== per-op" not in text
        assert data["run"]["events"] == len(events)
        assert data["throughput"]["best_fenced_samples_per_s"] == 256.0
        assert data["serving"]["qps"] == 10.0
        assert data["spans"]["spans"] == 1

    def test_cli_round_trip(self, tmp_path, capsys):
        path = self._events(tmp_path)
        rc = report_main(["report", path, "--format", "json"])
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["run"]["events"] == 3
        assert data["serving"]["requests"] == 5
        # every section the text report prints appears as a JSON key
        text = format_report(load_events(path))
        for key, header in (("throughput", "== throughput =="),
                            ("serving", "== serving =="),
                            ("spans", "== spans ==")):
            assert (header in text) == (key in data)


# ------------------------------------------------------------------ regress

class TestRegress:
    def _write(self, tmp_path, name, value,
               metric="dlrm_synthetic_samples_per_sec"):
        p = str(tmp_path / name)
        with open(p, "w") as f:
            json.dump({"parsed": {"metric": metric, "value": value,
                                  "unit": "samples/s"}}, f)
        return p

    def test_self_comparison_passes(self, tmp_path):
        p = self._write(tmp_path, "a.json", 1000.0)
        assert regress_main(["--baseline", p, "--new", p,
                             "--tolerance", "5"]) == 0

    def test_doctored_baseline_fails_named(self, tmp_path, capsys):
        new = self._write(tmp_path, "new.json", 1000.0)
        base = self._write(tmp_path, "base.json", 1100.0)  # +10%
        rc = regress_main(["--baseline", base, "--new", new,
                           "--tolerance", "5"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION dlrm_synthetic_samples_per_sec" in out
        assert "9.09%" in out

    def test_improvement_passes(self, tmp_path):
        new = self._write(tmp_path, "new.json", 2000.0)
        base = self._write(tmp_path, "base.json", 1000.0)
        assert regress_main(["--baseline", base, "--new", new,
                             "--tolerance", "5"]) == 0

    def test_within_tolerance_passes(self, tmp_path):
        new = self._write(tmp_path, "new.json", 970.0)  # -3%
        base = self._write(tmp_path, "base.json", 1000.0)
        assert regress_main(["--baseline", base, "--new", new,
                             "--tolerance", "5"]) == 0

    def test_no_shared_metrics_is_config_error(self, tmp_path):
        new = self._write(tmp_path, "new.json", 1.0, metric="a")
        base = self._write(tmp_path, "base.json", 1.0, metric="b")
        assert regress_main(["--baseline", base, "--new", new]) == 2

    def test_history_baseline_parses(self, tmp_path):
        hist = [
            {"value": 100.0, "batch": 2, "num_batches": 2, "epochs": 1,
             "rows": 10},  # unfenced: excluded
            {"app": "dlrm", "value": 200.0, "fenced": True, "batch": 256,
             "num_batches": 4, "epochs": 2, "device_busy_ms": 10.0,
             "mfu_pct": 12.5},
            {"app": "dlrm_serving", "value": 5000.0, "fenced": True},
        ]
        p = str(tmp_path / "hist.json")
        with open(p, "w") as f:
            json.dump(hist, f)
        m = load_metrics(p)
        assert m["dlrm_synthetic_samples_per_sec"] == 200.0
        assert m["dlrm_serving_qps"] == 5000.0
        assert m["dlrm_synthetic_samples_per_sec:mfu_pct"] == 12.5
        busy = m["dlrm_synthetic_samples_per_sec:busy_samples_per_s"]
        assert busy == pytest.approx(256 * 4 * 2 / 0.010)
        rows, reg = compare(m, dict(m), 5.0)
        assert len(rows) == 4 and not reg

    def test_real_repo_artifacts(self, tmp_path):
        # the repo's own history must gate a driver-format record clean
        # (the BENCH_rNN.json shape: the bench's JSON line under
        # "parsed" — here the last such record the history was anchored
        # against)
        rec = str(tmp_path / "BENCH_r05.json")
        with open(rec, "w") as f:
            json.dump({"n": 5, "cmd": "python bench.py", "rc": 0,
                       "parsed": {
                           "metric": "dlrm_synthetic_samples_per_sec",
                           "value": 1416751.26, "unit": "samples/s",
                           "vs_baseline": 105.4904}}, f)
        rc = regress_main(["--baseline",
                           os.path.join(REPO, "bench_history.json"),
                           "--new", rec, "--tolerance", "5"])
        assert rc == 0


# ------------------------------------------------------------ training spans

class TestTrainingSpans:
    def test_fit_epoch_dispatch_chain(self):
        from dlrm_flexflow_tpu.data.loader import ArrayDataLoader
        # fit_scan_max_bytes=0 keeps fit on the per-epoch path (the
        # fused multi-epoch dispatch has no host epoch boundary and
        # correctly emits fit -> dispatch only — covered below)
        m = ff.FFModel(ff.FFConfig(batch_size=4, fit_scan_max_bytes=0))
        x = m.create_tensor((4, 3), name="x")
        m.dense(m.dense(x, 8, activation="relu"), 1)
        m.compile(optimizer=ff.SGDOptimizer(0.01),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        rng = np.random.default_rng(0)
        loader = ArrayDataLoader(
            {"x": rng.standard_normal((16, 3)).astype(np.float32)},
            rng.standard_normal((16, 1)).astype(np.float32), batch_size=4)
        with event_log() as log:
            m.fit(m.init(seed=0), loader, epochs=2, verbose=False)
            spans = log.events("span")
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e)
        assert set(by_name) >= {"train.fit", "train.epoch",
                                "train.dispatch"}
        assert len(by_name["train.epoch"]) == 2
        fit = by_name["train.fit"][0]
        assert all(e["trace_id"] == fit["trace_id"] for e in spans)
        assert all(e["parent_id"] == fit["span_id"]
                   for e in by_name["train.epoch"])
        # dispatch spans parent to their epoch, completing the chain
        epoch_ids = {e["span_id"] for e in by_name["train.epoch"]}
        assert all(e["parent_id"] in epoch_ids
                   for e in by_name["train.dispatch"])

    def test_fused_fit_has_dispatch_span(self):
        from dlrm_flexflow_tpu.data.loader import ArrayDataLoader
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        x = m.create_tensor((4, 3), name="x")
        m.dense(x, 1)
        m.compile(optimizer=ff.SGDOptimizer(0.01),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        rng = np.random.default_rng(0)
        loader = ArrayDataLoader(
            {"x": rng.standard_normal((8, 3)).astype(np.float32)},
            rng.standard_normal((8, 1)).astype(np.float32), batch_size=4)
        with event_log() as log:
            m.fit(m.init(seed=0), loader, epochs=2, verbose=False)
            spans = log.events("span")
        disp = [e for e in spans if e["name"] == "train.dispatch"]
        assert len(disp) == 1 and disp[0]["attrs"].get("fused") is True

    def test_diverged_fit_leaves_no_stale_parent(self):
        # a fit that DIES (TrainingDiverged) abandons its open spans;
        # it must not leave them on the thread's span stack where a
        # later, unrelated span would wrongly parent into the dead
        # trace
        from dlrm_flexflow_tpu.data.loader import ArrayDataLoader
        from dlrm_flexflow_tpu.resilience import (NaNSentinel,
                                                  TrainingDiverged)
        m = ff.FFModel(ff.FFConfig(batch_size=4,
                                   faults="nan_grads@step=0"))
        x = m.create_tensor((4, 3), name="x")
        m.dense(x, 1)
        m.compile(optimizer=ff.SGDOptimizer(0.01),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        rng = np.random.default_rng(0)
        loader = ArrayDataLoader(
            {"x": rng.standard_normal((8, 3)).astype(np.float32)},
            rng.standard_normal((8, 1)).astype(np.float32), batch_size=4)
        from dlrm_flexflow_tpu.resilience import faultinject
        try:
            with event_log() as log:
                with pytest.raises(TrainingDiverged):
                    m.fit(m.init(seed=0), loader, epochs=1, verbose=False,
                          sentinel=NaNSentinel(max_rollbacks=0))
                assert current_span() is None
                with span("after"):
                    pass
                after = [e for e in log.events("span")
                         if e["name"] == "after"][0]
        finally:
            faultinject.clear()  # config-installed faults are global
        assert "parent_id" not in after

    def test_resilient_fit_checkpoint_span(self, tmp_path):
        from dlrm_flexflow_tpu.data.loader import ArrayDataLoader
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        x = m.create_tensor((4, 3), name="x")
        m.dense(x, 1)
        m.compile(optimizer=ff.SGDOptimizer(0.01),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        rng = np.random.default_rng(0)
        loader = ArrayDataLoader(
            {"x": rng.standard_normal((8, 3)).astype(np.float32)},
            rng.standard_normal((8, 1)).astype(np.float32), batch_size=4)
        with event_log() as log:
            m.fit(m.init(seed=0), loader, epochs=1, verbose=False,
                  checkpoint_manager=str(tmp_path),
                  checkpoint_every_n_epochs=1)
            spans = log.events("span")
        names = {e["name"] for e in spans}
        assert {"train.fit", "train.epoch", "train.dispatch",
                "ckpt.save"} <= names
        fit = [e for e in spans if e["name"] == "train.fit"][0]
        saves = [e for e in spans if e["name"] == "ckpt.save"]
        assert all(e["trace_id"] == fit["trace_id"] for e in saves)


# ------------------------------------------------------------------ tooling

class TestObservabilityTooling:
    def test_smoke_matrix_passes(self):
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_observability.py")],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK (4 observability paths)" in r.stdout

    def test_metrics_port_cli_flag(self):
        cfg = ff.FFConfig.parse_args(["--metrics-port", "9109"])
        assert cfg.metrics_port == 9109
        assert ff.FFConfig().metrics_port == 0
