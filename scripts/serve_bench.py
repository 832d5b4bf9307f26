"""Serving load generator: open/closed-loop QPS + latency measurement.

Drives a DLRM :class:`InferenceEngine` + :class:`DynamicBatcher`
(docs/serving.md) with synthetic request traffic and reports
p50/p95/p99 latency and QPS — the serving twin of the training
``bench.py`` windows:

  * **closed loop** (default): ``--clients`` threads each fire
    ``--requests`` back-to-back requests (each waits for its response
    before sending the next) — measures sustainable throughput at a
    fixed concurrency;
  * **open loop**: requests arrive at a fixed ``--qps`` schedule for
    ``--duration`` seconds regardless of completions (the
    coordinated-omission-free arrival model) — measures behavior under
    offered load, including explicit `Rejected` shedding when the
    bounded queue fills.

Telemetry lands in a JSONL (default
``artifacts/telemetry_serving.jsonl`` under the repo root;
``--telemetry`` overrides) whose ``serve`` + ``span`` events feed::

    python -m dlrm_flexflow_tpu.telemetry report artifacts/telemetry_serving.jsonl
    python -m dlrm_flexflow_tpu.telemetry export-trace artifacts/telemetry_serving.jsonl

the report's ``== serving ==`` / ``== spans ==`` sections and the
Perfetto timeline of every request's submit → queue-wait → forward →
reply chain.  With ``--checkpoint DIR`` the engine loads params from a
training checkpoint (optimizer slots skipped — checkpoint.py
inference-only restore) instead of a fresh init; ``--metrics-port N``
serves live Prometheus metrics at ``http://:N/metrics`` for the run's
duration (docs/telemetry.md).

``--slo "p99_ms=5,availability=99.9"`` declares serving objectives for
the run (docs/slo.md): an :class:`SLOMonitor` evaluates multi-window
burn rates against the live metrics registry while the load runs
(windows shrunk to bench scale via ``--slo-fast-window`` /
``--slo-slow-window``), emits schema-checked ``slo`` events into the
telemetry JSONL, and the end-of-run summary prints remaining error
budget, the worst burn rate, and the dominant tail phase from the
latency exemplars.

``--replicas N`` routes the load through a least-loaded
:class:`ReplicaRouter` over N batcher replicas (per-replica breakdown
in the report: dispatched / shed / p99 — the router-absorbs-overload
claim visible in one run's output); ``--mesh-shape data=2,model=4``
compiles and serves mesh-native (sharded params, AOT bucket programs
under the mesh — docs/serving.md).
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
if __name__ == "__main__":
    # the load generator measures the chip unless the caller asks for
    # the CPU (JAX_PLATFORMS=cpu — main() refuses a silent fallback).
    # a --mesh-shape run on the CPU backend needs the virtual device
    # count pinned BEFORE jax initializes (the flag is read at backend
    # start); respect an explicit XLA_FLAGS from the caller.  Both
    # argparse spellings ("--mesh-shape SPEC" and "--mesh-shape=SPEC")
    # must hit this path.
    _spec = None
    for _j, _arg in enumerate(sys.argv):
        if _arg == "--mesh-shape" and _j + 1 < len(sys.argv):
            _spec = sys.argv[_j + 1]
        elif _arg.startswith("--mesh-shape="):
            _spec = _arg.partition("=")[2]
    if _spec is not None and os.environ.get(
            "JAX_PLATFORMS") == "cpu" and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        try:
            _n = 1
            for _part in _spec.split(","):
                _n *= int(_part.partition("=")[2] or 1)
        except ValueError:
            _n = 1
        if _n > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={_n}").strip()

import numpy as np  # noqa: E402

import dlrm_flexflow_tpu as ff  # noqa: E402
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm  # noqa: E402
from dlrm_flexflow_tpu.serving import (DynamicBatcher,  # noqa: E402
                                       InferenceEngine, Rejected,
                                       ReplicaRouter)
from dlrm_flexflow_tpu.telemetry import event_log  # noqa: E402


def parse_mesh_shape(spec: str):
    """``"data=2,model=4"`` -> {"data": 2, "model": 4}; "" -> None."""
    spec = (spec or "").strip()
    if not spec:
        return None
    shape = {}
    for part in spec.split(","):
        axis, _, n = part.partition("=")
        if not axis or not n:
            raise ValueError(
                f"--mesh-shape wants axis=N[,axis=N...], got {spec!r}")
        shape[axis.strip()] = int(n)
    return shape


def build_model(args):
    mesh_shape = parse_mesh_shape(getattr(args, "mesh_shape", ""))
    cfg = DLRMConfig(sparse_feature_size=args.emb_dim,
                     embedding_size=[args.table_rows] * args.tables,
                     embedding_bag_size=args.bag,
                     mlp_bot=[args.dense, 32, args.emb_dim],
                     mlp_top=[args.emb_dim * args.tables + args.emb_dim,
                              32, 1])
    fc = ff.FFConfig(batch_size=max_bucket(args),
                     serve_buckets=args.buckets,
                     serve_max_wait_us=args.max_wait_us,
                     serve_queue_depth=args.queue_depth,
                     serve_timeout_us=args.timeout_us,
                     serve_storage=getattr(args, "storage", "resident"),
                     storage_hot_rows=getattr(args, "hot_rows", 4096))
    # table-parallel strategies only make sense with a model axis to
    # shard over; a pure-data mesh serves replicated params
    table_parallel = bool(mesh_shape and mesh_shape.get("model", 1) > 1)
    m = build_dlrm(cfg, fc, table_parallel=table_parallel)
    mesh = ff.make_mesh(mesh_shape) if mesh_shape else False
    m.compile(optimizer=ff.SGDOptimizer(0.01),
              loss_type="mean_squared_error", metrics=(), mesh=mesh)
    return cfg, m


def max_bucket(args) -> int:
    from dlrm_flexflow_tpu.serving import parse_buckets

    return parse_buckets(args.buckets)[-1]


def request_pool(cfg, args, n_pool: int = 256):
    """Pre-generate a pool of requests so the load loop measures
    serving, not numpy RNG.  ``--id-dist zipf`` draws the sparse ids
    power-law skewed (exponent ``--zipf-alpha``) — the regime a tiered
    hot cache (``--storage tiered``) is built for."""
    from dlrm_flexflow_tpu.data.loader import zipf_ids

    rng = np.random.default_rng(args.seed)
    zipf = getattr(args, "id_dist", "uniform") == "zipf"
    alpha = getattr(args, "zipf_alpha", 1.05)

    def ids(r, n):
        if zipf:
            return zipf_ids(rng, r, (n, cfg.embedding_bag_size),
                            a=alpha)
        return rng.integers(0, r, size=(n, cfg.embedding_bag_size),
                            dtype=np.int64)

    pool = []
    for _ in range(n_pool):
        n = args.rows
        pool.append({
            "dense": rng.standard_normal(
                (n, cfg.mlp_bot[0])).astype(np.float32),
            "sparse": np.stack(
                [ids(r, n) for r in cfg.embedding_size], axis=1),
        })
    return pool


def closed_loop(batcher, pool, clients: int, requests: int):
    """``clients`` threads, each ``requests`` sequential requests
    (every client waits for its response before sending the next).
    Returns (wall_s, rejected).  THE closed-loop harness — bench.py's
    ``BENCH_APP=dlrm_serving`` headline drives the same code."""
    rejected = [0] * clients

    def client(i):
        for k in range(requests):
            try:
                batcher.predict(pool[(i * requests + k) % len(pool)])
            except Rejected:
                rejected[i] += 1

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, sum(rejected)


def open_loop(batcher, pool, qps: float, duration: float):
    """Fixed-rate arrivals for ``duration`` seconds; responses are
    collected after the offered-load window closes (submit never
    blocks on a result).  Returns (wall_s, rejected)."""
    futures = []
    rejected = 0
    period = 1.0 / max(qps, 1e-9)
    t0 = time.perf_counter()
    k = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= duration:
            break
        target = t0 + k * period
        if target > now:
            time.sleep(target - now)
        try:
            futures.append(batcher.submit(pool[k % len(pool)]))
        except Rejected:
            rejected += 1
        k += 1
    for f in futures:
        try:
            f.result(timeout=30.0)
        except Exception:
            pass  # deadline misses / cancelled drains counted in stats
    # wall spans submit THROUGH completion of everything offered, so
    # served/wall is sustainable throughput — stopping the clock at the
    # window edge would credit the post-window backlog drain as free
    return time.perf_counter() - t0, rejected


def main(argv=None) -> int:
    from dlrm_flexflow_tpu.entrypoint import (enable_compile_cache,
                                              require_tpu)

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--mode", choices=("closed", "open"), default="closed")
    p.add_argument("--clients", type=int, default=4,
                   help="closed-loop concurrent clients")
    p.add_argument("--requests", type=int, default=64,
                   help="closed-loop requests per client")
    p.add_argument("--qps", type=float, default=500.0,
                   help="open-loop offered arrival rate")
    p.add_argument("--duration", type=float, default=2.0,
                   help="open-loop window seconds")
    p.add_argument("--rows", type=int, default=1,
                   help="rows per request")
    p.add_argument("--replicas", type=int, default=1,
                   help="serving replicas behind a least-loaded "
                        "ReplicaRouter (1 = single DynamicBatcher); "
                        "replicas share one engine (queue-level "
                        "replication) — docs/serving.md")
    p.add_argument("--mesh-shape", default="",
                   help="compile + serve under a device mesh, e.g. "
                        "data=2,model=4 (model>1 builds the "
                        "table-parallel strategy); empty = single "
                        "device")
    p.add_argument("--buckets", default="1,8,32")
    p.add_argument("--max-wait-us", type=float, default=1000.0)
    p.add_argument("--queue-depth", type=int, default=256)
    p.add_argument("--timeout-us", type=float, default=0.0)
    p.add_argument("--tables", type=int, default=4)
    p.add_argument("--table-rows", type=int, default=1000)
    p.add_argument("--emb-dim", type=int, default=8)
    p.add_argument("--bag", type=int, default=2)
    p.add_argument("--dense", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint", default="",
                   help="CheckpointManager dir (or one ckpt dir) to "
                        "load params from (inference-only restore)")
    p.add_argument("--quantize", default="off",
                   choices=("off", "int8", "bf16"),
                   help="row-quantize the embedding tables at engine "
                        "load (docs/serving.md; tolerance-pinned "
                        "outputs, ~4x/2x smaller table sweep)")
    p.add_argument("--storage", default="resident",
                   choices=("resident", "tiered"),
                   help="embedding residency: resident keeps full "
                        "tables on device; tiered caches --hot-rows "
                        "hot rows and streams misses from host RAM "
                        "(docs/storage.md; mutually exclusive with "
                        "--quantize)")
    p.add_argument("--hot-rows", type=int, default=4096,
                   help="per-table device hot-row budget for "
                        "--storage tiered")
    p.add_argument("--id-dist", default="uniform",
                   choices=("uniform", "zipf"),
                   help="sparse-id law for the request pool; zipf "
                        "gives the power-law skew a tiered hot cache "
                        "is built for")
    p.add_argument("--zipf-alpha", type=float, default=1.05,
                   help="zipf exponent for --id-dist zipf (>1; "
                        "higher = more skew)")
    p.add_argument("--slo", default="",
                   help='serving objectives for the run, e.g. '
                        '"p99_ms=5,availability=99.9" (docs/slo.md); '
                        "monitored at --slo-interval with burn-rate "
                        "windows shrunk to bench scale, summarized "
                        "at end of run")
    p.add_argument("--slo-interval", type=float, default=0.25,
                   help="--slo evaluation period seconds")
    p.add_argument("--slo-fast-window", type=float, default=1.0,
                   help="--slo fast burn-rate window seconds (the "
                        "SRE default is 60s; a bench run wants the "
                        "whole state machine inside its wall)")
    p.add_argument("--slo-slow-window", type=float, default=5.0,
                   help="--slo slow burn-rate window seconds")
    p.add_argument("--telemetry",
                   default=os.path.join(REPO, "artifacts",
                                        "telemetry_serving.jsonl"))
    p.add_argument("--metrics-port", type=int, default=0,
                   help="serve Prometheus /metrics + /healthz on this "
                        "port for the run (0 = off)")
    p.add_argument("--metrics-host", default="127.0.0.1",
                   help="bind address for /metrics (loopback by "
                        "default — the endpoint is unauthenticated; "
                        "0.0.0.0 exposes it to the network)")
    args = p.parse_args(argv)

    enable_compile_cache()
    require_tpu(allow_requested_cpu=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.telemetry)),
                exist_ok=True)
    if args.metrics_port:
        from dlrm_flexflow_tpu.telemetry.exporter import start_metrics_server

        srv = start_metrics_server(args.metrics_port,
                                   host=args.metrics_host)
        print(f"serve_bench: metrics at "
              f"http://{args.metrics_host}:{srv.port}/metrics")
    cfg, model = build_model(args)
    with event_log(args.telemetry, mode="w"):
        # pool before engine: a tiered engine prices + warms its hot
        # tier from observed id frequencies, so feed the counters the
        # traffic it is about to serve (docs/storage.md)
        pool = request_pool(cfg, args)
        if args.storage == "tiered":
            from dlrm_flexflow_tpu.telemetry import rowfreq

            for req in pool:
                for t in range(len(cfg.embedding_size)):
                    rowfreq.counter(f"sparse[{t}]").observe(
                        req["sparse"][:, t, :])
        if args.checkpoint:
            engine = InferenceEngine.from_checkpoint(
                model, args.checkpoint, quantize=args.quantize,
                storage=args.storage)
        else:
            engine = InferenceEngine(model, model.init(seed=args.seed),
                                     quantize=args.quantize,
                                     storage=args.storage)
        if engine.quantization["mode"] != "off":
            q = engine.quantization
            print(f"serve_bench: quantized tables ({q['mode']}): "
                  f"{q['bytes_before']:,} -> {q['bytes_after']:,} bytes")
        if args.storage == "tiered":
            s = engine.storage
            if s["mode"] == "tiered":
                tot_rows = sum(t["rows"] for t in s["tables"].values())
                tot_hot = sum(t["hot_slots"]
                              for t in s["tables"].values())
                print(f"serve_bench: tiered storage: {tot_hot:,} hot "
                      f"slots over {tot_rows:,} rows "
                      f"({len(s['tables'])} table group(s), "
                      f"{args.id_dist} ids)")
            else:
                why = "; ".join(f"{k}: {v}"
                                for k, v in s["fallbacks"].items()) \
                    or "no embedding ops"
                print(f"serve_bench: tiered storage fell back to "
                      f"resident — {why}")
        if args.replicas > 1:
            # N batcher replicas over ONE engine (shared params + AOT
            # cache; each replica still has its own queue + dispatcher
            # thread) — pass distinct engines for per-slice serving
            batcher = ReplicaRouter([engine] * args.replicas)
        else:
            batcher = DynamicBatcher(engine)
        monitor, slo_sum, slo_dom = None, None, "none"
        if args.slo:
            from dlrm_flexflow_tpu.telemetry import slo as slo_mod

            monitor = slo_mod.SLOMonitor(
                slo_mod.parse_slos(
                    args.slo, fast_window_s=args.slo_fast_window,
                    slow_window_s=args.slo_slow_window),
                interval_s=args.slo_interval).start()
        if args.mode == "closed":
            wall, rejected = closed_loop(batcher, pool, args.clients,
                                         args.requests)
        else:
            wall, rejected = open_loop(batcher, pool, args.qps,
                                       args.duration)
        if monitor is not None:
            # one final pass over the drained counters (the thread may
            # be mid-sleep), then read the tail attribution BEFORE
            # close() retires the replica stats out of the exemplar sweep
            monitor.tick()
            slo_dom = slo_mod.dominant_tail_phase()
            slo_sum = monitor.summary()
            monitor.stop()
        summary = batcher.close()  # drains + emits the serve summary
    served = summary["requests"]
    qps = served / max(wall, 1e-9)
    line = (f"serve_bench[{args.mode}]: {served} requests in "
            f"{wall:.2f}s = {qps:,.0f} QPS")
    if args.replicas > 1:
        line += f" across {args.replicas} replicas"
    if "p50_us" in summary:
        line += (f"; latency p50 {summary['p50_us']:.0f} us / "
                 f"p95 {summary['p95_us']:.0f} us / "
                 f"p99 {summary['p99_us']:.0f} us")
    if rejected or summary.get("deadline_misses"):
        line += (f" ({rejected} rejected, "
                 f"{summary.get('deadline_misses', 0)} deadline misses)")
    print(line)
    for i, rep in enumerate(summary.get("per_replica") or []):
        # the absorb claim in one run's output: who dispatched, who
        # shed (local queue_full probes), each replica's tail
        p99 = (f"{rep['p99_us']:.0f} us" if "p99_us" in rep else "n/a")
        print(f"serve_bench:   replica {i}: {rep['requests']} served / "
              f"{rep['dispatches']} dispatched, {rep['rejected']} shed, "
              f"p99 {p99}")
    if engine.storage["mode"] == "tiered":
        st = engine.storage_stats()
        print(f"serve_bench: storage hit {st['hit_pct']:.1f}% "
              f"({st['hits']:,}/{st['lookups']:,} lookups), "
              f"{st['evictions']:,} evictions, miss stall last "
              f"{st['stall_us_last']:.0f} us")
    if args.replicas > 1:
        print(f"serve_bench:   router shed "
              f"{summary.get('router_shed', 0)} request(s) — a shed "
              f"means ALL {args.replicas} replicas were saturated")
    if slo_sum:
        for name in sorted(slo_sum):
            s = slo_sum[name]
            state = "BREACHED" if s["breached"] else "ok"
            print(f"serve_bench: slo {name}: {state}, "
                  f"{s['budget_pct']:.1f}% error budget remaining, "
                  f"burn {s['burn']:.2f}x")
        worst = max(slo_sum.items(), key=lambda kv: kv[1]["burn"])
        line = (f"serve_bench: slo worst burn {worst[1]['burn']:.2f}x "
                f"({worst[0]}); dominant tail phase: {slo_dom}")
        if monitor.breach_count:
            line += f"; {monitor.breach_count} breach(es)"
            if monitor.flight_paths:
                line += f", flight record -> {monitor.flight_paths[-1]}"
        print(line)
    print(f"serve_bench: telemetry -> {args.telemetry} "
          f"(python -m dlrm_flexflow_tpu.telemetry report "
          f"{os.path.relpath(args.telemetry, os.getcwd())})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
