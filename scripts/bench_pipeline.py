"""Per-batch hot-loop input-pipeline micro-bench (docs/pipeline.md).

The headline bench (bench.py) times SCANNED epochs — the whole epoch is
one dispatch and the input pipeline is off the hot path by design.  The
per-batch loops (every resilient run: checkpoint cadence, sentinel,
fault injection) are where host-side input work and the per-dispatch
loss fence used to serialize the device: THIS driver measures that
path, before/after, on the same seed.

Three identical runs of the sentinel-armed per-batch loop (the scanned
fast path force-disabled) on the same seed:

    python scripts/bench_pipeline.py    # the chip; JAX_PLATFORMS=cpu
                                        # to measure the CPU on purpose

  fenced        — the pre-pipeline hot loop: a no-op per-batch callback
                  forces the eager path, so every dispatch fences on
                  its folded loss before the next one issues;
  lag1          — the pipelined loop, prefetch off: step k's loss check
                  overlaps step k+1's device window;
  lag1+prefetch — plus the async input pipeline (prefetch_depth=2).

Prints per-run wall samples/s plus the step event's `data_stall_ms` /
`dispatch_ms` decomposition, verifies the adopted loss trajectories are
BIT-IDENTICAL (the pipeline re-orders *when* host work happens, never
*what* is computed), and reports the speedups.  Knobs: PIPE_BATCH
(256), PIPE_BATCHES (32), PIPE_EPOCHS (2), PIPE_ROWS (100000),
PIPE_PREFETCH (depth for the prefetch leg, default 2).

Exit 0 when trajectories match bitwise; 1 otherwise.
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import jax  # noqa: E402

import dlrm_flexflow_tpu as ff  # noqa: E402
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm  # noqa: E402
from dlrm_flexflow_tpu.data.loader import SyntheticDLRMLoader  # noqa: E402
from dlrm_flexflow_tpu.resilience import NaNSentinel  # noqa: E402
from dlrm_flexflow_tpu.telemetry import event_log  # noqa: E402


def main() -> int:
    from dlrm_flexflow_tpu.entrypoint import require_tpu

    require_tpu(allow_requested_cpu=True)
    batch = int(os.environ.get("PIPE_BATCH", "256"))
    nbatches = int(os.environ.get("PIPE_BATCHES", "32"))
    epochs = int(os.environ.get("PIPE_EPOCHS", "2"))
    rows = int(os.environ.get("PIPE_ROWS", "100000"))
    depth = int(os.environ.get("PIPE_PREFETCH", "2"))
    modes = [("fenced", 0, True), ("lag1", 0, False),
             ("lag1+prefetch", depth, False)]

    # the run_random.sh shape with env-scaled tables (CPU-friendly
    # default; on the bench chip use PIPE_ROWS=1000000)
    cfg = DLRMConfig(sparse_feature_size=64, embedding_size=[rows] * 8,
                     embedding_bag_size=64, mlp_bot=[64, 512, 512, 64],
                     mlp_top=[576, 1024, 1024, 1024, 1])
    platform = jax.devices()[0].platform
    print(f"pipeline-bench batch={batch} batches={nbatches} "
          f"epochs={epochs} rows={rows} platform={platform}")

    results = []
    for label, pf_depth, eager in modes:
        ffconfig = ff.FFConfig(batch_size=batch)
        ffconfig.prefetch_depth = pf_depth
        ffconfig.fit_scan_max_bytes = 0  # force the per-batch loop
        model = build_dlrm(cfg, ffconfig)
        model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False if jax.device_count() == 1 else None)
        loader = SyntheticDLRMLoader(batch * nbatches, cfg.mlp_bot[0],
                                     cfg.embedding_size,
                                     cfg.embedding_bag_size, batch,
                                     seed=3)
        state = model.init(seed=0)
        # warmup compile outside the timed stretch (one real step's
        # worth of compiles; the per-batch loop has no warmup step of
        # its own — step parity with resume)
        w0, w1 = loader.peek()
        model.train_step(state, w0, w1, donate=False)
        # a no-op per-batch callback is a host decision point: the loop
        # settles every dispatch eagerly — the pre-pipeline behavior
        from dlrm_flexflow_tpu.frontends.keras_callbacks import Callback
        cbs = [Callback()] if eager else None
        t0 = time.perf_counter()
        with event_log() as log:
            state, thpt = model.fit(
                state, loader, epochs=epochs, verbose=False,
                show_throughput=False, callbacks=cbs,
                sentinel=NaNSentinel(policy="skip"))
        wall = time.perf_counter() - t0
        ev = log.last("step")
        stall, disp = ev["data_stall_ms"], ev["dispatch_ms"]
        print(f"{label}: wall {wall:.2f} s, {thpt:,.0f} samples/s; "
              f"data_stall {stall:,.1f} ms "
              f"({0.1 * stall / max(wall, 1e-9):.1f}% of wall), "
              f"dispatch {disp:,.1f} ms")
        results.append((label, thpt, stall, wall,
                        model._fit_loss_trace.copy()))

    ok = True
    base = results[0]
    for label, thpt, stall, wall, trace in results[1:]:
        if not np.array_equal(base[4], trace):
            bad = int(np.argmax(base[4] != trace))
            print(f"FAIL: loss trajectory diverges from {base[0]} at "
                  f"step {bad}: {base[4][bad]} vs {trace[bad]}")
            ok = False
            continue
        print(f"{base[0]} -> {label}: loss trajectory bit-identical "
              f"({len(trace)} steps); wall speedup "
              f"{base[3] / max(wall, 1e-9):.2f}x")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
