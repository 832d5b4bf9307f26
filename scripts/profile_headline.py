"""Attribute the fused headline window's device time by HLO op.

Captures a jax.profiler trace around ONE fused multi-epoch window of the
bench headline config (bench.py:175-230) and aggregates the TPU track's
slice durations by op name, so the per-step embedding tax (PERF.md
round-3 roofline: ~1.0 of the 1.14 ms step) is measured, not inferred.

Usage: python scripts/profile_headline.py [nb] [epochs]
Env: PROF_ROWS (default 1e6), PROF_BATCH (256), PROF_LEVELS (ladder
override, e.g. "256,32,8"), PROF_TOP (default 30 lines), PROF_ZIPF (a
Zipf exponent, e.g. 1.05, for the ids; default uniform).
"""

import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build():
    import numpy as np
    import jax
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

    batch = int(os.environ.get("PROF_BATCH", 256))
    nb = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    epochs = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    rows = int(float(os.environ.get("PROF_ROWS", 1_000_000)))

    cfg = DLRMConfig()
    cfg.embedding_size = [rows] * 8
    kw = {}
    if os.environ.get("PROF_LEVELS"):
        kw["epoch_cache_levels"] = os.environ["PROF_LEVELS"]
    ffconfig = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16",
                           embedding_dtype=os.environ.get(
                               "PROF_EMB_DTYPE", "float32"), **kw)
    model = build_dlrm(cfg, ffconfig)
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01),
                  loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"),
                  mesh=False if jax.device_count() == 1 else None)
    state = model.init(seed=0)
    rng = np.random.default_rng(0)
    shape = (nb, batch, 8, cfg.embedding_bag_size)
    if os.environ.get("PROF_ZIPF"):
        from dlrm_flexflow_tpu.data.loader import zipf_ids
        sparse = zipf_ids(rng, rows, shape, a=float(os.environ["PROF_ZIPF"]))
    else:
        sparse = rng.integers(0, rows, size=shape, dtype=np.int64)
    inputs = {
        "dense": rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32),
        "sparse": sparse,
    }
    labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
    inputs, labels = model.place_dataset(inputs, labels)
    return model, state, inputs, labels, nb, epochs, batch


def parse_trace(logdir, min_frac=0.001):
    """Shared implementation lives in dlrm_flexflow_tpu.profiling (the
    bench protocol records the same busy statistic as ``device_busy_ms``).
    Op times are SELF times — a scan's ``while`` slice spans its body in
    the trace, so raw sums would double-count."""
    from dlrm_flexflow_tpu.profiling import parse_device_trace

    try:
        return parse_device_trace(logdir)
    except FileNotFoundError as e:
        raise SystemExit(str(e))


def region_fetch_line(model, state, inputs, labels, epochs):
    """What the single-level region fetch (``row_cache._region_fetch``)
    meets on these ids: per leaf block, the positions it gathers (rows
    another block holds too: the plan's own count,
    ``ops/slotting.py::region_slots``) and the share it streams with
    its one ``dynamic_slice``.  ``None`` unless the program that ran
    contains that fetch: its scope ``ff.ladder.fetch.own`` in the
    lowered text is the one test, as in ``check_region_exact.py``; the
    rule that decides it lives in ``model.py`` alone."""
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops.slotting import region_slots

    hlo = model._train_epochs.lower(state, inputs, labels, epochs).as_text(
        debug_info=True)
    if "ff.ladder.fetch.own" not in hlo:
        return None
    op = model.get_op("emb")
    # the single-level layout's block: the ladder's one level
    levels = os.environ.get("PROF_LEVELS")
    steps = int(levels) if levels else int(model.config.epoch_cache_inner)
    ids = inputs["sparse"]
    view_rows = math.prod(state.params["emb"]["embedding"].shape[:-1])
    blocks = (op.flat_ids(ids.astype(jnp.int32))
              // op.storage_pack).reshape(ids.shape[0] // steps, -1)
    counts = region_slots(blocks, view_rows)[2]
    m = blocks.shape[1]
    mean, top = float(counts.mean()), int(counts.max())
    return (f"# region fetch: m {m}, foreign rows a block mean {mean:.0f} / "
            f"max {top}, share of positions streamed {1 - mean / m:.3f}")


def main():
    from dlrm_flexflow_tpu.profiling import device_fence

    model, state, inputs, labels, nb, epochs, batch = build()

    def window(st):
        st, _ = model.train_epochs(st, inputs, labels, epochs)
        return st

    state = window(state)  # compile
    device_fence(state.step)
    t0 = time.perf_counter()
    state = window(state)
    device_fence(state.step)
    dt_plain = time.perf_counter() - t0
    steps = nb * epochs
    print(f"# fused window (untraced): {dt_plain*1e3:.1f} ms, "
          f"{steps} steps -> {dt_plain/steps*1e6:.1f} us/step, "
          f"{steps*batch/dt_plain:,.0f} samples/s")

    logdir = os.environ.get("PROF_LOGDIR", "/tmp/ff_trace")
    import jax
    jax.profiler.start_trace(logdir)
    state = window(state)
    device_fence(state.step)
    jax.profiler.stop_trace()

    path, pnames, tot, busy_ms = parse_trace(logdir)
    print(f"# trace: {path}")
    print(f"# tracks: {sorted(set(pnames.values()))}")
    total = sum(tot.values())
    print(f"# device busy (module track): {busy_ms:.1f} ms = "
          f"{busy_ms*1e3/steps:.1f} us/step")
    print(f"# op self-time total: {total/1e3:.1f} ms over "
          f"{len(tot)} op names")
    top = int(os.environ.get("PROF_TOP", 30))
    for name, dur in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{dur/1e3:10.2f} ms  {dur/total*100:5.1f}%  "
              f"{dur/steps:8.1f} us/step  {name[:110]}")
    # the same self time by phase of the compiled program (the scopes of
    # model.py::_compile_body and row_cache.py, read off each slice's
    # name stack)
    from dlrm_flexflow_tpu.profiling import parse_device_trace_phases

    _path, by_phase, _busy = parse_device_trace_phases(logdir)
    print("# by phase:")
    for phase, dur in sorted(by_phase.items(), key=lambda kv: -kv[1]):
        print(f"{dur/1e3:10.2f} ms  {dur/total*100:5.1f}%  "
              f"{dur/steps:8.1f} us/step  {phase}")
    line = region_fetch_line(model, state, inputs, labels, epochs)
    if line:
        print(line)


if __name__ == "__main__":
    main()
