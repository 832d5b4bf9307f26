"""The benchmark's command: one cell per process, on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names a
configuration and a traffic mix, found as ``configs/<config>.json`` and
``traffic/<traffic>.json`` beside this file; the traffic file names its
driver (``drivers/<kind>.py``), the configuration its family
(``models/<family>.py``, which also holds the comparison with the plain
reference); a per-layer metric is ``layer_metrics/<name>.py``.  A new
cell or metric is new files and new entries, never an edit here.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``compared``: each number that decided ``correct`` beside its
limit (also the last line of stderr).  Lines before it say what a
strange number would need: the device, the compile cache, compiles
inside the window, the walls of the window's dispatches, the
comparison's report.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str):
    print(msg, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file(path: str):
    """Import one driver or metric reader by its path (their names may
    hold dots, and later PRs add them without touching a package)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _in_cell(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: str, workload: str) -> dict:
    """Everything ``BENCHMARK.json`` under ``root`` says about one cell,
    with its files loaded: configuration, traffic, driver path, and the
    cell's end-to-end and per-layer metrics (readers by path)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      entry["traffic"] + ".json"))
    layers = [m for m in bench["per_layer"] if _in_cell(m, workload)]
    return {"name": workload, "chips": entry["chips"],
            "config": _load_json(os.path.join(
                root, configs[entry["config"]]["file"])),
            "traffic": traffic,
            "driver": os.path.join(bench_dir, "drivers",
                                   traffic["driver"] + ".py"),
            "end_to_end": [m for m in bench["end_to_end"]
                           if _in_cell(m, workload)],
            "per_layer": layers,
            "readers": {m["name"]: os.path.join(
                bench_dir, "layer_metrics", m["name"] + ".py")
                for m in layers}}


def _peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    say("memory: " + "; ".join(
        f"dev{d.id} peak {s.get('peak_bytes_in_use', 0)} in use "
        f"{s.get('bytes_in_use', 0)} limit {s.get('bytes_limit', 0)}"
        for d, s in zip(devices, stats)))
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _traced(driver, ctx, seconds: float, limit: int):
    """One short steady window under the profiler (Python tracer off: it
    would slow a host-bound loop) with the program's telemetry on.
    Returns ``(window, reduced trace, telemetry events)``."""
    import jax

    from benchmarks.lib import trace as trace_lib
    from dlrm_flexflow_tpu.telemetry import event_log

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with event_log(ring=1 << 16) as log:
            jax.profiler.start_trace(logdir, profiler_options=opts)
            try:
                window = driver.run_window(ctx, seconds, limit=limit)
            finally:
                jax.profiler.stop_trace()
            events = log.events()
        reduced = trace_lib.reduce_trace(trace_lib.load_newest_trace(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return window, reduced, events


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            peaks=None, check_steps=None) -> dict:
    """Set up, run the window (traced: a short one), read the memory,
    compare with the reference.  Returns the result object.  Knows no
    device check and no cache: ``main`` does those, and the tests run
    this on the CPU at a tiny size.  ``check_steps`` stands in for the
    driver's in the comparison: the control, or a test's planted fault,
    either of which has to read ``correct: false``."""
    import jax
    import numpy as np

    from benchmarks.lib import trace as trace_lib
    from dlrm_flexflow_tpu.telemetry import (compile_stats,
                                             install_compile_hooks)

    config, traffic = cell["config"], cell["traffic"]
    family = importlib.import_module("benchmarks.models." + config["family"])
    driver = load_file(cell["driver"])
    install_compile_hooks()
    devices = jax.devices()[:cell["chips"]]

    model, state = family.build(config, traffic["batch"], seed, devices)
    dataset = family.make_dataset(config, traffic, seed)
    ctx = driver.prepare(model, state, dataset, traffic, seed)
    del state, dataset
    step_before = int(np.asarray(ctx["state"].step))
    at_open = compile_stats()

    if trace:
        window, reduced, events = _traced(driver, ctx, seconds,
                                          traffic["traced_units"])
        say(f"trace: {reduced['modules']} programs, {len(reduced['gaps'])} "
            f"gaps, busy us by chip {reduced['busy_us_by_chip']}")
    else:
        window = driver.run_window(ctx, seconds)
    at_close = compile_stats()
    peak = _peak_bytes(devices)
    setup_s = window["t0"] - T_START

    in_window = int(at_close.get("backend_compile", 0)
                    - at_open.get("backend_compile", 0))
    say(f"compiles: {int(at_open.get('backend_compile', 0))} in set-up "
        f"({at_open.get('backend_compile_s', 0.0):.2f} s), persistent cache "
        f"{int(at_open.get('cache_hits', 0))} hits / "
        f"{int(at_open.get('cache_misses', 0))} written; inside the window: "
        f"{in_window}" + ("  <-- COMPILED INSIDE THE WINDOW" if in_window
                          else ""))
    walls = window["dispatch_walls_s"]
    wanted = window.get("dispatches_wanted", len(walls))
    say(f"window: {window['wall_s']:.4f} s, {window['steps']} steps, "
        f"{len(walls)} of {wanted} dispatches"
        + (f" (STOPPED SHORT: --seconds {seconds:g} had passed)"
           if len(walls) < wanted else "") + "; walls s: "
        + " ".join(f"{w:.4f}" for w in walls))

    advanced = int(np.asarray(ctx["state"].step)) - step_before
    counted = window["steps"] + driver.UNCOUNTED_STEPS
    if advanced != counted:
        say(f"state.step advanced by {advanced}, steps counted {counted}")
    start = step_before + advanced  # where the comparison starts
    ok, report, ctx["state"] = family.check(
        config, traffic, model, ctx.pop("state"), seed,
        check_steps or driver.check_steps, driver.CHECK_BATCHES)
    say(f"reference: {'agrees' if ok else 'DISAGREES'} from state.step "
        f"{start} {json.dumps(report)}")

    # the process's peak as JAX reports it now, the comparison's copy of
    # the table included; the trainer's own is the per-layer peak_hbm_gib
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": _peak_bytes(devices)}
    values = {"setup_s": setup_s,
              traffic["rate_metric"]: window["samples"] / window["wall_s"]}
    result = {"correct": bool(ok and advanced == counted
                              and window["failed_steps"] == 0),
              "attempted": window["steps"],
              "failed": window["failed_steps"]}
    if not trace:
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    else:
        rctx = {"cell": cell["name"], "config": config, "traffic": traffic,
                "chips": cell["chips"], "peaks": peaks, "window": window,
                "trace": reduced, "events": events,
                "setup_compile_s": at_open.get("backend_compile_s", 0.0),
                "memory_peak_bytes": peak}
        result["metrics"] = {}
        for m in cell["per_layer"]:
            value = load_file(cell["readers"][m["name"]]).read(rctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        info["busy_s"] = reduced["busy_us_mean"] / 1e6
        info["window_s"] = window["wall_s"]
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(reduced["self_us"], 10),
            "idle_gaps": trace_lib.longest_gaps(reduced["gaps"],
                                                reduced["spans"], 5)}
    result["device"] = info
    # each number compared beside its limit: the last key of the line and
    # the last line of stderr, which is all the driver keeps of a run that
    # is not correct.  Where the comparison starts is the traffic file's
    # to fix, not the program's speed: a window that stopped short starts
    # it that many steps early
    short = (wanted - len(walls)) * (window["steps"] // len(walls))
    compared = {name: [report[name], limit]
                for name, limit in family.LIMITS.items()}
    compared.update(check_from_step=[start, start + short],
                    steps_advanced=[advanced, counted],
                    nonfinite_steps=[window["failed_steps"], 0])
    result["compared"] = compared
    print("compared (value, limit): " + "; ".join(
        f"{name} {value} {limit}" for name, (value, limit)
        in compared.items()), file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the family's control (its reference one "
                         "precision down) in the program's place; it has to "
                         "print correct: false.  Never part of a measurement")
    args = ap.parse_args(argv)

    cell = resolve(ROOT, args.workload)
    try:
        from dlrm_flexflow_tpu.entrypoint import enable_compile_cache
    except ImportError as e:
        print(f"benchmarks/run.py needs the repository around it: {e}",
              file=sys.stderr)
        return 3
    import jax

    from benchmarks.lib.flops import PEAKS

    devs = jax.devices()
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)}")
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"refusing to run: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} x {devs[0].platform}",
              file=sys.stderr)
        return 3
    if devs[0].device_kind not in PEAKS:
        print(f"refusing to run: no peaks for {devs[0].device_kind!r} in "
              f"benchmarks/lib/flops.py", file=sys.stderr)
        return 3
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache (fixed);
    # serve the ~120 sub-second programs from it too
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir}, {entries} entries at start "
        f"({'warm' if entries else 'cold'})")

    control = None
    if args.control:
        control = importlib.import_module(
            "benchmarks.models." + cell["config"]["family"]).control_steps(
                cell["config"])
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     peaks=PEAKS[devs[0].device_kind], check_steps=control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
