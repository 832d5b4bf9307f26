"""Record the phase fixture of tests/test_profiling.py on the chip.

One 16-batch ``train_epoch`` of the run_random.sh DLRM (the program
``benchmarks/drivers/staged.py::check_steps`` runs) under the profiler
with telemetry on, written next to that program's ``program_phases``
map: the trace route (``args.tf_op``) and the map route must give the
same ``{phase: us}`` on it.  Re-record when a phase scope of
``model.py::_compile_body`` or ``row_cache.py`` is added, renamed or
given another extent (a scope that moves with its code, as in PR 33,
changes no program).

Usage (on a TPU): python scripts/record_phase_fixture.py <outdir>
Writes <outdir>/v5e_train_epoch_phases_trace.json.gz (not
``.trace.json.gz``: readers of ``tests/data`` take the newest file of
that suffix for the older recording) and
<outdir>/v5e_train_epoch_phases_map.json, and prints the host-device
clock offset read on the one fenced dispatch.
"""

import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEM = "v5e_train_epoch_phases"


def main(outdir: str) -> int:
    import jax

    from dlrm_flexflow_tpu import profiling
    from dlrm_flexflow_tpu.entrypoint import require_tpu
    from dlrm_flexflow_tpu.telemetry import event_log
    from profile_headline import build

    require_tpu()
    sys.argv[1:] = ["16", "1"]  # build() reads nb from argv
    model, state, inputs, labels, *_ = build()
    state, _ = model.train_epoch(state, inputs, labels)  # compile
    jax.block_until_ready(state.step)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    logdir = tempfile.mkdtemp(prefix="phase_fixture_")
    try:
        with event_log() as log:
            jax.profiler.start_trace(logdir, profiler_options=opts)
            state, _ = model.train_epoch(state, inputs, labels)
            with jax.profiler.TraceAnnotation("fixture.fence"):
                jax.block_until_ready(state.step)
            jax.profiler.stop_trace()
            (program,) = [e["name"] for e in log.events("program")]
        path, by_phase, busy_ms = profiling.parse_device_trace_phases(logdir)
        phases = profiling.program_phases(program)
        os.makedirs(outdir, exist_ok=True)
        shutil.copy(path, os.path.join(outdir, STEM + "_trace.json.gz"))
        with open(os.path.join(outdir, STEM + "_map.json"), "w") as f:
            json.dump(phases, f, sort_keys=True, separators=(",", ":"))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    print(f"program {program}: {len(phases)} instructions; busy "
          f"{busy_ms * 1e3:.2f} us")
    for phase, us in sorted(by_phase.items(), key=lambda kv: -kv[1]):
        print(f"{us:10.2f} us  {phase}")
    with gzip.open(os.path.join(outdir, STEM + "_trace.json.gz"), "rt") as f:
        events = json.load(f)["traceEvents"]
    fence = [e for e in events if e.get("name") == "fixture.fence"]
    module = [e for e in events if e.get("ph") == "X"
              and str(e.get("name", "")).startswith("jit_train_epoch")]
    if fence and module:
        # the fence returns when the device is done: what is left between
        # the two ends is the clocks' offset plus the wake-up latency
        off = (fence[0]["ts"] + fence[0]["dur"]
               - module[-1]["ts"] - module[-1]["dur"])
        print(f"host fence end - device module end: {off:.1f} us "
              f"(trace clock)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
