"""The five ``setup_*`` readers (PR 40) and the program's set-up
timeline they read, on the CPU: the entries, every cell's rehearsal at
its tiny size with a persistent compile cache of its own (the
invariants the chip runs are held to), a program without the timeline,
and a staged window that adds no record."""

import copy
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchmarks import run  # noqa: E402
from test_benchmark_yardstick import make_tiny_root  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
READERS = {"setup_own_s": "s", "setup_trace_s": "s", "setup_lower_s": "s",
           "setup_cache_load_s": "s", "setup_compiled_programs": "programs"}


def _read(name, ctx):
    return run.load_file(os.path.join(
        ROOT, "benchmarks/layer_metrics", name + ".py")).read(ctx)


def test_the_entries_are_what_the_issue_named():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit in READERS.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["layer"], m["moves"]) \
            == (unit, "lower", "entry points", "setup_s")
        assert m["source"] == ("program_counter" if unit == "programs"
                               else "program_span")
        assert m["workloads"] == CELLS
    assert list(entries)[-5:] == list(READERS)  # appended, nothing moved


@pytest.fixture()
def persistent_cache(tmp_path):
    """A compile cache of the test's own, written from the first
    compile on, as ``run.py`` sets it on the chip; put back after."""
    import jax
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "jax_cache"))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture()
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root", ROOT, copy.deepcopy(BENCH))


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_reports_the_five_within_their_invariants(
        tiny_root, persistent_cache, workload, capsys):
    """What ISSUE 40 holds each chip run to, at the tiny size: the set-up
    the program saw lies inside ``setup_s`` and after the runtime's
    start-up, its stages inside it, and the executables it compiled are
    those the cache wrote."""
    from dlrm_flexflow_tpu.telemetry import compile_stats

    cell = run.resolve(tiny_root, workload)
    assert set(READERS) <= {m["name"] for m in cell["per_layer"]}
    written_before = compile_stats().get("cache_misses", 0)
    result = run.measure(cell, 2 ** 31 + 40, 0.2, trace=False)
    assert result["correct"] is True
    out = capsys.readouterr().out
    setup_s = result["metrics"]["setup_s"]["value"]
    ctx = {"window": {"t0": run.T_START + setup_s}}
    got = {name: _read(name, ctx) for name in READERS}
    before_measure = float(re.search(r"= ([0-9.]+) before measure",
                                     out).group(1))
    written = int(re.search(r"(\d+) written;", out).group(1))
    assert 0 < got["setup_own_s"] < setup_s
    assert setup_s - got["setup_own_s"] >= before_measure - 0.01
    assert (got["setup_trace_s"] + got["setup_lower_s"]
            + got["setup_cache_load_s"]) <= got["setup_own_s"]
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    assert got["setup_cache_load_s"] <= compile_stats()["backend_compile_s"]
    assert got["setup_compiled_programs"] == written - written_before


def test_the_readers_say_nothing_on_a_program_without_the_timeline(
        monkeypatch):
    from dlrm_flexflow_tpu import telemetry

    # before the first model of the process: no ``setup.model`` mark
    assert all(_read(name, {"window": {"t0": 0.0}}) is None
               for name in READERS)
    # a program as the parent's, which has no setup_breakdown at all
    monkeypatch.delattr(telemetry, "setup_breakdown")
    assert all(_read(name, {"window": {"t0": 1e12}}) is None
               for name in READERS)


def test_a_staged_window_without_a_log_adds_no_record(tiny_root):
    """The window the driver times, with no EventLog: nothing traces,
    lowers, compiles or marks, so the timeline is what it was."""
    from benchmarks.models import dlrm as family
    from dlrm_flexflow_tpu.telemetry import (active_log,
                                             install_compile_hooks, timeline)

    install_compile_hooks()
    cell = run.resolve(tiny_root, "dlrm-random.staged-uniform")
    config, traffic = cell["config"], cell["traffic"]
    driver = run.load_file(cell["driver"])
    model, state = family.build(config, traffic, 5, None)
    ctx = driver.prepare(model, state,
                         family.make_dataset(config, traffic, 5), traffic, 5)
    assert active_log() is None
    before = timeline()
    window = driver.run_window(ctx, 50)
    assert window["steps"] > 0
    assert timeline() == before
