"""The benchmark's yardstick, on the CPU: the trace reduction, the FLOP
arithmetic, the traffic generator, the reference that decides
``correct`` (and that it fails on a lost update), ``BENCHMARK.json``'s
shape, and a dry rehearsal of ``benchmarks/run.py``'s own functions at
the tiny size of ``tiny.json``.  No test needs a chip."""

import copy
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import flops, trace, traffic  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------------ trace
def test_busy_and_self_time_on_the_recorded_v5e_trace():
    r = trace.reduce_trace(trace.load_newest_trace(
        os.path.join(ROOT, "tests", "data")))
    assert r["chips"] == 1 and r["modules"] == 1 and r["gaps"] == []
    assert r["busy_us"] == pytest.approx(2577.4999, abs=1e-3)
    assert r["busy_us_mean"] == r["busy_us"]
    # nested slices: self times add up to (almost) the module, not twice it
    assert 0.99 * r["busy_us"] < sum(r["self_us"].values()) <= r["busy_us"]
    name, seconds = trace.top_ops(r["self_us"], 1)[0]
    assert name == "train_epoch.1"
    assert seconds == pytest.approx(787.94e-6, rel=1e-4)
    assert trace.collective_us(r["self_us"]) == 0.0


def _synthetic_trace():
    def meta(pid, name, tid=None, tname=None):
        out = [{"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}]
        if tid is not None:
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": tname}})
        return out

    def x(pid, tid, name, ts, dur):
        return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
                "dur": dur}

    ev = (meta(1, "/device:TPU:0", 2, "XLA Modules")
          + meta(1, "/device:TPU:0", 3, "XLA Ops")
          + meta(2, "/device:TPU:1", 2, "XLA Modules")
          + meta(9, "/host:CPU", 5, "python"))
    ev += [x(1, 2, "jit_step(1)", 0, 100), x(1, 2, "jit_step(1)", 150, 100),
           x(2, 2, "jit_step(1)", 0, 80),
           # ops of chip 0: a while spanning a fusion and an all-reduce
           x(1, 3, "while.1", 0, 100), x(1, 3, "fusion.1", 10, 30),
           x(1, 3, "all-reduce.7", 50, 40),
           x(1, 3, "all-gather-start.2", 150, 5),
           x(1, 3, "all-to-all.1", 160, 15), x(1, 3, "fusion.2", 180, 70),
           x(9, 5, "bench.window", -10, 300), x(9, 5, "bench.wait", 90, 70),
           x(9, 5, "something else", 0, 400)]
    return ev


def test_collectives_gaps_and_chips_on_a_synthetic_trace():
    r = trace.reduce_trace(_synthetic_trace())
    assert r["chips"] == 2
    assert r["busy_us"] == 200 and r["busy_us_mean"] == 140
    assert r["self_us"]["while.1"] == 30          # 100 - 30 - 40
    assert trace.collective_us(r["self_us"]) == 40 + 5 + 15
    assert r["gaps"] == [(100, 50)]
    assert trace.longest_gaps(r["gaps"], r["spans"]) == [["bench.wait", 50e-6]]
    assert trace.longest_gaps(r["gaps"], []) == [["unattributed", 50e-6]]
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce_trace([e for e in _synthetic_trace()
                            if e.get("pid") == 9])


# ------------------------------------------------------- flops and traffic
def test_flops_against_a_hand_count_and_unknown_device_is_an_error():
    shape = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/dlrm-random.json")))["model"]
    # bottom 64-512-512-64: the first layer needs no input gradient (x2),
    # every other layer forward + two backward matmuls (x3)
    bottom = 2 * (64 * 512 * 2 + 512 * 512 * 3 + 512 * 64 * 3)
    top = 2 * 3 * (576 * 1024 + 1024 * 1024 + 1024 * 1024 + 1024 * 1)
    assert bottom + top == 18_028_544
    assert flops.train_flops_per_sample(shape) == bottom + top
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks_for("TPU v9")
    with pytest.raises(ValueError, match="cat"):
        flops.train_flops_per_sample(dict(shape, arch_interaction_op="dot"))


def test_traffic_is_the_seed_and_the_check_batches_repeat_ids():
    shape = {"embedding_size": [50, 70], "embedding_bag_size": 2,
             "mlp_bot": [3, 4]}
    big = 2 ** 31 + 11
    for ids in ({"dist": "uniform"}, {"dist": "zipf", "a": 1.05}):
        a, la = traffic.make_samples(shape, ids, 64, big)
        b, lb = traffic.make_samples(shape, ids, 64, big)
        c, _ = traffic.make_samples(shape, ids, 64, big + 1)
        assert a["dense"].shape == (64, 3) and a["sparse"].shape == (64, 2, 2)
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert np.array_equal(la, lb)
        assert not np.array_equal(a["sparse"], c["sparse"])
        assert a["sparse"][:, 0].max() < 50 and a["sparse"][:, 1].max() < 70
        assert a["sparse"].min() >= 0
    inputs, labels = traffic.make_check_batches(shape, {"dist": "uniform"},
                                                8, 3, big)
    assert inputs["sparse"].shape == (3, 8, 2, 2) and labels.shape == (3, 8, 1)
    assert np.array_equal(inputs["sparse"][:, 1], inputs["sparse"][:, 0])
    assert np.array_equal(inputs["sparse"][:, 2, 0], inputs["sparse"][:, 0, 0])
    with pytest.raises(ValueError, match="unknown id distribution"):
        traffic.make_samples(shape, {"dist": "normal"}, 8, 0)


# ------------------------------------------------------- BENCHMARK.json
def test_every_workload_resolves_to_files_that_exist_and_parse():
    for w in BENCH["workloads"]:
        cell = run.resolve(ROOT, w["name"])
        assert os.path.isfile(cell["driver"]), cell["driver"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks/models", cell["config"]["family"] + ".py"))
        assert cell["config"]["chips"] == w["chips"]
        assert all(os.path.isfile(p) for p in cell["readers"].values())
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert e2e == {"setup_s", cell["traffic"]["rate_metric"]}
        assert cell["per_layer"], "every cell reports a per-layer metric"
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
    with pytest.raises(KeyError, match="no workload"):
        run.resolve(ROOT, "dlrm-random.nothing")


def test_names_units_and_shape_are_what_the_driver_admits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] \
            == c["reduced"]
        names += [c["name"]] + c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        assert all(1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
                   for x in BENCH[group])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        for _dir, _sub, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" not in _dir:
                assert all(re.match(r"^[A-Za-z0-9_.\-]+$", f) for f in files)


#: measured on the chip in PR 24 and `correct`, but absent from
#: BENCHMARK.json (PERF.md, Open questions): the four-chip cell's one
#: dispatch outlasts the window, the stream cell fills 3.87 GiB of the 4.00
#: a cell must.  Their files stay; a later PR adds each back as entries alone
X4_CELL = {"name": "dlrm-random-x4.hybrid-staged", "config": "dlrm-random-x4",
           "traffic": "hybrid-staged", "chips": 4,
           "why": "the hybrid strategy across four chips"}
STREAM_CELL = {"name": "dlrm-random.stream-shuffle", "config": "dlrm-random",
               "traffic": "stream-shuffle", "chips": 1,
               "why": "a shuffling host loader through fit's per-batch loop"}
LEFT_OUT_CELLS = [STREAM_CELL, X4_CELL]


def _layer(name, unit, source, layer, moves, cell):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves, "workloads": [cell["name"]]}


STREAM_RATE = {"name": "stream_samples_per_s", "unit": "samples/s",
               "better": "higher", "bound": 0.06, "source": "host_clock",
               "workloads": [STREAM_CELL["name"]]}
LEFT_OUT_METRICS = [
    _layer("collective_share_pct", "%", "device_trace", "parallel",
           "samples_per_s", X4_CELL),
    _layer("device_idle_pct.stream", "%", "device_trace", "device",
           STREAM_RATE["name"], STREAM_CELL),
    _layer("busy_us_per_step.stream", "us", "device_trace", "ops / kernels",
           STREAM_RATE["name"], STREAM_CELL),
    _layer("dispatch_ms_per_step.stream", "ms", "program_span", "trainer",
           STREAM_RATE["name"], STREAM_CELL),
    _layer("data_wait_ms_per_step.stream", "ms", "program_span", "trainer",
           STREAM_RATE["name"], STREAM_CELL)]


def test_every_metric_named_has_its_reader_file():
    files = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmarks/layer_metrics")) if f.endswith(".py")}
    assert files == ({m["name"] for m in BENCH["per_layer"]}
                     | {m["name"] for m in LEFT_OUT_METRICS})


def test_the_runner_refuses_a_cpu(capsys):
    rc = run.main(["--workload", "dlrm-random.staged-uniform", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "refusing to run" in out.err
    assert '"correct"' not in out.out


# --------------------------------------------------- the dry rehearsal
@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's worth of benchmark in ``tmp_path``: the real files,
    with ``tiny.json`` laid over every configuration and traffic file."""
    tiny = json.load(open(os.path.join(HERE, "tiny.json")))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in (tmp_path / "benchmarks/configs").iterdir():
        cfg = json.loads(f.read_text())
        cfg["model"].update(tiny["model"])
        cfg["ffconfig"].update(tiny["ffconfig"])
        f.write_text(json.dumps(cfg))
    for f in (tmp_path / "benchmarks/traffic").iterdir():
        mix = json.loads(f.read_text())
        mix.update(tiny["traffic"])
        f.write_text(json.dumps(mix))
    # the cells left out, added back the way a later PR will: as entries
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "dlrm-random-x4", "source": "see the file", "reduced": [],
        "file": "benchmarks/configs/dlrm-random-x4.json", "why": "hybrid"})
    bench["workloads"] += LEFT_OUT_CELLS
    bench["end_to_end"][0]["workloads"].append(X4_CELL["name"])
    bench["end_to_end"].append(STREAM_RATE)
    bench["per_layer"] += LEFT_OUT_METRICS
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]
                                      + LEFT_OUT_CELLS])
def test_rehearsal_of_each_cell_at_the_tiny_size(tiny_root, workload):
    cell = run.resolve(tiny_root, workload)
    result = run.measure(cell, 2 ** 31 + 11, 0.2, trace=False)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"  # and never a device metric


@pytest.mark.parametrize("fault", ["lost", "stray"])
def test_the_comparison_fails_on_a_lost_or_stray_update(tiny_root, fault):
    """``lost``: one named row keeps its old value (its update dropped);
    ``stray``: one row no batch named moves."""
    cell = run.resolve(tiny_root, "dlrm-random.stream-shuffle")
    config, mix = cell["config"], cell["traffic"]
    driver = run.load_file(cell["driver"])
    from benchmarks.models import dlrm as family

    def faulty(model, state, inputs, labels):
        before = np.array(model.get_weights(state, "emb", "embedding"))
        state, losses = driver.check_steps(model, state, inputs, labels)
        after = np.array(model.get_weights(state, "emb", "embedding"))
        row = int(inputs["sparse"][0, 0, 0, 0])
        if fault == "lost":
            assert not np.array_equal(after[0, row], before[0, row])
            after[0, row] = before[0, row]
        else:
            free = np.setdiff1d(np.arange(after.shape[1]),
                                inputs["sparse"][:, :, 0])[0]
            after[0, free] += 1e-3
        return model.set_weights(state, "emb", "embedding", after), losses

    for steps, want in ((driver.check_steps, True), (faulty, False)):
        model, state = family.build(config, mix["batch"], 5, None)
        ok, report, _ = family.check(config, mix, model, state, 5, steps,
                                     driver.CHECK_BATCHES)
        assert ok is want, report
    key = "rows_over_max" if fault == "lost" else "moved_untouched"
    assert report[key] == 1


def test_a_new_cell_and_metric_are_new_files_and_entries_alone(tiny_root):
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "dlrm-random.made-up", "config": "dlrm-random",
        "traffic": "made-up", "chips": 1, "why": "shows that a cell is data"})
    bench["end_to_end"][0]["workloads"].append("dlrm-random.made-up")
    bench["per_layer"].append({
        "name": "made_up_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "samples_per_s", "workloads": ["dlrm-random.made-up"]})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    bdir = os.path.join(tiny_root, "benchmarks")
    mix = json.load(open(os.path.join(bdir, "traffic/staged-zipf.json")))
    mix["ids"]["a"] = 1.2
    with open(os.path.join(bdir, "traffic/made-up.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "layer_metrics/made_up_steps.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['window']['steps']\n")
    cell = run.resolve(tiny_root, "dlrm-random.made-up")
    assert cell["traffic"]["ids"] == {"dist": "zipf", "a": 1.2}
    assert "made_up_steps" in [m["name"] for m in cell["per_layer"]]
    reader = run.load_file(cell["readers"]["made_up_steps"])
    assert reader.read({"window": {"steps": 7}}) == 7
    assert run.measure(cell, 3, 0.1, trace=False)["correct"] is True


# ------------------------------- the staged window is fixed work (PR 28)
STAGED_CELLS = [w["name"] for w in BENCH["workloads"] + LEFT_OUT_CELLS
                if json.load(open(os.path.join(
                    ROOT, "benchmarks/traffic", w["traffic"] + ".json")))
                ["driver"] == "staged"]
STAGED_FILES = sorted(
    f for f in os.listdir(os.path.join(ROOT, "benchmarks/traffic"))
    if json.load(open(os.path.join(ROOT, "benchmarks/traffic", f)))
    .get("driver") == "staged")


def _steps_a_dispatch(cell):
    return cell["traffic"]["epochs_per_dispatch"] * cell["traffic"]["batches"]


def _line(out: str, head: str) -> str:
    (line,) = [x for x in out.splitlines() if x.startswith(head)]
    return line


@pytest.mark.parametrize("workload", STAGED_CELLS)
def test_correct_does_not_depend_on_seconds(tiny_root, workload, capsys):
    """The same seed under ``--seconds`` 5 and 50: the same count of
    dispatches, so the same ``attempted``, the same ``state.step`` under
    the comparison and the same report, number for number."""
    cell = run.resolve(tiny_root, workload)
    assert cell["traffic"]["dispatches"] == 4
    seen = []
    for seconds in (5, 50):
        result = run.measure(cell, 2 ** 31 + 28, seconds, trace=False)
        out = capsys.readouterr()
        seen.append((result["attempted"], result["correct"],
                     result["compared"], _line(out.out, "reference: ")))
        assert " 4 of 4 dispatches;" in _line(out.out, "window: ")
        assert list(result)[-1] == "compared"
        assert out.err.strip().splitlines()[-1].startswith("compared ")
    assert seen[0] == seen[1]
    attempted, correct, compared, line = seen[0]
    assert correct is True and attempted == 4 * _steps_a_dispatch(cell)
    # two warm-up dispatches, then the window: where the comparison starts
    assert compared["check_from_step"] == [6 * _steps_a_dispatch(cell)] * 2
    assert f"agrees from state.step {6 * _steps_a_dispatch(cell)} " in line
    assert all(limit is not None and value <= limit
               for value, limit in compared.values())


def test_seconds_too_short_stops_the_window_early_and_says_so(tiny_root,
                                                              capsys):
    cell = run.resolve(tiny_root, "dlrm-random.staged-uniform")
    result = run.measure(cell, 7, 0.0, trace=False)
    line = _line(capsys.readouterr().out, "window: ")
    # the ceiling is read when a dispatch completes, one more in flight
    assert " 2 of 4 dispatches (STOPPED SHORT" in line
    assert result["attempted"] == 2 * _steps_a_dispatch(cell)
    assert result["correct"] is True  # trained less: the safe side
    assert result["compared"]["check_from_step"] == [
        4 * _steps_a_dispatch(cell), 6 * _steps_a_dispatch(cell)]


@pytest.mark.parametrize("limit", [1, 3])
def test_the_traced_window_keeps_its_own_count(tiny_root, limit):
    cell = run.resolve(tiny_root, "dlrm-random.staged-zipf")
    config, mix = cell["config"], cell["traffic"]
    driver = run.load_file(cell["driver"])
    from benchmarks.models import dlrm as family
    model, state = family.build(config, mix["batch"], 3, None)
    ctx = driver.prepare(model, state, family.make_dataset(config, mix, 3),
                         mix, 3)
    window = driver.run_window(ctx, 50, limit=limit)
    assert window["steps"] == limit * _steps_a_dispatch(cell)
    assert len(window["dispatch_walls_s"]) == limit \
        == window["dispatches_wanted"]
    assert int(ctx["state"].step) == (2 + limit) * _steps_a_dispatch(cell)


@pytest.mark.parametrize("value", ["missing", 0, -3, True, 2.5, "24"])
def test_a_staged_traffic_file_must_name_its_dispatches(value):
    driver = run.load_file(os.path.join(ROOT, "benchmarks/drivers/staged.py"))
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic/staged-uniform.json")))
    assert mix.pop("dispatches") >= 1
    if value != "missing":
        mix["dispatches"] = value
    # before the model or the data are looked at: nothing has compiled
    with pytest.raises(KeyError, match='"dispatches"'):
        driver.prepare(None, None, None, mix, 0)
    with pytest.raises(KeyError, match='"dispatches"'):
        driver.run_window({"model": None, "staged": None, "traffic": mix}, 1)


@pytest.mark.parametrize("name", STAGED_FILES)
def test_every_staged_traffic_file_fixes_its_window(name):
    mix = json.load(open(os.path.join(ROOT, "benchmarks/traffic", name)))
    count = mix["dispatches"]
    assert isinstance(count, int) and count >= 1
    assert count >= mix["traced_units"]


@pytest.mark.parametrize("traffic", ["staged-uniform", "staged-zipf"])
def test_the_two_cells_compare_at_the_distance_their_limits_were_read_at(
        traffic):
    """(2 warm-up + 24) dispatches x 8 epochs x 512 batches: PERF.md
    section 4 has the curve of the comparison's error against it.  (A
    cell a later PR adds fixes its own distance in its own file.)"""
    mix = json.load(open(os.path.join(ROOT, "benchmarks/traffic",
                                      traffic + ".json")))
    assert (2 + mix["dispatches"]) * mix["epochs_per_dispatch"] \
        * mix["batches"] == 106_496
    (cell,) = [w for w in BENCH["workloads"] if w["traffic"] == traffic]
    assert "24 dispatches of 8 epochs" in cell["why"]


# ------------------- a whole run with the timed path broken underneath
def _planted(fault: str, real):
    """``check_steps`` with one fault planted in the path it times."""
    import jax
    import jax.numpy as jnp

    def check_steps(model, state, inputs, labels):
        if fault == "state_unchanged":
            kept = jax.tree_util.tree_map(jnp.copy, state)  # state is donated
            _, losses = real(model, state, inputs, labels)
            return kept, losses
        if fault == "half_batch":
            half = labels.shape[1] // 2
            inputs = {k: v[:, :half] for k, v in inputs.items()}
            labels = labels[:, :half]
        return real(model, state, inputs, labels)

    return check_steps


@pytest.mark.parametrize("fault,fails", [
    ("none", ()),
    ("state_unchanged", ("row_err_max", "row_err_median", "mlp_update_err")),
    ("half_batch", ("row_err_max", "mlp_update_err"))])
def test_a_run_with_the_timed_path_broken_is_not_correct(tiny_root, fault,
                                                         fails, capsys):
    """``run.measure`` from end to end (all but its look for a chip)
    over steps that return the state unchanged, or leave half of every
    batch out and take the mean over the rest: ``correct`` is false, and
    the numbers printed beside their limits say by what."""
    cell = run.resolve(tiny_root, "dlrm-random.staged-uniform")
    real = run.load_file(cell["driver"]).check_steps
    result = run.measure(cell, 2 ** 31 + 5, 50, trace=False,
                         check_steps=_planted(fault, real))
    err = capsys.readouterr().err
    over = {name for name, (value, limit) in result["compared"].items()
            if value > limit}
    assert result["correct"] is (fault == "none"), result["compared"]
    assert over >= set(fails) and (fails or not over), result["compared"]
    assert all(f"{n} {result['compared'][n][0]} " in err for n in over)


@pytest.mark.parametrize("seed", [1, 3, 2 ** 31 + 7])
def test_the_control_one_precision_down_is_not_correct(tiny_root, seed):
    """The reference in the program's place with bfloat16 operands, where
    ``tiny.json`` states float32 (``run.py --control 1`` on the chip:
    float8 where the configuration states bfloat16).  At this size only
    the widest row gap tells the two apart, and on some seeds (2 is one:
    0.40) not even that: the limits were read at full width on the chip,
    and PERF.md section 4 has the control's readings there."""
    from benchmarks.models import dlrm as family
    cell = run.resolve(tiny_root, "dlrm-random.staged-uniform")
    assert family.LOWER[cell["config"]["ffconfig"]["compute_dtype"]] \
        == "bfloat16"
    result = run.measure(cell, seed, 50, trace=False,
                         check_steps=family.control_steps(cell["config"]))
    assert result["correct"] is False
    value, limit = result["compared"]["row_err_max"]
    assert value > limit
    # a sound run at this precision agrees to the bit (0.0 everywhere)
    assert result["compared"]["row_err_median"][0] > 0
