"""``benchmarks/lib/phases.py`` and the six per-phase readers, on the
CPU: a synthetic reduced trace joined with a synthetic map (the sum
rule, the ambiguous-name rule, ``phase_attributed_pct``), what a reader
does on a program without the instrument or a window that named no
program, and the join with the real map of a tiny staged window."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import phases  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

#: instruction -> (self us, phase); 1000 us busy, 40 of them between ops
OPS = {
    "fusion.148": (300.0, "ff.step.model.bwd"),
    "fusion.126": (200.0, "ff.step.model"),
    "fusion.9": (10.0, "ff.step.metrics"),
    "multiply_subtract_fusion.37": (50.0, "ff.step.dense_update"),
    "fusion.5": (120.0, "ff.step.gather"),
    "fusion.2": (80.0, "ff.step.row_update"),
    "dynamic_update_slice.95": (60.0, "ff.ladder"),
    "fusion.77": (40.0, "ff.ladder.fetch"),
    "sort.3": (30.0, "ff.cache.prologue"),
    "train_epochs.1": (20.0, "ff.cache.epilogue"),
    "while.66": (25.0, "unattributed"),
    "fusion.400": (15.0, "ff.something.new"),   # a scope no group holds
    "copy.1": (10.0, None),                     # not in the map at all
}
WANT = {"cache_us_per_step": 5.0, "ladder_us_per_step": 10.0,
        "embedding_us_per_step": 20.0, "mlp_us_per_step": 51.0,
        "dense_update_us_per_step": 5.0, "phase_attributed_pct": 91.0}
GROUP_OF = {"cache_us_per_step": "cache", "ladder_us_per_step": "ladder",
            "embedding_us_per_step": "embedding", "mlp_us_per_step": "mlp",
            "dense_update_us_per_step": "dense_update"}


def _ctx(events=({"type": "program", "name": "train_epochs#1"},)):
    return {"trace": {"self_us": {k: us for k, (us, _p) in OPS.items()},
                      "busy_us": 1000.0},
            "window": {"steps": 10}, "events": list(events)}


@pytest.fixture()
def synthetic_map(monkeypatch):
    from dlrm_flexflow_tpu import profiling

    maps = {"train_epochs#1": {k: p for k, (_us, p) in OPS.items()
                               if p is not None}}
    monkeypatch.setattr(profiling, "program_phases", maps.__getitem__,
                        raising=False)
    return maps


def _reader(name):
    return run.load_file(os.path.join(ROOT, "benchmarks/layer_metrics",
                                      name + ".py"))


def test_every_phase_has_one_group_and_the_six_add_up_to_busy():
    assert phases.group_of("ff.cache.prologue") == "cache"
    assert phases.group_of("ff.ladder") == "ladder"
    assert phases.group_of("ff.ladder.writeback") == "ladder"
    assert phases.group_of("ff.step.model.bwd") == "mlp"
    assert phases.group_of("ff.step.row_update") == "embedding"
    assert phases.group_of("ff.step.dense_update") == "dense_update"
    assert phases.group_of("ff.step") is None
    assert phases.group_of("ff.ladderx") is None
    assert phases.group_of("unattributed") is None
    parts = phases.split({k: us for k, (us, _p) in OPS.items()},
                         {k: p for k, (_us, p) in OPS.items() if p}, 1000.0)
    assert parts == {"cache": 50.0, "ladder": 100.0, "embedding": 200.0,
                     "mlp": 510.0, "dense_update": 50.0,
                     "unattributed": 90.0}   # 25 + 15 + 10 + the 40 between
    assert sum(parts.values()) == 1000.0


def test_a_name_two_programs_give_different_phases_is_unattributed():
    merged = phases.merge_maps([
        {"fusion.5": "ff.step.gather", "fusion.2": "ff.step.row_update"},
        {"fusion.5": "ff.step.model", "fusion.2": "ff.step.row_update",
         "fusion.9": "ff.ladder"}])
    assert merged == {"fusion.5": "unattributed",
                      "fusion.2": "ff.step.row_update",
                      "fusion.9": "ff.ladder"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_synthetic_window(synthetic_map, name, capsys):
    assert _reader(name).read(_ctx()) == pytest.approx(WANT[name])
    if name == "phase_attributed_pct":
        assert "fusion.148 0.30 ms ff.step.model.bwd" in capsys.readouterr().out


def test_two_programs_of_one_window_are_merged(synthetic_map):
    synthetic_map["train_step#2"] = {"fusion.5": "ff.step.model"}
    ctx = _ctx(events=[{"type": "program", "name": "train_epochs#1"},
                       {"type": "step"},
                       {"type": "program", "name": "train_step#2"},
                       {"type": "program", "name": "train_epochs#1"}])
    assert _reader("embedding_us_per_step").read(ctx) \
        == pytest.approx(WANT["embedding_us_per_step"] - 12.0)
    assert _reader("mlp_us_per_step").read(ctx) \
        == pytest.approx(WANT["mlp_us_per_step"])
    assert _reader("phase_attributed_pct").read(ctx) == pytest.approx(79.0)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_says_nothing_on_a_program_without_the_instrument(
        monkeypatch, name):
    from dlrm_flexflow_tpu import profiling

    monkeypatch.delattr(profiling, "program_phases")
    assert _reader(name).read(_ctx()) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_raises_where_the_map_cannot_be_had(synthetic_map, name):
    with pytest.raises(RuntimeError, match="named no program"):
        _reader(name).read(_ctx(events=[{"type": "step"}]))
    with pytest.raises(KeyError):   # a program nobody noted
        _reader(name).read(_ctx(events=[{"type": "program",
                                         "name": "train_epochs#9"}]))


def test_the_entries_are_what_the_issue_named():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    cells = [w["name"] for w in BENCH["workloads"]
             if w["traffic"].startswith("staged-")]
    for name, group in GROUP_OF.items():
        assert group in phases.GROUPS
        assert entries[name]["unit"] == "us"
        assert entries[name]["better"] == "lower"
    assert entries["phase_attributed_pct"]["unit"] == "%"
    assert entries["phase_attributed_pct"]["better"] == "higher"
    for name in WANT:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "samples_per_s"
        assert entries[name]["workloads"] == cells
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == [
        "cache_us_per_step", "ladder_us_per_step", "embedding_us_per_step",
        "mlp_us_per_step", "dense_update_us_per_step",
        "phase_attributed_pct"]


def test_a_tiny_staged_window_names_its_program_and_the_map_joins(tmp_path):
    """The staged driver's own window under telemetry, as ``run.py``'s
    traced run opens it: the events name ``train_epochs``, and its map
    puts an instruction in every group."""
    import shutil

    from benchmarks.models import dlrm as family
    from dlrm_flexflow_tpu.telemetry import event_log

    tiny = json.load(open(os.path.join(HERE, "tiny.json")))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    cell = run.resolve(str(tmp_path), "dlrm-random.staged-uniform")
    config, traffic = cell["config"], dict(cell["traffic"])
    config["model"].update(tiny["model"],
                           embedding_size=[50_000] * 4)
    config["ffconfig"].update(tiny["ffconfig"], epoch_row_cache="on",
                              packed_tables="on", epoch_cache_regions="on")
    traffic.update(tiny["traffic"], epochs_per_dispatch=2)
    driver = run.load_file(cell["driver"])
    model, state = family.build(config, traffic["batch"], 7, None)
    ctx = driver.prepare(model, state,
                         family.make_dataset(config, traffic, 7), traffic, 7)
    with event_log(ring=1 << 16) as log:
        window = driver.run_window(ctx, 0.05, limit=traffic["traced_units"])
        events = log.events()
    assert window["steps"] == 2 * traffic["batches"]
    assert [e["fn"] for e in events if e["type"] == "program"] \
        == ["train_epochs"]
    by_instruction = phases.window_phases(events)
    groups = {phases.group_of(p) for p in by_instruction.values()}
    assert groups == set(phases.GROUPS) | {None}
    # joined with a trace that holds one slice per instruction, nothing
    # but the unnamed instructions is left over
    self_us = dict.fromkeys(by_instruction, 1.0)
    parts = phases.split(self_us, by_instruction, float(len(self_us)))
    unnamed = sum(1 for p in by_instruction.values()
                  if phases.group_of(p) is None)
    assert parts["unattributed"] == unnamed
    assert all(parts[g] > 0 for g in phases.GROUPS)
