"""Unit tests for profiling.parse_device_trace: synthetic traces, and
one recorded on a TPU v5e.

The device trace's "XLA Ops" track NESTS (a scan's `while` slice spans
the ops of its body), so raw-summing slice durations overcounts; busy
time comes from the "XLA Modules" track of a ``/device:TPU:<n>``
process, per-op time is SELF time.  These tests pin that accounting —
and that nothing is substituted where the process or the track is
missing: the parser raises (reference analogue: per-op cudaEvent timing,
src/ops/linear.cu:499-531 never double-counts nested kernels).
"""

import gzip
import json
import os

import pytest

from dlrm_flexflow_tpu.profiling import parse_device_trace


def _write_trace(tmpdir, events):
    path = os.path.join(tmpdir, "t.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def _meta(pid, name, tid=None, tname=None):
    out = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}]
    if tid is not None:
        out.append({"ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})
    return out


def _slice(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur}


class TestParseDeviceTrace:
    def test_modules_track_is_busy_ops_are_self_times(self, tmp_path):
        # device pid 1: Modules track (tid 10) + Ops track (tid 20)
        # with a nesting while(0..100) containing fusion(10..40) and
        # fusion(50..90): raw ops sum = 100+30+40 = 170 us, but busy
        # must be the module total (100) and per-op SELF times
        # while=30, fusion=70.
        ev = (_meta(1, "/device:TPU:0", 10, "XLA Modules")
              + _meta(1, "/device:TPU:0", 20, "XLA Ops")
              + [_slice(1, 10, "jit_step", 0, 100),
                 _slice(1, 20, "while", 0, 100),
                 _slice(1, 20, "fusion", 10, 30),
                 _slice(1, 20, "fusion", 50, 40)])
        _write_trace(tmp_path, ev)
        _p, _pn, tot, busy_ms = parse_device_trace(str(tmp_path))
        assert busy_ms == pytest.approx(0.100)
        assert tot["fusion"] == pytest.approx(70.0)
        assert tot["while"] == pytest.approx(30.0)

    def test_no_modules_track_raises(self, tmp_path):
        # Ops but NO "XLA Modules" thread: busy is not guessed from the
        # ops (their raw sum double-counts, their self-time sum misses
        # the gaps inside a module).
        ev = (_meta(1, "/device:TPU:0", 20, "XLA Ops")
              + [_slice(1, 20, "while", 0, 100),
                 _slice(1, 20, "fusion", 10, 30),
                 _slice(1, 20, "fusion", 50, 40)])
        _write_trace(tmp_path, ev)
        with pytest.raises(ValueError, match="XLA Modules"):
            parse_device_trace(str(tmp_path))

    def test_no_thread_names_raises(self, tmp_path):
        ev = (_meta(1, "/device:TPU:0")
              + [_slice(1, 20, "fusion", 0, 30),
                 _slice(1, 20, "copy", 40, 20)])
        _write_trace(tmp_path, ev)
        with pytest.raises(ValueError, match="XLA Modules"):
            parse_device_trace(str(tmp_path))

    def test_no_tpu_process_raises(self, tmp_path):
        # what a CPU run's trace looks like; "anything that is not the
        # host" is not a device
        ev = (_meta(2, "/host:CPU", 5, "python")
              + _meta(3, "some plugin", 10, "XLA Modules")
              + [_slice(2, 5, "hostwork", 0, 1000),
                 _slice(3, 10, "jit_step", 0, 50)])
        _write_trace(tmp_path, ev)
        with pytest.raises(ValueError, match="/device:TPU:"):
            parse_device_trace(str(tmp_path))

    def test_several_chips_report_the_busiest(self, tmp_path):
        ev = []
        for pid, dur in ((1, 100), (2, 120)):
            ev += (_meta(pid, f"/device:TPU:{pid - 1}", 10, "XLA Modules")
                   + _meta(pid, f"/device:TPU:{pid - 1}", 20, "XLA Ops")
                   + [_slice(pid, 10, "jit_step", 0, dur),
                      _slice(pid, 20, "fusion", 0, dur - 10)])
        _write_trace(tmp_path, ev)
        _p, _pn, tot, busy_ms = parse_device_trace(str(tmp_path))
        assert busy_ms == pytest.approx(0.120)    # not 0.220
        assert tot == {"fusion": pytest.approx(110.0)}

    def test_modules_only_attributes_at_module_granularity(self, tmp_path):
        # Named Modules track but no Ops track: busy AND per-op totals
        # both come from the module slices (no double-count).
        ev = (_meta(1, "/device:TPU:0", 10, "XLA Modules")
              + [_slice(1, 10, "jit_step", 0, 100)])
        _write_trace(tmp_path, ev)
        _p, _pn, tot, busy_ms = parse_device_trace(str(tmp_path))
        assert busy_ms == pytest.approx(0.100)
        assert tot == {"jit_step": pytest.approx(100.0)}

    def test_named_but_unrecognized_tracks_raise(self, tmp_path):
        # Thread names exist but neither Ops nor Modules: tracks like
        # "Steps" mirror the same wall time, so summing across them
        # would double-count — the parser must refuse, not guess.
        ev = (_meta(1, "/device:TPU:0", 30, "Steps")
              + _meta(1, "/device:TPU:0", 40, "TensorFlow Name Scope")
              + [_slice(1, 30, "step0", 0, 100),
                 _slice(1, 40, "scope", 0, 100)])
        _write_trace(tmp_path, ev)
        with pytest.raises(ValueError):
            parse_device_trace(str(tmp_path))

    def test_host_slices_excluded(self, tmp_path):
        ev = (_meta(1, "/device:TPU:0", 10, "XLA Modules")
              + _meta(1, "/device:TPU:0", 20, "XLA Ops")
              + _meta(2, "host threads", 5, "python")
              + [_slice(1, 10, "jit_step", 0, 50),
                 _slice(1, 20, "fusion", 0, 50),
                 _slice(2, 5, "hostwork", 0, 1000)])
        _write_trace(tmp_path, ev)
        _p, _pn, tot, busy_ms = parse_device_trace(str(tmp_path))
        assert busy_ms == pytest.approx(0.050)
        assert "hostwork" not in tot


def test_recorded_v5e_trace(tmp_path):
    """One scanned 16-step ``train_epoch`` of the full-width DLRM on a
    TPU v5e (jax 0.9.0, PR 21's chip run): the names the parser keys on
    are the ones a real trace carries."""
    import shutil
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copy(os.path.join(here, "data", "v5e_train_epoch.trace.json.gz"),
                str(tmp_path))
    _p, pnames, tot, busy_ms = parse_device_trace(str(tmp_path))
    assert sorted(pnames.values()) == ["/device:TPU:0", "/host:CPU"]
    assert busy_ms == pytest.approx(2.5775, abs=1e-3)
    # self times never exceed the module's wall; the nested raw sum did
    assert sum(tot.values()) / 1e3 <= busy_ms + 1e-6
    assert "while.66" in tot and "fusion.209" in tot
