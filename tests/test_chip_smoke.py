"""chip_smoke.py, rehearsed on the CPU.

The chip budget depends on the smoke not tripping over itself there, so
its legs run here as functions at a tiny size on the 8-device CPU mesh:
the modes "auto" picks on a chip forced "on", the Pallas kernels in
interpret mode, no device track to trace.  Also: the script refuses a
CPU, and the compile-cache helper leaves a cache placed from outside
alone.
"""

import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from dlrm_flexflow_tpu import entrypoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="a dozen tiny-model compiles plus a subprocess: ~30 s on two "
           "cores — skipped on 1-core containers so tier-1 fits its 870 s "
           "window")

#: the DLRM CLI's flags at a toy size: 4 x 8192-row tables, feature 64
#: (so storage packs 2 rows per 128 lanes, as at full width), 32 batches
TINY_ARGV = ("-b", "16", "--wd", "0", "--data-size", str(32 * 16),
             "--arch-embedding-size", "8192-8192-8192-8192",
             "--arch-sparse-feature-size", "64",
             "--arch-mlp-bot", "8-16-64", "--arch-mlp-top", "320-32-1")
FORCED = {"epoch_row_cache": "on", "packed_tables": "on"}


def test_refuses_a_cpu_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout      # names what it found
    assert "'cpu', not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_one_chip_legs_tiny():
    losses = chip_smoke.one_chip(
        TINY_ARGV, auto_overrides=FORCED, interpret=True, trace=False,
        fence_calls=1,
        kernel_kwargs=dict(rows=256, tables=2, set_rows=32, upd_rows=32,
                           bag_batch=8,
                           fused_shapes=chip_smoke.FUSED_SHAPES[:1]))
    assert len(losses) == (chip_smoke.FIT_EPOCHS + chip_smoke.STEPS + 1)


def test_four_chip_legs_tiny():
    results = chip_smoke.four_chip(TINY_ARGV, overrides=FORCED)
    assert set(results) == {"auto", "allgather", "all_to_all", "dp", "one"}


def test_kernel_table_fails_on_a_gated_kernel_that_disagrees():
    row = {"kernel": "k", "shape": "s", "gated": True, "compiled": True,
           "matches": False, "note": "within 1e-6"}
    with pytest.raises(AssertionError, match="its gate can select"):
        chip_smoke.print_kernel_table([row])
    # a refusal the gate excludes is reported, not fatal
    chip_smoke.print_kernel_table(
        [dict(row, gated=False, compiled=False, matches=None,
              note="MosaicError: nope")])


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert entrypoint.enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout_when_unset(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert entrypoint.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_require_tpu_lets_a_requested_cpu_through(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert entrypoint.require_tpu(allow_requested_cpu=True)["platform"] \
        == "cpu"
    with pytest.raises(SystemExit):
        entrypoint.require_tpu()
    monkeypatch.delenv("JAX_PLATFORMS")  # a silent fallback is refused
    with pytest.raises(SystemExit):
        entrypoint.require_tpu(allow_requested_cpu=True)
    assert "platform=cpu" in capsys.readouterr().out
