"""Fused embedding-bag->interaction kernel + quantized serving tables
(ops/pallas_fused_interact.py, ops/fused_interact.py, ops/quantized.py,
ops/kernel_costs.py): interpret-mode kernel-vs-emitter bit-exactness,
dropped-id parity, the unified dispatch cost model, per-bucket serving
latency stats, the regress latency gate, and the tier-1 smoke matrix
(scripts/check_kernels.py)."""

import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
from dlrm_flexflow_tpu.ops.pallas_fused_interact import (
    fused_interact_pallas, fused_interact_ref, interact_width,
    mask_local_ids, pool_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROW_COUNTS = [40, 24, 32]
OFFSETS = np.concatenate([[0], np.cumsum(ROW_COUNTS[:-1])])
D = 16


def _table_bottom(rng, bsz):
    total = int(sum(ROW_COUNTS))
    table = jnp.asarray(rng.standard_normal((total, D)).astype(np.float32))
    bottom = jnp.asarray(rng.standard_normal((bsz, D)).astype(np.float32))
    return table, bottom


class TestFusedKernelInterpret:
    """Kernel vs emitter reference, interpret mode, both jitted (the
    production paths always run jitted; eager XLA may fold a divide
    differently)."""

    @pytest.mark.parametrize("interact", ["cat", "dot"])
    @pytest.mark.parametrize("aggr", ["sum", "avg"])
    def test_bit_exact_vs_emitter(self, interact, aggr):
        bsz = 13  # odd batch: a padded block AND full blocks in one run
        rng = np.random.default_rng(0)
        table, bottom = _table_bottom(rng, bsz)
        # narrow id range -> guaranteed duplicates, incl. within a bag
        local = rng.integers(0, 10, size=(bsz, len(ROW_COUNTS), 3))
        gids = mask_local_ids(jnp.asarray(local), OFFSETS, ROW_COUNTS)
        kf = jax.jit(functools.partial(fused_interact_pallas,
                                       interact=interact, aggr=aggr,
                                       interpret=True))
        rf = jax.jit(functools.partial(fused_interact_ref,
                                       interact=interact, aggr=aggr))
        k = np.asarray(kf(table, gids, bottom))
        r = np.asarray(rf(table, gids, bottom))
        assert k.shape == (bsz, interact_width(interact, len(ROW_COUNTS),
                                               D, D))
        np.testing.assert_array_equal(k, r)

    def test_negative_and_oob_ids_dropped_in_both_paths(self):
        """The regression the PR-1 row-set fix asked for: negative ids
        (and >= table-rows ids) must be DROPPED — exact 0.0
        contribution — by the kernel AND the emitter path alike."""
        rng = np.random.default_rng(1)
        bsz, t, bag = 8, len(ROW_COUNTS), 2
        table, bottom = _table_bottom(rng, bsz)
        local = rng.integers(0, 12, size=(bsz, t, bag))
        local[0, 0, 0] = -1
        local[1, 1, :] = -3
        local[2, 2, 1] = ROW_COUNTS[2]            # local overflow
        local[3, 0, 0] = np.iinfo(np.int32).min
        gids = mask_local_ids(jnp.asarray(local), OFFSETS, ROW_COUNTS)
        kf = jax.jit(functools.partial(fused_interact_pallas,
                                       interact="cat", aggr="sum",
                                       interpret=True))
        rf = jax.jit(functools.partial(fused_interact_ref,
                                       interact="cat", aggr="sum"))
        k = np.asarray(kf(table, gids, bottom))
        np.testing.assert_array_equal(k, np.asarray(rf(table, gids,
                                                       bottom)))
        # hand-built expectation
        rows = np.zeros((bsz, t, bag, D), np.float32)
        for b in range(bsz):
            for ti in range(t):
                for j in range(bag):
                    li = local[b, ti, j]
                    if 0 <= li < ROW_COUNTS[ti]:
                        rows[b, ti, j] = np.asarray(table)[OFFSETS[ti] + li]
        want = np.concatenate(
            [np.asarray(bottom), rows.sum(axis=2).reshape(bsz, -1)], axis=1)
        np.testing.assert_allclose(k, want, rtol=1e-6, atol=1e-6)

    def test_mask_local_ids(self):
        # (B=2, T=2, bag=2); tables: 40 rows at offset 0, 24 at 40
        idx = jnp.asarray([[[0, -1], [5, 24]], [[39, 2], [-9, 0]]])
        gids = mask_local_ids(idx, OFFSETS[:2], ROW_COUNTS[:2])
        np.testing.assert_array_equal(
            np.asarray(gids),
            [[[0, -1], [45, -1]], [[39, 2], [-1, 40]]])

    def test_dot_bf16_compute_matches_batchmatmul_cast(self):
        """compute_dtype='bfloat16' must change the dot numerics the
        SAME way in kernel and emitter (BatchMatmul's bf16 operand
        cast with f32 accumulation) — toggling fusion never changes
        numerics at either compute precision."""
        rng = np.random.default_rng(5)
        table, bottom = _table_bottom(rng, 8)
        local = rng.integers(0, 10, size=(8, len(ROW_COUNTS), 2))
        gids = mask_local_ids(jnp.asarray(local), OFFSETS, ROW_COUNTS)
        outs = {}
        for cd in (None, "bfloat16"):
            kf = jax.jit(functools.partial(
                fused_interact_pallas, interact="dot", aggr="sum",
                interpret=True, compute_dtype=cd))
            rf = jax.jit(functools.partial(
                fused_interact_ref, interact="dot", aggr="sum",
                compute_dtype=cd))
            k = np.asarray(kf(table, gids, bottom))
            np.testing.assert_array_equal(
                k, np.asarray(rf(table, gids, bottom)))
            assert k.dtype == np.float32  # f32 accumulation/output
            outs[cd] = k
        # the cast actually engaged (bf16 products differ from f32)
        assert not np.array_equal(outs[None], outs["bfloat16"])

    def test_empty_bag_pools_to_zero(self):
        rows = jnp.zeros((4, 3, 0, D), jnp.float32)
        for aggr in ("sum", "avg"):  # avg of nothing must not be NaN
            pooled = np.asarray(pool_rows(rows, aggr, jnp.float32))
            assert pooled.shape == (4, 3, D)
            np.testing.assert_array_equal(pooled, 0.0)


class TestFusedOpTraining:
    """The FusedEmbedInteract op trains through the row-sparse fast
    path (rows__ injection) like every embedding-family op."""

    @pytest.mark.parametrize("interact", ["cat", "dot"])
    def test_train_epoch_and_registration(self, interact):
        t, bag, b = len(ROW_COUNTS), 2, 8
        top_in = D + t * D if interact == "cat" else D + (t + 1) ** 2
        cfg = DLRMConfig(sparse_feature_size=D,
                         embedding_size=list(ROW_COUNTS),
                         embedding_bag_size=bag, mlp_bot=[6, 8, D],
                         mlp_top=[top_in, 8, 1],
                         arch_interaction_op=interact,
                         fused_interaction="on")
        m = build_dlrm(cfg, ff.FFConfig(batch_size=b))
        m.compile(optimizer=ff.SGDOptimizer(0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        assert m._sparse_emb_ops == ["emb"]  # sparse fast path engaged
        st = m.init(seed=0)
        rng = np.random.default_rng(0)
        inputs = {"dense": rng.standard_normal((4, b, 6)).astype(np.float32),
                  "sparse": np.stack(
                      [rng.integers(0, r, size=(4, b, bag), dtype=np.int64)
                       for r in ROW_COUNTS], axis=2)}
        labels = rng.integers(0, 2, size=(4, b, 1)).astype(np.float32)
        # snapshot BEFORE training: the epoch program donates the state
        # a dropped (negative) id rides along: the masked rows__ path
        # must pool it as 0.0 AND its zero row-grad must leave the
        # clip-addressed foreign row (offsets[1] - 1 = last row of
        # table 0) untouched by training
        inputs["sparse"][:, :, 1, 0] = -1
        foreign_row = ROW_COUNTS[0] - 1  # flat id of local -1 in table 1
        # keep table 0's own ids off that row so only the dropped id
        # could ever touch it
        inputs["sparse"][:, :, 0, :] %= foreign_row
        t0 = np.asarray(st.params["emb"]["embedding"]).copy()
        st2, _ = m.train_epoch(st, inputs, labels)
        t1 = np.asarray(st2.params["emb"]["embedding"])
        assert not np.array_equal(t0, t1)  # tables actually trained
        assert np.isfinite(t1).all()
        np.testing.assert_array_equal(t0[foreign_row], t1[foreign_row])


class TestDispatchCostModel:
    def test_row_set_wins_unified_and_anchored(self):
        from dlrm_flexflow_tpu.ops import kernel_costs as kc
        from dlrm_flexflow_tpu.ops import pallas_scatter
        assert pallas_scatter.row_set_wins is kc.row_set_wins
        assert kc.row_set_wins(4_000_000, 128, 8_192, 4)        # hybrid
        assert not kc.row_set_wins(804_024, 128, 26_624, 4)     # kaggle
        assert not kc.row_set_wins(4_000_000, 128, 1_048_576, 4)

    def test_fused_gate_regimes(self):
        from dlrm_flexflow_tpu.ops.kernel_costs import fused_interact_wins
        # smallest serving buckets: kernel (boundary-cost dominated)
        assert fused_interact_wins(1, 8, 1, 64, 4, "cat")
        assert fused_interact_wins(8, 8, 1, 64, 4, "dot")
        # training headline: emitter (gather-pipeline dominated), the
        # pallas_embedding bring-up measurement
        assert not fused_interact_wins(256, 8, 1, 64, 4, "cat")
        assert not fused_interact_wins(256, 26, 1, 16, 4, "dot")


    def test_eligibility_is_what_the_chip_compiles(self):
        """PR 21's chip run (TPU v5e, jax 0.9.0): Mosaic accepts the
        forward kernel only for whole 128-lane rows and the backward
        kernel nowhere — so no compiled program may select them there.
        The interpreter keeps the looser rules these tests run under."""
        from dlrm_flexflow_tpu.ops.pallas_fused_interact import (
            bwd_kernel_eligible, kernel_eligible)
        assert not kernel_eligible(jnp.float32, 64, 1)
        assert kernel_eligible(jnp.float32, 128, 1)
        assert kernel_eligible(jnp.float32, 256, 4)
        assert not kernel_eligible(jnp.bfloat16, 128, 1)
        assert not kernel_eligible(jnp.float32, 128, 0)
        assert kernel_eligible(jnp.float32, 64, 1, interpret=True)
        assert not bwd_kernel_eligible(False)
        assert bwd_kernel_eligible(True)
        assert not bwd_kernel_eligible(True, "bfloat16")


class TestQuantizedTables:
    def test_int8_round_trip_error_bound(self):
        from dlrm_flexflow_tpu.ops.quantized import (dequant_rows,
                                                     quantize_table)
        rng = np.random.default_rng(3)
        table = rng.standard_normal((32, D)).astype(np.float32) * 3.0
        table[5] = 0.0  # all-zero row: scale must not divide by zero
        codes, scale = quantize_table(table, "int8", D)
        assert codes.dtype == np.int8 and scale.shape == (32, 1)
        ids = jnp.asarray(np.arange(32, dtype=np.int32))
        deq = np.asarray(dequant_rows(jnp.asarray(codes), jnp.asarray(scale),
                                      ids))
        # symmetric per-row quantization: error <= scale/2 per element
        bound = np.asarray(scale) / 2.0 + 1e-7
        assert (np.abs(deq - table) <= bound).all()
        np.testing.assert_array_equal(deq[5], 0.0)

    def test_bf16_mode_halves_storage(self):
        from dlrm_flexflow_tpu.ops.quantized import quantize_table
        table = np.random.default_rng(4).standard_normal(
            (16, D)).astype(np.float32)
        stored, scale = quantize_table(table, "bf16", D)
        assert scale is None
        assert np.dtype(stored.dtype).itemsize == 2
        np.testing.assert_allclose(stored.astype(np.float32), table,
                                   rtol=1e-2, atol=1e-2)

    def test_stacked_quantized_stays_in_table(self):
        """An invalid local id on the quantized flat path must clamp
        WITHIN its own table — a stray -1 must never pool the previous
        table's last row (the f32 vmap path wraps -1 / NaN-fills >= R
        per jnp.take; int8 codes cannot NaN-fill, so the quantized
        contract is in-table clamping), and valid ids must match the
        f32 path within quantization error."""
        from dlrm_flexflow_tpu.ops import StackedEmbedding
        from dlrm_flexflow_tpu.ops.quantized import (
            quantize_embedding_params)
        from dlrm_flexflow_tpu.tensor import Tensor
        ids_t = Tensor(shape=(2, 2, 2), dtype=np.int64, name="ids")
        op = StackedEmbedding("emb", ids_t, 2, 8, D)
        params = {"emb": op.init_params(jax.random.PRNGKey(0))}
        qparams, _ = quantize_embedding_params([op], params, "int8")
        valid = jnp.asarray([[[1, 0], [7, 2]], [[3, 3], [0, 7]]])
        f32 = np.asarray(op.forward(params["emb"], [valid])[0])
        q = np.asarray(op.forward(qparams["emb"], [valid])[0])
        np.testing.assert_allclose(q, f32, atol=1e-2)
        # invalid ids (-1 in table 1, ==R in table 0): identical to
        # the in-table clamped lookup, finite, never a foreign row
        bad = jnp.asarray([[[1, 0], [-1, 2]], [[8, 3], [0, 7]]])
        clamped = jnp.asarray([[[1, 0], [0, 2]], [[7, 3], [0, 7]]])
        q_bad = np.asarray(op.forward(qparams["emb"], [bad])[0])
        np.testing.assert_array_equal(
            q_bad, np.asarray(op.forward(qparams["emb"], [clamped])[0]))
        assert np.isfinite(q_bad).all()

    def test_unknown_mode_raises(self):
        from dlrm_flexflow_tpu.ops.quantized import (
            quantize_embedding_params, quantize_table)
        with pytest.raises(ValueError):
            quantize_table(np.zeros((4, 4), np.float32), "int4", 4)
        with pytest.raises(ValueError):
            quantize_embedding_params([], {}, "int4")


class TestBucketLatencyStats:
    def test_histograms_and_percentile(self):
        from dlrm_flexflow_tpu.serving import LatencyStats
        s = LatencyStats()
        for _ in range(99):
            s.record_dispatch(bucket=8, lat_us=200.0)   # <= 250 edge
        s.record_dispatch(bucket=8, lat_us=90_000.0)    # the tail
        s.record_dispatch(bucket=64, lat_us=400.0)
        h = s.bucket_histograms()
        assert set(h) == {8, 64}
        cum8, sum8, n8 = h[8]
        assert n8 == 100 and cum8[-1] == 100
        assert sum8 == pytest.approx(99 * 200.0 + 90_000.0)
        p50 = s.bucket_percentile(8, 50)
        assert 100.0 <= p50 <= 250.0
        p995 = s.bucket_percentile(8, 99.5)
        assert p995 > 50_000.0  # the tail slot
        assert s.bucket_percentile(1, 99) is None  # never dispatched

    def test_metrics_family_renders_labeled(self):
        from dlrm_flexflow_tpu.serving import LatencyStats
        from dlrm_flexflow_tpu.telemetry import metrics as tm
        s = LatencyStats()
        s.record_dispatch(bucket=4, lat_us=123.0)
        tm._live_stats.add(s)
        try:
            body = tm.REGISTRY.render()
        finally:
            tm._live_stats.discard(s)
        assert ('dlrm_serve_bucket_latency_us_bucket{bucket="4",'
                'le="250"} 1') in body
        assert 'dlrm_serve_bucket_latency_us_count{bucket="4"} 1' in body

    def test_fold_on_retire_keeps_counts(self):
        from dlrm_flexflow_tpu.serving import LatencyStats
        from dlrm_flexflow_tpu.telemetry import metrics as tm
        s = LatencyStats()
        s.record_dispatch(bucket=2, lat_us=99.0)
        with tm._retired_lock:
            before = dict(tm._retired_bucket_n)
            tm._fold_stats_locked(s)
            after = dict(tm._retired_bucket_n)
        assert after.get(2, 0) == before.get(2, 0) + 1
        # scrape still exposes the folded count (monotone contract)
        got = tm._bucket_latency_hists()
        assert got["2"][2] >= after[2]


class TestRegressLatencyGate:
    def test_lower_is_better_names(self):
        from dlrm_flexflow_tpu.telemetry.regress import lower_is_better
        assert lower_is_better("dlrm_serving_p99_ms")
        assert lower_is_better("serve_latency_us")
        assert not lower_is_better("dlrm_serving_qps")
        assert not lower_is_better("dlrm_synthetic_samples_per_sec")

    def test_latency_regresses_upward(self):
        from dlrm_flexflow_tpu.telemetry.regress import compare
        base = {"dlrm_serving_p99_ms": 10.0, "dlrm_serving_qps": 100.0}
        rows, reg = compare(base, {"dlrm_serving_p99_ms": 12.0,
                                   "dlrm_serving_qps": 100.0}, 5.0)
        assert [r[0] for r in reg] == ["dlrm_serving_p99_ms"]
        _, reg = compare(base, {"dlrm_serving_p99_ms": 7.0,
                                "dlrm_serving_qps": 80.0}, 5.0)
        assert [r[0] for r in reg] == ["dlrm_serving_qps"]

    def test_history_metric_field_preferred(self):
        from dlrm_flexflow_tpu.telemetry.regress import _history_metrics
        entries = [
            {"app": "dlrm_serving", "value": 500.0, "fenced": True},
            {"app": "dlrm_serving", "metric": "dlrm_serving_p99_ms",
             "value": 9.5, "fenced": True},
        ]
        got = _history_metrics(entries)
        assert got == {"dlrm_serving_qps": 500.0,
                       "dlrm_serving_p99_ms": 9.5}

    def test_quantized_entries_anchor_separately(self):
        from dlrm_flexflow_tpu.telemetry.regress import (_history_metrics,
                                                         lower_is_better)
        entries = [
            {"app": "dlrm_serving", "metric": "dlrm_serving_p99_ms",
             "quantize": "off", "value": 9.0, "fenced": True},
            {"app": "dlrm_serving", "metric": "dlrm_serving_p99_ms",
             "quantize": "int8", "value": 22.0, "fenced": True},
        ]
        got = _history_metrics(entries)
        # int8 must NOT overwrite the f32 anchor (different numerics)
        assert got == {"dlrm_serving_p99_ms": 9.0,
                       "dlrm_serving_p99_ms:quantize=int8": 22.0}
        assert lower_is_better("dlrm_serving_p99_ms:quantize=int8")
        # ...and a NEWER f32 entry must not sweep away the quantized
        # anchor either (the prefix-overwrite bug): both survive
        entries.append({"app": "dlrm_serving",
                        "metric": "dlrm_serving_p99_ms",
                        "quantize": "off", "value": 8.0, "fenced": True})
        got = _history_metrics(entries)
        assert got == {"dlrm_serving_p99_ms": 8.0,
                       "dlrm_serving_p99_ms:quantize=int8": 22.0}
        # the largest-dispatched-bucket qualifier separates anchors the
        # same way (which bucket tops out is load-dependent)
        entries.append({"app": "dlrm_serving",
                        "metric": "dlrm_serving_p99_ms",
                        "quantize": "off", "bucket": 64, "value": 30.0,
                        "fenced": True})
        got = _history_metrics(entries)
        assert got["dlrm_serving_p99_ms:bucket=64"] == 30.0
        assert got["dlrm_serving_p99_ms"] == 8.0  # untouched


class TestCheckKernelsSmoke:
    def test_check_kernels_smoke(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "check_kernels.py")],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stdout + out.stderr
        assert "check_kernels: OK (4 kernel paths)" in out.stdout
