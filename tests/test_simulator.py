"""Simulator + MCMC search tests (reference subsystem §2.1 simulator rows,
model.cc:1082-1144)."""

import numpy as np

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
from dlrm_flexflow_tpu.parallel.parallel_config import ParallelConfig, Strategy
from dlrm_flexflow_tpu.sim import CostModel, Simulator, TPUMachineModel, mcmc_search
from dlrm_flexflow_tpu.sim.search import legal_configs, _factorizations


def mlp_model(batch=64, widths=(64, 256, 256, 8)):
    m = ff.FFModel(ff.FFConfig(batch_size=batch))
    t = m.create_tensor((batch, widths[0]), name="x")
    for i, w in enumerate(widths[1:]):
        t = m.dense(t, w, activation="relu", name=f"fc{i}")
    return m


class TestMachineModel:
    def test_ring_allreduce_scaling(self):
        m = TPUMachineModel()
        # 2(n-1)/n factor: n=2 -> 1x bytes, n->inf -> 2x bytes
        t2 = m.all_reduce_time(1e6, 2)
        t8 = m.all_reduce_time(1e6, 8)
        assert t2 < t8 < 2 * t2 + 1e-12
        assert m.all_reduce_time(1e6, 1) == 0.0

    def test_matmul_vs_memory_bound(self):
        m = TPUMachineModel()
        # big matmul: compute bound
        assert m.matmul_time(1e12) > m.memory_time(1e6)


class TestCostModel:
    def test_analytic_monotone_in_parts(self):
        model = mlp_model()
        cm = CostModel()
        op = model.layers[0]
        f1, b1 = cm.op_times(op, 1)
        f4, b4 = cm.op_times(op, 4)
        assert f4 < f1 and b4 < b1

    def test_memoization(self):
        model = mlp_model()
        cm = CostModel()
        op = model.layers[0]
        assert cm.op_times(op, 2) == cm.op_times(op, 2)
        assert len(cm._cache) == 1


class TestSimulator:
    def test_dp_faster_than_single_device(self):
        # compute-dominated regime (huge batch, small weights): DP wins;
        # in weight-dominated regimes the all-reduce makes DP lose, which
        # the simulator also (correctly) reports
        model = mlp_model(batch=65536, widths=(64, 64, 64, 64))
        sim = Simulator(model, 8)
        single = Strategy()
        for op in model.layers:
            single[op.name] = ParallelConfig(dims=(1, 1), device_ids=[0])
        dp = Strategy()
        for op in model.layers:
            dp[op.name] = ParallelConfig.data_parallel(2, 8)
        t_single = sim.simulate(single)
        t_dp = sim.simulate(dp)
        assert t_dp < t_single, (t_dp, t_single)

    def test_comm_cost_charged_between_different_placements(self):
        model = mlp_model(batch=64)
        sim = Simulator(model, 4)
        # all on device 0 vs alternating placement: the latter adds comm
        same = Strategy()
        alt = Strategy()
        for i, op in enumerate(model.layers):
            same[op.name] = ParallelConfig(dims=(1, 1), device_ids=[0])
            alt[op.name] = ParallelConfig(dims=(1, 1), device_ids=[i % 4])
        # same per-op compute, but alt must pay ICI transfers
        assert sim.simulate(alt) > sim.simulate(same)

    def test_simulate_is_deterministic(self):
        model = mlp_model()
        sim = Simulator(model, 8)
        dp = Strategy()
        for op in model.layers:
            dp[op.name] = ParallelConfig.data_parallel(2, 8)
        assert sim.simulate(dp) == sim.simulate(dp)


class TestSearch:
    def test_factorizations(self):
        assert set(_factorizations(4, 2)) == {(1, 4), (2, 2), (4, 1)}

    def test_legal_configs_divisibility(self):
        model = mlp_model(batch=6)  # 6 not divisible by 4
        op = model.layers[0]        # out (6, 256)
        cands = legal_configs(op, 4)
        for pc in cands:
            assert 6 % pc.dims[0] == 0
            assert 256 % pc.dims[1] == 0

    def test_search_improves_or_matches_dp(self):
        model = mlp_model(batch=512, widths=(512, 1024, 1024, 256))
        sim = Simulator(model, 8)
        dp = Strategy()
        for op in model.layers:
            dp[op.name] = ParallelConfig.data_parallel(2, 8)
        t_dp = sim.simulate(dp)
        best = mcmc_search(model, 8, budget=200, seed=1, simulator=sim)
        assert best.best_simulated_time <= t_dp + 1e-12

    def test_search_result_compiles_and_trains(self):
        """A searched strategy must be executable end-to-end (SOAP output
        feeds the sharding compiler)."""
        import jax
        model = mlp_model(batch=64, widths=(64, 128, 128, 8))
        best = mcmc_search(model, 8, budget=50, seed=0)
        mesh = ff.make_mesh({"data": 4, "model": 2})
        model.compile(loss_type="mean_squared_error", metrics=(),
                      strategy=best, mesh=mesh)
        state = model.init(seed=0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 64)).astype(np.float32)
        y = rng.standard_normal((64, 8)).astype(np.float32)
        state, mets = model.train_step(state, {"x": x}, y)
        assert np.isfinite(float(mets["loss"]))

    def test_search_export_import_roundtrip(self, tmp_path):
        model = mlp_model(batch=64)
        best = mcmc_search(model, 4, budget=20, seed=0)
        path = str(tmp_path / "s.json")
        best.save(path)
        loaded = Strategy.load(path)
        assert loaded.configs.keys() == best.configs.keys()

    def test_compile_runs_search_when_budget_set(self, tmp_path):
        path = str(tmp_path / "exported.json")
        cfg = ff.FFConfig(batch_size=64, search_budget=20, num_devices=4)
        cfg.export_strategy_file = path
        m = ff.FFModel(cfg)
        t = m.create_tensor((64, 32), name="x")
        m.dense(t, 16, name="fc0")
        m.compile(loss_type="mean_squared_error", metrics=(), mesh=False)
        import os
        assert os.path.exists(path)
        assert "fc0" in Strategy.load(path).configs


class TestDLRMSearch:
    def test_dlrm_search_places_embeddings(self):
        """On the DLRM graph the search should find a strategy at least as
        good as pure DP (the reference's hybrid result,
        dlrm_strategy.cc:242-296)."""
        cfg = DLRMConfig(sparse_feature_size=16, embedding_size=[4096] * 8,
                         embedding_bag_size=2, mlp_bot=[13, 64, 16],
                         mlp_top=[16 * 8 + 16, 64, 1])
        model = build_dlrm(cfg, ff.FFConfig(batch_size=256))
        sim = Simulator(model, 8)
        dp = Strategy()
        for op in model.layers:
            nd = op.outputs[0].ndim
            dp[op.name] = ParallelConfig.data_parallel(nd, 8)
        t_dp = sim.simulate(dp)
        best = mcmc_search(model, 8, budget=300, seed=2, simulator=sim)
        assert best.best_simulated_time <= t_dp


class TestStandaloneCLI:
    """python -m dlrm_flexflow_tpu.sim — the analogue of the reference's
    standalone analytic simulator (scripts/simulator.cc)."""

    def test_cli_search_and_export(self, tmp_path, capsys):
        from dlrm_flexflow_tpu.sim.__main__ import main
        out = tmp_path / "s.json"
        rc = main(["--app", "dlrm", "--devices", "4", "--budget", "50",
                   "--export", str(out)])
        assert rc == 0
        assert out.exists()
        text = capsys.readouterr().out
        assert "data-parallel baseline" in text
        assert "searched strategy" in text

    def test_cli_every_app_builds(self):
        from dlrm_flexflow_tpu.sim.__main__ import build_app
        for app in ["dlrm", "alexnet", "resnet", "inception",
                    "candle_uno", "nmt"]:
            m = build_app(app, 16)
            assert m.layers, app


class TestInputRects:
    """True per-op input rectangles (VERDICT r1 item 5): the comm volume
    between producer and consumer parts must follow what each consumer
    part actually READS, not a projection of its output partitioning
    (reference add_task_dependencies_with_xfer, simulator.cc:200-233)."""

    def test_linear_tp_comm_bytes_hand_computed(self):
        """DP(2) producer -> channel-parallel(2) Linear consumer over an
        (8, 4) f32 activation: each TP part reads the FULL input, so each
        of the 2 cross-device (src part, dst part) pairs moves half the
        tensor = 4*4*4 = 64 bytes, in fwd and in grad direction."""
        m = ff.FFModel(ff.FFConfig(batch_size=8))
        x = m.create_tensor((8, 4), name="x")
        h = m.dense(x, 4, name="dense1")
        m.dense(h, 6, name="dense2")
        s = Strategy()
        s["dense1"] = ParallelConfig(dims=(2, 1))   # DP over 2 devices
        s["dense2"] = ParallelConfig(dims=(1, 2))   # TP over 2 devices

        sim = Simulator(m, 2)
        tasks, _ = sim._build_tasks(s)
        fwd_comm = [t for t in tasks
                    if t.kind == "comm" and t.name == "dense1->dense2"]
        bwd_comm = [t for t in tasks
                    if t.kind == "comm" and t.name == "dense2->dense1:grad"]
        # dst part0 (dev0) pulls src part1's rows (dev1) and vice versa
        assert len(fwd_comm) == 2 and len(bwd_comm) == 2
        want = sim.machine.ici_time(64)
        for t in fwd_comm + bwd_comm:
            assert t.run_time == want

    def test_linear_tp_part_reads_full_input(self):
        m = ff.FFModel(ff.FFConfig(batch_size=8))
        x = m.create_tensor((8, 4), name="x")
        m.dense(x, 6, name="dense")
        op = m.get_op("dense")
        pc = ParallelConfig(dims=(1, 2))
        for part in range(2):
            lo, hi = op.input_rect(pc, 0, part)
            assert (lo, hi) == ((0, 0), (8, 4))

    def test_concat_rect_hand_computed(self):
        """concat([(8,4), (8,6)], axis=1) -> (8,10), split 2x on the
        concat axis: part0 covers cols 0-5 -> reads all of input0 and
        cols 0-1 of input1; part1 covers cols 5-10 -> reads nothing of
        input0 and cols 1-6 of input1."""
        m = ff.FFModel(ff.FFConfig(batch_size=8))
        a = m.create_tensor((8, 4), name="a")
        b = m.create_tensor((8, 6), name="b")
        m.concat([a, b], axis=1, name="cat")
        op = m.get_op("cat")
        pc = ParallelConfig(dims=(1, 2))
        assert op.input_rect(pc, 0, 0) == ((0, 0), (8, 4))
        assert op.input_rect(pc, 1, 0) == ((0, 0), (8, 1))
        lo, hi = op.input_rect(pc, 0, 1)
        assert lo[1] == hi[1]  # empty: part1 reads none of input0
        assert op.input_rect(pc, 1, 1) == ((0, 1), (8, 6))

    def test_batch_matmul_rects(self):
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        a = m.create_tensor((4, 6, 8), name="a")
        b = m.create_tensor((4, 8, 10), name="b")
        m.batch_matmul(a, b, name="bmm")
        op = m.get_op("bmm")
        pc = ParallelConfig(dims=(2, 1, 1))  # batch split
        # part1: batch rows 2-4; A reads (2:4, :, :), B reads (2:4, :, :)
        assert op.input_rect(pc, 0, 1) == ((2, 0, 0), (4, 6, 8))
        assert op.input_rect(pc, 1, 1) == ((2, 0, 0), (4, 8, 10))

    def test_transpose_rect_permutes(self):
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        x = m.create_tensor((4, 6, 8), name="x")
        m.transpose(x, name="t")  # (4, 8, 6)
        op = m.get_op("t")
        pc = ParallelConfig(dims=(2, 1, 1))
        # output part1 rows 2-4 -> input rows 2-4, full inner dims
        assert op.input_rect(pc, 0, 1) == ((2, 0, 0), (4, 6, 8))

    def test_elementwise_identity_rect(self):
        m = ff.FFModel(ff.FFConfig(batch_size=8))
        x = m.create_tensor((8, 4), name="x")
        m.relu(x, name="r")
        op = m.get_op("r")
        pc = ParallelConfig(dims=(2, 1))
        assert op.input_rect(pc, 0, 0) == ((0, 0), (4, 4))
        assert op.input_rect(pc, 0, 1) == ((4, 0), (8, 4))

    def test_conv_halo_rect(self):
        m = ff.FFModel(ff.FFConfig(batch_size=2))
        x = m.create_tensor((2, 3, 16, 16), name="x")
        m.conv2d(x, 8, 3, 3, 1, 1, 1, 1, name="conv")  # same-pad 3x3
        op = m.get_op("conv")
        pc = ParallelConfig(dims=(1, 1, 2, 1))  # H split in two
        # part0: out rows 0-8 -> in rows 0..(7*1-1+3)=9 (one-row halo)
        lo, hi = op.input_rect(pc, 0, 0)
        assert (lo[2], hi[2]) == (0, 9)
        assert (lo[1], hi[1]) == (0, 3)  # all input channels
        # part1: out rows 8-16 -> in rows 7..16
        lo, hi = op.input_rect(pc, 0, 1)
        assert (lo[2], hi[2]) == (7, 16)


class TestOverlapMode:
    """Weight-sync modeling (VERDICT r1 item 5, reference
    simulator.cc:327-408): bulk-sync barriers every update behind the
    LAST backward; overlap lets each op's grad sync + update chase its
    own backward — the flag must change the simulated makespan."""

    def _model(self):
        m = ff.FFModel(ff.FFConfig(batch_size=64))
        x = m.create_tensor((64, 64), name="x")
        h = m.dense(x, 256, name="dense1")
        m.dense(h, 8, name="dense2")
        s = Strategy()
        s["dense1"] = ParallelConfig.data_parallel(2, 2)
        s["dense2"] = ParallelConfig.data_parallel(2, 2)
        return m, s

    def test_overlap_strictly_faster(self):
        m, s = self._model()
        bulk = Simulator(m, 2, overlap_backward_update=False).simulate(s)
        over = Simulator(m, 2, overlap_backward_update=True).simulate(s)
        assert over < bulk

    def test_native_parity_both_modes(self):
        from dlrm_flexflow_tpu.sim.native_sim import (NativeSimulator,
                                                      native_available)
        if not native_available():
            import pytest
            pytest.skip("native lib unavailable")
        m, s = self._model()
        for overlap in (False, True):
            py = Simulator(m, 2,
                           overlap_backward_update=overlap).simulate(s)
            nat = NativeSimulator.for_strategy(
                m, 2, s, overlap_backward_update=overlap).simulate(s)
            assert abs(py - nat) < 1e-9, (overlap, py, nat)


class TestMeasuredOpTakesParamsAsArguments:
    def test_no_table_sized_constant_in_the_measured_program(self):
        """The measured program takes the op's params as ARGUMENTS.  As
        closed-over values they were lowered as constants: at the DLRM
        bench width a 2 GB table inside the HLO, whose compile and
        cache-write took the chip machine's whole host memory (PR 21)."""
        import warnings

        import jax

        m = ff.FFModel(ff.FFConfig(batch_size=8))
        ids = m.create_tensor((8, 2), "int32", name="ids")
        m.embedding(ids, 4096, 16, name="emb")  # a 256 KB table
        before = jax.config.jax_captured_constants_warn_bytes
        jax.config.update("jax_captured_constants_warn_bytes", 1024)
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                CostModel(measure=True, measure_iters=2).op_times(
                    m.get_op("emb"), 1)
        finally:
            jax.config.update("jax_captured_constants_warn_bytes", before)
        assert not [x for x in w if "constants were captured"
                    in str(x.message)]


class TestMeasureBudget:
    def test_budget_exhaustion_falls_back_to_analytic(self):
        """The measured cost model stops compiling new op measurements
        once its wall-clock budget is spent (each distinct shape costs a
        compile; a big graph must not stall a compile-time search)."""
        import warnings

        m = mlp_model()
        cm = CostModel(measure=True, measure_budget_s=0.0)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            f, b = cm.op_times(m.layers[0], 1)
        assert f > 0 and b > 0
        assert any("budget" in str(x.message) for x in w)
        # and the analytic result is cached like any other
        assert cm.op_times(m.layers[0], 1) == (f, b)

    def test_post_budget_analytic_is_ratio_calibrated(self):
        """Post-budget estimates are scaled by the measured/analytic
        ratio of the already-measured keys, so one search never compares
        raw roofline numbers against measured times."""
        m = mlp_model()
        cm = CostModel(measure=True, measure_budget_s=1e9)
        # seed the ratio with a fake "measured" history: 10x analytic
        af, ab = cm._analytic_op(m.layers[0], 1)
        cm._measured_total = 10.0 * (af + ab)
        cm._analytic_total = af + ab
        cm.measure_budget_s = 0.0  # exhaust
        import warnings

        import pytest
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            f, b = cm.op_times(m.layers[1], 1)
        a2f, a2b = cm._analytic_op(m.layers[1], 1)
        assert f == pytest.approx(10.0 * a2f, rel=1e-9)
        assert b == pytest.approx(10.0 * a2b, rel=1e-9)
