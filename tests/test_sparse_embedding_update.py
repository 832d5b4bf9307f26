"""Sparse embedding update fast path: under plain SGD, the compiled
train_step gathers rows outside the differentiated region and scatter-
applies -lr*row_grad — numerics must match the dense autodiff path
EXACTLY (same adds, different traffic)."""

import numpy as np
import pytest

import dlrm_flexflow_tpu as ff


def _dlrm(batch=16, rows=64, tables=4, bag=2, stacked=True, mesh=False,
          table_parallel=False, optimizer=None):
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
    cfg = DLRMConfig(sparse_feature_size=8,
                     embedding_size=[rows] * tables,
                     embedding_bag_size=bag,
                     mlp_bot=[4, 16, 8],
                     mlp_top=[8 * tables + 8, 16, 1])
    fc = ff.FFConfig(batch_size=batch)
    m = build_dlrm(cfg, fc, stacked_embeddings=stacked,
                   table_parallel=table_parallel)
    m.compile(optimizer=optimizer or ff.SGDOptimizer(lr=0.05),
              loss_type="mean_squared_error", metrics=(), mesh=mesh)
    return cfg, m


def _batch(cfg, batch=16, tables=4, stacked=True, seed=0):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((batch, cfg.mlp_bot[0])).astype(np.float32)
    if stacked:
        inputs = {"dense": dense,
                  "sparse": rng.integers(0, cfg.embedding_size[0],
                                         size=(batch, tables,
                                               cfg.embedding_bag_size),
                                         dtype=np.int64)}
    else:
        inputs = {"dense": dense}
        for i in range(tables):
            inputs[f"sparse_{i}"] = rng.integers(
                0, cfg.embedding_size[i],
                size=(batch, cfg.embedding_bag_size), dtype=np.int64)
    labels = rng.integers(0, 2, size=(batch, 1)).astype(np.float32)
    return inputs, labels


class TestSparseMatchesDense:
    @pytest.mark.parametrize("stacked", [True, False])
    def test_train_steps_identical(self, stacked):
        cfg, m = _dlrm(stacked=stacked)
        assert m._sparse_emb_ops  # fast path active
        st_sparse = m.init(seed=0)

        # dense reference: same graph, momentum!=0 disables the fast path
        # is not fair (different math); instead force dense by rebuilding
        # with the fast path disabled via monkeypatched eligibility
        cfg2, m2 = _dlrm(stacked=stacked,
                         optimizer=ff.SGDOptimizer(lr=0.05, momentum=0.9))
        assert not m2._sparse_emb_ops
        # momentum=0.9 changes the update; emulate dense plain SGD by
        # zeroing momentum's contribution is wrong — instead compare
        # against a manual dense step below.
        del cfg2, m2

        import jax
        import jax.numpy as jnp
        inputs, labels = _batch(cfg, stacked=stacked)

        # manual dense reference step (autodiff through the table)
        final_uid = m.final_tensor.uid

        def loss_fn(params):
            values, _ = m._apply(params, inputs, training=True, rng=None,
                                 bn_state={})
            return m._loss_fn(values[final_uid], labels)

        g = jax.grad(loss_fn)(st_sparse.params)
        ref_params = jax.tree_util.tree_map(
            lambda w, gg: w - 0.05 * gg, st_sparse.params, g)

        st1, _ = m.train_step(st_sparse, inputs, labels)

        for opn in st1.params:
            for k in st1.params[opn]:
                np.testing.assert_allclose(
                    np.asarray(st1.params[opn][k]),
                    np.asarray(ref_params[opn][k]),
                    rtol=1e-6, atol=1e-6,
                    err_msg=f"{opn}/{k} ({'stacked' if stacked else 'per-table'})")

    def test_repeated_ids_accumulate(self):
        """Duplicate ids in one batch must accumulate their grads (the
        reference's atomicAdd semantics)."""
        cfg, m = _dlrm(stacked=True)
        st = m.init(seed=0)
        inputs, labels = _batch(cfg)
        # force every lookup to the same id
        inputs["sparse"] = np.zeros_like(inputs["sparse"])
        import jax

        def loss_fn(params):
            values, _ = m._apply(params, inputs, training=True, rng=None,
                                 bn_state={})
            return m._loss_fn(values[m.final_tensor.uid], labels)

        g = jax.grad(loss_fn)(st.params)
        ref_emb = np.asarray(st.params["emb"]["embedding"]) \
            - 0.05 * np.asarray(g["emb"]["embedding"])
        st1, _ = m.train_step(st, inputs, labels)
        np.testing.assert_allclose(np.asarray(st1.params["emb"]["embedding"]),
                                   ref_emb, rtol=1e-6, atol=1e-6)

    def test_momentum_and_wd_fall_back_to_dense(self):
        _, m_mom = _dlrm(optimizer=ff.SGDOptimizer(lr=0.05, momentum=0.9))
        assert not m_mom._sparse_emb_ops
        _, m_wd = _dlrm(optimizer=ff.SGDOptimizer(lr=0.05, weight_decay=0.1))
        assert not m_wd._sparse_emb_ops
        _, m_adam = _dlrm(optimizer=ff.AdamOptimizer(lr=0.001))
        assert not m_adam._sparse_emb_ops

    def test_table_parallel_mesh_matches_single_device(self):
        """Fast path under the hybrid strategy on an 8-device mesh equals
        single-device numerics."""
        import jax
        cfg, m1 = _dlrm(mesh=False)
        st1 = m1.init(seed=0)
        inputs, labels = _batch(cfg)
        st1, _ = m1.train_step(st1, inputs, labels)

        mesh = ff.make_mesh({"data": 2, "model": 4})
        cfg2, m2 = _dlrm(mesh=mesh, table_parallel=True)
        assert m2._sparse_emb_ops
        st2 = m2.init(seed=0)
        st2, _ = m2.train_step(st2, inputs, labels)
        np.testing.assert_allclose(
            np.asarray(st1.params["emb"]["embedding"]),
            np.asarray(st2.params["emb"]["embedding"]),
            rtol=1e-5, atol=1e-5)

    def test_lr_schedule_still_applies(self):
        """The scatter step reads lr from opt_state so schedules work."""
        cfg, m = _dlrm()
        st = m.init(seed=0)
        inputs, labels = _batch(cfg)
        st_lr = m.set_learning_rate(st, 0.0)  # freeze
        before = np.asarray(st_lr.params["emb"]["embedding"])
        st1, _ = m.train_step(st_lr, inputs, labels)
        np.testing.assert_array_equal(
            before, np.asarray(st1.params["emb"]["embedding"]))


class TestSparseModeKnob:
    def test_off_forces_dense(self):
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[64] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 8],
                         mlp_top=[8 * 2 + 8, 1])
        fc = ff.FFConfig(batch_size=8, sparse_embedding_updates="off")
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        assert not m._sparse_emb_ops

    def test_auto_enables_on_cpu(self):
        # the test platform is cpu (conftest), an aliasing backend
        import jax
        assert jax.default_backend() == "cpu"
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[64] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 8],
                         mlp_top=[8 * 2 + 8, 1])
        m = build_dlrm(cfg, ff.FFConfig(batch_size=8))
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        assert m._sparse_emb_ops

    def test_invalid_mode_raises(self):
        import pytest as _pytest
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[64] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 8],
                         mlp_top=[8 * 2 + 8, 1])
        m = build_dlrm(cfg, ff.FFConfig(batch_size=8,
                                        sparse_embedding_updates="On"))
        with _pytest.raises(ValueError):
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False)


class TestBF16Tables:
    """FFConfig.embedding_dtype="bfloat16": table storage in bf16 halves
    the full-table sweep that dominates big-table steps (PERF.md); the
    sparse fast path must still match dense autodiff at the same dtype,
    and training must still learn."""

    def _dlrm_emb16(self, sparse_mode):
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8,
                         embedding_size=[64] * 4,
                         embedding_bag_size=2,
                         mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 4 + 8, 16, 1])
        fc = ff.FFConfig(batch_size=16, embedding_dtype="bfloat16",
                         sparse_embedding_updates=sparse_mode)
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        return cfg, m

    def test_param_dtype_is_bf16(self):
        import jax.numpy as jnp
        _, m = self._dlrm_emb16("on")
        st = m.init(seed=0)
        emb = [v for k, v in st.params.items() if "embedding" in v]
        assert emb and all(v["embedding"].dtype == jnp.bfloat16 for v in emb)

    def test_sparse_matches_dense_bf16(self):
        cfg, m_s = self._dlrm_emb16("on")
        _, m_d = self._dlrm_emb16("off")
        st_s, st_d = m_s.init(seed=0), m_d.init(seed=0)
        for step in range(3):
            inputs, labels = _batch(cfg, seed=step)
            st_s, _ = m_s.train_step(st_s, inputs, labels)
            st_d, _ = m_d.train_step(st_d, inputs, labels)
        for opn in st_s.params:
            for k, v in st_s.params[opn].items():
                np.testing.assert_allclose(
                    np.asarray(v, dtype=np.float32),
                    np.asarray(st_d.params[opn][k], dtype=np.float32),
                    rtol=2e-2, atol=2e-2)

    def test_bf16_training_learns_like_f32(self):
        # loss trajectory of bf16 tables tracks the f32 run
        losses = {}
        for dt in ("float32", "bfloat16"):
            from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
            cfg = DLRMConfig(sparse_feature_size=8,
                             embedding_size=[64] * 4,
                             embedding_bag_size=2,
                             mlp_bot=[4, 16, 8],
                             mlp_top=[8 * 4 + 8, 16, 1])
            fc = ff.FFConfig(batch_size=16, embedding_dtype=dt)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False)
            st = m.init(seed=0)
            ls = []
            for step in range(20):
                inputs, labels = _batch(cfg, seed=step % 5)
                st, mets = m.train_step(st, inputs, labels)
                ls.append(float(mets["loss"]))
            losses[dt] = ls
        assert losses["bfloat16"][-1] < losses["bfloat16"][0]  # learns
        assert abs(losses["bfloat16"][-1] - losses["float32"][-1]) < 0.05


class TestEpochRowCache:
    """train_epoch's epoch row-cache (epoch_row_cache="on" forces it off
    TPU): one table sweep in, scan against the small cache by unique
    slot, one scatter-set back — must equal the stepwise path exactly."""

    def _run(self, stacked, emb_dtype, cache_mode, nb=6, batch=16,
             tables=4, bag=2, big=True, view="auto"):
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        # big tables: the cache engages (epoch ids < rows); small tables:
        # the clamp skips caching (cache would be >= the table)
        if big:
            # non-divisible row counts (1396 % 8 != 0) exercise the
            # lane_pack cache rounding on tables the per-step packed view
            # cannot handle directly
            rows = [4096, 1396, 2048, 8190][:tables] if not stacked \
                else [4096] * tables
        else:
            rows = [64, 96, 32, 80][:tables] if not stacked \
                else [64] * tables
        cfg = DLRMConfig(sparse_feature_size=8,
                         embedding_size=list(rows),
                         embedding_bag_size=bag,
                         mlp_bot=[4, 16, 8],
                         mlp_top=[8 * tables + 8, 16, 1])
        fc = ff.FFConfig(batch_size=batch, embedding_dtype=emb_dtype,
                         epoch_row_cache=cache_mode,
                         epoch_cache_view=view)
        m = build_dlrm(cfg, fc, stacked_embeddings=stacked)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=("accuracy",),
                  mesh=False)
        rng = np.random.default_rng(0)
        inputs = {"dense": rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32)}
        if stacked:
            inputs["sparse"] = rng.integers(
                0, rows[0], size=(nb, batch, tables, bag), dtype=np.int64)
        else:
            for i, r in enumerate(rows):
                inputs[f"sparse_{i}"] = rng.integers(
                    0, r, size=(nb, batch, bag), dtype=np.int64)
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        st = m.init(seed=0)
        st, mets = m.train_epoch(st, inputs, labels)
        return st, mets

    @pytest.mark.parametrize("big", [True, False])
    @pytest.mark.parametrize("stacked", [True, False])
    @pytest.mark.parametrize("emb_dtype", ["float32", "bfloat16"])
    def test_cached_equals_uncached_epoch(self, stacked, emb_dtype, big):
        st_c, mets_c = self._run(stacked, emb_dtype, "on", big=big)
        st_u, mets_u = self._run(stacked, emb_dtype, "off", big=big)
        for opn in st_c.params:
            for k in st_c.params[opn]:
                np.testing.assert_array_equal(
                    np.asarray(st_c.params[opn][k]),
                    np.asarray(st_u.params[opn][k]),
                    err_msg=f"{opn}/{k} (stacked={stacked}, {emb_dtype})")
        for k in mets_c:
            np.testing.assert_allclose(np.asarray(mets_c[k]),
                                       np.asarray(mets_u[k]), rtol=1e-6)

    @pytest.mark.parametrize("stacked", [True, False])
    @pytest.mark.parametrize("emb_dtype", ["float32", "bfloat16"])
    def test_view_row_transport_bit_exact(self, stacked, emb_dtype):
        """epoch_cache_view="on" (128-lane view-row fetch/writeback at
        the top level) must equal the uncached path BIT-exactly: the
        view row's untouched halves are fetched with it, addressed by
        no slot, and written back with their original bytes.  The
        unstacked shape mixes pack-divisible tables (view engages) with
        non-divisible ones (logical fallback) in one model."""
        st_v, mets_v = self._run(stacked, emb_dtype, "on", view="on")
        st_u, mets_u = self._run(stacked, emb_dtype, "off", view="off")
        for opn in st_v.params:
            for k in st_v.params[opn]:
                np.testing.assert_array_equal(
                    np.asarray(st_v.params[opn][k]),
                    np.asarray(st_u.params[opn][k]),
                    err_msg=f"{opn}/{k} (stacked={stacked}, {emb_dtype})")
        for k in mets_v:
            np.testing.assert_allclose(np.asarray(mets_v[k]),
                                       np.asarray(mets_u[k]), rtol=1e-6)

    @pytest.mark.parametrize("stacked", [True, False])
    @pytest.mark.parametrize("levels", ["auto", "3", "off"])
    def test_packed_storage_bit_exact(self, stacked, levels):
        """packed_tables="on" (tables live as (R/pack, 128) arrays,
        caches in view-row units at every ladder level) must equal the
        logical-storage uncached path bit-exactly, and get_weights must
        return the logical shape."""
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        tables, bag, batch, nb = 3, 2, 16, 6
        rows = [4096, 2048, 1024][:tables] if not stacked else [4096] * 3
        cfg = DLRMConfig(sparse_feature_size=8,
                         embedding_size=list(rows),
                         embedding_bag_size=bag,
                         mlp_bot=[4, 16, 8],
                         mlp_top=[8 * tables + 8, 16, 1])
        rng = np.random.default_rng(7)
        inputs = {"dense": rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32)}
        if stacked:
            inputs["sparse"] = rng.integers(
                0, rows[0], size=(nb, batch, tables, bag), dtype=np.int64)
        else:
            for i, r in enumerate(rows):
                inputs[f"sparse_{i}"] = rng.integers(
                    0, r, size=(nb, batch, bag), dtype=np.int64)
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        runs = {}
        for packed, cache in (("on", "on"), ("off", "off")):
            fc = ff.FFConfig(batch_size=batch, epoch_row_cache=cache,
                             packed_tables=packed,
                             epoch_cache_levels=levels,
                             epoch_cache_chunk=3, epoch_cache_inner=3)
            m = build_dlrm(cfg, fc, stacked_embeddings=stacked)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error",
                      metrics=("accuracy",), mesh=False)
            st = m.init(seed=0)
            if packed == "on" and stacked:
                emb = [op for op in m.layers
                       if op.op_type == "StackedEmbedding"][0]
                assert emb.storage_pack == 16  # d=8
                assert st.params[emb.name]["embedding"].shape[-1] == 128
            st, mets = m.train_epoch(st, inputs, labels)
            runs[packed] = (st, mets, m)
        st_p, mets_p, m_p = runs["on"]
        st_u, mets_u, m_u = runs["off"]
        for opn in st_p.params:
            for k in st_p.params[opn]:
                np.testing.assert_array_equal(
                    m_p.get_weights(st_p, opn, k),
                    m_u.get_weights(st_u, opn, k),
                    err_msg=f"{opn}/{k} stacked={stacked} {levels}")
        for k in mets_p:
            np.testing.assert_allclose(np.asarray(mets_p[k]),
                                       np.asarray(mets_u[k]), rtol=1e-6)

    def test_packed_storage_set_get_roundtrip(self):
        """set_weights accepts logical values for packed tables and
        get_weights returns them unchanged."""
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[512] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 8, 8],
                         mlp_top=[8 * 2 + 8, 8, 1])
        fc = ff.FFConfig(batch_size=8, packed_tables="on")
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        st = m.init(seed=0)
        emb = [op for op in m.layers
               if op.op_type == "StackedEmbedding"][0]
        assert emb.storage_pack > 1
        w = np.random.default_rng(3).standard_normal(
            (2, 512, 8)).astype(np.float32)
        st = m.set_weights(st, emb.name, "embedding", w)
        got = m.get_weights(st, emb.name, "embedding")
        assert got.shape == (2, 512, 8)
        np.testing.assert_array_equal(got, w)

    def test_heavy_duplicate_ids_across_steps(self):
        # many cross-step collisions: ids drawn from just 8 rows
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[64] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        rng = np.random.default_rng(1)
        nb, batch = 5, 16
        inputs = {"dense": rng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            "sparse": rng.integers(0, 8, size=(nb, batch, 2, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        states = {}
        for mode in ("on", "off"):
            fc = ff.FFConfig(batch_size=batch, epoch_row_cache=mode)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False)
            st = m.init(seed=0)
            st, _ = m.train_epoch(st, inputs, labels)
            states[mode] = st
        a, b = states["on"].params, states["off"].params
        for opn in a:
            for k in a[opn]:
                np.testing.assert_array_equal(np.asarray(a[opn][k]),
                                              np.asarray(b[opn][k]))

    def test_chunked_equals_unchunked(self):
        # chunk boundary correctness: rows updated in chunk k must be
        # re-cached with their new values by chunk k+1
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[4096] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        rng = np.random.default_rng(2)
        nb, batch = 9, 16  # 9 steps, chunk 4 -> chunks of 4+4+1
        inputs = {"dense": rng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            # ids from a narrow range so chunks share rows
            "sparse": rng.integers(0, 32, size=(nb, batch, 2, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        states = {}
        for chunk in (4, 0):
            fc = ff.FFConfig(batch_size=batch, epoch_row_cache="on",
                             epoch_cache_chunk=chunk)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error",
                      metrics=("accuracy",), mesh=False)
            st = m.init(seed=0)
            st, mets = m.train_epoch(st, inputs, labels)
            states[chunk] = (st, mets)
        a, b = states[4][0].params, states[0][0].params
        for opn in a:
            for k in a[opn]:
                np.testing.assert_array_equal(np.asarray(a[opn][k]),
                                              np.asarray(b[opn][k]))
        np.testing.assert_allclose(
            float(states[4][1]["loss"]), float(states[0][1]["loss"]),
            rtol=1e-6)

    def test_fit_scan_path_uses_chunks(self):
        # fit()'s staged-scan fast path must route through the chunked
        # dispatch when the epoch row-cache is active
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        from dlrm_flexflow_tpu.data.loader import SyntheticDLRMLoader
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[4096] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        fc = ff.FFConfig(batch_size=16, epoch_row_cache="on",
                         epoch_cache_chunk=4)
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=("accuracy",),
                  mesh=False)
        loader = SyntheticDLRMLoader(
            num_samples=16 * 9, num_dense=4, table_sizes=cfg.embedding_size,
            bag_size=2, batch_size=16)
        st = m.init(seed=0)
        st, _ = m.fit(st, loader, epochs=2, verbose=False)
        assert m._last_fit_used_scan
        # 9 batches x 2 epochs + fit's one warmup update
        assert int(st.step) == 19

    def test_inner_block_cache_equals_stepwise(self):
        # nb divisible by epoch_cache_inner so the in-graph L0 nested
        # scan actually executes (the other cases fall back)
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[8192] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        rng = np.random.default_rng(3)
        nb, batch = 12, 16  # inner=4 -> 3 L0 blocks
        inputs = {"dense": rng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            # narrow id range: heavy duplicates within and across blocks
            "sparse": rng.integers(0, 48, size=(nb, batch, 2, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        states = {}
        for mode, inner in (("on", 4), ("off", 0)):
            fc = ff.FFConfig(batch_size=batch, epoch_row_cache=mode,
                             epoch_cache_inner=inner)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error",
                      metrics=("accuracy",), mesh=False)
            st = m.init(seed=0)
            st, mets = m.train_epoch(st, inputs, labels)
            states[mode] = (st, mets)
        a, b = states["on"][0].params, states["off"][0].params
        for opn in a:
            for k in a[opn]:
                np.testing.assert_array_equal(np.asarray(a[opn][k]),
                                              np.asarray(b[opn][k]))
        for k in states["on"][1]:
            np.testing.assert_allclose(
                np.asarray(states["on"][1][k]),
                np.asarray(states["off"][1][k]), rtol=1e-6)

    def test_three_level_ladder_equals_stepwise(self):
        # explicit epoch_cache_levels forces a 3-deep in-graph ladder
        # (16 -> 8 -> 4 -> 2-step blocks); every level's fetch/writeback
        # pair must compose bit-exactly with the uncached path
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[8192] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        rng = np.random.default_rng(5)
        nb, batch = 16, 16
        inputs = {"dense": rng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            # narrow range: rows recur across blocks at every level
            "sparse": rng.integers(0, 40, size=(nb, batch, 2, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        states = {}
        for mode, levels in (("on", "8,4,2"), ("off", "off")):
            fc = ff.FFConfig(batch_size=batch, epoch_row_cache=mode,
                             epoch_cache_levels=levels)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error",
                      metrics=("accuracy",), mesh=False)
            st = m.init(seed=0)
            st, mets = m.train_epoch(st, inputs, labels)
            states[mode] = (st, mets)
        a, b = states["on"][0].params, states["off"][0].params
        for opn in a:
            for k in a[opn]:
                np.testing.assert_array_equal(np.asarray(a[opn][k]),
                                              np.asarray(b[opn][k]))
        for k in states["on"][1]:
            np.testing.assert_allclose(
                np.asarray(states["on"][1][k]),
                np.asarray(states["off"][1][k]), rtol=1e-6)

    def test_ladder_fuses_chunked_multi_epoch(self):
        # nb > chunk with chunk | nb: the auto ladder absorbs chunking
        # into the jitted program (no host-side chunk dispatches), and
        # the fused multi-epoch run matches repeated train_epoch calls
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[4096] * 2,
                         embedding_bag_size=2, mlp_bot=[4, 16, 8],
                         mlp_top=[8 * 2 + 8, 16, 1])
        rng = np.random.default_rng(6)
        nb, batch = 8, 16
        inputs = {"dense": rng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            "sparse": rng.integers(0, 32, size=(nb, batch, 2, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        fc = ff.FFConfig(batch_size=batch, epoch_row_cache="on",
                         epoch_cache_chunk=4, epoch_cache_inner=2)
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error",
                  metrics=("accuracy",), mesh=False)
        # chunk divides nb -> one fused dispatch, no host chunking
        assert m._epoch_chunk_bounds(nb) is None
        st_f = m.init(seed=0)
        st_f, _ = m.train_epochs(st_f, inputs, labels, 2)
        st_r = m.init(seed=0)
        for _ in range(2):
            st_r, _ = m.train_epoch(st_r, inputs, labels)
        for opn in st_f.params:
            for k in st_f.params[opn]:
                np.testing.assert_array_equal(
                    np.asarray(st_f.params[opn][k]),
                    np.asarray(st_r.params[opn][k]))

    def test_chunk_bounds_round_to_inner(self):
        import dlrm_flexflow_tpu as ffm
        from dlrm_flexflow_tpu.row_cache import CachePolicy
        m = ffm.FFModel(ff.FFConfig(epoch_cache_chunk=256,
                                    epoch_cache_inner=8))
        m._epoch_cache_active = True
        m._cache_policy = CachePolicy.resolve(m.config, "cpu", None)
        # inner divides nb -> an in-graph ladder level engages over the
        # whole epoch, so the dispatch is UNCHUNKED (round 4: host-side
        # chunking cost ~5 ms/dispatch and was the real source of the
        # round-3 "shallow ladders are slow" artifact)
        assert m._epoch_chunk_bounds(1000) is None
        # nothing engages (inner does not divide) -> chunked, with all
        # but the tail rounded to whole inner blocks
        bounds = m._epoch_chunk_bounds(1001)
        sizes = [hi - lo for lo, hi in bounds]
        assert sum(sizes) == 1001
        assert all(s % 8 == 0 for s in sizes[:-1])
        assert bounds[-1][1] == 1001


class TestMeshSparseFastPath:
    """The sparse-update fast path + epoch row-cache under a mesh: the
    flagship distributed-DLRM configuration (table-parallel embeddings +
    DP MLPs, reference dlrm_strategy.cc:242-296) must keep the row-sparse
    path ACTIVE and train to the same result as single-device (exact but
    for the DP gradient-reduction order, same tolerance as the
    device-count matrix in test_parallel.py)."""

    def _epoch_data(self, cfg, nb=8, batch=16, tables=4, stacked=True,
                    seed=0):
        rng = np.random.default_rng(seed)
        inputs = {"dense": rng.standard_normal(
            (nb, batch, cfg.mlp_bot[0])).astype(np.float32)}
        if stacked:
            inputs["sparse"] = rng.integers(
                0, cfg.embedding_size[0],
                size=(nb, batch, tables, cfg.embedding_bag_size),
                dtype=np.int64)
        else:
            for i in range(tables):
                inputs[f"sparse_{i}"] = rng.integers(
                    0, cfg.embedding_size[i],
                    size=(nb, batch, cfg.embedding_bag_size),
                    dtype=np.int64)
        labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
        return inputs, labels

    @pytest.mark.parametrize("cache", ["on", "off"])
    @pytest.mark.parametrize("stacked", [True, False])
    def test_mesh_matches_single_device(self, stacked, cache):
        from dlrm_flexflow_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": 4, "model": 2})

        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

        def build(mesh_arg):
            tables = 4
            cfg = DLRMConfig(sparse_feature_size=8,
                             embedding_size=[64] * tables,
                             embedding_bag_size=2,
                             mlp_bot=[4, 16, 8],
                             mlp_top=[8 * tables + 8, 16, 1])
            fc = ff.FFConfig(batch_size=16, epoch_row_cache=cache,
                             epoch_cache_inner=2)
            m = build_dlrm(cfg, fc, stacked_embeddings=stacked,
                           table_parallel=mesh_arg is not False)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=mesh_arg)
            return cfg, m

        cfg, m_mesh = build(mesh)
        _, m_single = build(False)

        # THE assertion of VERDICT item 1: fast path active under mesh
        assert m_mesh._sparse_emb_ops, "sparse fast path inactive under mesh"
        assert m_mesh._sparse_emb_ops == m_single._sparse_emb_ops
        if cache == "on":
            assert m_mesh._epoch_cache_active

        inputs, labels = self._epoch_data(cfg, stacked=stacked)
        st_m, st_s = m_mesh.init(seed=0), m_single.init(seed=0)
        for _ in range(3):
            st_m, mets_m = m_mesh.train_epoch(st_m, inputs, labels)
            st_s, mets_s = m_single.train_epoch(st_s, inputs, labels)
        assert float(mets_m["loss"]) == pytest.approx(
            float(mets_s["loss"]), rel=1e-5)
        for opn in st_s.params:
            for k in st_s.params[opn]:
                np.testing.assert_allclose(
                    np.asarray(st_m.params[opn][k]),
                    np.asarray(st_s.params[opn][k]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{opn}/{k}")

    def test_mesh_table_parallel_sharding_applied(self):
        """The stacked table must actually be sharded over 'model' under
        the table-parallel strategy (not replicated)."""
        from dlrm_flexflow_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": 4, "model": 2})
        _, m = _dlrm(stacked=True, mesh=mesh, table_parallel=True)
        st = m.init(seed=0)
        spec = st.params["emb"]["embedding"].sharding.spec
        assert spec and spec[0] == "model", spec

    def test_mesh_train_step_sparse(self):
        """Per-step (non-epoch) path under mesh: fast path active and one
        train_step matches the single-device step."""
        from dlrm_flexflow_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": 4, "model": 2})
        cfg, m_mesh = _dlrm(stacked=True, mesh=mesh, table_parallel=True)
        _, m_single = _dlrm(stacked=True)
        assert m_mesh._sparse_emb_ops
        inputs, labels = _batch(cfg)
        st_m, st_s = m_mesh.init(seed=0), m_single.init(seed=0)
        st_m, _ = m_mesh.train_step(st_m, inputs, labels)
        st_s, _ = m_single.train_step(st_s, inputs, labels)
        for opn in st_s.params:
            for k in st_s.params[opn]:
                np.testing.assert_allclose(
                    np.asarray(st_m.params[opn][k]),
                    np.asarray(st_s.params[opn][k]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{opn}/{k}")


class TestMultiEpochFusion:
    """train_epochs(n) — one dispatch for n epochs — must be bit-exact
    with n successive train_epoch calls (the row cache stays live across
    epochs; each epoch's writeback/re-cache pair is the identity)."""

    @pytest.mark.parametrize("cache", ["on", "off"])
    def test_train_epochs_matches_repeated_train_epoch(self, cache):
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        cfg = DLRMConfig(sparse_feature_size=8,
                         embedding_size=[64] * 4, embedding_bag_size=2,
                         mlp_bot=[4, 16, 8], mlp_top=[8 * 4 + 8, 16, 1])

        def build():
            fc = ff.FFConfig(batch_size=16, epoch_row_cache=cache,
                             epoch_cache_inner=2)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error",
                      metrics=("accuracy",), mesh=False)
            return m

        rng = np.random.default_rng(0)
        nb = 4
        inputs = {"dense": rng.standard_normal(
            (nb, 16, 4)).astype(np.float32),
            "sparse": rng.integers(0, 64, size=(nb, 16, 4, 2),
                                   dtype=np.int64)}
        labels = rng.integers(0, 2, size=(nb, 16, 1)).astype(np.float32)

        m1 = build()
        st1 = m1.init(seed=0)
        per_epoch = []
        for _ in range(3):
            st1, mets = m1.train_epoch(st1, inputs, labels)
            per_epoch.append(mets)

        m2 = build()
        st2 = m2.init(seed=0)
        st2, stacked = m2.train_epochs(st2, inputs, labels, 3)

        for opn in st1.params:
            for k in st1.params[opn]:
                np.testing.assert_array_equal(
                    np.asarray(st1.params[opn][k]),
                    np.asarray(st2.params[opn][k]), err_msg=f"{opn}/{k}")
        for k in stacked:
            np.testing.assert_allclose(
                np.asarray(stacked[k]),
                np.asarray([m[k] for m in per_epoch]), rtol=1e-6)

    def test_fit_uses_fused_multi_epoch(self):
        """fit() with a scan-eligible loader and no callbacks runs all
        epochs in one dispatch and reports per-epoch metrics."""
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
        from dlrm_flexflow_tpu.data.loader import SyntheticDLRMLoader
        cfg = DLRMConfig(sparse_feature_size=8,
                         embedding_size=[64] * 4, embedding_bag_size=2,
                         mlp_bot=[4, 16, 8], mlp_top=[8 * 4 + 8, 16, 1])
        fc = ff.FFConfig(batch_size=16)
        m = build_dlrm(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error",
                  metrics=("accuracy",), mesh=False)
        st = m.init(seed=0)
        loader = SyntheticDLRMLoader(64, 4, [64] * 4, 2, 16, stacked=True)
        loader.shuffle = False
        st, thpt = m.fit(st, loader, epochs=3, verbose=False)
        assert m._last_fit_used_scan
        assert thpt > 0
        assert int(st.step) == 1 + 3 * loader.num_batches  # warmup + 3 ep


class TestRandomizedEquivalence:
    """Property sweep: for RANDOM shapes (odd table sizes, non-lane-
    compatible dims, ragged bags, epoch lengths that don't divide the
    inner block), the four execution modes — dense autodiff, sparse
    updates, epoch cache on/off — must agree on the training result.
    Hits build_cache's no-win branch, sentinel padding, pack rounding,
    and chunk-boundary logic at configurations the targeted tests don't
    enumerate."""

    @pytest.mark.parametrize("seed", range(6))
    def test_modes_agree(self, seed):
        from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

        prng = np.random.default_rng(100 + seed)
        tables = int(prng.integers(2, 5))
        rows = int(prng.integers(17, 300))
        d = int(prng.choice([4, 8, 12, 16, 24]))  # 12/24: not 128-compat
        bag = int(prng.integers(1, 4))
        batch = int(prng.choice([8, 16]))
        nb = int(prng.integers(3, 9))
        inner = int(prng.choice([0, 2, 3]))
        # small chunk so the chunked-epoch dispatch (equalized chunks +
        # remainder folding) actually triggers at these nb values
        chunk = int(prng.choice([0, 2, 4]))

        cfg = DLRMConfig(sparse_feature_size=d,
                         embedding_size=[rows] * tables,
                         embedding_bag_size=bag,
                         mlp_bot=[4, 8, d],
                         mlp_top=[d * tables + d, 8, 1])
        inputs = {"dense": prng.standard_normal(
            (nb, batch, 4)).astype(np.float32),
            "sparse": prng.integers(0, rows, size=(nb, batch, tables, bag),
                                    dtype=np.int64)}
        labels = prng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)

        results = {}
        for mode, cache, view, packed in (
                ("on", "on", "off", "off"), ("on", "on", "on", "off"),
                ("on", "on", "off", "on"), ("on", "off", "off", "on"),
                ("on", "off", "off", "off"), ("off", "off", "off", "off")):
            fc = ff.FFConfig(batch_size=batch,
                             sparse_embedding_updates=mode,
                             epoch_row_cache=cache,
                             epoch_cache_view=view,
                             packed_tables=packed,
                             epoch_cache_inner=inner,
                             epoch_cache_chunk=chunk)
            m = build_dlrm(cfg, fc)
            m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False)
            st = m.init(seed=0)
            st, mets = m.train_epoch(st, inputs, labels)
            results[(mode, cache, view, packed)] = (
                st, float(mets["loss"]), m)

        ref_st, ref_loss, ref_m = results[("off", "off", "off", "off")]
        for key, (st, loss, mm) in results.items():
            assert loss == pytest.approx(ref_loss, rel=1e-5), (key, seed)
            for opn in ref_st.params:
                for k in ref_st.params[opn]:
                    np.testing.assert_allclose(
                        mm.get_weights(st, opn, k),
                        ref_m.get_weights(ref_st, opn, k),
                        rtol=1e-5, atol=1e-6,
                        err_msg=f"{key} {opn}/{k} seed={seed}")
