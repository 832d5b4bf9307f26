"""Op-level numerical tests vs torch/numpy references.

TPU-native tier-1 equivalent of the reference op unit tests
(reference: src/ops/tests/test_harness.py — Linear/Concat/BatchMatmul/
Transpose/Reshape/Tanh tests asserting allclose vs PyTorch within epsilon).
Instead of files + subprocesses, each test builds a one-op FFModel, runs
forward (and gradients where the reference checks backward) and compares
against torch on the same data.
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import sdpa

ATOL = 1e-4
RTOL = 1e-4


def one_op_model(build, input_specs, batch=8):
    """Build a model with given inputs; build(model, tensors) -> output."""
    m = ff.FFModel(ff.FFConfig(batch_size=batch))
    tensors = [m.create_tensor(shape, dtype, name=f"in{i}")
               for i, (shape, dtype) in enumerate(input_specs)]
    build(m, tensors)
    return m, tensors


def run_forward(m, feeds):
    m.compile(loss_type="mean_squared_error", metrics=())
    state = m.init(seed=0)
    return np.asarray(m.forward(state, feeds)), state


class TestLinear:
    def test_forward_vs_torch(self, rng):
        x = rng.standard_normal((8, 32), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.dense(ts[0], 16), [((8, 32), "float32")])
        out, state = run_forward(m, {"in0": x})
        w = m.get_weights(state, "dense", "kernel")
        b = m.get_weights(state, "dense", "bias")
        ref = torch.nn.functional.linear(torch.from_numpy(x),
                                         torch.from_numpy(w.T),
                                         torch.from_numpy(b)).numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_grad_vs_torch(self, rng):
        """Backward parity (reference linear.cu:616-634 3-gemm backward)."""
        x = rng.standard_normal((4, 8), dtype=np.float32)
        w = rng.standard_normal((8, 5), dtype=np.float32)
        b = rng.standard_normal((5,), dtype=np.float32)
        y = rng.standard_normal((4, 5), dtype=np.float32)

        def loss(params):
            out = jax.nn.relu(jnp.asarray(x) @ params["w"] + params["b"])
            return jnp.mean(jnp.sum((out - y) ** 2, axis=1))

        g = jax.grad(loss)({"w": jnp.asarray(w), "b": jnp.asarray(b)})

        xt = torch.from_numpy(x)
        wt = torch.from_numpy(w).requires_grad_()
        bt = torch.from_numpy(b).requires_grad_()
        out = torch.relu(xt @ wt + bt)
        torch.sum((out - torch.from_numpy(y)) ** 2, dim=1).mean().backward()
        np.testing.assert_allclose(np.asarray(g["w"]), wt.grad.numpy(),
                                   atol=ATOL, rtol=RTOL)
        np.testing.assert_allclose(np.asarray(g["b"]), bt.grad.numpy(),
                                   atol=ATOL, rtol=RTOL)


class TestEmbedding:
    def test_bag_sum_vs_torch(self, rng):
        ids = rng.integers(0, 50, size=(8, 4), dtype=np.int64)
        m, _ = one_op_model(lambda m, ts: m.embedding(ts[0], 50, 16, aggr="sum"),
                            [((8, 4), "int64")])
        out, state = run_forward(m, {"in0": ids})
        table = m.get_weights(state, "embedding", "embedding")
        bag = torch.nn.EmbeddingBag(50, 16, mode="sum")
        with torch.no_grad():
            bag.weight.copy_(torch.from_numpy(table))
        ref = bag(torch.from_numpy(ids)).detach().numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_bag_avg(self, rng):
        ids = rng.integers(0, 20, size=(4, 3), dtype=np.int64)
        m, _ = one_op_model(lambda m, ts: m.embedding(ts[0], 20, 8, aggr="avg"),
                            [((4, 3), "int64")])
        out, state = run_forward(m, {"in0": ids})
        table = m.get_weights(state, "embedding", "embedding")
        ref = table[ids].mean(axis=1)
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_scatter_add_grad(self, rng):
        """Backward = scatter-add of output grads into looked-up rows
        (reference embedding.cu:199-224 atomicAdd kernel)."""
        ids = np.array([[0, 1], [1, 1]], dtype=np.int64)
        table = rng.standard_normal((3, 4), dtype=np.float32)

        def f(tbl):
            return jnp.sum(jnp.take(tbl, jnp.asarray(ids), axis=0))

        g = np.asarray(jax.grad(f)(jnp.asarray(table)))
        expected = np.zeros_like(table)
        for row in ids.flatten():
            expected[row] += 1.0
        np.testing.assert_allclose(g, expected)

    def test_stacked_matches_separate(self, rng):
        ids = rng.integers(0, 30, size=(6, 4, 2), dtype=np.int64)
        m, _ = one_op_model(
            lambda m, ts: m.stacked_embedding(ts[0], 4, 30, 8, aggr="sum"),
            [((6, 4, 2), "int64")])
        out, state = run_forward(m, {"in0": ids})
        tables = m.get_weights(state, "stacked_embedding", "embedding")
        for t in range(4):
            ref = tables[t][ids[:, t]].sum(axis=1)
            np.testing.assert_allclose(out[:, t], ref, atol=ATOL, rtol=RTOL)


class TestShapeOps:
    def test_concat(self, rng):
        a = rng.standard_normal((4, 3), dtype=np.float32)
        b = rng.standard_normal((4, 5), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.concat(ts, axis=1),
                            [((4, 3), "float32"), ((4, 5), "float32")])
        out, _ = run_forward(m, {"in0": a, "in1": b})
        np.testing.assert_allclose(out, np.concatenate([a, b], axis=1))

    def test_split_roundtrip(self, rng):
        x = rng.standard_normal((4, 8), dtype=np.float32)
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        t = m.create_tensor((4, 8), name="in0")
        parts = m.split(t, [3, 5], axis=1)
        m.concat(parts, axis=1)
        out, _ = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, x)

    def test_batch_matmul_vs_torch(self, rng):
        a = rng.standard_normal((2, 3, 4), dtype=np.float32)
        b = rng.standard_normal((2, 4, 5), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.batch_matmul(ts[0], ts[1]),
                            [((2, 3, 4), "float32"), ((2, 4, 5), "float32")])
        out, _ = run_forward(m, {"in0": a, "in1": b})
        ref = torch.bmm(torch.from_numpy(a), torch.from_numpy(b)).numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_transpose_default_last_two(self, rng):
        x = rng.standard_normal((2, 3, 4), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.transpose(ts[0]),
                            [((2, 3, 4), "float32")])
        out, _ = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, np.swapaxes(x, -1, -2))

    def test_reshape_reverse_flat(self, rng):
        x = rng.standard_normal((2, 3, 4), dtype=np.float32)
        m = ff.FFModel(ff.FFConfig(batch_size=2))
        t = m.create_tensor((2, 3, 4), name="in0")
        r = m.reshape(t, (2, 12))
        rv = m.reverse(r, axis=1)
        m.flat(rv)
        out, _ = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, x.reshape(2, 12)[:, ::-1])


class TestElementwise:
    @pytest.mark.parametrize("fn,np_fn", [
        ("add", np.add), ("sub", np.subtract), ("mul", np.multiply),
        ("div", np.divide)])
    def test_binary(self, rng, fn, np_fn):
        a = rng.standard_normal((4, 5), dtype=np.float32)
        b = rng.standard_normal((4, 5), dtype=np.float32) + 2.0
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        ts = [m.create_tensor((4, 5), name=f"in{i}") for i in range(2)]
        getattr(m, {"add": "add", "sub": "subtract", "mul": "multiply",
                    "div": "divide"}[fn])(ts[0], ts[1])
        out, _ = run_forward(m, {"in0": a, "in1": b})
        np.testing.assert_allclose(out, np_fn(a, b), atol=ATOL, rtol=RTOL)

    @pytest.mark.parametrize("fn,ref", [
        ("relu", lambda x: np.maximum(x, 0)),
        ("sigmoid", lambda x: 1 / (1 + np.exp(-x))),
        ("tanh", np.tanh),
        ("exp", np.exp),
    ])
    def test_unary(self, rng, fn, ref):
        x = rng.standard_normal((4, 5), dtype=np.float32)
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        t = m.create_tensor((4, 5), name="in0")
        getattr(m, fn)(t)
        out, _ = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, ref(x), atol=ATOL, rtol=RTOL)

    def test_scalar_ops(self, rng):
        x = rng.standard_normal((4, 5), dtype=np.float32)
        m = ff.FFModel(ff.FFConfig(batch_size=4))
        t = m.create_tensor((4, 5), name="in0")
        y = m.scalar_multiply(t, 3.0)
        m.scalar_add(y, 1.0)
        out, _ = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, x * 3.0 + 1.0, atol=ATOL, rtol=RTOL)


class TestConvPool:
    def test_conv2d_vs_torch(self, rng):
        x = rng.standard_normal((2, 3, 8, 8), dtype=np.float32)
        m, _ = one_op_model(
            lambda m, ts: m.conv2d(ts[0], 4, 3, 3, 1, 1, 1, 1),
            [((2, 3, 8, 8), "float32")])
        out, state = run_forward(m, {"in0": x})
        k = m.get_weights(state, "conv2d", "kernel")  # HWIO
        b = m.get_weights(state, "conv2d", "bias")
        kt = torch.from_numpy(np.transpose(k, (3, 2, 0, 1)))  # OIHW
        ref = torch.nn.functional.conv2d(torch.from_numpy(x), kt,
                                         torch.from_numpy(b), stride=1,
                                         padding=1).numpy()
        np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)

    def test_pool2d_max_vs_torch(self, rng):
        x = rng.standard_normal((2, 3, 8, 8), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.pool2d(ts[0], 2, 2, 2, 2, 0, 0),
                            [((2, 3, 8, 8), "float32")])
        out, _ = run_forward(m, {"in0": x})
        ref = torch.nn.functional.max_pool2d(torch.from_numpy(x), 2).numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_maxpool_mask_backward_matches_sas_and_torch(self, rng):
        """The equality-mask maxpool backward (ops/conv.py::_maxpool —
        replaces select_and_scatter, 7.4% of Inception busy) must match
        autodiff's select_and_scatter gradient on continuous data and
        torch's max_pool2d gradient, across overlapping/strided/padded
        window configs (reference pool_2d.cu:510 semantics)."""
        import jax
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.ops.conv import _maxpool, _maxpool_reduce

        for (k, s, p, h, w) in [((3, 3), (2, 2), (0, 0), 13, 15),
                                ((3, 3), (1, 1), (1, 1), 9, 9),
                                ((2, 2), (2, 2), (0, 0), 8, 8)]:
            x = rng.standard_normal((2, 3, h, w), dtype=np.float32)
            xj = jnp.asarray(x)
            gm = jax.grad(lambda v: jnp.sum(jnp.sin(
                _maxpool(v, k, s, p))))(xj)
            gs = jax.grad(lambda v: jnp.sum(jnp.sin(
                _maxpool_reduce(v, k, s, p))))(xj)
            np.testing.assert_allclose(np.asarray(gm), np.asarray(gs),
                                       rtol=1e-6, atol=1e-6,
                                       err_msg=str((k, s, p)))
            xt = torch.from_numpy(x).requires_grad_(True)
            yt = torch.nn.functional.max_pool2d(
                xt, k, stride=s, padding=p)
            torch.sin(yt).sum().backward()
            np.testing.assert_allclose(np.asarray(gm),
                                       xt.grad.numpy(),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=str((k, s, p)))

    def test_pool2d_avg_vs_torch(self, rng):
        x = rng.standard_normal((2, 3, 8, 8), dtype=np.float32)
        m, _ = one_op_model(
            lambda m, ts: m.pool2d(ts[0], 2, 2, 2, 2, 0, 0, pool_type="avg"),
            [((2, 3, 8, 8), "float32")])
        out, _ = run_forward(m, {"in0": x})
        ref = torch.nn.functional.avg_pool2d(torch.from_numpy(x), 2).numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_batchnorm_train_vs_torch(self, rng):
        x = rng.standard_normal((4, 3, 5, 5), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.batch_norm(ts[0]),
                            [((4, 3, 5, 5), "float32")])
        m.compile(loss_type="mean_squared_error", metrics=())
        state = m.init(seed=0)
        # training-mode forward uses batch stats
        vals, _ = m._apply(state.params, {"in0": jnp.asarray(x)},
                           training=True, rng=jax.random.PRNGKey(0),
                           bn_state=state.bn_state)
        out = np.asarray(vals[m.final_tensor.uid])
        bn = torch.nn.BatchNorm2d(3, eps=1e-5)
        bn.train()
        ref = bn(torch.from_numpy(x)).detach().numpy()
        np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


class TestSoftmaxDropout:
    def test_softmax_vs_torch(self, rng):
        x = rng.standard_normal((4, 10), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.softmax(ts[0]),
                            [((4, 10), "float32")])
        out, _ = run_forward(m, {"in0": x})
        ref = torch.softmax(torch.from_numpy(x), dim=-1).numpy()
        np.testing.assert_allclose(out, ref, atol=ATOL, rtol=RTOL)

    def test_dropout_eval_identity_train_scales(self, rng):
        x = np.ones((64, 64), dtype=np.float32)
        m, _ = one_op_model(lambda m, ts: m.dropout(ts[0], rate=0.5),
                            [((64, 64), "float32")])
        out, state = run_forward(m, {"in0": x})
        np.testing.assert_allclose(out, x)  # eval mode: identity
        vals, _ = m._apply(state.params, {"in0": jnp.asarray(x)},
                           training=True, rng=jax.random.PRNGKey(1),
                           bn_state={})
        tr = np.asarray(vals[m.final_tensor.uid])
        kept = tr[tr != 0]
        assert np.allclose(kept, 2.0)  # inverted dropout scaling
        assert 0.3 < (tr == 0).mean() < 0.7


class TestAttention:
    def test_sdpa_vs_torch(self, rng):
        q = rng.standard_normal((2, 3, 8, 16), dtype=np.float32)
        k = rng.standard_normal((2, 3, 8, 16), dtype=np.float32)
        v = rng.standard_normal((2, 3, 8, 16), dtype=np.float32)
        out = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
        ref = torch.nn.functional.scaled_dot_product_attention(
            torch.from_numpy(q), torch.from_numpy(k), torch.from_numpy(v)
        ).numpy()
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    def test_sdpa_causal_vs_torch(self, rng):
        q = rng.standard_normal((1, 2, 6, 8), dtype=np.float32)
        k = rng.standard_normal((1, 2, 6, 8), dtype=np.float32)
        v = rng.standard_normal((1, 2, 6, 8), dtype=np.float32)
        out = np.asarray(sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True))
        ref = torch.nn.functional.scaled_dot_product_attention(
            torch.from_numpy(q), torch.from_numpy(k), torch.from_numpy(v),
            is_causal=True).numpy()
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


class TestHeldShares:
    """A mixer's share of the heads and an expert layer's groups (PR 37):
    the arguments' bounds and the parameter shapes they give;
    ``tests/test_kda_moe_lm.py`` has the numbers."""

    @pytest.mark.parametrize("held,want", [(None, 8), (4, 4), (1, 1)])
    def test_held_heads(self, held, want):
        from dlrm_flexflow_tpu.ops.base import held_heads
        assert held_heads(held, 8) == want

    @pytest.mark.parametrize("held", [9, -1, 0])
    def test_held_heads_outside_the_mixer_are_refused(self, held):
        from dlrm_flexflow_tpu.ops.base import held_heads
        with pytest.raises(AssertionError):
            held_heads(held, 8)

    @pytest.mark.parametrize("held,heads", [(None, 8), (2, 2)])
    def test_both_mixers_size_their_parameters_by_the_heads_held(self, held,
                                                                 heads):
        m = ff.FFModel(ff.FFConfig(batch_size=1))
        x = m.create_tensor((1, 16, 32), name="x")
        m.kimi_delta_attention(x, 8, 4, 6, heads_held=held, name="kda")
        m.latent_attention(x, 8, None, 16, 4, 2, 6, qk_norm=True,
                           gate="head_wise", heads_held=held, name="mla")
        kda, mla = m.layers
        shapes = {s.param_name: s.shape for s in kda.param_specs()}
        assert shapes["w_q"] == shapes["w_f"] == (32, heads * 4)
        assert shapes["w_v"] == shapes["w_g"] == (32, heads * 6)
        assert shapes["w_beta"] == (32, heads)
        assert shapes["conv_k"] == (4, heads * 4)
        assert shapes["a_log"] == (heads,)
        assert shapes["dt_bias"] == (heads * 4,)
        assert shapes["w_out"] == (heads * 6, 32)
        shapes = {s.param_name: s.shape for s in mla.param_specs()}
        assert shapes["w_q"] == (32, heads * 6)
        assert shapes["w_kva"] == (32, 18) and shapes["kv_norm"] == (16,)
        assert shapes["w_kvb"] == (16, heads * 10)
        assert shapes["q_head_norm"] == shapes["k_head_norm"] == (6,)
        assert shapes["w_gate"] == (32, heads)
        assert shapes["w_o"] == (heads * 6, 32)
        assert kda.outputs[0].shape == mla.outputs[0].shape == (1, 16, 32)
        assert kda.core_form() == "chunked"

    @pytest.mark.parametrize("groups,kept,top_k", [(3, 1, 2), (4, 5, 2),
                                                   (8, 1, 4), (16, 4, 2)])
    def test_groups_the_selection_cannot_fit_are_refused(self, groups, kept,
                                                         top_k):
        m = ff.FFModel(ff.FFConfig(batch_size=1))
        x = m.create_tensor((1, 16, 32), name="x")
        with pytest.raises(AssertionError):
            m.held_experts_moe(x, 16, 8, top_k, n_group=groups,
                               topk_group=kept)

    @pytest.mark.parametrize("kw", [
        {}, {"score_func": "softmax", "num_shared": 1, "shared_gated": True},
        {"n_group": 4, "topk_group": 2, "num_shared": 1}])
    def test_the_linear_router_builds_the_layer_it_built(self, kw):
        """The held-experts layer without the MLP router's arguments:
        one input, one output, the one router matrix, no skip
        counter, and an output that is the gated sum, over the selected
        experts that are held, of ``route``'s choice (plus the shared
        expert)."""
        from dlrm_flexflow_tpu.ops import moe
        from dlrm_flexflow_tpu.ops.transformer import swiglu
        m = ff.FFModel(ff.FFConfig(batch_size=2))
        x = m.create_tensor((2, 8, 16), name="x")
        m.held_experts_moe(x, 16, 8, 4, (4, 8), name="moe", **kw)
        (op,) = m.layers
        assert len(op.inputs) == len(op.outputs) == 1
        assert [s.param_name for s in op.param_specs()][:4] == [
            "router", "w_gate", "w_up", "w_down"]
        assert set(op.init_state()) == {"bias", "tokens_per_expert",
                                        *moe.COUNTERS}
        params = op.init_params(jax.random.PRNGKey(0))
        params = {k: 10.0 * v for k, v in params.items()}
        xv = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        with jax.default_matmul_precision("highest"):
            (out,) = op.forward(params, [xv], training=True,
                                state=op.init_state())
            flat = xv.reshape(-1, 16)
            idx, gates, counts = op.route(flat, params["router"],
                                          jnp.zeros(16))
            want = jnp.zeros_like(flat)
            for e in range(8):
                ffn = [params[k][e] for k in ("w_gate", "w_up", "w_down")]
                gate = jnp.sum(jnp.where(idx == 4 + e, gates, 0.0), axis=1)
                want = want + gate[:, None] * swiglu(flat, *ffn)
            if kw.get("num_shared"):
                shared = swiglu(flat, params["shared_gate"],
                                params["shared_up"], params["shared_down"])
                if kw.get("shared_gated"):
                    shared = shared * jax.nn.sigmoid(
                        flat @ params["shared_sigmoid"])
                want = want + shared
        np.testing.assert_allclose(out.reshape(flat.shape), want, atol=1e-4,
                                   rtol=1e-5)
        np.testing.assert_array_equal(op._last_state["tokens_per_expert"],
                                      counts)
        assert int(op._last_state["held_assignments"]) \
            == int(counts[4:12].sum())


class TestActivationDtype:
    """FFConfig.activation_dtype="bfloat16" (bf16 activation STORAGE
    between ops — the conv-net bandwidth lever, PERF.md round 3): the
    final output tensor stays f32, the rewrite is idempotent across
    recompiles, and the loss trajectory tracks the f32-activation run."""

    def _conv_model(self, act, softmax_final=False):
        import dlrm_flexflow_tpu as ff
        fc = ff.FFConfig(batch_size=8, compute_dtype="bfloat16",
                         activation_dtype=act)
        m = ff.FFModel(fc)
        x = m.create_tensor((8, 3, 16, 16), name="input")
        t = m.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation="relu")
        t = m.batch_norm(t, relu=True)
        t = m.pool2d(t, 2, 2, 2, 2, 0, 0, pool_type="avg")
        t = m.conv2d(t, 8, 3, 3, 1, 1, 1, 1, activation="relu")
        t = m.flat(t)
        t = m.dense(t, 10)
        if softmax_final:
            # the shape both benchmarked conv apps actually use
            # (alexnet.py/inception.py end in m.softmax)
            t = m.softmax(t)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=("accuracy",), mesh=False)
        return m

    def _losses(self, m, steps=20):
        rng = np.random.default_rng(0)
        st = m.init(seed=0)
        # one fixed batch, memorized over the steps — random labels are
        # learnable only when repeated
        inputs = {"input": rng.standard_normal(
            (8, 3, 16, 16)).astype(np.float32)}
        labels = rng.integers(0, 10, size=(8, 1)).astype(np.int32)
        out = []
        for _ in range(steps):
            st, mets = m.train_step(st, inputs, labels)
            out.append(float(mets["loss"]))
        return out

    @pytest.mark.parametrize("softmax_final", [False, True])
    def test_final_output_stays_f32_and_intermediates_flip(
            self, softmax_final):
        m = self._conv_model("bfloat16", softmax_final=softmax_final)
        inter = [t for op in m.layers for t in op.outputs]
        final = m.layers[-1].outputs[0]
        assert final.dtype == jnp.float32
        # the loss input is exempt like the final output: under the
        # fused softmax+CCE path that's the pre-softmax logits tensor
        exempt = {final.uid, m._loss_uid}
        assert all(t.dtype == jnp.bfloat16 for t in inter
                   if t.uid not in exempt)
        if softmax_final:
            logits = m.layers[-1].inputs[0]
            assert m._loss_uid == logits.uid
            assert logits.dtype == jnp.float32
        # the RUNTIME final array is f32 too (a producer that ignores
        # its declared dtype — softmax-final was the review catch —
        # would emit bf16 probabilities into the fused CCE)
        st = m.init(seed=0)
        rng = np.random.default_rng(1)
        preds = m.forward(st, {"input": rng.standard_normal(
            (8, 3, 16, 16)).astype(np.float32)})
        assert preds.dtype == jnp.float32
        # recompile with f32 restores every dtype (idempotence)
        m.config.activation_dtype = "float32"
        m.compile(optimizer=__import__(
            "dlrm_flexflow_tpu").SGDOptimizer(lr=0.05),
            loss_type="sparse_categorical_crossentropy",
            metrics=("accuracy",), mesh=False)
        assert all(t.dtype == jnp.float32 for t in inter)

    def test_newly_exempt_loss_input_is_restored(self):
        """A tensor bf16-flipped by one compile must return to f32 when
        a recompile makes it the loss input (advisor r3): mse on a
        softmax-final graph reads the softmax output, so the pre-softmax
        logits are a plain intermediate (bf16); switching to the fused
        softmax+CCE makes those logits the loss input — exempt, f32."""
        import dlrm_flexflow_tpu as ff
        m = self._conv_model("bfloat16", softmax_final=True)
        logits = m.layers[-1].inputs[0]
        assert logits.dtype == jnp.float32  # exempt under fused CCE
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        assert logits.dtype == jnp.bfloat16  # plain intermediate now
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=(), mesh=False)
        assert logits.dtype == jnp.float32  # restored on re-exemption

    def test_epoch_cache_view_validated_without_sparse_ops(self):
        """epoch_cache_view typos must fail compile even when no sparse
        embedding op exists to need a row cache (advisor r3)."""
        import dlrm_flexflow_tpu as ff
        fc = ff.FFConfig(batch_size=8)
        fc.epoch_cache_view = "one"  # typo for "on"
        m = ff.FFModel(fc)
        x = m.create_tensor((8, 4), name="input")
        t = m.dense(x, 2)
        with pytest.raises(ValueError, match="epoch_cache_view"):
            m.compile(optimizer=ff.SGDOptimizer(lr=0.1),
                      loss_type="mean_squared_error", metrics=(),
                      mesh=False)

    def test_lstm_initial_state_under_bf16_activations(self):
        """A decoder LSTM receives its initial (h, c) from encoder
        output tensors, which the bf16 rewrite flips — the recurrent
        carry must stay f32 regardless (scan requires carry-in ==
        carry-out dtypes; review-r3 era bug found by the NMT A/B)."""
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.apps.nmt import NMTConfig, build_nmt
        cfg = NMTConfig(vocab_size=128, embed_size=16, hidden_size=16,
                        num_layers=1, src_len=5, tgt_len=4)
        fc = ff.FFConfig(batch_size=4, compute_dtype="bfloat16",
                         activation_dtype="bfloat16")
        m = build_nmt(cfg, fc)
        m.compile(optimizer=ff.SGDOptimizer(lr=0.1),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=(), mesh=False)
        rng = np.random.default_rng(0)
        st = m.init(seed=0)
        inputs = {"src": rng.integers(0, 128, size=(4, 5), dtype=np.int32),
                  "tgt_in": rng.integers(0, 128, size=(4, 4),
                                         dtype=np.int32)}
        labels = rng.integers(0, 128, size=(4, 4, 1)).astype(np.int32)
        st, mets = m.train_step(st, inputs, labels)
        assert np.isfinite(float(mets["loss"]))

    def test_elementwise_final_clamped_to_f32(self):
        """Ops that pass their input dtype through uncast (elementwise,
        concat) must not leak bf16 past the exempted final tensor — the
        model clamps the final output to its declared dtype (review
        r3)."""
        import dlrm_flexflow_tpu as ff
        fc = ff.FFConfig(batch_size=8, compute_dtype="bfloat16",
                         activation_dtype="bfloat16")
        m = ff.FFModel(fc)
        x = m.create_tensor((8, 4), name="input")
        a = m.dense(x, 8, activation="relu")
        b = m.dense(x, 8, activation="relu")
        t = m.add(a, b)  # elementwise-final graph
        m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
                  loss_type="mean_squared_error", metrics=(), mesh=False)
        st = m.init(seed=0)
        rng = np.random.default_rng(2)
        preds = m.forward(st, {"input": rng.standard_normal(
            (8, 4)).astype(np.float32)})
        assert preds.dtype == jnp.float32

    @pytest.mark.parametrize("softmax_final", [False, True])
    def test_loss_trajectory_tracks_f32_activations(self, softmax_final):
        l_bf = self._losses(self._conv_model(
            "bfloat16", softmax_final=softmax_final))
        l_f32 = self._losses(self._conv_model(
            "float32", softmax_final=softmax_final))
        assert l_bf[-1] < l_bf[0]  # learns
        assert abs(l_bf[-1] - l_f32[-1]) < 0.05
