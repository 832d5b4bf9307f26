"""The Compressed Convolutional Attention + MLP-routed experts language
model (ZAYA1-8B) on the CPU at a small size, seeded random weights, each
piece against the plain reference
(``benchmarks/reference/cca_moe_lm_ref.py``): the CCA sublayer and its
causality, the q-k mean, the MLP router with its state carried from
layer to layer and its skip slot, the two shares of the experts adding
up to the whole layer, the whole model through the trainer with its
tied embedding and head, the configuration's file, and the family's
comparison with faults planted in the path it times."""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.models import cca_moe_lm as family  # noqa: E402
from benchmarks.reference import cca_moe_lm_ref as ref  # noqa: E402
from dlrm_flexflow_tpu import profiling  # noqa: E402
from dlrm_flexflow_tpu.apps import cca_moe_lm as app  # noqa: E402
from dlrm_flexflow_tpu.apps import lm_common  # noqa: E402
from dlrm_flexflow_tpu.config import FFConfig  # noqa: E402
from dlrm_flexflow_tpu.model import FFModel  # noqa: E402
from dlrm_flexflow_tpu.ops import attention as attention_ops  # noqa: E402
from dlrm_flexflow_tpu.ops import moe as moe_ops  # noqa: E402

F32 = jnp.dtype("float32")
CONFIG = os.path.join(ROOT, "benchmarks/configs/zaya1-8b-ep2.json")
CELL = "zaya1-8b-ep2.pretrain-16k"


def _small(**changes):
    """Three layers (the router state handed on twice), 4 query heads on
    2 key/value heads of 8, 8 experts of which 4 held, and the skip
    slot."""
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                num_experts=8, experts_held=4, moe_intermediate_size=16,
                router_hidden_size=12, seq_len=32)
    base.update(changes)
    return app.CcaMoeLmConfig(**base)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Key blocks of 8: the blockwise core loops over four."""
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", 8)


def _hp(cfg, batch=2, **changes):
    return ref._Frozen({**family.hyper(cfg, {"batch": batch,
                                             "seq_len": cfg.seq_len}),
                        **changes})


def _compiled(cfg, batch=2, seed=0):
    model = app.build(cfg, FFConfig(batch_size=batch))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    return model, model.init(seed=seed)


def _tokens(cfg, steps, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size,
                        size=(steps, batch, cfg.seq_len + 1)).astype(np.int32)


def _out_and_grads(forward):
    """``params -> (out, d sum(sin(out)) / d params)`` as one program."""
    def loss(p):
        out = forward(p)
        return jnp.sum(jnp.sin(out)), out
    grad = jax.jit(jax.value_and_grad(loss, has_aux=True))

    def out_and_grads(p):
        (_, out), grads = grad(p)
        return out, grads
    return out_and_grads


# ------------------------------------------------------------- the CCA
def _cca(cfg, batch=2):
    model = FFModel(FFConfig(batch_size=batch))
    x = model.create_tensor((batch, cfg.seq_len, cfg.hidden_size), name="x")
    model.compressed_conv_attention(
        x, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.cca_time0, cfg.cca_time1,
        int(cfg.head_dim * cfg.partial_rotary_factor), cfg.rope_theta,
        name="cca")
    return model.layers[0]


def _cca_case(heads=4, kv=2, taps=(2, 2)):
    cfg = _small(num_attention_heads=heads, num_key_value_heads=kv,
                 cca_time0=taps[0], cca_time1=taps[1])
    op = _cca(cfg)
    params = op.init_params(jax.random.PRNGKey(5))
    # the temperature off its initial value, the two key heads apart
    params["temperature"] = jnp.array([2.5, 7.0][:kv] + [4.0] * (kv - 2))
    params = {k: (4.0 * v if k.startswith("w_") else v)
              for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    return cfg, op, params, x


@pytest.mark.parametrize("heads,kv,taps", [(4, 2, (2, 2)), (2, 2, (2, 2)),
                                           (4, 1, (3, 1))])
def test_the_cca_sublayer_is_the_references(heads, kv, taps):
    """The op's output and every parameter's gradient against the
    reference's token-by-token form: 4 query heads on 2 (the cell's
    ratio), one query head a key head (the paper's q-k mean) and one key
    head with three and one taps."""
    cfg, op, params, x = _cca_case(heads, kv, taps)
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = _out_and_grads(lambda p: op.forward(p, [x])[0])(params)
        want, want_g = _out_and_grads(lambda p: jax.vmap(
            lambda seq: ref.cca(p, seq, hp, F32))(x))(params)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=3e-5)
    for name in params:
        scale = float(jnp.max(jnp.abs(want_g[name]))) + 1e-9
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-5 * scale + 1e-8, err_msg=name)


@pytest.mark.parametrize("t", [0, 7, 8, 30])
def test_a_token_moves_no_output_before_it(t):
    """Token ``t`` changed: every output before ``t`` is the same bit
    for bit (both convolutions, the value shift and the core are
    causal), the outputs at ``t`` and at ``t + 1`` (the second tap and
    the shifted value head) move."""
    _cfg, op, params, x = _cca_case()
    run_op = jax.jit(lambda inp: op.forward(params, [inp])[0])
    before = run_op(x)
    after = run_op(x.at[:, t].add(1.0))
    np.testing.assert_array_equal(after[:, :t], before[:, :t])
    for at in {t, min(t + 1, 31)}:
        assert float(jnp.max(jnp.abs(after[:, at] - before[:, at]))) > 1e-3


def test_the_qk_mean_with_one_query_head_a_group_is_the_papers():
    """``qk_mean`` with as many query heads as key heads adds ``(q~ +
    k~) / 2`` to both; with two query heads a key head, the key gets
    the mean of its two heads' ``m``."""
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q0, k0 = (jax.random.normal(keys[0], (2, 5, 2, 8)),
              jax.random.normal(keys[1], (2, 5, 2, 8)))
    qm, km = (jax.random.normal(keys[2], (2, 5, 2, 8)),
              jax.random.normal(keys[3], (2, 5, 2, 8)))
    q, k = attention_ops.qk_mean(q0, k0, qm, km)
    np.testing.assert_allclose(q, qm + (q0 + k0) / 2, atol=1e-6)
    np.testing.assert_allclose(k, km + (q0 + k0) / 2, atol=1e-6)
    q, k = attention_ops.qk_mean(q0, k0[:, :, :1], qm, km[:, :, :1])
    m = (q0 + k0[:, :, :1]) / 2
    np.testing.assert_allclose(q, qm + m, atol=1e-6)
    np.testing.assert_allclose(k, km[:, :, :1] + jnp.mean(m, axis=2,
                                                          keepdims=True),
                               atol=1e-6)


def test_the_cca_op_at_the_published_widths_takes_the_kernels_shapes():
    """8 on 2 heads of 128 over 16,384 tokens: whole key blocks and
    lane-wide heads, so a TPU runs the Pallas core; 5,573,122
    parameters without the norm."""
    cfg = app.CcaMoeLmConfig(seq_len=16384)
    op = _cca(cfg, batch=1)
    shapes = {s.param_name: s.shape for s in op.param_specs()}
    assert shapes == {"w_q": (2048, 1024), "w_k": (2048, 256),
                      "w_v": (2048, 256), "conv0": (2, 1280),
                      "conv1": (2, 10, 128, 128), "temperature": (2,),
                      "w_o": (1024, 2048)}
    assert sum(int(np.prod(s)) for s in shapes.values()) == 5_573_122
    assert attention_ops.pallas_attention.takes(16384, 128, 128,
                                                jnp.bfloat16)
    assert op.core_form() == "plain"    # the CPU


# ------------------------------------------------- the MLP router
def _moe(cfg, held, state_in=True, batch=2):
    model = FFModel(FFConfig(batch_size=batch))
    x = model.create_tensor((batch, cfg.seq_len, cfg.hidden_size), name="x")
    r = (model.create_tensor((batch, cfg.seq_len, cfg.router_hidden_size),
                             name="r") if state_in else None)
    model.held_experts_moe(x, cfg.num_experts, cfg.moe_intermediate_size,
                           cfg.num_experts_per_tok, held, name="moe",
                           router="mlp",
                           router_hidden=cfg.router_hidden_size,
                           router_state=r)
    return model.layers[0]


@pytest.fixture(scope="module")
def uncut():
    """An expert layer that holds all 8 experts, its router drawn wide
    enough to spread 64 tokens over the 9 slots, the depth averaging at
    g = sigmoid(1.2), and a previous layer's state."""
    cfg = _small()
    op = _moe(cfg, None)
    params = op.init_params(jax.random.PRNGKey(11))
    params = {k: (20.0 * v if k.startswith("router_") else v)
              for k, v in params.items()}
    params["router_depth"] = jnp.float32(1.2)
    params["w_gate"], params["w_up"] = 8 * params["w_gate"], \
        8 * params["w_up"]
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    x = jax.random.normal(keys[0], (2, 32, cfg.hidden_size))
    r_prev = 0.5 * jax.random.normal(keys[1], (2, 32, cfg.router_hidden_size))
    return cfg, op, params, x, r_prev


def _ref_moe(params, lo=0, hi=None):
    return {**{k: v for k, v in params.items() if k.startswith("router_")},
            **{k: params[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}}


def _forward(op, params, x, r_prev):
    with jax.default_matmul_precision("highest"):
        y, r = op.forward(params, [x, r_prev], training=True,
                          state=op.init_state())
    return y, r, op._last_state


def test_the_mlp_router_with_its_state_is_the_references(uncut):
    """Output, the state handed on, the counts over the experts and the
    skip slot, against the reference's dense layer; every slot is
    chosen by some token."""
    cfg, op, params, x, r_prev = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    y, r, state = _forward(op, params, x, r_prev)
    with jax.default_matmul_precision("highest"):
        want, want_r, counts = ref.expert_layer(
            _ref_moe(params), flat, r_prev.reshape(flat.shape[0], -1),
            _hp(cfg, experts_held=cfg.num_experts), F32)
    np.testing.assert_allclose(y.reshape(flat.shape), want, atol=3e-5)
    np.testing.assert_allclose(r.reshape(want_r.shape), want_r, atol=1e-6)
    assert counts.shape == (cfg.num_experts + 1,)
    assert np.count_nonzero(counts) >= 6
    np.testing.assert_array_equal(state["tokens_per_expert"],
                                  counts[:cfg.num_experts])
    assert int(state["skipped_tokens"]) == int(counts[-1]) > 0
    assert int(state["held_assignments"]) == 64 - int(counts[-1])


def test_the_choice_follows_the_previous_layers_state(uncut):
    """The same tokens under two previous states route differently, as
    the reference does under each; with the state left out the layer
    routes as the reference with ``r_(-1) = 0``."""
    cfg, _op, params, x, r_prev = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    seen = []
    for state_in in (r_prev, -r_prev, None):
        op = _moe(cfg, None, state_in=state_in is not None)
        with jax.default_matmul_precision("highest"):
            inputs = [x] if state_in is None else [x, state_in]
            op.forward(params, inputs, training=True, state=op.init_state())
            _p, choice, _r = ref.route(
                params, flat, 0.0 if state_in is None
                else state_in.reshape(flat.shape[0], -1))
        counts = np.bincount(np.asarray(choice),
                             minlength=cfg.num_experts + 1)
        np.testing.assert_array_equal(op._last_state["tokens_per_expert"],
                                      counts[:cfg.num_experts])
        seen.append(tuple(counts))
    assert len(set(seen)) == 3


def test_a_skipped_token_leaves_its_residual_unchanged_bit_for_bit(uncut):
    """``x + experts(N(x))`` over a layer that holds half the experts: a
    token routed to the skip slot, or to an expert held elsewhere, comes
    out exactly as it went in; the others move."""
    cfg = uncut[0]
    model = FFModel(FFConfig(batch_size=2))
    x = model.create_tensor((2, 32, cfg.hidden_size), name="x")
    f, _r = model.held_experts_moe(
        model.rms_norm(x, name="norm"), cfg.num_experts,
        cfg.moe_intermediate_size, 1, (0, 4), name="moe", router="mlp",
        router_hidden=cfg.router_hidden_size)
    model.add(x, f, name="add")
    model.compile(optimizer=app.optimizer(cfg), loss_type="mean_squared_error",
                  metrics=(), mesh=False)
    state = model.init(seed=3)
    params = dict(state.params)
    params["moe"] = {k: (20.0 * v if k.startswith("router_") else v)
                     for k, v in params["moe"].items()}
    values = jax.random.normal(jax.random.PRNGKey(2), (2, 32,
                                                       cfg.hidden_size))
    state.params = params
    out = model.predict(state, {"x": values})
    op = model.get_op("moe")
    normed = values * jax.lax.rsqrt(jnp.mean(values ** 2, -1, keepdims=True)
                                    + 1e-6)
    with jax.default_matmul_precision("highest"):
        idx = op.route_mlp(normed.reshape(64, -1), params["moe"], None)[0]
    idx = np.asarray(idx[:, 0]).reshape(2, 32)
    away = idx >= 4
    assert (idx == cfg.num_experts).any() and away.any() and (~away).any()
    np.testing.assert_array_equal(np.asarray(out)[away],
                                  np.asarray(values)[away])
    assert np.all(np.any(np.asarray(out)[~away] != np.asarray(values)[~away],
                         axis=-1))


def test_the_two_expert_shares_add_up_to_the_uncut_layer(uncut):
    """The model-configs guide's share test: two chips hold experts 0-3
    and 4-7; their outputs add up to the uncut reference layer, the
    router (what both compute alike) counted once: each hands on the
    same state and counts the same routing."""
    cfg, _op, params, x, r_prev = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        want, want_r, counts = ref.expert_layer(
            _ref_moe(params), flat, r_prev.reshape(flat.shape[0], -1),
            _hp(cfg, experts_held=cfg.num_experts), F32)
    total = 0.0
    for lo in (0, 4):
        share = _moe(cfg, (lo, 4))
        mine = _ref_moe(params, lo, lo + 4)
        y, r, state = _forward(share, mine, x, r_prev)
        np.testing.assert_array_equal(state["tokens_per_expert"],
                                      counts[:cfg.num_experts])
        assert int(state["held_assignments"]) == int(counts[lo:lo + 4].sum())
        np.testing.assert_allclose(r.reshape(want_r.shape), want_r,
                                   atol=1e-6)
        total = total + y
    np.testing.assert_allclose(total.reshape(flat.shape), want, atol=3e-5)


def test_the_linear_router_is_refused_a_state():
    m = FFModel(FFConfig(batch_size=1))
    x = m.create_tensor((1, 16, 32), name="x")
    r = m.create_tensor((1, 16, 8), name="r")
    with pytest.raises(AssertionError):
        m.held_experts_moe(x, 8, 16, 1, router_state=r)


def test_the_wide_experts_tile_fits_the_scoped_memory():
    """The grouped matmul's (k, n) tile on the chip: the siblings'
    experts (2048 -> 768 -> 2048, 2560 -> 512) keep 1024 x 768 / 768 x
    1024 / 1024 x 512; 2048-wide experts halve n to 512."""
    seen = {}

    def gmm(rows, weights, group_sizes, dtype, tiling):
        seen[(rows.shape[1], weights.shape[2])] = tiling
        return jnp.zeros((rows.shape[0], weights.shape[2]), dtype)

    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_ops, "_on_tpu", lambda: True)
        mp.setattr(megablox, "gmm", gmm)
        for k, n in ((2048, 768), (768, 2048), (2560, 512), (2048, 2048)):
            moe_ops.grouped_matmul(jnp.zeros((1024, k), jnp.bfloat16),
                                   jnp.zeros((4, k, n), jnp.bfloat16),
                                   jnp.array([256] * 4, jnp.int32))
    assert seen == {(2048, 768): (512, 1024, 768),
                    (768, 2048): (512, 768, 1024),
                    (2560, 512): (512, 1024, 512),
                    (2048, 2048): (512, 1024, 512)}


# ----------------------------------------- the model through the trainer
def _program_and_reference(cfg, steps=2, batch=2, depth=None):
    """``steps`` of ``train_epoch`` and of ``ref.train_steps`` from one
    initial state (the router's depth parameters set to ``depth`` by
    layer, where given)."""
    model, state = _compiled(cfg, batch)
    if depth is not None:
        params = dict(state.params)
        for i, c in enumerate(depth):
            params[f"layer_{i}_moe"] = {**params[f"layer_{i}_moe"],
                                        "router_depth": jnp.float32(c)}
        state.params = params
    snap = family._snapshot(state, cfg)
    tokens = _tokens(cfg, steps, batch)
    inputs, labels = family._split(tokens)
    start = jax.tree_util.tree_map(jnp.copy, (snap["params"], snap["m"],
                                              snap["v"], snap["step"]))
    want = ref.train_steps(start, tokens, dict(_hp(cfg, batch)))
    state, mets = model.train_epoch(state, inputs, labels)
    return model, state, mets, want


def test_the_loss_every_gradient_and_the_update_are_the_references():
    """Two Adam steps from one state with the depth averaging drawn off
    its initial value: the mean loss, every tensor's first moment (the
    gradients and nothing else) and every updated tensor, and the
    routing's counts over the experts and the skip slot."""
    cfg = _small()
    _m, state, mets, (want_state, losses, counts) = \
        _program_and_reference(cfg, depth=(0.4, -0.8, 1.5))
    np.testing.assert_allclose(float(mets["loss"]), np.mean(losses),
                               rtol=1e-6)
    got = family._snapshot(state, cfg)
    for part, want, tol in (("params", want_state[0], 3e-6),
                            ("m", want_state[1], 1e-6)):
        flat_got = ref.leaves_by_name(got[part])
        flat_want = ref.leaves_by_name(want)
        # embed (the head's too), final norm; 3 x (2 norms + 7 CCA + 7)
        assert len(flat_got) == len(flat_want) == 2 + 3 * 16
        for name, value in flat_want.items():
            np.testing.assert_allclose(flat_got[name], value, atol=tol,
                                       err_msg=f"{part} {name}")
    for layer, name in enumerate(family._moe_ops(cfg)):
        both = np.sum([c[layer] for c in counts], axis=0)
        np.testing.assert_array_equal(
            got["counters"]["tokens_per_expert"][layer], both[:-1])
        assert int(got["counters"]["skipped_tokens"][layer]) == both[-1]
        np.testing.assert_array_equal(mets[f"{name}/skipped_tokens"],
                                      both[-1])


def test_one_step_gives_the_references_gradients():
    cfg = _small()
    _m, state, _mets, (want, *_rest) = _program_and_reference(cfg, steps=1)
    got = ref.leaves_by_name(family._snapshot(state, cfg)["m"])
    for name, value in ref.leaves_by_name(want[1]).items():
        scale = float(jnp.max(jnp.abs(value)))
        assert scale > 0, name
        np.testing.assert_allclose(got[name], value, atol=2e-5 * scale
                                   + 1e-9, err_msg=name)


def test_each_layer_routes_on_the_state_the_layer_before_handed_on():
    """The program's counts by layer are the reference's with the state
    carried, and not the reference's with it dropped before each layer:
    layer l's choice depends on layer l - 1's state."""
    cfg = _small()
    model, state = _compiled(cfg)
    params = dict(state.params)
    for i in range(cfg.num_hidden_layers):
        params[f"layer_{i}_moe"] = {
            **params[f"layer_{i}_moe"], "router_depth": jnp.float32(2.0),
            **{k: 20.0 * v for k, v in params[f"layer_{i}_moe"].items()
               if k.startswith("router_") and k != "router_depth"}}
    state.params = params
    w = family.to_reference(jax.tree_util.tree_map(jnp.copy, params), cfg)
    tokens = _tokens(cfg, 1)
    inputs, labels = family._split(tokens)
    state, mets = model.train_epoch(state, inputs, labels)
    got = [np.append(np.asarray(mets[f"{n}/tokens_per_expert"]),
                     int(mets[f"{n}/skipped_tokens"]))
           for n in family._moe_ops(cfg)]
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        _loss, carried = ref.losses(w, jnp.asarray(tokens[0]), hp, F32)
        x = w["embed"][tokens[0][:, :cfg.seq_len]]
        dropped = []
        for layer in w["layers"]:
            zeros = jnp.zeros(x.shape[:2] + (cfg.router_hidden_size,))
            x, _r, c = jax.vmap(lambda seq, st: ref.decoder_layer(
                layer, seq, st, hp, F32))(x, zeros)
            dropped.append(np.sum(c, axis=0))
    for layer, counts in enumerate(got):
        np.testing.assert_array_equal(counts, carried[layer])
    np.testing.assert_array_equal(got[0], dropped[0])
    assert any(not np.array_equal(got[i], dropped[i])
               for i in range(1, cfg.num_hidden_layers))


def test_recomputation_changes_no_number():
    runs = []
    for recompute in (True, False):
        cfg = _small(recompute=recompute)
        model, state = _compiled(cfg)
        inputs, labels = family._split(_tokens(cfg, 2))
        state, mets = model.train_epoch(state, inputs, labels)
        runs.append((state, mets, model))
    tags = {op.recompute for op in runs[0][2].layers if op.recompute}
    assert {"layer_0_mixer", "layer_0_ffn_half", "layer_2_mixer"} <= tags
    assert not any(op.recompute for op in runs[1][2].layers)
    assert float(runs[0][1]["loss"]) == float(runs[1][1]["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(runs[0][0].params),
                    jax.tree_util.tree_leaves(runs[1][0].params)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)


def test_the_head_is_the_embeddings_own_table():
    """One (V, d) matrix, one gradient (the lookup's and the head's
    summed), one pair of Adam moments; the head holds no parameter."""
    cfg = _small()
    model, state = _compiled(cfg)
    head = model.get_op("lm_head")
    assert head.params_of == "embed" and head.param_specs() == []
    assert "lm_head" not in state.params
    assert state.params["embed"]["embedding"].shape == (96, 32)
    assert "lm_head" not in state.opt_state["m"]
    assert model._sparse_emb_ops == []
    # drawn at initializer_range, as the head (the configuration's
    # assumed.tied_embedding_scale says why)
    assert float(jnp.std(state.params["embed"]["embedding"])) \
        == pytest.approx(cfg.initializer_range, rel=0.05)
    assert app.token_loss is lm_common.token_loss
    assert app.optimizer is lm_common.optimizer


def test_every_scope_of_the_compiled_step_is_attributed():
    """The optimized HLO of the tiny model's ``train_epoch``: every
    ``ff.lm.*`` scope of the family is there, CCA's forward, recomputed
    and backward, and the family's groups hold every phase found."""
    from benchmarks.lib import phases

    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    text = model._train_epoch.lower(state, inputs,
                                    labels).compile().as_text()
    found = set(profiling.hlo_phases(text).values())
    for scope in ("ff.lm.embed", "ff.lm.cca.proj", "ff.lm.cca.mix",
                  "ff.lm.cca.core", "ff.lm.cca.out", "ff.lm.moe.route",
                  "ff.lm.moe.dispatch", "ff.lm.moe.experts",
                  "ff.lm.moe.combine", "ff.lm.head", "ff.step.dense_update"):
        assert scope in found or scope + ".bwd" in found, scope
    for scope in ("ff.lm.cca.proj", "ff.lm.cca.mix"):
        assert scope + ".remat" in found, scope
        assert scope + ".bwd" in found, scope
    assert "ff.lm.cca.core.bwd" in found
    groups = {phases.group_of(p, family.PHASE_GROUPS)
              for p in found - {profiling.UNATTRIBUTED}}
    assert None not in groups
    assert {"attn", "moe", "head", "dense_update"} <= groups


def test_the_op_counters_event_carries_the_skip_count():
    from dlrm_flexflow_tpu.telemetry import event_log
    from dlrm_flexflow_tpu.telemetry.schema import validate_event
    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    with event_log() as log:
        model.train_epoch(state, inputs, labels)
    counted = [e for e in log.events() if e["type"] == "op_counters"]
    assert len(counted) == 3 and validate_event(counted[0]) == []
    for event in counted:
        c = event["counters"]
        assert len(c["tokens_per_expert"]) == 8
        assert int(np.sum(c["tokens_per_expert"])) + int(
            c["skipped_tokens"]) == 2 * 2 * 32


# ------------------------------------------------ the configuration's file
def test_the_configuration_file_holds_the_catalogs_numbers():
    """Every number of the catalog's ZAYA1-8B ``config`` under its own
    key, the nested groups whole, but the three keys ``reduced`` names;
    the cut, the deployment and the assumptions stated beside them."""
    config = json.load(open(CONFIG))
    catalog = {"attention_bias": False, "cca_time0": 2, "cca_time1": 2,
               "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
               "lm_head_bias": False, "max_position_embeddings": 131072,
               "model_type": "zaya", "moe_intermediate_size": 2048,
               "num_attention_heads": 8, "num_experts": 16,
               "num_experts_per_tok": 1, "num_key_value_heads": 2,
               "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
               "router_hidden_size": 256, "sliding_window": None,
               "tie_word_embeddings": True}
    assert {k: config[k] for k in catalog} == catalog
    assert config["layer_types"] == ["hybrid"] * 40
    assert config["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert config["overrides"] == {}   # the rehearsal's alone
    assert (config["num_hidden_layers"], config["experts_held"],
            config["first_expert_held"], config["vocab_size"]) \
        == (4, 8, 0, 32784)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "experts_held": 16, "vocab_size": 262272}
    assert config["vocab_size"] * 8 == 262272
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_size"]
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] == 8 == dep["vocabulary_parallel"] \
        == dep["expert_parallel"] * dep["data_parallel"]
    for name in ("optimizer", "initializer_range", "tied_embedding_scale",
                 "cca_value_shift", "cca_qk_mean", "cca_convolutions",
                 "cca_normalisation", "router", "no_balancing",
                 "no_scale_residual_merge", "seq_len", "compute_dtype",
                 "recompute"):
        assert config["assumed"][name], name
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in bench["configs"] if c["name"] == "zaya1-8b-ep2"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic", cell["traffic"] + ".json")))
    # the sibling's 16k mix under a name of this family's own (a mix
    # serves one family: tests/benchmark's ``lay_tiny``)
    assert mix == json.load(open(os.path.join(
        ROOT, "benchmarks/traffic/pretrain-16k.json")))


def _sizes(cfg):
    model = app.build(cfg, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    shapes = jax.eval_shape(lambda: model.init(seed=0))
    return model, {name: sum(int(np.prod(a.shape)) for a in p.values())
                   for name, p in shapes.params.items()}


def test_the_cells_size_has_494_5_million_parameters():
    config = json.load(open(CONFIG))
    cfg = family.model_config(config, {"seq_len": 16384})
    assert (cfg.num_experts, cfg.experts_held, cfg.rope_theta,
            cfg.partial_rotary_factor) == (16, 8, 5000000, 0.5)
    model, by_op = _sizes(cfg)
    assert by_op["layer_0_cca"] + by_op["layer_0_cca_norm"] == 5_575_170
    assert by_op["layer_0_moe"] - 8 * 3 * 2048 * 2048 \
        + by_op["layer_0_moe_norm"] == 596_225
    assert by_op["embed"] == 32784 * 2048
    assert sum(by_op.values()) == 494_482_444
    assert model._program_fields["attention_core"] == {"pallas": 0,
                                                       "plain": 4}


def test_without_a_share_it_is_the_whole_published_language_model():
    """All 16 experts, 40 layers and the whole vocabulary, shapes alone:
    8.30B parameters outside the embedding (ZAYA1-base's "8.3B-A0.8B"),
    0.75B of them active a token."""
    config = json.load(open(CONFIG))
    whole = {**config, **config["published"], **config["train"],
             "experts_held": None, "seq_len": 4096}
    cfg = app.CcaMoeLmConfig.from_dict(whole)
    _model, by_op = _sizes(cfg)
    layer = by_op["layer_0_cca"] + by_op["layer_0_cca_norm"] \
        + by_op["layer_0_moe"] + by_op["layer_0_moe_norm"]
    assert layer == 5_575_170 + 596_225 + 16 * 3 * 2048 * 2048
    outside = sum(by_op.values()) - by_op["embed"]
    assert outside == 40 * layer + 2048
    assert 8.25e9 < outside < 8.35e9
    active = outside - 40 * 15 * 3 * 2048 * 2048
    assert 0.74e9 < active < 0.77e9


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", False),
    ("sliding_window", 4096), ("lm_head_bias", True)])
def test_a_variant_that_is_not_built_is_refused_by_name(key, value):
    config = json.load(open(CONFIG))
    with pytest.raises(ValueError, match=key):
        app.CcaMoeLmConfig.from_dict({**config, key: value})


def test_a_sliding_window_layer_is_refused_by_name():
    cfg = _small(layer_types=("hybrid", "hybrid_sliding", "hybrid"))
    with pytest.raises(ValueError, match=r"layer_types\[1\]"):
        app.build(cfg, FFConfig(batch_size=1))


def test_the_flop_count_against_a_hand_count():
    """``train_flops_per_sample`` at the cell's sizes by hand, and the
    core's count."""
    cell = run.resolve(ROOT, CELL)
    config, traffic = cell["config"], cell["traffic"]
    s = 16384
    cca = 2048 * 1536 + 1024 * 2048 + 2 * 10 * 128 * 128
    router = 2048 * 256 + 256 * 256 + 256 * 17
    experts = 3 * 2048 * 2048 * 8 / 17
    matmuls = 6 * s * (4 * (cca + router + experts) + 2048 * 32784)
    core = 4 * 3 * s * s * 8 * 256
    got = family.train_flops_per_sample(config, traffic)
    assert got == pytest.approx(matmuls + core, rel=1e-9)
    assert got == pytest.approx(17.8e12, rel=0.01)
    flops, nbytes = family.cca_core_work(config, traffic)
    assert flops == pytest.approx(3.5 * s * s * 8 * 256)
    assert nbytes == 3 * s * 128 * 20 * 2
    assert family.cca_layers(config, traffic) \
        == family.moe_layers(config, traffic) == 4
    assert traffic["batches"] * traffic["dispatches"] \
        * traffic["epochs_per_dispatch"] == 8


# ------------------------- the family's comparison, with faults planted
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's real files at the rehearsal's size (as
    ``tests/benchmark`` lays them), and the staged driver."""
    cell = run.resolve(ROOT, CELL)
    tiny = json.load(open(os.path.join(
        ROOT, "tests/benchmark/tiny.cca_moe_lm.json")))
    config, traffic = copy.deepcopy(cell["config"]), dict(cell["traffic"])
    for key, block in tiny.items():
        if key not in ("about", "traffic"):
            config[key].update(block)
    traffic.update(tiny["traffic"])
    return config, traffic, run.load_file(cell["driver"])


def _check(tiny_cell, run_steps, seed=7):
    config, traffic, driver = tiny_cell
    model, state = family.build(config, traffic, seed, None)
    ok, report, _ = family.check(config, traffic, model, state, seed,
                                 run_steps or driver.check_steps,
                                 traffic["check_batches"])
    return ok, report


def test_the_sound_path_is_correct(tiny_cell):
    ok, report = _check(tiny_cell, None)
    assert ok, report
    assert report["counter_slack"] == 0 and report["count_err"] == 0
    assert report["grad_err_max"] < 1e-4 and report["update_err"] < 1e-4


def test_the_seed_makes_the_data_and_not_the_weights(tiny_cell):
    """Two seeds: the same weights (``train.weights_seed``), so the same
    routing function and work a step, over other token ids."""
    config, traffic, _driver = tiny_cell
    (_m, one), (_m2, two) = (family.build(config, traffic, seed, None)
                             for seed in (5, 2 ** 31 + 11))
    for a, b in zip(jax.tree_util.tree_leaves(one.params),
                    jax.tree_util.tree_leaves(two.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert config["train"]["weights_seed"] == 0
    ids_one, ids_two = (family.make_dataset(config, traffic, seed)[0]["ids"]
                        for seed in (5, 2 ** 31 + 11))
    assert ids_one.shape == ids_two.shape
    assert not np.array_equal(ids_one, ids_two)


def _lagged_fault(monkeypatch, which):
    """``ops/attention.py::lagged`` with call ``which`` of the five a
    CCA forward makes (0: the value shift; 1, 2: the depthwise
    convolution's taps; 3, 4: the grouped one's) broken: the shift
    removed (0), a tap's rows zeroed (2)."""
    whole, calls = attention_ops.lagged, [0]

    def lagged(x, lag):
        at, calls[0] = calls[0] % 5, calls[0] + 1
        if at == which == 0:
            return x
        if at == which:
            return jnp.zeros_like(x)
        return whole(x, lag)

    jax.clear_caches()
    monkeypatch.setattr(attention_ops, "lagged", lagged)


@pytest.mark.parametrize("fault,over", [
    ("state_unchanged", {"update_err", "grad_err_max"}),
    ("half_batch", {"grad_err_max", "counter_slack"}),
    ("expert_dropped", {"grad_err_max"}),
    ("conv_tap_dropped", {"grad_err_cca_max"}),
    ("shift_removed", {"grad_err_cca_max"})])
def test_a_planted_fault_is_not_correct(tiny_cell, fault, over, monkeypatch):
    """The timed path broken underneath the comparison: the state
    returned unchanged; half of every batch left out; one held expert's
    rows zeroed behind the grouped matmul; the depthwise convolution's
    second tap dropped; the value shift removed."""
    real = tiny_cell[2].check_steps

    def steps(model, state, inputs, labels):
        if fault == "state_unchanged":
            kept = jax.tree_util.tree_map(jnp.copy, state)
            return kept, real(model, state, inputs, labels)[1]
        if fault == "half_batch":
            inputs = {k: v[:, :1] for k, v in inputs.items()}
            labels = labels[:, :1]
        return real(model, state, inputs, labels)

    if fault == "expert_dropped":
        whole = moe_ops.grouped_matmul

        def dropping(rows, weights, group_sizes):
            out = whole(rows, weights, group_sizes)
            start = group_sizes[0]
            at = jnp.arange(rows.shape[0])[:, None]
            lost = (at >= start) & (at < start + group_sizes[1])
            return jnp.where(lost, 0.0, out)

        monkeypatch.setattr(moe_ops, "grouped_matmul", dropping)
    if fault == "conv_tap_dropped":
        _lagged_fault(monkeypatch, 2)
    if fault == "shift_removed":
        _lagged_fault(monkeypatch, 0)
    ok, report = _check(tiny_cell, steps)
    jax.clear_caches()
    assert not ok, report
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= over, report
    if fault == "expert_dropped":
        # held expert 1's tensors lose their whole gradient (the router's
        # scalar depth parameter may read higher: its gradient is a sum
        # over the tokens of terms that cancel)
        assert any(".moe.w_" in name and name.endswith(".1")
                   and err == pytest.approx(1.0, abs=1e-3)
                   for name, err in report["grad_worst_five"])
    if fault in ("conv_tap_dropped", "shift_removed"):
        assert report["grad_err_cca_max"] > 0.5


def test_the_control_one_precision_down_is_not_correct(tiny_cell):
    """The chip's arrangement at the rehearsal's size: the program in
    bfloat16 against the bfloat16 reference stays inside every limit
    that no selection moves, and ``control_steps`` (the reference in the
    program's place with float8 operands) does not.  (At 64 tokens a
    step one token that the two sides route apart, a near tie of the
    scores at the initial weights, moves the per-tensor errors of the
    router and of an expert far: the limits were read at the published
    widths, PERF.md section 4.)  The control writes a whole state back,
    and the program trains on from it."""
    config, traffic, driver = tiny_cell
    config = copy.deepcopy(config)
    config["ffconfig"]["compute_dtype"] = "bfloat16"
    cell = (config, traffic, driver)
    assert family.LOWER["bfloat16"] == "float8_e4m3fn"
    _ok, report = _check(cell, None)
    unmoved = ("grad_err_cca_max", "grad_err_all", "update_err", "loss_err",
               "counter_slack")
    assert all(report[name] <= family.LIMITS[name] for name in unmoved), \
        report
    ok, report = _check(cell, family.control_steps(config))
    assert not ok, report
    assert report["counter_slack"] == 0
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= {"grad_err_all", "update_err"}
    model, state = family.build(config, traffic, 3, None)
    inputs, labels = family._split(family._sequences(
        config, traffic, 2 * traffic["batch"], 3, 1).reshape(
            2, traffic["batch"], -1))
    state, _ = family.control_steps(config)(model, state, inputs, labels)
    assert int(state.step) == 2
    state, losses = driver.check_steps(model, state, inputs, labels)
    assert np.isfinite(float(losses[0])) and int(state.step) == 4
