"""The MLA + routed-experts language model on the CPU at a small size,
seeded random weights, each piece against the plain reference
(``benchmarks/reference/mla_moe_lm_ref.py``): the latent attention
layer and its blockwise core, the held-experts layer and the share
cut, the MTP loss, the shared embedding and head, recomputation, the
phases of a checkpointed run, and the family's comparison with faults
planted in the path it times."""

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
from benchmarks.models import mla_moe_lm as family  # noqa: E402
from benchmarks.reference import mla_moe_lm_ref as ref  # noqa: E402
from dlrm_flexflow_tpu import profiling  # noqa: E402
from dlrm_flexflow_tpu.apps import mla_moe_lm as app  # noqa: E402
from dlrm_flexflow_tpu.config import FFConfig  # noqa: E402
from dlrm_flexflow_tpu.ops import attention as attention_ops  # noqa: E402
from dlrm_flexflow_tpu.ops import moe as moe_ops  # noqa: E402
from dlrm_flexflow_tpu.ops.attention import (LatentAttention,  # noqa: E402
                                             blockwise_causal_attention,
                                             sdpa)
from dlrm_flexflow_tpu.ops.moe import HeldExpertsMoE  # noqa: E402
from dlrm_flexflow_tpu.tensor import Tensor  # noqa: E402

F32 = jnp.dtype("float32")


def _small(**changes):
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                intermediate_size=48, moe_intermediate_size=16,
                n_routed_experts=16, experts_held=4, num_experts_per_tok=4,
                num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
                seq_len=32)
    base.update(changes)
    return app.MlaMoeLmConfig(**base)


@pytest.fixture(autouse=True)
def four_key_blocks(monkeypatch):
    """32 tokens in key blocks of 8, so that the blockwise core loops."""
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", 8)


def _hp(cfg, batch=2):
    return ref._Frozen(family.hyper(cfg, {"batch": batch,
                                          "seq_len": cfg.seq_len}))


def _compiled(cfg, batch=2, seed=0):
    model = app.build(cfg, FFConfig(batch_size=batch))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    return model, model.init(seed=seed)


def _tokens(cfg, steps, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size,
                        size=(steps, batch, cfg.seq_len + 2)).astype(np.int32)


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("block", [4, 16, 64])
def test_blockwise_core_is_the_full_softmax_core(block):
    """Forward and all three gradients, query/key width 24 against value
    width 16, at block sizes below, dividing and equal to the sequence."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (2, 3, 64, 24)) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 3, 64, 16))
    w = jax.random.normal(keys[3], (2, 3, 64, 16))
    full = lambda q, k, v: sdpa(q, k, v, causal=True)
    blocked = lambda q, k, v: blockwise_causal_attention(q, k, v,
                                                         block=block)
    np.testing.assert_allclose(blocked(q, k, v), full(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(blocked(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(full(*a) * w), (0, 1, 2))(q, k, v)
    for g, t in zip(got, want):
        np.testing.assert_allclose(g, t, atol=1e-5)


def test_blockwise_core_takes_a_block_that_does_not_divide():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 24, 8))
    np.testing.assert_allclose(
        blockwise_causal_attention(q, q, q, block=16),   # runs at 12
        sdpa(q, q, q, causal=True), atol=2e-6)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The fused core's choice and kernels as on a TPU, the kernels run
    by the Pallas interpreter."""
    from dlrm_flexflow_tpu.ops import pallas_attention
    real = pallas_attention.pl.pallas_call
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        pallas_attention.pl, "pallas_call",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    return pallas_attention


def _published_widths(seq, dtype, heads=2):
    """q (f32, as the layer hands it over), k, v at 192 / 128 wide."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (1, heads, seq, 192))
    k = jax.random.normal(keys[1], (1, heads, seq, 192)).astype(dtype)
    v = jax.random.normal(keys[2], (1, heads, seq, 128)).astype(dtype)
    return q, k, v, jax.random.normal(keys[3], (1, heads, seq, 128))


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("dtype,o_tol,g_tol", [
    ("float32", 2e-6, 2e-5), ("bfloat16", 8e-3, 4e-2)])
def test_fused_core_is_the_full_softmax_core_and_the_plain_one(
        dtype, o_tol, g_tol, kernels_interpreted, monkeypatch):
    """The Pallas kernels (interpret mode) at widths they take: 2 heads,
    4 blocks, 192 / 128 wide: output and all three gradients against
    ``sdpa(..., causal=True)`` in f32 on the operands the kernels
    multiply (the scale folded into the query before its one rounding),
    and against the plain core, whose query is rounded before the
    scale."""
    dtype = jnp.dtype(dtype)
    seq = 4 * kernels_interpreted.BLOCK
    q, k, v, w = _published_widths(seq, dtype)
    scale = 192 ** -0.5
    core = lambda q, k, v: blockwise_causal_attention(
        q, k, v, scale, compute_dtype=dtype)
    out = lambda f, *a: (f(*a), *jax.grad(
        lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(*a))
    assert _pallas_calls(core, q, k, v) == 1
    got = out(core, q, k, v)
    assert got[0].dtype == jnp.float32 and got[2].dtype == dtype

    def full(q, k, v):   # q: what the kernels multiply, in f32
        with jax.default_matmul_precision("highest"):
            return sdpa(q, k.astype(F32), v.astype(F32), causal=True,
                        scale=1.0)
    folded = (q * scale).astype(dtype).astype(F32)
    want = out(full, folded, k, v)
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: False)
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", 512)
    plain_core = lambda q, k, v: blockwise_causal_attention(   # a new trace
        q, k, v, scale, compute_dtype=dtype)
    assert _pallas_calls(plain_core, q, k, v) == 0
    plain = out(plain_core, q, k, v)
    for name, g, t, p, tol, unit in zip(
            ("o", "dq", "dk", "dv"), got, want, plain,
            (o_tol, g_tol, g_tol, g_tol), (1.0, scale, 1.0, 1.0)):
        # the reference's dq is the folded query's: scale times the query's
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(t, np.float32) * unit,
                                   atol=tol, err_msg=name)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(p, np.float32),
                                   atol=2 * tol, err_msg=name + " / plain")


@pytest.mark.parametrize("on_tpu,seq,qk,vd,form", [
    (True, 2048, 192, 128, "pallas"),
    (True, 2048 + 256, 192, 128, "plain"),   # the blocks do not divide S
    (True, 12, 192, 128, "plain"),
    (True, 2048, 24, 16, "plain"),           # widths Mosaic does not take
    (False, 2048, 192, 128, "plain"),        # another backend
])
def test_the_core_form_is_chosen_from_backend_and_shapes(
        on_tpu, seq, qk, vd, form, monkeypatch):
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: on_tpu)
    assert attention_ops.core_form(seq, qk, vd, jnp.bfloat16) == form
    q = jax.ShapeDtypeStruct((1, 2, seq, qk), jnp.float32)
    v = jax.ShapeDtypeStruct((1, 2, seq, vd), jnp.float32)
    calls = _pallas_calls(
        lambda q, k, v: blockwise_causal_attention(
            q, k, v, compute_dtype=jnp.bfloat16), q, q, v)
    assert calls == (form == "pallas")


def test_a_sequence_the_blocks_do_not_divide_runs_the_plain_core(
        monkeypatch):
    """On a TPU's choice, 192 / 128 wide, S = 2.5 blocks: the plain core
    runs (no kernel in the jaxpr) and is still the full softmax core."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", 512)
    q, k, v, _ = _published_widths(1280, F32, heads=1)
    core = lambda q, k, v: blockwise_causal_attention(q, k, v)
    assert attention_ops.core_form(1280, 192, 128, F32) == "plain"
    assert _pallas_calls(core, q, k, v) == 0
    np.testing.assert_allclose(core(q, k, v), sdpa(q, k, v, causal=True),
                               atol=2e-6)


def _program_events(model, state, inputs, labels):
    from dlrm_flexflow_tpu.telemetry import event_log
    with event_log() as log:
        model.train_epoch(state, inputs, labels)
    return [e for e in log.events() if e["type"] == "program"]


def test_the_program_event_counts_the_attention_cores():
    """On the CPU backend, no interpreter: the tiny model's three
    ``LatentAttention`` ops (two layers and the MTP module) all run the
    plain core and the ``program`` event says so; a model without such
    an op carries no such field."""
    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    events = _program_events(model, state, inputs, labels)
    assert [e["attention_core"] for e in events] \
        == [{"pallas": 0, "plain": 3}]
    assert [op.core_form() for op in model.layers
            if isinstance(op, LatentAttention)] == ["plain"] * 3
    from dlrm_flexflow_tpu.telemetry.schema import validate_event
    assert validate_event(events[0]) == []


def test_a_recomputed_layer_runs_the_forward_kernel_once(monkeypatch):
    """The training step of a recomputed model at the published head
    widths, as a TPU would trace it: one forward and one backward kernel
    for each of the three ``LatentAttention`` ops and no third: the
    recomputation keeps the core's output and log-sum-exp
    (``saved_in_recompute``), so the backward holds no second forward
    kernel; the ``program`` event would read ``pallas: 3``, all three
    kept through their recomputation, and the two expert layers' routing
    kept through theirs (``route_kept``)."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    cfg = _small(num_attention_heads=2, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, seq_len=2048)
    assert cfg.recompute
    model, state = _compiled(cfg, batch=1)
    assert model._program_fields == {
        "attention_core": {"pallas": 3, "plain": 0},
        "core_kept": {"attention_core": 3}, "route_kept": 2}
    inputs, labels = family._split(_tokens(cfg, 1, batch=1))
    text = str(jax.make_jaxpr(model._train_step)(
        state, {k: v[0] for k, v in inputs.items()}, labels[0]))
    assert text.count("causal_attention_fwd") == 3
    assert text.count("causal_attention_bwd") == 3
    assert text.count("pallas_call") == 6
    for name in attention_ops.CORE_SAVED:
        assert text.count("name=" + name) == 3, name


@pytest.mark.parametrize("block", [8, 32])
def test_latent_attention_is_the_references(block, monkeypatch):
    """The op against ``ref.mla``: low-rank query and key/value paths
    with their norms, one rotary key for all heads, interleaved RoPE,
    widths 12 (query/key) against 6 (value); in four key blocks and in
    one."""
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", block)
    cfg = _small()
    x_t = Tensor((2, cfg.seq_len, cfg.hidden_size), jnp.float32, name="x")
    op = LatentAttention("mla", x_t, cfg.num_attention_heads,
                         cfg.q_lora_rank, cfg.kv_lora_rank,
                         cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.rope_theta, cfg.rms_norm_eps)
    params = op.init_params(jax.random.PRNGKey(2))
    params["q_norm"] = params["q_norm"] * 1.5   # the scales take part
    x = jax.random.normal(jax.random.PRNGKey(3), x_t.shape)
    want = jax.vmap(lambda seq: ref.mla(params, seq, _hp(cfg), F32))(x)
    np.testing.assert_allclose(op.forward(params, [x])[0], want, atol=2e-6)
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p)))
    got_g = jax.grad(loss(lambda p: op.forward(p, [x])[0]))(params)
    want_g = jax.grad(loss(lambda p: jax.vmap(
        lambda seq: ref.mla(p, seq, _hp(cfg), F32))(x)))(params)
    for name in params:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-5,
                                   err_msg=name)


def test_rope_turns_interleaved_pairs():
    from dlrm_flexflow_tpu.ops.transformer import rope_interleaved
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 3, 8))
    got = rope_interleaved(x, jnp.arange(5), 1e4, seq_axis=1)
    angle = 3 * 1e4 ** (-2 / 8)   # position 3, pair 1 = elements (2, 3)
    a, b = x[0, 3, 1, 2], x[0, 3, 1, 3]
    np.testing.assert_allclose(got[0, 3, 1, 2],
                               a * np.cos(angle) - b * np.sin(angle),
                               rtol=1e-5)
    np.testing.assert_allclose(got, jnp.swapaxes(jax.vmap(
        lambda seq: ref.rope(seq, 1e4))(x), 0, 0), atol=1e-6)


# -------------------------------------------------------- expert layer
def _moe(cfg, held, shared=1, tokens=(2, 32)):
    x_t = Tensor(tokens + (cfg.hidden_size,), jnp.float32, name="x")
    return HeldExpertsMoE("moe", x_t, cfg.n_routed_experts,
                          cfg.moe_intermediate_size, cfg.num_experts_per_tok,
                          held, shared, cfg.routed_scaling_factor,
                          cfg.bias_update_speed)


def _ref_moe_params(params, lo=None, hi=None):
    out = {"router": params["router"],
           "shared": {k: params["shared_" + k[2:]]
                      for k in ("w_gate", "w_up", "w_down")}}
    out.update({k: params[k][lo:hi] for k in ("w_gate", "w_up", "w_down")})
    return out


@pytest.fixture(scope="module")
def uncut():
    """A layer that holds all 16 experts, its parameters, a bias that
    moves the selection, tokens, and the reference's output for them."""
    cfg = _small()
    op = _moe(cfg, None)
    params = op.init_params(jax.random.PRNGKey(5))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                    (cfg.n_routed_experts,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    hp = dict(_hp(cfg), first_expert_held=0)
    want, counts = ref.expert_layer(_ref_moe_params(params), bias,
                                    x.reshape(-1, cfg.hidden_size), hp, F32)
    return cfg, op, params, bias, x, want.reshape(x.shape), counts


def test_all_experts_held_is_the_uncut_reference(uncut):
    cfg, op, params, bias, x, want, counts = uncut
    state = dict(op.init_state(), bias=bias)
    got = op.forward(params, [x], training=True, state=state)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)
    new = op._last_state
    np.testing.assert_array_equal(new["tokens_per_expert"], counts)
    assert int(new["held_assignments"]) == 64 * cfg.num_experts_per_tok
    assert int(new["padded_rows"]) == 0
    mean = float(np.mean(counts))
    np.testing.assert_allclose(
        new["bias"], bias + cfg.bias_update_speed * np.sign(mean - counts))
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p)))
    got_g = jax.grad(loss(lambda p: op.forward(
        p, [x], training=True, state=state)[0]))(params)
    want_g = jax.grad(loss(lambda p: ref.expert_layer(
        _ref_moe_params(p), bias, x.reshape(-1, cfg.hidden_size),
        dict(_hp(cfg), first_expert_held=0), F32)[0].reshape(x.shape)))(
            params)
    for name in params:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-5,
                                   err_msg=name)


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """The model-configs guide's share test: four chips hold four
    experts each; their routed parts, plus the shared expert counted
    once, are the uncut layer; each share is the reference's for that
    share; every share counts the same routing."""
    cfg, _op, params, bias, x, want, counts = uncut
    total, flat = 0.0, x.reshape(-1, cfg.hidden_size)
    for rank in range(4):
        lo = 4 * rank
        share = _moe(cfg, (lo, 4), shared=0)
        mine = {"router": params["router"],
                **{k: params[k][lo:lo + 4]
                   for k in ("w_gate", "w_up", "w_down")}}
        part = share.forward(mine, [x], training=True,
                             state=dict(share.init_state(), bias=bias))[0]
        np.testing.assert_array_equal(share._last_state["tokens_per_expert"],
                                      counts)
        assert int(share._last_state["held_assignments"]) \
            == int(counts[lo:lo + 4].sum())
        hp = dict(_hp(cfg), first_expert_held=lo)
        with_shared, _ = ref.expert_layer(
            _ref_moe_params(params, lo, lo + 4), bias, flat, hp, F32)
        shared = ref.swiglu(flat, _ref_moe_params(params)["shared"], F32)
        np.testing.assert_allclose(part.reshape(flat.shape),
                                   with_shared - shared, atol=2e-6)
        total = total + part
    np.testing.assert_allclose(
        total + shared.reshape(x.shape), want, atol=4e-6)


def _steered(cfg, op, whole: int, one_more: bool = False):
    """Parameters and tokens (2, 32) for a layer that holds experts 4-7
    in which the first ``whole`` tokens select all four held experts,
    one further token selects expert 4 alone if ``one_more``, and the
    others select none of the four: ``4 * whole + one_more``
    assignments to held experts, exactly."""
    params = op.init_params(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (64, cfg.hidden_size))
    x = x.at[:, 0].set(jnp.where(jnp.arange(64) < whole, 6.0, -6.0))
    x = x.at[:, 1].set(0.0)
    router = params["router"].at[:, 4:8].set(0.0).at[0, 4:8].set(5.0)
    if one_more:
        x = x.at[whole, 1].set(12.0)
        router = router.at[1, 4].set(5.0)
    return dict(params, router=router), x.reshape(2, 32, cfg.hidden_size)


def _held_layer_against_the_reference(cfg, op, params, bias, x, atol=2e-5):
    """Output, every parameter's gradient and the input's, against the
    reference's expert layer; returns the counters the step left."""
    hp = dict(_hp(cfg), first_expert_held=op.first_held)
    flat = x.reshape(-1, cfg.hidden_size)
    state = dict(op.init_state(), bias=bias)
    got = op.forward(params, [x], training=True, state=state)[0]
    new = {k: int(op._last_state[k]) for k in moe_ops.COUNTERS}
    want, counts = ref.expert_layer(_ref_moe_params(params), bias, flat, hp,
                                    F32)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-5)
    lo = op.first_held
    assert new["held_assignments"] == int(counts[lo:lo + op.num_held].sum())
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    got_g = jax.grad(loss(lambda p, x: op.forward(
        p, [x], training=True, state=state)[0]), (0, 1))(params, x)
    want_g = jax.grad(loss(lambda p, x: ref.expert_layer(
        _ref_moe_params(p), bias, x.reshape(flat.shape), hp,
        F32)[0].reshape(x.shape)), (0, 1))(params, x)
    for name in params:
        assert np.all(np.isfinite(got_g[0][name])), name
        np.testing.assert_allclose(got_g[0][name], want_g[0][name],
                                   rtol=1e-5, atol=atol, err_msg=name)
    np.testing.assert_allclose(got_g[1], want_g[1], rtol=1e-5, atol=atol)
    return new


def _slab_counters_hold(new, assignments, slab):
    """What the counters promise whatever the step held."""
    here = new["held_assignments"]
    assert here + new["padded_rows"] == assignments
    slabs = max(1, -(-here // slab))
    assert new["buffer_rows"] == slabs * slab
    assert new["buffer_rows"] % slab == 0
    assert new["overflow_steps"] == int(here > slab)


@pytest.mark.parametrize("impl", ["ragged", "megablox"])
def test_no_token_is_dropped_when_every_token_selects_held_experts(
        impl, monkeypatch):
    """A bias that makes every token select the four held experts: all
    T * k = 256 assignments are held, twice the slab of 128, so the
    step overflows into a second slab, none is padded or dropped, and
    output and gradients are still the reference's.  ``megablox`` runs
    in Pallas interpret mode here."""
    cfg = _small()
    op = _moe(cfg, (4, 4))
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: impl == "megablox")
    params = op.init_params(jax.random.PRNGKey(8))
    bias = jnp.zeros((16,)).at[4:8].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.hidden_size))
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        real = megablox.gmm   # its backward takes the option along
        monkeypatch.setattr(
            megablox, "gmm",
            lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    new = _held_layer_against_the_reference(cfg, op, params, bias, x)
    assert new["held_assignments"] == 64 * 4 and new["padded_rows"] == 0
    assert moe_ops.slab_rows(256, 4, 16) == 128
    assert new["buffer_rows"] == 256 and new["overflow_steps"] == 1
    _slab_counters_hold(new, 256, 128)
    np.testing.assert_array_equal(op._last_state["tokens_per_expert"][4:8],
                                  [64] * 4)


@pytest.mark.parametrize("whole,one_more,slabs", [
    (None, False, 1),   # a held share as the router deals it: one slab
    (32, False, 1),     # exactly the slab's 128 rows
    (32, True, 2),      # one row over: a second slab for it
    (0, False, 1),      # no token selects a held expert
    (63, True, 2),      # all but three rows of two slabs
])
def test_the_held_share_works_on_slabs_and_drops_nothing(whole, one_more,
                                                         slabs):
    """A layer holding 4 of 16 experts, 64 tokens, top-4: A = 256, the
    slab 128 rows.  However many assignments the step holds, output and
    every gradient are the reference's and the counters add up."""
    cfg = _small()
    op = _moe(cfg, (4, 4))
    if whole is None:
        params = op.init_params(jax.random.PRNGKey(8))
        x = jax.random.normal(jax.random.PRNGKey(9),
                              (2, 32, cfg.hidden_size))
    else:
        params, x = _steered(cfg, op, whole, one_more)
    new = _held_layer_against_the_reference(cfg, op, params,
                                            jnp.zeros((16,)), x)
    if whole is None:
        assert 0 < new["held_assignments"] < 128
    else:
        assert new["held_assignments"] == 4 * whole + one_more
    assert new["buffer_rows"] == slabs * 128
    _slab_counters_hold(new, 256, 128)
    if whole == 0:   # the shared expert's output alone
        got = op.forward(params, [x])[0].reshape(-1, cfg.hidden_size)
        np.testing.assert_allclose(got, ref.swiglu(
            x.reshape(got.shape), _ref_moe_params(params)["shared"], F32),
            atol=1e-6)


@pytest.mark.parametrize("assignments,held,experts,rows", [
    (65536, 16, 256, 8192),   # the language-model cell: two even shares
    (65536, 256, 256, 65536),  # every expert held: the layer uncut
    (256, 4, 16, 128),        # the tests' and the CPU rehearsal's
    (8192, 3, 64, 1024),      # 768 rows, in whole row tiles of 512
    (96, 1, 16, 16),          # 12 rows, in whole sublane tiles
    (40, 8, 16, 40),          # never more than all of them
])
def test_a_slab_is_a_few_even_shares_in_whole_tiles(assignments, held,
                                                    experts, rows):
    assert moe_ops.slab_rows(assignments, held, experts) == rows


def test_no_array_has_all_assignments_by_a_width():
    """Forward and backward of the held share: only vectors are as long
    as all T * k assignments; nothing of that many rows has a model or
    hidden width beside it (the (A, held) compare that counts the groups
    is the one two-dimensional exception, 4 wide here)."""
    cfg = _small()
    op = _moe(cfg, (4, 4))
    params = op.init_params(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.hidden_size))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(op.forward(
        p, [x], training=True)[0] ** 2), (0, 1)))(params, x)
    widths = {cfg.hidden_size, cfg.moe_intermediate_size}
    assert 4 not in widths and 256 not in widths

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                yield eqn.primitive.name, getattr(var.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert any(shape == (256,) for _, shape in seen)
    wide = sorted({(name, shape) for name, shape in seen
                   if shape[:1] == (256,) and set(shape[1:]) & widths})
    assert wide == []


def test_padding_rows_are_masked_whatever_they_hold(monkeypatch):
    """A grouped matmul that leaves rubbish behind the last group, as
    megablox does on the chip: output and gradients do not see it."""
    cfg = _small()
    op = _moe(cfg, (0, 4))
    params = op.init_params(jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 32, cfg.hidden_size))
    real = moe_ops.grouped_matmul

    def rubbish(rows, weights, group_sizes):
        out = real(rows, weights, group_sizes)
        live = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
        return jnp.where(live, out, jnp.nan)

    loss = lambda p: jnp.sum(op.forward(p, [x], training=True)[0] ** 2)
    want, want_g = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(moe_ops, "grouped_matmul", rubbish)
    got, got_g = jax.value_and_grad(loss)(params)
    assert int(op._last_state["padded_rows"]) > 0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name in params:
        assert np.all(np.isfinite(got_g[name])), name
        np.testing.assert_allclose(got_g[name], want_g[name], atol=1e-6)


# ----------------------------------------- the model through the trainer
def _program_and_reference(cfg, steps=2, batch=2):
    """``steps`` of ``train_epoch`` and of ``ref.train_steps`` from one
    initial state.  Returns ``(state, folded metrics, reference
    (params, m, v, step), biases, reference losses...)``."""
    model, state = _compiled(cfg, batch)
    snap = family._snapshot(state, cfg)
    tokens = _tokens(cfg, steps, batch)
    inputs, labels = family._split(tokens)
    start = jax.tree_util.tree_map(jnp.copy, (snap["params"], snap["m"],
                                              snap["v"], snap["step"]))
    want = ref.train_steps(start, list(snap["biases"]), tokens,
                           dict(_hp(cfg, batch)))
    state, mets = model.train_epoch(state, inputs, labels)
    return model, state, mets, want


def test_both_losses_every_update_bias_and_counts_are_the_references():
    cfg = _small()
    _m, state, mets, (want_state, biases, losses, main, mtp, counts) = \
        _program_and_reference(cfg)
    np.testing.assert_allclose(float(mets["loss"]), np.mean(losses),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.mean(losses), np.mean(main) + cfg.mtp_loss_weight
        * np.mean([m[0] for m in mtp]), rtol=1e-6)
    got = family._snapshot(state, cfg)
    flat_got = ref.leaves_by_name(got["params"])
    flat_want = ref.leaves_by_name(want_state[0])
    assert len(flat_got) == len(flat_want) == 51
    for name, value in flat_want.items():
        np.testing.assert_allclose(flat_got[name], value, atol=3e-6,
                                   err_msg=name)
    for layer, name in enumerate(family._moe_ops(cfg)):
        np.testing.assert_array_equal(got["biases"][layer], biases[layer])
        np.testing.assert_array_equal(
            got["counters"]["tokens_per_expert"][layer],
            np.sum([c[layer] for c in counts], axis=0))
        np.testing.assert_array_equal(
            mets[f"{name}/tokens_per_expert"],
            got["counters"]["tokens_per_expert"][layer])
        assert float(mets[f"{name}/bias_abs_max"]) \
            == float(jnp.max(jnp.abs(biases[layer])))


def test_the_shared_embedding_and_head_get_the_sum_of_both_gradients():
    """After one step from zero moments ``m = (1 - b1) g``: the
    embedding's and the head's are the reference's, which reads each in
    the model and in the MTP module; with the MTP term's weight at zero
    they are different ones (so the second reader's part is in the
    sum), and the tied ops own no tensor."""
    cfg = _small()
    _m, state, _mets, (want, *_rest) = _program_and_reference(cfg, steps=1)
    assert "mtp_0_embed" not in state.params
    assert "mtp_0_head" not in state.params
    without = _program_and_reference(_small(mtp_loss_weight=0.0), steps=1)[1]
    for op_name, pname, key in (("embed", "embedding", "embed"),
                                ("lm_head", "kernel", "head")):
        got = state.opt_state["m"][op_name][pname]
        np.testing.assert_allclose(got, want[1][key], atol=1e-8)
        alone = without.opt_state["m"][op_name][pname]
        assert float(jnp.max(jnp.abs(got - alone))) \
            > 0.05 * float(jnp.max(jnp.abs(got)))


def test_recomputation_changes_no_number():
    runs = []
    for recompute in (True, False):
        cfg = _small(recompute=recompute)
        model, state = _compiled(cfg)
        inputs, labels = family._split(_tokens(cfg, 2))
        state, mets = model.train_epoch(state, inputs, labels)
        runs.append((state, mets, model))
    assert any(op.recompute for op in runs[0][2].layers)
    assert not any(op.recompute for op in runs[1][2].layers)
    assert float(runs[0][1]["loss"]) == float(runs[1][1]["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(runs[0][0].params),
                    jax.tree_util.tree_leaves(runs[1][0].params)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)


def test_phase_of_sees_through_a_checkpoint():
    inner = "transpose(jvp(ff.step.model))/jvp(ff.step.model)/checkpoint/"
    assert profiling.phase_of(
        "jit(f)/" + inner + "rematted_computation/layer_1_mla/ff.lm.mla/"
        "ff.lm.mla.core/while/body/dot_general") == "ff.lm.mla.core.remat"
    assert profiling.phase_of(
        "jit(f)/" + inner + "layer_1_moe/ff.lm.moe/ff.lm.moe.experts/"
        "ragged_dot") == "ff.lm.moe.experts.bwd"
    assert profiling.phase_of(
        "jit(f)/jvp(ff.step.model)/checkpoint/layer_0_ffn/ff.lm.ffn/"
        "dot_general") == "ff.lm.ffn"
    # what it read before this PR, unchanged
    assert profiling.phase_of(
        "jit(f)/transpose(jvp(ff.step.model))/top_1/dot_general") \
        == "ff.step.model.bwd"
    assert profiling.phase_of("jit(f)/ff.ladder/while/body/ff.step.gather/"
                              "gather") == "ff.step.gather"


def test_every_scope_of_the_compiled_step_is_attributed():
    """The optimized HLO of the tiny model's ``train_epoch``: every
    ``ff.lm.*`` scope of the issue's list is there forward, backward and
    recomputed, and the family's groups hold every phase found."""
    from benchmarks.lib import phases

    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    text = model._train_epoch.lower(state, inputs,
                                    labels).compile().as_text()
    found = set(profiling.hlo_phases(text).values())
    for scope in ("ff.lm.embed", "ff.lm.mla.proj", "ff.lm.mla.core",
                  "ff.lm.ffn", "ff.lm.moe.route", "ff.lm.moe.dispatch",
                  "ff.lm.moe.experts", "ff.lm.moe.combine",
                  "ff.lm.moe.shared", "ff.lm.mtp", "ff.lm.head",
                  "ff.step.dense_update"):
        assert scope in found or scope + ".bwd" in found, scope
    for scope in ("ff.lm.mla.proj", "ff.lm.ffn"):
        assert scope + ".remat" in found, scope
        assert scope + ".bwd" in found, scope
    # the core's output and log-sum-exp are kept: its forward runs once;
    # the held experts' backward computes its own slabs again from the
    # tokens, so the recomputation before it has none of them to compute;
    # the dispatch's order and group sizes are kept (``moe.ROUTE_SAVED``):
    # the recomputation sorts nothing
    for scope in ("ff.lm.mla.core", "ff.lm.moe.experts",
                  "ff.lm.moe.combine", "ff.lm.moe.dispatch"):
        assert scope + ".bwd" in found, scope
        assert scope + ".remat" not in found, scope
    for phase in found - {profiling.UNATTRIBUTED}:
        assert phases.group_of(phase, family.PHASE_GROUPS), phase


def test_dlrm_keeps_the_sparse_path_and_a_tied_table_leaves_it():
    """A model without a row-sparse table trains on the plain scan
    (plain SGD would take an untied embedding row-sparse; a table two
    ops read is updated densely)."""
    from dlrm_flexflow_tpu.optim import SGDOptimizer
    cfg = _small()
    model = app.build(cfg, FFConfig(batch_size=2))
    model.compile(optimizer=SGDOptimizer(0.01, 0.0, False, 0.0),
                  loss_type=app.token_loss, metrics=(), mesh=False)
    assert model._sparse_emb_ops == []
    state = model.init(seed=0)
    inputs, labels = family._split(_tokens(cfg, 2))
    state, mets = model.train_epochs(state, inputs, labels, 2)
    assert np.all(np.isfinite(np.asarray(mets["loss"])))
    assert int(state.step) == 4


def test_the_flop_count_against_a_hand_count():
    """``train_flops_per_sample`` at the published sizes: the issue's
    own arithmetic, 27.8 TFLOP a step."""
    config = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/joyai-flash-ep16.json")))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic/pretrain-8k.json")))
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 4096 * 2048)
    expert = 3 * 2048 * 768
    active = (6 * mla + 3 * 2048 * 7168
              + 5 * (expert + 2048 * 256 + 0.5 * expert)
              + 2 * 2048 * 2048 + 2 * 2048 * 16160)
    core = 6 * 3 * 8192 ** 2 * 32 * 320
    want = 6 * active * 8192 + core
    got = family.train_flops_per_sample(config, traffic)
    assert got == pytest.approx(want, rel=1e-9)
    assert got == pytest.approx(27.8e12, rel=0.01)
    cfg = family.model_config(config, traffic)
    assert family.attention_core_flops(cfg) == pytest.approx(
        3.5 * 8192 ** 2 * 32 * 320)


def test_the_configuration_file_holds_the_published_widths():
    config = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/joyai-flash-ep16.json")))
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "n_routed_experts": 256,
                 "num_experts_per_tok": 8, "n_shared_experts": 1,
                 "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
                 "rope_theta": 32000000, "routed_scaling_factor": 2.5,
                 "rms_norm_eps": 1e-6}
    assert {k: config[k] for k in published} == published
    assert config["overrides"] == {}   # the rehearsal's alone
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_size"]
    cfg = family.model_config(config, {"seq_len": 8192})
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.seq_len) \
        == (256, 16, 8192)
    model = app.build(cfg, FFConfig(batch_size=1,
                                    compute_dtype="bfloat16"))
    shapes = jax.eval_shape(lambda: (model.compile(
        optimizer=app.optimizer(cfg), loss_type=app.token_loss, metrics=(),
        mesh=False) and None) or model.init(seed=0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes.params))
    assert count == 680_439_808   # the issue's 680.5M, to the parameter


# ------------------------- the family's comparison, with faults planted
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's real files at the rehearsal's size (as
    ``tests/benchmark`` lays them), and the staged driver."""
    cell = run.resolve(ROOT, "joyai-flash-ep16.pretrain-8k")
    tiny = json.load(open(os.path.join(
        ROOT, "tests/benchmark/tiny.mla_moe_lm.json")))
    config, traffic = copy.deepcopy(cell["config"]), dict(cell["traffic"])
    for key, block in tiny.items():
        if key not in ("about", "traffic"):
            config[key].update(block)
    traffic.update(tiny["traffic"])
    return config, traffic, run.load_file(cell["driver"])


def _check(tiny_cell, run_steps, seed=7, build=None):
    config, traffic, driver = tiny_cell
    model, state = (build or family.build)(config, traffic, seed, None)
    ok, report, _ = family.check(config, traffic, model, state, seed,
                                 run_steps or driver.check_steps,
                                 traffic["check_batches"])
    return ok, report


def test_the_sound_path_is_correct(tiny_cell):
    ok, report = _check(tiny_cell, None)
    assert ok, report
    assert report["counter_slack"] == 0 and report["count_err"] == 0
    assert report["grad_err_max"] < 1e-5 and report["update_err"] < 1e-5


@pytest.mark.parametrize("fault,over", [
    ("state_unchanged", {"update_err", "grad_err_max", "bias_err"}),
    ("half_batch", {"grad_err_max", "counter_slack"}),
    ("bias_never_updated", {"bias_err"}),
    ("expert_dropped", {"grad_err_max"})])
def test_a_planted_fault_is_not_correct(tiny_cell, fault, over, monkeypatch):
    """The timed path broken underneath the comparison: the state
    returned unchanged; half of every batch left out; the router bias
    put back to what it was; one held expert's rows zeroed behind the
    grouped matmul (its tokens dropped: its weights' gradient reads
    1.0)."""
    real = tiny_cell[2].check_steps

    def steps(model, state, inputs, labels):
        from dlrm_flexflow_tpu.model import TrainState
        if fault == "state_unchanged":
            kept = jax.tree_util.tree_map(jnp.copy, state)
            return kept, real(model, state, inputs, labels)[1]
        if fault == "half_batch":
            inputs = {k: v[:, :1] for k, v in inputs.items()}
            labels = labels[:, :1]
        if fault == "bias_never_updated":
            old = {name: jnp.copy(s["bias"])
                   for name, s in state.bn_state.items()}
            state, losses = real(model, state, inputs, labels)
            bn = {name: dict(s, bias=old[name])
                  for name, s in state.bn_state.items()}
            return TrainState(state.params, state.opt_state, bn, state.rng,
                              state.step), losses
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
    ok, report = _check(tiny_cell, steps)
    assert not ok, report
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= over, report
    if fault == "expert_dropped":
        assert ".moe.w_" in report["grad_worst_tensor"]
        assert report["grad_worst_tensor"].endswith(".1")
        assert report["grad_err_max"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("sent,took,wrong,ok", [
    ([3, 40, 30, 27], [3, 40, 30, 27], 0, False),   # thin, the same tokens
    ([3, 40, 30, 27], [4, 40, 30, 26], 0, True),    # thin, one selected over
    ([3, 40, 30, 27], [3, 41, 30, 26], 1, False),   # not thin
    ([3, 40, 30, 27], [3, 40, 30, 27], None, True)])
def test_only_a_thin_expert_with_other_tokens_is_left_out(sent, took, wrong,
                                                          ok):
    """``ref.compare`` on a made-up state of one expert layer holding two
    of four experts: a held expert whose gradient is lost reads 1.0 and
    fails the comparison, unless the reference sent it fewer than
    ``THIN_TOKENS`` tokens and the program's count differs (the two
    selected different tokens for it)."""
    assert ref.THIN_TOKENS > 3
    rng = np.random.default_rng(0)
    tree = lambda: {"embed": rng.standard_normal((2000, 2),
                                                 dtype=np.float32),
                    "layers": [{"moe": {"w_gate": rng.standard_normal(
                        (2, 3, 2), dtype=np.float32)}}], "mtp": []}
    zeros = jax.tree_util.tree_map(np.zeros_like, tree())
    before = {"params": tree(), "m": zeros}
    step = jax.tree_util.tree_map(lambda x: 0.01 * x, tree())
    moved = jax.tree_util.tree_map(lambda a, b: a - b, before["params"], step)
    want = {"params": moved, "m": step, "biases": [np.zeros(4)],
            "losses": [2.0], "counts": [[np.asarray(sent)]]}
    m_got = copy.deepcopy(step)
    if wrong is not None:
        m_got["layers"][0]["moe"]["w_gate"][wrong] = 0.0
    here = int(np.sum(took[:2]))
    got = {"params": moved, "m": m_got, "biases": [np.zeros(4)],
           "losses": [2.0],
           "counts": {"tokens_per_expert": [np.asarray(took)],
                      "held_assignments": [here], "padded_rows": [100 - here]}}
    hp = {"adam_beta1": 0.9, "first_expert_held": 0, "experts_held": 2,
          "num_experts_per_tok": 1, "tokens_per_step": 100,
          "bias_update_speed": 1e-3}
    good, report = ref.compare(before, got, want, 1, hp)
    assert good == ok, report
    assert report["counter_slack"] == 0
    assert report["thin_expert_tensors"] == int(sent != took and wrong == 0)
    if wrong is not None and not ok:
        assert report["grad_err_max"] == pytest.approx(1.0)
        assert report["grad_worst_tensor"] == f"layers.0.moe.w_gate.{wrong}"


def test_the_control_steps_write_a_whole_state_back(tiny_cell):
    """``control_steps`` (the reference in the program's place, one
    precision down) returns a state the comparison can read: every
    tensor, both moments, the biases and the counters, and the program
    can train on from it."""
    config, traffic, driver = tiny_cell
    assert family.LOWER[config["ffconfig"]["compute_dtype"]] == "bfloat16"
    ok, report = _check(tiny_cell, family.control_steps(config))
    assert report["counter_slack"] == 0
    assert 0 < report["grad_err_median"] < 0.1   # bf16 against f32: near
    assert report["loss_err"] < 1e-3
    model, state = family.build(config, traffic, 3, None)
    inputs, labels = family._split(family._sequences(
        config, traffic, 2 * traffic["batch"], 3, 1).reshape(
            2, traffic["batch"], -1))
    state, _ = family.control_steps(config)(model, state, inputs, labels)
    assert int(state.step) == 2
    state, losses = driver.check_steps(model, state, inputs, labels)
    assert np.isfinite(float(losses[0])) and int(state.step) == 4
