"""The Kimi Delta Attention + latent attention + routed-experts language
model (Ling-3.0-flash) on the CPU at a small size, seeded random weights,
each piece against the plain reference
(``benchmarks/reference/kda_moe_lm_ref.py``): the chunked delta rule with
a decay per channel against the token-by-token recurrence, the latent
attention op's new arguments (and its old ones, bit for bit), the
group-limited router against explicit loops, the shares of the heads and
of the experts adding up to the whole layers, the whole model through
the trainer, the configuration's file, and the family's comparison with
faults planted in the path it times."""

import copy
import json
import math
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
from benchmarks.models import kda_moe_lm as family  # noqa: E402
from benchmarks.reference import kda_moe_lm_ref as ref  # noqa: E402
from dlrm_flexflow_tpu import profiling  # noqa: E402
from dlrm_flexflow_tpu.apps import (gdn_moe_lm, kda_moe_lm as app,  # noqa: E402
                                    lm_common, mla_moe_lm)
from dlrm_flexflow_tpu.config import FFConfig  # noqa: E402
from dlrm_flexflow_tpu.model import TrainState  # noqa: E402
from dlrm_flexflow_tpu.ops import attention as attention_ops  # noqa: E402
from dlrm_flexflow_tpu.ops import deltanet  # noqa: E402
from dlrm_flexflow_tpu.ops import moe as moe_ops  # noqa: E402
from dlrm_flexflow_tpu.ops.attention import LatentAttention  # noqa: E402
from dlrm_flexflow_tpu.ops.base import matmul  # noqa: E402
from dlrm_flexflow_tpu.ops.deltanet import KimiDeltaAttention  # noqa: E402
from dlrm_flexflow_tpu.ops.moe import HeldExpertsMoE  # noqa: E402
from dlrm_flexflow_tpu.ops.transformer import (rms_norm,  # noqa: E402
                                               rope_interleaved)
from dlrm_flexflow_tpu.tensor import Tensor  # noqa: E402

F32 = jnp.dtype("float32")
CONFIG = os.path.join(ROOT, "benchmarks/configs/ling3-flash-ep64.json")
CELL = "ling3-flash-ep64.pretrain-8k"


def _small(**changes):
    """Every kind of the cell's layers at a small size: published
    layers 1-3 with every third layer latent attention: a dense layer
    under KDA, then MLA and KDA with experts."""
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
                layer_group_size=3, first_layer_held=1, intermediate_size=48,
                num_attention_heads=4, heads_held=2, head_dim=8,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=6, num_experts=16, experts_held=4,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                moe_intermediate_size=16,
                moe_shared_expert_intermediate_size=16, seq_len=32)
    base.update(changes)
    return app.KdaMoeLmConfig(**base)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """32 tokens in chunks of 16 with sub-blocks of 4 and key blocks of
    8: the chunked rule carries its state once, every chunk has four
    sub-blocks, and the blockwise core loops."""
    monkeypatch.setattr(deltanet, "CHUNK", 16)
    monkeypatch.setattr(deltanet, "SUB", 4)
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
                        size=(steps, batch, cfg.seq_len + 1)).astype(np.int32)


def _out_and_grads(forward):
    """``params -> (out, d sum(sin(out)) / d params)`` as one program (an
    eager run compiles an op at a time: 20-40 s a test)."""
    def loss(p):
        out = forward(p)
        return jnp.sum(jnp.sin(out)), out
    run = jax.jit(jax.value_and_grad(loss, has_aux=True))

    def out_and_grads(p):
        (_, out), grads = run(p)
        return out, grads
    return out_and_grads


# ------------------------------------- the delta rule, a decay per channel
def _rule_inputs(seq, decays="spread", h=3, dk=8, dv=6, batch=2):
    """q, k normalised as the mixer hands them over.  ``decays``:
    ``spread`` over (-5, 0) by channel; ``bound``: -5 on every channel
    and token; ``mixed``: half the entries at the bound, half at -1e-3;
    ``constant``: one decay a head and token, the same on its channels."""
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    q = deltanet.l2_normalised(jax.random.normal(
        keys[0], (batch, seq, h, dk))) * dk ** -0.5
    k = deltanet.l2_normalised(jax.random.normal(keys[1],
                                                 (batch, seq, h, dk)))
    v = jax.random.normal(keys[2], (batch, seq, h, dv))
    noise = jax.random.normal(keys[3], (batch, seq, h, dk))
    g = {"spread": -5.0 * jax.nn.sigmoid(4.0 * noise),
         "bound": jnp.full(noise.shape, -5.0),
         "mixed": jnp.where(noise > 0, -5.0, -1e-3),
         "constant": jnp.broadcast_to(
             -5.0 * jax.nn.sigmoid(4.0 * noise[..., :1]), noise.shape)}[decays]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5],
                                                 (batch, seq, h, dv))


def _token_rule(q, k, v, g, beta):
    """The reference's recurrence, one sequence at a time."""
    return jax.vmap(ref.delta_rule)(q, k, v, g, beta)


@pytest.mark.parametrize("chunk,sub,seq,decays", [
    (16, 4, 29, "spread"), (16, 4, 29, "bound"), (16, 4, 29, "mixed"),
    (16, 4, 48, "spread"), (64, 16, 64, "mixed"), (64, 16, 150, "spread"),
    (64, 16, 150, "bound"), (64, 16, 150, "mixed")])
def test_the_chunked_rule_is_the_token_recurrence(chunk, sub, seq, decays,
                                                  monkeypatch):
    """Output and all five gradients, over several chunks and sub-blocks
    and lengths the chunk does not divide; with every decay at the bound
    (-5 a token on every channel, 80 nats a sub-block) and with bound
    and near-zero decays mixed they are finite and still the
    recurrence's."""
    monkeypatch.setattr(deltanet, "CHUNK", chunk)
    monkeypatch.setattr(deltanet, "SUB", sub)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", seq)
    args, w = _rule_inputs(seq, decays)

    def out_and_grads(rule):         # one program a side, not one an op
        def loss(*a):
            out = rule(*a)
            return jnp.sum(out * w), out
        return jax.jit(jax.value_and_grad(loss, argnums=range(5),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, got), got_g = out_and_grads(deltanet.kimi_delta_rule)(*args)
        (_, want), want_g = out_and_grads(_token_rule)(*args)
    assert np.all(np.isfinite(got))
    # (with decays of -1e-3 among the bound's the state lives on and the
    # sums are longer: 2.4e-6 there, under 1e-6 elsewhere)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


def test_with_one_decay_a_head_it_is_the_gated_delta_rule(monkeypatch):
    """``g`` constant over a head's channels: the sibling's rule, whose
    scalar mask this one moved inside the dot product."""
    monkeypatch.setattr(deltanet, "CHUNK", 64)
    monkeypatch.setattr(deltanet, "SUB", 16)
    (q, k, v, g, beta), w = _rule_inputs(150, "constant")
    def out_and_grads(rule):
        def loss(*a):
            out = rule(*a)
            return jnp.sum(out * w), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 4),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, got), got_g = out_and_grads(deltanet.kimi_delta_rule)(
            q, k, v, g, beta)
        (_, want), want_g = out_and_grads(deltanet.gated_delta_rule)(
            q, k, v, g[..., 0], beta)
    np.testing.assert_allclose(got, want, atol=1e-6)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_chunked_rule_in_bfloat16_is_near_the_recurrence(monkeypatch):
    """bf16 operands, f32 decays, state and accumulators: within bf16's
    rounding of the f32 recurrence on the same rounded inputs."""
    monkeypatch.setattr(deltanet, "CHUNK", 64)
    monkeypatch.setattr(deltanet, "SUB", 16)
    args, _ = _rule_inputs(128)
    q, k, v = (x.astype(jnp.bfloat16).astype(F32) for x in args[:3])
    got = jax.jit(lambda *a: deltanet.kimi_delta_rule(
        *a, compute_dtype=jnp.bfloat16))(q, k, v, *args[3:])
    want = jax.jit(_token_rule)(q, k, v, *args[3:])
    assert got.dtype == F32
    np.testing.assert_allclose(got, want, atol=0.03)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-5   # it did round


def test_the_backward_keeps_a_state_a_chunk_and_never_one_a_token():
    """What the differentiated rule keeps between its passes: its five
    inputs and the state at each chunk's start."""
    args, _ = _rule_inputs(32)
    _out, pull = jax.vjp(deltanet.kimi_delta_rule, *args)
    kept = sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(pull)
                  if hasattr(x, "shape") and x.ndim >= 3)
    assert (2, 2, 3, 8, 6) in kept            # chunks, batch, heads, dk, dv
    assert max(int(np.prod(s)) for s in kept) <= 2 * 32 * 3 * 8


def test_a_sub_blocks_factors_stay_inside_f32():
    """At the bound a row's and a column's factor around the sub-block's
    middle are e^+-40 at most, and a masked pair e^80 a channel: what
    ``SUB`` is sized by."""
    assert deltanet.DECAY_FLOOR == -5.0
    assert math.exp(40) < np.finfo(np.float32).max ** 0.5
    assert 128 * math.exp(80) < np.finfo(np.float32).max
    with pytest.raises(AssertionError, match="log-decays down to"):
        KimiDeltaAttention("kda", Tensor((1, 8, 16), jnp.float32, name="x"),
                           2, 8, 8, lower_bound=-6.0)


# -------------------------------------------------------- the KDA mixer
def _kda(cfg, heads_held=None, tokens=(2, 32)):
    x_t = Tensor(tokens + (cfg.hidden_size,), jnp.float32, name="x")
    return KimiDeltaAttention("kda", x_t, cfg.num_attention_heads,
                              cfg.head_dim, cfg.head_dim,
                              cfg.short_conv_kernel_size,
                              cfg.kda_lower_bound, cfg.rms_norm_eps,
                              heads_held)


def _head_slice(params, lo, hi, width_of):
    """The heads ``[lo, hi)`` of a mixer's parameters: ``width_of`` maps
    a parameter to ``(axis, elements a head)``, absent for a parameter
    every share holds whole."""
    out = {}
    for name, value in params.items():
        if name not in width_of:
            out[name] = value
            continue
        axis, width = width_of[name]
        out[name] = jax.lax.slice_in_dim(value, lo * width, hi * width,
                                         axis=axis)
    return out


def _kda_widths(cfg):
    hd = cfg.head_dim
    return {**{n: (1, hd) for n in ("w_q", "w_k", "w_v", "w_f", "w_g",
                                    "conv_q", "conv_k", "conv_v")},
            "w_beta": (1, 1), "a_log": (0, 1), "dt_bias": (0, hd),
            "w_out": (0, hd)}


@pytest.fixture(scope="module")
def whole_kda():
    cfg = _small()
    op = _kda(cfg)
    params = op.init_params(jax.random.PRNGKey(5))
    # decays off the bound, gates off one half
    params["w_f"], params["w_g"] = 8.0 * params["w_f"], 8.0 * params["w_g"]
    params["a_log"] = jnp.log(jnp.array([0.3, 1.0, 2.0, 6.0]))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    return cfg, op, params, x


def test_the_kda_mixer_is_the_references(whole_kda):
    cfg, op, params, x = whole_kda
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = _out_and_grads(lambda p: op.forward(p, [x])[0])(params)
        want, want_g = _out_and_grads(lambda p: jax.vmap(
            lambda seq: ref.kimi_delta_attention(p, seq, hp, F32))(x))(
                params)
    np.testing.assert_allclose(got, want, atol=5e-6)   # of up to 3
    for name in params:
        scale = float(jnp.max(jnp.abs(want_g[name]))) + 1e-9
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-5 * scale + 1e-8, err_msg=name)


def test_the_head_shares_of_a_kda_mixer_add_up(whole_kda):
    """Two chips hold two of the four heads each: their outputs sum to
    the whole mixer's, and each is the reference's for its heads."""
    cfg, op, params, x = whole_kda
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: op.forward(p, [x])[0])(params)
        total = 0.0
        for first in (0, 2):
            share = _kda(cfg, 2)
            mine = _head_slice(params, first, first + 2, _kda_widths(cfg))
            assert {s.param_name: s.shape for s in share.param_specs()} \
                == {k: v.shape for k, v in mine.items()}
            part = jax.jit(lambda p: share.forward(p, [x])[0])(mine)
            np.testing.assert_allclose(part, jax.jit(lambda p: jax.vmap(
                lambda seq: ref.kimi_delta_attention(p, seq, hp, F32))(x))(mine),
                atol=5e-6)
            total = total + part
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert float(jnp.max(jnp.abs(total - part))) > 1e-3   # both matter


# --------------------------------------------------- latent attention
def _mla(cfg, heads_held=None, **kw):
    x_t = Tensor((2, 32, cfg.hidden_size), jnp.float32, name="x")
    args = dict(qk_norm=True, gate="head_wise")
    args.update(kw)
    return LatentAttention("mla", x_t, cfg.num_attention_heads,
                           args.pop("q_lora_rank", None), cfg.kv_lora_rank,
                           cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim, cfg.rope_theta, cfg.rms_norm_eps,
                           heads_held=heads_held, **args)


def _old_latent_attention(op, params, x):
    """``LatentAttention.forward`` as it was before this model's
    arguments existed, operation for operation."""
    b, s, _ = x.shape
    h, nope, rope, vd = op.num_heads, op.nope, op.rope, op.v_dim
    positions = jnp.arange(s)
    c_q = rms_norm(matmul(x, params["w_qa"], None), params["q_norm"], op.eps)
    q = matmul(c_q, params["w_qb"], None).reshape(b, s, h, nope + rope)
    kva = matmul(x, params["w_kva"], None)
    c_kv, k_r = kva[..., :op.kv_lora_rank], kva[..., op.kv_lora_rank:]
    kv = matmul(rms_norm(c_kv, params["kv_norm"], op.eps),
                params["w_kvb"], None).reshape(b, s, h, nope + vd)
    q_rope = rope_interleaved(q[..., nope:], positions, op.rope_theta,
                              seq_axis=1)
    k_rope = rope_interleaved(k_r, positions, op.rope_theta, seq_axis=1)
    q_all = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k_all = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope))], axis=-1)
    heads_first = lambda t: t.transpose(0, 2, 1, 3)
    o = attention_ops.blockwise_causal_attention(
        heads_first(q_all), heads_first(k_all), heads_first(kv[..., nope:]),
        1.0 / math.sqrt(nope + rope), compute_dtype=jnp.dtype("float32"))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * vd)
    return matmul(o, params["w_o"], None)


def test_latent_attention_with_the_old_arguments_is_bit_equal():
    """The sibling family's op: the same parameters in the same order
    from the same key, and the same output bit for bit."""
    cfg = _small()
    x_t = Tensor((2, 32, cfg.hidden_size), jnp.float32, name="x")
    op = LatentAttention("mla", x_t, 4, 24, 16, 8, 4, 6, 1e6, 1e-6)
    assert [s.param_name for s in op.param_specs()] == [
        "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_o"]
    assert (op.qk_norm, op.gate, op.num_heads) == (False, None, 4)
    params = op.init_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.hidden_size))
    got = jax.jit(lambda p, x: op.forward(p, [x])[0])(params, x)
    want = jax.jit(lambda p, x: _old_latent_attention(op, p, x))(params, x)
    np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def whole_mla():
    cfg = _small()
    op = _mla(cfg)
    params = op.init_params(jax.random.PRNGKey(11))
    params["w_gate"] = 8.0 * params["w_gate"]
    params["q_head_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(12), params["q_head_norm"].shape)
    params["k_head_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(13), params["k_head_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    return cfg, op, params, x


def test_latent_attention_with_the_new_arguments_is_the_references(
        whole_mla):
    """No query latent, the heads' norms before the rotary embedding,
    the head-wise gate: output and every gradient."""
    cfg, op, params, x = whole_mla
    assert [s.param_name for s in op.param_specs()] == [
        "w_q", "w_kva", "kv_norm", "w_kvb", "q_head_norm", "k_head_norm",
        "w_gate", "w_o"]
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = _out_and_grads(lambda p: op.forward(p, [x])[0])(params)
        want, want_g = _out_and_grads(lambda p: jax.vmap(
            lambda seq: ref.latent_attention(p, seq, hp, F32))(x))(params)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for name in params:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-5,
                                   err_msg=name)


def test_the_head_shares_of_a_latent_attention_mixer_add_up(whole_mla):
    """Two chips hold two of the four heads each (their columns of
    ``W_q``, ``W_kvb`` and ``W_g``, their rows of ``W_o``; the latent
    projection and the norms whole): their outputs sum to the whole
    mixer's."""
    cfg, op, params, x = whole_mla
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    widths = {"w_q": (1, qk),
              "w_kvb": (1, cfg.qk_nope_head_dim + cfg.v_head_dim),
              "w_gate": (1, 1), "w_o": (0, cfg.v_head_dim)}
    hp = _hp(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: op.forward(p, [x])[0])(params)
        total = 0.0
        for first in (0, 2):
            share = _mla(cfg, 2)
            mine = _head_slice(params, first, first + 2, widths)
            assert {s.param_name: s.shape for s in share.param_specs()} \
                == {k: v.shape for k, v in mine.items()}
            part = jax.jit(lambda p: share.forward(p, [x])[0])(mine)
            np.testing.assert_allclose(part, jax.jit(lambda p: jax.vmap(
                lambda seq: ref.latent_attention(p, seq, hp, F32))(x))(mine),
                atol=2e-6)
            total = total + part
    np.testing.assert_allclose(total, want, atol=3e-6)
    assert float(jnp.max(jnp.abs(total - part))) > 1e-3


# ----------------------------------------------- group-limited routing
def _moe(cfg, held, shared=1, tokens=(2, 32), **kw):
    x_t = Tensor(tokens + (cfg.hidden_size,), jnp.float32, name="x")
    args = dict(n_group=cfg.n_group, topk_group=cfg.topk_group)
    args.update(kw)
    return HeldExpertsMoE("moe", x_t, cfg.num_experts,
                          cfg.moe_intermediate_size, cfg.num_experts_per_tok,
                          held, shared, cfg.routed_scaling_factor,
                          cfg.bias_update_speed, **args)


def _ref_moe_params(params, lo=None, hi=None):
    out = {"router": params["router"],
           "shared": {k: params["shared_" + k[2:]]
                      for k in ("w_gate", "w_up", "w_down")}}
    out.update({k: params[k][lo:hi] for k in ("w_gate", "w_up", "w_down")})
    return out


def _route_by_loops(scores, bias, n_group, topk_group, top_k, scaling):
    """The selection in plain Python, a token and a group at a time."""
    scores, bias = np.asarray(scores, np.float64), np.asarray(bias)
    tokens, experts = scores.shape
    size = experts // n_group
    chosen = np.zeros((tokens, experts), bool)
    gates = np.zeros((tokens, experts))
    for t in range(tokens):
        c = scores[t] + bias
        group_score = [sum(sorted(c[g * size:(g + 1) * size])[-2:])
                       for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -group_score[g])[
            :topk_group]
        allowed = [e for g in kept for e in range(g * size, (g + 1) * size)]
        picked = sorted(allowed, key=lambda e: -c[e])[:top_k]
        chosen[t, picked] = True
        gates[t, picked] = scaling * scores[t, picked] \
            / scores[t, picked].sum()
    return chosen, gates


@pytest.fixture(scope="module")
def uncut():
    """A layer that holds all 32 experts in 8 groups of 4, its
    parameters, a bias that moves the selection, tokens, and the
    reference's output for them."""
    cfg = _small(num_experts=32, experts_held=None, n_group=8, topk_group=3)
    op = _moe(cfg, None)
    params = op.init_params(jax.random.PRNGKey(5))
    params["router"] = 20.0 * params["router"]   # scores off one half
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (32,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    hp = dict(_hp(cfg), first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.expert_layer(
            _ref_moe_params(params), bias, x.reshape(-1, cfg.hidden_size),
            hp, F32)
    return cfg, op, params, bias, x, want.reshape(x.shape), counts


def test_group_limited_routing_against_explicit_loops(uncut):
    cfg, op, params, bias, x, _want, counts = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        idx, gates, got_counts = op.route(flat, params["router"], bias)
        scores = jax.nn.sigmoid(flat @ params["router"])
    chosen, want_gates = _route_by_loops(
        scores, bias, 8, 3, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor)
    got = np.zeros(chosen.shape, bool)
    np.put_along_axis(got, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(got, chosen)
    dense = np.zeros(chosen.shape)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), axis=1)
    np.testing.assert_allclose(dense, want_gates, atol=1e-6)
    np.testing.assert_array_equal(got_counts, chosen.sum(0))
    np.testing.assert_array_equal(got_counts, counts)
    # every token's experts lie in three groups, and the limit binds
    groups = np.asarray(idx) // 4
    assert max(len(set(row)) for row in groups) <= 3
    free, _, _ = _moe(cfg, None, n_group=1, topk_group=1).route(
        flat, params["router"], bias)
    assert np.any(np.sort(np.asarray(free), -1) != np.sort(np.asarray(idx),
                                                           -1))
    np.testing.assert_allclose(jnp.sum(gates, axis=-1),
                               cfg.routed_scaling_factor, atol=1e-5)


def test_one_group_routes_exactly_as_before(uncut):
    """``n_group=1, topk_group=1`` (JoyAI's file says so) and the
    arguments left out are one layer: the same selection and gates, bit
    for bit."""
    cfg, _op, params, bias, x, _want, _counts = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    x_t = Tensor((2, 32, cfg.hidden_size), jnp.float32, name="x")
    old = HeldExpertsMoE("moe", x_t, 32, 16, 4, None, 1, 2.5, 1e-3)
    assert (old.n_group, old.topk_group) == (1, 1)
    one = _moe(cfg, None, n_group=1, topk_group=1)

    def today(x, router, bias):      # the selection before the groups
        logits = jnp.matmul(x, router, precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias, 4)
        picked = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, 2.5 * picked / jnp.sum(picked, axis=-1, keepdims=True)

    want_idx, want_gates = today(flat, params["router"], bias)
    for op in (old, one):
        idx, gates, _ = op.route(flat, params["router"], bias)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(gates, want_gates)


def test_grouped_routing_with_the_shared_expert_is_the_references(uncut):
    cfg, op, params, bias, x, want, counts = uncut
    state = {**op.init_state(), "bias": bias}
    with jax.default_matmul_precision("highest"):
        got = op.forward(params, [x], training=True, state=state)[0]
    np.testing.assert_allclose(got, want, atol=3e-6)
    new = op._last_state
    np.testing.assert_array_equal(new["tokens_per_expert"], counts)
    assert int(new["held_assignments"]) == 64 * cfg.num_experts_per_tok
    mean = np.mean(np.asarray(counts, np.float32))
    np.testing.assert_allclose(
        new["bias"], bias + cfg.bias_update_speed * np.sign(mean - counts),
        atol=1e-7)


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer(uncut):
    """The model-configs guide's share test: sixteen chips hold two of
    the 32 experts each; their routed parts, plus the shared expert
    (what every chip computes alike) counted once, are the uncut layer
    of the reference; every share counts the same group-limited
    routing."""
    cfg, _op, params, bias, x, want, counts = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(flat, _ref_moe_params(params)["shared"], F32)
        total = 0.0
        for rank in range(16):
            lo = 2 * rank
            share = _moe(cfg, (lo, 2), shared=0)
            mine = {"router": params["router"],
                    **{k: params[k][lo:lo + 2]
                       for k in ("w_gate", "w_up", "w_down")}}
            part = share.forward(mine, [x], training=True,
                                 state={**share.init_state(),
                                        "bias": bias})[0]
            np.testing.assert_array_equal(
                share._last_state["tokens_per_expert"], counts)
            assert int(share._last_state["held_assignments"]) \
                == int(counts[lo:lo + 2].sum())
            hp = dict(_hp(cfg), first_expert_held=lo)
            with_shared, _ = ref.expert_layer(
                _ref_moe_params(params, lo, lo + 2), bias, flat, hp, F32)
            np.testing.assert_allclose(part.reshape(flat.shape),
                                       with_shared - shared, atol=3e-6)
            total = total + part
    np.testing.assert_allclose(total + shared.reshape(x.shape), want,
                               atol=6e-6)


def test_the_slab_at_the_published_share():
    """8 of 512 held, 65,536 assignments a step: two even shares of
    1,024 rows in whole row tiles."""
    assert moe_ops.slab_rows(8192 * 8, 8, 512) == 2048
    assert 2048 % moe_ops.ROW_TILE == 0


# ----------------------------------------- the model through the trainer
def _program_and_reference(cfg, steps=2, batch=2):
    """``steps`` of ``train_epoch`` and of ``ref.train_steps`` from one
    initial state."""
    model, state = _compiled(cfg, batch)
    snap = family._snapshot(state, cfg)
    tokens = _tokens(cfg, steps, batch)
    inputs, labels = family._split(tokens)
    start = jax.tree_util.tree_map(jnp.copy, (snap["params"], snap["m"],
                                              snap["v"], snap["step"]))
    want = ref.train_steps(start, [jnp.copy(b) for b in snap["biases"]],
                           tokens, dict(_hp(cfg, batch)))
    state, mets = model.train_epoch(state, inputs, labels)
    return model, state, mets, want


def test_the_loss_every_gradient_the_update_and_the_bias_are_the_references():
    """Two Adam steps from one state: the mean loss, every tensor's
    first moment (after the first step ``(1 - b1) g``: the gradients
    and nothing else) and every updated tensor, the routing's counts and
    the router bias after its two steps."""
    cfg = _small()
    _m, state, mets, (want_state, want_bias, losses, counts) = \
        _program_and_reference(cfg)
    np.testing.assert_allclose(float(mets["loss"]), np.mean(losses),
                               rtol=1e-6)
    got = family._snapshot(state, cfg)
    for part, want, tol in (("params", want_state[0], 3e-6),
                            ("m", want_state[1], 1e-6)):
        flat_got = ref.leaves_by_name(got[part])
        flat_want = ref.leaves_by_name(want)
        # embed, head, final norm; 3 x 2 norms; 2 KDA x 13, 1 MLA x 8;
        # the dense layer's 3; 2 expert layers x (router + 3 + 3 shared)
        assert len(flat_got) == len(flat_want) == 3 + 6 + 26 + 8 + 3 + 14
        for name, value in flat_want.items():
            np.testing.assert_allclose(flat_got[name], value, atol=tol,
                                       err_msg=f"{part} {name}")
    assert len(family._moe_ops(cfg)) == len(counts[0]) == 2
    for layer, name in enumerate(family._moe_ops(cfg)):
        np.testing.assert_array_equal(
            got["counters"]["tokens_per_expert"][layer],
            np.sum([c[layer] for c in counts], axis=0))
        np.testing.assert_array_equal(
            mets[f"{name}/tokens_per_expert"],
            got["counters"]["tokens_per_expert"][layer])
        np.testing.assert_allclose(got["biases"][layer], want_bias[layer],
                                   atol=1e-7)
        assert float(mets[f"{name}/bias_abs_max"]) > 0.0


def test_one_step_gives_the_references_gradients():
    cfg = _small()
    _m, state, _mets, (want, *_rest) = _program_and_reference(cfg, steps=1)
    got = ref.leaves_by_name(family._snapshot(state, cfg)["m"])
    for name, value in ref.leaves_by_name(want[1]).items():
        scale = float(jnp.max(jnp.abs(value)))
        assert scale > 0, name
        np.testing.assert_allclose(got[name], value, atol=2e-5 * scale
                                   + 1e-9, err_msg=name)


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


def test_the_program_event_names_the_kda_cores_form():
    from dlrm_flexflow_tpu.telemetry import event_log
    from dlrm_flexflow_tpu.telemetry.schema import validate_event
    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    with event_log() as log:
        model.train_epoch(state, inputs, labels)
    events = [e for e in log.events() if e["type"] == "program"]
    assert [(e["kda_core"], e["attention_core"]) for e in events] \
        == [({"chunked": 2}, {"pallas": 0, "plain": 1})]
    assert validate_event(events[0]) == []
    counted = [e for e in log.events() if e["type"] == "op_counters"]
    assert len(counted) == 2
    assert len(counted[0]["counters"]["tokens_per_expert"]) == 16


def test_every_scope_of_the_compiled_step_is_attributed():
    """The optimized HLO of the tiny model's ``train_epoch``: every
    ``ff.lm.*`` scope of the issue's list is there, the KDA core
    forward, recomputed and backward, and the family's groups hold
    every phase found."""
    from benchmarks.lib import phases

    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    text = model._train_epoch.lower(state, inputs,
                                    labels).compile().as_text()
    found = set(profiling.hlo_phases(text).values())
    for scope in ("ff.lm.embed", "ff.lm.kda.proj", "ff.lm.kda.conv",
                  "ff.lm.kda.core", "ff.lm.kda.gate", "ff.lm.mla.proj",
                  "ff.lm.mla.core", "ff.lm.ffn", "ff.lm.moe.route",
                  "ff.lm.moe.dispatch", "ff.lm.moe.experts",
                  "ff.lm.moe.combine", "ff.lm.moe.shared", "ff.lm.head",
                  "ff.step.dense_update"):
        assert scope in found or scope + ".bwd" in found, scope
    for scope in ("ff.lm.kda.proj", "ff.lm.kda.conv", "ff.lm.kda.core",
                  "ff.lm.mla.proj"):
        assert scope + ".remat" in found, scope
        assert scope + ".bwd" in found, scope
    assert "ff.lm.mla.core.bwd" in found
    groups = {phases.group_of(p, family.PHASE_GROUPS)
              for p in found - {profiling.UNATTRIBUTED}}
    assert None not in groups
    assert {"kda", "attn", "moe", "ffn", "head", "dense_update"} <= groups


def test_the_three_apps_share_one_loss_scale_and_optimizer():
    """``apps/lm_common.py`` holds them; the older apps keep their names
    as re-exports, so what imported them imports what it did."""
    for module in (mla_moe_lm, gdn_moe_lm, app):
        assert module.token_loss is lm_common.token_loss
        assert module.optimizer is lm_common.optimizer
        assert module.EMBEDDING_STDDEV == lm_common.EMBEDDING_STDDEV == 1.0
    assert lm_common.token_loss.__name__ == "sparse_token_crossentropy"
    opt = app.optimizer(_small(learning_rate=1e-3, adam_beta2=0.9))
    assert (opt.lr, opt.beta1, opt.beta2, opt.epsilon) \
        == (1e-3, 0.9, 0.9, 1e-8)


# ------------------------------------------------ the configuration's file
#: the catalog's numbers for Ling-3.0-flash-VL (architectures.jsonl), the
#: keys this PR did not reduce
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-6, "head_dim": 128, "partial_rotary_factor": 0.5,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "use_qk_norm": True,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "linear_silu": True, "rotary_dim": 64,
    "use_mla_nope": False, "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True}


def test_the_configuration_file_holds_the_catalogs_numbers():
    config = json.load(open(CONFIG))
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    for key, on_from in (("expert_swiglu_limit_list", 35),
                         ("share_expert_swiglu_limit_list", 34)):
        assert len(config[key]) == 42 and not any(config[key][:on_from])
        assert all(config[key][on_from:])
    assert config["overrides"] == {}   # the rehearsal's alone
    assert (config["num_hidden_layers"], config["experts_held"],
            config["heads_held"], config["vocab_size"],
            config["first_layer_held"]) == (7, 8, 16, 19648, 1)
    assert config["published"] == {"num_hidden_layers": 42,
                                   "vocab_size": 157184,
                                   "experts_held": 512, "heads_held": 32}
    assert config["vocab_size"] * 8 == 157184
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "heads_held", "vocab_size"]
    assert config["deployment"]["chips_sharing_a_layer"] == 64 \
        == config["deployment"]["expert_parallel"] \
        == config["deployment"]["tensor_parallel_heads"] \
        * config["deployment"]["data_parallel"]
    for name in ("optimizer", "bias_update_speed", "initializer_range",
                 "kda_init", "layer_type_rule", "kda_safe_gate",
                 "qk_norm_placement", "group_mask", "swiglu_limit",
                 "no_mtp", "no_vision_tower", "no_auxiliary_loss",
                 "compute_dtype", "recompute"):
        assert config["assumed"][name], name
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "ling3-flash-ep64"]
    assert entry[0]["reduced"] == config["reduced"]
    assert entry[0]["source"] == config["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic", cell["traffic"] + ".json")))
    # the sibling's 8k mix under a name of this family's own (a mix
    # serves one family: tests/benchmark's ``lay_tiny``)
    assert mix == json.load(open(os.path.join(
        ROOT, "benchmarks/traffic/pretrain-8k.json")))


def _shapes(cfg):
    model = app.build(cfg, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    shapes = jax.eval_shape(lambda: (model.compile(
        optimizer=app.optimizer(cfg), loss_type=app.token_loss, metrics=(),
        mesh=False) and None) or model.init(seed=0))
    return model, {name: sum(int(np.prod(a.shape)) for a in p.values())
                   for name, p in shapes.params.items()}


def test_the_cells_size_has_680_1_million_parameters():
    config = json.load(open(CONFIG))
    cfg = family.model_config(config, {"seq_len": 8192})
    assert (cfg.num_experts, cfg.experts_held, cfg.heads_held,
            cfg.seq_len) == (512, 8, 16, 8192)
    assert [cfg.published_index(i) for i in range(7)] == list(range(1, 8))
    assert [cfg.is_latent_attention(i) for i in range(7)] \
        == [False, False, False, False, True, False, False]
    assert [cfg.is_dense(i) for i in range(7)] == [True] + [False] * 6
    _model, by_op = _shapes(cfg)
    assert by_op["layer_0_kda"] == 31_525_008     # the issue's 31.5M
    assert by_op["layer_0_kda"] == 6 * 2560 * 2048 + 2560 * 16 \
        + 3 * 4 * 2048 + 16 + 2048 + 128
    assert by_op["layer_4_mla"] == 16_720_768     # 16.7M
    assert by_op["layer_0_ffn"] == 3 * 2560 * 6144
    assert by_op["layer_1_moe"] == 2560 * 512 + 9 * 3 * 2560 * 768
    assert sum(by_op.values()) == 680_062_176     # 680.1M, to the parameter


def test_without_a_share_it_is_the_whole_published_language_model():
    """``heads_held``, ``experts_held`` and ``first_layer_held`` unset
    and the published depth: 42 layers, 2 dense, every sixth latent
    attention, 32 heads, 512 experts, the whole vocabulary; shapes
    alone, nothing allocated.  The SwiGLU clamp of the last layers is
    not built, so their limits are zeroed here, and refused as
    published."""
    config = json.load(open(CONFIG))
    whole = {**config, **config["published"], **config["train"],
             "experts_held": None, "heads_held": None,
             "first_layer_held": 0, "seq_len": 8192}
    with pytest.raises(ValueError,
                       match=r"share_expert_swiglu_limit_list\[34\] = 5"):
        app.build(app.KdaMoeLmConfig.from_dict(whole),
                  FFConfig(batch_size=1))
    whole.update(expert_swiglu_limit_list=[0] * 42,
                 share_expert_swiglu_limit_list=[0] * 42)
    cfg = app.KdaMoeLmConfig.from_dict(whole)
    model, by_op = _shapes(cfg)
    kinds = ["mla" if cfg.is_latent_attention(i) else "kda"
             for i in range(42)]
    assert kinds.count("mla") == 7 and kinds[5] == kinds[41] == "mla"
    assert [i for i in range(42) if cfg.is_dense(i)] == [0, 1]
    kda = 6 * 2560 * 4096 + 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 2 * 192 \
        + 2560 * 32 + 4096 * 2560
    moe = 2560 * 512 + 513 * 3 * 2560 * 768
    assert by_op["layer_0_kda"] == kda and by_op["layer_5_mla"] == mla
    assert by_op["layer_2_moe"] == moe
    total = 35 * kda + 7 * mla + 2 * 3 * 2560 * 6144 + 40 * moe \
        + 2 * 157184 * 2560 + (2 * 42 + 1) * 2560
    assert sum(by_op.values()) == total
    assert 123e9 < total < 127e9          # "about 125B parameters"
    active = total - 40 * 504 * 3 * 2560 * 768
    assert 5.3e9 < active < 5.7e9          # "5.5B active"
    assert model._program_fields["kda_core"] == {"chunked": 35}


@pytest.mark.parametrize("key,value", [
    ("score_function", "softmax"), ("kda_safe_gate", False),
    ("use_kda_lora", True), ("use_nGPT", True),
    ("gated_attention_proj_granularity_type", "elementwise")])
def test_a_variant_that_is_not_built_is_refused_by_name(key, value):
    config = json.load(open(CONFIG))
    with pytest.raises(ValueError, match=key):
        app.KdaMoeLmConfig.from_dict({**config, key: value})


def test_the_flop_count_against_a_hand_count():
    """``train_flops_per_sample`` at the cell's sizes by hand, and the
    two cores' counts."""
    cell = run.resolve(ROOT, CELL)
    config, traffic = cell["config"], cell["traffic"]
    s = 8192
    kda = 2560 * (5 * 2048 + 16) + 2048 * 2560
    mla = 2560 * 3072 + 2560 * 576 + 512 * 4096 + 2560 * 16 + 2048 * 2560
    moe = 2560 * 512 + 3 * 2560 * 768 * (8 * 8 / 512 + 1)
    matmuls = 6 * s * (6 * kda + mla + 3 * 2560 * 6144 + 6 * moe
                       + 2560 * 19648)
    core = 3 * s * s * 16 * 320
    rule = 6 * 3 * s * 16 * 6 * 128 * 128
    got = family.train_flops_per_sample(config, traffic)
    assert got == pytest.approx(matmuls + core + rule, rel=1e-9)
    assert got == pytest.approx(18.4e12, rel=0.01)
    assert family.attention_core_work(config, traffic)[0] \
        == pytest.approx(3.5 * s * s * 16 * 320)
    flops, nbytes = family.kda_core_work(config, traffic)
    assert flops == 3 * s * 16 * 6 * 128 * 128
    assert nbytes == 3 * s * 16 * (3 * 128 * 2 + 128 * 4 + 128 * 4 + 4)
    assert (family.kda_layers(config, traffic),
            family.attention_layers(config, traffic),
            family.moe_layers(config, traffic)) == (6, 1, 6)
    assert traffic["batches"] * traffic["dispatches"] \
        * traffic["epochs_per_dispatch"] == 16


# ------------------------- the family's comparison, with faults planted
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's real files at the rehearsal's size (as
    ``tests/benchmark`` lays them), and the staged driver."""
    cell = run.resolve(ROOT, CELL)
    tiny = json.load(open(os.path.join(
        ROOT, "tests/benchmark/tiny.kda_moe_lm.json")))
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
    assert report["bias_err"] == 0
    assert report["grad_err_q90"] < 1e-4 and report["update_err"] < 1e-4
    assert report["grad_err_mixer_max"] < 0.01


def scalar_decay_operands(q, k, v, g, beta, cd):
    """The planted fault: the decay taken as one scalar a head (the mean
    over its channels), which is the sibling's rule."""
    return deltanet._chunk_operands(q, k, v, jnp.mean(g, axis=-1), beta, cd)


@pytest.mark.parametrize("fault,over", [
    ("state_unchanged", {"update_err", "grad_err_max"}),
    ("half_batch", {"grad_err_max", "counter_slack"}),
    ("bias_never_updated", {"bias_err"}),
    ("expert_dropped", {"grad_err_max"}),
    ("decay_one_scalar_a_head", {"grad_err_mixer_max"}),
    ("group_mask_dropped", {"count_err"})])
def test_a_planted_fault_is_not_correct(tiny_cell, fault, over, monkeypatch):
    """The timed path broken underneath the comparison: the state
    returned unchanged; half of every batch left out; the router bias
    put back to what it was; one held expert's rows zeroed behind the
    grouped matmul; the KDA rule's decay taken as one scalar a head; the
    router choosing over all groups."""
    real = tiny_cell[2].check_steps

    def steps(model, state, inputs, labels):
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
    if fault == "decay_one_scalar_a_head":
        monkeypatch.setattr(deltanet, "_channel_chunk_operands",
                            scalar_decay_operands)
    if fault == "group_mask_dropped":
        whole_init = HeldExpertsMoE.__init__

        def ungrouped(self, *args, **kw):
            whole_init(self, *args, **{**kw, "n_group": 1, "topk_group": 1})

        monkeypatch.setattr(HeldExpertsMoE, "__init__", ungrouped)
    ok, report = _check(tiny_cell, steps)
    assert not ok, report
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= over, report
    if fault == "expert_dropped":
        assert ".moe.w_" in report["grad_worst_tensor"]
        assert report["grad_worst_tensor"].endswith(".1")
        assert report["grad_err_max"] == pytest.approx(1.0, abs=1e-3)
    if fault == "decay_one_scalar_a_head":
        assert ".kda." in report["grad_worst_mixer"]


def test_the_control_one_precision_down_is_not_correct(tiny_cell):
    """The chip's arrangement at the rehearsal's size: the program in
    bfloat16 against the bfloat16 reference reads correct, and
    ``control_steps`` (the reference in the program's place with float8
    operands) does not, by more than one limit.  The control writes a
    whole state back, and the program trains on from it."""
    config, traffic, driver = tiny_cell
    config = copy.deepcopy(config)
    config["ffconfig"]["compute_dtype"] = "bfloat16"
    cell = (config, traffic, driver)
    assert family.LOWER["bfloat16"] == "float8_e4m3fn"
    ok, report = _check(cell, None)
    assert ok, report
    assert 1e-4 < report["grad_err_max"] < family.LIMITS["grad_err_mixer_max"]
    ok, report = _check(cell, family.control_steps(config))
    assert not ok, report
    assert report["counter_slack"] == 0
    assert len({name for name, limit in family.LIMITS.items()
                if report[name] > limit}) >= 2, report
    model, state = family.build(config, traffic, 3, None)
    inputs, labels = family._split(family._sequences(
        config, traffic, 2 * traffic["batch"], 3, 1).reshape(
            2, traffic["batch"], -1))
    state, _ = family.control_steps(config)(model, state, inputs, labels)
    assert int(state.step) == 2
    state, losses = driver.check_steps(model, state, inputs, labels)
    assert np.isfinite(float(losses[0])) and int(state.step) == 4
