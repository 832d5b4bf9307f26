"""The Gated DeltaNet + routed-experts language model (Qwen3-Next) on the
CPU at a small size, seeded random weights, each piece against the plain
reference (``benchmarks/reference/gdn_moe_lm_ref.py``): the chunked
delta rule against the token-by-token recurrence, the gated attention op
with grouped key/value heads, softmax routing with the gated shared
expert and the share cut, the whole model through the trainer, and the
family's comparison with faults planted in the path it times."""

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
from benchmarks.models import gdn_moe_lm as family  # noqa: E402
from benchmarks.reference import gdn_moe_lm_ref as ref  # noqa: E402
from dlrm_flexflow_tpu import profiling  # noqa: E402
from dlrm_flexflow_tpu.apps import gdn_moe_lm as app  # noqa: E402
from dlrm_flexflow_tpu.config import FFConfig  # noqa: E402
from dlrm_flexflow_tpu.ops import attention as attention_ops  # noqa: E402
from dlrm_flexflow_tpu.ops import deltanet  # noqa: E402
from dlrm_flexflow_tpu.ops import moe as moe_ops  # noqa: E402
from dlrm_flexflow_tpu.ops.attention import (GatedAttention,  # noqa: E402
                                             blockwise_causal_attention,
                                             sdpa)
from dlrm_flexflow_tpu.ops.deltanet import GatedDeltaNet  # noqa: E402
from dlrm_flexflow_tpu.ops.moe import HeldExpertsMoE  # noqa: E402
from dlrm_flexflow_tpu.ops.transformer import (RMSNorm,  # noqa: E402
                                               rope_half_split)
from dlrm_flexflow_tpu.tensor import Tensor  # noqa: E402

F32 = jnp.dtype("float32")
CONFIG = os.path.join(ROOT, "benchmarks/configs/qwen3-next-ep16.json")
TRAFFIC = os.path.join(ROOT, "benchmarks/traffic/pretrain-16k.json")


def _small(**changes):
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                linear_num_key_heads=2, linear_num_value_heads=4,
                linear_key_head_dim=8, linear_value_head_dim=6,
                num_experts=16, experts_held=4, num_experts_per_tok=4,
                moe_intermediate_size=16, shared_expert_intermediate_size=16,
                seq_len=32)
    base.update(changes)
    return app.GdnMoeLmConfig(**base)


@pytest.fixture(autouse=True)
def four_blocks(monkeypatch):
    """32 tokens in chunks and key blocks of 8, so that the chunked rule
    carries its state three times and the blockwise core loops."""
    monkeypatch.setattr(deltanet, "CHUNK", 8)
    monkeypatch.setattr(attention_ops, "ATTENTION_BLOCK", 8)


def _hp(cfg, batch=2):
    return ref._Frozen(family.hyper(cfg, {"batch": batch,
                                          "seq_len": cfg.seq_len}))


def _built(cfg, batch=2):
    model = app.build(cfg, FFConfig(batch_size=batch))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    return model


def _compiled(cfg, batch=2, seed=0):
    model = _built(cfg, batch)
    return model, model.init(seed=seed)


def _tokens(cfg, steps, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size,
                        size=(steps, batch, cfg.seq_len + 1)).astype(np.int32)


# ---------------------------------------------------- the chunked delta rule
def _rule_inputs(seq, hk=2, hv=4, dk=8, dv=6, batch=2):
    """q, k normalised as the mixer hands them over; the four value
    heads' decays near 1 (exp(-12) a token), near 0 (exp(3 .. 30)),
    and two in between."""
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    q = deltanet.l2_normalised(jax.random.normal(
        keys[0], (batch, seq, hk, dk))) * dk ** -0.5
    k = deltanet.l2_normalised(jax.random.normal(keys[1],
                                                 (batch, seq, hk, dk)))
    v = jax.random.normal(keys[2], (batch, seq, hv, dv))
    g = -jnp.exp(jnp.array([-12.0, 3.0, 0.0, 1.0])[:hv]) * jax.nn.softplus(
        jax.random.normal(keys[3], (batch, seq, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, hv)))
    return (q, k, v, g, beta), jax.random.normal(keys[5],
                                                 (batch, seq, hv, dv))


def _token_rule(q, k, v, g, beta):
    """The reference's recurrence, one sequence at a time."""
    group = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x, group, axis=2) for x in (q, k))
    return jax.vmap(ref.delta_rule)(q, k, v, g, beta)


@pytest.mark.parametrize("chunk,seq", [(8, 29), (16, 48), (64, 160)])
def test_the_chunked_rule_is_the_token_recurrence(chunk, seq, monkeypatch):
    """Output and all five gradients, over several chunks and lengths
    the chunk does not divide, with decays from exp(-30) to 1 - 1e-6 a
    token."""
    monkeypatch.setattr(deltanet, "CHUNK", chunk)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 1)
    args, w = _rule_inputs(seq)
    decay = np.exp(np.asarray(args[3]))
    assert decay.min() < 1e-8 and decay.max() > 1 - 1e-5
    with jax.default_matmul_precision("highest"):
        loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
        got = deltanet.gated_delta_rule(*args)
        want = _token_rule(*args)
        np.testing.assert_allclose(got, want, atol=2e-6)
        got_g = jax.grad(loss(deltanet.gated_delta_rule),
                         argnums=range(5))(*args)
        want_g = jax.grad(loss(_token_rule), argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_the_chunked_rule_in_bfloat16_is_near_the_recurrence():
    """bf16 operands, f32 state and accumulators: within bf16's rounding
    of the f32 recurrence on the same rounded inputs."""
    args, _ = _rule_inputs(64)
    q, k, v = (x.astype(jnp.bfloat16).astype(F32) for x in args[:3])
    got = deltanet.gated_delta_rule(q, k, v, *args[3:],
                                    compute_dtype=jnp.bfloat16)
    want = _token_rule(q, k, v, *args[3:])
    assert got.dtype == F32
    np.testing.assert_allclose(got, want, atol=0.03)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-5   # it did round


def test_the_backward_keeps_a_state_a_chunk_and_never_one_a_token():
    """What the differentiated rule keeps between its passes: its five
    inputs and the state at each chunk's start."""
    args, _ = _rule_inputs(32)
    _out, pull = jax.vjp(deltanet.gated_delta_rule, *args)
    kept = sorted(tuple(x.shape) for x in jax.tree_util.tree_leaves(pull)
                  if hasattr(x, "shape") and x.ndim >= 3)
    assert (4, 2, 4, 8, 6) in kept            # chunks, batch, heads, dk, dv
    assert not any(len(s) >= 4 and s[-2:] == (8, 6) and 32 in s
                   for s in kept), kept       # no (.., 32 tokens, .., dk, dv)
    assert max(int(np.prod(s)) for s in kept) <= 4 * 2 * 4 * 8 * 6


@pytest.mark.parametrize("n", [8, 64])
def test_the_unit_lower_inverse_and_its_gradient(n):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1) \
        * min(1.0, 4.0 / n)     # the inverse's entries stay of order 1
    eye = jnp.eye(n)
    with jax.default_matmul_precision("highest"):
        got = deltanet.unit_lower_inverse(a)
        np.testing.assert_allclose(jnp.matmul(got, eye + a),
                                   jnp.broadcast_to(eye, a.shape), atol=1e-4)
        w = jax.random.normal(jax.random.PRNGKey(1), a.shape)
        strict = jnp.tril(jnp.ones((n, n)), -1)
        got_g = jax.grad(lambda a: jnp.sum(
            deltanet.unit_lower_inverse(a) * w))(a)
        want_g = jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(eye + a * strict) * w))(a)
    scale = float(jnp.max(jnp.abs(want_g)))
    np.testing.assert_allclose(got_g, want_g, atol=1e-4 * max(scale, 1.0))


def test_the_causal_convolution_is_a_convolution():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 5))
    got = deltanet.causal_conv(x, w)
    for b in range(2):
        for c in range(5):
            full = np.convolve(np.asarray(x[b, :, c]),
                               np.asarray(w[::-1, c]))[:11]
            np.testing.assert_allclose(got[b, :, c], full, atol=1e-5)


def _gdn_op(cfg, batch=2):
    x_t = Tensor((batch, cfg.seq_len, cfg.hidden_size), jnp.float32, name="x")
    return GatedDeltaNet("gdn", x_t, cfg.linear_num_key_heads,
                         cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim,
                         cfg.linear_conv_kernel_dim, cfg.rms_norm_eps)


def test_the_deltanet_mixer_is_the_references():
    cfg = _small()
    op = _gdn_op(cfg)
    params = op.init_params(jax.random.PRNGKey(2))
    assert float(jnp.max(jnp.abs(params["conv"]))) <= 0.5
    assert np.all(np.asarray(params["a_log"]) <= np.log(16.0))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.hidden_size))
    hp = dict(_hp(cfg))
    with jax.default_matmul_precision("highest"):
        fn = lambda p, x: op.forward(p, [x])[0]
        want_fn = lambda p, x: jax.vmap(
            lambda seq: ref.gated_delta_net(p, seq, hp, F32))(x)
        np.testing.assert_allclose(fn(params, x), want_fn(params, x),
                                   atol=2e-6)
        loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
        got_g = jax.grad(loss(fn), argnums=(0, 1))(params, x)
        want_g = jax.grad(loss(want_fn), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=2e-4)


# ------------------------------------- the rule's Pallas kernels (interpreted)
@pytest.fixture
def rule_kernels_interpreted(monkeypatch):
    """The rule's choice and kernels as on a TPU, the kernels run by the
    Pallas interpreter, at the chunk the kernels are written for."""
    from dlrm_flexflow_tpu.ops import pallas_deltanet
    real = pallas_deltanet.pl.pallas_call
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: True)
    monkeypatch.setattr(deltanet, "CHUNK", pallas_deltanet.CHUNK)
    monkeypatch.setattr(
        pallas_deltanet.pl, "pallas_call",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    jax.clear_caches()      # the jit callee keeps what it traced
    yield pallas_deltanet
    jax.clear_caches()


def _with_grads(rule, args, w):
    """(o, dq, dk, dv, dg, dbeta) under the loss ``sum(o * w)``."""
    both = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * w), o))(rule(*a)),
        argnums=range(5), has_aux=True))
    (_, o), grads = both(*args)
    return (o, *grads)


def _chunked_form(*args, **kw):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(deltanet, "_on_tpu", lambda: False)
        return deltanet.gated_delta_rule(*args, **kw)


RULE_NAMES = ("o", "q", "k", "v", "g", "beta")


def test_the_rule_kernels_are_the_token_recurrence(rule_kernels_interpreted):
    """Both kernels in f32 at the published head width, 2 key heads on
    4 value heads, 8 chunks: output and all five gradients against the
    token recurrence at the chunked form's tolerances (decays from
    exp(-30) to 1 - 1e-6 a token), and against the chunked form; two
    ``pallas_call``s."""
    seq = rule_kernels_interpreted.BLOCK
    args, w = _rule_inputs(seq, dk=128, dv=128, batch=1)
    decay = np.exp(np.asarray(args[3]))
    assert decay.min() < 1e-8 and decay.max() > 1 - 1e-5
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(deltanet.gated_delta_rule(*a) * w),
        argnums=range(5)))(*args))
    assert jaxpr.count("pallas_call") == 2
    assert jaxpr.count("name=gated_delta_fwd\n") \
        == jaxpr.count("name=gated_delta_bwd\n") == 1
    with jax.default_matmul_precision("highest"):
        got = _with_grads(deltanet.gated_delta_rule, args, w)
        want = _with_grads(_token_rule, args, w)
        chunked = _with_grads(_chunked_form, args, w)
    for name, a, b, c in zip(RULE_NAMES, got, want, chunked):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(a)), name
        # the chunked form's bounds; 128-wide heads over 512 tokens sum
        # to gradients of 30, so a millionth of the largest beside them
        atol = 2e-6 if name == "o" else max(
            2e-5, 1e-6 * float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(a, b, atol=atol, err_msg=name)
        np.testing.assert_allclose(a, c, atol=atol, err_msg=name)


def test_the_rule_kernels_in_bfloat16_are_near_the_recurrence(
        rule_kernels_interpreted):
    """bf16 operands, f32 decays, ``T``, state and accumulators: every
    number within bf16's rounding of the f32 recurrence on the same
    rounded inputs, the output no further from it than the chunked
    form's."""
    bf = jnp.bfloat16
    args, w = _rule_inputs(rule_kernels_interpreted.BLOCK, dk=128, dv=128,
                           batch=1)
    args = tuple(x.astype(bf).astype(F32) for x in args[:3]) + args[3:]
    rule = lambda f: lambda *a: f(*a, compute_dtype=bf)
    got = _with_grads(rule(deltanet.gated_delta_rule), args, w)
    chunked = jax.jit(rule(_chunked_form))(*args)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(_token_rule, args, w)
    assert got[0].dtype == F32
    for name, a, b in zip(RULE_NAMES, got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < 0.02 * scale, name
    err, chunked_err = (float(jnp.max(jnp.abs(x - want[0])))
                        for x in (got[0], chunked))
    assert 1e-5 < err < 2 * chunked_err      # it did round, and no more


def test_a_state_dropped_between_the_kernels_blocks_is_caught(
        rule_kernels_interpreted, monkeypatch):
    """What carries the state from a grid step to the next is the
    kernel's scratch: the kept states equal the chunked form's, and a
    forward that loses the state at one block's boundary (the two halves
    run apart) reads gradients thousands of times over the limit."""
    kernels = rule_kernels_interpreted
    seq = 2 * kernels.BLOCK
    args, w = _rule_inputs(seq, hk=1, hv=2, dk=128, dv=128, batch=1)
    with jax.default_matmul_precision("highest"):
        _, starts = jax.jit(kernels.forward)(*args)
        want_starts = jax.jit(lambda *a: deltanet._scan_chunks(
            deltanet._chunk_operands(
                *deltanet._laid_out(*a, kernels.CHUNK), F32), F32)[1])(*args)
        assert starts.shape == (2, seq // kernels.CHUNK, 128, 128)
        np.testing.assert_allclose(
            starts, jnp.moveaxis(want_starts[:, 0], 0, 1), atol=2e-6)
        assert float(jnp.max(jnp.abs(starts[0, -1]))) > 0.1
        want = _with_grads(_token_rule, args, w)
        whole = kernels.forward

        def forgetting(*xs):
            halves = [whole(*(x[:, part] for x in xs))
                      for part in (slice(0, seq // 2), slice(seq // 2, seq))]
            return tuple(jnp.concatenate(pair, axis=1)
                         for pair in zip(*halves))

        monkeypatch.setattr(kernels, "forward", forgetting)
        got = _with_grads(deltanet.gated_delta_rule, args, w)
    errs = {name: float(jnp.max(jnp.abs(a - b)))
            for name, a, b in zip(RULE_NAMES, got, want)}
    assert errs["o"] > 1e-2 and max(errs.values()) > 1e-1, errs


@pytest.mark.parametrize("on_tpu,seq,dk,dv,dtype,form", [
    (True, 16384, 128, 128, "bfloat16", "pallas"),
    (True, 512, 128, 128, "float32", "pallas"),
    (True, 1024, 256, 128, "bfloat16", "pallas"),
    (False, 16384, 128, 128, "bfloat16", "chunked"),    # the CPU
    (True, 29, 128, 128, "bfloat16", "chunked"),        # no whole block
    (True, 576, 128, 128, "bfloat16", "chunked"),       # whole chunks alone
    (True, 512, 24, 128, "bfloat16", "chunked"),        # no lane tile
    (True, 512, 128, 64, "float32", "chunked"),
    (True, 512, 128, 128, "float16", "chunked")])
def test_the_rule_form_follows_the_backend_and_the_shapes(
        on_tpu, seq, dk, dv, dtype, form, monkeypatch):
    """``core_form`` from the backend and the shapes alone, and the
    differentiated rule's jaxpr holds the two kernels or none."""
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(deltanet, "CHUNK", 64)
    assert deltanet.core_form(seq, dk, dv, dtype) == form
    if dtype == "float16":
        return      # the op's compute dtype is bfloat16 or float32
    tokens = min(seq, 1024)
    assert deltanet.core_form(tokens, dk, dv, dtype) == form
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, F32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(deltanet.gated_delta_rule(
            *a, compute_dtype=jnp.dtype(dtype))), argnums=range(5)))(
        shape(1, tokens, 1, dk), shape(1, tokens, 1, dk),
        shape(1, tokens, 2, dv), shape(1, tokens, 2), shape(1, tokens, 2)))
    assert jaxpr.count("pallas_call") == (2 if form == "pallas" else 0)


@pytest.mark.parametrize("layers", [1, 3])
def test_a_recomputed_layer_runs_the_rule_forward_once(layers, monkeypatch):
    """The training step of a recomputed model at the published head
    widths, lowered for a TPU here (no chip, no compile): one
    ``gated_delta_fwd`` and one ``gated_delta_bwd`` custom call in the
    module whether it holds one DeltaNet layer or three (the layers share
    the rule's jit callee and one recompute policy), the forward's
    function called once a layer and never inside a recomputation (the
    run keeps the rule's output and boundary states, ``GDN_SAVED``), the
    backward's once a layer under the transpose; the ``program`` event
    would read ``pallas`` and ``core_kept`` the layer count."""
    from dlrm_flexflow_tpu.ops import pallas_deltanet
    from tests.test_pallas_kda import _functions_calling
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: True)
    jax.clear_caches()
    cfg = _small(num_hidden_layers=layers, linear_key_head_dim=128,
                 linear_value_head_dim=128, seq_len=pallas_deltanet.BLOCK)
    assert cfg.recompute
    model = _built(cfg, batch=1)
    assert model._program_fields["gdn_core"] == {"pallas": layers,
                                                 "chunked": 0}
    assert model._program_fields["core_kept"] == {"gdn_core": layers}
    state = jax.eval_shape(lambda: model.init(seed=0))
    ids = jax.ShapeDtypeStruct((1, cfg.seq_len), jnp.int32)
    labels = jax.ShapeDtypeStruct((1, cfg.seq_len, 1), jnp.int32)
    text = model._train_step.trace(state, {"ids": ids}, labels).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 2
    forward = _functions_calling(text, "gated_delta_fwd")
    backward = _functions_calling(text, "gated_delta_bwd")
    assert len(forward) == len(backward) == 1
    (fwd_sites,), (bwd_sites,) = forward.values(), backward.values()
    assert len(fwd_sites) == len(bwd_sites) == layers
    assert all("ff.lm.gdn.core" in s and "rematted_computation" not in s
               and "transpose" not in s for s in fwd_sites), fwd_sites
    assert all("ff.lm.gdn.core" in s and "transpose" in s
               for s in bwd_sites), bwd_sites
    jax.clear_caches()


def test_a_recomputed_model_with_the_kernels_is_the_chunked_ones(
        rule_kernels_interpreted):
    """One f32 training step of a one-layer model at 128-wide heads and
    one block of tokens, the mixer recomputed: with the kernels (their
    output and states kept through the recomputation) the loss and every
    gradient (Adam's first moment after one step) are the chunked
    form's."""
    cfg = _small(num_hidden_layers=1, linear_key_head_dim=128,
                 linear_value_head_dim=128,
                 seq_len=rule_kernels_interpreted.BLOCK)
    assert cfg.recompute
    model = _built(cfg, batch=1)
    assert model._program_fields["core_kept"] == {"gdn_core": 1}
    inputs, labels = family._split(_tokens(cfg, 1, batch=1))

    def one_step():
        with jax.default_matmul_precision("highest"):
            state, mets = model.train_epoch(model.init(seed=0), inputs,
                                            labels)
        return float(mets["loss"]), state.opt_state

    got_loss, got = one_step()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(deltanet, "_on_tpu", lambda: False)
        jax.clear_caches()
        want_loss, want = one_step()
    assert abs(got_loss - want_loss) < 1e-5 * abs(want_loss)
    moments = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-5 * scale + 1e-12,
                                   err_msg=jax.tree_util.keystr(path))
        moments += scale > 0
    assert moments > 10


@pytest.mark.parametrize("on_tpu,recompute", [(True, False), (False, True)])
def test_nothing_is_kept_without_a_recomputation_or_the_kernels(
        on_tpu, recompute, monkeypatch):
    """``core_kept`` counts a core only where its kernels run inside a
    recomputed run: 0 for a model built without recomputation (the
    kernels chosen), and 0 in the CPU's chunked form (recomputed)."""
    from dlrm_flexflow_tpu.ops import pallas_deltanet
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: on_tpu)
    cfg = _small(linear_key_head_dim=128, linear_value_head_dim=128,
                 seq_len=pallas_deltanet.BLOCK, recompute=recompute)
    fields = _built(cfg)._program_fields
    assert fields["gdn_core"] == ({"pallas": 3, "chunked": 0} if on_tpu
                                  else {"pallas": 0, "chunked": 3})
    assert fields["core_kept"] == {"attention_core": 0, "gdn_core": 0}


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1)])
def test_blockwise_core_with_grouped_heads_is_sdpa_on_repeated_heads(
        heads, kv_heads):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (2, heads, 32, 8))
    k = jax.random.normal(keys[1], (2, kv_heads, 32, 8))
    v = jax.random.normal(keys[2], (2, kv_heads, 32, 6))
    w = jax.random.normal(keys[3], (2, heads, 32, 6))
    full = lambda q, k, v: sdpa(
        q, jnp.repeat(k, heads // kv_heads, axis=1),
        jnp.repeat(v, heads // kv_heads, axis=1), causal=True)
    out = lambda f: (f(q, k, v), *jax.grad(
        lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v))
    for a, b in zip(out(blockwise_causal_attention), out(full)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-6)


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


def test_the_kernels_read_a_groups_head_in_place(kernels_interpreted):
    """The Pallas kernels (interpret mode) at the published head width,
    4 query heads on 2 key/value heads, 2 blocks: output and the three
    gradients against f32 ``sdpa`` on repeated heads, the key/value
    gradients summed over each group; k and v are never repeated."""
    seq, d = 2 * kernels_interpreted.BLOCK, 256
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (1, 4, seq, d))
    k = jax.random.normal(keys[1], (1, 2, seq, d))
    v = jax.random.normal(keys[2], (1, 2, seq, d))
    w = jax.random.normal(keys[3], (1, 4, seq, d))
    scale = d ** -0.5
    core = lambda q, k, v: blockwise_causal_attention(q, k, v, scale)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(core(*a) * w), (0, 1, 2)))(q, k, v))
    assert jaxpr.count("pallas_call") == 2
    out = lambda f: (f(q, k, v), *jax.grad(
        lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v))

    def full(q, k, v):
        with jax.default_matmul_precision("highest"):
            return sdpa(q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
                        causal=True, scale=scale)
    for name, a, b in zip(("o", "dq", "dk", "dv"), out(core), out(full)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


def test_the_half_split_rotary_embedding_turns_the_leading_part():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 16))
    got = rope_half_split(x, jnp.arange(12), 1e7, 8, seq_axis=1)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[:, 0], x[:, 0])        # position 0
    want = jax.vmap(lambda seq: ref.rope(seq, 1e7, 8))(x)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # element i pairs with i + 4, turned by position * theta^(-2i / 8)
    angle = 5.0 * 1e7 ** (-2 * 1 / 8)
    np.testing.assert_allclose(
        got[0, 5, 0, 1], x[0, 5, 0, 1] * np.cos(angle)
        - x[0, 5, 0, 5] * np.sin(angle), rtol=1e-5)


def test_the_zero_centred_norm_starts_at_one():
    x_t = Tensor((2, 5, 8), jnp.float32, name="x")
    op = RMSNorm("n", x_t, 1e-6, zero_centred=True)
    params = op.init_params(jax.random.PRNGKey(0))
    np.testing.assert_array_equal(params["scale"], np.zeros(8))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    np.testing.assert_allclose(op.forward({"scale": w}, [x])[0],
                               ref.norm(x, w, 1e-6), atol=1e-6)
    plain = RMSNorm("p", x_t, 1e-6)
    np.testing.assert_allclose(op.forward(params, [x])[0],
                               plain.forward(plain.init_params(
                                   jax.random.PRNGKey(0)), [x])[0])


def test_the_gated_attention_op_is_the_references():
    """Grouped heads, zero-centred q/k norms with weights off zero, the
    partial rotary embedding and the output gate: the op against the
    reference's layer (``sdpa``-style full softmax on repeated heads),
    output and every gradient."""
    cfg = _small()
    x_t = Tensor((2, 32, cfg.hidden_size), jnp.float32, name="x")
    op = GatedAttention("attn", x_t, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim, 4,
                        cfg.rope_theta, cfg.rms_norm_eps)
    params = op.init_params(jax.random.PRNGKey(3))
    assert not np.any(np.asarray(params["q_norm"]))
    params = dict(params, q_norm=0.1 * jax.random.normal(
        jax.random.PRNGKey(8), (cfg.head_dim,)), k_norm=0.1 * jax.random.normal(
            jax.random.PRNGKey(9), (cfg.head_dim,)))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.hidden_size))
    hp = dict(_hp(cfg), partial_rotary_factor=0.5)
    fn = lambda p, x: op.forward(p, [x])[0]
    want_fn = lambda p, x: jax.vmap(
        lambda seq: ref.gated_attention(p, seq, hp, F32))(x)
    np.testing.assert_allclose(fn(params, x), want_fn(params, x), atol=2e-6)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    got_g = jax.grad(loss(fn), argnums=(0, 1))(params, x)
    want_g = jax.grad(loss(want_fn), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-5)


# ---------------------------------------------- experts and the share cut
def _moe(cfg, held, shared=1, gated=True, tokens=(2, 32)):
    x_t = Tensor(tokens + (cfg.hidden_size,), jnp.float32, name="x")
    return HeldExpertsMoE("moe", x_t, cfg.num_experts,
                          cfg.moe_intermediate_size, cfg.num_experts_per_tok,
                          held, shared, score_func="softmax",
                          shared_gated=gated)


def _ref_moe_params(params, lo=None, hi=None):
    out = {"router": params["router"],
           "shared_gate": params["shared_sigmoid"],
           "shared": {k: params["shared_" + k[2:]]
                      for k in ("w_gate", "w_up", "w_down")}}
    out.update({k: params[k][lo:hi] for k in ("w_gate", "w_up", "w_down")})
    return out


@pytest.fixture(scope="module")
def uncut():
    """A layer that holds all 32 experts, its parameters, tokens, and
    the reference's output for them."""
    cfg = _small(num_experts=32, experts_held=None)
    op = _moe(cfg, None)
    params = op.init_params(jax.random.PRNGKey(5))
    params["router"] = 20.0 * params["router"]   # scores off the uniform
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.hidden_size))
    hp = dict(_hp(cfg), first_expert_held=0)
    want, counts = ref.expert_layer(_ref_moe_params(params),
                                    x.reshape(-1, cfg.hidden_size), hp, F32)
    return cfg, op, params, x, want.reshape(x.shape), counts


def test_softmax_routing_with_the_gated_shared_expert_is_the_references(
        uncut):
    cfg, op, params, x, want, counts = uncut
    got = op.forward(params, [x], training=True, state=op.init_state())[0]
    np.testing.assert_allclose(got, want, atol=2e-6)
    new = op._last_state
    np.testing.assert_array_equal(new["tokens_per_expert"], counts)
    assert int(new["held_assignments"]) == 64 * cfg.num_experts_per_tok
    assert not np.any(np.asarray(new["bias"]))           # no bias rule
    idx, gates, _ = op.route(x.reshape(-1, cfg.hidden_size),
                             params["router"], jnp.zeros(cfg.num_experts))
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 1.0, atol=1e-6)
    assert float(jnp.max(gates)) > 2.0 / cfg.num_experts_per_tok
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p)))
    got_g = jax.grad(loss(lambda p: op.forward(
        p, [x], training=True, state=op.init_state())[0]))(params)
    hp = dict(_hp(cfg), first_expert_held=0)
    want_g = jax.grad(loss(lambda p: ref.expert_layer(
        _ref_moe_params(p), x.reshape(-1, cfg.hidden_size), hp,
        F32)[0].reshape(x.shape)))(params)
    for name in params:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-5,
                                   err_msg=name)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(uncut):
    """The model-configs guide's share test: sixteen chips hold two of
    the 32 experts each; their routed parts, plus the gated shared
    expert (what every chip computes alike) counted once, are the uncut
    layer of the reference; each share is the reference's for that
    share; every share counts the same routing."""
    cfg, _op, params, x, want, counts = uncut
    flat = x.reshape(-1, cfg.hidden_size)
    ref_params = _ref_moe_params(params)
    shared = ref.swiglu(flat, ref_params["shared"], F32) * jax.nn.sigmoid(
        flat @ ref_params["shared_gate"])
    total = 0.0
    for rank in range(16):
        lo = 2 * rank
        share = _moe(cfg, (lo, 2), shared=0, gated=False)
        mine = {"router": params["router"],
                **{k: params[k][lo:lo + 2]
                   for k in ("w_gate", "w_up", "w_down")}}
        part = share.forward(mine, [x], training=True,
                             state=share.init_state())[0]
        np.testing.assert_array_equal(share._last_state["tokens_per_expert"],
                                      counts)
        assert int(share._last_state["held_assignments"]) \
            == int(counts[lo:lo + 2].sum())
        hp = dict(_hp(cfg), first_expert_held=lo)
        with_shared, _ = ref.expert_layer(
            _ref_moe_params(params, lo, lo + 2), flat, hp, F32)
        np.testing.assert_allclose(part.reshape(flat.shape),
                                   with_shared - shared, atol=2e-6)
        total = total + part
    np.testing.assert_allclose(total + shared.reshape(x.shape), want,
                               atol=5e-6)


def test_the_slab_at_the_published_share():
    """32 of 512 held, 163,840 assignments a step: two even shares in
    whole row tiles."""
    assert moe_ops.slab_rows(16384 * 10, 32, 512) == 20480
    assert 20480 % moe_ops.ROW_TILE == 0


def test_the_sigmoid_router_is_what_it_was():
    """The sibling family's arguments give its layer: sigmoid scores, no
    gate on the shared expert, no new parameter."""
    cfg = _small()
    x_t = Tensor((2, 32, cfg.hidden_size), jnp.float32, name="x")
    op = HeldExpertsMoE("moe", x_t, 16, 16, 4, (0, 4), 1, 2.5, 1e-3)
    assert op.score_func == "sigmoid" and not op.shared_gated
    assert "shared_sigmoid" not in {s.param_name for s in op.param_specs()}
    params = op.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    _idx, gates, _ = op.route(x, params["router"], jnp.zeros(16))
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 2.5, atol=1e-5)


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
    want = ref.train_steps(start, tokens, dict(_hp(cfg, batch)))
    state, mets = model.train_epoch(state, inputs, labels)
    return model, state, mets, want


def test_the_loss_every_gradient_and_the_update_are_the_references():
    """Two Adam steps from one state: the mean loss, every tensor's
    first moment (after the first step ``(1 - b1) g``: the gradients
    and nothing else) and every updated tensor, and the routing's
    counts."""
    cfg = _small()
    _m, state, mets, (want_state, losses, counts) = \
        _program_and_reference(cfg)
    np.testing.assert_allclose(float(mets["loss"]), np.mean(losses),
                               rtol=1e-6)
    got = family._snapshot(state, cfg)
    for part, want, tol in (("params", want_state[0], 3e-6),
                            ("m", want_state[1], 1e-6)):
        flat_got = ref.leaves_by_name(got[part])
        flat_want = ref.leaves_by_name(want)
        assert len(flat_got) == len(flat_want) == 3 + 3 * 17 + 16
        for name, value in flat_want.items():
            np.testing.assert_allclose(flat_got[name], value, atol=tol,
                                       err_msg=f"{part} {name}")
    for layer, name in enumerate(family._moe_ops(cfg)):
        np.testing.assert_array_equal(
            got["counters"]["tokens_per_expert"][layer],
            np.sum([c[layer] for c in counts], axis=0))
        np.testing.assert_array_equal(
            mets[f"{name}/tokens_per_expert"],
            got["counters"]["tokens_per_expert"][layer])
        assert float(mets[f"{name}/bias_abs_max"]) == 0.0


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
    assert {"layer_0_mixer", "layer_0_experts", "layer_3_mixer"} <= tags
    assert not any(op.recompute for op in runs[1][2].layers)
    assert float(runs[0][1]["loss"]) == float(runs[1][1]["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(runs[0][0].params),
                    jax.tree_util.tree_leaves(runs[1][0].params)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)


def test_the_program_event_counts_both_cores():
    from dlrm_flexflow_tpu.telemetry import event_log
    from dlrm_flexflow_tpu.telemetry.schema import validate_event
    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    with event_log() as log:
        model.train_epoch(state, inputs, labels)
    events = [e for e in log.events() if e["type"] == "program"]
    assert [(e["gdn_core"], e["attention_core"]) for e in events] \
        == [({"pallas": 0, "chunked": 3}, {"pallas": 0, "plain": 1})]
    assert validate_event(events[0]) == []
    counted = [e for e in log.events() if e["type"] == "op_counters"]
    assert len(counted) == 4
    assert len(counted[0]["counters"]["tokens_per_expert"]) == 16


def test_every_scope_of_the_compiled_step_is_attributed():
    """The optimized HLO of the tiny model's ``train_epoch``: every
    ``ff.lm.*`` scope of the issue's list is there, the DeltaNet core
    forward, recomputed and backward, and the family's groups hold
    every phase found."""
    from benchmarks.lib import phases

    cfg = _small()
    model, state = _compiled(cfg)
    inputs, labels = family._split(_tokens(cfg, 2))
    text = model._train_epoch.lower(state, inputs,
                                    labels).compile().as_text()
    found = set(profiling.hlo_phases(text).values())
    for scope in ("ff.lm.embed", "ff.lm.gdn.proj", "ff.lm.gdn.conv",
                  "ff.lm.gdn.core", "ff.lm.gdn.gate", "ff.lm.attn.proj",
                  "ff.lm.attn.core", "ff.lm.moe.route",
                  "ff.lm.moe.dispatch", "ff.lm.moe.experts",
                  "ff.lm.moe.combine", "ff.lm.moe.shared", "ff.lm.head",
                  "ff.step.dense_update"):
        assert scope in found or scope + ".bwd" in found, scope
    for scope in ("ff.lm.gdn.proj", "ff.lm.gdn.conv", "ff.lm.gdn.core",
                  "ff.lm.attn.proj"):
        assert scope + ".remat" in found, scope
        assert scope + ".bwd" in found, scope
    assert "ff.lm.attn.core.bwd" in found
    groups = {phases.group_of(p, family.PHASE_GROUPS)
              for p in found - {profiling.UNATTRIBUTED}}
    assert None not in groups
    assert {"gdn", "attn", "moe", "head", "dense_update"} <= groups


# ------------------------------------------------ the configuration's file
def test_the_configuration_file_holds_the_catalogs_numbers():
    config = json.load(open(CONFIG))
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert config["overrides"] == {}   # the rehearsal's alone
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (4, 32, 18992)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "vocab_size": 151936,
                                   "experts_held": 512}
    assert config["vocab_size"] * 8 == 151936
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_size"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "qwen3-next-ep16"]
    assert entry[0]["reduced"] == config["reduced"]
    assert entry[0]["source"] == config["source"]


def test_the_published_size_has_625_7_million_parameters():
    config = json.load(open(CONFIG))
    cfg = family.model_config(config, {"seq_len": 16384})
    assert (cfg.num_experts, cfg.experts_held, cfg.seq_len) \
        == (512, 32, 16384)
    assert [cfg.is_full_attention(i) for i in range(4)] \
        == [False, False, False, True]
    model = app.build(cfg, FFConfig(batch_size=1,
                                    compute_dtype="bfloat16"))
    shapes = jax.eval_shape(lambda: (model.compile(
        optimizer=app.optimizer(cfg), loss_type=app.token_loss, metrics=(),
        mesh=False) and None) or model.init(seed=0))
    by_op = {name: sum(int(np.prod(a.shape)) for a in p.values())
             for name, p in shapes.params.items()}
    assert by_op["layer_0_gdn"] == 33_718_464     # the issue's 33.72M
    assert by_op["layer_3_attn"] == 27_263_488    # 27.26M
    assert by_op["layer_0_moe"] == 1_048_576 + 3_145_728 + 2048 \
        + 32 * 3_145_728
    assert sum(by_op.values()) == 625_667_136     # 625.7M, to the parameter


def test_the_flop_count_against_a_hand_count():
    """``train_flops_per_sample`` at the published sizes: the issue's own
    arithmetic, 26 TFLOP a step, and the two cores' counts."""
    config, traffic = json.load(open(CONFIG)), json.load(open(TRAFFIC))
    s = 16384
    gdn = 2048 * (12288 + 64) + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    moe = 2048 * 512 + 3 * 2048 * 512 * (10 * 32 / 512 + 1) + 2048
    matmuls = 6 * s * (3 * gdn + attn + 4 * moe + 2048 * 18992)
    core = 3 * s * s * 16 * 512
    rule = 3 * 3 * s * 32 * 6 * 128 * 128
    got = family.train_flops_per_sample(config, traffic)
    assert got == pytest.approx(matmuls + core + rule, rel=1e-9)
    assert matmuls == pytest.approx(18.9e12, rel=0.01)
    assert core == pytest.approx(6.6e12, rel=0.01)
    assert got == pytest.approx(26e12, rel=0.01)
    assert family.attention_core_work(config, traffic)[0] \
        == pytest.approx(3.5 * s * s * 16 * 512)
    flops, nbytes = family.gdn_core_work(config, traffic)
    assert flops == 3 * s * 32 * 6 * 128 * 128
    assert nbytes == 3 * s * ((2 * 2048 + 4096) * 2 + 4096 * 4 + 64 * 4)
    assert (family.gdn_layers(config, traffic),
            family.attention_layers(config, traffic),
            family.moe_layers(config, traffic)) == (3, 1, 4)
    assert traffic["batches"] * traffic["dispatches"] \
        * traffic["epochs_per_dispatch"] == 8


# ------------------------- the family's comparison, with faults planted
@pytest.fixture(scope="module")
def tiny_cell():
    """The cell's real files at the rehearsal's size (as
    ``tests/benchmark`` lays them), and the staged driver."""
    cell = run.resolve(ROOT, "qwen3-next-ep16.pretrain-16k")
    tiny = json.load(open(os.path.join(
        ROOT, "tests/benchmark/tiny.gdn_moe_lm.json")))
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
    assert report["grad_err_q90"] < 1e-5 and report["update_err"] < 1e-4
    # a_log and dt_bias: four numbers a layer, each a sum over the
    # tokens of terms that cancel, which the chunked rule and the token
    # recurrence add in other orders (f32: 1e-2 of a norm of 1e-6)
    assert report["grad_err_max"] < 0.05
    assert report["grad_worst_tensor"].split(".")[-1] in ("a_log",
                                                          "dt_bias")


@pytest.mark.parametrize("fault,over", [
    ("state_unchanged", {"update_err", "grad_err_max"}),
    ("half_batch", {"grad_err_max", "counter_slack"}),
    ("expert_dropped", {"grad_err_max"}),
    ("carried_state_zeroed", {"grad_err_mixer_max"})])
def test_a_planted_fault_is_not_correct(tiny_cell, fault, over, monkeypatch):
    """The timed path broken underneath the comparison: the state
    returned unchanged; half of every batch left out; one held expert's
    rows zeroed behind the grouped matmul; the DeltaNet rule's carried
    state zeroed at one chunk's boundary (forward, so the backward's
    saved states too)."""
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
    if fault == "carried_state_zeroed":
        whole_scan = deltanet._scan_chunks

        def forgetting(operands, cd):
            qd, p, kd, carry, w_k, w_v = operands
            carry = carry.at[1].set(0.0)       # what chunk 1 hands on
            kd = kd.at[1].set(0)
            return whole_scan((qd, p, kd, carry, w_k, w_v), cd)

        monkeypatch.setattr(deltanet, "_scan_chunks", forgetting)
    ok, report = _check(tiny_cell, steps)
    assert not ok, report
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= over, report
    if fault == "expert_dropped":
        assert ".moe.w_" in report["grad_worst_tensor"]
        assert report["grad_worst_tensor"].endswith(".1")
        assert report["grad_err_max"] == pytest.approx(1.0, abs=1e-3)
    if fault == "carried_state_zeroed":
        assert ".gdn." in report["grad_worst_tensor"]


def test_the_control_one_precision_down_is_not_correct(tiny_cell):
    """The chip's arrangement at the rehearsal's size: the program in
    bfloat16 against the bfloat16 reference reads correct, and
    ``control_steps`` (the reference in the program's place with
    float8 operands) does not, by more than one limit.  (One precision
    under the rehearsal's own float32, bfloat16, the control reads
    ``grad_err_max`` 0.02 at these widths: the limits were read at the
    published ones.)  The control writes a whole state back, and the
    program trains on from it."""
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
    assert {name for name, limit in family.LIMITS.items()
            if report[name] > limit} >= {"grad_err_max", "grad_err_median",
                                         "grad_err_all", "update_err"}
    model, state = family.build(config, traffic, 3, None)
    inputs, labels = family._split(family._sequences(
        config, traffic, 2 * traffic["batch"], 3, 1).reshape(
            2, traffic["batch"], -1))
    state, _ = family.control_steps(config)(model, state, inputs, labels)
    assert int(state.step) == 2
    state, losses = driver.check_steps(model, state, inputs, labels)
    assert np.isfinite(float(losses[0])) and int(state.step) == 4
