"""The delta rule with a decay per key channel as two Pallas kernels
(``ops/pallas_kda.py``), here on the CPU under the Pallas interpreter:
the output and all five gradients against the token-by-token recurrence
and against ``kimi_delta_rule``'s chunked form, in f32 and bf16, with
decays from ``DECAY_FLOOR`` to -1e-6 and the state carried across
several blocks; a state dropped at a block's boundary caught; the form
chosen from the backend and the shapes; and, lowered for a TPU through
``FFModel``, the kernels lowered once a program whatever the number of
layers, and never run forward again in a layer's recomputation."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.models import kda_moe_lm as family  # noqa: E402
from benchmarks.reference import kda_moe_lm_ref as ref  # noqa: E402
from dlrm_flexflow_tpu.apps import kda_moe_lm as app  # noqa: E402
from dlrm_flexflow_tpu.config import FFConfig  # noqa: E402
from dlrm_flexflow_tpu.ops import deltanet, pallas_kda  # noqa: E402
from dlrm_flexflow_tpu.ops.deltanet import KimiDeltaAttention  # noqa: E402
from dlrm_flexflow_tpu.tensor import Tensor  # noqa: E402

F32 = jnp.dtype("float32")
NAMES = ("o", "q", "k", "v", "g", "beta")


@pytest.fixture
def kernels(monkeypatch):
    """The rule's choice and kernels as on a TPU, the kernels run by the
    Pallas interpreter.  The jit callee keeps what it traced: caches are
    cleared on the way in and out."""
    real = pallas_kda.pl.pallas_call
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        pallas_kda.pl, "pallas_call",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    jax.clear_caches()
    yield pallas_kda
    jax.clear_caches()


def _inputs(seq, decays, h=2, dk=128, dv=128):
    """q, k normalised as the mixer hands them over; ``decays``:
    ``spread`` over (-5, 0) by channel, ``ends``: half the entries at
    ``DECAY_FLOOR``, half at -1e-6 (a state that outlives the sequence)."""
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    q = deltanet.l2_normalised(jax.random.normal(
        keys[0], (1, seq, h, dk))) * dk ** -0.5
    k = deltanet.l2_normalised(jax.random.normal(keys[1], (1, seq, h, dk)))
    v = jax.random.normal(keys[2], (1, seq, h, dv))
    noise = jax.random.normal(keys[3], (1, seq, h, dk))
    g = {"spread": -5.0 * jax.nn.sigmoid(4.0 * noise),
         "ends": jnp.where(noise > 0, deltanet.DECAY_FLOOR, -1e-6)}[decays]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, seq, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (1, seq, h, dv))


def _with_grads(rule, args, w):
    """(o, dq, dk, dv, dg, dbeta) under the loss ``sum(o * w)``."""
    both = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * w), o))(rule(*a)),
        argnums=range(5), has_aux=True))
    (_, o), grads = both(*args)
    return (o, *grads)


def _chunked(*args, **kw):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(deltanet, "_on_tpu", lambda: False)
        return deltanet.kimi_delta_rule(*args, **kw)


def _token_rule(q, k, v, g, beta):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ref, "TOKEN_BLOCK", q.shape[1])
        return jax.vmap(ref.delta_rule)(
            *(x.astype(F32) for x in (q, k, v)), g, beta)


@pytest.mark.parametrize("decays", ["spread", "ends"])
def test_the_kernels_are_the_token_recurrence(kernels, decays):
    """f32 at the published head width over three blocks (the state
    carried across two block boundaries): the output and all five
    gradients against the recurrence and against the chunked form, at
    the chunked form's own distance from the recurrence; two
    ``pallas_call``s named for the rule."""
    seq = 3 * kernels.BLOCK
    args, w = _inputs(seq, decays)
    assert float(jnp.min(args[3])) == deltanet.DECAY_FLOOR \
        or decays == "spread"
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(deltanet.kimi_delta_rule(*a) * w),
        argnums=range(5)))(*args))
    assert jaxpr.count("name=kimi_delta_fwd\n") \
        == jaxpr.count("name=kimi_delta_bwd\n") == 1
    with jax.default_matmul_precision("highest"):
        got = _with_grads(deltanet.kimi_delta_rule, args, w)
        want = _with_grads(_token_rule, args, w)
        chunked = _with_grads(_chunked, args, w)
    for name, a, b, c in zip(NAMES, got, want, chunked):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(a)), name
        # 128-wide heads over 768 tokens sum to gradients of ~10: the
        # chunked form's own distance from the recurrence is 6e-5 there
        atol = 2e-6 if name == "o" else max(
            5e-5, 1e-5 * float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(a, b, atol=atol, err_msg=name)
        np.testing.assert_allclose(a, c, atol=atol, err_msg=name)


def test_the_kernels_in_bfloat16_are_near_the_recurrence(kernels):
    """bf16 operands, f32 decays, pair products, ``T``, state and
    accumulators: every number within bf16's rounding of the f32
    recurrence on the same rounded inputs, and the output no further from
    it than the chunked form's."""
    bf = jnp.bfloat16
    args, w = _inputs(2 * kernels.BLOCK, "spread")
    args = tuple(x.astype(bf).astype(F32) for x in args[:3]) + args[3:]
    rule = lambda f: lambda *a: f(*a, compute_dtype=bf)
    got = _with_grads(rule(deltanet.kimi_delta_rule), args, w)
    chunked = _with_grads(rule(_chunked), args, w)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(_token_rule, args, w)
    assert got[0].dtype == F32 and got[1].dtype == F32
    for name, a, b, c in zip(NAMES, got, want, chunked):
        scale = float(jnp.max(jnp.abs(b)))
        err, chunked_err = (float(jnp.max(jnp.abs(x - b))) for x in (a, c))
        assert err < 0.02 * scale, name
        assert err < 2 * chunked_err + 1e-6 * scale, name
    assert float(jnp.max(jnp.abs(got[0] - want[0]))) > 1e-5   # it rounded


def test_a_state_dropped_between_the_blocks_is_caught(kernels, monkeypatch):
    """What carries the state from a grid step to the next is the
    kernel's scratch: the kept (transposed) states equal the chunked
    form's, and a forward that loses the state at one block's boundary
    (the two halves run apart) reads gradients far over the tolerance."""
    seq = 2 * kernels.BLOCK
    args, w = _inputs(seq, "ends", h=1)
    with jax.default_matmul_precision("highest"):
        _, starts = jax.jit(kernels.forward)(*args)
        want_starts = jax.jit(lambda *a: deltanet._scan_chunks(
            deltanet._channel_chunk_operands(
                *deltanet._laid_out(*a, kernels.CHUNK), F32), F32)[1])(*args)
        assert starts.shape == (1, seq // kernels.CHUNK, 128, 128)
        # sums over 512 tokens of states that forget nothing: 2e-5 apart
        np.testing.assert_allclose(
            starts[0], jnp.swapaxes(want_starts[:, 0, 0], 1, 2), atol=2e-5)
        assert float(jnp.max(jnp.abs(starts[0, -1]))) > 0.1
        want = _with_grads(_token_rule, args, w)
        whole = kernels.forward

        def forgetting(*xs):
            halves = [whole(*(x[:, part] for x in xs))
                      for part in (slice(0, seq // 2), slice(seq // 2, seq))]
            return tuple(jnp.concatenate(pair, axis=1)
                         for pair in zip(*halves))

        monkeypatch.setattr(kernels, "forward", forgetting)
        jax.clear_caches()
        got = _with_grads(deltanet.kimi_delta_rule, args, w)
    errs = {name: float(jnp.max(jnp.abs(a - b)))
            for name, a, b in zip(NAMES, got, want)}
    assert errs["o"] > 1e-2 and max(errs.values()) > 1e-1, errs


@pytest.mark.parametrize("on_tpu,seq,dk,dv,dtype,form", [
    (True, 8192, 128, 128, "bfloat16", "pallas"),
    (True, 256, 128, 128, "float32", "pallas"),
    (True, 512, 256, 128, "bfloat16", "pallas"),
    (False, 8192, 128, 128, "bfloat16", "chunked"),    # the CPU
    (True, 29, 128, 128, "bfloat16", "chunked"),        # no whole block
    (True, 320, 128, 128, "bfloat16", "chunked"),       # whole chunks alone
    (True, 256, 24, 128, "bfloat16", "chunked"),        # no lane tile
    (True, 256, 128, 64, "float32", "chunked"),
    (True, 256, 128, 128, "float16", "chunked")])
def test_the_form_follows_the_backend_and_the_shapes(
        on_tpu, seq, dk, dv, dtype, form, monkeypatch):
    """``kimi_core_form`` and the op's ``core_form`` from the backend and
    the shapes alone, ``"chunked"`` wherever ``pallas_kda.takes``
    refuses; the differentiated rule's jaxpr holds the two kernels or
    none."""
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: on_tpu)
    jax.clear_caches()
    assert deltanet.kimi_core_form(seq, dk, dv, dtype) == form
    assert pallas_kda.takes(seq, dk, dv, dtype) == (form == "pallas"
                                                    or not on_tpu)
    if dtype == "float16":
        return      # the op's compute dtype is bfloat16 or float32
    op = KimiDeltaAttention("kda", Tensor((1, seq, 64), F32, name="x"), 2,
                            dk, dv, compute_dtype=dtype)
    assert op.core_form() == form
    tokens = min(seq, 512)
    assert deltanet.kimi_core_form(tokens, dk, dv, dtype) == form
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, F32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(deltanet.kimi_delta_rule(
            *a, compute_dtype=jnp.dtype(dtype))), argnums=range(5)))(
        shape(1, tokens, 1, dk), shape(1, tokens, 1, dk),
        shape(1, tokens, 1, dv), shape(1, tokens, 1, dk),
        shape(1, tokens, 1)))
    assert jaxpr.count("pallas_call") == (2 if form == "pallas" else 0)
    jax.clear_caches()


# ------------------------------------------- through FFModel, lowered once
def _stack(layers, seq=256):
    """The rehearsal's widths but for the kernels' own (128-wide heads,
    a whole block of tokens): ``layers`` KDA layers from published layer
    1 (none of them latent attention), each mixer a recomputed run."""
    cfg = app.KdaMoeLmConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=layers,
        layer_group_size=8, first_layer_held=1, intermediate_size=48,
        num_attention_heads=4, heads_held=2, head_dim=128, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
        num_experts=16, experts_held=4, num_experts_per_tok=4, n_group=4,
        topk_group=2, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=16, seq_len=seq)
    assert cfg.recompute
    model = app.build(cfg, FFConfig(batch_size=1))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    return cfg, model


def _functions_calling(text, kernel):
    """Names of the lowered module's functions whose body holds
    ``kernel``'s custom call, and the call sites of each (their
    locations' names)."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((\"[^\"]*\")", text, re.M))
    bodies = [b.split("\n  }")[0]
              for b in re.split(r"\n  func\.func ", text)[1:]]
    names = [re.match(r"(?:public |private )?@(\w+)", b).group(1)
             for b in bodies if kernel in b]
    return {name: [locs.get(m.group(1), m.group(1)) for m in re.finditer(
        r"call @%s\(.*loc\((#loc\d+)\)" % name, text)] for name in names}


@pytest.mark.parametrize("layers", [1, 3])
def test_each_kernel_is_lowered_once_a_program(layers, monkeypatch):
    """The training step lowered for a TPU here (no chip, no compile):
    one ``kimi_delta_fwd`` and one ``kimi_delta_bwd`` custom call in the
    module whether it holds one KDA layer or three (the layers share the
    rule's jit callee and one recompute policy), the forward's function
    called once a layer and never inside a recomputation (the run keeps
    the rule's output and boundary states, ``KDA_SAVED``), the backward's
    once a layer under the transpose."""
    monkeypatch.setattr(deltanet, "_on_tpu", lambda: True)
    jax.clear_caches()
    cfg, model = _stack(layers)
    assert model._program_fields["kda_core"] == {"pallas": layers,
                                                 "chunked": 0}
    state = jax.eval_shape(lambda: model.init(seed=0))
    ids = jax.ShapeDtypeStruct((1, cfg.seq_len), jnp.int32)
    labels = jax.ShapeDtypeStruct((1, cfg.seq_len, 1), jnp.int32)
    text = model._train_step.trace(state, {"ids": ids}, labels).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 2
    forward = _functions_calling(text, "kimi_delta_fwd")
    backward = _functions_calling(text, "kimi_delta_bwd")
    assert len(forward) == len(backward) == 1
    (fwd_sites,), (bwd_sites,) = forward.values(), backward.values()
    assert len(fwd_sites) == len(bwd_sites) == layers
    assert all("ff.lm.kda.core" in s and "rematted_computation" not in s
               and "transpose" not in s for s in fwd_sites), fwd_sites
    assert all("ff.lm.kda.core" in s and "transpose" in s
               for s in bwd_sites), bwd_sites
    jax.clear_caches()


def test_a_recomputed_model_with_the_kernels_is_the_chunked_ones(kernels):
    """One training step of a one-layer model, f32, the mixer recomputed:
    with the kernels (their output and states kept through the
    recomputation) the loss and every gradient (Adam's first moment after
    one step) are the chunked form's."""
    cfg, model = _stack(1)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 1, cfg.seq_len + 1)).astype(np.int32)
    inputs, labels = family._split(tokens)

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
    assert abs(got_loss - want_loss) < 1e-6 * abs(want_loss)
    moments = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-5 * scale + 1e-12,
                                   err_msg=jax.tree_util.keystr(path))
        moments += scale > 0
    assert moments > 10
