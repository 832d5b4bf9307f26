"""The held-experts layer keeps its routing through a recomputation
(``ops/moe.py::ROUTE_SAVED``): a recomputed run that holds the layer
computes no router product, top-k, gather or sort again, its gradients
and new state are those of the layer without a recomputation, and the
``program`` event counts the layers that keep it (``route_kept``)."""

import collections
import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the four router forms the cells run: Ling's groups, Qwen3-Next's
# softmax, JoyAI's one-group sigmoid, ZAYA1's MLP router with a state
FORMS = {
    "groups": dict(top_k=4, n_group=4, topk_group=2),
    "softmax": dict(top_k=4, score_func="softmax"),
    "sigmoid": dict(top_k=4),
    "mlp": dict(top_k=1, router="mlp", router_hidden=8),
}
# (top_k, sort, router dot_general) in the gradient's program: the top-k
# and the sort once, the router's products once forward and twice
# backward, none of them again in the recomputation
WANT = {"groups": (3, 1, 3), "softmax": (1, 1, 3), "sigmoid": (1, 1, 3),
        "mlp": (1, 1, 9)}
# what a recomputation of the routing would run again: the above, the
# score functions, the gathers of the selected scores and the compares
# of the selection with the experts
AGAIN = ("top_k", "sort", "dot_general", "logistic", "exp", "erf", "gather",
         "eq")
SHAPE = (2, 8, 16)


def _layer(form, recompute):
    """One ``HeldExpertsMoE`` (16 experts, 8 of them held, no shared
    expert, so every ``dot_general`` is the router's) in a run tagged
    ``recompute`` (or none), with its parameters scaled up so that the
    selection is decided far from a tie: ``(grad, params, inputs)``,
    ``grad`` giving the gradients of a loss over the layer's output
    with respect to the parameters and the inputs, and the layer's new
    state."""
    kw = dict(FORMS[form])
    top_k = kw.pop("top_k")
    m = ff.FFModel(ff.FFConfig(batch_size=SHAPE[0]))
    x = m.create_tensor(SHAPE, name="x")
    names = ["x"]
    with m.scope(recompute=recompute):
        if form == "mlp":
            names.append("r_prev")
            r_prev = m.create_tensor(SHAPE[:2] + (8,), name="r_prev")
            out = m.held_experts_moe(x, 16, 8, top_k, (4, 8), name="moe",
                                     router_state=r_prev, **kw)[0]
        else:
            out = m.held_experts_moe(x, 16, 8, top_k, (4, 8), name="moe",
                                     **kw)
    m.compile(loss_type="mean_squared_error", metrics=())
    state = m.init(seed=0)
    params = jax.tree_util.tree_map(lambda p: 4.0 * p, state.params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(names))
    inputs = {n: jax.random.normal(k, SHAPE[:2] + ((16,) if n == "x"
                                                   else (8,)))
              for n, k in zip(names, keys)}

    def loss(params, inputs):
        values, new_state = m._apply(params, inputs, training=True,
                                     rng=None, bn_state=state.bn_state)
        return jnp.sum(jnp.sin(values[out.uid])), new_state

    return jax.grad(loss, argnums=(0, 1), has_aux=True), params, inputs


def _subs(eqn):
    for value in eqn.params.values():
        for sub in (value if isinstance(value, (list, tuple)) else [value]):
            if isinstance(sub, jex.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jex.Jaxpr):
                yield sub


def _count(jaxpr, counts=None):
    """The primitives of ``jaxpr`` and the jaxprs inside it that make an
    array, by name (a scalar's, such as the MLP router's depth gate, is
    free to compute again)."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += any(
            getattr(v.aval, "shape", ()) for v in eqn.outvars)
        for sub in _subs(eqn):
            _count(sub, counts)
    return counts


@pytest.mark.parametrize("form", list(FORMS))
def test_a_recomputed_expert_layer_routes_once(form):
    """The gradient's program of a recomputed layer runs the router's
    products, its top-k, the dispatch's sort, the score function, the
    gathers and the compares of the selection with the experts no more
    often than the layer without a recomputation does: the
    recomputation runs none of them again.  The top-k and the sort run
    once, the router's products once forward and twice backward (the
    gradients of the weights and of the input)."""
    counts = {}
    for recompute in ("layer", None):
        grad, params, inputs = _layer(form, recompute)
        got = _count(jax.make_jaxpr(grad)(params, inputs).jaxpr)
        counts[recompute] = {name: got[name] for name in AGAIN}
    assert counts["layer"] == counts[None]
    assert tuple(counts["layer"][name] for name in AGAIN[:3]) == WANT[form]


@pytest.mark.parametrize("form", list(FORMS))
def test_a_recomputed_expert_layer_is_the_layer_without_one(form):
    """Keeping the routing moves no number: the gradients of every
    parameter and input and the layer's new state (the bias rule and
    the counters) are those of the layer without a recomputation."""
    got, want = [], []
    for recompute, into in (("layer", got), (None, want)):
        grad, params, inputs = _layer(form, recompute)
        into.extend(jax.jit(grad)(params, inputs))
    (got_grads, got_state), (want_grads, want_state) = got, want
    leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(leaves) >= 5
    for (path, b), a in zip(leaves, jax.tree_util.tree_leaves(got_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    assert set(got_state["moe"]) == set(want_state["moe"])
    for name, b in want_state["moe"].items():
        np.testing.assert_array_equal(got_state["moe"][name], b, name)
    assert int(want_state["moe"]["held_assignments"]) > 0


@pytest.mark.parametrize("func", ["softmax", "sigmoid"])
def test_the_scores_differentiate_as_jax_does(func):
    """``moe._scores``, which a recomputed run keeps, has the value and
    the gradient of JAX's own ``softmax`` / ``sigmoid``."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (16, 12))
    g = jax.random.normal(jax.random.PRNGKey(3), (16, 12))
    plain = {"softmax": jax.nn.softmax, "sigmoid": jax.nn.sigmoid}[func]
    got, pull = jax.vjp(lambda z: moe._scores(func, z), logits)
    want, pull_want = jax.vjp(plain, logits)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(pull(g)[0], pull_want(g)[0], rtol=1e-6,
                               atol=1e-7)


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    return {w["name"]: (files[w["config"]], w["traffic"])
            for w in bench["workloads"]
            if not w["config"].startswith("dlrm")}


# the expert layers of each language cell (the JoyAI cell's multi-token
# module holds one of its five)
ROUTE_KEPT = {"qwen3-next-ep16.pretrain-16k": 4,
              "ling3-flash-ep64.pretrain-8k": 6,
              "joyai-flash-ep16.pretrain-8k": 5,
              "zaya1-8b-ep2.pretrain-16k": 4}


@pytest.mark.parametrize("recompute", [True, False])
@pytest.mark.parametrize("cell", sorted(ROUTE_KEPT))
def test_each_cell_keeps_its_expert_layers_routing(cell, recompute):
    """The cell's model as its family builds it: ``route_kept`` in its
    ``program`` events is its expert-layer count, and 0 built without
    recomputation."""
    from dlrm_flexflow_tpu.config import FFConfig
    from dlrm_flexflow_tpu.ops.moe import HeldExpertsMoE
    from dlrm_flexflow_tpu.telemetry.schema import validate_event
    path, traffic_name = _cells()[cell]
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic",
                           traffic_name + ".json")) as f:
        traffic = json.load(f)
    family = importlib.import_module("benchmarks.models." + config["family"])
    app = importlib.import_module("dlrm_flexflow_tpu.apps."
                                  + config["family"])
    cfg = dataclasses.replace(family.model_config(config, traffic),
                              recompute=recompute)
    model = app.build(cfg, FFConfig(batch_size=traffic["batch"]))
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    layers = sum(isinstance(op, HeldExpertsMoE) for op in model.layers)
    assert layers == ROUTE_KEPT[cell] == family.moe_layers(config, traffic)
    fields = model._program_fields
    assert fields["route_kept"] == (layers if recompute else 0)
    assert validate_event({"type": "program", "ts": 0.0, "name": "p#0",
                           **fields}) == []
