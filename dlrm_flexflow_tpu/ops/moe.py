"""Mixture-of-Experts operator (expert parallelism).

No reference analogue (SURVEY §2.3: "no expert routing" — EP is absent in
the reference); included because expert sharding is a first-class axis of
this framework's SOAP space.

Design: E expert MLPs with stacked weights (E, d, h), (E, h, d) and a
learned router.  Computation is the dense-dispatch formulation — every
expert processes the full token batch, masked/combined by the top-k gate
weights — expressed as batched einsums over the expert axis.  Sharding the
expert axis of the weights over the mesh's "model"/"expert" axis gives
expert parallelism: XLA partitions the einsum over experts and inserts the
gather/reduce collectives (at large scale a capacity-based all-to-all
dispatch is cheaper; that variant can reuse this op's parameters).

``HeldExpertsMoE`` below is the routed form: a layer that is told which
of the experts it holds, routes every token over all of them, and
computes the part of the result its own experts give, through a sort
and grouped matmuls over the rows it was sent instead of every expert
on every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..initializers import DEFAULT_KERNEL_INIT, ZeroInitializer
from ..tensor import ParameterSpec
from .base import Op, matmul
from .transformer import swiglu


class MixtureOfExperts(Op):
    """(B, d) -> (B, d) with E gated expert MLPs (d -> hidden -> d)."""

    op_type = "MixtureOfExperts"

    def __init__(self, name, input_tensor, num_experts: int, hidden_dim: int,
                 top_k: int = 2, activation: str = "relu",
                 kernel_initializer=None):
        super().__init__(name, [input_tensor])
        assert 1 <= top_k <= num_experts
        self.num_experts = int(num_experts)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.activation = activation
        self.model_dim = input_tensor.shape[-1]
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        e, d, h = self.num_experts, self.model_dim, self.hidden_dim
        return [
            ParameterSpec(self.name, "router", (d, e),
                          initializer=self.kernel_initializer),
            ParameterSpec(self.name, "w_in", (e, d, h),
                          initializer=self.kernel_initializer, sharded_dim=0),
            ParameterSpec(self.name, "b_in", (e, h),
                          initializer=ZeroInitializer(), sharded_dim=0),
            ParameterSpec(self.name, "w_out", (e, h, d),
                          initializer=self.kernel_initializer, sharded_dim=0),
            ParameterSpec(self.name, "b_out", (e, d),
                          initializer=ZeroInitializer(), sharded_dim=0),
        ]

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs  # (..., d)
        from .base import activation_fn

        logits = x @ params["router"]  # (..., E)
        gates = jax.nn.softmax(logits, axis=-1)
        if self.top_k < self.num_experts:
            top_vals, _ = jax.lax.top_k(gates, self.top_k)
            thresh = top_vals[..., -1:]
            masked = jnp.where(gates >= thresh, gates, 0.0)
            gates = masked / jnp.sum(masked, axis=-1, keepdims=True)
        # dense dispatch: every expert runs the batch; experts sharded ->
        # XLA partitions the einsum over e
        h = jnp.einsum("...d,edh->e...h", x, params["w_in"],
                       preferred_element_type=jnp.float32)
        h = h + params["b_in"][(slice(None),) + (None,) * (x.ndim - 1)]
        h = activation_fn(self.activation)(h)
        y = jnp.einsum("e...h,ehd->e...d", h, params["w_out"],
                       preferred_element_type=jnp.float32)
        y = y + params["b_out"][(slice(None),) + (None,) * (x.ndim - 1)]
        out = jnp.einsum("e...d,...e->...d", y, gates)
        return [out.astype(self.outputs[0].dtype)]

    def output_pspec(self, pc, mesh):
        """The expert axis lives in the WEIGHTS, not the output: a non-batch
        partition in this op's config means expert parallelism, and the
        combined output stays data-sharded/replicated."""
        from ..parallel.mesh import DATA_AXIS
        from jax.sharding import PartitionSpec
        ndim = self.outputs[0].ndim
        axes = [None] * ndim
        if pc.dims and pc.dims[0] > 1 and DATA_AXIS in mesh.axis_names:
            axes[0] = DATA_AXIS
        return PartitionSpec(*axes)

    def flops(self, batch):
        e, d, h = self.num_experts, self.model_dim, self.hidden_dim
        return 2 * batch * e * (d * h + h * d) + 2 * batch * d * e


# ------------------------------------------------- the held-experts layer
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# what the layer's state counts beside ``tokens_per_expert``
COUNTERS = ("held_assignments", "padded_rows", "buffer_rows",
            "overflow_steps")
# and, in a layer with a skip slot, the tokens routed to it
SKIPPED = "skipped_tokens"
# the grouped matmul's row tile on the chip; a slab is whole tiles
ROW_TILE = 512
# the grouped matmul's (k, n) tile on the chip holds at most this many
# elements: at 512 rows a 1024 x 1024 tile overflows the v5e's 16 MiB of
# scoped VMEM in the weights' gradient (tgmm) by 124 KB (experts 2048
# wide both ways); 1024 x 768 fits
TILE_ELEMENTS = 1024 * 768
# A slab holds this many even shares of the held experts' assignments
# (``slab_rows``).  The language-model cell (16 of 256 experts held, an
# even share 4,096 rows) sends a layer 2.9-6.8 k rows a step and none
# of 1,200 layer-steps overflowed two shares, while every row-shaped pass
# costs in proportion to the slab: the routed part of one layer takes
# 12.2 ms forward + backward at 2 shares, 15.4 at 4, and a further slab
# in the rare step that needs one 6.7 (v5e; PERF.md section 6, PR 32).
SHARES = 2
# what a recomputed run keeps of the layer's routing and dispatch
# (``HeldExpertsMoE.saved_in_recompute``): the router's scores, the
# selection and the selected scores, the sorted assignments and the
# groups' sizes; the MLP router's also its first product and the gelu's
# input.  Kept, the recomputation holds no router matmul, top-k, gather
# of the selected scores (1.7 ms a layer at 16,384 x 10 of 512 on the
# v5e) or sort, for 4 T (E + 3 k) bytes a layer (and 8 T router_hidden
# more for the MLP router's): <= 35 MB in every cell
SCORES, SELECTION, PICKED = ("moe_route_scores", "moe_route_idx",
                             "moe_route_picked")
ORDER, GROUP_SIZES = "moe_dispatch_order", "moe_dispatch_group_sizes"
ROUTER_Z, ROUTER_H = "moe_router_z", "moe_router_h"
ROUTE_SAVED = (SCORES, SELECTION, PICKED, ORDER, GROUP_SIZES, ROUTER_Z,
               ROUTER_H)


def slab_rows(assignments: int, num_held: int, num_experts: int) -> int:
    """Rows of one slab of a layer that holds ``num_held`` of
    ``num_experts`` experts and routes ``assignments = T * top_k``:
    ``SHARES`` even shares, in whole row tiles of the grouped matmul
    (under one tile: whole bf16 sublane tiles of 16 rows, and the slab
    is the tile), and never more than all of them (a layer that holds
    every expert)."""
    rows = SHARES * -(-assignments * num_held // num_experts)
    tile = ROW_TILE if rows > ROW_TILE else 16
    return min(assignments, -(-rows // tile) * tile)


def grouped_matmul(rows, weights, group_sizes):
    """``rows[a] @ weights[group of a]`` for rows sorted by group,
    (A, k) x (G, k, n) -> (A, n) f32: megablox's Pallas kernel on a TPU
    (1.95 ms against ``ragged_dot``'s 3.96 at 16 groups, 4,096 live of
    65,536 rows, 2048 -> 768 on the v5e: ``scripts/ab_lm_kernels.py``),
    ``jax.lax.ragged_dot`` elsewhere.  Rows past ``sum(group_sizes)``
    belong to no group; what comes back for them is unspecified (zeros
    from ``ragged_dot``, whatever the buffer held from megablox, which
    never visits their tiles), and the caller masks them."""
    if _on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        tk, tn = min(1024, rows.shape[1]), min(1024, weights.shape[2])
        while tk * tn > TILE_ELEMENTS:
            tn //= 2
        tiling = (min(ROW_TILE, rows.shape[0]), tk, tn)
        return megablox.gmm(rows, weights, group_sizes, jnp.float32, tiling)
    return jax.lax.ragged_dot(rows, weights, group_sizes,
                              preferred_element_type=jnp.float32)


def _slab_experts(rows, w_gate, w_up, w_down, live, sizes):
    """The held experts' SwiGLU over one slab's rows; a row that is not
    ``live`` comes back zero whatever the grouped matmul left there."""
    gate = grouped_matmul(rows, w_gate, sizes)
    up = grouped_matmul(rows, w_up, sizes)
    act = jnp.where(live, jax.nn.silu(gate) * up, 0.0)
    y = grouped_matmul(act.astype(rows.dtype), w_down, sizes)
    return jnp.where(live, y, 0.0)


def _each_slab(slabs, body, carry):
    """``carry = body(s, carry)`` for ``s`` in ``range(slabs)``,
    ``slabs`` a traced count."""
    return jax.lax.while_loop(
        lambda sc: sc[0] < slabs,
        lambda sc: (sc[0] + 1, body(sc[0], sc[1])), (0, carry))[1]


def _slabs_of(static, x, gates, order, group_sizes):
    """``(slabs, fetch)`` for both passes of ``_held_experts``: how many
    slabs hold an assignment to a held expert (at least one), and
    ``fetch(s) -> (ids, tokens, live, sizes, rows, weights)`` for slab
    ``s``, sorted positions ``[s * slab, (s + 1) * slab)``: the
    assignments there and their tokens, which positions hold an
    assignment to a held expert ((slab, 1) bool), how many rows of each
    group fall inside, the tokens' rows of ``x`` and the assignments'
    gates, both zero where not ``live``."""
    slab, k, scope = static
    order = jnp.pad(order, (0, -order.shape[0] % slab))   # whole slabs
    ends = jnp.cumsum(group_sizes)

    def fetch(s):
        with jax.named_scope(scope + ".dispatch"):
            lo = s * slab
            ids = jax.lax.dynamic_slice(order, (lo,), (slab,))
            tokens = ids // k
            live = (lo + jnp.arange(slab) < ends[-1])[:, None]
            sizes = (jnp.clip(ends, lo, lo + slab)
                     - jnp.clip(ends - group_sizes, lo, lo + slab))
            rows = jnp.where(live, jnp.take(x, tokens, axis=0), 0)
        with jax.named_scope(scope + ".combine"):
            weights = jnp.where(live[:, 0], jnp.take(gates, ids), 0.0)
        return ids, tokens, live, sizes, rows, weights

    return jnp.maximum(1, -(-ends[-1] // slab)), fetch


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(static, x, w_gate, w_up, w_down, gates, order,
                  group_sizes):
    """``(out (T, d) f32, slabs)``: for every assignment to a held
    expert, ``gate * expert(x[token])`` added into its token's row, a
    slab of ``static = (slab, top_k, scope)`` sorted positions at a
    time; ``slabs = max(1, ceil(held assignments / slab))`` is how many
    ran.  ``order`` (A,) holds the assignments sorted by held expert,
    the others behind them; ``gates`` (A,) is in assignment order; the
    experts' weights are rounded to ``x``'s dtype.

    The loop's count is a traced number, so JAX cannot differentiate
    it and does not have to: the backward pass is written below, loops
    over the same slabs, and computes each slab's forward again from
    ``x`` and the (A,) vectors, which are all that is kept.  No array
    of A rows by a model or hidden width exists in either pass.  A
    token's rows are added in the order the slabs hold them: the one
    place where the order of the sums is not the dense layer's."""
    scope = static[2]
    slabs, fetch = _slabs_of(static, x, gates, order, group_sizes)
    with jax.named_scope(scope + ".experts"):
        experts = [w.astype(x.dtype) for w in (w_gate, w_up, w_down)]

    def add(s, out):
        _ids, tokens, live, sizes, rows, weights = fetch(s)
        with jax.named_scope(scope + ".experts"):
            y = _slab_experts(rows, *experts, live, sizes)
        with jax.named_scope(scope + ".combine"):
            return out.at[tokens].add(y * weights[:, None])

    return _each_slab(slabs, add, jnp.zeros(x.shape, jnp.float32)), slabs


def _held_experts_fwd(static, *args):
    return _held_experts(static, *args), args


def _held_experts_bwd(static, res, cts):
    x, w_gate, w_up, w_down, gates, order, group_sizes = res
    scope, g = static[2], cts[0]
    slabs, fetch = _slabs_of(static, x, gates, order, group_sizes)
    with jax.named_scope(scope + ".experts"):
        experts = [w.astype(x.dtype) for w in (w_gate, w_up, w_down)]
        dws = [jnp.zeros(w.shape, jnp.float32) for w in experts]

    def add(s, acc):
        dx, dgates, dws = acc
        ids, tokens, live, sizes, rows, weights = fetch(s)
        with jax.named_scope(scope + ".experts"):
            y, pull = jax.vjp(
                lambda r, *w: _slab_experts(r, *w, live, sizes), rows,
                *experts)
        with jax.named_scope(scope + ".combine"):
            g_rows = jnp.take(g, tokens, axis=0)
            dgates = dgates.at[ids].add(
                jnp.where(live[:, 0], jnp.sum(g_rows * y, axis=-1), 0.0))
            dy = g_rows * weights[:, None]
        with jax.named_scope(scope + ".experts"):
            drows, *dw = pull(dy)
            dws = [a + b for a, b in zip(dws, dw)]   # summed in f32
        with jax.named_scope(scope + ".dispatch"):
            # megablox never visits the padding's tiles, so their
            # cotangent is whatever was there
            dx = dx.at[tokens].add(
                jnp.where(live, drows, 0).astype(jnp.float32))
        return dx, dgates, dws

    dx, dgates, dws = _each_slab(slabs, add, (
        jnp.zeros(x.shape, jnp.float32), jnp.zeros(gates.shape, jnp.float32),
        dws))
    return (dx.astype(x.dtype), *(d.astype(w.dtype) for d, w in
                                  zip(dws, (w_gate, w_up, w_down))),
            dgates, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


_SCORE_FUNCS = {"softmax": jax.nn.softmax, "sigmoid": jax.nn.sigmoid}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scores(func, logits):
    """``softmax`` or ``sigmoid`` of ``logits`` (..., E) over the
    experts, differentiated from its output alone, which the forward
    names ``SCORES``: a recomputed run that keeps it never computes the
    logits again (JAX's own rules read an output of their own, which a
    name cannot reach).  The same numbers as JAX's rules."""
    return _SCORE_FUNCS[func](logits)


def _scores_fwd(func, logits):
    s = checkpoint_name(_SCORE_FUNCS[func](logits), SCORES)
    return s, s


def _scores_bwd(func, s, g):
    if func == "softmax":
        gs = g * s
        return (gs - s * jnp.sum(gs, axis=-1, keepdims=True),)
    return (g * (s * (1.0 - s)),)


_scores.defvjp(_scores_fwd, _scores_bwd)


class HeldExpertsMoE(Op):
    """A routed-expert layer that holds ``held = (first, count)`` of its
    ``num_experts`` experts (all of them by default), plus
    ``num_shared`` shared experts every token passes through.  Experts
    are SwiGLU MLPs ``d -> hidden -> d`` without biases.

    Routing is over ALL ``num_experts`` (DeepSeek-V3, arXiv:2412.19437
    section 2.1.2 and the ``noaux_tc`` method of its released code):
    ``s = sigmoid(x W_r)`` in f32; the ``top_k`` largest of ``s + b``
    are selected (``b`` selects and never weighs); gates are the
    selected ``s`` normalised to sum 1, times ``scaling``.  With
    ``n_group`` > 1 the selection is group-limited (the ``n_group`` /
    ``topk_group`` of the released code): the experts lie in ``n_group``
    consecutive groups, a group's score is the sum of its two largest
    ``s + b``, only the ``topk_group`` best groups stay, and the
    ``top_k`` are the largest ``s + b`` inside them (an expert outside
    them counts as -inf; the released code fills 0.0, which differs only
    where fewer than ``top_k`` of the ``s + b`` that stay are positive).
    The output is
    ``sum over selected AND held experts of gate * expert(x)``, plus the
    shared experts; what the absent experts would add is left out (one
    chip's share of an expert-parallel layer, without its exchange).
    ``score_func="softmax"``: ``s = softmax(x W_r)`` over all experts
    (Qwen3-Next's router; with ``bias_update_speed`` 0 the bias stays 0
    and there is no bias rule).  ``shared_gated``: the shared experts'
    output times ``sigmoid(x w_s)``, ``w_s`` (d, 1) a parameter.

    ``router="mlp"`` (ZAYA1's router; ``docs/CCA_MOE_LM.md``): ``z = x
    W_d`` (d -> ``router_hidden``); exponential depth averaging ``r =
    (1 - g) z + g r_prev``, ``g = sigmoid(c)`` with ``c`` a learned
    scalar drawn at 0 and ``r_prev`` the previous expert layer's ``r``
    (the op's second input; zeros without one); ``s = softmax(gelu(r
    W_a) W_e)`` over the experts and one slot more, the skip slot, that
    no expert holds (ZAYA1's mixture of depths); the ``top_k`` largest
    ``s`` are selected and
    their gates are the selected ``s`` themselves, unnormalised and
    without a bias.  A token whose selection is the skip slot gets
    nothing from the layer.  The op's second output is ``r``, in f32,
    for the next expert layer.

    No assignment to a held expert is dropped, and none is computed
    twice: a stable sort puts the assignments to held experts first,
    grouped by expert, and the layer works through them a slab of
    ``slab_rows(T * top_k, held, experts)`` sorted positions at a time
    (``_held_experts``): gather the slab's tokens, three grouped
    matmuls, scatter-add each row times its gate into its token's
    output.  One slab in the usual step, ``ceil(held assignments /
    slab)`` in a step that overflows it; a layer that holds every
    expert has one slab of all ``T * top_k`` rows.  Only (A,) vectors
    are as long as the assignments.  The combine is a scatter-add
    because a slab is short: over a slab's 8,192 rows it takes 1.6 ms
    forward + backward on the v5e, where gathering all 65,536 rows back
    to assignment order and summing each token's eight takes 7.0
    (``scripts/ab_lm_kernels.py rows``).

    ``b`` is state, not a parameter (the ``bn_state`` route): it takes
    no gradient, and each training step moves it by ``bias_update_speed
    * sign(mean(c) - c)``, ``c`` the step's tokens per expert.  The state
    also counts, since ``init``: ``tokens_per_expert`` (all experts);
    ``held_assignments``; ``padded_rows``, the step's assignments that
    go to no held expert (``T * top_k - held_assignments``, whatever
    the buffer: the two add up to every assignment, which the
    benchmark's comparison holds them to); ``buffer_rows``, the rows
    the grouped matmuls were given (slabs x the slab's rows, so
    ``held_assignments / buffer_rows`` is the fill); ``overflow_steps``,
    the steps that took more than one slab.  A state that lacks a
    counter (the benchmark's control writes the first three) is
    carried as it is.  The MLP router's layer also counts
    ``skipped_tokens``; ``tokens_per_expert`` counts the experts alone.

    In a recomputed run (``FFModel.scope(recompute=...)``) the layer
    keeps its routing (``saved_in_recompute = ROUTE_SAVED``): the
    scores, the selection and the selected scores, the sorted
    assignments and the groups' sizes, and the MLP router's first
    product and gelu input, so the backward pass uses the forward's own
    selection and computes no score, top-k, gather or sort again.
    Elsewhere the names are the identity.

    Scopes (the prefix is the op's ``phase``, ``FFModel.scope``'s word,
    ``ff.moe`` without one): ``.route`` (scores, top-k, counts),
    ``.dispatch`` (sort, gather; backward: the scatter-add into the
    tokens' gradient), ``.experts`` (the grouped matmuls and their
    activation: forward, again inside the backward, backward),
    ``.combine`` (gate, scatter-add; backward: gather), ``.shared``.
    """

    op_type = "HeldExpertsMoE"
    has_state = True
    saved_in_recompute = ROUTE_SAVED

    def __init__(self, name, input_tensor, num_experts: int, hidden_dim: int,
                 top_k: int, held=None, num_shared: int = 0,
                 scaling: float = 1.0, bias_update_speed: float = 0.0,
                 kernel_initializer=None, compute_dtype=None,
                 score_func: str = "sigmoid", shared_gated: bool = False,
                 n_group: int = 1, topk_group: int = 1,
                 router: str = "linear", router_hidden: int = 0,
                 router_state=None):
        super().__init__(name, [input_tensor] + (
            [router_state] if router_state is not None else []))
        assert score_func in ("sigmoid", "softmax"), score_func
        assert router in ("linear", "mlp"), router
        assert router == "mlp" or router_state is None, \
            "a router state belongs to the MLP router"
        self.router, self.router_hidden = router, int(router_hidden)
        self.score_func = score_func
        self.shared_gated = bool(shared_gated)
        self.num_experts = int(num_experts)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        assert self.num_experts % self.n_group == 0 \
            and 1 <= self.topk_group <= self.n_group
        # the selection has to fit inside the groups that stay, and a
        # group's score is the sum of its two largest
        assert self.n_group == 1 or (
            self.num_experts // self.n_group >= 2 and int(top_k)
            <= self.topk_group * (self.num_experts // self.n_group))
        first, count = held if held is not None else (0, self.num_experts)
        self.first_held, self.num_held = int(first), int(count)
        assert 0 <= self.first_held \
            and self.first_held + self.num_held <= self.num_experts
        assert 1 <= top_k <= self.num_experts
        self.slots = self.num_experts + int(self.router == "mlp")
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.num_shared = int(num_shared)
        self.scaling = float(scaling)
        self.bias_update_speed = float(bias_update_speed)
        self.model_dim = input_tensor.shape[-1]
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]
        if self.router == "mlp":
            self.outputs.append(self._make_output(
                input_tensor.shape[:-1] + (self.router_hidden,),
                jnp.float32, idx=1))

    def _router_specs(self):
        d, rh, init = self.model_dim, self.router_hidden, \
            self.kernel_initializer
        if self.router == "linear":
            return [ParameterSpec(self.name, "router", (d, self.num_experts),
                                  initializer=init)]
        return [
            ParameterSpec(self.name, "router_down", (d, rh),
                          initializer=init),
            ParameterSpec(self.name, "router_depth", (),
                          initializer=ZeroInitializer()),
            ParameterSpec(self.name, "router_hidden", (rh, rh),
                          initializer=init),
            ParameterSpec(self.name, "router_out", (rh, self.slots),
                          initializer=init)]

    def param_specs(self):
        d, h, e = self.model_dim, self.hidden_dim, self.num_held
        init = self.kernel_initializer
        specs = self._router_specs() + [
            ParameterSpec(self.name, "w_gate", (e, d, h), initializer=init,
                          sharded_dim=0),
            ParameterSpec(self.name, "w_up", (e, d, h), initializer=init,
                          sharded_dim=0),
            ParameterSpec(self.name, "w_down", (e, h, d), initializer=init,
                          sharded_dim=0)]
        if self.num_shared:
            hs = h * self.num_shared
            specs += [
                ParameterSpec(self.name, "shared_gate", (d, hs),
                              initializer=init, sharded_dim=1),
                ParameterSpec(self.name, "shared_up", (d, hs),
                              initializer=init, sharded_dim=1),
                ParameterSpec(self.name, "shared_down", (hs, d),
                              initializer=init, sharded_dim=0)]
            if self.shared_gated:
                specs.append(ParameterSpec(self.name, "shared_sigmoid",
                                           (d, 1), initializer=init))
        return specs

    def init_state(self):
        return {"bias": jnp.zeros((self.num_experts,), jnp.float32),
                "tokens_per_expert": jnp.zeros((self.num_experts,),
                                               jnp.int32),
                **{name: jnp.zeros((), jnp.int32) for name in COUNTERS
                   + ((SKIPPED,) if self.router == "mlp" else ())}}

    def step_metrics(self, old, new):
        """This step's counters for the step's metrics (``train_epoch``
        folds them: sums, and the largest for a name ending ``_max``)."""
        out = {k: new[k] - old[k]
               for k in ("tokens_per_expert", SKIPPED) + COUNTERS if k in old}
        out["bias_abs_max"] = jnp.max(jnp.abs(new["bias"]))
        return out

    def route(self, x, router, bias):
        """``(idx (T, k) int32, gates (T, k) f32, counts (E,) int32)``
        for tokens ``x`` (T, d): scores and selection in f32 at full
        matmul precision, whatever the compute dtype (a rounded score
        would move the selection)."""
        scores = _scores(self.score_func, jnp.matmul(
            x.astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST))
        choice = scores + jax.lax.stop_gradient(bias)
        if self.n_group > 1:
            # group-limited: the experts lie in n_group consecutive
            # groups, a group scores the sum of its two largest, the
            # topk_group best groups stay and the others cannot be chosen
            grouped = choice.reshape(choice.shape[:-1] + (self.n_group, -1))
            group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(group_score, self.topk_group)
            stays = jnp.any(kept[..., None] == jnp.arange(self.n_group),
                            axis=-2)
            choice = jnp.where(stays[..., None], grouped,
                               -jnp.inf).reshape(choice.shape)
        idx = checkpoint_name(jax.lax.top_k(choice, self.top_k)[1],
                              SELECTION)
        picked = checkpoint_name(jnp.take_along_axis(scores, idx, axis=-1),
                                 PICKED)
        gates = self.scaling * picked / jnp.sum(picked, axis=-1,
                                                keepdims=True)
        counts = jnp.sum(idx[..., None] == jnp.arange(self.num_experts),
                         axis=(0, 1), dtype=jnp.int32)
        return idx, gates, counts

    def route_mlp(self, x, params, r_prev):
        """``(idx, gates, counts, r (T, router_hidden) f32)`` for tokens
        ``x`` (T, d) and the previous layer's ``r_prev`` (or None): the
        MLP router, in f32 at full matmul precision as ``route``'s
        scores; ``idx`` may be the skip slot, ``num_experts``, which
        ``counts`` leaves out."""
        mm = functools.partial(jnp.matmul,
                               precision=jax.lax.Precision.HIGHEST)
        z = checkpoint_name(
            mm(x.astype(jnp.float32), params["router_down"]), ROUTER_Z)
        depth = jax.nn.sigmoid(params["router_depth"])
        r = (1.0 - depth) * z
        if r_prev is not None:
            r = r + depth * r_prev
        h = checkpoint_name(mm(r, params["router_hidden"]), ROUTER_H)
        scores = _scores("softmax", mm(jax.nn.gelu(h, approximate=False),
                                       params["router_out"]))
        idx = checkpoint_name(jax.lax.top_k(scores, self.top_k)[1],
                              SELECTION)
        gates = checkpoint_name(jnp.take_along_axis(scores, idx, axis=-1),
                                PICKED)
        counts = jnp.sum(idx[..., None] == jnp.arange(self.num_experts),
                         axis=(0, 1), dtype=jnp.int32)
        return idx, gates, counts, r

    def forward(self, params, xs, *, training=False, rng=None, state=None):
        x_in, r_prev = xs[0], (xs[1] if len(xs) > 1 else None)
        d, k = self.model_dim, self.top_k
        x = x_in.reshape(-1, d)
        tokens = x.shape[0]
        if state is None:
            state = self.init_state()
        cd = (jnp.bfloat16 if self.compute_dtype in ("bfloat16", jnp.bfloat16)
              else jnp.float32)
        scope = self.phase or "ff.moe"
        with jax.named_scope(scope + ".route"):
            if self.router == "mlp":
                idx, gates, counts, r = self.route_mlp(
                    x, params, None if r_prev is None
                    else r_prev.reshape(tokens, -1))
            else:
                idx, gates, counts = self.route(x, params["router"],
                                                state["bias"])
        with jax.named_scope(scope + ".dispatch"):
            local = idx.reshape(-1) - self.first_held
            here = (local >= 0) & (local < self.num_held)
            key = jnp.where(here, local, self.num_held)
            # stable, so a group keeps its tokens in sequence order, and
            # the assignments to held experts come first
            order = checkpoint_name(
                jnp.argsort(key, stable=True).astype(jnp.int32), ORDER)
            group_sizes = checkpoint_name(jnp.sum(
                key[:, None] == jnp.arange(self.num_held), axis=0,
                dtype=jnp.int32), GROUP_SIZES)
            n_here = jnp.sum(group_sizes)
        slab = slab_rows(tokens * k, self.num_held, self.num_experts)
        out, slabs = _held_experts(
            (slab, k, scope), x.astype(cd), params["w_gate"],
            params["w_up"], params["w_down"], gates.reshape(-1), order,
            group_sizes)
        if self.num_shared:
            with jax.named_scope(scope + ".shared"):
                shared = swiglu(x, params["shared_gate"], params["shared_up"],
                                params["shared_down"], self.compute_dtype)
                if self.shared_gated:
                    shared = shared * jax.nn.sigmoid(matmul(
                        x, params["shared_sigmoid"], self.compute_dtype))
                out = out + shared
        new_state = state
        if training:
            with jax.named_scope(scope + ".route"):
                mean = jnp.mean(counts.astype(jnp.float32))
                new_state = {
                    **state,
                    "bias": state["bias"] + self.bias_update_speed
                    * jnp.sign(mean - counts.astype(jnp.float32)),
                    "tokens_per_expert": state["tokens_per_expert"] + counts,
                    **{name: state[name] + count for name, count in (
                        ("held_assignments", n_here),
                        ("padded_rows", tokens * k - n_here),
                        ("buffer_rows", slabs * slab),
                        ("overflow_steps", (slabs > 1).astype(jnp.int32)))
                       if name in state}}
                if self.router == "mlp":
                    new_state[SKIPPED] = state[SKIPPED] + (
                        tokens * k - jnp.sum(counts))
        self._last_state = new_state
        out = out.reshape(x_in.shape).astype(self.outputs[0].dtype)
        if self.router == "mlp":
            return [out, r.reshape(x_in.shape[:-1] + (-1,))]
        return [out]

    def flops(self, batch):
        rows = self.inputs[0].numel() // self.model_dim
        d, h, rh = self.model_dim, self.hidden_dim, self.router_hidden
        routed = self.top_k * self.num_held / self.slots
        router = (d * self.num_experts if self.router == "linear"
                  else d * rh + rh * rh + rh * self.slots)
        return int(rows * (2 * router
                           + 6 * d * h * (routed + self.num_shared)))
