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
and a grouped matmul instead of every expert on every token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..initializers import DEFAULT_KERNEL_INIT, ZeroInitializer
from ..tensor import ParameterSpec
from .base import Op
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
@jax.custom_vjp
def _spread_rows(x, order, inverse):
    """Row ``order[a] // k`` of ``x`` (T, d) for each of the ``A = T*k``
    sorted assignments.  ``order`` is a permutation of the assignments
    and ``inverse`` its inverse, so the transpose is a gather too (sum
    over each token's ``k`` rows), never a scatter-add."""
    return jnp.take(x, order // (order.shape[0] // x.shape[0]), axis=0)


def _spread_fwd(x, order, inverse):
    return _spread_rows(x, order, inverse), (inverse, x.shape[0])


def _spread_bwd(res, g):
    inverse, tokens = res
    back = jnp.take(g, inverse, axis=0)
    return back.reshape(tokens, -1, g.shape[-1]).sum(axis=1), None, None


_spread_rows.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _unsort_rows(y, order, inverse):
    """``y`` (A, d) in sorted order back in assignment order: the
    inverse permutation's gather, whose transpose is ``order``'s."""
    return jnp.take(y, inverse, axis=0)


def _unsort_fwd(y, order, inverse):
    return _unsort_rows(y, order, inverse), order


def _unsort_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_unsort_rows.defvjp(_unsort_fwd, _unsort_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


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
        tiling = (min(512, rows.shape[0]), min(1024, rows.shape[1]),
                  min(1024, weights.shape[2]))
        return megablox.gmm(rows, weights, group_sizes, jnp.float32, tiling)
    return jax.lax.ragged_dot(rows, weights, group_sizes,
                              preferred_element_type=jnp.float32)


class HeldExpertsMoE(Op):
    """A routed-expert layer that holds ``held = (first, count)`` of its
    ``num_experts`` experts (all of them by default), plus
    ``num_shared`` shared experts every token passes through.  Experts
    are SwiGLU MLPs ``d -> hidden -> d`` without biases.

    Routing is over ALL ``num_experts`` (DeepSeek-V3, arXiv:2412.19437
    section 2.1.2 and the ``noaux_tc`` method of its released code):
    ``s = sigmoid(x W_r)`` in f32; the ``top_k`` largest of ``s + b``
    are selected (``b`` selects and never weighs); gates are the
    selected ``s`` normalised to sum 1, times ``scaling``.  The output is
    ``sum over selected AND held experts of gate * expert(x)``, plus the
    shared experts; what the absent experts would add is left out (one
    chip's share of an expert-parallel layer, without its exchange).

    No assignment to a held expert is dropped: the assignments are
    sorted by held expert into a buffer of all ``T * top_k`` of them
    (the worst case: every token choosing held experts only), the
    grouped matmul runs over the held groups, and the rows behind them
    are padding that is masked, and counted.

    ``b`` is state, not a parameter (the ``bn_state`` route): it takes
    no gradient, and each training step moves it by ``bias_update_speed
    * sign(mean(c) - c)``, ``c`` the step's tokens per expert.  The state
    also counts, since ``init``: ``tokens_per_expert`` (all experts),
    ``held_assignments``, ``padded_rows`` (buffer rows the grouped
    matmul was given beyond the held assignments).

    Scopes (the prefix is the op's ``phase``, ``FFModel.scope``'s word,
    ``ff.moe`` without one): ``.route`` (scores, top-k, counts),
    ``.dispatch`` (sort, gather), ``.experts`` (the grouped matmuls),
    ``.combine``, ``.shared``.
    """

    op_type = "HeldExpertsMoE"
    has_state = True

    def __init__(self, name, input_tensor, num_experts: int, hidden_dim: int,
                 top_k: int, held=None, num_shared: int = 0,
                 scaling: float = 1.0, bias_update_speed: float = 0.0,
                 kernel_initializer=None, compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.num_experts = int(num_experts)
        first, count = held if held is not None else (0, self.num_experts)
        self.first_held, self.num_held = int(first), int(count)
        assert 0 <= self.first_held \
            and self.first_held + self.num_held <= self.num_experts
        assert 1 <= top_k <= self.num_experts
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

    def param_specs(self):
        d, h, e = self.model_dim, self.hidden_dim, self.num_held
        init = self.kernel_initializer
        specs = [
            ParameterSpec(self.name, "router", (d, self.num_experts),
                          initializer=init),
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
        return specs

    def init_state(self):
        return {"bias": jnp.zeros((self.num_experts,), jnp.float32),
                "tokens_per_expert": jnp.zeros((self.num_experts,),
                                               jnp.int32),
                "held_assignments": jnp.zeros((), jnp.int32),
                "padded_rows": jnp.zeros((), jnp.int32)}

    def step_metrics(self, old, new):
        """This step's counters for the step's metrics (``train_epoch``
        folds them: sums, and the largest for a name ending ``_max``)."""
        out = {k: new[k] - old[k] for k in ("tokens_per_expert",
                                             "held_assignments",
                                             "padded_rows")}
        out["bias_abs_max"] = jnp.max(jnp.abs(new["bias"]))
        return out

    def route(self, x, router, bias):
        """``(idx (T, k) int32, gates (T, k) f32, counts (E,) int32)``
        for tokens ``x`` (T, d): scores and selection in f32 at full
        matmul precision, whatever the compute dtype (a rounded score
        would move the selection)."""
        logits = jnp.matmul(x.astype(jnp.float32), router,
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                               self.top_k)
        picked = jnp.take_along_axis(scores, idx, axis=-1)
        gates = self.scaling * picked / jnp.sum(picked, axis=-1,
                                                keepdims=True)
        counts = jnp.sum(idx[..., None] == jnp.arange(self.num_experts),
                         axis=(0, 1), dtype=jnp.int32)
        return idx.astype(jnp.int32), gates, counts

    def forward(self, params, xs, *, training=False, rng=None, state=None):
        (x_in,) = xs
        d, k = self.model_dim, self.top_k
        x = x_in.reshape(-1, d)
        tokens = x.shape[0]
        if state is None:
            state = self.init_state()
        cd = (jnp.bfloat16 if self.compute_dtype in ("bfloat16", jnp.bfloat16)
              else jnp.float32)
        scope = self.phase or "ff.moe"
        with jax.named_scope(scope + ".route"):
            idx, gates, counts = self.route(x, params["router"],
                                            state["bias"])
        with jax.named_scope(scope + ".dispatch"):
            local = idx.reshape(-1) - self.first_held
            here = (local >= 0) & (local < self.num_held)
            key = jnp.where(here, local, self.num_held)
            # stable, so a group keeps its tokens in sequence order
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(self.num_held), axis=0,
                dtype=jnp.int32)
            n_here = jnp.sum(group_sizes)
            live = (jnp.arange(tokens * k) < n_here)[:, None]
            # the select is for the backward: megablox never visits the
            # padding's tiles, so their cotangent is whatever was there
            rows = jnp.where(live, _spread_rows(x.astype(cd), order,
                                                inverse), 0)
        with jax.named_scope(scope + ".experts"):
            gate = grouped_matmul(rows, params["w_gate"].astype(cd),
                                  group_sizes)
            up = grouped_matmul(rows, params["w_up"].astype(cd), group_sizes)
            act = jnp.where(live, jax.nn.silu(gate) * up, 0.0)
            y = grouped_matmul(act.astype(cd), params["w_down"].astype(cd),
                               group_sizes)
            y = jnp.where(live, y, 0.0)
        with jax.named_scope(scope + ".combine"):
            weights = jnp.where(here, gates.reshape(-1), 0.0)
            y = _unsort_rows(y, order, inverse) * weights[:, None]
            out = jnp.sum(y.reshape(tokens, k, d), axis=1)
        if self.num_shared:
            with jax.named_scope(scope + ".shared"):
                out = out + swiglu(x, params["shared_gate"],
                                   params["shared_up"],
                                   params["shared_down"], self.compute_dtype)
        new_state = state
        if training:
            with jax.named_scope(scope + ".route"):
                mean = jnp.mean(counts.astype(jnp.float32))
                new_state = {
                    "bias": state["bias"] + self.bias_update_speed
                    * jnp.sign(mean - counts.astype(jnp.float32)),
                    "tokens_per_expert": state["tokens_per_expert"] + counts,
                    "held_assignments": state["held_assignments"] + n_here,
                    "padded_rows": state["padded_rows"]
                    + (tokens * k - n_here)}
        self._last_state = new_state
        return [out.reshape(x_in.shape).astype(self.outputs[0].dtype)]

    def flops(self, batch):
        rows = self.inputs[0].numel() // self.model_dim
        d, h = self.model_dim, self.hidden_dim
        routed = self.top_k * self.num_held / self.num_experts
        return int(rows * (2 * d * self.num_experts
                           + 6 * d * h * (routed + self.num_shared)))
