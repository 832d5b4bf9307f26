"""A ZAYA1-style decoder language model as published, plain: forward,
loss, gradients and one Adam step in ``jax.numpy`` and float32, and the
comparison that decides the benchmark's ``correct``.  Imports nothing
from ``dlrm_flexflow_tpu``.

The model (ZAYA1-8B's ``config.json``; Compressed Convolutional
Attention is Figliolia et al., arXiv:2510.04476; the router's form is
the family's published keys ``zaya_use_eda``, ``zaya_use_mod`` and
``zaya_mlp_expansion``; ``docs/CCA_MOE_LM.md`` has every assumption):

- norm: ``N(x; w) = x / sqrt(mean(x^2) + eps) w`` (``w`` starts at 1);
- layer ``l``: ``x += cca(N(x))``, ``x += experts(N(x), r_(l-1))``;
  after the last layer a final ``N`` and the head, which is the
  embedding's own matrix: ``logits = N(x) E^T``;
- CCA, ``H`` query heads on ``G`` key/value heads of width ``dh``, for
  token ``t`` (a row before the sequence's start is zero):
  ``q~_t = x_t W_q``, ``k~_t = x_t W_k``; ``v_t = [x_t W_v1 ; x_(t-1)
  W_v2]`` (the first and the second half of ``W_v``'s columns), read as
  ``G`` heads; ``c_t = [q~_t ; k~_t]``; ``c'_t = a_0 c_t + a_1 c_(t-1)``
  elementwise; ``c''_(t,g) = c'_(t,g) B_(g,0) + c'_(t-1,g) B_(g,1)`` for
  each of the ``H + G`` heads ``g``; ``q-bar``, ``k-bar`` its first
  ``H`` and last ``G`` heads; for query head ``h`` in group ``g(h) = h
  // (H / G)``: ``m_h = (q~_h + k~_g(h)) / 2``, ``q_h = q-bar_h + m_h``;
  ``k_g = k-bar_g + mean over the heads h of g of m_h``; ``q^ = q /
  |q|``, ``k^ = tau_g k / |k|`` (``|t| = sqrt(sum t^2 + 1e-6)``); the
  rotary embedding, base ``rope_theta``, in the half-split form on the
  first ``dh x partial_rotary_factor`` elements of each head; ``o_h =
  sum over s <= t of softmax_s(q^_h . k^_(s,g(h))) v_(s,g(h))``; ``y =
  [o_0 .. o_(H-1)] W_o``;
- experts: ``z = x W_d``; ``r = (1 - gamma) z + gamma r_(l-1)``, ``gamma
  = sigmoid(c)``, ``r_(-1) = 0``; ``p = softmax(gelu(r W_a) W_e)`` over
  the ``E`` experts and the skip slot ``E``; ``c = argmax p``; ``y =
  p_c SwiGLU_c(x)`` for ``c < E``, 0 for the skip slot;
- loss: mean cross-entropy over the positions; Adam (Kingma & Ba,
  arXiv:1412.6980, the form of the end of its section 2).

Departures from the released model, each the configuration's own cut
(``benchmarks/configs/*.json`` lists them under ``reduced`` / ``assumed``):

1. The chip's share.  ``params`` hold the expert weights of the ``held``
   experts only, ids ``[first, first + held)``; routing is over all
   ``num_experts`` and the skip slot, and the output is the selected
   expert's when it is held and 0 otherwise.  What the absent experts
   would add is left out, and that partial result goes on to the next
   layer.
2. Depth and vocabulary are whatever ``params`` hold (a slice of the
   vocabulary is a smaller vocabulary); the layers are the published
   ones from layer 0, so the router state enters as zeros.
3. No dropout, no load-balancing term, no ``scale_residual_merge``, full
   sequences without padding or packing.

Arithmetic: float32 throughout at ``highest`` matmul precision, with the
one exception the configuration states: under ``compute_dtype`` bfloat16
every matmul's two operands are rounded to it, with float32 accumulation
(so are the grouped convolution's taps and rows, the attention's q, k
and v, and its probabilities, an operand of ``P v``).  The residual
stream, norms, the depthwise convolution, the normalisation and the
temperature, softmax, the router (an f32 MLP of unrounded operands), the
SwiGLU product, the loss and Adam stay f32.  The (S, S) attention is
built a block of rows at a time, the head's logits a block of rows at a
time, and each expert is a dense FFN over every token times its gate: no
online softmax, no sort, no grouped matmul.  ``jax.checkpoint`` keeps
the memory of the backward pass down and changes no number.

Layout of ``params``::

    {"embed": (V, d), "final_norm": (d,), "layers": [LAYER, ...]}
    LAYER = {"cca_norm", "moe_norm": (d,),
             "cca": {"w_q": (d, H dh), "w_k", "w_v": (d, G dh),
                     "conv0": (taps0, (H + G) dh),
                     "conv1": (taps1, H + G, dh, dh), "temperature": (G,),
                     "w_o": (H dh, d)},
             "moe": {"router_down": (d, rh), "router_depth": (),
                     "router_hidden": (rh, rh), "router_out": (rh, E + 1),
                     "w_gate", "w_up": (held, d, h), "w_down": (held, h, d)}}
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gdn_moe_lm_ref import rope
from benchmarks.reference.mla_moe_lm_ref import (THIN_TOKENS, _Frozen,
                                                 _square_sums,
                                                 leaves_by_name)

ROW_BLOCK = 512     # rows of the (S, S) attention built at a time
HEAD_BLOCK = 2048   # rows of the head's logits built at a time


# ------------------------------------------------------------ the model
def _mm(a, w, dt):
    return jnp.matmul(a.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def earlier(x, lag):
    """Row ``t`` of ``x`` (S, ...) replaced by row ``t - lag``; zeros
    before the start."""
    return jnp.concatenate([jnp.zeros((lag,) + x.shape[1:], x.dtype),
                            x[:x.shape[0] - lag]])


def attention(q, k, v, dt):
    """Causal softmax attention of one sequence at scale 1, ``q`` (S, H,
    d), ``k``, ``v`` (S, G, d), the full (H, rows, S) logits of
    ``ROW_BLOCK`` rows at a time."""
    s, h = q.shape[:2]
    k, v = (jnp.repeat(x, h // k.shape[1], axis=1) for x in (k, v))
    rows = min(ROW_BLOCK, s)
    assert s % rows == 0
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        logits = jnp.einsum("rhd,shd->hrs", q_rows.astype(dt), k.astype(dt),
                            preferred_element_type=jnp.float32)
        seen = cols[None, :] <= (start + jnp.arange(rows))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        return jnp.einsum("hrs,shd->rhd", probs.astype(dt), v.astype(dt),
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(block, jnp.arange(0, s, rows))
    return out.reshape((s,) + out.shape[2:])


def cca(w, x, hp, dt):
    """``x`` (S, d) -> (S, d)."""
    s = x.shape[0]
    h, g, dh = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"])
    per = h // g
    q0 = _mm(x, w["w_q"], dt).reshape(s, h, dh)
    k0 = _mm(x, w["w_k"], dt).reshape(s, g, dh)
    v = _mm(x, w["w_v"], dt)
    half = v.shape[1] // 2
    v = jnp.concatenate([v[:, :half], earlier(v[:, half:], 1)],
                        axis=1).reshape(s, g, dh)
    c = jnp.concatenate([q0.reshape(s, -1), k0.reshape(s, -1)], axis=1)
    c = sum(earlier(c, j) * w["conv0"][j] for j in range(len(w["conv0"])))
    c = c.reshape(s, h + g, dh)
    c = sum(jnp.matmul(earlier(c, j).transpose(1, 0, 2).astype(dt),
                       w["conv1"][j].astype(dt),
                       preferred_element_type=jnp.float32)
            for j in range(len(w["conv1"]))).transpose(1, 0, 2)
    m = [(q0[:, i] + k0[:, i // per]) / 2 for i in range(h)]
    q = jnp.stack([c[:, i] + m[i] for i in range(h)], axis=1)
    k = jnp.stack([c[:, h + j] + sum(m[j * per:(j + 1) * per]) / per
                   for j in range(g)], axis=1)
    unit = lambda t: t / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1,
                                          keepdims=True) + 1e-6)
    rotary = int(dh * hp["partial_rotary_factor"])
    q = rope(unit(q), hp["rope_theta"], rotary)
    k = rope(unit(k) * w["temperature"][:, None], hp["rope_theta"], rotary)
    return _mm(attention(q, k, v, dt).reshape(s, h * dh), w["w_o"], dt)


def swiglu(x, w, dt):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"], dt))
               * _mm(x, w["w_up"], dt), w["w_down"], dt)


def route(w, x, r_prev):
    """``(p (T, E + 1), choice (T,), r (T, rh))``: the MLP router with
    depth averaging over the experts and the skip slot, in f32."""
    gamma = jax.nn.sigmoid(w["router_depth"])
    r = (1.0 - gamma) * jnp.matmul(x, w["router_down"]) + gamma * r_prev
    p = jax.nn.softmax(jnp.matmul(jax.nn.gelu(
        jnp.matmul(r, w["router_hidden"]), approximate=False),
        w["router_out"]), axis=-1)
    return p, jnp.argmax(p, axis=-1), r


def expert_layer(w, x, r_prev, hp, dt):
    """``x`` (T, d) -> ``(y (T, d), r, counts (E + 1,))``: every held
    expert over every token, times the chosen probability where it is
    the token's choice."""
    p, choice, r = route(w, x, r_prev)
    gate = jnp.take_along_axis(p, choice[:, None], axis=1)
    first = hp["first_expert_held"]

    @jax.checkpoint
    def one(total, e):
        ffn = {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        mine = jnp.where(choice[:, None] == first + e, gate, 0.0)
        return total + mine * swiglu(x, ffn, dt), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(w["w_gate"].shape[0]))
    counts = jnp.sum(jax.nn.one_hot(choice, p.shape[-1], dtype=jnp.int32),
                     axis=0)
    return y, r, counts


def _mixer(w, x, hp, dt):
    return x + cca(w["cca"], norm(x, w["cca_norm"], hp["rms_norm_eps"]),
                   hp, dt)


def _experts(w, x, r_prev, hp, dt):
    y, r, counts = expert_layer(w["moe"], norm(x, w["moe_norm"],
                                               hp["rms_norm_eps"]),
                                r_prev, hp, dt)
    return x + y, r, counts


def decoder_layer(w, x, r_prev, hp, dt):
    """One sequence ``x`` (S, d) through one layer; ``(x, r, counts)``."""
    mixer = jax.checkpoint(_mixer, static_argnums=(2, 3))
    experts = jax.checkpoint(_experts, static_argnums=(3, 4))
    return experts(w, mixer(w, x, hp, dt), r_prev, hp, dt)


def head_loss(embed, final_norm, x, targets, hp, dt):
    """Mean cross-entropy of ``N(x) E^T`` against ``targets``, over
    ``HEAD_BLOCK`` rows of logits at a time."""
    x, targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    rows = min(HEAD_BLOCK, x.shape[0])
    assert x.shape[0] % rows == 0

    @jax.checkpoint
    def block(xt):
        xb, tb = xt
        logits = _mm(norm(xb, final_norm, hp["rms_norm_eps"]), embed.T, dt)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(block, (x.reshape(-1, rows, x.shape[-1]),
                               targets.reshape(-1, rows)))
    return jnp.sum(sums) / x.shape[0]


def losses(params, tokens, hp, dt):
    """``tokens`` (B, S + 1): positions ``[0, S)`` are the inputs, ``[1,
    S]`` the targets; ``hp`` hashable (``_Frozen``).  Returns ``(loss,
    [counts per layer])``; a batch's counts are summed over its
    sequences."""
    b, s = tokens.shape[0], tokens.shape[1] - 1
    x = params["embed"][tokens[:, :s]]
    r = jnp.zeros((b, s, hp["router_hidden_size"]), jnp.float32)
    counts = []
    for w in params["layers"]:
        x, r, c = jax.vmap(lambda seq, st: decoder_layer(w, seq, st, hp,
                                                         dt))(x, r)
        counts.append(jnp.sum(c, axis=0))
    return head_loss(params["embed"], params["final_norm"], x,
                     tokens[:, 1:s + 1], hp, dt), counts


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _train_step(state, tokens, hp, dt):
    params, m, v, step = state
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.value_and_grad(losses, has_aux=True)(
            params, tokens, hp, dt)
    t = step + 1
    tf = t.astype(jnp.float32)
    b1, b2 = hp["adam_beta1"], hp["adam_beta2"]
    alpha = hp["learning_rate"] * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                               grads)
    params = jax.tree_util.tree_map(
        lambda w, m, v: w - alpha * m / (jnp.sqrt(v) + hp["adam_epsilon"]),
        params, m, v)
    return (params, m, v, t), (loss, counts)


def train_steps(state, tokens, hp, compute_dtype="float32"):
    """Adam steps over ``tokens`` (K, B, S + 1), one per leading entry.
    ``state = (params, m, v, step)`` is consumed.  Returns ``(state,
    [loss], [[counts (E + 1,) per layer]])``, a list entry per step."""
    out_loss, out_counts = [], []
    for batch in tokens:
        state, (loss, counts) = _train_step(
            state, jnp.asarray(batch), _Frozen(hp), jnp.dtype(compute_dtype))
        out_loss.append(float(loss))
        out_counts.append([np.asarray(c) for c in counts])
    return state, out_loss, out_counts


# ------------------------------------------------------- the comparison
# What is compared, from the state the window left, over the K check
# steps through the timed path and through ``train_steps``; the
# definitions are the sibling families' (``mla_moe_lm_ref.py`` has each
# one's and why no selection can be held to zero against an independent
# reference).  Readings on a TPU v5e at the published widths in bf16,
# the weights drawn from ``train.weights_seed`` and the tied matrix at
# 0.02: seven sound runs on seven seeds (six from step 12, one traced
# from step 6), and the control's (the reference in the program's place
# with float8 operands, seed 2147490001); each limit lies between the
# two, with room on both sides, and the control fails every one but
# ``counter_slack``.  PERF.md section 6 has the readings.
#
# ``grad_err_max`` / ``_median`` / ``_all``: per tensor (per held expert
#   for the stacked expert weights) the norm of the difference of the
#   two first-moment changes ``m_K - b1^K m_0`` over the norm of the
#   reference's: the steps' gradients and nothing else.  An expert that
#   the two sides sent different tokens and that the reference sent
#   fewer than ``THIN_TOKENS`` is left out, as in the siblings (none
#   was).  ``_max`` 0.077-0.221, a held expert's of the last two layers,
#   where most tokens go to a few experts and their gradients are sums
#   that largely cancel (control 207.6, a key head's temperature); the
#   limit is the sibling families' 0.5; ``_median`` 0.0068-0.0086
#   (control 1.0); ``_all`` 0.00040-0.00050 (control 0.94).
# ``grad_err_cca_max``: the largest of them over CCA's own tensors (no
#   selection moves them directly, so they are held tighter):
#   0.0032-0.0066 from step 12, 0.024 from step 6 (control 207.6).  At
#   the rehearsal's size in bf16 they read 0.010-0.024.
# ``update_err``: all parameter updates as one vector; a state left
#   unchanged reads 1.0.  0.013-0.016 from step 12, 0.017 from step 6
#   (control 0.25); the limit has the more room above the readings.
# ``loss_err``: the mean loss, relative: <= 4.3e-6 (control 0.079); the
#   limit is the harness's other cells'.
# ``count_err``: tokens per slot (the experts and the skip slot) over
#   all layers, summed over the steps: sum |got - want| over sum want,
#   0.00060-0.00093 (control 0.93).
# ``counter_slack``: exact, the program's own counters against each
#   other: every step's T assignments are counted once in
#   ``tokens_per_expert`` + ``skipped_tokens``, once in
#   ``held_assignments + padded_rows``; the held experts' counts add up
#   to ``held_assignments``.
LIMITS = {"grad_err_max": 0.5, "grad_err_cca_max": 0.1,
          "grad_err_median": 0.05, "grad_err_all": 0.01, "update_err": 0.08,
          "loss_err": 2e-3, "count_err": 0.01, "counter_slack": 0}


def compare(before, got, want, k: int, hp: dict):
    """``before`` / ``got`` / ``want``: ``{"params", "m", "losses",
    "counts"}`` (``before`` without the last two; ``got["counts"]``:
    ``{"tokens_per_expert": [(E,) per layer], "skipped_tokens",
    "held_assignments", "padded_rows": [...]}``, the counters' change
    over the ``k`` steps; ``want["counts"]``: per step, per layer, (E +
    1,) with the skip slot last).  Each tensor is brought to the device
    for its norms and let go.  Returns ``(ok, report)``."""
    decay = jnp.float32(hp["adam_beta1"] ** k)
    flat = {side: {part: leaves_by_name(tree[part])
                   for part in ("params", "m")}
            for side, tree in (("before", before), ("got", got),
                               ("want", want))}
    grad_err, diff_sq, moved_sq, gdiff_sq, gwant_sq = {}, 0.0, 0.0, 0.0, 0.0
    c_want = np.sum([np.stack(step) for step in want["counts"]],
                    axis=0).astype(np.int64)        # (layers, E + 1)
    c_got = np.concatenate([
        np.stack(got["counts"]["tokens_per_expert"]),
        np.stack(got["counts"]["skipped_tokens"])[:, None]],
        axis=1).astype(np.int64)
    first, held = hp["first_expert_held"], hp["experts_held"]
    per_step = hp["num_experts_per_tok"] * hp["tokens_per_step"] * k
    thin = 0
    for name in flat["before"]["params"]:
        sums = _square_sums(*(flat[side][part][name]
                              for part in ("params", "m")
                              for side in ("before", "got", "want")), decay)
        d_p, w_p, d_m, w_m = (np.atleast_1d(np.asarray(x, np.float64))
                              for x in sums)
        diff_sq += float(d_p.sum())
        moved_sq += float(w_p.sum())
        gdiff_sq += float(d_m.sum())
        gwant_sq += float(w_m.sum())
        errs = np.sqrt(d_m / np.maximum(w_m, 1e-60))
        if d_m.size == 1:
            grad_err[name] = float(errs[0])
            continue
        # a stacked expert weight ("layers.<i>.moe.w_*"): one entry for
        # each held expert; the thin rule is the sibling families'
        layer = int(name.split(".")[1])
        for e, err in enumerate(errs):
            sent = int(c_want[layer, first + e])
            took = int(c_got[layer, first + e])
            if sent < THIN_TOKENS and took != sent:
                thin += 1
                continue
            grad_err[f"{name}.{e}"] = float(err)
    ranked = sorted(grad_err, key=grad_err.get, reverse=True)
    own = {n: v for n, v in grad_err.items() if ".cca." in n}
    loss_got, loss_want = np.mean(got["losses"]), np.mean(want["losses"])
    slack = 0
    for layer, counts in enumerate(c_got):
        here = int(got["counts"]["held_assignments"][layer])
        slack += abs(int(counts.sum()) - per_step)
        slack += abs(here + int(got["counts"]["padded_rows"][layer])
                     - per_step)
        slack += abs(int(counts[first:first + held].sum()) - here)
    report = {
        "grad_err_max": grad_err[ranked[0]], "grad_worst_tensor": ranked[0],
        "grad_err_median": float(np.median(list(grad_err.values()))),
        "update_err": float(np.sqrt(diff_sq / max(moved_sq, 1e-60))),
        "loss_err": float(abs(loss_got - loss_want) / abs(loss_want)),
        "count_err": float(np.abs(c_got - c_want).sum() / c_want.sum()),
        "counter_slack": int(slack),
        "grad_err_all": float(np.sqrt(gdiff_sq / max(gwant_sq, 1e-60))),
        "grad_err_q90": float(np.quantile(list(grad_err.values()), 0.9)),
        "grad_worst_five": [[n, round(grad_err[n], 4)] for n in ranked[:5]],
        "grad_err_cca_max": max(own.values()),
        "grad_err_cca": {n: round(v, 4) for n, v in own.items()},
        "tensors_compared": len(grad_err), "thin_expert_tensors": thin,
        "loss_got": float(loss_got), "loss_want": float(loss_want),
        "skip_share": float(c_want[:, -1].sum() / c_want.sum()),
        "held_assignments": [int(x) for x in
                             got["counts"]["held_assignments"]],
    }
    ok = all(report[name] <= limit for name, limit in LIMITS.items())
    return bool(ok), report
