"""A Qwen3-Next-style hybrid decoder language model as published, plain:
forward, loss, gradients and one Adam step in ``jax.numpy`` and float32,
and the comparison that decides the benchmark's ``correct``.  Imports
nothing from ``dlrm_flexflow_tpu``.

The model (Qwen3-Next-80B-A3B's ``config.json``; the layer code is
Hugging Face's ``modeling_qwen3_next.py``; the Gated DeltaNet rule is
Yang, Kautz & Hatamizadeh, arXiv:2412.06464):

- norm: ``N(x; w) = x / sqrt(mean(x^2) + eps) (1 + w)`` (zero-centred;
  ``w`` starts at 0);
- layer ``i``: ``x += mixer(N(x))``, ``x += experts(N(x))``; the mixer
  is gated full attention where ``(i + 1) % full_attention_interval ==
  0`` and Gated DeltaNet elsewhere; after the last layer a final ``N``
  and the untied head;
- Gated DeltaNet: ``[q | k | v | z] = x W_qkvz`` (``linear_num_key_heads
  x linear_key_head_dim`` for q and k, ``linear_num_value_heads x
  linear_value_head_dim`` for v and z); ``[b | a] = x W_ba``; ``[q | k |
  v] <- silu(causalconv([q | k | v]))``, depthwise, ``linear_conv_kernel
  _dim`` taps, no bias; ``beta = sigmoid(b)``; ``g = -exp(A_log)
  softplus(a + dt_bias)``; q and k L2-normalised per head (``x / sqrt(sum
  x^2 + 1e-6)``), q times ``dk^-1/2``; each key head serves
  ``value heads / key heads`` consecutive value heads; per value head the
  state ``S`` (dk, dv), ``S_0 = 0``, ONE TOKEN AT A TIME: ``S <-
  exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``;
  ``o_t = S^T q_t``; then per head ``o <- o / sqrt(mean(o^2) + eps) w_n
  silu(z)`` and ``y = o W_out``;
- gated full attention: ``[q | gate] = x W_q`` per head (``head_dim``
  each); ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` heads);
  ``q <- N(q; w_q)``, ``k <- N(k; w_k)`` over the head; rotary embedding,
  base ``rope_theta``, in the half-split form (``rotate_half``) on the
  first ``head_dim x partial_rotary_factor`` elements; causal softmax
  attention at scale ``head_dim^-1/2``, ``heads / key-value heads``
  consecutive query heads to a key/value head; ``y = (o sigmoid(gate))
  W_o``;
- experts: ``p = softmax(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest selected; gates ``p_sel / sum p_sel``;
  ``y = sum over selected experts of gate_i SwiGLU_i(x) + sigmoid(x w_s)
  SwiGLU_shared(x)``;
- loss: mean cross-entropy over the positions; Adam (Kingma & Ba,
  arXiv:1412.6980, the form of the end of its section 2).

Departures from the released model, each the configuration's own cut
(``benchmarks/configs/*.json`` lists them under ``reduced`` / ``assumed``):

1. The chip's share.  ``params`` hold the expert weights of the ``held``
   experts only, ids ``[first, first + held)``; routing is over all
   ``num_experts``, and the sum runs over the selected experts that are
   held.  What the absent experts would add is left out, and that
   partial result goes on to the next layer.
2. Depth and vocabulary are whatever ``params`` hold.
3. The columns of ``W_qkvz`` lie q, k, v, z (the released projection
   interleaves them by key head: the same layer under a permutation of
   columns), those of ``W_ba`` b, a.  ``conv`` is stored (taps,
   channels).
4. No dropout, no load-balancing term (the released code adds none
   unless asked; the config gives no coefficient), no multi-token
   prediction (the config has no key for it), full sequences without
   padding or packing (no state reset, no segment mask).

Arithmetic: float32 throughout at ``highest`` matmul precision, with the
one exception the configuration states: under ``compute_dtype`` bfloat16
every matmul's two operands are rounded to it, with float32 accumulation
(so are the attention probabilities, an operand of ``P v``, and the
DeltaNet rule's q, k and v, which the program hands its chunked form in
that dtype).  The residual stream, norms, the convolution, softmax,
router scores (an f32 matmul of unrounded operands), decays, the DeltaNet
state and its recurrence, the SwiGLU product, the loss and Adam stay f32.
The (S, S) attention is built a block of rows at a time, the DeltaNet
state moves a token at a time (a ``lax.scan`` in blocks of
``TOKEN_BLOCK`` tokens under ``jax.checkpoint``: one state a token would
be 34 GB a layer at 16,384 tokens), and each expert is a dense FFN over
every token times its gate: no chunked rule, no triangular solve, no
sort, no grouped matmul, no online softmax.  ``jax.checkpoint`` keeps the
memory of the backward pass down and changes no number.

Layout of ``params``::

    {"embed": (V, d), "head": (d, V), "final_norm": (d,),
     "layers": [LAYER, ...]}
    LAYER = {"mixer_norm", "ffn_norm": (d,), then either "gdn": {"w_qkvz",
             "w_ba", "conv": (taps, channels), "a_log", "dt_bias": (Hv,),
             "norm": (dv,), "w_out"} or "attn": {"w_q", "w_k", "w_v",
             "q_norm", "k_norm": (head_dim,), "w_o"}, and "moe": {"router":
             (d, E), "w_gate", "w_up": (held, d, h), "w_down": (held, h, d),
             "shared": {"w_gate", "w_up", "w_down"}, "shared_gate": (d, 1)}}
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.mla_moe_lm_ref import (THIN_TOKENS, _Frozen,
                                                 _square_sums,
                                                 leaves_by_name)

ROW_BLOCK = 512     # rows of the (S, S) attention built at a time
TOKEN_BLOCK = 128   # tokens of the DeltaNet recurrence under one checkpoint


# ------------------------------------------------------------ the model
def _mm(a, w, dt):
    return jnp.matmul(a.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + w)


def rope(x, theta, rotary):
    """``rotate_half`` rotary embedding of the first ``rotary`` elements
    of ``x`` (S, H, d) by position along axis 0."""
    s = x.shape[0]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    turned, kept = x[..., :rotary], x[..., rotary:]
    half = rotary // 2
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]],
                              axis=-1)
    return jnp.concatenate([turned * jnp.cos(angles)
                            + rotated * jnp.sin(angles), kept], axis=-1)


def swiglu(x, w, dt):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"], dt))
               * _mm(x, w["w_up"], dt), w["w_down"], dt)


def attention(q, k, v, dt):
    """Causal softmax attention of one sequence, ``q`` (S, H, d), ``k``,
    ``v`` (S, Hkv, d), the full (H, rows, S) logits of ``ROW_BLOCK``
    rows at a time."""
    s, h = q.shape[:2]
    k, v = (jnp.repeat(x, h // k.shape[1], axis=1) for x in (k, v))
    rows = min(ROW_BLOCK, s)
    assert s % rows == 0
    scale = q.shape[-1] ** -0.5
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        logits = jnp.einsum("rhd,shd->hrs", q_rows.astype(dt), k.astype(dt),
                            preferred_element_type=jnp.float32) * scale
        seen = cols[None, :] <= (start + jnp.arange(rows))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        return jnp.einsum("hrs,shd->rhd", probs.astype(dt), v.astype(dt),
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(block, jnp.arange(0, s, rows))
    return out.reshape((s,) + out.shape[2:])


def gated_attention(w, x, hp, dt):
    """``x`` (S, d) -> (S, d)."""
    s = x.shape[0]
    h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                 hp["head_dim"])
    eps, rotary = hp["rms_norm_eps"], int(hd * hp["partial_rotary_factor"])
    q_gate = _mm(x, w["w_q"], dt).reshape(s, h, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    k = _mm(x, w["w_k"], dt).reshape(s, kv, hd)
    v = _mm(x, w["w_v"], dt).reshape(s, kv, hd)
    q = rope(norm(q, w["q_norm"], eps), hp["rope_theta"], rotary)
    k = rope(norm(k, w["k_norm"], eps), hp["rope_theta"], rotary)
    out = attention(q, k, v, dt) * jax.nn.sigmoid(gate)
    return _mm(out.reshape(s, h * hd), w["w_o"], dt)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule one token at a time: ``q``, ``k`` (S, H, dk),
    ``v`` (S, H, dv), ``g``, ``beta`` (S, H), all f32."""
    s, h, dk = q.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, None, None] * state
        u = beta[:, None] * (v - jnp.einsum("hde,hd->he", state, k))
        state = state + k[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(x.reshape((s // block, block) + x.shape[1:])
               for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(tokens, jnp.zeros((h, dk, v.shape[-1])), xs)
    return out.reshape(s, h, -1)


def gated_delta_net(w, x, hp, dt):
    """``x`` (S, d) -> (S, d)."""
    s = x.shape[0]
    hk, hv, dk, dv = (hp["linear_num_key_heads"],
                      hp["linear_num_value_heads"],
                      hp["linear_key_head_dim"], hp["linear_value_head_dim"])
    taps, channels = w["conv"].shape
    qkvz = _mm(x, w["w_qkvz"], dt)
    ba = _mm(x, w["w_ba"], dt)
    padded = jnp.pad(qkvz[:, :channels], ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + s] * w["conv"][j]
                            for j in range(taps)))
    q = mixed[:, :hk * dk].reshape(s, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(s, hv, dv)
    z = qkvz[:, channels:].reshape(s, hv, dv)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                               keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[:, hv:] + w["dt_bias"])
    rounded = lambda t: t.astype(dt).astype(jnp.float32)
    o = delta_rule(rounded(q), rounded(k), rounded(v), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + hp["rms_norm_eps"]) * w["norm"] * jax.nn.silu(z)
    return _mm(o.reshape(s, hv * dv), w["w_out"], dt)


def route(w_router, x, hp):
    """``(gates (T, E) f32, zero where not selected; counts (E,))``."""
    scores = jax.nn.softmax(jnp.matmul(x, w_router), axis=-1)
    _, idx = jax.lax.top_k(scores, hp["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32),
                     axis=1)
    picked = scores * chosen
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return gates, jnp.sum(chosen, axis=0).astype(jnp.int32)


def expert_layer(w, x, hp, dt):
    """``x`` (T, d) -> ``(y (T, d), counts (E,))``: every held expert
    over every token, times its gate, and the gated shared expert."""
    gates, counts = route(w["router"], x, hp)
    first = hp["first_expert_held"]

    @jax.checkpoint
    def one(total, e):
        ffn = {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return total + gate * swiglu(x, ffn, dt), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(w["w_gate"].shape[0]))
    shared = swiglu(x, w["shared"], dt) \
        * jax.nn.sigmoid(_mm(x, w["shared_gate"], dt))
    return y + shared, counts


def _mixer(w, x, hp, dt):
    inner = norm(x, w["mixer_norm"], hp["rms_norm_eps"])
    if "attn" in w:
        return x + gated_attention(w["attn"], inner, hp, dt)
    return x + gated_delta_net(w["gdn"], inner, hp, dt)


def _experts(w, x, hp, dt):
    y, counts = expert_layer(w["moe"], norm(x, w["ffn_norm"],
                                            hp["rms_norm_eps"]), hp, dt)
    return x + y, counts


def decoder_layer(w, x, hp, dt):
    """One sequence ``x`` (S, d) through one layer; ``(x, counts)``."""
    mixer = jax.checkpoint(_mixer, static_argnums=(2, 3))
    experts = jax.checkpoint(_experts, static_argnums=(2, 3))
    return experts(w, mixer(w, x, hp, dt), hp, dt)


def _cross_entropy(logits, targets):
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def losses(params, tokens, hp, dt):
    """``tokens`` (B, S + 1): positions ``[0, S)`` are the inputs, ``[1,
    S]`` the targets; ``hp`` hashable (``_Frozen``).  Returns ``(loss,
    [counts per layer])``; a batch's counts are summed over its
    sequences."""
    s = tokens.shape[1] - 1
    x = params["embed"][tokens[:, :s]]
    counts = []
    for w in params["layers"]:
        x, c = jax.vmap(lambda seq: decoder_layer(w, seq, hp, dt))(x)
        counts.append(jnp.sum(c, axis=0))
    logits = _mm(norm(x, params["final_norm"], hp["rms_norm_eps"]),
                 params["head"], dt)
    return _cross_entropy(logits, tokens[:, 1:s + 1]), counts


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
    [loss], [[counts per layer]])``, a list entry per step."""
    out_loss, out_counts = [], []
    for batch in tokens:
        state, (loss, counts) = _train_step(
            state, jnp.asarray(batch), _Frozen(hp), jnp.dtype(compute_dtype))
        out_loss.append(float(loss))
        out_counts.append([np.asarray(c) for c in counts])
    return state, out_loss, out_counts


# ------------------------------------------------------- the comparison
# What is compared, from the state the window left, over the K check
# steps through the timed path and through ``train_steps``; the numbers
# are the sibling family's (``mla_moe_lm_ref.py`` has each one's
# definition and why no selection can be held to zero against an
# independent reference), without the router bias, which this model has
# not.  Readings: my chip runs of PR 35 at the published widths in bf16:
# 10 seeds sound (seven from step 12, three traced runs from step 6),
# the control's (the reference in the program's place with float8
# operands) and three planted faults' (one held expert's rows zeroed
# behind the grouped matmul; the DeltaNet rule's carried state dropped
# at every chunk's boundary; at one boundary of 256).  Each limit lies
# between the sound readings and the control's, with room on both
# sides; PERF.md section 4 has the table.
#
# ``grad_err_max`` / ``_median`` / ``_all``: per tensor (per held expert
#   for the stacked expert weights) the norm of the difference of the
#   two first-moment changes ``m_K - b1^K m_0`` over the norm of the
#   reference's: the steps' gradients and nothing else.  ``_max``
#   0.100-0.125, always a held expert of layer 2 or 3 for which the two
#   sides selected different tokens (control 1.02; the zeroed expert
#   1.0 on its own tensors); ``_median`` 0.046-0.051 (control 1.0; every
#   state dropped 0.097); ``_all``, all of them as one vector,
#   0.0039-0.0043 (control 0.71; every state dropped 0.023; the zeroed
#   expert 0.0061).
# ``grad_err_mixer_max``: the largest of them over the mixers' own
#   tensors (DeltaNet's and attention's: no expert's selection moves
#   them, so they read lower and are held tighter): 0.0097-0.0225, led
#   by ``a_log`` / ``dt_bias`` (32 numbers a layer, each a sum over
#   16,384 tokens of terms that cancel, which the chunked rule and the
#   token recurrence add in other orders; the matrices read 0.005-0.010,
#   attention's 0.0005-0.0024); control 1.02; every chunk's carried
#   state dropped 0.128.  ONE boundary of 256 dropped reads 0.0114, a
#   sound run's: under the released initialisation (A = U(0, 16)) a
#   state outlives a few tokens in all but one head in a hundred, and no
#   comparison of gradients sees five tokens of 16,384.
# ``update_err``: all parameter updates as one vector; a state left
#   unchanged reads 1.0.  0.0167-0.0178 from step 12, 0.0215-0.0225 from
#   step 6 (control 0.305; the zeroed expert 0.081); the limit has the
#   more room above the readings, since fresh seeds read higher.
# ``loss_err``: the mean loss, relative: <= 5.4e-6 (control 0.0037); the
#   limit is the harness's other cells'.
# ``count_err``: tokens per expert over all experts and layers, summed
#   over the steps: sum |got - want| over sum want, 0.0025-0.0027
#   (control 0.0304).
# ``counter_slack``: exact, the program's own counters against each
#   other (every assignment counted once in ``tokens_per_expert``, once
#   in ``held_assignments + padded_rows``; the held experts' counts add
#   up to ``held_assignments``).
LIMITS = {"grad_err_max": 0.5, "grad_err_mixer_max": 0.08,
          "grad_err_median": 0.2, "grad_err_all": 0.015, "update_err": 0.06,
          "loss_err": 2e-3, "count_err": 0.01, "counter_slack": 0}


def compare(before, got, want, k: int, hp: dict):
    """``before`` / ``got`` / ``want``: ``{"params", "m", "losses",
    "counts"}`` (``before`` without the last two; ``got["counts"]``:
    ``{"tokens_per_expert": [(E,) per layer], "held_assignments": [...],
    "padded_rows": [...]}``, the counters' change over the ``k`` steps).
    Each tensor is brought to the device for its norms and let go.
    Returns ``(ok, report)``."""
    decay = jnp.float32(hp["adam_beta1"] ** k)
    flat = {side: {part: leaves_by_name(tree[part])
                   for part in ("params", "m")}
            for side, tree in (("before", before), ("got", got),
                               ("want", want))}
    grad_err, diff_sq, moved_sq, gdiff_sq, gwant_sq = {}, 0.0, 0.0, 0.0, 0.0
    c_want = np.sum([np.stack(step) for step in want["counts"]],
                    axis=0).astype(np.int64)          # (layers, experts)
    c_got = np.stack(got["counts"]["tokens_per_expert"]).astype(np.int64)
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
        # each held expert; the thin rule is the sibling family's
        layer = int(name.split(".")[1])
        for e, err in enumerate(errs):
            sent = int(c_want[layer, first + e])
            took = int(c_got[layer, first + e])
            if sent < THIN_TOKENS and took != sent:
                thin += 1
                continue
            grad_err[f"{name}.{e}"] = float(err)
    ranked = sorted(grad_err, key=grad_err.get, reverse=True)
    mixers = {n: v for n, v in grad_err.items()
              if ".gdn." in n or ".attn." in n}
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
        "grad_err_mixer_max": max(mixers.values()),
        "grad_err_mixers": {n: round(v, 4) for n, v in mixers.items()},
        "tensors_compared": len(grad_err), "thin_expert_tensors": thin,
        "loss_got": float(loss_got), "loss_want": float(loss_want),
        "held_assignments": [int(x) for x in
                             got["counts"]["held_assignments"]],
    }
    ok = all(report[name] <= limit for name, limit in LIMITS.items())
    return bool(ok), report
