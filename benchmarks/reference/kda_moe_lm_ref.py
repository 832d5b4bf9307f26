"""A Ling-3.0-style hybrid decoder language model as published, plain:
forward, loss, gradients, one Adam step and the router-bias rule in
``jax.numpy`` and float32, and the comparison that decides the
benchmark's ``correct``.  Imports nothing from ``dlrm_flexflow_tpu``.

The model (Ling-3.0-flash's ``config.json``; Kimi Delta Attention is Kimi
Linear's, arXiv:2510.26692 section 3, in the layout of the ``fla``
library's ``KimiDeltaAttention``; the router is DeepSeek-V3's
``noaux_tc`` with groups, arXiv:2412.19437; latent attention without a
query latent is DeepSeek-V2-Lite's, arXiv:2405.04434):

- norm: ``N(x; w) = x / sqrt(mean(x^2) + eps) w`` (``w`` starts at 1);
- published layer ``i``: ``x += mixer(N(x))``, ``x += ffn(N(x))``; the
  mixer is latent attention where ``(i + 1) % layer_group_size == 0``
  and KDA elsewhere; the ffn is a dense SwiGLU for ``i <
  first_k_dense_replace`` and the expert layer after; after the last
  layer a final ``N`` and the untied head;
- KDA, per head of width ``head_dim`` (keys and values alike): ``q =
  unit(silu(conv(x W_q))) / sqrt(head_dim)``, ``k = unit(silu(conv(x
  W_k)))``, ``v = silu(conv(x W_v))``, ``conv`` depthwise and causal
  over ``short_conv_kernel_size`` tokens, ``unit(t) = t / sqrt(sum t^2 +
  1e-6)``; ``g = kda_lower_bound sigmoid(exp(A_log_h) (x W_f +
  dt_bias))``, one log-decay for every key channel; ``beta = sigmoid(x
  W_beta)``; the state ``S`` (dk, dv), ``S_0 = 0``, ONE TOKEN AT A TIME:
  ``S <- Diag(exp(g_t)) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S +
  k_t u^T``; ``o_t = S^T q_t``; then ``o <- o / sqrt(mean(o^2) + eps)
  w_n sigmoid(x W_g)`` per head and ``y = o W_out``;
- latent attention: ``q = x W_q`` (per head ``nope + rope``); ``[c_kv ;
  k_r] = x W_kva``; ``[k_nope ; v] = N(c_kv) W_kvb`` per head; ``q_h <-
  N(q_h; w_qn)``, ``k_h <- N([k_nope_h ; k_r]; w_kn)`` over the head's
  ``nope + rope`` elements; the rotary embedding on interleaved pairs of
  the last ``rope`` elements of every ``q_h`` and ``k_h``; logits ``q_h
  . k_h / sqrt(nope + rope)``, causal, softmax; ``y = concat_h(sigmoid(x
  w_g)_h P_h v_h) W_o``;
- expert layer: ``s = sigmoid(x W_r)`` over all experts; ``c = s + b``;
  the experts lie in ``n_group`` consecutive groups, a group's score is
  the sum of its two largest ``c``, the ``topk_group`` best groups stay;
  the ``num_experts_per_tok`` largest ``c`` inside them are selected;
  ``gate = routed_scaling_factor s / sum of the selected s``; ``y = sum
  over selected experts of gate_i SwiGLU_i(x) + SwiGLU_shared(x)``;
  after a step ``b += gamma sign(mean(c) - c)``, ``c`` the step's tokens
  per expert;
- loss: mean cross-entropy over the positions; Adam (Kingma & Ba,
  arXiv:1412.6980, the form of the end of its section 2).

Departures from the released model, each the configuration's own cut
(``benchmarks/configs/*.json`` lists them under ``reduced`` / ``assumed``):

1. The chip's share of the experts.  ``params`` hold the expert weights
   of the ``held`` experts only, ids ``[first, first + held)``; routing
   is over all ``num_experts``, and the sum runs over the selected
   experts that are held.
2. The chip's share of the heads.  ``params`` hold, of every tensor with
   a head axis, the held heads' part (``W_out`` / ``W_o``: their rows),
   and a mixer's output is the held heads' part of the sum over all
   heads; the latent projection ``W_kva`` and its norm are whole.  What
   the absent experts and heads would add is left out, and that partial
   result goes on to the next layer.
3. Depth and vocabulary are whatever ``params`` hold; a layer's kind
   follows its published index ``first_layer_held + position``.
4. No vision tower, no multi-token prediction, no SwiGLU clamp (the
   published limits are 0 for the layers held), no dropout, no
   auxiliary loss, full sequences without padding or packing.

Arithmetic: float32 throughout at ``highest`` matmul precision, with the
one exception the configuration states: under ``compute_dtype`` bfloat16
every matmul's two operands are rounded to it, with float32 accumulation
(so are the attention probabilities, an operand of ``P v``, and the
rule's q, k and v, which the program hands its chunked form in that
dtype).  The residual stream, norms, the convolution, softmax, router
scores (an f32 matmul of unrounded operands), decays, the KDA state and
its recurrence, the SwiGLU product, the loss and Adam stay f32.  The (S,
S) attention is built a block of rows at a time, the KDA state moves a
token at a time (a ``lax.scan`` in blocks of ``TOKEN_BLOCK`` tokens
under ``jax.checkpoint``), and each expert is a dense FFN over every
token times its gate: no chunked rule, no triangular solve, no sort, no
grouped matmul, no online softmax.  ``jax.checkpoint`` keeps the memory
of the backward pass down and changes no number.

Layout of ``params``::

    {"embed": (V, d), "head": (d, V), "final_norm": (d,),
     "layers": [LAYER, ...]}
    LAYER = {"mixer_norm", "ffn_norm": (d,), then either "kda": {"w_q",
             "w_k", "w_v", "w_f", "w_g": (d, H x 128), "w_beta": (d, H),
             "conv_q", "conv_k", "conv_v": (taps, H x 128), "a_log": (H,),
             "dt_bias": (H x 128,), "norm": (128,), "w_out"} or "mla":
             {"w_q", "w_kva", "kv_norm", "w_kvb", "q_head_norm",
             "k_head_norm": (nope + rope,), "w_gate": (d, H), "w_o"}, and
             either "ffn": {"w_gate", "w_up", "w_down"} or "moe":
             {"router": (d, E), "w_gate", "w_up": (held, d, h), "w_down":
             (held, h, d), "shared": {"w_gate", "w_up", "w_down"}}}

``H`` the heads held.  ``biases``: one ``(E,)`` vector per expert layer.
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
TOKEN_BLOCK = 128   # tokens of the KDA recurrence under one checkpoint


# ------------------------------------------------------------ the model
def _mm(a, w, dt):
    return jnp.matmul(a.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def rope(x, theta):
    """Interleaved-pair rotary embedding of ``x`` (S, H, d) by position
    along axis 0."""
    s, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(angles) - odd * jnp.sin(angles),
                     even * jnp.sin(angles) + odd * jnp.cos(angles)], -1)
    return out.reshape(x.shape)


def swiglu(x, w, dt):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"], dt))
               * _mm(x, w["w_up"], dt), w["w_down"], dt)


def attention(q, k, v, dt):
    """Causal softmax attention of one sequence, ``q``, ``k`` (S, H, dk),
    ``v`` (S, H, dv), the full (H, rows, S) logits of ``ROW_BLOCK``
    rows at a time."""
    s = q.shape[0]
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


def latent_attention(w, x, hp, dt):
    """``x`` (S, d) -> (S, d), the heads ``w`` holds."""
    s = x.shape[0]
    nope, ropew, vd = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                       hp["v_head_dim"])
    eps, latent = hp["rms_norm_eps"], hp["kv_lora_rank"]
    h = w["w_gate"].shape[1]
    q = _mm(x, w["w_q"], dt).reshape(s, h, nope + ropew)
    kva = _mm(x, w["w_kva"], dt)
    kv = _mm(norm(kva[:, :latent], w["kv_norm"], eps), w["w_kvb"],
             dt).reshape(s, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kva[:, None, latent:], (s, h, ropew))], axis=-1)
    q, k = norm(q, w["q_head_norm"], eps), norm(k, w["k_head_norm"], eps)
    turned = lambda t: jnp.concatenate(
        [t[..., :nope], rope(t[..., nope:], hp["rope_theta"])], axis=-1)
    out = attention(turned(q), turned(k), kv[..., nope:], dt)
    out = out * jax.nn.sigmoid(_mm(x, w["w_gate"], dt))[..., None]
    return _mm(out.reshape(s, h * vd), w["w_o"], dt)


def delta_rule(q, k, v, g, beta):
    """The delta rule with a decay per key channel, one token at a time:
    ``q``, ``k``, ``g`` (S, H, dk), ``v`` (S, H, dv), ``beta`` (S, H),
    all f32."""
    s, h, dk = q.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state
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


def kimi_delta_attention(w, x, hp, dt):
    """``x`` (S, d) -> (S, d), the heads ``w`` holds."""
    s = x.shape[0]
    h, hd = w["a_log"].shape[0], hp["head_dim"]

    def conv(t, taps):
        padded = jnp.pad(t, ((taps.shape[0] - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[j:j + s] * taps[j]
                               for j in range(taps.shape[0])))

    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                               keepdims=True) + 1e-6)
    q = unit(conv(_mm(x, w["w_q"], dt), w["conv_q"]).reshape(s, h, hd)) \
        * hd ** -0.5
    k = unit(conv(_mm(x, w["w_k"], dt), w["conv_k"]).reshape(s, h, hd))
    v = conv(_mm(x, w["w_v"], dt), w["conv_v"]).reshape(s, h, hd)
    g = hp["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None]
        * (_mm(x, w["w_f"], dt) + w["dt_bias"]).reshape(s, h, hd))
    beta = jax.nn.sigmoid(_mm(x, w["w_beta"], dt))
    rounded = lambda t: t.astype(dt).astype(jnp.float32)
    o = delta_rule(rounded(q), rounded(k), rounded(v), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + hp["rms_norm_eps"]) * w["norm"] \
        * jax.nn.sigmoid(_mm(x, w["w_g"], dt).reshape(s, h, hd))
    return _mm(o.reshape(s, h * hd), w["w_out"], dt)


def route(w_router, bias, x, hp):
    """``(gates (T, E) f32, zero where not selected; counts (E,))``,
    group by group: explicit ranks, no ``top_k``."""
    scores = jax.nn.sigmoid(jnp.matmul(x, w_router))
    choice = scores + bias
    t, e = choice.shape
    groups, kept = hp["n_group"], hp["topk_group"]
    grouped = jnp.sort(choice.reshape(t, groups, e // groups), axis=-1)
    group_score = grouped[..., -1] + grouped[..., -2]
    # a group stays if fewer than ``kept`` groups score above it
    above = jnp.sum(group_score[:, None, :] > group_score[:, :, None], -1)
    stays = jnp.repeat(above < kept, e // groups, axis=-1)
    choice = jnp.where(stays, choice, -jnp.inf)
    rank = jnp.sum(choice[:, None, :] > choice[:, :, None], axis=-1)
    chosen = ((rank < hp["num_experts_per_tok"]) & stays).astype(jnp.float32)
    picked = scores * chosen
    gates = hp["routed_scaling_factor"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True)
    return gates, jnp.sum(chosen, axis=0).astype(jnp.int32)


def _route_in_rows(w_router, bias, x, hp):
    """``route`` over blocks of ``ROW_BLOCK`` tokens (its ranks are (T,
    E, E) wide)."""
    t = x.shape[0]
    rows = min(ROW_BLOCK, t)
    assert t % rows == 0
    gates, counts = jax.lax.map(
        lambda xs: route(w_router, bias, xs, hp),
        x.reshape(t // rows, rows, -1))
    return gates.reshape(t, -1), jnp.sum(counts, axis=0)


def expert_layer(w, bias, x, hp, dt):
    """``x`` (T, d) -> ``(y (T, d), counts (E,))``: every held expert
    over every token, times its gate, and the shared expert."""
    gates, counts = _route_in_rows(w["router"], jax.lax.stop_gradient(bias),
                                   x, hp)
    first = hp["first_expert_held"]

    @jax.checkpoint
    def one(total, e):
        ffn = {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1)
        return total + gate * swiglu(x, ffn, dt), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(w["w_gate"].shape[0]))
    return y + swiglu(x, w["shared"], dt), counts


def _mixer(w, x, hp, dt):
    inner = norm(x, w["mixer_norm"], hp["rms_norm_eps"])
    if "mla" in w:
        return x + latent_attention(w["mla"], inner, hp, dt)
    return x + kimi_delta_attention(w["kda"], inner, hp, dt)


def _ffn(w, bias, x, hp, dt):
    inner = norm(x, w["ffn_norm"], hp["rms_norm_eps"])
    if "ffn" in w:
        return x + swiglu(inner, w["ffn"], dt), None
    y, counts = expert_layer(w["moe"], bias, inner, hp, dt)
    return x + y, counts


def decoder_layer(w, bias, x, hp, dt):
    """One sequence ``x`` (S, d) through one layer; ``(x, counts or
    None)``."""
    mixer = jax.checkpoint(_mixer, static_argnums=(2, 3))
    ffn = jax.checkpoint(_ffn, static_argnums=(3, 4))
    return ffn(w, bias, mixer(w, x, hp, dt), hp, dt)


def _cross_entropy(logits, targets):
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def losses(params, biases, tokens, hp, dt):
    """``tokens`` (B, S + 1): positions ``[0, S)`` are the inputs, ``[1,
    S]`` the targets; ``hp`` hashable (``_Frozen``).  Returns ``(loss,
    [counts per expert layer])``; a batch's counts are summed over its
    sequences."""
    s = tokens.shape[1] - 1
    x = params["embed"][tokens[:, :s]]
    bias_of = iter(biases)
    counts = []
    for w in params["layers"]:
        bias = next(bias_of) if "moe" in w else None
        x, c = jax.vmap(lambda seq: decoder_layer(w, bias, seq, hp, dt))(x)
        if c is not None:
            counts.append(jnp.sum(c, axis=0))
    logits = _mm(norm(x, params["final_norm"], hp["rms_norm_eps"]),
                 params["head"], dt)
    return _cross_entropy(logits, tokens[:, 1:s + 1]), counts


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def _train_step(state, biases, tokens, hp, dt):
    params, m, v, step = state
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.value_and_grad(losses, has_aux=True)(
            params, biases, tokens, hp, dt)
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
    biases = [b + hp["bias_update_speed"]
              * jnp.sign(jnp.mean(c.astype(jnp.float32)) - c)
              for b, c in zip(biases, counts)]
    return (params, m, v, t), biases, (loss, counts)


def train_steps(state, biases, tokens, hp, compute_dtype="float32"):
    """Adam steps over ``tokens`` (K, B, S + 1), one per leading entry.
    ``state = (params, m, v, step)`` is consumed.  Returns ``(state,
    biases, [loss], [[counts per expert layer]])``, a list entry per
    step."""
    out_loss, out_counts = [], []
    for batch in tokens:
        state, biases, (loss, counts) = _train_step(
            state, biases, jnp.asarray(batch), _Frozen(hp),
            jnp.dtype(compute_dtype))
        out_loss.append(float(loss))
        out_counts.append([np.asarray(c) for c in counts])
    return state, biases, out_loss, out_counts


# ------------------------------------------------------- the comparison
# What is compared, from the state the window left, over the K check
# steps through the timed path and through ``train_steps``; the numbers
# are the sibling families' (``mla_moe_lm_ref.py`` has each one's
# definition and why no selection can be held to zero against an
# independent reference; ``gdn_moe_lm_ref.py`` the mixers' own).
# Readings: my chip runs of PR 37 at the published widths in bf16: the
# sound seeds (from step 24; one traced run from step 12), the control's
# (the reference in the program's place with float8 operands) and two
# planted faults' (``scripts/kda_fault.py``: the KDA rule's decay taken
# as one scalar a head; the router's group mask dropped).  Each limit
# lies between the sound readings and the control's, with room on both
# sides; PERF.md section 4 has the table.
#
# ``grad_err_max`` / ``_median`` / ``_all``: per tensor (per held expert
#   for the stacked expert weights) the norm of the difference of the
#   two first-moment changes ``m_K - b1^K m_0`` over the norm of the
#   reference's: the steps' gradients and nothing else.  ``_max``
#   0.19-0.22, a held expert or a router of layers 3-6 for which the two
#   sides selected different tokens (an even share is 256 tokens over the
#   check; control 1.14; the group mask dropped 0.71); ``_median``
#   0.056-0.061 (control 1.0; the scalar decay 0.73; no mask 0.37);
#   ``_all``, all of them as one vector, 0.012-0.015 (control 0.86; the
#   scalar decay 0.38; no mask 0.055).
# ``grad_err_mixer_max``: the largest of them over the mixers' own
#   tensors (KDA's and latent attention's: no expert's selection moves
#   them directly): 0.060-0.067, led by the decay gate's ``dt_bias`` /
#   ``w_f`` / ``a_log`` of the last KDA layers (the gradient of a decay
#   near the bound is small beside the terms it is the difference of;
#   the other KDA tensors read 0.006-0.026, latent attention's
#   0.002-0.009); control 1.14; the scalar decay 1.15 (every ``w_f`` and
#   ``dt_bias`` 1.0: they get no gradient by channel); no mask 0.26.
# ``update_err``: all parameter updates as one vector; a state left
#   unchanged reads 1.0.  0.022-0.023 from step 24, 0.027 from step 12
#   (control 0.25; the scalar decay 0.29; no mask 0.10); the limit has
#   the more room above the readings, since fresh seeds read higher.
# ``loss_err``: the mean loss, relative: <= 1.9e-5 (control 0.00198, no
#   mask 9.7e-6: the loss is not what finds either); the limit is the
#   harness's other cells'.
# ``count_err``: tokens per expert over all experts and layers, summed
#   over the steps: sum |got - want| over sum want, 0.0061-0.0066
#   (control 0.027; the scalar decay 0.042; no mask 0.054).  Twice the
#   siblings': the group choice is one more discrete step.
# ``bias_err``: of the router-bias entries whose sign the counts settle,
#   the share that differ by more than half a step of ``gamma``.  An
#   expert's mean count here is 128 tokens a step, a whole number that
#   many experts' counts sit on or beside, and one token selected the
#   other way (0.6% of the assignments differ, 1.6 tokens an expert over
#   the two steps) turns ``sign(mean - count)`` there: that is
#   ``count_err``'s to hold, and over every entry it read 0.053-0.084 on
#   sound runs against the control's 0.21.  So an entry is compared
#   where, in every step, the reference's count lies more than
#   ``BIAS_MARGIN`` = 1 token from the mean (2,662-2,702 of the 3,072):
#   0.013-0.019 on three seeds (control 0.152; the group mask dropped
#   0.245).  A bias never updated reads the share of compared entries
#   that moved (the CPU test: over the limit).
# ``counter_slack``: exact, the program's own counters against each
#   other (every assignment counted once in ``tokens_per_expert``, once
#   in ``held_assignments + padded_rows``; the held experts' counts add
#   up to ``held_assignments``).  (The control read 1: float8 layers hand
#   the reference's rank-based selection two equal scores.)
BIAS_MARGIN = 1     # tokens
LIMITS = {"grad_err_max": 0.5, "grad_err_mixer_max": 0.2,
          "grad_err_median": 0.2, "grad_err_all": 0.04, "update_err": 0.08,
          "loss_err": 2e-3, "count_err": 0.015, "bias_err": 0.06,
          "counter_slack": 0}


def compare(before, got, want, k: int, hp: dict):
    """``before`` / ``got`` / ``want``: ``{"params", "m", "biases",
    "losses", "counts"}`` (``before`` without the last two;
    ``got["counts"]``: ``{"tokens_per_expert": [(E,) per expert layer],
    "held_assignments": [...], "padded_rows": [...]}``, the counters'
    change over the ``k`` steps).  Each tensor is brought to the device
    for its norms and let go.  Returns ``(ok, report)``."""
    decay = jnp.float32(hp["adam_beta1"] ** k)
    flat = {side: {part: leaves_by_name(tree[part])
                   for part in ("params", "m")}
            for side, tree in (("before", before), ("got", got),
                               ("want", want))}
    grad_err, diff_sq, moved_sq, gdiff_sq, gwant_sq = {}, 0.0, 0.0, 0.0, 0.0
    c_want = np.sum([np.stack(step) for step in want["counts"]],
                    axis=0).astype(np.int64)     # (expert layers, experts)
    c_got = np.stack(got["counts"]["tokens_per_expert"]).astype(np.int64)
    first, held = hp["first_expert_held"], hp["experts_held"]
    per_step = hp["num_experts_per_tok"] * hp["tokens_per_step"] * k
    layer_of = {f"layers.{i}.moe": n for n, i in enumerate(
        i for i, w in enumerate(before["params"]["layers"]) if "moe" in w)}
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
        layer = layer_of[name.rsplit(".", 1)[0]]
        for e, err in enumerate(errs):
            sent = int(c_want[layer, first + e])
            took = int(c_got[layer, first + e])
            if sent < THIN_TOKENS and took != sent:
                thin += 1
                continue
            grad_err[f"{name}.{e}"] = float(err)
    ranked = sorted(grad_err, key=grad_err.get, reverse=True)
    mixers = {n: v for n, v in grad_err.items()
              if ".kda." in n or ".mla." in n}
    loss_got, loss_want = np.mean(got["losses"]), np.mean(want["losses"])
    gamma = hp["bias_update_speed"]
    b_got, b_want = np.stack(got["biases"]), np.stack(want["biases"])
    by_step = np.stack([np.stack(step) for step in want["counts"]])
    settled = np.all(np.abs(by_step - per_step / k / c_want.shape[1])
                     > BIAS_MARGIN, axis=0)       # (expert layers, experts)
    bias_off = (np.abs(b_got - b_want) > gamma / 2)[settled]
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
        "bias_err": float(np.mean(bias_off)) if bias_off.size else 0.0,
        "bias_entries_compared": int(bias_off.size),
        "counter_slack": int(slack),
        "grad_err_all": float(np.sqrt(gdiff_sq / max(gwant_sq, 1e-60))),
        "grad_err_q90": float(np.quantile(list(grad_err.values()), 0.9)),
        "grad_worst_five": [[n, round(grad_err[n], 4)] for n in ranked[:5]],
        "grad_err_mixer_max": max(mixers.values()),
        "grad_worst_mixer": max(mixers, key=mixers.get),
        "grad_err_mixers": {n: round(v, 4) for n, v in mixers.items()},
        "tensors_compared": len(grad_err), "thin_expert_tensors": thin,
        "loss_got": float(loss_got), "loss_want": float(loss_want),
        "held_assignments": [int(x) for x in
                             got["counts"]["held_assignments"]],
    }
    ok = all(report[name] <= limit for name, limit in LIMITS.items())
    return bool(ok), report
