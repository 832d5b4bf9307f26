"""The Gated DeltaNet mixer: a linear-attention layer with a per-head
matrix state carried along the sequence under a gated delta rule
(Yang, Kautz & Hatamizadeh, arXiv:2412.06464; the layout of the
released Qwen3-Next code), computed in chunks.

No reference analogue (the reference's one recurrence is NMT's LSTM,
``ops/rnn.py``, one step a token).  Per value head, with the state
``S`` (dk, dv) f32, ``S_0 = 0``, for each token ``t``::

    S <- exp(g_t) S                      g_t <= 0, the log-decay
    u  = beta_t (v_t - S^T k_t)          the delta rule's correction
    S <- S + k_t u^T
    o_t = S^T q_t

One step a token is 16,384 dependent steps of two rank-one products:
no program for an MXU.  ``gated_delta_rule`` computes the same function
a chunk of ``CHUNK`` tokens at a time (the WY / UT form).  With
``gamma_i`` the log-decays summed from the chunk's start through token
``i``, ``A_ij = beta_i exp(gamma_i - gamma_j) k_i.k_j`` for ``j < i``
and ``T = (I + A)^-1`` (unit lower triangular, ``unit_lower_inverse``),
the chunk's corrections are ``U = T (beta v) - T (beta exp(gamma) k)
S_0``, its outputs ``o_i = exp(gamma_i) q_i S_0 + sum_{j<=i}
exp(gamma_i - gamma_j) (q_i.k_j) u_j`` and the state it leaves ``S_C =
exp(gamma_C) S_0 + sum_j exp(gamma_C - gamma_j) k_j u_j^T``: all
matmuls.  What does not depend on the state (``T``, the two products
with it, the masked ``q k^T``) is computed for every chunk at once;
what does is a ``lax.scan`` over the chunks with four small matmuls a
chunk, every head at once.

The backward pass is written by hand (``jax.custom_vjp``): the forward
keeps its inputs and the state at every chunk's start (N x H x dk x dv
f32: 0.54 GB a layer at 16,384 tokens and 32 heads, never one state a
token), the backward computes the chunks' state-free operands again,
walks the chunks in reverse carrying the state's cotangent, and pulls
the operands' cotangents back through their ``jax.vjp``.

Arithmetic: decays, ``T``, the state and every accumulator in f32; the
matmul operands (``q``, ``k``, ``v``, ``T``, the state where it is
multiplied) in the compute dtype, f32 accumulation.  Every exponent is
of a difference ``gamma_i - gamma_j <= 0``, so nothing overflows
whatever the decays.

Two forms, one entry (``gated_delta_rule``), chosen by ``core_form``
from the backend and the shapes alone.  On a TPU, with the sequence
whole blocks of ``pallas_deltanet.BLOCK`` tokens and head widths that
are whole lane tiles (128 in the language model): one Pallas kernel
forward and one backward (``ops/pallas_deltanet.py``), the same
algorithm at the same chunk and with the same arithmetic, a chunk's
intermediates in VMEM, q, k and v read where they lie; it keeps between
its passes what this form keeps.  Anywhere else (the CPU, a 29-token
sequence, 24-wide heads): the chunked form below, ``jax.numpy`` under a
``lax.scan``, which is also the kernels' reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..initializers import (ConstantInitializer, DEFAULT_KERNEL_INIT,
                            Initializer, UniformInitializer)
from ..tensor import ParameterSpec
from . import pallas_deltanet
from .base import Op, held_heads, matmul

#: tokens of one chunk (a power of two): the released kernels' size.
#: v5e, 16 key heads on 32 value heads x 16,384 tokens x 128, bf16,
#: forward / forward + backward ms (``scripts/ab_lm_kernels.py gdn``,
#: PR 35): 64: 23.64 / 52.78; 32: 19.26 / 55.40; 128: 28.86 / 58.48;
#: with ``T`` by ``solve_triangular`` 21.50 / 62.68; the same chunks
#: differentiated by JAX 23.68 / 53.09 (the hand-written backward buys
#: what is kept between the passes, not time); the Pallas kernels that run
#: there since PR 36, at this chunk: 11.04 / 25.35
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def core_form(seq: int, dk: int, dv: int, dtype) -> str:
    """Which form ``gated_delta_rule`` runs at these shapes: ``"pallas"``
    on a TPU where the kernels take them, else ``"chunked"``.  Decided
    from what the trace can see, and by nothing else (the model's
    ``program`` events count it)."""
    fused = _on_tpu() and pallas_deltanet.takes(seq, dk, dv, dtype)
    return "pallas" if fused else "chunked"


# ------------------------------------------- (I + A)^-1, unit lower triangular
def _block_mm(x, y):
    """Batched (..., b, b) x (..., b, b) in f32: a broadcast product and
    a sum under 16 rows (no MXU tile to fill), a matmul at full
    precision from there."""
    if x.shape[-1] < 16:
        return jnp.sum(x[..., :, :, None] * y[..., None, :, :], axis=-2)
    return jnp.matmul(x, y, precision=_HIGHEST)


def _unit_lower_inverse(a):
    n = a.shape[-1]
    assert n & (n - 1) == 0, f"chunk {n} is no power of two"
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), jnp.float32)    # n blocks of 1 x 1
    b = 1
    while b < n:
        m = n // (2 * b)
        blocks = a.reshape(lead + (m, 2 * b, m, 2 * b))
        diag = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
        low = diag[..., b:, :b]                       # (..., m, b, b)
        pair = inv.reshape(lead + (m, 2, b, b))
        first, second = pair[..., 0, :, :], pair[..., 1, :, :]
        corner = -_block_mm(_block_mm(second, low), first)
        top = jnp.concatenate([first, jnp.zeros_like(first)], axis=-1)
        bottom = jnp.concatenate([corner, second], axis=-1)
        inv = jnp.concatenate([top, bottom], axis=-2)  # (..., m, 2b, 2b)
        b *= 2
    return inv.reshape(a.shape)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., n, n), n
    a power of two, in f32, exactly as forward substitution would give
    it: the inverse of a 2b-block from its two b-blocks, ``[[P, 0], [-R
    L P, R]]`` for ``[[P^-1, 0], [L, R^-1]]``, doubling from 1.  (The
    product form ``(I - a)(I + a^2)(I + a^4)...`` has as many matmuls
    and powers of ``a`` that can grow like binomials before they
    cancel.)  Differentiated: ``-T^T dT T^T`` from the inverse alone."""
    return _unit_lower_inverse(a)


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                     precision=_HIGHEST)
    n = t.shape[-1]
    strict = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]
    return (jnp.where(strict, da, 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


# --------------------------------------------------------- the chunked rule
def _mm(spec, x, y):
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _chunk_operands(q, k, v, g, beta, cd):
    """What a chunk needs that does not depend on the state, for every
    chunk at once.  ``q``, ``k`` (N, B, H, C, dk), ``v`` (N, B, H, C,
    dv), ``g``, ``beta`` (N, B, H, C); returns ``(qd, p, kd, carry,
    w_k, w_v)``: ``q exp(gamma)``, the masked ``q k^T`` with its decays,
    ``k exp(gamma_C - gamma)``, ``exp(gamma_C)`` (N, B, H, 1), ``T (beta
    exp(gamma) k)`` and ``T (beta v)``; the matmul operands in ``cd``,
    ``carry`` and ``w_v`` in f32."""
    c, dv = q.shape[-2], v.shape[-1]
    gamma = jnp.cumsum(g, axis=-1)
    at = jnp.arange(c)
    lower = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    qc, kc = q.astype(cd), k.astype(cd)
    kk = _mm("...id,...jd->...ij", kc, kc)
    a = jnp.where(at[:, None] > at[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    t = unit_lower_inverse(a)
    rhs = jnp.concatenate([v * beta[..., None],
                           k * (beta * jnp.exp(gamma))[..., None]], axis=-1)
    w = _mm("...ij,...jd->...id", t.astype(cd), rhs.astype(cd))
    p = _mm("...id,...jd->...ij", qc, kc) * decay
    total = gamma[..., -1]
    qd = q * jnp.exp(gamma)[..., None]
    kd = k * jnp.exp(total[..., None] - gamma)[..., None]
    return (qd.astype(cd), p.astype(cd), kd.astype(cd),
            jnp.exp(total)[..., None], w[..., dv:].astype(cd), w[..., :dv])


def _scan_chunks(operands, cd):
    """The part that depends on the state: ``(o (N, B, H, C, dv) f32,
    the state at every chunk's start (N, B, H, dk, dv) f32)``.
    ``carry``, what the chunk's decays leave of the state it found, is
    (N, B, H, 1) for one decay a head and (N, B, H, dk) for one a key
    channel (a row of the state)."""
    qd, _p, _kd, _carry, _w_k, w_v = operands
    state = jnp.zeros(qd.shape[1:3] + (qd.shape[-1], w_v.shape[-1]),
                      jnp.float32)

    def chunk(s, xs):
        qd, p, kd, carry, w_k, w_v = xs
        sc = s.astype(cd)
        u = (w_v - _mm("...cd,...de->...ce", w_k, sc)).astype(cd)
        o = _mm("...cd,...de->...ce", qd, sc) \
            + _mm("...ij,...je->...ie", p, u)
        nxt = carry[..., None] * s + _mm("...cd,...ce->...de", kd, u)
        return nxt, (o, s)

    _, (o, starts) = jax.lax.scan(chunk, state, operands)
    return o, starts


def _scan_chunks_bwd(operands, starts, do, cd):
    """The cotangents of ``_chunk_operands``' results from ``do``: the
    chunks in reverse, the state's cotangent carried, each chunk's
    corrections computed again from the state at its start."""
    def chunk(ds, xs):
        (qd, p, kd, carry, w_k, w_v), s, do = xs
        sc, dsc, doc = s.astype(cd), ds.astype(cd), do.astype(cd)
        u = (w_v - _mm("...cd,...de->...ce", w_k, sc)).astype(cd)
        du = _mm("...ij,...ie->...je", p, doc) \
            + _mm("...cd,...de->...ce", kd, dsc)
        duc = du.astype(cd)
        grads = (_mm("...ce,...de->...cd", doc, sc).astype(qd.dtype),
                 _mm("...ie,...je->...ij", doc, u).astype(p.dtype),
                 _mm("...ce,...de->...cd", u, dsc).astype(kd.dtype),
                 jnp.sum((ds * s).reshape(carry.shape + (-1,)), axis=-1),
                 (-_mm("...ce,...de->...cd", duc, sc)).astype(w_k.dtype),
                 du)
        before = _mm("...cd,...ce->...de", qd, doc) \
            + carry[..., None] * ds \
            - _mm("...cd,...ce->...de", w_k, duc)
        return before, grads

    _, grads = jax.lax.scan(chunk, jnp.zeros_like(starts[0]),
                            (operands, starts, do), reverse=True)
    return grads


def _chunks_first(x, chunk: int, repeat: int = 1):
    """(B, S, H, ...) -> (N, B, H * repeat, C, ...)."""
    b, s, h = x.shape[:3]
    x = x.reshape((b, s // chunk, chunk, h) + x.shape[3:])
    x = jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)     # (N, B, H, C, ...)
    return jnp.repeat(x, repeat, axis=2) if repeat > 1 else x


def _laid_out(q, k, v, g, beta, chunk):
    group = v.shape[2] // q.shape[2]
    return (_chunks_first(q, chunk, group), _chunks_first(k, chunk, group),
            _chunks_first(v, chunk), _chunks_first(g, chunk),
            _chunks_first(beta, chunk))


def _tokens_first(o):
    """(N, B, H, C, dv) -> (B, S, H, dv)."""
    n, b, h, c, dv = o.shape
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * c, h, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chunked_rule(q, k, v, g, beta, chunk, cd, operands_of):
    """The chunked form of either rule: ``operands_of`` is
    ``_chunk_operands`` (``g`` (B, S, H), one decay a head) or
    ``_channel_chunk_operands`` (``g`` (B, S, H, dk), one a key
    channel)."""
    operands = operands_of(*_laid_out(q, k, v, g, beta, chunk), cd)
    return _tokens_first(_scan_chunks(operands, cd)[0])


def _chunked_rule_fwd(q, k, v, g, beta, chunk, cd, operands_of):
    operands = operands_of(*_laid_out(q, k, v, g, beta, chunk), cd)
    o, starts = _scan_chunks(operands, cd)
    return _tokens_first(o), (q, k, v, g, beta, starts)


def _chunked_rule_bwd(chunk, cd, operands_of, res, do):
    q, k, v, g, beta, starts = res
    operands, pull = jax.vjp(
        lambda *xs: operands_of(*_laid_out(*xs, chunk), cd),
        q, k, v, g, beta)
    do = _chunks_first(do.astype(jnp.float32), chunk)
    return pull(_scan_chunks_bwd(operands, starts, do, cd))


_chunked_rule.defvjp(_chunked_rule_fwd, _chunked_rule_bwd)


def _in_whole_chunks(q, k, v, g, beta, cd, operands_of):
    """``_chunked_rule`` over sequences of any length: one the chunk
    does not divide is padded behind its end with tokens that change
    nothing (``beta`` 0, ``g`` 0)."""
    s = q.shape[1]
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad))
                                    + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    o = _chunked_rule(q.astype(cd), k.astype(cd), v.astype(cd), g, beta,
                      CHUNK, cd, operands_of)
    return o[:, :s] if pad else o


@jax.custom_vjp
def _pallas_rule(q, k, v, g, beta):
    return pallas_deltanet.forward(q, k, v, g, beta)[0]


def _pallas_rule_fwd(q, k, v, g, beta):
    o, starts = pallas_deltanet.forward(q, k, v, g, beta)
    return o, (q, k, v, g, beta, starts)


def _pallas_rule_bwd(res, do):
    return pallas_deltanet.backward(*res, do.astype(jnp.float32))


_pallas_rule.defvjp(_pallas_rule_fwd, _pallas_rule_bwd)


def gated_delta_rule(q, k, v, g, beta, compute_dtype=None):
    """The gated delta rule over whole sequences, in chunks of
    ``CHUNK``.  ``q``, ``k``: (B, S, Hk, dk), already normalised and
    scaled; ``v``: (B, S, Hv, dv), ``Hk`` dividing ``Hv`` (value head
    ``h`` reads key head ``h // (Hv / Hk)``); ``g`` (log-decay, <= 0)
    and ``beta``: (B, S, Hv), f32.  Returns (B, S, Hv, dv) f32.  Where
    ``core_form`` says ``"pallas"`` the two kernels of
    ``ops/pallas_deltanet.py`` run; else the chunked form, in which a
    sequence the chunk does not divide is padded behind its end with
    tokens that change nothing (``beta`` 0, ``g`` 0)."""
    cd = jnp.dtype(compute_dtype or jnp.float32)
    s = q.shape[1]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if core_form(s, q.shape[3], v.shape[3], cd) == "pallas":
        return _pallas_rule(q.astype(cd), k.astype(cd), v.astype(cd), g,
                            beta)
    return _in_whole_chunks(q, k, v, g, beta, cd, _chunk_operands)


# ------------------------------------------- one decay a key channel (KDA)
#: tokens of one sub-block of a chunk in ``kimi_delta_rule``.  With a
#: decay per channel the pair's decay ``exp(Gamma_i - Gamma_j)`` lies
#: inside the dot product over the channels, so it has to be split into
#: a factor on row ``i`` and a factor on column ``j`` around a point of
#: reference ``R``: ``exp(Gamma_i - R) exp(R - Gamma_j)``.  One point
#: for a 64-token chunk would need ``exp(5 x 64)``; each sub-block of
#: rows takes its own, the decays summed through its middle token.  A
#: row's factor then lies in ``e^+-40`` (8 tokens of at most 5 nats), a
#: column's inside the sub-block too, and an earlier column's is below 1;
#: a pair above the diagonal, masked afterwards, reaches ``e^80`` a
#: channel and ``128 e^80 = e^85`` a sum, inside f32's and bf16's range
#: (``e^88.7``).  Every pair that counts keeps both factors far from the
#: denormals, where the sub-block's START as the point would flush a
#: row's ``e^-80 q`` at the bound (relative error 1e-4 there, 1e-6 so).
#: 32 tokens would need a bound of -2.5 a token
SUB = 16
#: the lowest log-decay a token and channel ``kimi_delta_rule`` takes
DECAY_FLOOR = -80.0 / SUB


def _channel_chunk_operands(q, k, v, g, beta, cd):
    """``_chunk_operands`` for one decay a key channel: ``g`` (N, B, H,
    C, dk) in ``[DECAY_FLOOR, 0]``.  The same six operands, ``carry``
    (N, B, H, dk); ``A`` and the masked ``q k^T`` hold their decays
    inside the sum over the channels, sub-block by sub-block (``SUB``
    has why): one product of the rows ``[q ; k] exp(Gamma - R_a)``, a
    sub-block a batch entry, with the columns ``k exp(R_a - Gamma_j)``
    up to the sub-block's end, ``R_a`` the decays summed through the
    sub-block's middle token."""
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    lead = q.shape[:-2]
    sub = min(SUB, c)
    m = c // sub
    gamma = jnp.cumsum(g, axis=-2)                      # (..., C, dk)
    blocks = lambda x: x.reshape(lead + (m, sub) + x.shape[-1:])
    ref = blocks(gamma)[..., sub // 2 - 1, :]           # (..., m, dk)
    own = jnp.exp(blocks(gamma) - ref[..., None, :])    # rows: e^+-40
    at = jnp.arange(c)
    upto = at[None, :] < ((jnp.arange(m) + 1) * sub)[:, None]     # (m, C)
    cols = k[..., None, :, :] * jnp.exp(jnp.where(
        upto[..., None], ref[..., :, None, :] - gamma[..., None, :, :],
        -jnp.inf))                                      # (..., m, C, dk)
    rows = jnp.concatenate([blocks(q) * own, blocks(k) * own], axis=-2)
    # f32 operands at full precision, whatever the compute dtype: a
    # token's decay is a row's factor of every later pair and a column's
    # of every earlier one, and in d(loss)/dg the two cancel for every
    # pair that does not straddle the token.  They cancel only if both
    # paths multiply the same numbers; with the factors rounded to bf16
    # between the exponential and the product they do not, and what is
    # left, 2^-9 of every pair behind the token, drowns a gradient that
    # decays of e^-4 a token make small (a_log read 8.7 times its own
    # norm off the recurrence's, w_f 1.0).  4.3 GFLOP a layer forward at
    # 16 heads x 8,192 tokens: nothing beside the projections
    pairs = jnp.einsum("...aic,...ajc->...aij", rows, cols,
                       precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
    p = jnp.where(at[:, None] >= at[None, :],
                  pairs[..., :sub, :].reshape(lead + (c, c)), 0.0)
    a = jnp.where(at[:, None] > at[None, :],
                  beta[..., :, None]
                  * pairs[..., sub:, :].reshape(lead + (c, c)), 0.0)
    t = unit_lower_inverse(a)
    rhs = jnp.concatenate([v * beta[..., None],
                           k * beta[..., None] * jnp.exp(gamma)], axis=-1)
    w = _mm("...ij,...jd->...id", t.astype(cd), rhs.astype(cd))
    total = gamma[..., -1:, :]                          # (..., 1, dk)
    qd = q * jnp.exp(gamma)
    kd = k * jnp.exp(total - gamma)
    return (qd.astype(cd), p.astype(cd), kd.astype(cd),
            jnp.exp(total[..., 0, :]), w[..., dv:].astype(cd), w[..., :dv])


def kimi_delta_rule(q, k, v, g, beta, compute_dtype=None):
    """The delta rule with a decay for every key channel (Kimi Delta
    Attention: Kimi Linear, arXiv:2510.26692 section 3), over whole
    sequences, in chunks of ``CHUNK``.  Per head, the state ``S`` (dk,
    dv) f32, ``S_0 = 0``::

        S <- Diag(exp(g_t)) S               g_t (dk,) in [DECAY_FLOOR, 0]
        u  = beta_t (v_t - S^T k_t)
        S <- S + k_t u^T
        o_t = S^T q_t

    ``q``, ``k``: (B, S, H, dk), already normalised and scaled; ``v``:
    (B, S, H, dv); ``g``: (B, S, H, dk) f32; ``beta``: (B, S, H) f32.
    Returns (B, S, H, dv) f32.  ``gated_delta_rule``'s chunked form (its
    layout, its walk over the chunks, its hand-written backward that
    keeps the inputs and the state at each chunk's start) with the
    operands of ``_channel_chunk_operands``; with ``g`` constant over
    the channels it is that function.  A sequence the chunk does not
    divide is padded behind its end with tokens that change nothing
    (``beta`` 0, ``g`` 0).  There is one form, ``"chunked"``, on every
    backend (``KimiDeltaAttention.core_form``)."""
    cd = jnp.dtype(compute_dtype or jnp.float32)
    return _in_whole_chunks(q, k, v, g.astype(jnp.float32),
                            beta.astype(jnp.float32), cd,
                            _channel_chunk_operands)


# --------------------------------------------------------------- the mixer
def causal_conv(x, w):
    """Depthwise causal convolution along axis 1: ``y_t = sum_j w[j]
    x_(t - K + 1 + j)`` for ``x`` (B, S, channels), ``w`` (K, channels),
    zeros before the sequence's start."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[j] for j in range(taps))


def l2_normalised(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


class _LogUniform(Initializer):
    """``log(U(0, high))``: the released code's ``A_log``."""

    def __init__(self, high: float):
        self.high = float(high)

    def __call__(self, key, shape, dtype=jnp.float32):
        tiny = float(jnp.finfo(jnp.float32).tiny)
        return jnp.log(jax.random.uniform(key, shape, dtype, minval=tiny,
                                          maxval=self.high))


class GatedDeltaNet(Op):
    """The Gated DeltaNet mixer of Qwen3-Next (``Qwen3NextGatedDeltaNet``
    of Hugging Face's ``modeling_qwen3_next.py``): (B, S, d) -> (B, S,
    d), causal, no biases.

    ``[q | k | v | z] = x W_qkvz`` with ``num_k_heads x head_k_dim`` (q,
    k) and ``num_v_heads x head_v_dim`` (v, z); ``[b | a] = x W_ba``,
    ``num_v_heads`` each.  ``[q | k | v] <- silu(causalconv([q | k |
    v]))``, depthwise, ``conv_kernel`` taps.  ``beta = sigmoid(b)``; ``g
    = -exp(A_log) softplus(a + dt_bias)``.  q and k L2-normalised per
    head, q times ``head_k_dim^-1/2``; the gated delta rule
    (``gated_delta_rule``); per head ``o <- o / sqrt(mean(o^2) + eps)
    w_n silu(z)``; ``y = o W_out``.  (The released projection interleaves
    its columns by key head; here they lie q, k, v, z: the same layer
    under a permutation of ``W_qkvz``'s columns.)

    Scopes (the prefix is the op's ``phase``, ``ff.gdn`` without one):
    ``.proj`` (both input projections, ``W_out``), ``.conv``, ``.core``
    (normalisation of q and k, decays, the rule in the form
    ``core_form`` names), ``.gate``.
    """

    op_type = "GatedDeltaNet"
    core_field, core_forms = "gdn_core", ("pallas", "chunked")

    def __init__(self, name, input_tensor, num_k_heads: int,
                 num_v_heads: int, head_k_dim: int, head_v_dim: int,
                 conv_kernel: int = 4, eps: float = 1e-6,
                 kernel_initializer=None, compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.hk, self.hv = int(num_k_heads), int(num_v_heads)
        assert self.hv % self.hk == 0
        self.dk, self.dv = int(head_k_dim), int(head_v_dim)
        self.conv_kernel, self.eps = int(conv_kernel), float(eps)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    @property
    def _conv_dim(self):
        return 2 * self.hk * self.dk + self.hv * self.dv

    def param_specs(self):
        d, init = self.model_dim, self.kernel_initializer
        value = self.hv * self.dv
        bound = 1.0 / math.sqrt(self.conv_kernel)   # torch's Conv1d default
        return [
            ParameterSpec(self.name, "w_qkvz", (d, self._conv_dim + value),
                          initializer=init, sharded_dim=1),
            ParameterSpec(self.name, "w_ba", (d, 2 * self.hv),
                          initializer=init, sharded_dim=1),
            ParameterSpec(self.name, "conv",
                          (self.conv_kernel, self._conv_dim),
                          initializer=UniformInitializer(-bound, bound)),
            ParameterSpec(self.name, "a_log", (self.hv,),
                          initializer=_LogUniform(16.0)),
            ParameterSpec(self.name, "dt_bias", (self.hv,),
                          initializer=ConstantInitializer(1.0)),
            ParameterSpec(self.name, "norm", (self.dv,),
                          initializer=ConstantInitializer(1.0)),
            ParameterSpec(self.name, "w_out", (value, d), initializer=init,
                          sharded_dim=0)]

    @property
    def _cd(self):
        return (jnp.bfloat16 if self.compute_dtype in ("bfloat16",
                                                       jnp.bfloat16)
                else jnp.float32)

    def core_form(self) -> str:
        """The form this op's rule takes on this backend
        (``ops/deltanet.py::core_form`` at its shapes)."""
        return core_form(self.inputs[0].shape[1], self.dk, self.dv, self._cd)

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        hk, hv, dk, dv = self.hk, self.hv, self.dk, self.dv
        cdt, cd = self.compute_dtype, self._cd
        scope = self.phase or "ff.gdn"
        conv_dim = self._conv_dim
        with jax.named_scope(scope + ".proj"):
            # two products, so that no (S, 12288) array is sliced
            qkv = matmul(x, params["w_qkvz"][:, :conv_dim], cdt)
            z = matmul(x, params["w_qkvz"][:, conv_dim:], cdt)
            ba = matmul(x, params["w_ba"], cdt)

        @jax.checkpoint   # keeps qkv alone of its (S, 8192) f32 arrays
        def heads(qkv, conv):
            with jax.named_scope(scope + ".conv"):
                mixed = jax.nn.silu(causal_conv(qkv, conv))
            with jax.named_scope(scope + ".core"):
                q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
                k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
                v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
                return ((l2_normalised(q) * dk ** -0.5).astype(cd),
                        l2_normalised(k).astype(cd), v.astype(cd))

        q, k, v = heads(qkv, params["conv"])
        with jax.named_scope(scope + ".core"):
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(params["a_log"]) * jax.nn.softplus(
                ba[..., hv:] + params["dt_bias"])
            o = gated_delta_rule(q, k, v, g, beta, cd)
        with jax.named_scope(scope + ".gate"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + self.eps)
            o = o * params["norm"] * jax.nn.silu(z.reshape(b, s, hv, dv))
        with jax.named_scope(scope + ".proj"):
            out = matmul(o.reshape(b, s, hv * dv), params["w_out"], cdt)
        return [out.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        s, d = self.inputs[0].shape[1], self.model_dim
        value = self.hv * self.dv
        proj = d * (self._conv_dim + value + 2 * self.hv) + value * d
        core = 3 * self.hv * self.dk * self.dv     # 6 x dk x dv a token, / 2
        return batch * s * 2 * (proj + core)


class KimiDeltaAttention(Op):
    """The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692
    section 3; the layout of the ``fla`` library's
    ``KimiDeltaAttention`` with full-rank gates): (B, S, d) -> (B, S,
    d), causal, no biases.

    Per head ``h`` of ``num_heads``, ``head_k_dim`` wide for q and k and
    ``head_v_dim`` for v: ``q = l2norm(silu(conv(x W_q))) /
    sqrt(head_k_dim)``, ``k = l2norm(silu(conv(x W_k)))``, ``v =
    silu(conv(x W_v))``, each convolution depthwise and causal over
    ``conv_kernel`` tokens; the log-decay of every key channel ``g =
    lower_bound sigmoid(exp(A_log_h) (x W_f + dt_bias))``, in
    ``(lower_bound, 0)``; ``beta = sigmoid(x W_beta)``; the delta rule
    with a decay per channel (``kimi_delta_rule``); ``o <- o /
    sqrt(mean(o^2) + eps) w_n sigmoid(x W_g)``; ``y = o W_out``.

    ``heads_held``: this op holds that many of the heads (tensor
    parallelism over the heads, without its all-reduce):
    every parameter with a head axis has the held heads' part alone,
    ``W_out`` their rows, and the output is their part of the sum over
    all heads.  What the absent heads would add is left out.

    Scopes as ``GatedDeltaNet``'s (``ff.kda`` without a phase):
    ``.proj``, ``.conv``, ``.core`` (normalisation of q and k, both
    gates, the rule), ``.gate``.
    """

    op_type = "KimiDeltaAttention"
    core_field, core_forms = "kda_core", ("chunked",)

    def __init__(self, name, input_tensor, num_heads: int, head_k_dim: int,
                 head_v_dim: int, conv_kernel: int = 4,
                 lower_bound: float = -5.0, eps: float = 1e-6,
                 heads_held=None, kernel_initializer=None,
                 compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.heads = held_heads(heads_held, int(num_heads))
        self.dk, self.dv = int(head_k_dim), int(head_v_dim)
        self.conv_kernel, self.eps = int(conv_kernel), float(eps)
        self.lower_bound = float(lower_bound)
        assert DECAY_FLOOR <= self.lower_bound < 0, \
            f"kimi_delta_rule takes log-decays down to {DECAY_FLOOR}"
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        d, init = self.model_dim, self.kernel_initializer
        key, value = self.heads * self.dk, self.heads * self.dv
        bound = 1.0 / math.sqrt(self.conv_kernel)   # torch's Conv1d default
        conv = UniformInitializer(-bound, bound)
        wide = lambda name, n: ParameterSpec(self.name, name, (d, n),
                                             initializer=init, sharded_dim=1)
        taps = lambda name, n: ParameterSpec(
            self.name, name, (self.conv_kernel, n), initializer=conv)
        return [
            wide("w_q", key), wide("w_k", key), wide("w_v", value),
            wide("w_f", key), wide("w_g", value), wide("w_beta", self.heads),
            taps("conv_q", key), taps("conv_k", key), taps("conv_v", value),
            ParameterSpec(self.name, "a_log", (self.heads,),
                          initializer=_LogUniform(16.0)),
            ParameterSpec(self.name, "dt_bias", (key,),
                          initializer=ConstantInitializer(1.0)),
            ParameterSpec(self.name, "norm", (self.dv,),
                          initializer=ConstantInitializer(1.0)),
            ParameterSpec(self.name, "w_out", (value, d), initializer=init,
                          sharded_dim=0)]

    _cd = GatedDeltaNet._cd          # reads compute_dtype alone

    def core_form(self) -> str:
        return "chunked"

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        h, dk, dv = self.heads, self.dk, self.dv
        cdt, cd = self.compute_dtype, self._cd
        scope = self.phase or "ff.kda"
        with jax.named_scope(scope + ".proj"):
            q, k, v, f, z, beta = (matmul(x, params[name], cdt) for name in
                                   ("w_q", "w_k", "w_v", "w_f", "w_g",
                                    "w_beta"))

        @jax.checkpoint   # keeps the three projections alone
        def heads(q, k, v, conv_q, conv_k, conv_v):
            with jax.named_scope(scope + ".conv"):
                q, k, v = (jax.nn.silu(causal_conv(t, w)) for t, w in
                           ((q, conv_q), (k, conv_k), (v, conv_v)))
            with jax.named_scope(scope + ".core"):
                q = l2_normalised(q.reshape(b, s, h, dk)) * dk ** -0.5
                k = l2_normalised(k.reshape(b, s, h, dk))
                return q.astype(cd), k.astype(cd), \
                    v.reshape(b, s, h, dv).astype(cd)

        q, k, v = heads(q, k, v, params["conv_q"], params["conv_k"],
                        params["conv_v"])
        with jax.named_scope(scope + ".core"):
            rate = jnp.exp(params["a_log"])[:, None]
            g = self.lower_bound * jax.nn.sigmoid(
                rate * (f.reshape(b, s, h, dk)
                        + params["dt_bias"].reshape(h, dk)))
            o = kimi_delta_rule(q, k, v, g, jax.nn.sigmoid(beta), cd)
        with jax.named_scope(scope + ".gate"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + self.eps)
            o = o * params["norm"] * jax.nn.sigmoid(z.reshape(b, s, h, dv))
        with jax.named_scope(scope + ".proj"):
            out = matmul(o.reshape(b, s, h * dv), params["w_out"], cdt)
        return [out.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        s, d = self.inputs[0].shape[1], self.model_dim
        key, value = self.heads * self.dk, self.heads * self.dv
        proj = d * (3 * key + 2 * value + self.heads) + value * d
        core = 3 * self.heads * self.dk * self.dv  # 6 x dk x dv a token, / 2
        return batch * s * 2 * (proj + core)
