"""Pallas TPU kernels: the gated delta rule, one kernel forward and one
backward, a chunk's intermediates in VMEM.

The same function as ``ops/deltanet.py``'s chunked rule (which stays as
the path of every other backend and shape, and as this one's reference),
at the same ``CHUNK`` of 64 tokens and with the same arithmetic: decays,
``T = (I + A)^-1``, the state and every accumulator in f32; the matmul
operands (q, k, v, ``T``, ``P``, ``u``, the state where it is
multiplied) in the compute dtype, each rounded where the chunked form
rounds it; every exponent of a difference ``<= 0``; the output in f32.
What differs is where a chunk lives: ``k k^T``, the decay matrix, ``A``,
``T``, ``T``'s products, the masked ``q k^T`` and the corrections ``u``
stay in VMEM between the matmuls and never become an XLA buffer; q, k,
v are read where they lie ((B, S, H x d): a block picks its rows and its
head's lanes, value head ``h`` reading key head ``h // group``), so
nothing is laid out chunks-first and no key head is repeated.

Two chunks share the state-free work: ``ROWS`` = 128 tokens are one
(128, 128) tile whose off-diagonal 64-blocks are masked, so every matmul
is a whole MXU tile and ``T`` is two inverses in one.  ``T`` is got as
``unit_lower_inverse`` gets it, by block doubling, written for whole
tiles: with ``D`` the inverse of the b-blocks and ``L`` the part of
``A`` in the lower-left corner of each 2b-block, ``D <- D - D L D``,
from b = 1 (``D = I``) to 32, in f32 at full precision.

Forward (``forward``): grid (batch x value heads, blocks of ``BLOCK``
tokens), the second axis sequential; the state (dk, dv) f32 in VMEM
scratch, zeroed at a head's first block; a loop over the block's pairs
of chunks; writes ``o`` (f32, tokens-first) and the state at every
chunk's start.  Backward (``backward``): the same grid walked from the
last block to the first, the state's cotangent in scratch; each pair
rebuilds its operands and its corrections from the saved states, walks
its two chunks in reverse and pulls the cotangents back through ``P``,
``T`` (``-T^T dT T^T``), ``k k^T``, the decays and the running sum on
the chip; writes dq and dk in f32 a value head (summed over the group
behind the kernel), dv, dg and dbeta.

The pairs of a block go through the state-free work in step (their
inverses' matmuls interleaved in program order), since one pair's ten
dependent f32 matmuls leave the MXUs idle between them; only the
state's chain is walked pair by pair.

v5e, 16 key heads on 32 value heads x 16,384 tokens x 128, bf16
(``scripts/ab_lm_kernels.py gdn``; PERF.md section 6, PR 36 has every
form tried), forward / forward + backward ms a layer: the chunked form
23.70 / 52.82; these kernels **11.04 / 25.35** (in the cell 9.11 a
forward kernel, 10.97 the backward one); at blocks of 256: 11.26 /
26.64, of 1,024: 10.91 / 25.15; one pair at a time in a ``fori_loop``:
13.98 / 33.29, unrolled 12.76 / 30.45.  ``T`` is 5.8 ms of the forward's
11.0 (with ``T = I - A``, wrong, 5.27 / 14.22): its f32 products are six
bf16 passes of the MXU each (by hand 10.70 / 25.00); in three passes (16
bits of each operand) 8.05 / 19.66, not taken: ``T`` stays f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of one chunk: ``ops/deltanet.py::CHUNK`` as it ships
CHUNK = 64
#: tokens whose state-free work is one (ROWS, ROWS) tile: two chunks
ROWS = 128
#: tokens of one grid step
BLOCK = 512
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
_F32 = jnp.float32


def takes(seq: int, dk: int, dv: int, dtype) -> bool:
    """Shapes the kernels are built for: whole blocks of ``BLOCK``
    tokens, head widths that are whole lane tiles, a 16-bit or 32-bit
    float."""
    return (seq > 0 and seq % BLOCK == 0 and dk % LANES == 0
            and dv % LANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _dot(x, y, dims=_NN):
    """f32 accumulation; f32 operands at full precision (the MXU's
    default rounds them to bf16)."""
    exact = x.dtype == _F32 or y.dtype == _F32
    return lax.dot_general(
        x, y, dims, preferred_element_type=_F32,
        precision=lax.Precision.HIGHEST if exact else None)


class _Masks:
    """The (ROWS, ROWS) index masks of a pair of chunks."""

    def __init__(self):
        shape = (ROWS, ROWS)
        self.row = lax.broadcasted_iota(jnp.int32, shape, 0)
        self.col = lax.broadcasted_iota(jnp.int32, shape, 1)
        shift = CHUNK.bit_length() - 1
        self.same = (self.row >> shift) == (self.col >> shift)
        self.lower = self.same & (self.col <= self.row)
        self.strict = self.same & (self.col < self.row)
        self.eye = self.row == self.col

    def corner(self, b: int):
        """The lower-left b x b corner of every 2b-block."""
        shift = b.bit_length()            # log2(2b)
        return (((self.row >> shift) == (self.col >> shift))
                & ((self.row & b) != 0) & ((self.col & b) == 0))

    def across(self, mask, row_vec):
        """(1, ROWS) -> (ROWS, 1): row ``i`` the sum of the entries
        ``mask[i]`` keeps (``eye``: the vector turned)."""
        full = jnp.broadcast_to(row_vec, (ROWS, ROWS))
        return jnp.sum(jnp.where(mask, full, 0.0), axis=1, keepdims=True)

    def down(self, mask, col_vec):
        """(ROWS, 1) -> (1, ROWS): column ``j`` the sum of the entries
        ``mask[:, j]`` keeps."""
        full = jnp.broadcast_to(col_vec, (ROWS, ROWS))
        return jnp.sum(jnp.where(mask, full, 0.0), axis=0, keepdims=True)


def _each(fn, *lists):
    return [fn(*xs) for xs in zip(*lists)]


def _inverses(mats, m: _Masks):
    """``(I + a)^-1`` for every ``a`` of ``mats``, each strictly lower
    inside its chunks: block doubling over whole tiles (the module
    docstring), the tiles in step so that one's matmul need not wait for
    another's."""
    eye = jnp.where(m.eye, 1.0, 0.0)
    ds = [eye - jnp.where(m.corner(1), a, 0.0) for a in mats]
    b = 2
    while b < CHUNK:
        lows = [jnp.where(m.corner(b), a, 0.0) for a in mats]
        xs = _each(_dot, ds, lows)
        ds = [d - _dot(x, d) for x, d in zip(xs, ds)]
        b *= 2
    return ds


def _operands(tiles, m: _Masks, cd):
    """What pairs of chunks need that does not depend on the state, for
    the pairs of ``tiles`` (each ``(q, k, v, g_row, beta_row)``) in
    step: ``ops/deltanet.py::_chunk_operands`` over (ROWS, .) tiles,
    with the f32 pieces the backward pulls back through."""
    qs, ks, vs, g_rows, beta_rows = zip(*tiles)
    dv = vs[0].shape[1]
    gamma = [m.across(m.lower, g) for g in g_rows]   # the running sum
    total = [m.across(m.same, g) for g in g_rows]    # its last entry
    beta = [m.across(m.eye, b) for b in beta_rows]
    decay = [jnp.exp(jnp.where(m.lower, g - m.down(m.eye, g), -jnp.inf))
             for g in gamma]
    kk = [_dot(k, k, _NT) for k in ks]
    qk = _each(lambda q, k: _dot(q, k, _NT), qs, ks)
    t = _inverses([jnp.where(m.strict, b * x * d, 0.0)
                   for b, x, d in zip(beta, kk, decay)], m)
    tc = [x.astype(cd) for x in t]
    eg = [jnp.exp(g) for g in gamma]
    ek = _each(lambda tot, g: jnp.exp(tot - g), total, gamma)
    q32, k32, v32 = ([x.astype(_F32) for x in xs] for xs in (qs, ks, vs))
    rhs = [jnp.concatenate([v * b, k * (b * e)], axis=1).astype(cd)
           for v, k, b, e in zip(v32, k32, beta, eg)]
    w = _each(_dot, tc, rhs)
    return [dict(
        beta=beta[i], decay=decay[i], kk=kk[i], qk=qk[i], t=t[i], tc=tc[i],
        rhs=rhs[i], eg=eg[i], ek=ek[i], q32=q32[i], k32=k32[i], v32=v32[i],
        p=(qk[i] * decay[i]).astype(cd), qd=(q32[i] * eg[i]).astype(cd),
        kd=(k32[i] * ek[i]).astype(cd), carry=jnp.exp(total[i]),
        w_v=w[i][:, :dv], w_k=w[i][:, dv:].astype(cd))
        for i in range(len(tiles))]


def _chunk_rows(j: int):
    return slice(j * CHUNK, (j + 1) * CHUNK)


def _pair_rows(p: int):
    return slice(p * ROWS, (p + 1) * ROWS)


def _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref):
    """The block's pairs of chunks: ``(q, k, v, g_row, beta_row)``."""
    return [(q_ref[_pair_rows(p), :], k_ref[_pair_rows(p), :],
             v_ref[_pair_rows(p), :], g_ref[p:p + 1, :], beta_ref[p:p + 1, :])
            for p in range(BLOCK // ROWS)]


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, starts_ref,
                s_ref):
    cd = q_ref.dtype
    m = _Masks()

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    xs = _operands(_tiles(q_ref, k_ref, v_ref, g_ref, beta_ref), m, cd)
    for p, x in enumerate(xs):
        from_state, us = [], []
        for j in range(ROWS // CHUNK):
            r = _chunk_rows(j)
            s = s_ref[...]
            starts_ref[2 * p + j] = s
            sc = s.astype(cd)
            # w_k S and (q exp(gamma)) S: one product on the state
            both = _dot(jnp.concatenate([x["w_k"][r], x["qd"][r]], axis=0),
                        sc)
            u = (x["w_v"][r] - both[:CHUNK]).astype(cd)
            from_state.append(both[CHUNK:])
            us.append(u)
            s_ref[...] = (x["carry"][j * CHUNK:j * CHUNK + 1] * s
                          + _dot(x["kd"][r], u, _TN))
        o_ref[_pair_rows(p), :] = (jnp.concatenate(from_state, axis=0)
                                   + _dot(x["p"], jnp.concatenate(us, axis=0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _row_vectors(x, hv):
    """(B, S, Hv) f32 -> (B x Hv, S / BLOCK, BLOCK / ROWS, ROWS): a
    head's tokens along lanes, a pair of chunks a row."""
    b, s, _ = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * hv, s // BLOCK, BLOCK // ROWS,
                                         ROWS)


def _token_vectors(x, b, hv):
    """The inverse of ``_row_vectors``."""
    return jnp.swapaxes(x.reshape(b, hv, -1), 1, 2)


def _specs(hk, hv, dk, dv, blocks, reverse):
    """Block specs of the (B, S, H x d) operands and of the row vectors
    for a grid (B x Hv, blocks); ``reverse`` walks the blocks from the
    last to the first."""
    group = hv // hk
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    key = pl.BlockSpec((None, BLOCK, dk),
                       lambda n, i: (n // hv, at(i), (n % hv) // group))
    value = pl.BlockSpec((None, BLOCK, dv),
                         lambda n, i: (n // hv, at(i), n % hv))
    own_key = pl.BlockSpec((None, BLOCK, dk),
                           lambda n, i: (n // hv, at(i), n % hv))
    vector = pl.BlockSpec((None, None, BLOCK // ROWS, ROWS),
                          lambda n, i: (n, at(i), 0, 0))
    states = pl.BlockSpec((None, BLOCK // CHUNK, dk, dv),
                          lambda n, i: (n, at(i), 0, 0))
    return key, value, own_key, vector, states


def forward(q, k, v, g, beta):
    """``q``, ``k``: (B, S, Hk, dk); ``v``: (B, S, Hv, dv), Hk dividing
    Hv, all in the compute dtype; ``g``, ``beta``: (B, S, Hv) f32.
    Returns ``(o (B, S, Hv, dv) f32, starts (B x Hv, S / CHUNK, dk, dv)
    f32)``: the state at every chunk's start."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    blocks = s // BLOCK
    key, value, _, vector, states = _specs(hk, hv, dk, dv, blocks, False)
    o, starts = pl.pallas_call(
        _fwd_kernel,
        grid=(b * hv, blocks),
        in_specs=[key, key, value, vector, vector],
        out_specs=[value, states],
        out_shape=[jax.ShapeDtypeStruct((b, s, hv * dv), _F32),
                   jax.ShapeDtypeStruct((b * hv, s // CHUNK, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_params(),
        name="gated_delta_fwd",
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), _row_vectors(g, hv), _row_vectors(beta, hv))
    return o.reshape(b, s, hv, dv), starts


# ----------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref):
    cd = q_ref.dtype
    m = _Masks()
    dv_width = v_ref.shape[1]
    rounded = lambda x: x.astype(cd).astype(_F32)
    row_sum = lambda x: jnp.sum(x, axis=1, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    tiles = _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref)
    xs = _operands(tiles, m, cd)
    pairs = range(len(xs))
    halves = range(ROWS // CHUNK)
    docs = [do_ref[_pair_rows(p), :].astype(cd) for p in pairs]
    # the corrections again, from the states the forward kept
    scs = [[starts_ref[2 * p + j].astype(cd) for j in halves] for p in pairs]
    us = [[(x["w_v"][_chunk_rows(j)]
            - _dot(x["w_k"][_chunk_rows(j)], scs[p][j])).astype(cd)
           for j in halves] for p, x in enumerate(xs)]
    from_o = [_dot(x["p"], doc, _TN) for x, doc in zip(xs, docs)]  # P^T do
    d_p = [rounded(_dot(doc, jnp.concatenate(u, axis=0), _NT))
           for doc, u in zip(docs, us)]
    # the chunks from the last to the first, the state's cotangent carried
    chain = [None] * len(xs)
    for p in reversed(pairs):
        x, doc = xs[p], docs[p]
        d_qd, d_kd, d_wk, d_wv = ([None] * len(halves) for _ in range(4))
        d_total = jnp.zeros((ROWS, 1), _F32)
        for j in reversed(halves):
            r = _chunk_rows(j)
            ds = ds_ref[...]
            dsc = ds.astype(cd)
            du = from_o[p][r] + _dot(x["kd"][r], dsc)
            duc = du.astype(cd)
            d_wv[j] = du
            d_kd[j] = _dot(us[p][j], dsc, _NT)
            both = _dot(jnp.concatenate([doc[r], duc], axis=0), scs[p][j],
                        _NT)
            d_qd[j], d_wk[j] = both[:CHUNK], -both[CHUNK:]
            carry = x["carry"][j * CHUNK:j * CHUNK + 1]
            d_carry = jnp.sum(row_sum(ds * starts_ref[2 * p + j]), axis=0,
                              keepdims=True)
            # the cotangent of the chunk's ``total``, at its first row
            d_total = d_total + jnp.where(m.row[:, :1] == j * CHUNK,
                                          d_carry * carry, 0.0)
            ds_ref[...] = (_dot(x["qd"][r], doc[r], _TN) + carry * ds
                           - _dot(x["w_k"][r], duc, _TN))
        chain[p] = dict(
            d_qd=rounded(jnp.concatenate(d_qd, axis=0)),
            d_kd=rounded(jnp.concatenate(d_kd, axis=0)),
            d_w=jnp.concatenate([jnp.concatenate(d_wv, axis=0),
                                 jnp.concatenate(d_wk, axis=0)],
                                axis=1).astype(cd),
            d_total=d_total)
    # back through the state-free operands, the pairs in step
    d_t = [rounded(_dot(c["d_w"], x["rhs"], _NT)) for c, x in zip(chain, xs)]
    d_rhs = [rounded(_dot(x["tc"], c["d_w"], _TN))
             for c, x in zip(chain, xs)]
    d_a = [_dot(x["t"], d, _TN) for x, d in zip(xs, d_t)]
    d_a = [jnp.where(m.strict, -_dot(d, x["t"], _NT), 0.0)
           for x, d in zip(xs, d_a)]
    for p, (x, c, (q, k, _v, _g, _b)) in enumerate(zip(xs, chain, tiles)):
        rows = _pair_rows(p)
        beta, eg, ek, decay = x["beta"], x["eg"], x["ek"], x["decay"]
        q32, k32, v32 = x["q32"], x["k32"], x["v32"]
        d_qd, d_kd, d_total = c["d_qd"], c["d_kd"], c["d_total"]
        dq = d_qd * eg
        d_gamma = row_sum(d_qd * q32) * eg
        dk = d_kd * ek
        from_kd = row_sum(d_kd * k32) * ek
        d_gamma = d_gamma - from_kd
        d_total = d_total + from_kd
        d_qk = d_p[p] * decay
        through_decay = d_qk * x["qk"]
        d_qk = d_qk.astype(cd)
        dq = dq + _dot(d_qk, k)
        dk = dk + _dot(d_qk, q, _TN)
        d_rv, d_rk = d_rhs[p][:, :dv_width], d_rhs[p][:, dv_width:]
        dv_ref[rows, :] = (d_rv * beta).astype(dv_ref.dtype)
        d_beta = row_sum(d_rv * v32)
        dk = dk + d_rk * (beta * eg)
        from_rk = row_sum(d_rk * k32) * eg
        d_beta = d_beta + from_rk
        d_gamma = d_gamma + from_rk * beta
        scaled = d_a[p] * decay
        d_beta = d_beta + row_sum(scaled * x["kk"])
        d_kk = scaled * beta
        through_decay = through_decay + d_kk * x["kk"]
        dk = dk + _dot((d_kk + d_kk.T).astype(cd), k)
        d_gamma = (d_gamma + row_sum(through_decay)
                   - m.across(m.eye, jnp.sum(through_decay, axis=0,
                                             keepdims=True)))
        # gamma is the chunk's running sum, total its last entry
        dg_ref[p:p + 1, :] = (m.down(m.lower, d_gamma)
                              + m.down(m.same, d_total))
        dbeta_ref[p:p + 1, :] = m.down(m.eye, d_beta)
        dq_ref[rows, :] = dq
        dk_ref[rows, :] = dk


def backward(q, k, v, g, beta, starts, do):
    """The five gradients from the saved chunk-boundary states, shaped
    and typed as their operands.  ``do``: (B, S, Hv, dv) f32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    blocks = s // BLOCK
    key, value, own_key, vector, states = _specs(hk, hv, dk, dv, blocks, True)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(b * hv, blocks),
        in_specs=[key, key, value, vector, vector, states, value],
        out_specs=[own_key, own_key, value, vector, vector],
        out_shape=[jax.ShapeDtypeStruct((b, s, hv * dk), _F32),
                   jax.ShapeDtypeStruct((b, s, hv * dk), _F32),
                   jax.ShapeDtypeStruct((b, s, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((b * hv, blocks, BLOCK // ROWS, ROWS),
                                        _F32),
                   jax.ShapeDtypeStruct((b * hv, blocks, BLOCK // ROWS, ROWS),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_params(),
        name="gated_delta_bwd",
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), _row_vectors(g, hv), _row_vectors(beta, hv),
      starts, do.reshape(b, s, hv * dv))
    # a key head's gradient is its group's sum
    per_key = lambda x: jnp.sum(
        x.reshape(b, s, hk, hv // hk, dk), axis=3).astype(q.dtype)
    return (per_key(dq), per_key(dk_), dv_.reshape(v.shape),
            _token_vectors(dg, b, hv), _token_vectors(dbeta, b, hv))
