"""Pallas TPU kernels: the delta rule with a decay per key channel (Kimi
Delta Attention), one kernel forward and one backward, a chunk's
intermediates in VMEM.

The same function as ``ops/deltanet.py::kimi_delta_rule``'s chunked form
(which stays as the path of every other backend and shape, and as this
one's reference), at the same ``CHUNK`` of 64 tokens and ``SUB`` of 16,
with the same arithmetic (``_channel_chunk_operands``): decays, their
running sums, ``T = (I + A)^-1``, the state and every accumulator in
f32; a chunk's rows go in sub-blocks of ``SUB`` tokens, each around its
own point of reference, the decays summed through its middle token, so
that a row's factor ``exp(Gamma_i - R)`` and a column's ``exp(R -
Gamma_j)`` stay inside ``e^+-40``; **the pair product takes those f32
factors at full precision whatever the compute dtype** (with bf16
factors the decay's gradient read 8.7 times its own norm off the
recurrence's: ``docs/KDA_MOE_LM.md``); every other matmul operand (q, k,
v, ``T``, ``P``, ``u``, the state where it is multiplied) in the compute
dtype, each rounded where the chunked form rounds it; the output in f32.

What differs is where a chunk lives.  ``ROWS`` = 128 tokens, two
chunks, are one (128, 128) tile of pairs whose off-diagonal 64-blocks
are masked: the running sums (one product with a lower-triangular 0/1
tile), each row's point of reference and chunk total (products with 0/1
tiles that pick one row, exact at full precision), the eight sub-blocks'
row and column factors, their pair products, ``A``, ``P``, ``T`` (block
doubling over whole tiles, as ``ops/pallas_deltanet.py`` gets it), its
products and the corrections stay in VMEM and never become an XLA
buffer.  q, k, v and g are read where they lie ((B, S, H x d): a block
picks its rows and its head's lanes); the state is kept TRANSPOSED,
(dv, dk), so that a chunk's decay per key channel scales its columns as
a row vector and no vector is turned.

Forward (``forward``): grid (batch x heads, blocks of ``BLOCK`` tokens),
the second axis sequential; the state in VMEM scratch, zeroed at a
head's first block; the block's pairs of chunks through the state-free
work in step, then the state walked chunk by chunk; writes ``o`` (f32)
and the (transposed) state at every chunk's start.  Backward
(``backward``): the same grid walked from the last block to the first,
the state's cotangent in scratch; each pair rebuilds its operands and
corrections from the saved states, walks its two chunks in reverse and
pulls the cotangents back through ``P``, ``T`` (``-T^T dT T^T``), the
sub-blocks' products and factors, the running sums and ``beta`` on the
chip.  A point of reference cancels in every pair it serves, so its
cotangent is zero and is not formed.

v5e, 16 heads x 8,192 tokens x 128, bf16 (``scripts/ab_lm_kernels.py
kda``; PERF.md section 6, PR 39), forward / forward + backward ms a
layer: the chunked form 6.48 / 17.50; these kernels at blocks of 256
**4.19 / 10.56** (they ship; in the cell ``kimi_delta_bwd`` takes 5.63
ms a layer and step), 128: 5.07 / 12.37, 512: 4.11 / 10.47.  The sub-block loops stay unrolled: the kernels are
lowered once a program (``deltanet._kimi_pallas_rule``), 0.2 s for both
at the cell's shapes on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of one chunk: ``ops/deltanet.py::CHUNK`` as it ships
CHUNK = 64
#: tokens of one sub-block: ``ops/deltanet.py::SUB`` as it ships
SUB = 16
#: tokens whose state-free work is one (ROWS, ROWS) tile: two chunks
ROWS = 128
#: tokens of one grid step; its pairs of chunks go in step
BLOCK = 256
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


def _ones(mask):
    return jnp.where(mask, 1.0, 0.0).astype(_F32)


class _Masks:
    """The (ROWS, ROWS) index masks of a pair of chunks, and the 0/1
    tiles whose products sum and pick rows."""

    def __init__(self):
        shape = (ROWS, ROWS)
        self.row = lax.broadcasted_iota(jnp.int32, shape, 0)
        self.col = lax.broadcasted_iota(jnp.int32, shape, 1)
        shift = CHUNK.bit_length() - 1
        self.same = (self.row >> shift) == (self.col >> shift)
        self.lower = self.same & (self.col <= self.row)
        self.strict = self.same & (self.col < self.row)
        self.eye = self.row == self.col
        sub = SUB.bit_length() - 1
        # row i picks its sub-block's middle token, and its chunk's last
        self.middle = self.col == (((self.row >> sub) << sub) + SUB // 2 - 1)
        self.last = self.col == (((self.row >> shift) << shift) + CHUNK - 1)
        self.token = lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)

    def upto(self, a: int):
        """(ROWS, 1): the tokens of sub-block ``a``'s chunk up to its
        end, the columns its rows pair with."""
        start = (a * SUB // CHUNK) * CHUNK
        return (self.token >= start) & (self.token < (a + 1) * SUB)

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
    inside its chunks: with ``D`` the inverse of the b-blocks and ``L``
    the part of ``a`` in the lower-left corner of each 2b-block, ``D <-
    D - D L D`` from b = 1 (``D = I``) to 32, in f32 at full precision,
    the tiles in step so that one's matmul need not wait for another's
    (``ops/pallas_deltanet.py::_inverses``)."""
    eye = jnp.where(m.eye, 1.0, 0.0)
    ds = [eye - jnp.where(m.corner(1), a, 0.0) for a in mats]
    b = 2
    while b < CHUNK:
        lows = [jnp.where(m.corner(b), a, 0.0) for a in mats]
        xs = _each(_dot, ds, lows)
        ds = [d - _dot(x, d) for x, d in zip(xs, ds)]
        b *= 2
    return ds


def _sub_rows(a: int):
    return slice(a * SUB, (a + 1) * SUB)


def _pairs_of(rows_q, rows_k, gamma, ref, k32, m: _Masks):
    """The sub-blocks' pair products of one tile: for each sub-block
    ``a``, its rows ``[q ; k] exp(Gamma - R_a)`` against the columns
    ``k exp(R_a - Gamma_j)`` of its chunk up to its end (later and
    other-chunk columns zero, their exponent masked before it can
    overflow), f32 at full precision.  Returns ``(pq, pk, cols,
    factors)``: the (ROWS, ROWS) products of q's and of k's rows, and
    each sub-block's columns and their factors."""
    pieces, cols, factors = [], [], []
    for a in range(ROWS // SUB):
        r = _sub_rows(a)
        factor = jnp.exp(jnp.where(m.upto(a), ref[a * SUB:a * SUB + 1]
                                   - gamma, -jnp.inf))
        col = k32 * factor
        pieces.append(_dot(jnp.concatenate([rows_q[r], rows_k[r]], axis=0),
                           col, _NT))
        cols.append(col)
        factors.append(factor)
    pq = jnp.concatenate([x[:SUB] for x in pieces], axis=0)
    pk = jnp.concatenate([x[SUB:] for x in pieces], axis=0)
    return pq, pk, cols, factors


def _operands(tiles, m: _Masks, cd):
    """What pairs of chunks need that does not depend on the state, for
    the pairs of ``tiles`` (each ``(q, k, v, g, beta_row)``) in step:
    ``ops/deltanet.py::_channel_chunk_operands`` over (ROWS, .) tiles,
    with the f32 pieces the backward pulls back through."""
    out = []
    for q, k, v, g, beta_row in tiles:
        q32, k32, v32 = (x.astype(_F32) for x in (q, k, v))
        beta = m.across(m.eye, beta_row)
        gamma = _dot(_ones(m.lower), g)       # the running sums
        ref = _dot(_ones(m.middle), gamma)    # each row's point
        total = _dot(_ones(m.last), gamma)    # each row's chunk total
        own = jnp.exp(gamma - ref)            # rows: e^+-40
        rows_q, rows_k = q32 * own, k32 * own
        pq, pk, cols, factors = _pairs_of(rows_q, rows_k, gamma, ref, k32,
                                          m)
        out.append(dict(q32=q32, k32=k32, v32=v32, beta=beta, gamma=gamma,
                        total=total, own=own, rows_q=rows_q, rows_k=rows_k,
                        pk=pk, cols=cols, factors=factors,
                        p=jnp.where(m.lower, pq, 0.0).astype(cd),
                        a=jnp.where(m.strict, beta * pk, 0.0)))
    ts = _inverses([x["a"] for x in out], m)
    for x, t in zip(out, ts):
        dv = x["v32"].shape[1]
        eg = jnp.exp(x["gamma"])
        ek = jnp.exp(x["total"] - x["gamma"])
        tc = t.astype(cd)
        rhs = jnp.concatenate([x["v32"] * x["beta"],
                               x["k32"] * x["beta"] * eg], axis=1).astype(cd)
        w = _dot(tc, rhs)
        x.update(t=t, tc=tc, rhs=rhs, eg=eg, ek=ek,
                 qd=(x["q32"] * eg).astype(cd), kd=(x["k32"] * ek).astype(cd),
                 carry=jnp.exp(x["total"]), w_v=w[:, :dv],
                 w_k=w[:, dv:].astype(cd))
    return out


def _chunk_rows(j: int):
    return slice(j * CHUNK, (j + 1) * CHUNK)


def _pair_rows(p: int):
    return slice(p * ROWS, (p + 1) * ROWS)


def _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref):
    """The block's pairs of chunks: ``(q, k, v, g, beta_row)``."""
    return [(q_ref[_pair_rows(p), :], k_ref[_pair_rows(p), :],
             v_ref[_pair_rows(p), :], g_ref[_pair_rows(p), :],
             beta_ref[p:p + 1, :])
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
            s = s_ref[...]                               # (dv, dk)
            starts_ref[2 * p + j] = s
            sc = s.astype(cd)
            # w_k S and (q exp(Gamma)) S: one product on the state
            both = _dot(jnp.concatenate([x["w_k"][r], x["qd"][r]], axis=0),
                        sc, _NT)
            u = (x["w_v"][r] - both[:CHUNK]).astype(cd)
            from_state.append(both[CHUNK:])
            us.append(u)
            # Diag(exp(Gamma_C)) S + kd^T u, transposed
            s_ref[...] = (x["carry"][j * CHUNK:j * CHUNK + 1] * s
                          + _dot(u, x["kd"][r], _TN))
        o_ref[_pair_rows(p), :] = (jnp.concatenate(from_state, axis=0)
                                   + _dot(x["p"], jnp.concatenate(us, axis=0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _row_vectors(x, h):
    """(B, S, H) f32 -> (B x H, S / BLOCK, BLOCK / ROWS, ROWS): a head's
    tokens along lanes, a pair of chunks a row."""
    b, s, _ = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s // BLOCK, BLOCK // ROWS,
                                         ROWS)


def _token_vectors(x, b, h):
    """The inverse of ``_row_vectors``."""
    return jnp.swapaxes(x.reshape(b, h, -1), 1, 2)


def _specs(h, dk, dv, blocks, reverse):
    """Block specs of the (B, S, H x d) operands, the row vectors and the
    states for a grid (B x H, blocks); ``reverse`` walks the blocks from
    the last to the first."""
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    key = pl.BlockSpec((None, BLOCK, dk), lambda n, i: (n // h, at(i), n % h))
    value = pl.BlockSpec((None, BLOCK, dv),
                         lambda n, i: (n // h, at(i), n % h))
    vector = pl.BlockSpec((None, None, BLOCK // ROWS, ROWS),
                          lambda n, i: (n, at(i), 0, 0))
    states = pl.BlockSpec((None, BLOCK // CHUNK, dv, dk),
                          lambda n, i: (n, at(i), 0, 0))
    return key, value, vector, states


def forward(q, k, v, g, beta):
    """``q``, ``k``: (B, S, H, dk), ``v``: (B, S, H, dv), all in the
    compute dtype; ``g``: (B, S, H, dk) f32 in ``[DECAY_FLOOR, 0]``;
    ``beta``: (B, S, H) f32.  Returns ``(o (B, S, H, dv) f32, starts (B x
    H, S / CHUNK, dv, dk) f32)``: the state at every chunk's start,
    transposed."""
    b, s, h, dk = q.shape
    dv = v.shape[3]
    blocks = s // BLOCK
    key, value, vector, states = _specs(h, dk, dv, blocks, False)
    o, starts = pl.pallas_call(
        _fwd_kernel,
        grid=(b * h, blocks),
        in_specs=[key, key, value, key, vector],
        out_specs=[value, states],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), _F32),
                   jax.ShapeDtypeStruct((b * h, s // CHUNK, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params(),
        name="kimi_delta_fwd",
    )(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
      v.reshape(b, s, h * dv), g.reshape(b, s, h * dk), _row_vectors(beta, h))
    return o.reshape(b, s, h, dv), starts


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

    xs = _operands(_tiles(q_ref, k_ref, v_ref, g_ref, beta_ref), m, cd)
    pairs = range(len(xs))
    halves = range(ROWS // CHUNK)
    docs = [do_ref[_pair_rows(p), :].astype(cd) for p in pairs]
    # the corrections again, from the states the forward kept
    scs = [[starts_ref[2 * p + j].astype(cd) for j in halves] for p in pairs]
    us = [[(x["w_v"][_chunk_rows(j)]
            - _dot(x["w_k"][_chunk_rows(j)], scs[p][j], _NT)).astype(cd)
           for j in halves] for p, x in enumerate(xs)]
    from_o = [_dot(x["p"], doc, _TN) for x, doc in zip(xs, docs)]  # P^T do
    d_p = [rounded(_dot(doc, jnp.concatenate(u, axis=0), _NT))
           for doc, u in zip(docs, us)]
    # the chunks from the last to the first, the state's cotangent carried
    chain = [None] * len(xs)
    for p in reversed(pairs):
        x, doc = xs[p], docs[p]
        d_qd, d_kd, d_wk, d_wv = ([None] * len(halves) for _ in range(4))
        d_total = jnp.zeros(x["gamma"].shape, _F32)
        for j in reversed(halves):
            r = _chunk_rows(j)
            ds = ds_ref[...]                             # (dv, dk)
            dsc = ds.astype(cd)
            du = from_o[p][r] + _dot(x["kd"][r], dsc, _NT)
            duc = du.astype(cd)
            d_wv[j] = du
            d_kd[j] = _dot(us[p][j], dsc)
            both = _dot(jnp.concatenate([doc[r], duc], axis=0), scs[p][j])
            d_qd[j], d_wk[j] = both[:CHUNK], -both[CHUNK:]
            carry = x["carry"][j * CHUNK:j * CHUNK + 1]  # (1, dk)
            d_carry = jnp.sum(ds * starts_ref[2 * p + j], axis=0,
                              keepdims=True)
            # the cotangent of the chunk's total, at its first row
            d_total = d_total + jnp.where(m.token == j * CHUNK,
                                          d_carry * carry, 0.0)
            ds_ref[...] = (_dot(doc[r], x["qd"][r], _TN) + carry * ds
                           - _dot(duc, x["w_k"][r], _TN))
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
    for p, (x, c) in enumerate(zip(xs, chain)):
        rows = _pair_rows(p)
        beta, eg, ek = x["beta"], x["eg"], x["ek"]
        q32, k32, v32 = x["q32"], x["k32"], x["v32"]
        d_qd, d_kd, d_total = c["d_qd"], c["d_kd"], c["d_total"]
        dq = d_qd * eg
        d_gamma = d_qd * q32 * eg
        from_kd = d_kd * k32 * ek
        dk = d_kd * ek
        d_gamma = d_gamma - from_kd
        d_total = d_total + from_kd
        d_rv, d_rk = d_rhs[p][:, :dv_width], d_rhs[p][:, dv_width:]
        dv_ref[rows, :] = (d_rv * beta).astype(dv_ref.dtype)
        d_beta = row_sum(d_rv * v32)
        dk = dk + d_rk * (beta * eg)
        from_rk = d_rk * k32 * eg
        d_beta = d_beta + row_sum(from_rk)
        d_gamma = d_gamma + from_rk * beta
        # A = beta * pk below the diagonal, P = pq on and below it
        d_beta = d_beta + row_sum(d_a[p] * x["pk"])
        d_pq = jnp.where(m.lower, d_p[p], 0.0)
        d_pk = d_a[p] * beta
        # through each sub-block's product to its rows and its columns
        d_rows, d_cols = [], jnp.zeros(k32.shape, _F32)
        for a in range(ROWS // SUB):
            r = _sub_rows(a)
            d_pairs = jnp.concatenate([d_pq[r], d_pk[r]], axis=0)
            lhs = jnp.concatenate([x["rows_q"][r], x["rows_k"][r]], axis=0)
            d_rows.append(_dot(d_pairs, x["cols"][a]))
            d_cols = d_cols + _dot(d_pairs, lhs, _TN) * x["factors"][a]
        d_rows_q = jnp.concatenate([d[:SUB] for d in d_rows], axis=0)
        d_rows_k = jnp.concatenate([d[SUB:] for d in d_rows], axis=0)
        own = x["own"]
        dq = dq + d_rows_q * own
        dk = dk + d_rows_k * own + d_cols
        d_gamma = (d_gamma + (d_rows_q * q32 + d_rows_k * k32) * own
                   - d_cols * k32)
        # Gamma is the chunk's running sum of g, its total the last entry
        dg_ref[rows, :] = (_dot(_ones(m.lower), d_gamma, _TN)
                           + _dot(_ones(m.same), d_total))
        dbeta_ref[p:p + 1, :] = m.down(m.eye, d_beta)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)


def backward(q, k, v, g, beta, starts, do):
    """The five gradients from the saved chunk-boundary states, shaped
    and typed as their operands.  ``do``: (B, S, H, dv) f32."""
    b, s, h, dk = q.shape
    dv = v.shape[3]
    blocks = s // BLOCK
    key, value, vector, states = _specs(h, dk, dv, blocks, True)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(b * h, blocks),
        in_specs=[key, key, value, key, vector, states, value],
        out_specs=[key, key, value, key, vector],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dk), _F32),
                   jax.ShapeDtypeStruct((b * h, blocks, BLOCK // ROWS, ROWS),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params(),
        name="kimi_delta_bwd",
    )(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
      v.reshape(b, s, h * dv), g.reshape(b, s, h * dk), _row_vectors(beta, h),
      starts, do.reshape(b, s, h * dv))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), _token_vectors(dbeta, b, h))
