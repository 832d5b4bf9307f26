"""Pallas TPU kernels: the causal attention core, one kernel forward and
one fused kernel backward.

The same function as ``ops/attention.py``'s plain blockwise core (which
stays as the path of every other backend and shape, and as this one's
reference), with the same arithmetic: matmul operands in the compute
dtype, each rounded once; logits, softmax, log-sum-exp and every
accumulator in f32; the probabilities rounded to the compute dtype
before ``P v``; no (S, S) array.  What differs is where a tile lives:
the (block, block) logits, probabilities and ``ds`` stay in VMEM between
the matmuls and never become an XLA buffer, the tiles wholly below the
diagonal carry no mask, and the query arrives with the softmax scale
already folded in (``blockwise_causal_attention`` does that in f32,
before the one rounding), so no tile is scaled.

Forward: grid (heads, query blocks); a head's keys and values stay in
VMEM while its query blocks pass, and one program walks the key blocks
at or below its query block with the online softmax (running max, sum
and output in VMEM scratch).  It writes the output in f32 and the
log-sum-exp as one (1, S) row a head, the layout the backward reads.

Backward: grid (heads, key blocks); a head's queries, output gradients,
log-sum-exp and ``delta = sum(do * o)`` stay in VMEM, as does its whole
f32 ``dq``; one program walks the query blocks at or above its key block
TRANSPOSED (keys on sublanes, queries on lanes, so the per-query rows
broadcast over sublanes and ``dv`` / ``dk`` are plain matmuls): five
matmuls a tile, the probabilities rebuilt as ``exp(logits - lse)``;
``dk`` / ``dv`` of the block accumulate in scratch, ``dq`` in the head's
accumulator, each rounded once as it leaves.

Grouped heads (PR 35): with fewer key/value heads than query heads
both kernels fetch a group's key/value head by ``h // group`` (once for
the whole group forward, since the block does not change between the
group's programs), and the backward writes each query head's ``dk`` and
``dv`` in f32 for a sum over the group behind the kernel.  v5e, 16 on 2
heads x 16,384 x 256, bf16: forward 14.20 ms, forward + backward 47.45
(``ab_lm_kernels.py gqa``).

v5e, 32 heads x 8,192 tokens x 192 / 128, bf16 (``scripts/
ab_lm_kernels.py attn``; PERF.md section 6, PR 34 has every form
tried): forward 6.26 ms, forward + backward 20.55, where the plain core
takes 8.78 / 27.68 and the best splash-attention form 7.33 / 24.86.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a query block and of a key block, forward and backward
BLOCK = 512
LANES = 128
#: the whole-head operands (a head's k and v forward; q, do and the f32
#: dq backward) are over Mosaic's default 16 MiB of scoped VMEM; the
#: v5e has 128 MiB
VMEM_LIMIT = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def takes(seq: int, qk_dim: int, v_dim: int, dtype) -> bool:
    """Shapes the kernels are built for: whole blocks, head widths that
    are whole lane tiles (v) or half ones (q, k: 192 = 128 + 64), a
    16-bit or 32-bit float."""
    return (seq % BLOCK == 0 and qk_dim % (LANES // 2) == 0
            and v_dim % LANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _rows(index, block):
    return pl.ds(pl.multiple_of(index * block, block), block)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, block: int):
    i = pl.program_id(1)
    q = q_ref[...]
    repeats = block // LANES
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(j, on_diagonal):
        rows = _rows(j, block)
        logits = lax.dot_general(q, k_ref[rows, :], _NT,
                                 preferred_element_type=jnp.float32)
        if on_diagonal:   # the same offset on both sides
            row = lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            col = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(col <= row, logits, -jnp.inf)
        m_prev, l_prev = m_ref[...], l_ref[...]      # lane-replicated
        m_next = jnp.maximum(m_prev,
                             jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - jnp.tile(m_next, (1, repeats)))
        corr = jnp.exp(m_prev - m_next)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[rows, :]
        acc_ref[...] = (
            acc_ref[...] * jnp.tile(corr, (1, acc_ref.shape[1] // LANES))
            + jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32))

    lax.fori_loop(0, i, lambda j, _: tile(j, False), None)
    tile(i, True)
    l = l_ref[...]
    o_ref[...] = (acc_ref[...]
                  / jnp.tile(l, (1, acc_ref.shape[1] // LANES))
                  ).astype(o_ref.dtype)
    # one (1, block) row of the log-sum-exp: the lane-replicated column
    # turned by a select and a sum over sublanes
    lse = jnp.tile(m_ref[...] + jnp.log(l), (1, repeats))
    row = lax.broadcasted_iota(jnp.int32, lse.shape, 0)
    col = lax.broadcasted_iota(jnp.int32, lse.shape, 1)
    lse_ref[...] = jnp.sum(jnp.where(row == col, lse, 0.0), axis=0,
                           keepdims=True)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, block: int, blocks: int):
    j = pl.program_id(1)
    k, v = k_ref[...], v_ref[...]
    cd = k.dtype

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(i, on_diagonal):
        rows = _rows(i, block)
        q, do = q_ref[rows, :], do_ref[rows, :]
        logits = lax.dot_general(k, q, _NT,           # (keys, queries)
                                 preferred_element_type=jnp.float32)
        if on_diagonal:
            key = lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            query = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(key <= query, logits, -jnp.inf)
        p = jnp.exp(logits - lse_ref[:, rows])
        dv_acc[...] += jnp.dot(p.astype(cd), do,
                               preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, rows])).astype(cd)
        dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_acc[rows, :] += lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    tile(j, True)
    lax.fori_loop(j + 1, blocks, lambda i, _: tile(i, False), None)
    dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == blocks - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def forward(q, k, v):
    """``q``: (N, S, Dk), the scale folded in; ``k``: (M, S, Dk);
    ``v``: (M, S, Dv); N = batch x heads, M = batch x key/value heads
    dividing N: query head ``h`` reads key/value head ``h // (N / M)``,
    which is fetched once for the whole group (its block index does not
    change between the group's programs) and never written N / M times.
    Returns ``(o (N, S, Dv) f32, lse (N, S) f32)``."""
    n, s, dk = q.shape
    dv = v.shape[-1]
    group = n // k.shape[0]
    whole = lambda width: pl.BlockSpec((None, s, width),
                                       lambda h, i: (h // group, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block=BLOCK),
        grid=(n, s // BLOCK),
        in_specs=[pl.BlockSpec((None, BLOCK, dk), lambda h, i: (h, i, 0)),
                  whole(dk), whole(dv)],
        out_specs=[pl.BlockSpec((None, BLOCK, dv), lambda h, i: (h, i, 0)),
                   pl.BlockSpec((None, 1, BLOCK), lambda h, i: (h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((n, s, dv), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BLOCK, LANES), jnp.float32),
                        pltpu.VMEM((BLOCK, LANES), jnp.float32),
                        pltpu.VMEM((BLOCK, dv), jnp.float32)],
        compiler_params=_params(),
        name="causal_attention_fwd",
    )(q, k, v)
    return o, lse.reshape(n, s)


def backward(q, k, v, o, lse, do):
    """The three gradients from the saved output and log-sum-exp, in the
    operands' dtype.  ``o``, ``do``: (N, S, Dv) f32; ``lse``: (N, S).
    With grouped heads (``k``, ``v``: (M, S, .), M dividing N) a key
    block is read from its own head, each query head's ``dk`` and ``dv``
    leave the kernel in f32 and the group's are summed behind it."""
    n, s, dk = q.shape
    dv = v.shape[-1]
    group = n // k.shape[0]
    delta = jnp.sum(do * o, axis=-1).reshape(n, 1, s)
    whole = lambda width: pl.BlockSpec((None, s, width),
                                       lambda h, j: (h, 0, 0))
    block = lambda width: pl.BlockSpec((None, BLOCK, width),
                                       lambda h, j: (h, j, 0))
    shared = lambda width: pl.BlockSpec((None, BLOCK, width),
                                        lambda h, j: (h // group, j, 0))
    row = pl.BlockSpec((None, 1, s), lambda h, j: (h, 0, 0))
    per_head = k.dtype if group == 1 else jnp.float32
    dq, dk_, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, block=BLOCK, blocks=s // BLOCK),
        grid=(n, s // BLOCK),
        in_specs=[whole(dk), shared(dk), shared(dv), whole(dv), row, row],
        out_specs=[whole(dk), block(dk), block(dv)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, s, dk), per_head),
                   jax.ShapeDtypeStruct((n, s, dv), per_head)],
        scratch_shapes=[pltpu.VMEM((s, dk), jnp.float32),
                        pltpu.VMEM((BLOCK, dk), jnp.float32),
                        pltpu.VMEM((BLOCK, dv), jnp.float32)],
        compiler_params=_params(),
        name="causal_attention_bwd",
    )(q, k, v, do.astype(q.dtype), lse.reshape(n, 1, s), delta)
    if group > 1:
        dk_, dv_ = (jnp.sum(x.reshape((n // group, group) + x.shape[1:]),
                            axis=1).astype(k.dtype) for x in (dk_, dv_))
    return dq, dk_, dv_
