"""Pallas TPU kernel: fused embedding-bag -> feature-interaction.

The DLRM hot path is gather -> pool -> interact (reference
dlrm.cc:122-138; apps/dlrm.py::_interact_features): per-table embedding
rows are gathered and bag-pooled, then the pooled per-table vectors
meet the bottom-MLP output in the interaction — ``cat`` (concat) or
``dot`` (pairwise dots).  Unfused, XLA runs this as separate ops with a
materialized ``(batch, num_tables, dim)`` intermediate bounced through
HBM (plus the ``(batch, F, F)`` pairwise product for ``dot``), because
the gather is a fusion root it cannot fuse across.

This kernel streams the embedding rows from HBM straight through a
VMEM scratch (per-row async DMAs, start-all-then-wait like
``pallas_embedding._bag_kernel``), pools each bag on the VPU, and
feeds the pooled vectors DIRECTLY into the interaction — the pooled
intermediate never exists in HBM.  For ``dot`` the pairwise products
run as one small batched ``jnp.matmul`` per block (the MXU primitive
the unfused BatchMatmul op uses, so the two paths stay bit-exact).

Dropped-id semantics (parity with the row-set kernel, PR 1 advisor
r5): an id that is negative or out of its table's range is DROPPED —
its slot contributes exact 0.0 to the pool, and no HBM DMA is ever
issued for it.  ``mask_local_ids`` encodes the rule once (invalid ->
-1) so the kernel and the emitter reference path below cannot
disagree; ``tests/test_kernels.py`` pins both.

Dispatch is eligibility- then cost-model gated.  Eligibility is what
the compiler accepts (``kernel_eligible``): on the chip the forward
kernel compiles only for whole 128-lane rows (d % 128 == 0 — the app's
d = 64 is refused by Mosaic) and the backward kernel not at all
(``bwd_kernel_eligible``), so compiled programs differentiate a kernel
forward through the emitter VJP.  The cost gate
(``ops/kernel_costs.fused_interact_wins`` — the same constants as the
row-set gate): per-row DMAs are latency-bound, so the kernel wins only
where the unfused chain's fusion-boundary overheads and intermediate
bounce dominate (the small serving buckets); the training headline
keeps XLA's batched gather pipeline.  Off-TPU the reference path runs;
tests exercise both kernels in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_B = 8  # samples per grid step (min f32 sublane tile)


def mask_local_ids(idx, offsets, row_counts):
    """Per-table LOCAL ids ``(..., T, bag)`` -> flat global row ids
    with every invalid entry (negative, or >= its table's row count)
    mapped to -1.  THE dropped-id rule shared by the kernel (-1 slots
    fetch nothing and pool as 0.0) and the reference path (masked
    gather) — one encoding, so the two cannot drift."""
    rc = jnp.asarray(row_counts, dtype=idx.dtype)[:, None]
    off = jnp.asarray(offsets, dtype=idx.dtype)[:, None]
    valid = (idx >= 0) & (idx < rc)
    return jnp.where(valid, idx + off, jnp.array(-1, idx.dtype))


def interact_width(interact: str, num_tables: int, dim: int,
                   bot_dim: int) -> int:
    """Output feature width of the fused op."""
    if interact == "cat":
        return bot_dim + num_tables * dim
    if interact == "dot":
        f = num_tables + 1
        return dim + f * f
    raise ValueError(f"unknown interaction op {interact!r}")


def pool_rows(rows, aggr: str, out_dtype):
    """Bag-pool pre-gathered rows ``(B, T, bag, d)`` -> ``(B, T, d)``
    with the SAME reduce formulation on every path (bit-exactness
    demands one summation): ``jnp.sum`` over the bag axis, ``avg``
    divides by the static bag.  An EMPTY bag (bag == 0) pools to exact
    0.0 for both modes (the mean of nothing must not be NaN)."""
    b, t, bag, d = rows.shape
    if bag == 0:
        return jnp.zeros((b, t, d), out_dtype)
    pooled = jnp.sum(rows, axis=2)
    if aggr == "avg":
        pooled = pooled / bag
    return pooled.astype(out_dtype)


def _pairwise_dots(z, compute_dtype):
    """``z @ z^T`` exactly as BatchMatmul.forward computes it — incl.
    the bf16 operand cast under ``compute_dtype='bfloat16'`` with f32
    accumulation — so fused 'dot' stays bit-exact vs the classic graph
    at EITHER compute precision."""
    zt = jnp.swapaxes(z, -1, -2)
    if compute_dtype in ("bfloat16", jnp.bfloat16):
        z = z.astype(jnp.bfloat16)
        zt = zt.astype(jnp.bfloat16)
    return jnp.matmul(z, zt, preferred_element_type=jnp.float32)


def interact_features(bottom, pooled, interact: str, compute_dtype=None):
    """The interaction on pooled per-table vectors — the exact jnp
    formulation the UNFUSED graph ops compute (apps/dlrm.py
    ``_interact_features``: Concat / Reshape + BatchMatmul + Flat +
    Concat), so A/B against the emitter path is bit-exact.

    bottom ``(B, bot_dim)``, pooled ``(B, T, d)``; ``compute_dtype``
    is the model's MXU precision (BatchMatmul's cast, dot only)."""
    b, t, d = pooled.shape
    if interact == "cat":
        return jnp.concatenate([bottom, pooled.reshape(b, t * d)], axis=1)
    if interact == "dot":
        # z = [bottom; pooled] (B, F, d); zz = z @ z^T via the same
        # primitive BatchMatmul.forward lowers to; flat(zz) row-major —
        # Flat.forward's reshape
        z = jnp.concatenate([bottom[:, None, :], pooled], axis=1)
        zz = _pairwise_dots(z, compute_dtype).astype(bottom.dtype)
        return jnp.concatenate([bottom, zz.reshape(b, (t + 1) * (t + 1))],
                               axis=1)
    raise ValueError(f"unknown interaction op {interact!r}")


def masked_pool_interact(rows, gids, bottom, interact: str, aggr: str,
                         out_dtype=jnp.float32, compute_dtype=None):
    """THE shared tail of every emitter-side path: zero the dropped
    slots (``gids`` < 0, see ``mask_local_ids``), pool, interact.
    ``fused_interact_ref`` and the op's packed/quantized forward both
    call this, so the kernel's A/B target and the op's emitter branch
    can never drift apart."""
    rows = jnp.where((gids >= 0)[..., None], rows,
                     jnp.zeros((), rows.dtype))
    pooled = pool_rows(rows, aggr, out_dtype)
    return interact_features(bottom.astype(out_dtype), pooled, interact,
                             compute_dtype)


def fused_interact_ref(table, gids, bottom, *, interact: str = "cat",
                       aggr: str = "sum", out_dtype=jnp.float32,
                       compute_dtype=None):
    """The emitter REFERENCE path: masked gather -> pool -> interact,
    all plain XLA ops.  ``gids`` are pre-masked flat ids (invalid =
    -1, see ``mask_local_ids``); a dropped id contributes exact 0.0 —
    the kernel's semantics, asserted bit-equal in interpret mode."""
    safe = jnp.maximum(gids, 0).astype(jnp.int32)
    rows = jnp.take(table, safe, axis=0)              # (B, T, bag, d)
    return masked_pool_interact(rows, gids, bottom, interact, aggr,
                                out_dtype, compute_dtype)


def _fused_kernel(ids_ref, table_hbm, bottom_ref, out_ref, scratch, sems,
                  *, num_tables: int, bag: int, dim: int, bot_dim: int,
                  interact: str, aggr: str, block_b: int, num_rows: int,
                  compute_dtype=None):
    """One grid step = ``block_b`` samples: start every live row DMA
    (all in flight together), zero the dropped slots, wait, pool each
    bag on the VPU, interact, write the block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    nslots = num_tables * bag

    def row_id(i, s):
        return ids_ref[blk * block_b + i, s]

    def dma(i, s):
        slot = i * nslots + s
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(row_id(i, s), 1)],
            scratch.at[pl.ds(slot, 1)], sems.at[slot])

    def live(i, s):
        # ids are pre-masked to -1 by mask_local_ids; the upper bound
        # is the same defensive guard the row-set kernel carries (a
        # corrupt id must never issue an out-of-bounds HBM DMA)
        return (row_id(i, s) >= 0) & (row_id(i, s) < num_rows)

    for i in range(block_b):
        for s in range(nslots):
            @pl.when(live(i, s))
            def _():
                dma(i, s).start()

            @pl.when(jnp.logical_not(live(i, s)))
            def _():
                # dropped id: the slot pools as exact 0.0
                scratch[pl.ds(i * nslots + s, 1), :] = jnp.zeros(
                    (1, dim), scratch.dtype)
    for i in range(block_b):
        for s in range(nslots):
            @pl.when(live(i, s))
            def _():
                dma(i, s).wait()

    # pool each sample's bags with the SAME reduce the reference path
    # uses (jnp.sum over the bag axis), then interact in-register
    pooled = []
    for i in range(block_b):
        bags = scratch[pl.ds(i * nslots, nslots), :]
        bags = bags.reshape(num_tables, bag, dim)
        pt = jnp.sum(bags, axis=1)
        if aggr == "avg":
            pt = pt / bag
        pooled.append(pt.astype(out_ref.dtype))
    pooled_blk = jnp.stack(pooled)                    # (block_b, T, d)
    bottom_blk = bottom_ref[:, :].astype(out_ref.dtype)

    if interact == "cat":
        out_ref[:, pl.ds(0, bot_dim)] = bottom_blk
        out_ref[:, pl.ds(bot_dim, num_tables * dim)] = pooled_blk.reshape(
            block_b, num_tables * dim)
    else:  # dot — the same batched-matmul primitive (and bf16 operand
        # cast under compute_dtype) as BatchMatmul
        f = num_tables + 1
        z = jnp.concatenate([bottom_blk[:, None, :], pooled_blk], axis=1)
        zz = _pairwise_dots(z, compute_dtype)
        out_ref[:, pl.ds(0, dim)] = bottom_blk
        out_ref[:, pl.ds(dim, f * f)] = zz.astype(out_ref.dtype).reshape(
            block_b, f * f)


def fused_interact_pallas(table, gids, bottom, *, interact: str = "cat",
                          aggr: str = "sum", interpret: bool = False,
                          compute_dtype=None):
    """Run the fused kernel.  ``table`` (R, d) f32; ``gids`` (B, T,
    bag) pre-masked flat ids (invalid = -1); ``bottom`` (B, bot_dim).
    Any batch size: B pads up to the 8-sample block with dropped-id
    rows and the padding is sliced back off."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, bag = gids.shape
    rows_n, dim = table.shape
    bot_dim = bottom.shape[1]
    assert bag > 0, "empty bags run the reference path (nothing to DMA)"
    if interact == "dot":
        assert bot_dim == dim, (
            f"dot interaction needs bottom width {dim}, got {bot_dim}")
    width = interact_width(interact, t, dim, bot_dim)
    block_b = _BLOCK_B
    pad = (-bsz) % block_b
    if pad:
        gids = jnp.concatenate(
            [gids, jnp.full((pad, t, bag), -1, gids.dtype)])
        bottom = jnp.concatenate(
            [bottom, jnp.zeros((pad, bot_dim), bottom.dtype)])
    bp = bsz + pad
    ids2 = gids.reshape(bp, t * bag).astype(jnp.int32)
    kern = functools.partial(
        _fused_kernel, num_tables=t, bag=bag, dim=dim, bot_dim=bot_dim,
        interact=interact, aggr=aggr, block_b=block_b, num_rows=rows_n,
        compute_dtype=compute_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ids
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
            pl.BlockSpec((block_b, bot_dim), lambda b, ids: (b, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, width), lambda b, ids: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_b * t * bag, dim), table.dtype),
            pltpu.SemaphoreType.DMA((block_b * t * bag,)),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, width), jnp.float32),
        interpret=interpret,
    )(ids2, table, bottom)
    return out[:bsz]


def kernel_eligible(table_dtype, dim: int, bag: int,
                    interpret: bool = False) -> bool:
    """Static shape/dtype eligibility of the fused FORWARD kernel: f32
    tables (bf16/quantized serving tables take the reference path —
    their numerics are tolerance-pinned, not bit-exact), a non-empty
    bag, and whole 128-lane rows.  Mosaic refuses the (1, d) row DMA
    below that ("Slice shape along dimension 1 must be aligned to tiling
    (128), but is 64" — TPU v5e, jax 0.9.0, PERF.md PR 21); at
    d % 128 == 0 the kernel compiles and is bit-exact against the
    emitter on the chip (cat and dot, bag 1 and 4).  The interpreter
    has no lane tiling and keeps the 8-multiple rule the tests use."""
    lanes = 8 if interpret else 128
    return (jnp.dtype(table_dtype) == jnp.float32 and bag > 0
            and dim % lanes == 0)


def bwd_kernel_eligible(interpret: bool, compute_dtype=None) -> bool:
    """Whether the custom VJP runs the fused BACKWARD kernel.  Mosaic
    refuses it at every shape tried on the chip ("infer-vector-layout:
    unsupported shape cast" at d = 64 and 128, "Input offsets outside of
    the first tile" at bag 4 — TPU v5e, jax 0.9.0, PERF.md PR 21), so a
    compiled program never selects it: the backward of a kernel forward
    is the emitter VJP, which the interpret-mode tests pin bit-identical
    to the kernel.  The kernel stays for the interpreter (and for the
    perf_opt issue that repairs it from a trace); f32 only either way
    (the bf16 dot cast's autodiff chain stays on the emitter VJP)."""
    return interpret and compute_dtype is None


def interact_backward(g, bottom, pooled, interact: str):
    """Manual VJP of ``interact_features`` at f32, mirroring XLA
    autodiff primitive-for-primitive (concat VJP = slice; batched
    ``z @ z^T`` VJP = ``G @ z + (z^T G)^T`` as two matmuls + add) so
    the kernel backward is BIT-EXACT against ``jax.vjp`` of the
    emitter formulation — pinned in interpret mode by
    tests/test_kernels.py.  Returns ``(dbottom, dpooled)``.

    ``pooled`` may be None for ``cat`` (its dpooled is a pure slice of
    ``g`` — the backward never touches the table rows)."""
    if interact == "cat":
        bot_dim = bottom.shape[1]
        return g[:, :bot_dim], g[:, bot_dim:]  # dpooled (B, T*d) flat
    if interact != "dot":
        raise ValueError(f"unknown interaction op {interact!r}")
    b = g.shape[0]
    dim = bottom.shape[1]
    t = pooled.shape[1]
    f = t + 1
    G = g[:, dim:].reshape(b, f, f)
    z = jnp.concatenate([bottom[:, None, :], pooled], axis=1)  # (B,F,d)
    # zz = matmul(z, z^T): dz = G @ (z^T)^T  +  ((z)^T @ G)^T — the two
    # dot_general transposes autodiff emits, accumulated with one add
    dz = (jnp.matmul(G, z, preferred_element_type=jnp.float32)
          + jnp.swapaxes(
              jnp.matmul(jnp.swapaxes(z, -1, -2), G,
                         preferred_element_type=jnp.float32), -1, -2))
    dbottom = g[:, :dim] + dz[:, 0]
    return dbottom, dz[:, 1:]


def _fused_bwd_kernel(ids_ref, table_hbm, bottom_ref, g_ref, dbot_ref,
                      rowg_ref, scratch, sems, *, num_tables: int,
                      bag: int, dim: int, bot_dim: int, interact: str,
                      aggr: str, block_b: int, num_rows: int):
    """Backward twin of ``_fused_kernel``: one grid step = ``block_b``
    samples.  For ``dot`` the live rows stream HBM->VMEM exactly the
    way the forward does (per-row async DMAs, start-all-then-wait) to
    re-pool the residual-free pooled vectors; the interact backward
    then runs in-register (``interact_backward``'s formulation) and
    the per-slot row grads are written out as one contiguous block —
    dropped slots emit exact 0.0 so the caller's scatter-add leaves
    their clip-addressed rows untouched (the emitter-VJP semantics)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    nslots = num_tables * bag

    def row_id(i, s):
        return ids_ref[blk * block_b + i, s]

    def dma(i, s):
        slot = i * nslots + s
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(row_id(i, s), 1)],
            scratch.at[pl.ds(slot, 1)], sems.at[slot])

    def live(i, s):
        return (row_id(i, s) >= 0) & (row_id(i, s) < num_rows)

    bottom_blk = bottom_ref[:, :].astype(jnp.float32)
    g_blk = g_ref[:, :].astype(jnp.float32)

    if interact == "dot":
        # re-stream the rows to rebuild pooled (no residual bounced
        # through HBM) — the forward's DMA pattern verbatim
        for i in range(block_b):
            for s in range(nslots):
                @pl.when(live(i, s))
                def _():
                    dma(i, s).start()

                @pl.when(jnp.logical_not(live(i, s)))
                def _():
                    scratch[pl.ds(i * nslots + s, 1), :] = jnp.zeros(
                        (1, dim), scratch.dtype)
        for i in range(block_b):
            for s in range(nslots):
                @pl.when(live(i, s))
                def _():
                    dma(i, s).wait()
        pooled = []
        for i in range(block_b):
            bags = scratch[pl.ds(i * nslots, nslots), :]
            bags = bags.reshape(num_tables, bag, dim)
            pt = jnp.sum(bags, axis=1)
            if aggr == "avg":
                pt = pt / bag
            pooled.append(pt.astype(jnp.float32))
        pooled_blk = jnp.stack(pooled)                # (block_b, T, d)
        dbot, dpooled = interact_backward(g_blk, bottom_blk, pooled_blk,
                                          "dot")
    else:
        dbot, dpooled = interact_backward(g_blk, bottom_blk, None, "cat")
        dpooled = dpooled.reshape(block_b, num_tables, dim)

    if aggr == "avg":
        dpooled = dpooled / bag
    # expand pooled grads to per-slot row grads (sum VJP = broadcast),
    # zeroing dropped slots like the emitter's where-mask VJP
    rows = jnp.repeat(dpooled.reshape(block_b * num_tables, dim), bag,
                      axis=0)                         # (blk*T*bag, d)
    mask = []
    for i in range(block_b):
        for s in range(nslots):
            mask.append(live(i, s))
    rows = jnp.where(jnp.stack(mask)[:, None], rows,
                     jnp.zeros((), rows.dtype))
    dbot_ref[:, :] = dbot.astype(dbot_ref.dtype)
    rowg_ref[:, :] = rows.astype(rowg_ref.dtype)


def fused_interact_bwd_pallas(table, gids, bottom, g, *,
                              interact: str = "cat", aggr: str = "sum",
                              interpret: bool = False):
    """Run the backward kernel.  Inputs mirror the forward
    (``gids`` pre-masked, invalid = -1); ``g`` is the interaction
    output cotangent (B, width).  Returns ``(row_grads, dbottom)``
    with ``row_grads`` (B, T, bag, d) — exact 0.0 at dropped slots —
    for the caller's table scatter-add, and ``dbottom`` (B, bot_dim).
    f32 only (bf16-compute programs keep the emitter VJP)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, bag = gids.shape
    rows_n, dim = table.shape
    bot_dim = bottom.shape[1]
    assert bag > 0, "empty bags run the reference path (nothing to DMA)"
    width = interact_width(interact, t, dim, bot_dim)
    assert g.shape == (bsz, width), (g.shape, (bsz, width))
    block_b = _BLOCK_B
    pad = (-bsz) % block_b
    if pad:
        gids = jnp.concatenate(
            [gids, jnp.full((pad, t, bag), -1, gids.dtype)])
        bottom = jnp.concatenate(
            [bottom, jnp.zeros((pad, bot_dim), bottom.dtype)])
        g = jnp.concatenate([g, jnp.zeros((pad, width), g.dtype)])
    bp = bsz + pad
    nslots = t * bag
    ids2 = gids.reshape(bp, nslots).astype(jnp.int32)
    kern = functools.partial(
        _fused_bwd_kernel, num_tables=t, bag=bag, dim=dim,
        bot_dim=bot_dim, interact=interact, aggr=aggr, block_b=block_b,
        num_rows=rows_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ids
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
            pl.BlockSpec((block_b, bot_dim), lambda b, ids: (b, 0)),
            pl.BlockSpec((block_b, width), lambda b, ids: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, bot_dim), lambda b, ids: (b, 0)),
            pl.BlockSpec((block_b * nslots, dim), lambda b, ids: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b * nslots, dim), table.dtype),
            pltpu.SemaphoreType.DMA((block_b * nslots,)),
        ],
    )
    dbot, rowg = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bp, bot_dim), jnp.float32),
                   jax.ShapeDtypeStruct((bp * nslots, dim), jnp.float32)],
        interpret=interpret,
    )(ids2, table, bottom, g)
    return (rowg[:bsz * nslots].reshape(bsz, t, bag, dim),
            dbot[:bsz].astype(bottom.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def fused_embed_interact(table, gids, bottom, interact: str = "cat",
                         aggr: str = "sum", use_kernel: bool = False,
                         interpret: bool = False, compute_dtype=None):
    """Differentiable fused gather->pool->interact with the kernel/
    emitter dispatch already decided by the caller (the op consults
    ``kernel_eligible`` and ``kernel_costs.fused_interact_wins``).
    Backward: the fused backward kernel only where
    ``bwd_kernel_eligible`` allows it — today the interpreter, because
    Mosaic refuses the kernel on the chip; otherwise it re-derives
    through the reference formulation — identical to autodiff of the
    unfused graph, and pinned bit-exact against the kernel in interpret
    mode (the training fast path instead injects pre-gathered rows and
    never reaches this custom_vjp)."""
    if use_kernel:
        return fused_interact_pallas(table, gids, bottom,
                                     interact=interact, aggr=aggr,
                                     interpret=interpret,
                                     compute_dtype=compute_dtype)
    return fused_interact_ref(table, gids, bottom, interact=interact,
                              aggr=aggr, compute_dtype=compute_dtype)


def _fwd(table, gids, bottom, interact, aggr, use_kernel, interpret,
         compute_dtype):
    out = fused_embed_interact(table, gids, bottom, interact, aggr,
                               use_kernel, interpret, compute_dtype)
    return out, (table, gids, bottom)


def _bwd(interact, aggr, use_kernel, interpret, compute_dtype, res, g):
    table, gids, bottom = res
    if use_kernel and bwd_kernel_eligible(interpret, compute_dtype):
        # the fused backward kernel: per-slot row grads stream out of
        # VMEM, then ONE scatter-add touches exactly the looked-up
        # rows.  Same updates at the same indices as the emitter VJP's
        # take-transpose, so dtable is bit-identical.
        rowg, db = fused_interact_bwd_pallas(
            table, gids, bottom, g, interact=interact, aggr=aggr,
            interpret=interpret)
        safe = jnp.maximum(gids, 0).astype(jnp.int32)
        dt = jnp.zeros_like(table).at[safe].add(rowg)
        return dt, None, db
    _, vjp = jax.vjp(
        lambda t, b: fused_interact_ref(t, gids, b, interact=interact,
                                        aggr=aggr,
                                        compute_dtype=compute_dtype),
        table, bottom)
    dt, db = vjp(g)
    return dt, None, db


fused_embed_interact.defvjp(_fwd, _bwd)
