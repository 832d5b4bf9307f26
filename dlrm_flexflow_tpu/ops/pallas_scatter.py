"""Pallas TPU kernel: in-place sparse row update of an embedding table.

TPU-native replacement for the reference's scatter-add backward +
in-place SGD kernel pair on embedding tables (reference:
src/ops/embedding.cu:199-224 atomicAdd scatter, optimizer_kernel.cu:23-43
sgd_update).  XLA:TPU's scatter emitter forces its own operand layout and
wraps the update in FULL-TABLE layout copies (see PERF.md), so the
row-sparse SGD path is implemented as a hand-written kernel instead:

  table[ids[k]] += scale * updates[k]        (duplicates accumulate)

- The table stays in HBM and is updated IN PLACE via
  ``input_output_aliases`` — per step only the touched rows move.
- ids arrive SORTED (the wrapper sorts); duplicate ids form adjacent
  runs.  Within a block the kernel chains run accumulation sequentially
  on the VPU; only the LAST slot of each run writes back, so duplicate
  writebacks can never race.  Runs crossing a block boundary are carried
  in a VMEM scratch (grid steps execute sequentially on TPU).
- Row DMAs of one block are all started before any is awaited, so the
  fetch latency overlaps.

The wrapper falls back to ``table.at[ids].add`` off-TPU (and in tests via
interpret mode the kernel itself is exercised).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the static dispatch gate for the set kernel vs the scatter emitter
# lives in the UNIFIED cost module since the fused-interaction kernel
# joined the row-set/row-update family: one set of measured machine
# constants, three gates (ops/kernel_costs.py).  Re-exported here so
# the round-5 call sites and tests keep their import path.
from .kernel_costs import row_set_wins  # noqa: F401  (re-export)

_BLOCK = int(__import__("os").environ.get("FF_SCATTER_BLOCK", 16))
# ^ update slots per grid step (unrolled in-kernel); env-overridable for
#   block-size sweeps on real hardware (scripts/ab_scatter.py)
_PIPELINE = __import__("os").environ.get(
    "FF_SCATTER_PIPELINE", "1").strip().lower() not in ("0", "off",
                                                        "false", "no")
# ^ software-pipelined kernel (_row_update_kernel_v2), DEFAULT since
#   round 3: the on-hardware stress suite (scripts/stress_scatter.py —
#   adversarial duplicate runs straddling every block boundary,
#   whole-stream runs, all-unique writeback load, and a 20x determinism
#   hammer) passed bit-exactly on the real chip on 2026-07-31,
#   confirming the cross-step DMA no-race argument that interpret mode
#   cannot model (see _row_update_kernel_v2's docstring for the
#   argument itself).  FF_SCATTER_PIPELINE=0 restores the serial v1.
_IMPL = __import__("os").environ.get("FF_SCATTER_IMPL", "auto")
# ^ TPU sparse-update implementation (A/B on real hardware):
#   "auto"   — lane-packed XLA scatter-add on the (R/pack, 128) view
#              (default: measured 14x faster than the pallas kernel on the
#              bench slice — the packed view aligns the gather's and the
#              scatter's preferred table layouts, see PERF.md)
#   "kernel" — the in-place pallas row-update kernel
#   "xla"    — direct table.at[ids].add on the logical (R, d) shape
#              (slow when a gather of the same table sits in the program:
#              the layout conflict materializes full-table copies)


def _row_update_kernel(ids_ref, table_hbm, upd_ref, out_hbm,
                       scratch, acc_ref, carry_ref, sems, out_sems,
                       *, block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    base = blk * block

    # ---- fetch all rows of this block (overlapped DMAs) ------------------
    # rows are moved as 2-D (1, d) slices: 1-D (d,) row refs hit a Mosaic
    # lowering bug for d < 128
    def fetch(k):
        return pltpu.make_async_copy(
            out_hbm.at[pl.ds(ids_ref[base + k], 1)],
            scratch.at[pl.ds(k, 1)], sems.at[k])

    for k in range(block):
        fetch(k).start()
    for k in range(block):
        fetch(k).wait()

    # ---- sequential run accumulation -------------------------------------
    # acc_k = prev_acc + u_k   when ids[k] == ids[k-1]  (same run)
    #       = fetched_k + u_k  otherwise                (new run)
    # slot 0 continues the carry when the run crosses the block boundary
    for k in range(block):
        g = base + k
        u = upd_ref[k, :]
        if k == 0:
            prev = carry_ref[0, :]
            # clamp so grid step 0 never reads before the ids buffer (the
            # blk > 0 mask discards the value, not the load)
            prev_id = ids_ref[jnp.maximum(base - 1, 0)]
            same = (blk > 0) & (ids_ref[base] == prev_id)
        else:
            prev = acc_ref[k - 1, :]
            same = ids_ref[g] == ids_ref[g - 1]
        fetched = scratch[k, :]
        acc_ref[k, :] = jnp.where(same, prev, fetched) + u

    carry_ref[0, :] = acc_ref[block - 1, :]

    # ---- write back only the last slot of each run -----------------------
    # run-last <=> next id differs; ids_ref is padded with a sentinel at
    # position n, so slot n-1 is always run-last
    def wb(k):
        return pltpu.make_async_copy(
            acc_ref.at[pl.ds(k, 1)],
            out_hbm.at[pl.ds(ids_ref[base + k], 1)],
            out_sems.at[k])

    for k in range(block):
        g = base + k

        @pl.when(ids_ref[g] != ids_ref[g + 1])
        def _():
            wb(k).start()

    for k in range(block):
        g = base + k

        @pl.when(ids_ref[g] != ids_ref[g + 1])
        def _():
            wb(k).wait()


def _row_update_kernel_v2(ids_ref, table_hbm, upd_ref, out_hbm,
                          scratch, acc_ref, carry_ref, sems, out_sems,
                          *, block: int, nblocks: int):
    """Software-pipelined variant: row fetches for block b+1 and row
    writebacks of block b both overlap block b+1's compute.

    Why cross-step overlap cannot race: ids are sorted, so a row id
    appearing in two different blocks fills every slot between them —
    its run crosses the intermediate block boundaries and is CARRIED, not
    written back, until the run's final block.  Hence a row fetched in
    step b never has an outstanding writeback from any earlier step, and
    a writeback started in step b targets a row no later step fetches.
    Buffers and semaphores are double-buffered by grid-step parity; the
    only waits on the critical path are this step's own fetches (started
    one step ahead) and the buffer-reuse wait for writebacks started two
    steps ago."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    p = blk % 2
    q = 1 - p
    base = blk * block

    def fetch(b, k, buf):
        return pltpu.make_async_copy(
            out_hbm.at[pl.ds(ids_ref[b * block + k], 1)],
            scratch.at[buf, pl.ds(k, 1)], sems.at[buf, k])

    def wb(b, k, buf):
        return pltpu.make_async_copy(
            acc_ref.at[buf, pl.ds(k, 1)],
            out_hbm.at[pl.ds(ids_ref[b * block + k], 1)],
            out_sems.at[buf, k])

    # prologue: nothing prefetched our first block
    @pl.when(blk == 0)
    def _():
        for k in range(block):
            fetch(0, k, 0).start()

    for k in range(block):
        fetch(blk, k, p).wait()

    # prefetch the next block into the other buffer
    @pl.when(blk + 1 < nblocks)
    def _():
        for k in range(block):
            fetch(blk + 1, k, q).start()

    # before overwriting acc[p], drain writebacks issued from it 2 steps ago
    @pl.when(blk >= 2)
    def _():
        for k in range(block):
            g = (blk - 2) * block + k

            @pl.when(ids_ref[g] != ids_ref[g + 1])
            def _():
                wb(blk - 2, k, p).wait()

    for k in range(block):
        g = base + k
        u = upd_ref[k, :]
        if k == 0:
            prev = carry_ref[0, :]
            prev_id = ids_ref[jnp.maximum(base - 1, 0)]
            same = (blk > 0) & (ids_ref[base] == prev_id)
        else:
            prev = acc_ref[p, k - 1, :]
            same = ids_ref[g] == ids_ref[g - 1]
        fetched = scratch[p, k, :]
        acc_ref[p, k, :] = jnp.where(same, prev, fetched) + u

    carry_ref[0, :] = acc_ref[p, block - 1, :]

    for k in range(block):
        g = base + k

        @pl.when(ids_ref[g] != ids_ref[g + 1])
        def _():
            wb(blk, k, p).start()

    # epilogue: drain everything still in flight (parity q from blk-1 has
    # not been waited; parity p from blk was just started)
    @pl.when(blk == nblocks - 1)
    def _():
        @pl.when(blk >= 1)
        def _():
            for k in range(block):
                g = (blk - 1) * block + k

                @pl.when(ids_ref[g] != ids_ref[g + 1])
                def _():
                    wb(blk - 1, k, q).wait()

        for k in range(block):
            g = blk * block + k

            @pl.when(ids_ref[g] != ids_ref[g + 1])
            def _():
                wb(blk, k, p).wait()


def _row_update_pallas(table, ids_sorted, upd_sorted, interpret=False,
                       pipeline=None):
    """table (R, d) f32; ids_sorted (n,) int32 ascending (padded tail
    repeats the last id with zero updates); upd_sorted (n, d).  Returns
    the updated table, aliased in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = upd_sorted.shape
    assert n % _BLOCK == 0, f"n={n} must divide by {_BLOCK}"
    # sentinel pad so ids_ref[g + 1] is valid at g = n - 1
    ids_padded = jnp.concatenate(
        [ids_sorted, jnp.full((1,), -1, jnp.int32)])

    nblocks = n // _BLOCK
    if pipeline is None:
        pipeline = _PIPELINE
    if pipeline:
        kern = functools.partial(_row_update_kernel_v2, block=_BLOCK,
                                 nblocks=nblocks)
        scratch_shapes = [
            pltpu.VMEM((2, _BLOCK, d), table.dtype),  # fetched rows (x2)
            pltpu.VMEM((2, _BLOCK, d), table.dtype),  # accumulated (x2)
            pltpu.VMEM((1, d), table.dtype),          # cross-block carry
            pltpu.SemaphoreType.DMA((2, _BLOCK)),
            pltpu.SemaphoreType.DMA((2, _BLOCK)),
        ]
    else:
        kern = functools.partial(_row_update_kernel, block=_BLOCK)
        scratch_shapes = [
            pltpu.VMEM((_BLOCK, d), table.dtype),   # fetched rows
            pltpu.VMEM((_BLOCK, d), table.dtype),   # accumulated rows
            pltpu.VMEM((1, d), table.dtype),        # cross-block carry
            pltpu.SemaphoreType.DMA((_BLOCK,)),
            pltpu.SemaphoreType.DMA((_BLOCK,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ids
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table (HBM)
            pl.BlockSpec((_BLOCK, d), lambda b, ids: (b, 0)),  # updates
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # aliased table
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={1: 0},  # table input -> output, in place
        interpret=interpret,
    )(ids_padded, table, upd_sorted)


def lane_compatible(dim: int) -> bool:
    """d fits the 128-lane packed view (d | 128 or 128 | d).  Weaker than
    ``pack_factor`` > 0: the epoch row-cache only needs ITS OWN row count
    to divide the pack (it rounds it up itself), not the table's."""
    if dim >= 128:
        return dim % 128 == 0
    return 128 % dim == 0


def lane_pack(dim: int) -> int:
    """Rows per 128-lane view row by DIM alone (for sizing structures
    whose row count the caller rounds up itself, e.g. the epoch
    row-cache); 1 when the dim is not lane-compatible."""
    if dim < 128 and 128 % dim == 0:
        return 128 // dim
    return 1


def pack_factor(num_rows: int, dim: int) -> int:
    """Rows per 128-lane view row for the lane-packed table view, or 0
    when the (num_rows, dim) table cannot be viewed as (R/pack, 128*k)
    with a free row-major bitcast.  (One lane rule: lane_compatible +
    lane_pack; this adds the table-row divisibility requirement.)"""
    if not lane_compatible(dim):
        return 0
    pack = lane_pack(dim)
    return pack if num_rows % pack == 0 else 0


def packed_gather(table, ids):
    """``table[ids]`` read through the lane-packed (R/pack, 128) view.

    Numerically identical to ``jnp.take(table, ids, axis=0)`` (pure data
    movement), but keeps the table in the SAME layout the packed scatter
    update uses — gathering the logical (R, d<128) shape instead makes
    XLA pick conflicting layouts for gather vs scatter and materialize
    full-table copies every step (PERF.md).  ``ids`` may have any shape;
    returns ``ids.shape + (d,)`` rows."""
    r, d = table.shape
    pack = pack_factor(r, d)
    if pack <= 1:
        return jnp.take(table, ids, axis=0)
    return view_gather(table.reshape(r // pack, d * pack), ids, d)


def view_gather(view, ids, d: int):
    """Logical (..., d) rows from a PACKED (Rv, pack*d) storage array.

    The packed-STORAGE twin of ``packed_gather``: the table physically
    lives as 128-lane view rows (pack = view cols / d logical rows per
    view row), so no (R, d<128) array — whose T(8,128) tiling pads half
    the lanes and whose reshapes/layout conversions therefore cost
    full-table shuffles (PERF.md round 3) — ever exists on device."""
    pack = view.shape[-1] // d
    if pack <= 1:
        return jnp.take(view, ids, axis=0)
    # FLAT select-then-reshape: the gather, the half-select, and the
    # final reshape all run on (n, ...) 2-D/3-D forms.  The earlier
    # ids.shape + (pack, d) 5-D form made XLA tile the intermediates
    # T(2,128) and insert per-step layout copies around the select
    # (~7 us/step of pure data formatting at the headline shape,
    # round-5 trace: reshape.445 + copy.145/146).
    q = ids.reshape(-1) // pack
    h = (ids.reshape(-1) % pack).astype(jnp.int32)
    vrows = jnp.take(view, q, axis=0)          # (n, pack*d)
    vrows = vrows.reshape(-1, pack, d)
    # half-select as a WHERE chain, not take_along_axis: the dynamic
    # gather compiled to its own latency-bound kernel (~15 us/step at
    # the headline shape, 36 GB/s — round-4 trace); selects fuse into
    # the surrounding computation.  Pure data routing either way —
    # bit-exact, and safe for any lane contents (no 0*x arithmetic).
    # The chain is O(pack) sequential selects, so small-dim tables
    # (large pack) keep the single-gather form.
    if pack > 4:
        out = jnp.take_along_axis(
            vrows, h[:, None, None], axis=-2).squeeze(-2)
        return out.reshape(ids.shape + (d,))
    out = vrows[:, 0, :]
    for i in range(1, pack):
        out = jnp.where((h == i)[:, None], vrows[:, i, :], out)
    return out.reshape(ids.shape + (d,))


def _expand_lanes(ids_flat, upd_flat, pack, dtype):
    """THE one-hot lane expansion every packed write path shares:
    (q, packed) where q = view row per update and ``packed`` is the
    128-lane row with the (d,) update in its slot and exact 0.0
    elsewhere.  packed-XLA and kernel paths must stay numerically
    identical, so they all call this."""
    n, d = upd_flat.shape
    q = ids_flat // pack
    h = ids_flat % pack
    lanes = jax.nn.one_hot(h, pack, dtype=dtype)           # (n, pack)
    packed = (lanes[:, :, None] * upd_flat[:, None, :]).reshape(
        n, d * pack)
    return q, packed


def view_scatter_add(view, ids, upd, d: int):
    """``view[logical ids] += upd`` on a PACKED (Rv, pack*d) storage
    array: each (d,) update lands in its slot of the 128-lane view row
    via a one-hot expansion (other slots add exact 0.0); duplicates
    accumulate.  The packed-storage twin of ``packed_scatter_add``."""
    pack = view.shape[-1] // d
    ids_flat = ids.reshape(-1).astype(jnp.int32)
    upd_flat = upd.reshape(-1, d).astype(view.dtype)
    if pack <= 1:
        return view.at[ids_flat].add(upd_flat)
    q, packed = _expand_lanes(ids_flat, upd_flat, pack, view.dtype)
    return view.at[q].add(packed)


def sparse_view_update(view, ids, updates, scale, *, d: int,
                       interpret=False, force=False, allow_kernel=True,
                       pipeline=None):
    """``sparse_row_update`` for PACKED (Rv, pack*d) storage: logical
    ids, (..., d) updates, duplicate accumulation; the in-place pallas
    kernel applies directly to the 128-lane view rows when selected."""
    pack = view.shape[-1] // d
    if pack <= 1:
        return sparse_row_update(view, ids, updates, scale,
                                 interpret=interpret, force=force,
                                 allow_kernel=allow_kernel,
                                 pipeline=pipeline)
    ids_flat = ids.reshape(-1).astype(jnp.int32)
    upd_flat = (scale * updates.reshape(-1, d)).astype(view.dtype)
    n = ids_flat.shape[0]
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = force or interpret or (
        allow_kernel and _IMPL == "kernel" and on_tpu)
    if use_kernel and n % _BLOCK == 0:
        q, packed = _expand_lanes(ids_flat, upd_flat, pack, view.dtype)
        order = jnp.argsort(q)
        return _row_update_pallas(view, q[order], packed[order],
                                  interpret=interpret, pipeline=pipeline)
    return view_scatter_add(view, ids_flat, upd_flat, d)


def use_packed_view(mesh) -> bool:
    """THE predicate for the lane-packed table view: gather_rows and the
    scatter update must answer identically or XLA picks conflicting
    table layouts and re-materializes full-table copies every step.
    Single-device TPU only (under a mesh the packed view fights the
    sharded layout), and only for the default packed-XLA impl."""
    return (mesh is None and _IMPL == "auto"
            and jax.default_backend() == "tpu")


def _lane_pack(table, ids_flat, upd_flat, pack):
    """Lane-pack expansion against a LOGICAL (R, d) table: the
    (R/pack, 128) view plus ``_expand_lanes``' (q, packed)."""
    r, d = table.shape
    q, packed = _expand_lanes(ids_flat, upd_flat, pack, table.dtype)
    return table.reshape(r // pack, d * pack), q, packed


def packed_scatter_add(table, ids_flat, upd_flat):
    """``table.at[ids].add(upd)`` through the lane-packed view: each
    (d,) update lands in its slot of the 128-lane view row via a one-hot
    expansion (the other slots add exact 0.0).  Duplicates accumulate."""
    r, d = table.shape
    pack = pack_factor(r, d)
    if pack <= 1:
        return table.at[ids_flat].add(upd_flat)
    view, q, packed = _lane_pack(table, ids_flat, upd_flat, pack)
    return view.at[q].add(packed).reshape(r, d)


def _row_set_kernel(ids_ref, table_hbm, src_ref, out_hbm, sems,
                    *, block: int, num_rows: int):
    """Per-row SET: out[ids[k]] = src[k] for DISTINCT ids; out-of-range
    ids (< 0 or >= num_rows) are dropped (advisor r5: the previous
    >= num_rows-only predicate would have issued an out-of-bounds HBM
    DMA for a negative id).  Callers never produce negative ids — the
    writeback plans pad with sentinel R — so bit-identity with the
    emitter path holds on all real inputs; the lower bound is the
    defensive guard (note jnp's ``mode="drop"`` python-WRAPS -1 to the
    last row, which a corrupt id must not silently do either).  No
    fetch, no run accumulation — the source block arrives in VMEM via
    the BlockSpec pipeline and each live row leaves as one async DMA.
    Distinctness is the caller's contract (duplicate ids would race)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    base = blk * block

    def wb(k):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(k, 1)],
            out_hbm.at[pl.ds(ids_ref[base + k], 1)],
            sems.at[k])

    def live(k):
        return (ids_ref[base + k] >= 0) & (ids_ref[base + k] < num_rows)

    for k in range(block):
        @pl.when(live(k))
        def _():
            wb(k).start()
    for k in range(block):
        @pl.when(live(k))
        def _():
            wb(k).wait()


def _row_set_pallas(table, ids, rows, interpret=False):
    """``table[ids[k]] = rows[k]`` for DISTINCT int32 ids (sentinel
    >= R entries dropped), aliased in place — the low-density epilogue
    writeback (round 5).  XLA's scatter emitter RMW-SWEEPS the parent
    at a density-scaled useful rate, so setting 8k rows of a 2 GB
    table costs ~6.1 ms (measured, dlrm_hybrid epilogue); per-row DMAs
    pay ~64 ns/row instead and win whenever the touched rows are a
    small fraction of the parent (the dispatch gate lives in
    row_cache.py's _cache_writeback)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, d = table.shape
    n = ids.shape[0]
    pad = (-n) % _BLOCK
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((pad,), R, jnp.int32)])  # sentinel: dropped
        # (negative ids are dropped too — same mode="drop" semantics)
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, d), rows.dtype)])
        n += pad
    nblocks = n // _BLOCK
    kern = functools.partial(_row_set_kernel, block=_BLOCK, num_rows=R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ids
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table (HBM)
            pl.BlockSpec((_BLOCK, d), lambda b, ids: (b, 0)),  # rows
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # aliased table
        scratch_shapes=[pltpu.SemaphoreType.DMA((_BLOCK,))],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={1: 0},  # table input -> output, in place
        interpret=interpret,
    )(ids.astype(jnp.int32), table, rows.astype(table.dtype))




def supports_pallas_row_update(num_rows: int, dim: int, n: int) -> bool:
    """Static eligibility of the kernel for a (num_rows, dim) table with
    ``n`` updates per step (Mosaic needs 128-lane rows; narrower dims are
    packed, which needs both 128 % dim == 0 and num_rows % pack == 0)."""
    if n % _BLOCK != 0:
        return False
    if dim >= 128:
        return dim % 128 == 0
    if 128 % dim != 0:
        return False
    return num_rows % (128 // dim) == 0


def sparse_row_update(table, ids, updates, scale, *, interpret=False,
                      force=False, allow_kernel=True, pipeline=None):
    """``table[ids] += scale * updates`` with duplicate accumulation.

    table (R, d); ids (...,) int; updates (..., d).  Uses the pallas
    in-place kernel on TPU (or when forced/interpreted); otherwise the
    plain XLA scatter-add.

    Mosaic requires 128-lane row slices, so tables with d < 128 (and
    128 % d == 0) are viewed as (R/pack, d*pack) — a free row-major
    bitcast — and each update lands in its half/quarter row via a
    padded 128-lane update vector; duplicate-run accumulation then keys
    on VIEW rows, which also serializes updates to neighboring packed
    rows (they share a view row and would otherwise race on writeback).
    """
    r, d = table.shape
    ids_flat = ids.reshape(-1).astype(jnp.int32)
    upd_flat = (scale * updates.reshape(-1, d)).astype(table.dtype)
    n = ids_flat.shape[0]
    # allow_kernel=False (e.g. a sharded table under a mesh — SPMD cannot
    # partition a pallas_call; the packed view would also fight the
    # sharded layout) forces the XLA scatter path
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = force or interpret or (
        allow_kernel and _IMPL == "kernel" and on_tpu)
    if not (use_kernel and supports_pallas_row_update(r, d, n)):
        # allow_kernel is the caller's mesh-is-None bit, so
        # allow_kernel + use_packed_view(None) == use_packed_view(mesh) —
        # the same predicate gather_rows uses (layouts must agree)
        if (allow_kernel and not interpret and use_packed_view(None)
                and pack_factor(r, d)):
            return packed_scatter_add(table, ids_flat, upd_flat)
        return table.at[ids_flat].add(upd_flat)
    pack = 1 if d >= 128 else 128 // d
    if pack > 1:
        view, q, packed = _lane_pack(table, ids_flat, upd_flat, pack)
        order = jnp.argsort(q)
        out = _row_update_pallas(view, q[order], packed[order],
                                 interpret=interpret, pipeline=pipeline)
        return out.reshape(r, d)
    order = jnp.argsort(ids_flat)
    return _row_update_pallas(table, ids_flat[order], upd_flat[order],
                              interpret=interpret, pipeline=pipeline)
