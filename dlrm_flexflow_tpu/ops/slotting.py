"""Sort-position slot assignment for the epoch row-cache.

The row-cache prologue must map every id occurrence of an epoch/chunk/
block to a cache slot such that all occurrences of the same table row
share ONE slot (coherence of cross-step updates), and produce the slot ->
row map for the cache fill and writeback.  ``jnp.unique(...,
return_inverse=True)`` does this but measures ~15 ms per prologue at the
bench shape (524k ids) on the TPU slice: the sort itself is ~1 ms — the
cost is the dense-rank inverse construction, which lowers to scalar
scatters (~3-6 ms each on this platform, PERF.md round 3).

The cache is statically sized by the OCCURRENCE count n (the distinct
count is data-dependent), so ranks are computed with sorts only:

  s, perm = sort((ids, iota))          # one sort pass carries both
  flag[k]  = s[k] != s[k-1]            # run starts
  rank     = cumsum(flag) - 1          # dense rank of position k's run
  slots    = sort((perm, rank))[1]     # back to original order: a sort
                                       # by a permutation replaces the
                                       # scalar scatter a rank-inverse
                                       # would need
  rowof    = sort(where(flag, s, sentinel))
                                       # distinct rows compacted to the
                                       # front, sentinel holes at the end

Unlike jnp.unique's inverse this costs no scalar scatters, and unlike
the round-3 first-position slotting (rank = cummax of run-first
positions, holes interleaved) the produced ``rowof`` is NON-DECREASING:
distinct rows ascending, then all sentinel holes.  That makes the cache
fill (gather at ``rowof``, mode="clip") read ascending rows, keeps the
live slots contiguous at the front of every cache, and — the round-3
continuation's point — lets the writeback scatter
(``.at[rowof].set(..., mode="drop")``) carry ``indices_are_sorted=True``,
which switches XLA:TPU's scatter emitter onto a path measured 3.8x
faster at the ladder's mid-level writeback shape (7.4 -> 28 GB/s,
scripts/ab_prologue_layout.py protocol).  The cached training path
stays bit-exact with the uncached one — the same adds hit the same
values in the same order, only the slot numbering changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def slot_rows(ids, num_rows: int):
    """(rowof, slots) for ``ids`` over the bounded row space
    [0, num_rows).

    ``rowof``: (n,) int32 where n = ids.size — ``rowof[p]`` is the table
    row cached in slot p for p < (distinct count), else the sentinel
    ``num_rows``; NON-DECREASING (distinct rows ascending, holes at the
    end).  ``slots``: ids.shape int32 — the slot (dense rank) of each
    occurrence; all occurrences of one row share one slot, and
    ``rowof[slots] == ids`` everywhere.  Requires 0 <= ids < num_rows.
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    # one sort pass carries the positions along with the keys
    s, perm = jax.lax.sort((flat, pos), num_keys=1, is_stable=False)
    flag = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])
    rank = jnp.cumsum(flag.astype(jnp.int32)) - 1
    # slots back in original order: sorting by the permutation is the
    # scatter ``out[perm] = rank`` expressed as a (cheap) sort
    _, slots = jax.lax.sort((perm, rank), num_keys=1, is_stable=False)
    # compact: distinct rows to the front (ascending), sentinels last —
    # the non-sentinel values are already ascending, so this sort only
    # closes the holes
    rowof = jax.lax.sort(jnp.where(flag, s, jnp.int32(num_rows)))
    return rowof, slots.reshape(ids.shape)


def region_slots(blocks, num_rows: int):
    """Per-block slot plan of the SINGLE-LEVEL region layout, FOREIGN
    ROWS FIRST: ``(rowof_blocks, slots, foreign)`` for ``blocks``
    (nblk, m) rows per occurrence, 0 <= row < num_rows < 2^30.

    A block's row is FOREIGN iff another block of the epoch holds it
    too; only those have ``region_plan``'s ``src[p] != p``.  Region k's
    positions are assigned ``slot_rows``-wise but ordered (foreign rows
    ascending, then the block's own rows ascending, then sentinels), so
    the fetch streams the whole region with one ``dynamic_slice`` and
    gathers only the first ``foreign[k]`` positions over it.

    ``rowof_blocks`` (nblk, m): the row at each region position,
    sentinel ``num_rows`` after the distinct ones.  ``slots`` (nblk, m):
    each occurrence's position in its block, ``rowof_blocks[k,
    slots[k]] == blocks[k]``.  ``foreign`` (nblk,): the block's distinct
    foreign rows.  Nothing downstream reads the order inside a region:
    ``region_plan`` sorts globally and step slots are positions.

    Two sorts and two cumulative maxima on top of the per-block
    ``slot_rows``: one stable global sort by row puts a row's blocks
    side by side, ``_run_has_mark`` says which runs change block
    somewhere, one sort by position carries the verdict back as a key
    offset, and ``slot_rows`` then ranks ``row + (0 | num_rows)`` —
    scatter-free and gather-free like every plan here.  The count is
    what ``scripts/profile_headline.py`` prints as ``region fetch:``.
    """
    nblk, m = blocks.shape
    n = nblk * m
    assert 2 * max(num_rows, n) < (1 << 31) - 1, (num_rows, n)
    rows = blocks.reshape(n).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    srows, spos = jax.lax.sort((rows, pos), num_keys=1, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
    # stable: positions ascend within a run, so its blocks do, and the
    # row is foreign iff the block changes between two of its entries
    sblk = spos // m
    change = jnp.concatenate(
        [jnp.zeros((1,), bool), sblk[1:] != sblk[:-1]]) & ~first
    foreign = _run_has_mark(first, change)
    _, key = jax.lax.sort(
        (spos, srows + jnp.where(foreign, 0, jnp.int32(num_rows))),
        num_keys=1)
    rowof_key, slots = jax.vmap(
        lambda b: slot_rows(b, 2 * num_rows))(key.reshape(nblk, m))
    is_foreign = rowof_key < num_rows
    rowof_blocks = jnp.where(is_foreign, rowof_key,
                             rowof_key - jnp.int32(num_rows))
    return rowof_blocks, slots, jnp.sum(is_foreign, axis=1, dtype=jnp.int32)


def _cummax(x):
    """Cumulative maximum of a 1-D int32 vector by shift-and-max
    doubling: ten steps along the minor axis of a (r, 1024) reshape,
    then the same over the rows' last elements as a carry.  Measured
    on the v5e over 2^20 elements (``scripts/ab_scan.py``; PERF.md §6,
    PR 29): 28 us, against 372 us for a 1-D ``jax.lax.cummax`` and, for
    its two-pass form, 258 us on a (1024, 1024) reshape but 29 us on
    (4096, 256): speed does not decide against that last one.  Names
    do: ``python scripts/ab_scan.py names`` compiles this module's two
    callers under one scope for the v5e (libtpu 0.0.34; PR 29).  On
    the two-pass ``jax.lax.cummax`` 36 of 45 fusions and
    ``reduce-window`` instructions, the scans themselves among them,
    come out with no metadata or with the bare ``op_name``
    ``reduce_window_max`` and no name stack, so a profile reads their
    time as ``unattributed`` and ``phase_attributed_pct``, which guards
    the per-phase metrics, falls; on this form 3 of 99 do (the last
    combine, fused with a reshape).  Traced in the uniform cell with
    the two-pass form here (my chip run, PR 29; PERF.md §6):
    ``phase_attributed_pct`` 99.940 -> 99.914, ``cache_us_per_step``
    8.435 -> 8.414: 0.02 us a step left its group, not the program."""
    n = x.shape[0]
    c = min(1024, n)
    r = -(-n // c)
    lo = jnp.iinfo(jnp.int32).min
    if r * c > n:
        x = jnp.concatenate([x, jnp.full((r * c - n,), lo, jnp.int32)])

    def along_minor(a):
        k = 1
        while k < a.shape[-1]:
            pad = jnp.full(a.shape[:-1] + (k,), lo, jnp.int32)
            a = jnp.maximum(a, jnp.concatenate([pad, a[..., :-k]], axis=-1))
            k *= 2
        return a

    row = along_minor(x.reshape(r, c))
    carry = jnp.concatenate(
        [jnp.full((1,), lo, jnp.int32), along_minor(row[:, -1])[:-1]])
    return jnp.maximum(row, carry[:, None]).reshape(-1)[:n]


def _run_has_mark(first, marked):
    """Per entry, whether ANY entry of its run is ``marked``; runs start
    where ``first`` holds (``first[0]`` must, and no run-first is
    marked).  A segmented OR without a segmented scan: number the
    boundaries 2*i and the marks 2*i+1, and the nearest of either kind
    at or before an entry is one cumulative maximum: odd iff a mark
    lies between the run's start and the entry.  The same from the
    other end (a mark at i+1 is seen from i, so that marks and run ends
    never coincide) covers the marks after it."""
    n = first.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    none = jnp.int32(-1)
    before = _cummax(jnp.where(marked, 2 * idx + 1,
                               jnp.where(first, 2 * idx, none)))
    last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    nxt = jnp.concatenate([marked[1:], jnp.zeros((1,), bool)])
    back = 2 * (n - 1 - idx)
    after = jnp.flip(_cummax(jnp.flip(
        jnp.where(nxt, back + 1, jnp.where(last, back, none)))))
    return ((before | after) & 1) == 1


def region_plan(rowof_blocks, num_rows: int):
    """Circular-predecessor plan for BLOCK-MAJOR epoch-cache regions
    (round 5 — built on the ab_boundary.py measurement: a
    dynamic_update_slice moves the ladder-boundary bytes 8.4x faster
    than the scatter emitter's density-scaled RMW sweep, while gathers
    cost the same at any index order).

    The epoch cache is laid out as ``nblk`` occurrence-sized regions,
    region k seeded with block k's distinct rows (slot_rows per block).
    The top ladder level then STREAMS its writeback into the block's
    own region (dus at k*m) instead of scatter-setting shared slots;
    coherence across blocks moves into the FETCH, which gathers each
    region position's value from the row's most recent prior copy.

    ``rowof_blocks``: (nblk, m) int32 — per-block distinct rows with
    sentinel (``num_rows``) padding after them, in any order inside a
    block (``slot_rows``' ascending, ``region_slots``' foreign-first:
    the sort below is global).  Returns
    ``(src, final_rowof, final_src)``:

    - ``src`` (nblk, m): for region position p = k*m + j, the cache
      position holding that row's latest value when block k begins, in
      CIRCULAR block order — the previous epoch's copy (possibly its
      own region) when no earlier block this epoch holds the row.
      Circularity makes one plan correct for every fused epoch: before
      any update, every region holds the prologue-seeded table value.
    - ``final_rowof`` (nblk*m,): globally sorted distinct rows,
      sentinel-padded — the epilogue scatter's (sorted) index vector.
    - ``final_src`` (nblk*m,): cache position of each final row's LAST
      copy in natural block order — the epilogue gathers values there.
    """
    nblk, m = rowof_blocks.shape
    n = nblk * m
    rows = rowof_blocks.reshape(n).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    # lexicographic (row, position): runs of one row ordered by block.
    # Everything below is sorts, scans, shifts, and gathers — NO
    # scattered writes (scalar scatters cost 3-9 ms each on this
    # platform; the round-3 slot_rows lesson, re-learned on the first
    # cut of this function: the .at[].max/.set forms added ~50 ms of
    # prologue at the headline shape)
    srows, spos = jax.lax.sort((rows, pos), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
    last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    # run's last pos, per entry: positions ascend within a run, and
    # run-lasts are exactly the marked positions at-or-after each entry
    last_pos = _fill_from_marked(spos, last, reverse=True)
    prev = jnp.concatenate([spos[:1], spos[:-1]])
    src_sorted = jnp.where(first, last_pos, prev)
    # back to position order (out[spos] = src_sorted, as a sort)
    _, src = jax.lax.sort((spos, src_sorted), num_keys=1)
    # epilogue compaction, scatter-free: keep run-firsts, push the rest
    # to the sentinel end with one value-carrying sort (rows ascend)
    key = jnp.where(first, srows, jnp.int32(num_rows))
    final_rowof, final_src = jax.lax.sort((key, last_pos), num_keys=1)
    return src.reshape(nblk, m), final_rowof, final_src


def _fill_from_marked(vals, marked, *, reverse=False):
    """``out[i] = vals[j]`` at the nearest marked ``j <= i`` (``>= i``
    when ``reverse``) — the segmented broadcast every region plan
    needs, scatter-free AND gather-free.  ``vals``: int32 positions,
    ``0 <= vals < n`` for ``n`` entries (what the plans broadcast).

    Neither a gather nor a pair-valued scan: a 1-D ``jnp.take`` pays
    the gather emitter's per-ROW issue cost (~7.5 ns/element: 7.48 ms
    per 2^20-element broadcast, round-5 trace), and a pair-valued
    ``associative_scan`` compiles to 83 MB of TPU code and a minute of
    compile per call site (83 of the parent's 104 MB benchmark program
    and 2.7 ms a dispatch, PERF.md §6, PR 29).  The value rides on
    ``_cummax``, the region plans' one scan primitive: key a marked
    entry ``index << k | piece of its value`` and every other one -1,
    and the cumulative maximum carries the nearest mark's piece in its
    low ``k = 31 - bits`` bits, ``bits`` those of ``n - 1``;
    ``ceil(bits / k)`` passes give the whole position (two for the
    2^20 positions of the benchmark's epoch).

    Positions before the first mark (after the last, when ``reverse``)
    are undefined; every plan below guarantees a mark at the boundary.
    """
    if reverse:
        return jnp.flip(_fill_from_marked(jnp.flip(vals), jnp.flip(marked)))
    n = vals.shape[0]
    bits = max((n - 1).bit_length(), 1)
    k = 31 - bits
    assert k >= 1, n
    at = jnp.arange(n, dtype=jnp.int32) << k
    out = jnp.zeros((n,), jnp.int32)
    for shift in range(0, bits, k):
        piece = (vals >> shift) & ((1 << k) - 1)
        near = _cummax(jnp.where(marked, at | piece, jnp.int32(-1)))
        out = out | ((near & ((1 << k) - 1)) << shift)
    return out


def region_plan_l0(rowof_l0, num_rows: int):
    """Within-L1 predecessor plan for L0-level regions (round 5).

    The L1 cache is laid out as one region per L0 block; each L0
    block's writeback streams into its own region (dus) and the L0
    fetch gathers each position's value from the row's LAST copy in an
    EARLIER L0 block of the same L1 pass — or from ITSELF when none
    exists (the L1-level fetch re-seeds every position with the row's
    pre-L1-block value at the start of each pass, so self-default is
    correct on every epoch).

    ``rowof_l0``: (nl0, m0) per-L0-block sorted distinct rows with
    sentinel (num_rows) padding.  Returns ``src`` (nl0, m0): L1-cache
    positions (p = j*m0 + r).
    """
    nl0, m0 = rowof_l0.shape
    n = nl0 * m0
    rows = rowof_l0.reshape(n).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    srows, spos = jax.lax.sort((rows, pos), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool), srows[1:] != srows[:-1]])
    # previous copy of the same row in an earlier L0 block — positions
    # sort by block within a run; same-block duplicates cannot occur
    # (rowof is distinct per block).  First-of-run: self.
    prev = jnp.concatenate([spos[:1], spos[:-1]])
    src_sorted = jnp.where(first, spos, prev)
    _, src = jax.lax.sort((spos, src_sorted), num_keys=1)
    return src.reshape(nl0, m0)


def grouped_region_plan(rowof_l0, nblk_l1: int, num_rows: int):
    """Circular L1-level predecessor plan over an L0-REGION-major epoch
    cache (round 5 — the two-level extension of ``region_plan``).

    The epoch cache holds ``nblk_l1`` L1 regions, each of which is the
    L1 cache's L0-region-major layout ((nl0_per_l1, m0) per L1 block).
    The L1 fetch of block k gathers each position's value from the
    row's LAST-L0 copy within the latest L1 block STRICTLY before k in
    CIRCULAR order (all copies within one L1 block are written in the
    same dus, so a same-L1-block sibling is NOT a valid source; full
    wrap resolves to the row's own canonical copy from the previous
    epoch, seeded with table values before the first).

    ``rowof_l0``: (nblk_l1 * nl0, m0) — ALL L0 blocks' sorted distinct
    rows, L1-major.  Returns ``(src, final_rowof, final_src)`` exactly
    as ``region_plan`` (src shaped (nblk_l1, m1) with m1 = nl0*m0).
    """
    nl0_total, m0 = rowof_l0.shape
    assert nl0_total % nblk_l1 == 0
    nl0 = nl0_total // nblk_l1
    m1 = nl0 * m0
    n = nblk_l1 * m1
    rows = rowof_l0.reshape(n).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    grp = pos // m1  # L1 block of each position
    # scatter-free throughout (see region_plan): sorts + scans + gathers
    srows, sgrp, spos = jax.lax.sort((rows, grp, pos), num_keys=3)
    row_first = jnp.concatenate(
        [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
    sub_first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (srows[1:] != srows[:-1]) | (sgrp[1:] != sgrp[:-1])])
    row_last = jnp.concatenate([row_first[1:], jnp.ones((1,), bool)])
    # a row's wrap target is the canon of its LAST subrun = the spos at
    # the row's last entry (a subrun's canonical copy is its LAST
    # position — positions ascend within a subrun = L0-natural order)
    canon_wrap = _fill_from_marked(spos, row_last, reverse=True)
    # predecessor subrun's canon at a non-row-first subrun-first: the
    # previous entry IS the prior subrun's last entry, i.e. its canon
    prev = jnp.concatenate([spos[:1], spos[:-1]])
    pred_at_first = jnp.where(row_first, canon_wrap, prev)
    # broadcast over the subrun (meaningful at subrun-firsts only)
    src_sorted = _fill_from_marked(pred_at_first, sub_first)
    _, src = jax.lax.sort((spos, src_sorted), num_keys=1)
    # epilogue: per row, the canon of its LAST L1 block = canon at the
    # row's last entry; compact run-firsts by one value-carrying sort
    key = jnp.where(row_first, srows, jnp.int32(num_rows))
    final_rowof, final_src = jax.lax.sort((key, canon_wrap), num_keys=1)
    return src.reshape(nblk_l1, m1), final_rowof, final_src
