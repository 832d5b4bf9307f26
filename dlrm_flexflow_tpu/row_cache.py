"""The epoch row-cache and its in-graph ladder.

Big-table gather/scatter lowers to a full-table SWEEP per step on TPU
(cost scales with table bytes, PERF.md).  But an epoch program knows the
WHOLE epoch's ids up front, so the touched rows can be pulled into a
small cache with ONE sweep, the scan then gathers/scatters the cache by
slot (exact: unique slots keep cross-step updates coherent), and one
scatter-set writes the final rows back.  Per-step table cost becomes
O(cache bytes) instead of O(table bytes).  Mesh-compatible: the cache is
built from the full epoch's ids inside the jitted epoch program, so
under a mesh XLA SPMD owns its placement.  docs/CACHE_LADDER.md has the
design.

What the model hands over (``FFModel._compile_body`` builds one
``RowCache``; nothing here imports ``model``):

- a ``CachePolicy``: every ``epoch_*`` / ``packed_tables`` option
  resolved and validated once, at ``compile``; nothing under a trace
  reads a config or the environment;
- the ``CacheOp`` of every row-sparse table, the lazy optimizer's slot
  names, the mesh and the backend;
- the training step, as a function
  ``step(state, inputs, labels, slots) -> (state, metrics)``.

and the three entry points an epoch program calls, values in and out:

    state, plan = cache.plan(state, inputs, nb)
    state, mets = cache.scan(step, state, inputs, labels, plan)
    state = cache.finish(state, plan)

(``plan`` opens ``ff.cache.prologue`` and ``ff.cache.plan``, ``scan``
``ff.ladder.fetch`` / ``.writeback`` under the caller's ``ff.ladder``;
``ff.cache.epilogue`` around ``finish`` is the caller's too.)

``state`` is any dataclass with ``params`` and ``opt_state``
(``model.TrainState``); a cached op's ``params[name]["embedding"]`` holds
the cache in place of the table between ``plan`` and ``finish``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .ops import slotting
from .ops.pallas_scatter import (_row_set_pallas, row_set_wins,
                                 use_packed_view)

# rows a trip of _region_fetch's loop gathers: a measurement of XLA:TPU's
# gather emitter at 512 B rows and libtpu 0.0.34, not a law;
# scripts/ab_fetch.py times this very function at other sizes
REGION_FETCH_CHUNK = 768

# "auto" engages the region layout from this many id occurrences an
# epoch: the region plan's fixed costs (per-block sorts, the last-copy
# epilogue gather) beat the saved scatters only on big epochs:
# kaggle-shape A/B measured busy 4.275 -> 5.252 ms with regions at 26k
# occurrences, while the 1M-occurrence headline gains 10 ms (PERF.md
# round 5); "on" forces engagement for tests
REGION_AUTO_OCCURRENCES = 1 << 18


def _mode(config, field: str, default: str = "auto") -> str:
    value = getattr(config, field, default)
    if value not in ("auto", "on", "off"):
        raise ValueError(
            f"{field} must be 'auto'|'on'|'off', got {value!r}")
    return value


@dataclasses.dataclass(frozen=True)
class CachePolicy:
    """What the ``epoch_*`` / ``packed_tables`` options come to on this
    backend and mesh.  ``resolve`` is the one place that reads and
    validates them."""

    #: "auto": tpu only (the sweep the cache amortizes is a TPU lowering;
    #: cpu/gpu scatter is already per-row).  "on": anywhere (tests
    #: exercise the cached path on the CPU suite).  Whether a model HAS
    #: a row-sparse table is the model's to add.
    cache: bool
    #: d<128 tables live physically as (R/pack, 128) arrays
    packed_storage: bool
    #: logical tables move between table and epoch cache in 128-lane
    #: view rows (``build_cache``); "on" still requires no mesh (under
    #: SPMD the view fights the sharded layout, like every packed-view
    #: path)
    view: bool
    #: "auto" | "on" | "off" (``RowCache.region_engages``)
    regions: str
    #: explicit ladder sizes, outermost first; () = no ladder; None =
    #: the auto rule of ``ladder_sizes``
    levels: Optional[Tuple[int, ...]]
    inner: int
    chunk: int

    @classmethod
    def resolve(cls, config, backend: str, mesh) -> "CachePolicy":
        cache = _mode(config, "epoch_row_cache")
        packed = _mode(config, "packed_tables")
        view = _mode(config, "epoch_cache_view")
        levels = getattr(config, "epoch_cache_levels", "auto")
        if levels in ("off", "", None):
            levels = ()
        elif levels == "auto":
            levels = None
        else:
            try:
                sizes = (levels.split(",") if isinstance(levels, str)
                         else levels)
                levels = tuple(int(s) for s in sizes if str(s).strip())
            except (TypeError, ValueError):
                raise ValueError(
                    f"epoch_cache_levels must be 'auto', 'off' or sizes "
                    f"like '256,32,8', got {levels!r}") from None
        return cls(
            cache=cache == "on" or (cache == "auto" and backend == "tpu"),
            packed_storage=(packed == "on"
                            or (packed == "auto" and backend == "tpu")),
            view=(mesh is None if view == "on"
                  else view == "auto" and use_packed_view(mesh)),
            regions=_mode(config, "epoch_cache_regions", "off"),
            levels=levels,
            inner=int(getattr(config, "epoch_cache_inner", 8)),
            chunk=int(getattr(config, "epoch_cache_chunk", 256)))

    def ladder_sizes(self, nb: int, region_single: bool) -> List[int]:
        """Static block sizes of the in-graph cache ladder for an
        nb-step scan, outermost first.  Auto is the shallow two-level
        shape [8*inner, inner] (``chunk`` does not shape it; it only
        sizes host-side dispatch chunks for epochs the ladder cannot
        engage).  When 8*inner does not divide nb, auto falls back to
        [geometric mid, inner], and when ``inner`` <= 1 to a chunk-sized
        single level.

        ``region_single`` is the prologue's every-cache-op-engaged-
        regions decision, passed EXPLICITLY (advisor r5: this used to
        be a mutable closure flag set mid-trace, so a consumer that ran
        before the prologue would silently read a stale value and pick
        a ladder shape inconsistent with the region plans)."""
        if self.levels is not None:
            return list(self.levels)
        inner = self.inner
        # Auto is the SHALLOW two-level shape [8*inner, inner]: the
        # round-3 deep [chunk, mid, inner] ladder existed because
        # explicit-level probes looked 3.5x worse — but that was
        # chunked DISPATCH overhead, not device work (round-4 profile:
        # [64,8] busy 259 ms vs [256,32,8] busy 322 ms at the headline
        # shape — every extra level adds its own rebuild+writeback
        # boundary traffic, ~4 bytes moved per occurrence-row per
        # level).  The mid cache (8*inner steps) stays small enough for
        # XLA:TPU to keep in fast scoped memory while its writebacks
        # into the epoch cache amortize over 8 inner blocks.
        #
        # Under REGIONS for every cache op the mid level loses its
        # reason to exist — the region fetch's HBM gather issues are no
        # fewer for reading into a mid cache than straight into the
        # leaf block, so the mid level only adds its own S(1) rebuild +
        # dus layer: the ladder collapses to [inner] (busy 185.0 ->
        # 171.6 ms, bench-recorded 171.5, round 5), and only that
        # single-level layout has the streamed fetch (_region_fetch).
        # Mixed eligibility keeps the two-level shape so non-region ops
        # never rebuild straight from the table every 8 steps.
        if 1 < inner < nb:
            if region_single and nb % inner == 0:
                return [inner]
            top = inner * 8
            if top < nb and nb % top == 0:
                return [top, inner]
            if nb % inner == 0:
                # non-divisible top: single level, plus a geometric
                # mid when the epoch is long enough to need one
                sizes = []
                if nb // inner > 8:
                    target = math.isqrt(nb * inner)
                    cands = [s for s in range(inner + 1, nb)
                             if nb % s == 0 and s % inner == 0]
                    if cands:
                        sizes.append(min(cands,
                                         key=lambda s: abs(s - target)))
                sizes.append(inner)
                return sizes
        # inner disabled (<= 1) or not engaging: a chunk-sized single
        # level still bounds the per-step cache sweep
        if 0 < self.chunk < nb and nb % self.chunk == 0:
            return [self.chunk]
        return []

    def engages(self, nb: int) -> bool:
        """Does an in-graph level engage over an nb-step epoch?  (Which
        levels do may depend on ``region_single``; whether one does,
        does not.)"""
        return any(0 < s < nb and nb % s == 0
                   for s in self.ladder_sizes(nb, False))

    def chunk_bounds(self, nb: int) -> Optional[List[Tuple[int, int]]]:
        """(lo, hi) chunk slices for a chunked epoch dispatch of a
        cached model, or None when chunking doesn't apply: an in-graph
        level engages over the full epoch, so the whole (multi-epoch)
        run is one dispatch with one prologue (host-side chunking would
        pay one dispatch per chunk plus a per-chunk cache fill, which is
        what the round-3 ladder-shape probes actually measured).  Chunks
        are equalized (nb // ceil(nb/chunk)) so a non-divisible epoch
        compiles at most TWO scan shapes (equal chunks + one
        remainder-folded tail), and rounded to a multiple of the inner
        cache block so the in-graph L0 level stays engaged for
        non-divisible epoch lengths."""
        chunk, inner = self.chunk, self.inner
        if not (0 < chunk < nb) or self.engages(nb):
            return None
        if inner > 1 and chunk > inner:
            # work in whole inner blocks so every main chunk keeps the
            # in-graph L0 level; a sub-block remainder becomes one tiny
            # tail chunk (flat scan).  At most 3 compiled scan shapes,
            # all chunk sizes <= epoch_cache_chunk.
            q, r = divmod(nb, inner)
            per = chunk // inner                   # blocks per chunk
            k = max(-(-q // per), 1)
            bq, br = divmod(q, k)                  # equalized blocks
            sizes = [(bq + (1 if i < br else 0)) * inner for i in range(k)]
            if r:
                sizes.append(r)
        else:
            k = -(-nb // chunk)
            base = nb // k
            sizes = [base] * k
            sizes[-1] += nb - base * k
        bounds, lo = [], 0
        for s in sizes:
            bounds.append((lo, lo + s))
            lo += s
        return bounds


@dataclasses.dataclass(frozen=True)
class CacheOp:
    """What the cache knows of a row-sparse embedding op."""

    name: str
    #: the input that carries the op's ids
    ids: str
    #: ids -> rows of the op's flattened (R, d) table
    flat_ids: Callable[[Any], Any]
    #: logical rows a 128-lane row holds (``pallas_scatter.lane_pack``)
    lane_pack: int
    #: > 1: the table is stored packed, and its caches are sized and
    #: addressed in VIEW-row units at every ladder level
    storage_pack: int


class Built(NamedTuple):
    """``build_cache``'s shared-slot cache.  ``pack`` > 1: ``rowof``
    addresses view rows of ``pack`` logical rows."""
    cache: Any
    slots: Any
    rowof: Any
    pack: int


class RegionLayout(NamedTuple):
    """``RowCache.region_layout``'s block-major cache.  ``info`` is the
    fetch plan ``ladder_arrays`` consumes; ``rowof_all`` fills the
    optimizer-slot caches the same way."""
    cache: Any
    slots: Any
    info: Dict[str, Any]
    final_rowof: Any
    final_src: Any
    rowof_all: Any


class Writeback(NamedTuple):
    """What ``finish`` needs to put one op's rows back."""
    name: str
    #: the table, and each lazy optimizer slot's table, as ``plan``
    #: found them
    original: Any
    slot_originals: Dict[str, Any]
    #: cache row -> table row, non-decreasing, sentinel holes last
    rowof: Any
    pack: int
    #: region layout: cache position of each row's LAST copy, in
    #: ``rowof``'s order; None for shared slots (the cache IS in order)
    final_src: Any


class Plan(NamedTuple):
    """One epoch's cache plan: it depends on the epoch's ids alone, so
    ``train_epochs`` makes it once for all its epochs."""
    writebacks: List[Writeback]
    #: static ladder [(block steps, {op: cache rows}), ...]; [] = flat
    meta: List[Tuple[int, Dict[str, int]]]
    #: the scans' xs (``ladder_arrays``): every level's slot plans, and
    #: at the leaf the cache slot of every id occurrence
    arrays: Dict[str, Any]


def _region_fetch(parent, src, base, foreign, chunk=None):
    """Leaf-block fetch of the SINGLE-LEVEL region layout: one
    ``dynamic_slice`` streams the block's own region [base, base+m) of
    the epoch cache, then only the FOREIGN positions — ``region_slots``
    puts them first, ``foreign`` of them — are gathered from their
    newest copy (``src``) and laid over it, chunk by chunk, in a loop
    whose trip count follows the count: the cost follows the data, with
    no budget and no branch.  Positions past ``foreign`` hold
    ``src[p] == p`` (the slice brought exactly that row) or a sentinel
    nothing addresses, so a last chunk that runs past the count rewrites
    what is there.  Value-identical to the full gather at every live
    position.

    Measured on the v5e at m = 16,384 rows of 512 B (my chip runs, PR
    29; PERF.md §6): the full gather 134 us a block; here the slice 12
    us, and for the uniform cell's 3,719 foreign rows the gather 18.8
    us + laying 8.9 us (1.8 us a trip).  ``scripts/ab_fetch.py`` runs
    THIS function at other chunks (us a block at 3,800 foreign rows,
    the fetch with 29.6 us of stand-in work): one piece 182.5; 128:
    121.2, 256: 104.6, 384: 114.5, 512: 98.8, 640: 101.8, 768: 90.1,
    896: 100.5, 1,024: 107.5, 1,280: 87.7, 1,536: 93.5, 1,792: 96.6,
    2,048: 105.2, 3,072: 125.2.  768 is the smallest chunk within 3 us
    of the best; 1,280 read 2.3-2.4 us a block (0.3 us a step) better
    at 3,800 and 5,600 rows and 12.8 better when all 16,384 are foreign
    (174.2 against 187.0; one piece 184.4), and has not been run in
    the benchmark (PERF.md §7).  Nobody has explained the emitter's
    dependence on the piece size.  The other exact form, a static 3m/8
    prefix behind a ``lax.cond`` with the full gather as its other
    branch, lost by 31.6 us a step (PR 27's builder's chip runs): the
    conditional took the leaf cache out of fast memory
    (``ff.step.gather`` 3.3 -> 14.8 us a step) and added a copy of the
    block per fetch.

    ``chunk`` defaults to ``min(REGION_FETCH_CHUNK, max(m // 16, 1))``;
    only ``scripts/ab_fetch.py`` and the tests pass another.  The
    ``m // 16`` arm keeps the trip count following ``foreign`` where a
    block has fewer than 12,288 positions (the tests' tiny epochs, a
    small batch); the benchmark's traffic never meets it."""
    m, d = src.shape[0], parent.shape[-1]
    if chunk is None:
        chunk = min(REGION_FETCH_CHUNK, max(m // 16, 1))
    with jax.named_scope("ff.ladder.fetch.own"):
        own = jax.lax.dynamic_slice(parent, (base, 0), (m, d))

    def lay(i, blk):
        # both the index slice and the placement clamp a last chunk
        # that would run past m to [m - chunk, m)
        at = jnp.minimum(i * chunk, m - chunk)
        idx = jax.lax.dynamic_slice(src, (at,), (chunk,))
        rows = jnp.take(parent, idx, axis=0, mode="clip")
        return jax.lax.dynamic_update_slice(blk, rows, (at, 0))

    with jax.named_scope("ff.ladder.fetch.foreign"):
        return jax.lax.fori_loop(
            0, (foreign + chunk - 1) // chunk, lay, own)


def _cache_fetch(parent, rowof, pack=1):
    """THE cache fill all levels share: rows of the flattened parent at
    ``rowof``; sentinel holes clip to a garbage row that nothing
    addresses.  Accepts raw (T, R, d) tables and already-flat (R, d)
    caches alike (the reshape is a no-op for the latter).  ``pack > 1``:
    rowof addresses 128-lane VIEW rows of the (R/pack, d*pack) view —
    the top-level form that keeps the big-table gather in the same
    layout as every other table op (the logical-(R, d<128) form made XLA
    pick a transposed table layout and pay full-table layout copies +
    loop transposes around the prologue/epilogue, ~180 ms per fused run
    at the bench shape — measured via scripts/profile_headline.py,
    round 3)."""
    fl = parent.reshape(-1, parent.shape[-1])
    if pack > 1:
        view = fl.reshape(fl.shape[0] // pack, fl.shape[1] * pack)
        return jnp.take(view, rowof, axis=0,
                        mode="clip").reshape(-1, fl.shape[1])
    return jnp.take(fl, rowof, axis=0, mode="clip")


def build_cache(flat, ids, pack, view_ok, storage=1) -> Optional[Built]:
    """Shared-slot cache of the rows ``ids`` touches in the (R, d)
    source ``flat``, or None when the cache would not be smaller than
    the source.  Slot assignment is sort-position based (ops/slotting.py
    — no dense-rank inverse, whose scalar scatters dominated the
    prologue); ``rowof`` maps slot -> row, non-decreasing with sentinel
    holes last, which the fill (mode="clip") and the writeback
    (mode="drop") both tolerate.  Works on traced values; all shapes are
    static (the cache is sized by the occurrence count — the distinct
    count is data-dependent).

    ``view_ok`` + pack > 1 selects the VIEW-ROW form: slots are assigned
    per 128-lane view row (pack logical rows each), so the table-side
    fetch and writeback move whole view rows — the layout every other
    table op prefers.  Exact: a touched view row's untouched halves are
    fetched with it, never addressed by any slot (slots only point at
    run-first view slots, offset by each id's half), and written back
    with their original bytes.  Costs up to pack x the cache bytes (view
    rows rarely coalesce under random ids) in exchange for killing the
    transposed-layout pathology above."""
    size = int(np.prod(ids.shape))
    sentinel = flat.shape[0]  # OOB -> dropped at writeback
    if storage > 1:
        # packed STORAGE: flat already is the (Rv, 128) view and rowof
        # addresses its view rows directly — the epoch cache is packed
        # too, so every later fetch/writeback is a plain whole-row
        # take/set (pack 1).
        if size >= flat.shape[0]:
            return None
        rowof_v, vslots = slotting.slot_rows(ids // storage, sentinel)
        slots = vslots * storage + (ids % storage).astype(jnp.int32)
        return Built(_cache_fetch(flat, rowof_v), slots, rowof_v, 1)
    if (view_ok and pack > 1 and flat.shape[0] % pack == 0
            and size < flat.shape[0] // pack):
        vrows = flat.shape[0] // pack
        rowof_v, vslots = slotting.slot_rows(ids // pack, vrows)
        slots = vslots * pack + (ids % pack).astype(jnp.int32)
        return Built(_cache_fetch(flat, rowof_v, pack), slots, rowof_v,
                     pack)
    # pad to the lane-pack multiple so the packed view applies to the
    # cache too
    m = -(-size // pack) * pack
    if m >= flat.shape[0]:
        return None
    rowof, slots = slotting.slot_rows(ids, sentinel)
    if m > size:
        rowof = jnp.concatenate(
            [rowof, jnp.full((m - size,), sentinel, rowof.dtype)])
    return Built(_cache_fetch(flat, rowof), slots, rowof, 1)


def _last_copies(cache, final_src):
    """The rows ``finish`` writes back, in ``rowof``'s order.  Region
    layout: each row's LAST copy, compacted to global row order
    (final_src — region_plan), so the table scatter stays sorted."""
    fl = cache.reshape(-1, cache.shape[-1])
    return fl if final_src is None else jnp.take(fl, final_src, axis=0)


def _swap_opt_entry(opt_state, sn, name, arr):
    """Rebuild opt_state with slot tree ``sn``'s entry for ``name``
    replaced by ``arr`` — the one dict-rebuild shared by every
    slot-cache swap and writeback site."""
    opt_state = dict(opt_state)
    tree = dict(opt_state[sn])
    tree[name] = {"embedding": arr}
    opt_state[sn] = tree
    return opt_state


class RowCache:
    """The cache of one compiled model: its ops, where they run, and the
    policy; see the module's docstring for the three entry points."""

    def __init__(self, ops: Sequence[CacheOp], lazy_slots: Sequence[str],
                 mesh, backend: str, policy: CachePolicy):
        self.ops = tuple(ops)
        self.lazy_slots = tuple(lazy_slots)
        self.mesh = mesh
        self.backend = backend
        self.policy = policy
        self._by_name = {op.name: op for op in self.ops}

    # ------------------------------------------------------- entry points
    def plan(self, state, inputs, nb: int):
        """What an epoch program does before its scan: the row-cache
        prologue, then the ladder's slot plans.  Returns the state with
        caches in the tables' places, and the plan."""
        with jax.named_scope("ff.cache.prologue"):
            state, slots, writebacks, region_src, region_single = \
                self._prologue(state, inputs)
        with jax.named_scope("ff.cache.plan"):
            meta, arrays = self._ladder_plan(state, slots, nb, region_src,
                                             region_single)
        return state, Plan(writebacks, meta, arrays)

    def scan(self, step, state, inputs, labels, plan: Plan):
        """One epoch's steps against the cached tables, down the ladder
        where one engages; returns (state, the steps' metrics, stacked
        over the leading step axes).  The caller owns ``ff.ladder``."""
        return self._ladder_scan(step, state, inputs, labels, plan.meta,
                                 plan.arrays)

    def finish(self, state, plan: Plan):
        """Write the final rows back, each live slot exactly once (set,
        not add — bit-exact with the per-step path); sentinel indices
        (padding holes) are dropped.  Lazy mode writes the optimizer
        slot caches back the same way."""
        if not plan.writebacks:
            return state
        new_params = dict(state.params)
        opt_state = state.opt_state
        for wb in plan.writebacks:
            new_params[wb.name] = {"embedding": self._cache_writeback(
                wb.original, wb.rowof,
                _last_copies(state.params[wb.name]["embedding"],
                             wb.final_src), wb.pack)}
            for sn in self.lazy_slots:
                opt_state = _swap_opt_entry(
                    opt_state, sn, wb.name,
                    self._cache_writeback(
                        wb.slot_originals[sn], wb.rowof,
                        _last_copies(
                            state.opt_state[sn][wb.name]["embedding"],
                            wb.final_src), wb.pack))
        return dataclasses.replace(state, params=new_params,
                                   opt_state=opt_state)

    # ----------------------------------------------------------- prologue
    def _swap_slot_caches(self, opt_state, name, fn):
        """Rebuild opt_state with each lazy slot table of ``name``
        replaced by fn(flat_slot_table)."""
        for sn in self.lazy_slots:
            old = opt_state[sn][name]["embedding"]
            opt_state = _swap_opt_entry(
                opt_state, sn, name, fn(old.reshape(-1, old.shape[-1])))
        return opt_state

    def _prologue(self, state, inputs):
        """Per op, map the epoch's ids to unique cache slots and pull
        the touched rows in with one table sweep (plus, in lazy mode,
        the optimizer slot tables — same rowof, same slots).  Returns
        (state-with-caches, slots, writebacks, region_src,
        region_single).  ``region_single`` (every cache op engaged the
        region layout — the ladder-collapse flag) is decided HERE, once
        per trace, and threaded explicitly into every ``ladder_sizes``
        consumer."""
        params = dict(state.params)
        opt_state = state.opt_state
        slots_ep, writebacks, region_src = {}, [], {}
        # one engagement decision per op, shared by the ladder-shape
        # choice below AND region_layout (review r5: the gate must not
        # be evaluated twice or the two could diverge); parent_rows is
        # pure shape math — no traced reshape
        region_ok = {
            op.name: self.region_engages(
                op.storage_pack,
                int(np.prod(op.flat_ids(
                    inputs[op.ids].astype(jnp.int32)).shape)),
                int(np.prod(params[op.name]["embedding"].shape[:-1])))
            for op in self.ops}
        region_single = bool(region_ok) and all(region_ok.values())
        for op in self.ops:
            ids = inputs[op.ids].astype(jnp.int32)
            tb = params[op.name]["embedding"]
            flat = tb.reshape(-1, tb.shape[-1])
            reg = (self.region_layout(op, flat, ids, ids.shape[0],
                                      region_single)
                   if region_ok[op.name] else None)
            if reg is not None:
                cache, slots = reg.cache, reg.slots
                region_src[op.name] = reg.info
                fill, pack = reg.rowof_all, 1
                rowof, final_src = reg.final_rowof, reg.final_src
            else:
                built = build_cache(flat, op.flat_ids(ids), op.lane_pack,
                                    self.policy.view,
                                    storage=op.storage_pack)
                if built is None:
                    # cache would be as big as the table — no win; keep
                    # this op on the direct per-step path
                    continue
                cache, slots, rowof, pack = built
                fill, final_src = rowof, None
            params[op.name] = {"embedding": cache}
            slots_ep[op.name] = slots
            writebacks.append(Writeback(
                op.name, tb,
                {sn: opt_state[sn][op.name]["embedding"]
                 for sn in self.lazy_slots},
                rowof, pack, final_src))
            opt_state = self._swap_slot_caches(
                opt_state, op.name,
                lambda fl, r=fill, p=pack: _cache_fetch(fl, r, p))
        state = dataclasses.replace(state, params=params,
                                    opt_state=opt_state)
        return state, slots_ep, writebacks, region_src, region_single

    def region_engages(self, storage_pack: int, n_occ: int,
                       parent_rows: int) -> bool:
        """Size/flag gate of the region layout for one op — everything
        that does NOT depend on the ladder shape, so the prologue can
        decide the auto ladder (single leaf level when every cache op
        engages) before any ladder_sizes consumer runs.  "auto" is ON
        from ``REGION_AUTO_OCCURRENCES`` (round-5 headline A/B measured
        busy 243.5 -> 219.0 ms, two-level, scatter-free plans; bit-exact
        incl. lazy Adam and Zipf ids)."""
        mode = self.policy.regions
        if mode == "off":
            return False
        if storage_pack <= 1 or self.mesh is not None:
            # packed-storage ops only; under a mesh the region
            # dus/gather would fight the SPMD-sharded cache layout
            # (untested) — keep shared slots there
            return False
        # the region cache holds n_occ PACKED view rows — compare
        # against the table's packed rows (build_cache's guard), not
        # the logical count (review r5)
        if n_occ >= parent_rows:  # cache not smaller: no win
            return False
        return mode == "on" or n_occ >= REGION_AUTO_OCCURRENCES

    def region_layout(self, op: CacheOp, flat, ids, nb: int,
                      region_single: bool) -> Optional[RegionLayout]:
        """Block-major region layout for the epoch cache
        (FFConfig.epoch_cache_regions; ops/slotting.py::region_plan for
        the design), or None when the ladder shape does not support it
        (the size/flag gate is the caller's ``region_engages`` —
        computed ONCE per op in the prologue, which also decides
        ``region_single``)."""
        sp = op.storage_pack
        sizes = self.policy.ladder_sizes(nb, region_single)
        top = sizes[0] if sizes else 0
        if not (0 < top < nb and nb % top == 0):
            return None
        nblk = nb // top
        if nblk <= 1:
            return None
        fv = op.flat_ids(ids)
        n_occ = int(np.prod(fv.shape))
        sentinel = flat.shape[0]
        inner = sizes[1] if len(sizes) >= 2 else 0
        if 0 < inner < top and top % inner == 0:
            # TWO-LEVEL regions: the L1 cache itself is L0-region-
            # major, so the L0 writebacks stream too (dus into the
            # scoped L1 buffer); the L1 fetch uses the GROUPED circular
            # plan (same-L1-block siblings are not valid sources — they
            # are written by the same dus)
            nl0 = top // inner
            v0 = fv.reshape(nblk * nl0, -1)
            m0 = v0.shape[1]
            m1 = nl0 * m0
            rowof_l0, vs_l0 = jax.vmap(
                lambda b: slotting.slot_rows(b // sp, sentinel))(v0)
            base0 = (jnp.arange(nblk * nl0, dtype=jnp.int32)
                     * m0)[:, None]
            slots = ((base0 + vs_l0) * sp
                     + (v0 % sp).astype(jnp.int32)).reshape(fv.shape)
            rowof_all = rowof_l0.reshape(-1)
            cache = _cache_fetch(flat, rowof_all)
            src_l1, final_rowof, final_src = slotting.grouped_region_plan(
                rowof_l0, nblk, sentinel)
            src_l0 = jax.vmap(
                lambda rb: slotting.region_plan_l0(rb, sentinel))(
                    rowof_l0.reshape(nblk, nl0, m0))
            info = {
                "src": src_l1,
                "base": jnp.arange(nblk, dtype=jnp.int32) * m1,
                "inner": {
                    "src": src_l0,
                    "base": jnp.broadcast_to(
                        jnp.arange(nl0, dtype=jnp.int32) * m0,
                        (nblk, nl0)),
                },
            }
            return RegionLayout(cache, slots, info, final_rowof,
                                final_src, rowof_all)
        # SINGLE-LEVEL regions: each region holds its block's FOREIGN
        # rows first (rows another block holds too — the only positions
        # whose src differs from themselves), so the leaf fetch streams
        # the region and gathers only those (_region_fetch): a
        # position's fetch index differs from the position itself only
        # where ANOTHER block holds the row too (23% of a block's
        # positions on uniform ids at the benchmark's shape, 33% on
        # Zipf 1.05)
        m_occ = n_occ // nblk
        v = fv.reshape(nblk, m_occ)
        rowof_blocks, vslots, foreign = slotting.region_slots(v // sp,
                                                              sentinel)
        base = (jnp.arange(nblk, dtype=jnp.int32) * m_occ)[:, None]
        slots = ((base + vslots) * sp
                 + (v % sp).astype(jnp.int32)).reshape(fv.shape)
        rowof_all = rowof_blocks.reshape(-1)
        cache = _cache_fetch(flat, rowof_all)
        src, final_rowof, final_src = slotting.region_plan(rowof_blocks,
                                                           sentinel)
        info = {"src": src,
                "base": jnp.arange(nblk, dtype=jnp.int32) * m_occ,
                "foreign": foreign}
        return RegionLayout(cache, slots, info, final_rowof, final_src,
                            rowof_all)

    # ------------------------------------------------------------- ladder
    def ladder_meta(self, nb: int, slots_ep, rows0: Dict[str, int],
                    region_single: bool):
        """Static ladder plan [(size, {op: cache rows}), ...]: at each
        level every op whose padded block cache would be smaller than
        its current parent cache participates; a level nobody joins is
        dropped.  Pure shape math — the traced twin is ladder_arrays.
        Row units follow the op's storage form: STORAGE rows (view rows,
        one per id occurrence) for packed-storage ops, logical rows
        otherwise — matching the actual cache arrays' shape[0] at every
        level."""
        meta, rows, cur = [], dict(rows0), nb
        for size in self.policy.ladder_sizes(nb, region_single):
            if not (0 < size < cur and cur % size == 0):
                continue
            part = {}
            for name, sl in slots_ep.items():
                per_step = int(np.prod(sl.shape[1:]))
                op = self._by_name[name]
                if op.storage_pack > 1:
                    m = size * per_step  # view slots: 1/occurrence
                else:
                    m = -(-(size * per_step) // op.lane_pack) * op.lane_pack
                if m < rows[name]:
                    part[name] = m
            if part:
                meta.append((size, part))
                rows.update(part)
                cur = size
        return meta

    def ladder_arrays(self, slots, meta, rows, region_src=None):
        """The ladder's slot plans, precomputed OUTSIDE the scans (the
        slot math — ops/slotting.py sorts — depends only on the epoch's
        ids, so under ``train_epochs`` it runs once for ALL fused
        epochs).  Returns a nested pytree consumed as scan xs: each
        level {"rowof": {op: (nblk, m)}, "next": ...}; the leaf carries
        the per-step slots into each op's innermost cache."""
        if not meta:
            return {"slots": slots}
        (size, part), rest = meta[0], meta[1:]
        nb = next(iter(slots.values())).shape[0]
        nblk = nb // size
        blks = {n: s.reshape((nblk, size) + s.shape[1:])
                for n, s in slots.items()}
        # block-major region ops: the fetch indices are the precomputed
        # predecessor src plan, block slots are the region POSITIONS (a
        # subtraction, not a re-ranking — the two-level layout's
        # inter-region sentinel holes make dense ranks diverge from
        # positions), and the writeback streams into the block's own
        # region (the level keys on "region_base").  ``region_src``
        # entries: {"src": (nblk, m), "base": (nblk,), ["foreign":
        # (nblk,)], ["inner": ...]} — "inner" recurses one level down;
        # "foreign" (single-level layout only) is the count of leading
        # positions the fetch has to gather.
        srcs = {n: s for n, s in (region_src or {}).items() if n in part}

        def per_block(blk, src_blk):
            rowof_d, slots_d = {}, {}
            for name, b in blk.items():
                if name in part:
                    sp = self._by_name[name].storage_pack
                    if name in src_blk:
                        rowof = src_blk[name]["src"]
                        s = b - src_blk[name]["base"] * sp
                    elif sp > 1:
                        # view-unit slotting: parent rows are view
                        # rows; each occurrence gets a view slot, its
                        # logical slot offset by the id's half
                        rowof, s = slotting.slot_rows(b // sp, rows[name])
                        s = s * sp + (b % sp).astype(jnp.int32)
                    else:
                        rowof, s = slotting.slot_rows(b, rows[name])
                    m, n = part[name], int(np.prod(b.shape))
                    if m > n:
                        rowof = jnp.concatenate(
                            [rowof, jnp.full((m - n,), rows[name],
                                             rowof.dtype)])
                    rowof_d[name], slots_d[name] = rowof, s
                else:
                    slots_d[name] = b
            inner_srcs = {n: s["inner"] for n, s in src_blk.items()
                          if "inner" in s}
            return {"rowof": rowof_d,
                    "next": self.ladder_arrays(slots_d, rest,
                                               {**rows, **part},
                                               region_src=inner_srcs)}

        arrs = jax.vmap(per_block)(blks, srcs)
        if srcs:
            arrs["region_base"] = {n: srcs[n]["base"] for n in srcs}
            arrs["region_foreign"] = {
                n: srcs[n]["foreign"] for n in srcs
                if "foreign" in srcs[n]}
        return arrs

    def _ladder_plan(self, state, slots_ep, nb, region_src, region_single):
        """(meta, arrays) of the in-graph ladder; ([], the epoch's
        slots alone) where no level engages."""
        rows0 = {name: state.params[name]["embedding"].shape[0]
                 for name in slots_ep}
        meta = (self.ladder_meta(nb, slots_ep, rows0, region_single)
                if slots_ep else [])
        if meta and region_src:
            # region layout presumes its ops engage the top level at
            # exactly the nblk the plan was built for — and the
            # TWO-level layout additionally presumes the inner level
            # engages with exactly nl0 blocks (a row has one slot PER
            # L0 REGION; without the inner level, same-L1-block
            # occurrences would stop propagating updates to each other
            # — silently bit-inexact)
            top = meta[0][0]
            for name, info in region_src.items():
                assert (name in meta[0][1]
                        and info["src"].shape[0] == nb // top), \
                    (name, info["src"].shape, top, nb)
                if "inner" in info:
                    assert (len(meta) >= 2 and name in meta[1][1]
                            and info["inner"]["src"].shape[1]
                            == top // meta[1][0]), \
                        (name, info["inner"]["src"].shape, meta)
        return meta, self.ladder_arrays(slots_ep, meta, rows0,
                                        region_src=region_src)

    def _cache_writeback(self, parent, rowof, cache_final, pack=1):
        """THE cache writeback all levels share: live rows set once,
        sentinel holes dropped — param and optimizer-slot tables must
        stay bit-identical in this formulation for the hierarchy's
        exactness claim.  ``pack > 1``: rowof addresses view rows (see
        _cache_fetch).  ``rowof`` is non-decreasing by construction for
        every slot plan (ops/slotting.py compacts distinct rows to the
        front, sentinel pads at the end), so the scatter carries
        indices_are_sorted — measured 3.8x on the mid-level writeback
        shape (PERF.md round 3 continuation)."""
        fl = parent.reshape(-1, parent.shape[-1])
        if pack > 1:
            target = fl.reshape(fl.shape[0] // pack, fl.shape[1] * pack)
            vals = cache_final.reshape(-1, fl.shape[1] * pack)
        else:
            target, vals = fl, cache_final
        # low-density writebacks take the per-row-DMA SET kernel: the
        # scatter emitter RMW-sweeps the PARENT, so setting a few
        # thousand rows of a GB-scale table costs the sweep (6.1 ms
        # measured at the dlrm_hybrid epilogue) where row DMAs cost ~64
        # ns/row.  The static cost-model gate keeps the emitter
        # everywhere else (ladder levels, dense epilogues).  rowof rows
        # are DISTINCT in every caller (dense-rank/region plans), which
        # the kernel requires.  Eligibility is MANDATORY: no mesh (SPMD
        # cannot partition a pallas_call), TPU backend, and
        # Mosaic-lane-compatible rows (the kernel DMAs (1, d) row
        # slices).  rowof.shape[0] is the PADDED plan length (sentinel
        # holes included: the lane-pack pad) — the live distinct-row
        # count is data-dependent and not static here, so the gate sees
        # an upper bound on the kernel's row DMAs.  The slack only
        # overstates kernel cost (sentinel rows issue no DMA at
        # runtime), so near the threshold the dispatch errs toward the
        # proven emitter path — conservative by construction (advisor
        # r5; see row_set_wins).
        use_kernel = (self.mesh is None and self.backend == "tpu"
                      and target.shape[1] % 128 == 0
                      and row_set_wins(target.shape[0], target.shape[1],
                                       int(rowof.shape[0]),
                                       target.dtype.itemsize))
        if use_kernel:
            out = _row_set_pallas(target, rowof, vals)
        else:
            out = target.at[rowof].set(vals, mode="drop",
                                       indices_are_sorted=True)
        return out.reshape(parent.shape)

    def _level_fetch(self, parent, rowof, base, foreign):
        """A block's rows out of its parent cache.  Region mode:
        ``rowof`` IS the src plan — the single-level layout streams its
        own region and gathers the foreign positions, the grouped
        two-level one gathers every position."""
        if foreign is not None:
            return _region_fetch(parent.reshape(-1, parent.shape[-1]),
                                 rowof, base, foreign)
        return _cache_fetch(parent, rowof)

    def _level_writeback(self, parent, rowof, child, base):
        if base is not None:
            # block-major region: stream the whole block cache into the
            # block's own region (the measured-8.4x dus; ab_boundary.py)
            fl = parent.reshape(-1, parent.shape[-1])
            out = jax.lax.dynamic_update_slice(
                fl, child.reshape(-1, fl.shape[-1]), (base, 0))
            return out.reshape(parent.shape)
        return self._cache_writeback(parent, rowof, child)

    def _ladder_scan(self, step, state, inputs, labels, meta, arrs):
        """Nested scans down the ladder: each level pulls its block's
        rows from the parent cache (one gather at the precomputed
        rowof), recurses against the block cache, and writes the final
        rows back — so the per-step table cost scales with the innermost
        block's rows while each level's rebuild sweep amortizes over its
        block length.  Exactness: every distinct parent row has exactly
        ONE slot in the block cache, so the same adds hit the same
        values in the same order at every level (the single-level proof
        composes)."""
        if not meta:
            return jax.lax.scan(lambda st, b: step(st, *b), state,
                                (inputs, labels, arrs["slots"]))
        (size, part), rest = meta[0], meta[1:]
        nb = labels.shape[0]

        def blk(x):
            return x.reshape((nb // size, size) + x.shape[1:])

        def outer(st, xs_k):
            in_k, lab_k, a_k = xs_k
            reg_b = a_k.get("region_base", {})
            reg_f = a_k.get("region_foreign", {})
            params2 = dict(st.params)
            opt2 = st.opt_state
            wb, slot_wb = [], []
            for name in part:
                parent = st.params[name]["embedding"]
                rowof = a_k["rowof"][name]
                base = reg_b.get(name)

                def fetch(fl, r=rowof, b=base, f=reg_f.get(name)):
                    return self._level_fetch(fl, r, b, f)

                with jax.named_scope("ff.ladder.fetch"):
                    params2[name] = {"embedding": fetch(parent)}
                wb.append((name, rowof, parent, base))
                for sn in self.lazy_slots:
                    slot_wb.append((sn, name, rowof,
                                    opt2[sn][name]["embedding"], base))
                if self.lazy_slots:
                    with jax.named_scope("ff.ladder.fetch"):
                        opt2 = self._swap_slot_caches(opt2, name, fetch)
            st2 = dataclasses.replace(st, params=params2, opt_state=opt2)
            st2, mets_k = self._ladder_scan(step, st2, in_k, lab_k, rest,
                                            a_k["next"])
            new_p = dict(st2.params)
            opt3 = st2.opt_state
            with jax.named_scope("ff.ladder.writeback"):
                for name, rowof, parent, base in wb:
                    new_p[name] = {"embedding": self._level_writeback(
                        parent, rowof, st2.params[name]["embedding"],
                        base)}
                for sn, name, rowof, parent, base in slot_wb:
                    final = st2.opt_state[sn][name]["embedding"]
                    opt3 = _swap_opt_entry(
                        opt3, sn, name,
                        self._level_writeback(parent, rowof, final, base))
            st3 = dataclasses.replace(st2, params=new_p, opt_state=opt3)
            return st3, mets_k

        return jax.lax.scan(outer, state,
                            (jax.tree.map(blk, inputs), blk(labels), arrs))
