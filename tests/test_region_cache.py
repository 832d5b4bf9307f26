"""Block-major epoch-cache regions (FFConfig.epoch_cache_regions).

Round 5: the ladder's top-level writeback streams into per-block
regions (dynamic_update_slice — measured 8.4x the scatter emitter at
the boundary shape, scripts/ab_boundary.py) with coherence moved into
a circular-predecessor fetch plan (ops/slotting.py::region_plan) and a
last-copy epilogue.  These tests pin (a) the plan against brute force
and (b) BIT-exact training equivalence with shared-slot mode across
optimizers, id distributions, and multi-epoch fusion.
"""

import numpy as np
import pytest

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm


class TestRegionPlan:
    def test_against_brute_force(self):
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.ops.slotting import region_plan, slot_rows

        rng = np.random.default_rng(0)
        for trial in range(60):
            nblk = int(rng.integers(2, 5))
            per = int(rng.integers(2, 6))
            rows_n = int(rng.integers(4, 12))
            ids = rng.integers(0, rows_n, size=(nblk, per))
            rowof_blocks = np.stack(
                [np.asarray(slot_rows(jnp.asarray(ids[k]), rows_n)[0])
                 for k in range(nblk)])
            src, frow, fsrc = map(np.asarray, region_plan(
                jnp.asarray(rowof_blocks), rows_n))
            m = rowof_blocks.shape[1]
            for k in range(nblk):
                for j in range(m):
                    r = rowof_blocks[k, j]
                    if r == rows_n:
                        continue
                    # circular prior blocks: k-1 .. 0, nblk-1 .. k
                    exp = None
                    for d in range(1, nblk + 1):
                        kb = (k - d) % nblk
                        hits = np.where(rowof_blocks[kb] == r)[0]
                        if len(hits):
                            exp = kb * m + hits[0]
                            break
                    assert src[k, j] == exp, (trial, k, j, r)
            allrows = sorted(set(
                rowof_blocks[rowof_blocks < rows_n].ravel()))
            for i, r in enumerate(allrows):
                assert frow[i] == r
                lasts = [k * m + np.where(rowof_blocks[k] == r)[0][0]
                         for k in range(nblk) if r in rowof_blocks[k]]
                assert fsrc[i] == lasts[-1], (trial, r)
            assert (frow[len(allrows):] == rows_n).all()


class TestRegionSlots:
    @pytest.mark.parametrize("seed", range(4))
    def test_foreign_first_against_brute_force(self, seed):
        """The single-level layout's slot plan: every foreign position
        of a block (its row lies in another block too) comes before
        every self-sourced one, the count is the brute-force count,
        ``src[p] == p`` from the count on wherever a row is held, and
        ``src`` names the same copy the old row-ordered plan named."""
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.ops.slotting import (region_plan,
                                                    region_slots,
                                                    slot_rows)

        rng = np.random.default_rng(seed)
        for trial in range(15):
            nblk = int(rng.integers(1, 6))
            per = int(rng.integers(1, 9))
            rows_n = int(rng.integers(2, 24))
            ids = rng.integers(0, rows_n, size=(nblk, per))
            rowof, slots, count = map(np.asarray, region_slots(
                jnp.asarray(ids, jnp.int32), rows_n))
            src = np.asarray(region_plan(jnp.asarray(rowof), rows_n)[0])
            m = per
            held = [set(b.tolist()) for b in ids]
            for k in range(nblk):
                # the slot contract slot_rows gives, per block
                np.testing.assert_array_equal(rowof[k][slots[k]], ids[k])
                live = rowof[k][rowof[k] < rows_n]
                assert sorted(live.tolist()) == sorted(held[k])
                assert (rowof[k][len(live):] == rows_n).all()
                foreign = [any(r in held[j] for j in range(nblk) if j != k)
                           for r in live]
                assert count[k] == sum(foreign), (trial, k)
                assert all(foreign[:count[k]]), (trial, k)
                assert not any(foreign[count[k]:]), (trial, k)
                # ascending inside each class: the gather reads
                # ascending rows, as before
                assert (np.diff(live[:count[k]]) > 0).all()
                assert (np.diff(live[count[k]:]) > 0).all()
                for j in range(count[k], len(live)):
                    assert src[k, j] == k * m + j, (trial, k, j)
            # the same copies as the row-ordered plan: block of the
            # source and the row it holds there
            old_rowof = np.stack(
                [np.asarray(slot_rows(jnp.asarray(ids[k]), rows_n)[0])
                 for k in range(nblk)])
            old_src = np.asarray(region_plan(jnp.asarray(old_rowof),
                                             rows_n)[0])
            for k in range(nblk):
                for j in range(m):
                    r = rowof[k, j]
                    if r == rows_n:
                        continue
                    jo = int(np.where(old_rowof[k] == r)[0][0])
                    assert src[k, j] // m == old_src[k, jo] // m
                    assert rowof.reshape(-1)[src[k, j]] == r
                    assert old_rowof.reshape(-1)[old_src[k, jo]] == r


class TestGroupedRegionPlan:
    def test_against_brute_force(self):
        """The two-level plan: L1 fetch takes the row's LAST-L0 copy
        within the latest CIRCULARLY-prior L1 block (same-block
        siblings are invalid — one dus writes them all); the epilogue
        takes the last L1 block's canonical copy."""
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.ops.slotting import (grouped_region_plan,
                                                    region_plan_l0,
                                                    slot_rows)

        rng = np.random.default_rng(1)
        for trial in range(40):
            nl1 = int(rng.integers(2, 4))
            nl0 = int(rng.integers(2, 4))
            per = int(rng.integers(2, 5))
            rows_n = int(rng.integers(4, 10))
            ids = rng.integers(0, rows_n, size=(nl1 * nl0, per))
            rb = np.stack(
                [np.asarray(slot_rows(jnp.asarray(ids[b]), rows_n)[0])
                 for b in range(nl1 * nl0)])
            m0 = rb.shape[1]
            m1 = nl0 * m0
            src, frow, fsrc = map(np.asarray, grouped_region_plan(
                jnp.asarray(rb), nl1, rows_n))

            def canon(k, r):
                best = None
                for j in range(nl0):
                    hits = np.where(rb[k * nl0 + j] == r)[0]
                    if len(hits):
                        best = k * m1 + j * m0 + hits[0]
                return best

            for k in range(nl1):
                for p in range(m1):
                    j, t = divmod(p, m0)
                    r = rb[k * nl0 + j, t]
                    if r == rows_n:
                        continue
                    exp = next(c for d in range(1, nl1 + 1)
                               if (c := canon((k - d) % nl1, r))
                               is not None)
                    assert src[k, p] == exp, (trial, k, p, r)
            allrows = sorted(set(rb[rb < rows_n].ravel()))
            for i, r in enumerate(allrows):
                assert frow[i] == r
                assert fsrc[i] == [canon(k, r) for k in range(nl1)
                                   if canon(k, r) is not None][-1]
            assert (frow[len(allrows):] == rows_n).all()

            # the within-L1 plan: last copy in an EARLIER L0 block,
            # self-default
            for k in range(nl1):
                sub = rb[k * nl0:(k + 1) * nl0]
                src0 = np.asarray(region_plan_l0(jnp.asarray(sub),
                                                 rows_n))
                for j in range(nl0):
                    for t in range(m0):
                        r = sub[j, t]
                        if r == rows_n:
                            continue
                        exp = j * m0 + t
                        for jb in range(j - 1, -1, -1):
                            hits = np.where(sub[jb] == r)[0]
                            if len(hits):
                                exp = jb * m0 + hits[0]
                                break
                        assert src0[j, t] == exp, (trial, k, j, t)


class TestRegionFetch:
    """``row_cache._region_fetch`` alone, at the chunk sizes
    ``scripts/ab_fetch.py`` passes it as well as its own default: the
    block it returns is the one-piece gather's, whatever the chunk."""

    @pytest.mark.parametrize("chunk", [None, 1, 5, 13, 16, 48])
    @pytest.mark.parametrize("foreign", [0, 1, 13, 40, 48])
    def test_equals_the_one_piece_gather(self, chunk, foreign):
        import jax
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.row_cache import _region_fetch

        nblk, m, d, k = 4, 48, 8, 2
        rng = np.random.default_rng(foreign)
        parent = jnp.asarray(
            rng.standard_normal((nblk * m, d)).astype(np.float32))
        # foreign positions first, reading anywhere in the epoch cache;
        # every other position is its own source
        src = np.arange(k * m, (k + 1) * m, dtype=np.int32)
        src[:foreign] = np.sort(rng.integers(0, nblk * m, size=foreign))
        got = jax.jit(_region_fetch, static_argnums=4)(
            parent, jnp.asarray(src), jnp.int32(k * m), jnp.int32(foreign),
            chunk)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(parent)[src])


# Table large enough that the region cache (n_occ = nb*8*4*2 = 1024
# packed rows) is SMALLER than the table's packed rows (16384*4/16 =
# 4096) — the size guard a 64-row table silently fails, which made the
# first cut of these tests vacuous (review r5: region_plan ran 0 times)
ROWS = 16384


#: steps a leaf block (epoch_cache_inner) and view rows a block of the
#: patterned epochs below draws from
INNER, PACK = 2, 16


def _view_blocks(ids, inner=INNER):
    """(nblk, m) view rows per leaf block, as the region layout sees
    them: the stacked op's flat row (table * ROWS + id) // pack."""
    flat = ids + (np.arange(4) * ROWS)[:, None]
    return (flat // PACK).reshape(ids.shape[0] // inner, -1)


def _foreign_counts(ids, inner=INNER):
    blocks = _view_blocks(ids, inner)
    held = [set(b.tolist()) for b in blocks]
    return np.array([sum(any(r in h for j, h in enumerate(held) if j != k)
                         for r in held[k]) for k in range(len(held))])


def _patterned_ids(pattern, nb, rng):
    """Epochs built for the region fetch's corners (the fetch gathers
    ``foreign`` positions of a block in chunks of m/16 = 8):

    - ``disjoint``: no view row in two blocks (0 everywhere: the fetch
      is the slice alone);
    - ``everywhere``: every block holds the same rows (all foreign: the
      fetch gathers every held position, the worst case);
    - ``mixed``: disjoint but for blocks 0/1, which share 3 view rows
      (one chunk), and blocks 2/3, which share 40 (five chunks);
    - ``clamped`` (see ``_clamped_ids``) has a shape of its own."""
    nblk = nb // INNER
    span = ROWS // nblk            # ids of one block's own range
    assert span % PACK == 0
    if pattern == "everywhere":
        one = rng.integers(0, ROWS, size=(INNER, 8, 4, 2), dtype=np.int64)
        ids = np.tile(one, (nblk, 1, 1, 1))
        assert (_foreign_counts(ids) == [
            len(set(b.tolist())) for b in _view_blocks(ids)]).all()
        return ids
    ids = np.stack([rng.integers(k * span, (k + 1) * span,
                                 size=(INNER, 8, 4, 2), dtype=np.int64)
                    for k in range(nblk)]).reshape(nb, 8, 4, 2)
    if pattern == "mixed":
        # table 0, bag slot 0 of the second block's first step borrows
        # ids of the first block's range: 3 view rows, then 40
        ids[1 * INNER, :3, 0, 0] = np.arange(3) * PACK
        ids[0 * INNER, :3, 0, 0] = np.arange(3) * PACK + 1
        lo = 2 * span
        ids[3 * INNER:3 * INNER + 2, :, 0, :] = (
            lo + np.arange(32) * PACK).reshape(2, 8, 2)
        ids[3 * INNER, :, 1, 0] = lo + (32 + np.arange(8)) * PACK
        ids[2 * INNER:2 * INNER + 2, :, 0, :] = (
            lo + np.arange(32) * PACK + 1).reshape(2, 8, 2)
        ids[2 * INNER, :, 1, 0] = lo + (32 + np.arange(8)) * PACK + 1
        # table 1's borrowed ids address table 1's own rows: the view
        # rows (table offset included) still pair up block 2 with 3
        counts = _foreign_counts(ids)
        assert counts[:4].tolist() == [3, 3, 40, 40], counts
        assert (counts[4:] == 0).all(), counts
    else:
        assert pattern == "disjoint", pattern
        assert (_foreign_counts(ids) == 0).all()
    return ids


#: batch and steps a leaf block of the ``clamped`` pattern: m = 9 * 4 *
#: 2 * 3 = 216 positions, which chunks of m // 16 = 13 do not divide
CLAMP_BATCH, CLAMP_INNER = 9, 3


def _clamped_ids(nb, rng):
    """Every block holds the same 216 DISTINCT view rows (another
    element of each pack and another order in every block), so all m
    positions are foreign and the fetch's 17th chunk, which would
    cover [208, 221), is clamped to [203, 216) and gathers five
    positions a second time."""
    nblk, per = nb // CLAMP_INNER, CLAMP_INNER * CLAMP_BATCH * 2
    views = np.stack([rng.permutation(ROWS // PACK)[:per]
                      for _ in range(4)])                  # (4, per)
    ids = np.stack([
        rng.permuted(views, axis=1) * PACK
        + rng.integers(0, PACK, size=views.shape)
        for _ in range(nblk)])                             # (nblk, 4, per)
    ids = ids.reshape(nblk, 4, CLAMP_INNER, CLAMP_BATCH, 2).transpose(
        0, 2, 3, 1, 4).reshape(nb, CLAMP_BATCH, 4, 2)
    m = 4 * per
    assert m % (m // 16), m
    assert (_foreign_counts(ids, CLAMP_INNER) == m).all()
    return ids.astype(np.int64)


def _train(regions, opt="sgd", zipf=False, epochs=2, nb=16,
           expect_engaged=None, monkeypatch=None, levels=None,
           expect_plan=None):
    batch, inner = ((CLAMP_BATCH, CLAMP_INNER) if zipf == "clamped"
                    else (8, INNER))
    cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[ROWS] * 4,
                     embedding_bag_size=2, mlp_bot=[4, 16, 8],
                     mlp_top=[8 * 4 + 8, 16, 1])
    fc = ff.FFConfig(batch_size=batch, packed_tables="on",
                     epoch_row_cache="on", epoch_cache_inner=inner,
                     epoch_cache_regions=regions,
                     **({"epoch_cache_levels": levels} if levels else {}))
    m = build_dlrm(cfg, fc)
    o = (ff.AdamOptimizer(lr=0.05, lazy_embeddings=True)
         if opt == "adam" else ff.SGDOptimizer(lr=0.05))
    m.compile(optimizer=o, loss_type="mean_squared_error", metrics=(),
              mesh=False)
    st = m.init(seed=0)
    assert m.get_op("emb").storage_pack > 1
    if expect_engaged is not None:
        # spy on the plan functions so the engagement claim can never
        # go silently vacuous again (review r5) — per-function lists so
        # a silent single-level fallback in the two-level case is
        # caught too (second review pass)
        import dlrm_flexflow_tpu.ops.slotting as slotting
        calls = {"region_plan": [], "grouped_region_plan": []}
        for fn in calls:
            real = getattr(slotting, fn)
            monkeypatch.setattr(
                slotting, fn,
                lambda *a, _r=real, _c=calls[fn], **k:
                    _c.append(1) or _r(*a, **k))
    rng = np.random.default_rng(7)
    if zipf is True:
        ids = np.minimum(rng.zipf(1.5, size=(nb, 8, 4, 2)) - 1,
                         ROWS - 1).astype(np.int64)
    elif zipf is False:
        ids = rng.integers(0, ROWS, size=(nb, 8, 4, 2), dtype=np.int64)
    elif zipf == "clamped":
        ids = _clamped_ids(nb, rng)
    else:
        ids = _patterned_ids(zipf, nb, rng)
    inputs = {"dense": rng.standard_normal(
        (nb, batch, 4)).astype(np.float32), "sparse": ids}
    labels = rng.integers(0, 2, size=(nb, batch, 1)).astype(np.float32)
    st, mets = m.train_epochs(st, inputs, labels, epochs)
    if expect_engaged is not None:
        if not expect_engaged:
            assert not any(calls.values()), (regions, calls)
        elif expect_plan == "grouped":
            # the two-level layout must use the GROUPED plan
            # specifically — a fallback to single-level would still be
            # bit-exact and pass silently
            assert calls["grouped_region_plan"], (regions, calls)
        else:
            assert calls["region_plan"], (regions, calls)
            # the round-5 auto collapse: when every cache op engages
            # regions the ladder is the single leaf level, so the
            # grouped (two-level) plan must NOT run unless explicit
            # levels request it
            if not levels:
                assert not calls["grouped_region_plan"], (regions, calls)
    out = {"embedding": np.asarray(st.params["emb"]["embedding"]),
           "loss": np.asarray(mets["loss"])}
    if opt == "adam":
        out["m_slot"] = np.asarray(st.opt_state["m"]["emb"]["embedding"])
        out["v_slot"] = np.asarray(st.opt_state["v"]["emb"]["embedding"])
    return out


class TestRegionEquivalence:
    @pytest.mark.parametrize("opt", ["sgd", "adam"])
    @pytest.mark.parametrize("zipf,nb,levels,levels_off,plan", [
        (zipf, *layout) for zipf in (False, True) for layout in (
            (16, None, None, "single"),  # auto ladder [2]: single-level
            (32, None, "2", "single"),   # auto COLLAPSES to [2] under
                                         # regions (round 5 — the mid
                                         # level saves no HBM gather
                                         # issues); the shared-slot
                                         # baseline pins the same [2]
                                         # scan shape so the folded
                                         # metric's mean reduces in the
                                         # same order (the tables are
                                         # bit-equal either way)
            (32, "16,2", "16,2", "grouped"),  # explicit two-level
        )] + [
        # the single-level fetch's corners (_patterned_ids): nothing
        # foreign, everything foreign, blocks either side of a chunk
        # of the foreign gather, and a last chunk clamped back into
        # the block (_clamped_ids)
        ("disjoint", 16, None, None, "single"),
        ("everywhere", 16, None, None, "single"),
        ("mixed", 16, None, None, "single"),
        ("clamped", 18, None, None, "single"),
    ])
    def test_bit_exact_vs_shared_slots(self, opt, zipf, nb, levels,
                                       levels_off, plan, monkeypatch):
        """"on" forces region engagement below the auto size gate; the
        fused multi-epoch run must be BIT-identical to shared-slot mode
        — same adds on the same values, only the address space
        changes (the ladder's exactness proof extends).  Engagement is
        spy-asserted per layout: auto runs the SINGLE-level region
        ladder at any nb (the round-5 collapse), explicit levels
        "16,2" pin the two-level grouped-plan layout."""
        a = _train("on", opt, zipf, nb=nb, expect_engaged=True,
                   monkeypatch=monkeypatch, levels=levels,
                   expect_plan=plan)
        b = _train("off", opt, zipf, nb=nb, expect_engaged=False,
                   monkeypatch=monkeypatch, levels=levels_off)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)

    def test_auto_gate_spares_small_epochs(self, monkeypatch):
        """auto engages only at >=2^18 occurrences (kaggle-shape A/B
        measured the fixed plan costs beating the saved scatters on
        small windows, PERF.md round 5) — small epochs run shared-slot
        even on auto, and still train identically."""
        a = _train("auto", expect_engaged=False, monkeypatch=monkeypatch)
        b = _train("off")
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
