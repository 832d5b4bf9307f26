"""slot_rows invariants — the epoch row-cache's exactness proof needs
every occurrence of a row to share one slot, and the slot -> row map to
round-trip (row_cache.py build_cache)."""

import jax.numpy as jnp
import numpy as np
import pytest

from dlrm_flexflow_tpu.ops.slotting import slot_rows


def check(ids, num_rows):
    rowof, slots = slot_rows(ids, num_rows)
    rowof, slots = np.asarray(rowof), np.asarray(slots)
    flat = np.asarray(ids).reshape(-1)
    assert slots.shape == np.asarray(ids).shape
    assert rowof.shape == (flat.size,)
    sf = slots.reshape(-1)
    # every occurrence resolves to its own row through the slot map
    np.testing.assert_array_equal(rowof[sf], flat)
    # occurrences of one row share ONE slot (cross-step coherence)
    for r in np.unique(flat):
        assert len(np.unique(sf[flat == r])) == 1
    # distinct rows get distinct slots (no aliasing)
    assert len(np.unique(sf)) == len(np.unique(flat))
    # non-slot positions hold the sentinel, slot positions are live rows
    live = np.zeros(flat.size, bool)
    live[np.unique(sf)] = True
    assert (rowof[~live] == num_rows).all()
    assert (rowof[live] < num_rows).all()
    # rowof is NON-DECREASING (distinct rows compacted to the front,
    # sentinels at the end) — the writeback scatter's
    # indices_are_sorted=True hint depends on this (row_cache.py
    # _cache_writeback; 3.8x on the mid-level writeback, PERF.md)
    assert (np.diff(rowof.astype(np.int64)) >= 0).all()
    assert live[:live.sum()].all()  # live slots contiguous at the front


@pytest.mark.parametrize("n,num_rows,seed", [
    (64, 100, 0),          # duplicates likely
    (256, 50, 1),          # n > R: every row hit multiple times
    (100, 10_000, 2),      # sparse touch
    (1, 7, 3),             # single id
    (128, 128, 4),
])
def test_invariants(n, num_rows, seed):
    rng = np.random.default_rng(seed)
    check(jnp.asarray(rng.integers(0, num_rows, size=n, dtype=np.int32)),
          num_rows)


def test_shaped_ids_and_all_duplicates():
    check(jnp.asarray([[3, 3], [3, 3]], jnp.int32), 10)


def test_jittable_and_deterministic():
    import jax
    rng = np.random.default_rng(9)
    ids = jnp.asarray(rng.integers(0, 64, size=(4, 8), dtype=np.int32))
    a = jax.jit(lambda i: slot_rows(i, 64))(ids)
    b = slot_rows(ids, 64)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


@pytest.mark.parametrize("n,frac,reverse,seed", [
    (64, 0.3, False, 0),
    (64, 0.3, True, 1),
    (1000, 0.05, False, 2),   # pads to a 256-col multiple; long runs
    (1000, 0.05, True, 3),
    (4096, 0.9, False, 4),    # dense marks
    (4096, 0.9, True, 5),
    (1, 1.0, False, 6),
    (1, 1.0, True, 7),
    (257, 0.2, False, 8),     # one element past a full row
    (257, 0.2, True, 9),
    (5000, 0.01, False, 10),  # runs that span rows of the reshape
    (5000, 0.01, True, 11),
    (1 << 16, 0.3, True, 12),  # 15 bits a pass: two passes for 16
    (3, 0.5, False, 13),       # 29 bits a pass: one pass
])
def test_fill_from_marked_brute_force(n, frac, reverse, seed):
    """The segmented broadcast under every region plan: out[i] = vals
    at the nearest marked index at-or-before i (at-or-after when
    reverse).  The boundary position is always marked and the values
    are positions below n, matching the plans' contract."""
    from dlrm_flexflow_tpu.ops.slotting import _fill_from_marked
    rng = np.random.default_rng(seed)
    marked = rng.random(n) < frac
    marked[-1 if reverse else 0] = True
    vals = rng.integers(0, n, size=n).astype(np.int32)
    got = np.asarray(_fill_from_marked(
        jnp.asarray(vals), jnp.asarray(marked), reverse=reverse))
    exp = np.empty(n, np.int32)
    if reverse:
        cur = 0
        for i in range(n - 1, -1, -1):
            if marked[i]:
                cur = vals[i]
            exp[i] = cur
    else:
        cur = 0
        for i in range(n):
            if marked[i]:
                cur = vals[i]
            exp[i] = cur
    np.testing.assert_array_equal(got, exp)


def _foreign_brute(blocks):
    """Distinct rows of each block that another block holds too."""
    sets = [set(b.ravel().tolist()) for b in blocks]
    return np.array([sum(any(r in s for j, s in enumerate(sets) if j != k)
                         for r in sets[k]) for k in range(len(sets))],
                    np.int32)


@pytest.mark.parametrize("blocks,num_rows", [
    # shaped ids: a table of 12 rows, 4 blocks of 6 occurrences
    (np.random.default_rng(11).integers(0, 12, size=(4, 6)), 12),
    (np.random.default_rng(12).integers(0, 500, size=(8, 64)), 500),
    (np.full((3, 4), 3), 10),                   # all duplicates: 1 row
    (np.arange(12).reshape(3, 4), 12),          # nothing shared: 0
    (np.tile(np.arange(4), (3, 1)), 4),         # every row everywhere
    (np.array([[5, 5, 5, 5]]), 6),              # one block: no other
])
def test_foreign_counts_brute_force(blocks, num_rows):
    from dlrm_flexflow_tpu.ops.slotting import region_slots
    got = np.asarray(region_slots(jnp.asarray(blocks, jnp.int32),
                                  num_rows)[2])
    np.testing.assert_array_equal(got, _foreign_brute(np.asarray(blocks)))


def test_foreign_counts_jittable_and_deterministic():
    import jax
    from dlrm_flexflow_tpu.ops.slotting import region_slots
    rng = np.random.default_rng(13)
    blocks = jnp.asarray(rng.integers(0, 40, size=(4, 16), dtype=np.int32))
    a = jax.jit(lambda b: region_slots(b, 40)[2])(blocks)
    b = region_slots(blocks, 40)[2]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a),
                                  _foreign_brute(np.asarray(blocks)))


@pytest.mark.parametrize("n,p_first,p_mark,seed", [
    (1, 1.0, 0.0, 0),
    (64, 0.3, 0.2, 1),
    (1000, 0.05, 0.02, 2),   # long runs, few marks; pads to 1024
    (1025, 0.5, 0.5, 3),     # one element past a full row
    (5000, 0.01, 0.001, 4),  # runs that span rows of the reshape
    (4096, 0.2, 0.0, 5),     # no mark at all
])
def test_run_has_mark_brute_force(n, p_first, p_mark, seed):
    """The segmented OR under ``region_slots``: whether any entry of an
    entry's run is marked (no run-first is, as in the plan: a block
    cannot change at a row's first entry)."""
    from dlrm_flexflow_tpu.ops.slotting import _run_has_mark
    rng = np.random.default_rng(seed)
    first = rng.random(n) < p_first
    first[0] = True
    marked = (rng.random(n) < p_mark) & ~first
    got = np.asarray(_run_has_mark(jnp.asarray(first), jnp.asarray(marked)))
    run = np.cumsum(first) - 1
    exp = np.bincount(run, weights=marked, minlength=run[-1] + 1)[run] > 0
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n,seed", [
    (1, 0),
    (3, 1),          # shorter than a row of the reshape
    (1024, 2),       # exactly one row: no carry
    (1025, 3),       # one element past it: the carry's first use
    (5000, 4),       # a maximum that rides over several rows
    (1 << 16, 5),
])
def test_cummax_brute_force(n, seed):
    """The region plans' one scan primitive against
    ``np.maximum.accumulate``, negative entries (the plans' "no mark"
    is -1) and int32's extremes included."""
    from dlrm_flexflow_tpu.ops.slotting import _cummax
    rng = np.random.default_rng(seed)
    x = rng.integers(-5, 1 << 30, size=n).astype(np.int32)
    x[rng.random(n) < 0.7] = -1          # long stretches without a mark
    x[rng.integers(0, n)] = np.iinfo(np.int32).max
    x[0] = np.iinfo(np.int32).min
    got = np.asarray(_cummax(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.maximum.accumulate(x))
