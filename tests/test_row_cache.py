"""The epoch row-cache's pieces (dlrm_flexflow_tpu/row_cache.py), each
called on made-up values without building or compiling a model: the
policy's ladder rule and host chunk bounds, the static ladder plan, the
shared-slot cache in its three forms, the region gate and the region
layouts' writeback rows; then that a bad option raises at ``compile``
and that a model without a row-sparse table never enters the module.
Whole-program equivalence (cached against uncached, regions against
shared slots, bit for bit) lives in tests/test_region_cache.py and
tests/test_sparse_embedding_update.py."""

import jax.numpy as jnp
import numpy as np
import pytest

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu import row_cache
from dlrm_flexflow_tpu.row_cache import (CacheOp, CachePolicy, RowCache,
                                         build_cache)


def _policy(**options):
    return CachePolicy.resolve(ff.FFConfig(**options), "cpu", None)


def _cache(ops, mesh=None, **options):
    return RowCache(ops, (), mesh, "cpu", _policy(**options))


def _op(name="t", lane_pack=16, storage_pack=1):
    return CacheOp(name, "ids", lambda ids: ids, lane_pack, storage_pack)


# ------------------------------------------------------------- the policy
@pytest.mark.parametrize("options,nb,sizes,sizes_region_single", [
    # 8*inner divides: the shallow two-level shape; every cache op on
    # regions: the ladder collapses to the leaf level
    ({}, 512, [64, 8], [8]),
    # 8*inner does not divide a long epoch: a geometric mid, isqrt(72*8)
    ({}, 72, [24, 8], [8]),
    # ... a short one (5 inner blocks): the leaf level alone
    ({}, 40, [8], [8]),
    # inner <= 1: a chunk-sized single level, regions or not
    ({"epoch_cache_inner": 0}, 512, [256], [256]),
    ({"epoch_cache_inner": 1}, 512, [256], [256]),
    # inner does not divide, the chunk does
    ({"epoch_cache_inner": 7}, 512, [256], [256]),
    # nothing divides: no level, the host chunks the epoch
    ({}, 1001, [], []),
    # the epoch is one inner block and longer than a chunk
    ({"epoch_cache_inner": 6, "epoch_cache_chunk": 4}, 6, [], []),
    # the epoch is no longer than a chunk: nothing to chunk
    ({}, 7, [], []),
    ({"epoch_cache_levels": "off"}, 512, [], []),
    ({"epoch_cache_levels": ""}, 512, [], []),
    ({"epoch_cache_levels": "16,8"}, 32, [16, 8], [16, 8]),
    ({"epoch_cache_levels": (256, 32, 8)}, 1024, [256, 32, 8],
     [256, 32, 8]),
    # explicit sizes that do not divide are kept (ladder_meta skips
    # them) and engage nothing
    ({"epoch_cache_levels": "48"}, 500, [48], [48]),
])
def test_ladder_sizes_and_chunk_bounds_never_disagree(
        options, nb, sizes, sizes_region_single):
    policy = _policy(**options)
    assert policy.ladder_sizes(nb, False) == sizes
    assert policy.ladder_sizes(nb, True) == sizes_region_single
    engages = any(0 < s < nb and nb % s == 0 for s in sizes)
    assert engages == any(0 < s < nb and nb % s == 0
                          for s in sizes_region_single)
    assert policy.engages(nb) == engages
    bounds = policy.chunk_bounds(nb)
    if engages or nb <= policy.chunk:
        assert bounds is None       # one dispatch, one prologue
    else:
        # the host chunks exactly the epochs no level engages over, in
        # chunks no longer than epoch_cache_chunk that tile the epoch
        assert bounds[0][0] == 0 and bounds[-1][1] == nb
        assert all(a[1] == b[0] for a, b in zip(bounds, bounds[1:]))
        assert all(0 < hi - lo <= policy.chunk for lo, hi in bounds)


def test_chunks_keep_whole_inner_blocks():
    bounds = _policy().chunk_bounds(1001)
    assert all((hi - lo) % 8 == 0 for lo, hi in bounds[:-1])
    assert bounds[-1] == (1000, 1001)


@pytest.mark.parametrize("backend,cache,packed", [("cpu", False, False),
                                                  ("tpu", True, True)])
def test_auto_follows_the_backend(backend, cache, packed):
    policy = CachePolicy.resolve(ff.FFConfig(), backend, None)
    assert (policy.cache, policy.packed_storage) == (cache, packed)
    assert policy.levels is None and policy.regions == "auto"
    forced = CachePolicy.resolve(
        ff.FFConfig(epoch_row_cache="on", packed_tables="off",
                    epoch_cache_view="on"), backend, object())
    assert forced.cache and not forced.packed_storage
    assert not forced.view          # never under a mesh


# ------------------------------------------------------- the static ladder
def _slots(nb, *per_step):
    return np.zeros((nb,) + per_step, np.int32)


@pytest.mark.parametrize("levels,rows0,slots,want", [
    # both forms join both levels: one view slot an occurrence for the
    # packed op, logical rows for the other (64 occurrences a step)
    ("16,2", {"p": 2048, "l": 2048}, (8, 4, 2),
     [(16, {"p": 1024, "l": 1024}), (2, {"p": 128, "l": 128})]),
    # an op whose block cache would not be smaller than its parent sits
    # a level out
    ("16,2", {"p": 2048, "l": 500}, (8, 4, 2),
     [(16, {"p": 1024}), (2, {"p": 128, "l": 128})]),
    # a level nobody joins is dropped
    ("16,2", {"p": 100, "l": 100}, (8, 4, 2), []),
    # a size that does not divide the level above is skipped; a logical
    # cache is padded to whole 128-lane rows (3 an occurrence -> 16)
    ("5,2", {"p": 96, "l": 96}, (3,),
     [(2, {"p": 6, "l": 16})]),
])
def test_ladder_meta(levels, rows0, slots, want):
    cache = _cache([_op("p", storage_pack=16), _op("l")],
                   epoch_cache_levels=levels)
    slots_ep = {"p": _slots(32, *slots), "l": _slots(32, *slots)}
    assert cache.ladder_meta(32, slots_ep, rows0, False) == want


# -------------------------------------------------- the shared-slot cache
@pytest.mark.parametrize("form,rows,n,zipf", [
    (form, rows, n, zipf)
    for form in ("storage", "view", "logical")
    for rows, n, zipf in ((4096, 100, False), (4096, 100, True),
                          (512, 24, False))])
def test_build_cache(form, rows, n, zipf):
    """All three forms: every occurrence finds its row through its slot,
    ``rowof`` is non-decreasing with the sentinel holes last (so the
    writeback's scatter may say ``indices_are_sorted``), and the cache
    holds the table's rows."""
    d, pack = 8, 16
    rng = np.random.default_rng(n)
    table = rng.standard_normal((rows, d)).astype(np.float32)
    ids = (np.minimum(rng.zipf(1.3, size=(n,)) - 1, rows - 1) if zipf
           else rng.integers(0, rows, size=(n,))).astype(np.int32)
    if form == "storage":
        built = build_cache(jnp.asarray(table.reshape(-1, d * pack)),
                            jnp.asarray(ids), pack, False, storage=pack)
        unit, sentinel, cache_rows = pack, rows // pack, n
    elif form == "view":
        built = build_cache(jnp.asarray(table), jnp.asarray(ids), pack,
                            True)
        unit, sentinel, cache_rows = pack, rows // pack, n
    else:
        built = build_cache(jnp.asarray(table), jnp.asarray(ids), pack,
                            False)
        unit, sentinel, cache_rows = 1, rows, -(-n // pack) * pack
    rowof, slots = np.asarray(built.rowof), np.asarray(built.slots)
    assert built.pack == (pack if form == "view" else 1)
    assert rowof.shape == (cache_rows,)
    np.testing.assert_array_equal(rowof[slots // unit], ids // unit)
    assert (np.diff(rowof.astype(np.int64)) >= 0).all()
    live = len(np.unique(ids // unit))
    assert (rowof[:live] < sentinel).all()
    assert (rowof[live:] == sentinel).all()      # the padding
    np.testing.assert_array_equal(
        np.asarray(built.cache).reshape(-1, d)[slots], table[ids])


@pytest.mark.parametrize("form", ["storage", "view", "logical"])
def test_build_cache_declines_a_cache_no_smaller_than_the_table(form):
    """As many occurrences as the source has rows (view rows, where it
    is stored packed): the op stays on the per-step path."""
    d, pack, rows = 8, 16, 256
    if form == "storage":
        flat, n, args = jnp.zeros((rows // pack, d * pack)), rows // pack, \
            (pack, False, pack)
    else:
        flat, n, args = jnp.zeros((rows, d)), rows, (pack, form == "view")
    assert build_cache(flat, jnp.zeros((n,), jnp.int32), *args) is None
    if form == "view":
        # a few occurrences more than view rows: the view form declines,
        # the logical form behind it still wins
        ids = jnp.zeros((rows // pack + 1,), jnp.int32)
        assert build_cache(flat, ids, *args).pack == 1


# --------------------------------------------------------- the region gate
@pytest.mark.parametrize("why,options,mesh,storage,n_occ,rows,engages", [
    ("logical storage", {"epoch_cache_regions": "on"}, None, 1, 1024,
     4096, False),
    ("a mesh", {"epoch_cache_regions": "on"}, object(), 16, 1024, 4096,
     False),
    ("a cache not smaller than the table", {"epoch_cache_regions": "on"},
     None, 16, 4096, 4096, False),
    ("under 2^18 occurrences at auto", {}, None, 16, (1 << 18) - 1,
     1 << 20, False),
    ("off", {"epoch_cache_regions": "off"}, None, 16, 1 << 18, 1 << 20,
     False),
    ("2^18 occurrences at auto", {}, None, 16, 1 << 18, 1 << 20, True),
    ("on, at any size", {"epoch_cache_regions": "on"}, None, 16, 1024,
     4096, True),
])
def test_region_gate(why, options, mesh, storage, n_occ, rows, engages):
    cache = _cache([_op(storage_pack=storage)], mesh=mesh, **options)
    assert cache.region_engages(storage, n_occ, rows) is engages, why


@pytest.mark.parametrize("levels,two_level", [(None, False),
                                              ("16,2", True)])
def test_region_layouts_write_back_sorted_rows(levels, two_level):
    """Either region layout: ``final_rowof`` is every touched view row
    once, ascending, sentinels last, and ``final_src`` is a cache
    position that holds that row."""
    pack, rows, nb = 16, 16384, 32
    op = _op(storage_pack=pack)
    cache = _cache([op], epoch_cache_regions="on", epoch_cache_inner=2,
                   **({"epoch_cache_levels": levels} if levels else {}))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, rows, size=(nb, 8, 2)).astype(np.int32)
    table = jnp.asarray(rng.standard_normal(
        (rows // pack, 8 * pack)).astype(np.float32))
    reg = cache.region_layout(op, table, jnp.asarray(ids), nb, not levels)
    assert ("inner" in reg.info) == two_level
    assert ("foreign" in reg.info) == (not two_level)
    final_rowof = np.asarray(reg.final_rowof)
    touched = np.unique(ids // pack)
    np.testing.assert_array_equal(final_rowof[:len(touched)], touched)
    assert (final_rowof[len(touched):] == rows // pack).all()
    np.testing.assert_array_equal(
        np.asarray(reg.rowof_all)[np.asarray(reg.final_src)[:len(touched)]],
        touched)
    np.testing.assert_array_equal(
        np.asarray(reg.rowof_all)[np.asarray(reg.slots) // pack],
        ids // pack)


# ------------------------------------------------------------- at compile
def _toy(**options):
    m = ff.FFModel(ff.FFConfig(batch_size=8, **options))
    x = m.create_tensor((8, 4), name="x")
    m.dense(m.dense(x, 16, activation="relu"), 1)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
              loss_type="mean_squared_error", metrics=(), mesh=False)
    return m


@pytest.mark.parametrize("option,value", [
    ("epoch_row_cache", "yes"), ("epoch_cache_view", "view"),
    ("epoch_cache_regions", "single"), ("packed_tables", "packed"),
    ("epoch_cache_levels", "16,eight"), ("epoch_cache_levels", 16),
])
def test_a_bad_cache_option_raises_at_compile(option, value):
    """Every one of them, on a model that has no table at all: none
    waits for a trace to reach it."""
    with pytest.raises(ValueError, match=option):
        _toy(**{option: value})


def _tiny_lm():
    from dlrm_flexflow_tpu.apps import mla_moe_lm as app
    cfg = app.MlaMoeLmConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=16, experts_held=4, num_experts_per_tok=4,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6, seq_len=32)
    m = app.build(cfg, ff.FFConfig(batch_size=2))
    m.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
              metrics=(), mesh=False)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 2, cfg.seq_len + 2)).astype(np.int32)
    # as benchmarks/models/mla_moe_lm.py::_split lays a window out
    return m, {"ids": tokens[..., :-2], "next_ids": tokens[..., 1:-1],
               "mtp_labels": tokens[..., 2:, None]}, tokens[..., 1:-1, None]


def _toy_epoch():
    rng = np.random.default_rng(0)
    return (_toy(epoch_row_cache="on"),
            {"x": rng.standard_normal((4, 8, 4)).astype(np.float32)},
            rng.standard_normal((4, 8, 1)).astype(np.float32))


@pytest.mark.parametrize("build", [_toy_epoch, _tiny_lm])
def test_a_model_without_a_row_sparse_table_never_enters_the_cache(
        build, monkeypatch):
    """The cache forced on, and every way into the module made to raise:
    both epoch programs are a plain scan of the step."""
    def refuse(*args, **kwargs):
        raise AssertionError("the row cache was entered")

    for entry in ("__init__", "plan", "scan", "finish"):
        monkeypatch.setattr(row_cache.RowCache, entry, refuse)
    model, inputs, labels = build()
    assert model._sparse_emb_ops == [] and not model._epoch_cache_active
    state = model.init(seed=0)
    state, mets = model.train_epoch(state, inputs, labels)
    state, stacked = model.train_epochs(state, inputs, labels, 2)
    steps = labels.shape[0]
    assert int(state.step) == 3 * steps
    assert np.asarray(stacked["loss"]).shape == (2,)
    assert np.isfinite(np.asarray(mets["loss"]))
    assert np.all(np.isfinite(np.asarray(stacked["loss"])))
