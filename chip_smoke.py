"""chip_smoke.py — the standing proof that the DLRM trainer starts on the TPU.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # one process driving a four-chip host

One process drives the flagship path through the functions the CLI calls
(``apps/dlrm.py:setup`` -> ``build_dlrm`` -> ``FFModel.compile`` -> ``init``
-> ``fit``) at the ``run_random.sh`` width: 8 x 1M-row tables, feature 64,
bottom 64-512-512-64, top 576-1024-1024-1024-1, batch 256.  Depth is cut
(a few epochs of a few dozen batches); the weights are random, from a seed.

It prints the device first and exits 2 when JAX found no TPU.  Every leg
checks its output by the repo's own means and raises on failure; the last
line of stdout is ``{"ok": true, "device": {...}}`` only if every leg
passed.  ``tests/test_chip_smoke.py`` rehearses the same legs at a tiny
size on the CPU (modes forced "on", kernels interpreted) — the legs take
their size and modes as arguments for that reason.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

#: the ``run_random.sh`` width (``DLRMConfig()`` defaults) — only batch,
#: weight decay and the depth cut are spelled out.  ``--wd 0``: plain SGD
#: is what the row-sparse path needs (the CLI's default 1e-4 weight decay
#: takes the dense-update path), and what ``bench.py`` trains with.
FULL_ARGV = ("-b", "256", "--wd", "0", "--data-size", str(32 * 256))
FIT_EPOCHS = 3
STEPS = 4           # per-batch train_steps after fit
EPOCH_BATCHES = 16  # one scanned train_epoch over the first 16 batches
#                     (32k rows into the 2 GB table: where the row-set
#                     kernel's gate says "kernel")
#: tolerance of the cached-vs-stepwise loss trajectory: what the CPU tests
#: pin for f32 compute (tests/test_sparse_embedding_update.py)
TRAJ_RTOL = 1e-6
#: agreement between shardings / exchange forms of one model, as the
#: dry-run pins on the CPU mesh (__graft_entry__.py)
MESH_RTOL = 1e-4


def say(msg: str = ""):
    print(msg, flush=True)


# ---------------------------------------------------------------- train leg
def train_leg(argv, *, overrides=None, mesh=None, table_parallel=False,
              fit_epochs=FIT_EPOCHS, steps=STEPS,
              epoch_batches=EPOCH_BATCHES, mem_devices=()):
    """The CLI's path, then the two other training verbs: ``setup`` ->
    ``fit`` (stages the dataset, one warm-up ``train_step``, the fused
    multi-epoch scan) -> ``steps`` per-batch ``train_step``s -> one scanned
    ``train_epoch``.  Returns ``(model, state, losses, staged)`` with
    ``losses`` the trajectory as host floats: fit's per-epoch folded losses,
    then each step's, then the epoch's.  ``mem_devices``: print their
    allocator stats after ``init`` and after the run."""
    import jax

    from dlrm_flexflow_tpu.apps.dlrm import setup
    from dlrm_flexflow_tpu.config import FFConfig

    fc = FFConfig.parse_args(argv)
    for k, v in (overrides or {}).items():
        if not hasattr(fc, k):
            raise AttributeError(f"FFConfig has no field {k!r}")
        setattr(fc, k, v)
    model, state, loader = setup(argv, ffconfig=fc, mesh=mesh,
                                 table_parallel=table_parallel)
    if mem_devices:
        say("after init: " + memory_line(mem_devices))
    losses = []
    if fit_epochs:
        state, _ = model.fit(state, loader, epochs=fit_epochs,
                             show_throughput=False)
        assert model._last_fit_used_scan, "fit fell to the per-batch loop"
        assert len(model._last_fit_losses) == fit_epochs
        losses += list(model._last_fit_losses)
    b = loader.batch_size
    for i in range(steps):
        sl = slice(i * b, (i + 1) * b)
        state, mets = model.train_step(
            state, {k: v[sl] for k, v in loader.inputs.items()},
            loader.labels[sl])
        losses.append(mets["loss"])
    n = epoch_batches * b
    staged = model.place_dataset(
        {k: v[:n].reshape((epoch_batches, b) + v.shape[1:])
         for k, v in loader.inputs.items()},
        loader.labels[:n].reshape((epoch_batches, b) + loader.labels.shape[1:]))
    state, mets = model.train_epoch(state, *staged)
    losses.append(mets["loss"])
    losses = [float(x) for x in jax.device_get(losses)]
    assert np.all(np.isfinite(losses)), f"non-finite loss in {losses}"
    if mem_devices:
        say("after run:  " + memory_line(mem_devices))
    return model, state, losses, staged


def assert_auto_picked(model, expect_pack: int):
    """What "auto" is supposed to pick on a chip is what ran."""
    assert model._sparse_emb_ops == ["emb"], model._sparse_emb_ops
    assert model._epoch_cache_active, "epoch row-cache inactive"
    pack = model.get_op("emb").storage_pack
    assert pack == expect_pack, f"storage_pack {pack} != {expect_pack}"


def row_set_expected(model, n_rows: int) -> bool:
    """Whether ``_cache_writeback`` should take the row-set kernel for an
    epilogue of ``n_rows`` plan rows — the gate of row_cache.py restated
    from its inputs, so the lowered text can be held against it."""
    import jax

    from dlrm_flexflow_tpu.ops.kernel_costs import row_set_wins

    op = model.get_op("emb")
    spec = op.param_specs()[0]
    shape = spec.storage_shape or (int(np.prod(spec.shape[:-1])),
                                   spec.shape[-1])
    return (model.mesh is None and jax.default_backend() == "tpu"
            and shape[1] % 128 == 0
            and row_set_wins(shape[0], shape[1], n_rows,
                             np.dtype(spec.dtype).itemsize))


def has_pallas_call(lowered_text: str) -> bool:
    return "tpu_custom_call" in lowered_text


def check_kernel_dispatch(model, state, staged):
    """The row-set kernel is in the lowered ``train_epoch`` exactly where
    its gate says so (asserted on the lowered text, not on the absence of
    an error).  Returns the line to print."""
    ids = staged[0]["sparse"]
    n_rows = int(np.prod(ids.shape))  # plan rows of the epilogue
    want = row_set_expected(model, n_rows)
    got = has_pallas_call(model._train_epoch.lower(state, *staged).as_text())
    assert got == want, (
        f"row-set kernel in lowered train_epoch: {got}, gate says {want} "
        f"({n_rows} rows)")
    return (f"dispatch: train_epoch epilogue of {n_rows} rows -> "
            f"{'row-set pallas kernel' if got else 'XLA scatter emitter'} "
            f"(gate agrees)")


# ------------------------------------------------------- trace / fence legs
def traced_window(fn):
    """Run ``fn`` under the profiler; return the parsed trace's busy ms
    (from the device's "XLA Modules" track — ``parse_device_trace`` raises
    where that track is missing rather than substituting another)."""
    from dlrm_flexflow_tpu.profiling import traced_device_busy_ms

    busy_ms = traced_device_busy_ms(fn)
    assert busy_ms > 0, f"device_busy_ms = {busy_ms}"
    return busy_ms


def fence_verdict(window, calls=20):
    """Time a window of ``calls`` chained, state-donating dispatches
    closed by ``jax.block_until_ready``, then by ``device_fence``.  After
    the first, a ``device_fence`` of the same output shows what
    ``block_until_ready`` missed — plus the fence's own cost, which a
    second ``device_fence`` of the now finished output measures alone.
    ``early``: the first fence waited a quarter of the window longer
    than its own cost — the early return this fence was written for
    closed a 120 s window in 0.7 ms.  Returns ms."""
    import jax

    from dlrm_flexflow_tpu.profiling import device_fence

    def timed(wait, *args):
        t0 = time.perf_counter()
        wait(*args)
        return (time.perf_counter() - t0) * 1e3

    def run(wait):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = window()
        wait(out)
        return (time.perf_counter() - t0) * 1e3, out

    run(device_fence)  # settle
    bur, out = run(jax.block_until_ready)
    after = timed(device_fence, out)
    alone = timed(device_fence, out)
    fence, _ = run(device_fence)
    return {"block_until_ready_ms": bur, "then_device_fence_ms": after,
            "device_fence_alone_ms": alone, "device_fence_ms": fence,
            "early": after - alone > 0.25 * bur}


# --------------------------------------------------------------- kernel leg
def _try(fn):
    """(compiled, result-or-error-line).  The verdict leg has to survive a
    refusal to report it; the package's own dispatch never wraps a
    ``pallas_call`` like this."""
    import jax

    try:
        return True, jax.block_until_ready(fn())
    except Exception as e:  # a Mosaic refusal surfaces as several types
        first = str(e).strip().splitlines()[0] if str(e).strip() else ""
        return False, f"{type(e).__name__}: {first[:160]}"


#: (dim, batch, bag, interact) of the fused-interaction kernel calls:
#: the serving buckets its cost gate selects at the run_random.sh table
#: set (batch <= 4 for cat, <= 8 for dot), at the app's d=64 and at the
#: d=128 where packed storage does not pre-empt the kernel, plus one bag
FUSED_SHAPES = ((64, 4, 1, "cat"), (64, 8, 1, "dot"), (128, 4, 1, "cat"),
                (128, 8, 1, "dot"), (128, 1, 4, "cat"))


def kernel_table(*, interpret=False, rows=1_000_000, tables=8,
                 set_rows=8192, upd_rows=2048, bag_batch=256,
                 fused_shapes=FUSED_SHAPES):
    """Each Pallas kernel against its reference at the shapes its gate
    selects.  Rows: dict(kernel, shape, gated, compiled, matches, note) —
    ``gated`` is whether the dispatch predicate in the package can select
    this kernel at this shape on a chip."""
    import jax
    import jax.numpy as jnp

    from dlrm_flexflow_tpu.ops import pallas_fused_interact as pfi
    from dlrm_flexflow_tpu.ops.pallas_embedding import (_bag_fwd_ref,
                                                        embedding_bag_pallas)
    from dlrm_flexflow_tpu.ops.pallas_scatter import (
        _row_set_pallas, sparse_view_update, supports_pallas_row_update,
        view_scatter_add)

    rng = np.random.default_rng(0)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(shape):  # drawn on the device: the big ones are GBs
        return jax.random.normal(next(keys), shape, jnp.float32)

    out = []

    def record(kernel, shape, gated, run, ref, exact=True):
        say(f"  kernel {kernel} {shape} ...")
        ok, got = _try(run)
        row = {"kernel": kernel, "shape": shape, "gated": bool(gated),
               "compiled": ok, "matches": None, "note": ""}
        if ok:
            want = ref()
            pairs = list(zip(jax.tree_util.tree_leaves(got),
                             jax.tree_util.tree_leaves(want)))
            bit = all(bool(jnp.array_equal(g, w)) for g, w in pairs)
            row["matches"] = bit if exact else all(
                bool(jnp.allclose(g, w, rtol=1e-6, atol=1e-6))
                for g, w in pairs)
            row["note"] = "bit-exact" if bit else "within 1e-6"
        else:
            row["note"] = got
        out.append(row)

    # ---- row-set (default path: the low-density epilogue) ---------------
    vrows = tables * rows // 2
    parent = normal((vrows, 128))
    live = np.sort(rng.choice(vrows, size=set_rows - set_rows // 8,
                              replace=False)).astype(np.int32)
    ids = jnp.asarray(np.concatenate(
        [live, np.full(set_rows - live.size, vrows, np.int32)]))
    vals = normal((set_rows, 128))
    record("_row_set_pallas", f"{set_rows} rows -> ({vrows},128) f32", True,
           lambda: _row_set_pallas(parent, ids, vals, interpret=interpret),
           lambda: parent.at[ids].set(vals, mode="drop"))

    # ---- row-update v1/v2 (FF_SCATTER_IMPL=kernel, packed storage) ------
    uids = jnp.asarray(rng.integers(0, 2 * vrows, size=upd_rows // 2,
                                    dtype=np.int32))
    uids = jnp.concatenate([uids, uids[: upd_rows // 4],
                            uids[: upd_rows // 4]])  # duplicate runs
    upd = normal((upd_rows, 64))
    for name, pipe in (("_row_update_pallas v1", False),
                       ("_row_update_pallas v2", True)):
        record(name, f"{upd_rows} updates -> ({vrows},128) f32",
               supports_pallas_row_update(2 * vrows, 64, upd_rows),
               functools.partial(sparse_view_update, parent, uids, upd, -0.01,
                                 d=64, force=True, interpret=interpret,
                                 pipeline=pipe),
               lambda: view_scatter_add(parent, uids, -0.01 * upd, 64),
               exact=False)  # duplicate runs accumulate in another order
    del parent

    # ---- embedding bag (use_pallas=True; needs dim % 128 == 0) ----------
    tb128 = normal((rows, 128))
    bids = jnp.asarray(rng.integers(0, rows, size=(bag_batch, 8),
                                    dtype=np.int32))
    record("embedding_bag_pallas", f"({rows},128) batch {bag_batch} bag 8",
           True,
           lambda: embedding_bag_pallas(tb128, bids, "sum",
                                        interpret=interpret),
           lambda: _bag_fwd_ref(tb128, bids, "sum"), exact=False)

    # ---- fused gather->pool->interact, forward and backward -------------
    def fused(dim, batch, bag, interact, table):
        t = tables
        n = table.shape[0]
        gids = jnp.asarray(rng.integers(0, n, size=(batch, t, bag),
                                        dtype=np.int32))
        gids = gids.at[0, 0, 0].set(-1)  # one dropped id
        bottom = normal((batch, dim))
        g = normal((batch, pfi.interact_width(interact, t, dim, dim)))
        gated = pfi.kernel_eligible(table.dtype, dim, bag,
                                    interpret=interpret)
        shape = f"({n},{dim}) T{t} bag {bag} batch {batch} {interact}"
        fwd = functools.partial(pfi.fused_interact_pallas, interact=interact,
                                interpret=interpret)
        ref = functools.partial(pfi.fused_interact_ref, interact=interact)
        record("fused_interact_pallas", shape, gated,
               lambda: jax.jit(fwd)(table, gids, bottom),
               lambda: jax.jit(ref)(table, gids, bottom))

        def ref_bwd(table, gids, bottom, g):
            """(row grads, dbottom) by autodiff of the emitter path."""
            rows_ = jnp.take(table, jnp.maximum(gids, 0), axis=0)
            _, vjp = jax.vjp(
                lambda r, b: pfi.masked_pool_interact(r, gids, b, interact,
                                                      "sum"), rows_, bottom)
            return vjp(g)

        bwd = functools.partial(pfi.fused_interact_bwd_pallas,
                                interact=interact, interpret=interpret)
        record("fused_interact_bwd_pallas", shape,
               gated and pfi.bwd_kernel_eligible(interpret),
               lambda: jax.jit(bwd)(table, gids, bottom, g),
               lambda: jax.jit(ref_bwd)(table, gids, bottom, g))

    tb64 = normal((tables * rows, 64))
    for dim, batch, bag, interact in fused_shapes:
        fused(dim, batch, bag, interact, {64: tb64, 128: tb128}[dim])
    return out


def print_kernel_table(table):
    say("  | kernel | shape | gate can select | compiled | matches |")
    for r in table:
        say(f"  | {r['kernel']} | {r['shape']} | "
            f"{'yes' if r['gated'] else 'no'} | "
            f"{'yes' if r['compiled'] else 'NO'} | "
            f"{r['note'] if r['compiled'] else '-'} |")
        if not r["compiled"]:
            say(f"      refused: {r['note']}")
    bad = [r for r in table
           if r["gated"] and not (r["compiled"] and r["matches"])]
    assert not bad, (
        "a kernel its gate can select did not compile or disagreed with "
        f"its reference: {[(r['kernel'], r['shape'], r['note']) for r in bad]}")


# ------------------------------------------------------------ one-chip mode
def one_chip(argv=FULL_ARGV, *, auto_overrides=None, expect_pack=2,
             interpret=False, kernel_kwargs=None, fence_calls=20,
             trace=True):
    """All one-chip legs.  On the chip ``auto_overrides`` stays None (the
    point is what "auto" picks) and ``trace`` stays on; the CPU rehearsal
    forces the same modes "on" and has no device track to trace."""
    import jax

    from dlrm_flexflow_tpu.profiling import device_fence

    t0 = time.perf_counter()
    say(f"== main path: setup -> fit -> train_step x{STEPS} -> train_epoch ==")
    model, state, losses, staged = train_leg(
        argv, overrides=auto_overrides, mem_devices=jax.devices()[:1])
    assert_auto_picked(model, expect_pack)
    say(f"auto picked: sparse_emb_ops={model._sparse_emb_ops} "
        f"epoch_cache_active={model._epoch_cache_active} "
        f"storage_pack={model.get_op('emb').storage_pack}")
    say(f"losses: {' '.join(f'{x:.7f}' for x in losses)}")
    say(f"main path took {time.perf_counter() - t0:.1f} s, compiles included")
    say(check_kernel_dispatch(model, state, staged))

    box = [state]
    del state  # donated by the first window

    def window():
        box[0], _ = model.train_epoch(box[0], *staged)
        return box[0]

    extra = 0  # epochs the windows run; the reference leg takes as many
    if trace:
        say("== one traced window ==")
        busy_ms = traced_window(lambda: device_fence(window()))
        extra += 1
        say(f"traced train_epoch window: device_busy_ms={busy_ms:.3f} "
            f'(from the "XLA Modules" track)')

    say("== block_until_ready vs device_fence ==")
    v = fence_verdict(window, calls=fence_calls)
    extra += 3 * fence_calls
    say(f"{fence_calls} chained train_epoch calls closed by "
        f"block_until_ready: {v['block_until_ready_ms']:.2f} ms; a "
        f"device_fence of the whole state right after: "
        f"{v['then_device_fence_ms']:.2f} ms, and again on the finished "
        f"state: {v['device_fence_alone_ms']:.2f} ms (its own cost); the "
        f"same window closed by device_fence: {v['device_fence_ms']:.2f} ms")
    if trace:
        busy_n = traced_window(
            lambda: device_fence([window() for _ in range(fence_calls)][-1]))
        extra += fence_calls
        say(f"the same window traced: device_busy_ms={busy_n:.2f}")
        v["early"] = v["early"] or v["block_until_ready_ms"] < 0.95 * busy_n
    say("verdict: block_until_ready "
        + ("RETURNS EARLY" if v["early"] else "waits for the device"))

    final = model.get_weights(box[0], "emb", "embedding")
    del box, model, staged

    say("== reference: epoch_row_cache=off, packed_tables=off ==")
    ref_over = dict(auto_overrides or {},
                    epoch_row_cache="off", packed_tables="off")
    rmodel, rstate, rlosses, rstaged = train_leg(argv, overrides=ref_over)
    assert not rmodel._epoch_cache_active
    assert rmodel.get_op("emb").storage_pack == 1
    say(f"losses: {' '.join(f'{x:.7f}' for x in rlosses)}")
    np.testing.assert_allclose(losses, rlosses, rtol=TRAJ_RTOL, atol=0,
                               err_msg="cached vs stepwise loss trajectory")
    for _ in range(extra):
        rstate, _ = rmodel.train_epoch(rstate, *rstaged)
    rfinal = rmodel.get_weights(rstate, "emb", "embedding")
    diff = float(np.max(np.abs(final - rfinal)))
    say(f"loss trajectory agrees at rtol={TRAJ_RTOL}; embedding table "
        f"after {extra} more epochs: max|diff|={diff:.3e}"
        f"{' (bit-exact)' if diff == 0.0 else ''}")
    assert diff <= 1e-6, f"cached vs stepwise table differs by {diff}"
    del rmodel, rstate, rstaged, final, rfinal

    say(f"== Pallas kernels, interpret={interpret} ==")
    print_kernel_table(kernel_table(interpret=interpret,
                                    **(kernel_kwargs or {})))
    return losses


# ----------------------------------------------------------- four-chip mode
def memory_line(devices):
    parts = []
    for d in devices:
        ms = d.memory_stats() or {}
        parts.append(f"dev{d.id}: in_use={ms.get('bytes_in_use', 0) / 2**20:.0f}"
                     f" MiB peak={ms.get('peak_bytes_in_use', 0) / 2**20:.0f}"
                     f" MiB")
    return "; ".join(parts)


def assert_table_sharded(model, state, devices):
    """The embedding is sharded over "model" across all the mesh's
    devices: every device holds one 1/mp shard, and device memory is
    spread evenly — not the whole table on device 0."""
    emb = state.params["emb"]["embedding"]
    assert emb.sharding.spec[0] == "model", emb.sharding.spec
    where = {s.device.id for s in emb.addressable_shards}
    assert where == {d.id for d in devices}, (where, devices)
    mp = model.mesh.shape["model"]
    for s in emb.addressable_shards:
        assert s.data.nbytes == emb.nbytes // mp, (s.data.nbytes, emb.nbytes)
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if all(used):  # the CPU backend reports nothing
        assert min(used) >= emb.nbytes // mp, (used, emb.nbytes)
        assert max(used) <= 1.25 * min(used), f"uneven device memory {used}"


def four_chip(argv=FULL_ARGV, *, overrides=None, expect_pack=2, steps=STEPS,
              epoch_batches=EPOCH_BATCHES):
    """The hybrid DLRM strategy on a 2 x 2 mesh three ways (auto-SPMD,
    allgather, all_to_all), the CLI's default pure data-parallel mesh, and
    a one-device run of the same seed and data they all must agree with.
    No ``fit`` here: a few ``train_step``s and one scanned ``train_epoch``
    per form.  Pallas kernels are off under any mesh (SPMD cannot
    partition a pallas_call), so everything but the one-device reference
    is all XLA."""
    import jax

    from dlrm_flexflow_tpu.parallel.mesh import make_mesh

    devices = jax.devices()[:4]
    assert len(devices) == 4, f"four-chip mode found {len(jax.devices())}"
    say("devices: " + ", ".join(
        f"{d.id}@{getattr(d, 'coords', None)}" for d in devices))
    leg = functools.partial(train_leg, argv, fit_epochs=0, steps=steps,
                            epoch_batches=epoch_batches, mem_devices=devices)
    results = {}

    say("== hybrid (table-parallel) on {data: 2, model: 2}, auto-SPMD ==")
    mesh = make_mesh({"data": 2, "model": 2}, devices=devices)
    model, state, losses, staged = leg(overrides=overrides, mesh=mesh,
                                       table_parallel=True)
    emb = state.params["emb"]["embedding"]
    assert model.get_op("emb").exchange_mode is None
    assert model.get_op("emb").storage_pack == expect_pack
    assert model._sparse_emb_ops == ["emb"]
    assert_table_sharded(model, state, devices)
    assert model.shard_batch(np.zeros((8, 4))).sharding.spec[0] == "data"
    assert staged[1].sharding.spec[1] == "data"
    assert not has_pallas_call(
        model._train_epoch.lower(state, *staged).as_text())
    say(f"embedding {emb.shape} sharded {emb.sharding.spec} over devices "
        f"{sorted(s.device.id for s in emb.addressable_shards)}; "
        f"batch sharded over 'data'; no pallas_call in the lowered "
        f"train_epoch (all XLA under a mesh)")
    say(f"losses: {' '.join(f'{x:.7f}' for x in losses)}")
    results["auto"] = losses
    del model, state, staged, emb

    for mode in ("allgather", "all_to_all"):
        say(f"== hybrid, table_exchange={mode} ==")
        over = dict(overrides or {}, table_exchange=mode)
        model, state, losses, staged = leg(overrides=over, mesh=mesh,
                                           table_parallel=True)
        got = model.get_op("emb").exchange_mode
        assert got == mode, f"asked for {mode}, compile set {got}"
        emb = state.params["emb"]["embedding"]
        assert_table_sharded(model, state, devices)
        say(f"exchange_mode={got}; embedding {emb.shape} sharded "
            f"{emb.sharding.spec}")
        say(f"losses: {' '.join(f'{x:.7f}' for x in losses)}")
        results[mode] = losses
        del model, state, staged, emb

    say("== CLI default: data-parallel over all devices ==")
    model, state, losses, staged = leg(overrides=overrides, mesh=None)
    assert model.mesh is not None and dict(model.mesh.shape) == {
        "data": len(jax.devices())}, model.mesh
    emb = state.params["emb"]["embedding"]
    assert all(ax is None for ax in emb.sharding.spec), emb.sharding.spec
    assert staged[1].sharding.spec[1] == "data"
    say(f"mesh {dict(model.mesh.shape)}; embedding replicated; batch "
        f"sharded over 'data'")
    say(f"losses: {' '.join(f'{x:.7f}' for x in losses)}")
    results["dp"] = losses
    del model, state, staged, emb

    say("== one device, same seed and data ==")
    model, state, losses, staged = leg(overrides=overrides, mesh=False)
    assert model.mesh is None
    say(f"losses: {' '.join(f'{x:.7f}' for x in losses)}")
    results["one"] = losses
    del model, state, staged

    for name, got in results.items():
        np.testing.assert_allclose(
            got, results["one"], rtol=MESH_RTOL, atol=0,
            err_msg=f"{name} vs the one-device run")
    for a in ("auto", "allgather", "all_to_all"):
        for b in ("auto", "allgather", "all_to_all"):
            np.testing.assert_allclose(results[a], results[b],
                                       rtol=MESH_RTOL, atol=0,
                                       err_msg=f"{a} vs {b}")
    say(f"auto / allgather / all_to_all / dp / one-device losses agree at "
        f"rtol={MESH_RTOL}")
    return results


# -------------------------------------------------------------------- main
def native_line():
    """Whether the native libraries are here, and from when.  The training
    path needs neither; git ignores them, so a checkout starts without."""
    from dlrm_flexflow_tpu.native_lib import NATIVE_DIR

    boot = time.time() - time.monotonic()
    parts = []
    for so in ("libffruntime.so", "libffsim.so"):
        p = os.path.join(NATIVE_DIR, so)
        if not os.path.exists(p):
            parts.append(f"{so}: absent (built on demand)")
        else:
            here = os.path.getmtime(p) >= boot
            parts.append(f"{so}: present, built "
                         f"{'on this machine' if here else 'BEFORE this machine booted (stale copy)'}")
    return "native: " + "; ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    try:
        from dlrm_flexflow_tpu.entrypoint import (enable_compile_cache,
                                                  require_tpu)
    except ImportError as e:
        print(f"chip_smoke.py needs the repository around it: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    info = require_tpu()  # prints the device line; exits 2 off-TPU
    if info["count"] != args.chips:
        print(f"--chips {args.chips} but JAX reports {info['count']} "
              f"device(s)", file=sys.stderr)
        return 2
    from dlrm_flexflow_tpu.telemetry import (compile_stats,
                                             install_compile_hooks)

    install_compile_hooks()
    n_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} "
        f"({'from JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed in-checkout default'}"
        f", {n_before} entries at start)")
    say(native_line())
    if args.chips == 1:
        one_chip()
    else:
        four_chip()
    cs = compile_stats()
    say(f"compiles: {int(cs.get('backend_compile', 0))} backend compiles in "
        f"{cs.get('backend_compile_s', 0.0):.1f} s; persistent cache: "
        f"{int(cs.get('cache_hits', 0))} hits, "
        f"{int(cs.get('cache_misses', 0))} written")
    say(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
