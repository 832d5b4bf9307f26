"""Bit-equality of the region layout on the real chip (PR 29).

The benchmark's ``correct`` compares a 16-batch ``train_epoch`` (shared
slots): it cannot reach the region plans, ``region_slots`` or
``row_cache._region_fetch``, which engage from 2^18 occurrences an epoch.  This
runs the same fused ``train_epochs`` dispatch twice at a shape where
they do — by default the benchmark cells' own, 512 batches x 256 x 8
fused epochs on the run_random.sh model — once with
``epoch_cache_regions="auto"`` and once with ``"off"``, and compares
the final tables (and lazy Adam's two slot tables) bit for bit.  So
that an equality cannot be vacuous it also prints how far training
moved each tensor from ``init`` and how many of its elements moved, in
scientific notation.  Every ``exact`` line and the verdict name the
device they were read on.  On a TPU it ends with ``EXACT_OK`` (exit 0)
or ``EXACT_FAIL`` (exit 1).  Off the TPU it refuses to run, unless the
caller set ``JAX_PLATFORMS=cpu``: then it forces what "auto" picks on
the chip, labels every line ``rehearsal`` and ends with
``REHEARSAL_OK`` / ``REHEARSAL_FAIL``, never ``EXACT_OK``: XLA:CPU's
programs say nothing about XLA:TPU's.  The CPU twin at a tiny size is
``tests/test_region_cache.py``.  Tables cross to the host 2 GB at a
time, but the lazy-Adam case still peaks at 31 GB of host RSS on the
chip's machine (PERF.md §6, PR 29): run it under ``scripts/chip_guard.sh``.

Usage: python scripts/check_region_exact.py [--batches 512] [--epochs 8]
           [--cases uniform:sgd,zipf:sgd,zipf:adam]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

BATCH, ROWS, TABLES = 256, 1_000_000, 8


def trained(regions: str, opt: str, data, epochs: int):
    """``(state, table at init)`` after one fused ``train_epochs``
    dispatch from ``init(seed=0)``; ``regions="auto"`` must put the
    streamed fetch into the program and ``"off"`` must not.  The
    initial table comes back only for ``"auto"`` (2 GB on the host)."""
    import jax
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm

    fc = ff.FFConfig(batch_size=BATCH, compute_dtype="bfloat16",
                     epoch_cache_regions=regions)
    if jax.default_backend() != "tpu":
        # what "auto" picks on the chip, for the CPU rehearsal
        fc.epoch_row_cache, fc.packed_tables = "on", "on"
    model = build_dlrm(DLRMConfig(), fc)
    optimizer = (ff.AdamOptimizer(lr=0.001, lazy_embeddings=True)
                 if opt == "adam" else ff.SGDOptimizer(lr=0.01))
    model.compile(optimizer=optimizer, loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"), mesh=False)
    state = model.init(seed=0)
    before = (np.array(state.params["emb"]["embedding"])
              if regions == "auto" else None)
    ids, dense, labels = data
    inputs, lab = model.place_dataset({"dense": dense, "sparse": ids}, labels)
    hlo = model._train_epochs.lower(state, inputs, lab, epochs).as_text(
        debug_info=True)
    if ("ff.ladder.fetch.own" in hlo) != (regions == "auto"):
        raise SystemExit(f"epoch_cache_regions={regions!r}: the streamed "
                         f"region fetch is in the wrong program")
    state, _ = model.train_epochs(state, inputs, lab, epochs)
    jax.block_until_ready(state.step)
    return state, before


def tensors(state, opt: str):
    """The table and, under lazy Adam, its two slot tables (which start
    at zero), each as a device array."""
    out = {"table": state.params["emb"]["embedding"]}
    if opt == "adam":
        out.update({slot: state.opt_state[slot]["emb"]["embedding"]
                    for slot in ("m", "v")})
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batches", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--cases", default="uniform:sgd,zipf:sgd,zipf:adam")
    args = ap.parse_args()

    import jax
    from dlrm_flexflow_tpu.data.loader import zipf_ids
    from dlrm_flexflow_tpu.entrypoint import enable_compile_cache
    enable_compile_cache()  # uniform and Zipf share their two programs
    print(jax.devices(), flush=True)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("check_region_exact.py holds XLA:TPU's programs to "
                         "bit-equality; set JAX_PLATFORMS=cpu for a "
                         "rehearsal")
    where = jax.devices()[0].device_kind
    tag = "exact" if on_chip else "rehearsal"
    nb = args.batches
    rng = np.random.default_rng(27)
    dense = rng.standard_normal((nb, BATCH, 64)).astype(np.float32)
    labels = rng.integers(0, 2, size=(nb, BATCH, 1)).astype(np.float32)
    shape = (nb, BATCH, TABLES, 1)
    # the benchmark cells' two id laws (benchmarks/lib/traffic.py
    # copies zipf_ids): uniform, and Zipf(1.05) truncated to the table
    ids = {"uniform": rng.integers(0, ROWS, size=shape, dtype=np.int64),
           "zipf": zipf_ids(rng, ROWS, shape, a=1.05)}
    ok = True
    for case in args.cases.split(","):
        dist, opt = case.split(":")
        data = (ids[dist], dense, labels)
        state, before = trained("auto", opt, data, args.epochs)
        steps = int(state.step)
        auto = {k: np.asarray(v) for k, v in tensors(state, opt).items()}
        del state  # the device holds one trainer at a time
        moved = {}
        for name, after in auto.items():
            delta = after - before if name == "table" else after
            moved[name] = (float(np.max(np.abs(delta))),
                           int(np.count_nonzero(delta)))
        del before, delta
        state, _ = trained("off", opt, data, args.epochs)
        for name, off in tensors(state, opt).items():
            # one 2 GB tensor of the second run on the host at a time
            a, b = auto.pop(name), np.asarray(off)
            diff = (0.0 if np.array_equal(a, b)
                    else float(np.max(np.abs(a - b))))
            print(f"{tag} [{where}] {dist} {opt} {name}: "
                  f"{ids[dist][0].size * nb} "
                  f"occurrences an epoch x {args.epochs} epochs, steps "
                  f"{steps}/{int(state.step)}, max|auto - off| = {diff:.3e}, "
                  f"training moved it by max {moved[name][0]:.3e} in "
                  f"{moved[name][1]} of {a.size} elements", flush=True)
            ok &= (diff == 0.0 and moved[name][1] > 0
                   and steps == int(state.step))
            del a, b
        del state
    print(f"{tag.upper()}_{'OK' if ok else 'FAIL'} [{where}]")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
