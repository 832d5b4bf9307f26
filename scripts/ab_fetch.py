"""A/B the region fetch by PIECE SIZE on the real chip (PR 29).

``row_cache.py::_region_fetch`` streams a leaf block's own region with one
``dynamic_slice`` and gathers its ``P`` foreign positions over it in
pieces of ``chunk`` rows.  ``REGION_FETCH_CHUNK = 768`` comes from this
program, which calls that very function with ``chunk`` as given:
XLA:TPU's gather emitter charges per row, and what it charges depends
on the piece size, not monotonically.  My chip runs, PR 29 (v5e, libtpu
0.0.34, rows of 512 B, 3,800 foreign rows a block; us a block, and ns a
row gathered AND laid): one piece of 16,384: 182.5 (8.18); 128: 121.2
(15.44), 256: 104.6 (11.23), 384: 114.5 (13.81), 512: 98.8 (9.12),
640: 101.8 (10.55), 768: 90.1 (7.44), 896: 100.5 (8.75), 1,024: 107.5
(11.26), 1,280: 87.7 (6.80), 1,536: 93.5 (6.92), 1,792: 96.6 (6.52),
2,048: 105.2 (10.63), 3,072: 125.2 (10.40).  768 is the smallest size
within 3 us a block of the best, so the loop's cost follows the count
most closely; 1,280 read better by 2.3-2.4 us a block at 3,800 and
5,600 rows and by 12.8 at 16,384 (PERF.md §6-§7).  Run it again when
libtpu, the row width or the block size changes.

The program stands for the ladder's outer scan at the benchmark's
shape (64 blocks of m = 16,384 view rows of 128 f32 in a 512 MB epoch
cache, 4 epochs): per block a fetch, an elementwise pass that stands
for the steps, and the write-back ``dynamic_update_slice``.  Each form
runs in one traced dispatch; the time is the trace's device busy, split
by the fetch's own scopes ``ff.ladder.fetch.own`` (the slice) and
``ff.ladder.fetch.foreign`` (gather and laying), ``ff.ab.gather`` (the
one-piece form) and ``ff.ab.rest``.

Usage: python scripts/ab_fetch.py [chunk ...] [--rows P[,P...]]
       (chunk 0 = the one-piece gather of all m positions, the fetch
        before PR 29; defaults: chunks 0 512 768 1024 1280 1536, rows
        3800,5600,16384 = the benchmark's uniform and Zipf blocks, and
        the worst case)
Off the TPU (JAX_PLATFORMS=cpu, with --blocks / --m small) it times
nothing and checks that every form returns the one-piece gather's
cache.
"""

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

D, EPOCHS = 128, 4


def make_plan(rng, nblk: int, m: int, foreign: int):
    """``(src, counts)``: every position its own source but the first
    ``foreign`` of each block, which read sorted random positions."""
    import jax.numpy as jnp
    src = np.arange(nblk * m, dtype=np.int32).reshape(nblk, m).copy()
    for k in range(nblk):
        src[k, :foreign] = np.sort(rng.integers(0, nblk * m, size=foreign))
    return jnp.asarray(src), jnp.full((nblk,), foreign, jnp.int32)


def one_piece(parent, src, base, count):
    """The fetch before PR 29: one gather of all m positions."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("ff.ab.gather"):
        return jnp.take(parent, src, axis=0, mode="clip")


def in_pieces(chunk: int):
    """The program's own ``_region_fetch``, with ``chunk`` as given."""
    from dlrm_flexflow_tpu.row_cache import _region_fetch

    def fetch(parent, src, base, count):
        return _region_fetch(parent, src, base, count, chunk)
    return fetch


def program(fetch):
    import jax

    def run(parent, src, base, counts):
        def block(par, xs):
            s, b, c = xs
            blk = fetch(par, s, b, c)
            with jax.named_scope("ff.ab.rest"):
                blk = blk * 1.0001 + 0.5          # stands for the steps
                return jax.lax.dynamic_update_slice(par, blk, (b, 0)), None

        def epoch(par, _):
            return jax.lax.scan(block, par, (src, base, counts))[0], None

        return jax.lax.scan(epoch, parent, None, length=EPOCHS)[0]
    return jax.jit(run, donate_argnums=(0,))


def traced_phases(call):
    """``({phase: self_us}, busy_ms)`` of one traced ``call()`` after a
    compile-and-warm call and one more."""
    import jax
    from dlrm_flexflow_tpu.profiling import parse_device_trace_phases, trace
    for _ in range(2):
        jax.block_until_ready(call())
    logdir = tempfile.mkdtemp(prefix="ab_fetch_")
    try:
        with trace(logdir):
            jax.block_until_ready(call())
        _path, phases, busy_ms = parse_device_trace_phases(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return phases, busy_ms


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("chunks", nargs="*", type=int,
                    default=[0, 512, 768, 1024, 1280, 1536])
    ap.add_argument("--rows", default="3800,5600,16384")
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--m", type=int, default=16384)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    print(jax.devices(), flush=True)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("ab_fetch.py times the TPU's gather emitter")
    nblk, m = args.blocks, args.m
    rng = np.random.default_rng(0)
    base = jnp.arange(nblk, dtype=jnp.int32) * m
    blocks = EPOCHS * nblk
    for foreign in (int(x) for x in args.rows.split(",")):
        src, counts = make_plan(rng, nblk, m, foreign)
        want = None
        for chunk in args.chunks:
            prog = program(in_pieces(chunk) if chunk else one_piece)

            def call():
                # the parent is donated: a fresh one for each call
                fresh = jnp.arange(nblk * m * D, dtype=jnp.float32)
                return prog(fresh.reshape(-1, D) * 1e-6, src, base, counts)

            if not on_chip:
                got = np.asarray(call())
                want = got if want is None else want
                np.testing.assert_array_equal(got, want)
                print(f"rehearsal: foreign {foreign} chunk {chunk or m}: "
                      f"the cache agrees with chunk {args.chunks[0] or m}'s")
                continue
            phases, busy_ms = traced_phases(call)
            gathered = -(-foreign // chunk) * chunk if chunk else m
            us = {k: phases.get(scope, 0.0) / blocks
                  for k, scope in (("own", "ff.ladder.fetch.own"),
                                   ("foreign", "ff.ladder.fetch.foreign"),
                                   ("one", "ff.ab.gather"),
                                   ("rest", "ff.ab.rest"))}
            pieces = us["foreign"] + us["one"]
            print(f"fetch foreign {foreign:5d} chunk {chunk or m:5d}: busy "
                  f"{busy_ms * 1e3 / blocks:7.1f} us a block; own "
                  f"{us['own']:5.1f} gather and laying {pieces:6.1f} "
                  f"({pieces * 1e3 / max(gathered, 1):5.2f} ns a row of "
                  f"{gathered}) rest {us['rest']:5.1f}", flush=True)


if __name__ == "__main__":
    main()
