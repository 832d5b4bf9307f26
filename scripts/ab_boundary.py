"""A/B the cache-ladder BOUNDARY ops on the real chip (judge r4 item 2).

The headline trace attributes the ladder's non-leaf time to the L1<->
epoch-cache boundary: the writeback scatter (fusion.131, 48.8 ms / 24
executions = 2.03 ms) and the rebuild gather (25.4 ms / 24 = 1.06 ms)
at the exact shape (131072 sorted distinct view rows against the
(1048576, 128) f32 epoch cache).  The round-3/4 emitter-rate model says
both ops SWEEP the parent array (scatter = RMW stream, useful rate =
density x stream rate; gather = read stream at ~100-125 GB/s useful
regardless of density), so a pallas per-row-DMA kernel beats them only
if its DMA issue rate exceeds the sweep's row-equivalent rate.  This
script measures, chained inside one dispatch each (per-launch timing
would measure the launch):

  set      - the emitter writeback exactly as the ladder issues it
  gather   - the emitter rebuild exactly as the ladder issues it
  dus/ds   - dynamic_update_slice / dynamic_slice of the same BYTES
             contiguously (the no-sweep upper bound a block-major slot
             layout could reach)
  kernel   - the pallas per-row-DMA row update (FF_SCATTER_PIPELINE=1
             path) at n in {2048..131072} to extract the DMA issue rate

Every timing is printed with the launch-latency probe before and after
(scripts/probe_chip.py).
Usage: python scripts/ab_boundary.py [reps]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.profiling import device_fence
    from scripts.probe_chip import probe

    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    R, n, d = 1_048_576, 131_072, 128
    rng = np.random.default_rng(0)
    rowof = np.sort(rng.choice(R, size=n, replace=False)).astype(np.int32)
    # chain() does not donate, so the jit copies its inputs internally —
    # one device placement serves every variant and timing run
    cache_d = jax.device_put(
        rng.standard_normal((R, d)).astype(np.float32))
    l1_d = jax.device_put(rng.standard_normal((n, d)).astype(np.float32))

    def fresh():
        return cache_d, l1_d
    rowof_d = jax.device_put(rowof)

    def chain(body):
        """reps executions inside ONE dispatch; the carry threads the
        array so nothing hoists, barrier keeps ordering."""
        def f(arrs):
            def step(c, _):
                c = jax.lax.optimization_barrier(c)
                return body(c), None
            return jax.lax.scan(step, arrs, None, length=reps)[0]
        # no donation: the caller keeps ``arrs`` to fence on; the
        # scan's internal carry aliasing still lets every iteration
        # update in place (one initial copy amortized)
        return jax.jit(f)

    def timeit(name, build_arrs, body, bytes_useful):
        """Trace-derived device-busy per op (wall includes the launch):
        one traced window of ``reps`` chained executions; busy/reps is
        the op."""
        from dlrm_flexflow_tpu.profiling import traced_device_busy_ms
        g = chain(body)
        arrs = build_arrs()
        device_fence(g(arrs))   # compile + warm
        pre = probe()
        arrs2 = build_arrs()
        busy_ms = traced_device_busy_ms(lambda: device_fence(g(arrs2)))
        post = probe()
        dt = busy_ms * 1e-3 / reps
        print(f"{name:24s} {dt*1e3:8.3f} ms/op busy  "
              f"{bytes_useful/dt/1e9:7.1f} GB/s useful  "
              f"probes {pre:.0f}/{post:.0f} us", flush=True)
        return dt

    row_bytes = n * d * 4

    # -- the ladder's exact writeback: sorted scatter-SET --------------
    timeit("set(sorted,drop)",
           lambda: fresh() + (rowof_d,),
           lambda a: (a[0].at[a[2]].set(a[1], mode="drop",
                                        indices_are_sorted=True),
                      a[1], a[2]),
           row_bytes)

    # -- the ladder's exact rebuild: row gather ------------------------
    def g_body(a):
        got = jnp.take(a[0], a[2], axis=0)
        # fold the gather into the carry so it cannot be DCE'd/hoisted
        return a[0], got, a[2]
    timeit("gather(rows)", lambda: fresh() + (rowof_d,), g_body,
           row_bytes)

    # -- contiguous upper bounds (what block-major slots would issue) --
    timeit("dus(contiguous)",
           fresh,
           lambda a: (jax.lax.dynamic_update_slice(a[0], a[1], (0, 0)),
                      a[1]),
           row_bytes)

    def ds_body(a):
        got = jax.lax.dynamic_slice(a[0], (0, 0), (n, d))
        return a[0], got
    timeit("ds(contiguous)", fresh, ds_body, row_bytes)

    # -- pallas per-row-DMA kernel: issue-rate curve -------------------
    from dlrm_flexflow_tpu.ops.pallas_scatter import (
        sparse_row_update, supports_pallas_row_update)
    for nk in (2048, 8192, 32768, 131072):
        # force=True does not bypass the static eligibility gate — an
        # inherited FF_SCATTER_BLOCK that doesn't divide nk would make
        # sparse_row_update silently time the XLA fallback and label it
        # kernel data (ab_scatter.py guards the same way)
        assert supports_pallas_row_update(R, d, nk), (
            f"FF_SCATTER_BLOCK must divide n={nk} for a real kernel A/B")
        ids_k = jax.device_put(np.sort(
            rng.choice(R, size=nk, replace=False)).astype(np.int32))
        upd_k = jax.device_put(
            rng.standard_normal((nk, d)).astype(np.float32))

        def k_body(a, ids_k=ids_k, upd_k=upd_k):
            return (sparse_row_update(a[0], ids_k, upd_k, 1.0,
                                      force=True),) + a[1:]
        dt = timeit(f"kernel(n={nk})",
                    lambda: fresh() + (rowof_d,), k_body, nk * d * 4)
        print(f"{'':24s} -> {nk/dt/1e6:6.2f} M row-DMAs/s", flush=True)


if __name__ == "__main__":
    main()
