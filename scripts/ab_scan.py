"""A/B 1-D cumulative-scan lowerings on the real chip (round 5).

The headline trace shows grouped_region_plan's three cumulative scans
(cummin x2 via _last_idx_from_first, cummax x1) cost 7.48 ms EACH over
s32[1,048,576] — 22.4 ms of the 219 ms busy window (10%) for 12 MB of
traffic (~1.7 GB/s).  XLA:TPU's 1-D cumulative lowering is the suspect;
a two-pass reshaped form (per-row scan along the minor dim + a tiny
carry scan + a broadcast combine) moves the same data through O(n)
vectorized work.

Measures, chained inside one dispatch each (trace-derived busy; wall
would include the launch):

  cummax_1d      - jax.lax.cummax over s32[n]           (the ladder's form)
  cummax_2d_rxc  - reshape (r, c), cummax axis=1, carry combine
  cummax_doubling - ops/slotting.py::_cummax, the region plans' own
                   (shift-and-max doubling on a (r, 1024) reshape)
  assoc_scan     - jax.lax.associative_scan(maximum)
  suffix_min_1d  - flip-cummin-flip (the _last_idx_from_first form)
  suffix_min_2d  - two-pass suffix-min, same reshape trick
  cumsum_1d/2d   - the slot_rows rank scan, both forms

Usage: python scripts/ab_scan.py [reps]
       python scripts/ab_scan.py names   (no chip needed: compiles the
           region plans' two scans for a described v5e, once on
           ``_cummax`` and once on the two-pass ``jax.lax.cummax`` at
           (4096, 256), and prints which instructions keep the caller's
           ``ff.*`` scope: why ``_cummax`` is not the library's scan)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def two_pass_cummax(r, c):
    import jax
    import jax.numpy as jnp

    def body(x):
        m = x.reshape(r, c)
        row = jax.lax.cummax(m, axis=1)
        carry = jax.lax.cummax(row[:, -1])
        carry = jnp.concatenate(
            [jnp.full((1,), jnp.iinfo(jnp.int32).min, jnp.int32),
             carry[:-1]])
        return jnp.maximum(row, carry[:, None]).reshape(-1)
    return body


def names():
    """Compile ``_run_has_mark`` + ``_fill_from_marked`` under one
    scope for a v5e (attached or described) on each scan and count the
    compiled instructions by ``profiling.phase_of``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import collections
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from dlrm_flexflow_tpu.ops import slotting
    from dlrm_flexflow_tpu.profiling import hlo_phases

    n = 1 << 20
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    args = [jax.ShapeDtypeStruct((n,), dt, sharding=chip)
            for dt in (jnp.bool_, jnp.bool_, jnp.int32)]
    doubling = slotting._cummax
    for name, scan in (("_cummax (doubling)", doubling),
                       ("two-pass lax.cummax", two_pass_cummax(4096, 256))):
        def plans(first, marked, vals):  # a new function: a new trace
            with jax.named_scope("ff.cache.plan"):
                return (slotting._run_has_mark(first, marked),
                        slotting._fill_from_marked(vals, first,
                                                   reverse=True))

        slotting._cummax = scan
        try:
            text = jax.jit(plans).lower(*args).compile().as_text()
        finally:
            slotting._cummax = doubling
        phases = hlo_phases(text)
        # what runs on the device: fusions and the scans' own ops
        timed = {k: v for k, v in phases.items()
                 if k.startswith(("fusion", "reduce-window"))
                 or "_fusion" in k}
        lost = sorted(k for k, v in timed.items() if v == "unattributed")
        print(f"{name}: {dict(collections.Counter(timed.values()))}; "
              f"unattributed: {' '.join(lost[:12]) or 'none'}")


def main():
    if sys.argv[1:] == ["names"]:
        return names()
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.profiling import device_fence, traced_device_busy_ms
    from scripts.probe_chip import probe

    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    n = 1 << 20
    rng = np.random.default_rng(0)
    # run-start-flag-like payload: mostly large sentinel, some indices
    x_np = np.where(rng.random(n) < 0.4, np.arange(n), n).astype(np.int32)
    x_d = jax.device_put(x_np)

    def chain(body):
        def f(x):
            def step(c, _):
                c = jax.lax.optimization_barrier(c)
                return body(c), None
            return jax.lax.scan(step, x, None, length=reps)[0]
        return jax.jit(f)

    def timeit(name, body, check=None):
        g = chain(body)
        device_fence(g(x_d))  # compile + warm
        pre = probe()
        busy_ms = traced_device_busy_ms(lambda: device_fence(g(x_d)))
        post = probe()
        dt_ms = busy_ms / reps
        ok = ""
        if check is not None:
            got = np.asarray(jax.jit(body)(x_d))
            ok = "  OK" if np.array_equal(got, check) else "  MISMATCH"
        print(f"{name:18s} {dt_ms:8.3f} ms/op   "
              f"(probe {pre:.0f}/{post:.0f} us){ok}")
        return dt_ms

    ref_cummax = np.maximum.accumulate(x_np)
    ref_sufmin = np.minimum.accumulate(x_np[::-1])[::-1]
    ref_cumsum = np.cumsum((x_np < n).astype(np.int32)).astype(np.int32)

    timeit("cummax_1d", lambda x: jax.lax.cummax(x), ref_cummax)

    for r, c in ((1024, 1024), (4096, 256), (256, 4096), (8192, 128)):
        timeit(f"cummax_2d_{r}x{c}", two_pass_cummax(r, c), ref_cummax)

    from dlrm_flexflow_tpu.ops.slotting import _cummax
    timeit("cummax_doubling", _cummax, ref_cummax)

    timeit("assoc_scan_max",
           lambda x: jax.lax.associative_scan(jnp.maximum, x), ref_cummax)

    timeit("suffix_min_1d",
           lambda x: jnp.flip(jax.lax.cummin(jnp.flip(x))), ref_sufmin)

    def two_pass_sufmin(r, c):
        def body(x):
            m = x.reshape(r, c)
            row = jnp.flip(jax.lax.cummin(jnp.flip(m, 1), axis=1), 1)
            carry = jnp.flip(jax.lax.cummin(jnp.flip(row[:, 0])))
            carry = jnp.concatenate(
                [carry[1:], jnp.full((1,), jnp.iinfo(jnp.int32).max,
                                     jnp.int32)])
            return jnp.minimum(row, carry[:, None]).reshape(-1)
        return body

    for r, c in ((1024, 1024), (4096, 256)):
        timeit(f"suffix_min_2d_{r}x{c}", two_pass_sufmin(r, c), ref_sufmin)

    timeit("cumsum_1d",
           lambda x: jnp.cumsum((x < n).astype(jnp.int32)), ref_cumsum)

    def two_pass_cumsum(r, c):
        def body(x):
            f = (x < n).astype(jnp.int32).reshape(r, c)
            row = jnp.cumsum(f, axis=1)
            carry = jnp.cumsum(row[:, -1])
            carry = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), carry[:-1]])
            return (row + carry[:, None]).reshape(-1)
        return body

    timeit("cumsum_2d_1024", two_pass_cumsum(1024, 1024), ref_cumsum)


if __name__ == "__main__":
    main()
