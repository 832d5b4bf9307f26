"""Launch-latency probe: one chained 1024^3 bf16 matmul per dispatch.

Prints one line: ``probe_us=<N>`` — the wall time per launch of a jitted
1024^3 bf16 matmul (about 11 us of MXU work at the v5e peak), 30 launches
chained on their outputs and closed by one fence.  What it measures is
the host's cost to launch a small program on this machine, not device
speed: compare it with the device-busy time of the program under study to
see whether per-launch dispatch can matter.  The A/B scripts print it
next to their timings for the same reason.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dlrm_flexflow_tpu.profiling import device_fence


def probe(n=30):
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    device_fence(f(x))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        y = f(x)
        for _ in range(n - 1):
            y = f(y)
        device_fence(y)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


if __name__ == "__main__":
    print(f"probe_us={probe():.1f}")
