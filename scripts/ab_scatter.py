"""A/B the pallas sparse-row-update kernel on the real chip.

Sweeps FF_SCATTER_BLOCK (the kernel re-imports per value via subprocess)
over the DLRM headline shape: stacked 8x1M x 64 table (viewed (4M, 128)),
2048 updates/step.  Each timing is printed with the launch-latency probe
(scripts/probe_chip.py) before and after it.

Usage:  python scripts/ab_scatter.py [block ...]   (default 8 16 32 64)
"""
import os
import subprocess
import sys

_CHILD = r"""
import os, time
import numpy as np
import jax, jax.numpy as jnp
from dlrm_flexflow_tpu.ops.pallas_scatter import sparse_row_update, _BLOCK
from dlrm_flexflow_tpu.profiling import device_fence
from scripts.probe_chip import probe

rows, d, n = 8 * 1_000_000, 64, 2048
from dlrm_flexflow_tpu.ops.pallas_scatter import supports_pallas_row_update
assert supports_pallas_row_update(rows, d, n), (
    f"FF_SCATTER_BLOCK={_BLOCK} would silently fall back to XLA scatter "
    f"(n={n} must divide by it) — refusing to report a bogus A/B line")
key = jax.random.PRNGKey(0)
table = jax.random.normal(key, (rows, d), jnp.float32)
ids = jax.random.randint(key, (n,), 0, rows)
upd = jax.random.normal(key, (n, d), jnp.float32)

f = jax.jit(lambda t, i, u: sparse_row_update(t, i, u, -0.01),
            donate_argnums=0)
table = f(table, ids, upd)
device_fence(table)
pre = probe()
reps = 50
t0 = time.perf_counter()
for _ in range(reps):
    table = f(table, ids, upd)
device_fence(table)
dt = (time.perf_counter() - t0) / reps * 1e3
post = probe()
pipe = os.environ.get("FF_SCATTER_PIPELINE", "0")
print(f"BLOCK={_BLOCK} PIPE={pipe}: {dt:.3f} ms/update  "
      f"probes {pre:.0f}/{post:.0f} us", flush=True)
"""


def main():
    blocks = [int(b) for b in sys.argv[1:]] or [8, 16, 32, 64]
    for pipe in ("0", "1"):
        for b in blocks:
            env = dict(os.environ, FF_SCATTER_BLOCK=str(b),
                       FF_SCATTER_PIPELINE=pipe,
                       # this script A/Bs the pallas kernel's tuning knobs;
                       # without this the default impl (packed XLA scatter)
                       # would be timed instead and labeled as kernel data
                       FF_SCATTER_IMPL="kernel")
            subprocess.run([sys.executable, "-c", _CHILD], env=env,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))


if __name__ == "__main__":
    main()
