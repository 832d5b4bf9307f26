"""``benchmarks/run.py`` with a fault planted in the program, for the
Ling-style cell's comparison on the chip:

    chiprun -- python3 scripts/kda_fault.py <kind> --workload \\
        ling3-flash-ep64.pretrain-8k --seed <n> --seconds 10 --trace 0

``decay``: the KDA rule's decay taken as one scalar a head (the mean over
its channels: the sibling's rule); ``groups``: the router's group mask
dropped (the top-8 over all 512).  Each has to print ``correct: false``
(PERF.md section 4 has the readings); never part of a measurement.  (A
held expert's rows zeroed is planted by ``tests/test_kda_moe_lm.py``,
on the CPU.)
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402

from dlrm_flexflow_tpu.ops import deltanet, moe as moe_ops  # noqa: E402

kind = sys.argv[1]
if kind == "decay":
    def scalar_decay(q, k, v, g, beta, cd):
        return deltanet._chunk_operands(q, k, v, jnp.mean(g, axis=-1), beta,
                                        cd)

    deltanet._channel_chunk_operands = scalar_decay
elif kind == "groups":
    whole_init = moe_ops.HeldExpertsMoE.__init__

    def ungrouped(self, *args, **kw):
        whole_init(self, *args, **{**kw, "n_group": 1, "topk_group": 1})

    moe_ops.HeldExpertsMoE.__init__ = ungrouped
else:
    sys.exit(f"kda_fault.py: no fault {kind!r} (decay, groups)")
print(f"fault: planted {kind}", flush=True)
sys.argv = ["benchmarks/run.py"] + sys.argv[2:]
runpy.run_path(os.path.join(ROOT, "benchmarks/run.py"), run_name="__main__")
