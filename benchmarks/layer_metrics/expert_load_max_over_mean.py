"""expert_load_max_over_mean — layer: ops / kernels; moves:
samples_per_s.  How unevenly the router loads the experts: in each
expert layer the most loaded expert's tokens over the mean, over ALL
experts of the layer (held or not: the counters count the routing, not
the share), from the ``tokens_per_expert`` of the window's
``op_counters`` events; the mean over the layers.  1 is an even load; in
a deployment the most loaded expert's chip sets the layer's time.
``None`` where the window counted nothing."""

import numpy as np


def read(ctx):
    per_op = {}
    for event in ctx["events"]:
        if event.get("type") == "op_counters":
            tokens = np.asarray(event["counters"]["tokens_per_expert"],
                                np.float64)
            per_op[event["op"]] = per_op.get(event["op"], 0) + tokens
    if not per_op:
        return None
    return float(np.mean([t.max() / t.mean() for t in per_op.values()]))
