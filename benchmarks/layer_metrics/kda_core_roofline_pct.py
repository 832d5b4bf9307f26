"""kda_core_roofline_pct — layer: ops / kernels; moves: samples_per_s.
The Kimi Delta Attention recurrence's share of its roofline: the least
time the chip could take for the recurrences the window's samples
require (every KDA layer's, forward + backward, from shapes:
``models/<family>.py::kda_core_work`` counts 6 dk dv operations a token
and head forward and twice that backward, and q, k, v, the dk-wide g,
beta, o and their gradients each moved once at the width the program
holds them; the larger of operations / bf16 peak and bytes / HBM
bandwidth) over the self time under ``ff.lm.kda.core`` (normalisation
of q and k, both gates' arithmetic, the chunked rule: forward, backward
and the forward computed again).  The same work whatever implements it:
the chunked form's triangular systems, its sub-blocks' products and
their exponentials are in the time and not in the work, as recomputed
work is not.  ``None`` where the family counts no such work or no
instruction carries the scope."""

from benchmarks.lib import phases


def read(ctx):
    family, peaks = ctx["family"], ctx["peaks"]
    if not hasattr(family, "kda_core_work"):
        return None
    by_phase = phases.window_phases(ctx["events"])
    if by_phase is None:
        return None
    core_us = phases.split(ctx["trace"]["self_us"], by_phase,
                           ctx["trace"]["busy_us"],
                           {"core": ("ff.lm.kda.core",)})["core"]
    if core_us <= 0:
        return None
    flops, nbytes = family.kda_core_work(ctx["config"], ctx["traffic"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    cores = family.kda_layers(ctx["config"], ctx["traffic"]) \
        * ctx["window"]["samples"]
    return 100.0 * least_s * cores / (core_us / 1e6)
