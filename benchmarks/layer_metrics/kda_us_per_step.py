"""kda_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``kda`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the Ling-style model's: everything
under ``ff.lm.kda`` — the Kimi Delta Attention layers' pre-norm, the six
input projections, the three causal convolutions, both gates, the chunked
delta rule with its decay per channel, the gated norm, ``W_out`` and the
residual add — forward, backward and the forward computed again) over
the window's steps.  ``None`` where the family has no such group."""

from benchmarks.lib import phases


def read(ctx):
    if "kda" not in ctx["family"].PHASE_GROUPS:
        return None
    return phases.us_per_step(ctx, "kda")
