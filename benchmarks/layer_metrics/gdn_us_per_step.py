"""gdn_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``gdn`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the hybrid model's: everything
under ``ff.lm.gdn`` — the Gated DeltaNet layers' pre-norm, the two input
projections, the causal convolution, the chunked delta rule, the gated
norm, ``W_out`` and the residual add — forward, backward and the forward
computed again) over the window's steps.  ``None`` where the family has
no such group."""

from benchmarks.lib import phases


def read(ctx):
    if "gdn" not in ctx["family"].PHASE_GROUPS:
        return None
    return phases.us_per_step(ctx, "gdn")
