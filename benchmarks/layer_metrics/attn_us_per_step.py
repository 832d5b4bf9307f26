"""attn_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``attn`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the language model's: everything
under ``ff.lm.mla`` — the latent projections, norms and rotary
embedding, the attention core, the residual add — forward, backward and
the forward computed again) over the window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "attn")
