"""ffn_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``ffn`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the language model's: the leading
dense layers' gated FFN under ``ff.lm.ffn``, its pre-norm and residual
add — forward, backward and the forward computed again) over the
window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "ffn")
