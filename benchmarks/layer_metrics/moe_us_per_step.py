"""moe_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``moe`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the language model's: everything
under ``ff.lm.moe`` — routing, dispatch, the held experts' grouped
matmuls, combine, the shared expert) over the window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "moe")
