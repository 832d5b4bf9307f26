"""mlp_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``mlp`` group of ``lib/phases.py`` (the graph ops forward
and backward: MLPs, interaction, loss; and the step's metrics fold) over
the window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "mlp")
