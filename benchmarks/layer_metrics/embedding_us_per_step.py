"""embedding_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``embedding`` group of ``lib/phases.py`` (the step's row
gather from the innermost cache and its row-sparse update) over the
window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "embedding")
