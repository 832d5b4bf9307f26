"""dense_update_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``dense_update`` group of ``lib/phases.py`` (the
optimizer's update of the MLP parameters) over the window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "dense_update")
