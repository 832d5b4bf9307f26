"""optimizer_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``dense_update`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``: ``ff.step.dense_update``, the
optimizer's update of every dense tensor; the language model's Adam
reads and writes weights, both moments and the gradient, 16 bytes a
parameter and step) over the window's steps.  The same scope as
``dense_update_us_per_step``, whose entry is held to DLRM's cells."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "dense_update")
