"""cache_us_per_step — layer: epoch row-cache + ladder; moves: samples_per_s.
Self time of the instructions in the ``cache`` group of
``lib/phases.py`` (row-cache prologue, slot plans, epilogue) over the
window's steps: a dispatch's fixed cost, amortised over its epochs."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "cache")
