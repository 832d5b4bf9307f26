"""ladder_us_per_step — layer: epoch row-cache + ladder; moves: samples_per_s.
Self time of the ``ladder`` group of ``lib/phases.py`` (each level's fetch
and writeback, and the scans' own carry and stacking ops) over the
window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "ladder")
