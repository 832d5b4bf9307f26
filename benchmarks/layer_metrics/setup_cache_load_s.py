"""setup_cache_load_s — layer: entry points; moves: setup_s.  Wall
seconds reading executables back from the persistent compilation cache
between the ``setup.model`` mark and the window (``cache_load`` records,
united): the part of ``compile_s`` that is no compilation."""

from benchmarks.lib import setup_timeline


def read(ctx):
    return setup_timeline.read(ctx, "cache_load_s")
