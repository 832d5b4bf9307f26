"""setup_compiled_programs — layer: entry points; moves: setup_s.
Executables compiled, not loaded from the persistent cache, between the
``setup.model`` mark and the window (``compile`` records with no cache
hit inside them): 0 in a warm run."""

from benchmarks.lib import setup_timeline


def read(ctx):
    return setup_timeline.read(ctx, "compiled_programs")
