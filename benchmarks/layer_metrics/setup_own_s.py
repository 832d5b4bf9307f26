"""setup_own_s — layer: entry points; moves: setup_s.  Seconds from the
cell's ``setup.model`` mark (``FFModel.__init__``) to the window's first
instruction: ``setup_s`` without the imports and the runtime's
start-up."""

from benchmarks.lib import setup_timeline


def read(ctx):
    return setup_timeline.read(ctx, "own_s")
