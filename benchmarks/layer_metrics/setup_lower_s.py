"""setup_lower_s — layer: entry points; moves: setup_s.  Wall seconds
lowering jaxprs to MLIR, the Mosaic lowering of Pallas kernels included,
between the ``setup.model`` mark and the window (``lower`` records,
united; less what a compile inside them took)."""

from benchmarks.lib import setup_timeline


def read(ctx):
    return setup_timeline.read(ctx, "lower_s")
