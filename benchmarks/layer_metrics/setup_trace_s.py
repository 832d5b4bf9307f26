"""setup_trace_s — layer: entry points; moves: setup_s.  Wall seconds
tracing jaxprs between the ``setup.model`` mark and the window (the set-
up timeline's ``trace`` records, united: a nested trace counts once;
less what a lowering or compile inside them took)."""

from benchmarks.lib import setup_timeline


def read(ctx):
    return setup_timeline.read(ctx, "trace_s")
