"""head_us_per_step — layer: ops / kernels; moves: samples_per_s.
Self time of the ``head`` group of the cell's family
(``models/<family>.py::PHASE_GROUPS``; the language model's: the
embedding lookups, the MTP module's norms and projection, both final
norms, both uses of the head and the two cross-entropies) over the
window's steps."""

from benchmarks.lib import phases


def read(ctx):
    return phases.us_per_step(ctx, "head")
