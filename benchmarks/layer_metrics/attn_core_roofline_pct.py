"""attn_core_roofline_pct — layer: ops / kernels; moves: samples_per_s.
The causal attention core's share of its roofline: the least time the
chip could take for the cores the window's samples require (every
layer's, forward + backward, from shapes:
``models/<family>.py::attention_core_work`` counts operations and
bytes; the larger of operations / bf16 peak and bytes / HBM bandwidth)
over the self time under ``ff.lm.mla.core``.  That time holds the
forward computed again in the backward pass; the work counted does not,
so recomputation lowers the share.  ``None`` where no instruction
carries the scope."""

from benchmarks.lib import phases


def read(ctx):
    by_phase = phases.window_phases(ctx["events"])
    if by_phase is None:
        return None
    core_us = phases.split(ctx["trace"]["self_us"], by_phase,
                           ctx["trace"]["busy_us"],
                           {"core": ("ff.lm.mla.core",)})["core"]
    if core_us <= 0:
        return None
    family, peaks = ctx["family"], ctx["peaks"]
    flops, nbytes = family.attention_core_work(ctx["config"], ctx["traffic"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    cores = family.attention_layers(ctx["config"], ctx["traffic"]) \
        * ctx["window"]["samples"]
    return 100.0 * least_s * cores / (core_us / 1e6)
