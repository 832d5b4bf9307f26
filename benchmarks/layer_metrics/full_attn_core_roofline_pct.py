"""full_attn_core_roofline_pct — layer: ops / kernels; moves:
samples_per_s.  The gated full-attention layers' causal core's share of
its roofline: the least time the chip could take for the cores the
window's samples require (every full-attention layer's, forward +
backward, from shapes: ``models/<family>.py::attention_core_work``; the
larger of operations / bf16 peak and bytes / HBM bandwidth) over the
self time under ``ff.lm.attn.core`` (``attn_core_roofline_pct`` reads
``ff.lm.mla.core``, the latent-attention family's).  The core's output
and log-sum-exp are kept through the recomputation, so that time holds
one forward and one backward.  ``None`` where no instruction carries the
scope."""

from benchmarks.lib import phases


def read(ctx):
    by_phase = phases.window_phases(ctx["events"])
    if by_phase is None:
        return None
    core_us = phases.split(ctx["trace"]["self_us"], by_phase,
                           ctx["trace"]["busy_us"],
                           {"core": ("ff.lm.attn.core",)})["core"]
    if core_us <= 0:
        return None
    family, peaks = ctx["family"], ctx["peaks"]
    flops, nbytes = family.attention_core_work(ctx["config"], ctx["traffic"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    cores = family.attention_layers(ctx["config"], ctx["traffic"]) \
        * ctx["window"]["samples"]
    return 100.0 * least_s * cores / (core_us / 1e6)
