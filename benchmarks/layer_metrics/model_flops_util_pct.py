"""model_flops_util_pct — layer: ops / kernels; moves: samples_per_s.
Forward + backward FLOPs the MLPs require for the traced window's
samples (the benchmark's arithmetic, from the configuration's shapes)
over busy time x the chip's bf16 matmul peak x chips.  An end-to-end
utilisation of the matmul peak, not a kernel's roofline share."""

from benchmarks.lib.flops import train_flops_per_sample


def read(ctx):
    flops = train_flops_per_sample(ctx["config"]["model"]) \
        * ctx["window"]["samples"]
    busy_s = ctx["trace"]["busy_us"] / 1e6
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (busy_s * peak)
