"""collective_share_pct — layer: parallel; moves: samples_per_s.  Self
time of the collective ops on the busiest chip's "XLA Ops" track over
its busy time: the time the core spends issuing and waiting in
all-to-all, all-gather, all-reduce, reduce-scatter and
collective-permute ops.  Not their exposed part alone (PERF.md, Open
questions)."""

from benchmarks.lib.trace import collective_us


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * collective_us(trace["self_us"]) / trace["busy_us"]
