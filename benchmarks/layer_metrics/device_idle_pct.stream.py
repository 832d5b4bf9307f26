"""device_idle_pct.stream — layer: device; moves: stream_samples_per_s.  1 - busy /
wall of the one traced steady window, busiest chip's "XLA Modules"."""


def read(ctx):
    busy_s = ctx["trace"]["busy_us"] / 1e6
    return 100.0 * (1.0 - busy_s / ctx["window"]["wall_s"])
