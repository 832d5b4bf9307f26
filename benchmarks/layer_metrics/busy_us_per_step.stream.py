"""busy_us_per_step.stream — layer: ops / kernels; moves: stream_samples_per_s.
Device busy of the traced window (busiest chip) over the training steps
in it."""


def read(ctx):
    return ctx["trace"]["busy_us"] / ctx["window"]["steps"]
