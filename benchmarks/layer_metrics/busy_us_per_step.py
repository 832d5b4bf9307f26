"""busy_us_per_step — layer: ops / kernels; moves: samples_per_s.
Device busy of the traced window (busiest chip) over the training steps
in it."""


def read(ctx):
    return ctx["trace"]["busy_us"] / ctx["window"]["steps"]
