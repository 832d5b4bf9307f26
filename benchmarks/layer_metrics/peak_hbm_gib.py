"""peak_hbm_gib — layer: device; moves: setup_s.  Peak bytes in use on
the fullest chip, read right after the window and before the reference
is built, so it is the trainer's own (today ``init()``'s transient)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
