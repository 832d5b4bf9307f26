"""phase_attributed_pct — layer: device; moves: samples_per_s.
Share of device busy time on instructions that carry a phase of one of
the five ``*_us_per_step`` groups.  It guards them: each of the five
reads better when an instruction loses its name."""

from benchmarks.lib import phases


def read(ctx):
    parts = phases.window_split(ctx)
    if parts is None:
        return None
    phases.say_top(ctx)
    return 100.0 * (1.0 - parts[phases.UNATTRIBUTED]
                    / ctx["trace"]["busy_us"])
