"""moe_route_us_per_step — layer: ops / kernels; moves: samples_per_s.
The part of the expert layers that is not a matmul: self time under
``ff.lm.moe.route`` (scores, top-k, counts, the bias rule),
``ff.lm.moe.dispatch`` (sort, gather into the grouped matmul's buffer)
and ``ff.lm.moe.combine`` (back into token order, weighted sum), over
the window's steps.  In this cell each held expert sees a sixteenth of
its deployed load, so this part weighs more against the experts'
matmuls than it would there."""

from benchmarks.lib import phases

SCOPES = ("ff.lm.moe.route", "ff.lm.moe.dispatch", "ff.lm.moe.combine")


def read(ctx):
    by_phase = phases.window_phases(ctx["events"])
    if by_phase is None:
        return None
    parts = phases.split(ctx["trace"]["self_us"], by_phase,
                         ctx["trace"]["busy_us"], {"route": SCOPES})
    return parts["route"] / ctx["window"]["steps"]
