"""Device time by phase of the compiled training program.

The reduced trace (``lib/trace.py``) keeps ``{HLO instruction name:
self us}`` and nothing that says what an instruction is for.  The
program can say: its training wrappers name every jitted program they
dispatch in a ``program`` telemetry event, and
``dlrm_flexflow_tpu.profiling.program_phases(name)`` gives ``{HLO
instruction name: phase}`` from that program's optimized HLO, a phase
being the innermost ``jax.named_scope`` the program opened around the
code (``profiling.phase_of`` knows the naming rule; this file only
groups phases into metrics).  The join of the two is read here, after
the window: nothing below runs while anything is measured.

On a program without ``program_phases`` (a commit before the scopes)
every function returns ``None`` and the line leaves the metric out.
With it, a window that named no program, or a map that cannot be
built, raises: no reader prints 0 or a guess.
"""

from __future__ import annotations

import time

UNATTRIBUTED = "unattributed"

#: metric group -> the phase scopes it sums; a phase belongs to the
#: group holding it or its longest dotted prefix (``<scope>.bwd`` is the
#: backward of ``<scope>``), and to no group if none does.  The five
#: groups and the rest add up to ``busy_us`` (PERF.md §3 repeats this).
GROUPS = {
    "cache": ("ff.cache",),        # prologue, slot plans, epilogue
    "ladder": ("ff.ladder",),      # fetch, writeback, the scans' own ops
    "embedding": ("ff.step.gather", "ff.step.row_update"),
    "mlp": ("ff.step.model", "ff.step.metrics"),  # forward + backward
    "dense_update": ("ff.step.dense_update",),
}


def group_of(phase: str):
    """The group of one phase, or ``None``."""
    best, size = None, -1
    for group, scopes in GROUPS.items():
        for scope in scopes:
            if (phase == scope or phase.startswith(scope + ".")) \
                    and len(scope) > size:
                best, size = group, len(scope)
    return best


def merge_maps(maps: list) -> dict:
    """One ``{instruction: phase}`` for a window that ran several
    programs: XLA numbers instructions per program, so two programs can
    both have a ``fusion.5``.  A name they give different phases is
    ``unattributed``: the trace cannot say whose slice it was."""
    merged = {}
    for phases in maps:
        for name, phase in phases.items():
            if merged.setdefault(name, phase) != phase:
                merged[name] = UNATTRIBUTED
    return merged


def split(self_us: dict, phases: dict, busy_us: float) -> dict:
    """``{group: us}`` for the five groups plus ``unattributed``: what
    is left of ``busy_us`` (instructions without a phase or outside
    every group, and the moments inside a program between two
    instructions).  The six add up to ``busy_us``."""
    out = dict.fromkeys(GROUPS, 0.0)
    for name, us in self_us.items():
        group = group_of(phases.get(name, UNATTRIBUTED))
        if group is not None:
            out[group] += us
    out[UNATTRIBUTED] = busy_us - sum(out.values())
    return out


def window_phases(events: list):
    """``{instruction: phase}`` for the programs the window's telemetry
    named, or ``None`` where the program has no such instrument."""
    from dlrm_flexflow_tpu import profiling

    if not hasattr(profiling, "program_phases"):
        return None
    names = list(dict.fromkeys(e["name"] for e in events
                               if e.get("type") == "program"))
    if not names:
        raise RuntimeError("the window's telemetry named no program: "
                           "no `program` event among its events")
    maps = []
    for name in names:
        t0 = time.perf_counter()
        maps.append(profiling.program_phases(name))
        dt = time.perf_counter() - t0
        if dt > 0.01:  # the first ask builds the map; later ones are free
            print(f"phases: map of {name} built in {dt:.3f} s after the "
                  f"window, {len(maps[-1])} instructions", flush=True)
    return merge_maps(maps)


def window_split(ctx: dict):
    """``split`` of a reader's context, or ``None`` (see the top)."""
    phases = window_phases(ctx["events"])
    if phases is None:
        return None
    return split(ctx["trace"]["self_us"], phases, ctx["trace"]["busy_us"])


def us_per_step(ctx: dict, group: str):
    """One group's device time over the window's training steps."""
    parts = window_split(ctx)
    return None if parts is None else parts[group] / ctx["window"]["steps"]


def say_top(ctx: dict, n: int = 10) -> None:
    """Print the window's ``n`` instructions with most self time, each
    with its phase: what a strange ``*_us_per_step`` would need."""
    phases = window_phases(ctx["events"])
    ranked = sorted(ctx["trace"]["self_us"].items(), key=lambda kv: -kv[1])
    print("phases: top ops: " + ", ".join(
        f"{name} {us / 1e3:.2f} ms {phases.get(name, UNATTRIBUTED)}"
        for name, us in ranked[:n]), flush=True)
