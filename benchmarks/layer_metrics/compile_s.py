"""compile_s — layer: entry points; moves: setup_s.  Seconds the
set-up spent in backend compiles, cache loads included
(``telemetry.compile_stats``, the program's jax.monitoring hooks)."""


def read(ctx):
    return ctx["setup_compile_s"]
