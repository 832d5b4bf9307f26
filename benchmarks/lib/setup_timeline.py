"""The program's own set-up, as the five ``setup_*`` readers read it:
``dlrm_flexflow_tpu.telemetry.setup_breakdown`` from the cell's
``setup.model`` mark (``FFModel.__init__``) to the window's first
instruction (``ctx["window"]["t0"]``, on ``time.perf_counter`` like the
program's timeline).  A program without the timeline, as before PR 40,
gives nothing, and its readers ``None``."""


def read(ctx, key: str):
    try:
        from dlrm_flexflow_tpu.telemetry import setup_breakdown
    except ImportError:
        return None
    breakdown = setup_breakdown(ctx["window"]["t0"])
    return None if breakdown is None else breakdown[key]
