"""data_wait_ms_per_step.stream — layer: trainer; moves:
stream_samples_per_s.  ``fit``'s own ``phase_time`` summary
(``phase="fit"``): host milliseconds waiting on the loader's next batch
over the steps it counted."""


def read(ctx):
    fits = [e for e in ctx["events"]
            if e.get("type") == "phase_time" and e.get("phase") == "fit"]
    if not fits:
        return None
    return fits[-1]["data_wait_ms"] / fits[-1]["steps"]
