"""expert_mm_roofline_pct — layer: ops / kernels; moves: samples_per_s.
The held experts' grouped matmuls' share of their roofline: the least
time the chip could take for the rows the window really routed to held
experts (``held_assignments`` of the window's ``op_counters`` events;
``models/<family>.py::expert_matmul_work`` counts operations and bytes,
forward + backward, each held expert's weights read once forward and
twice backward in every layer and step) over the self time under
``ff.lm.moe.experts`` (which holds the forward computed again).
``None`` where the window counted nothing or no instruction carries the
scope."""

from benchmarks.lib import phases


def read(ctx):
    by_phase = phases.window_phases(ctx["events"])
    counted = [e["counters"] for e in ctx["events"]
               if e.get("type") == "op_counters"]
    if by_phase is None or not counted:
        return None
    experts_us = phases.split(ctx["trace"]["self_us"], by_phase,
                              ctx["trace"]["busy_us"],
                              {"experts": ("ff.lm.moe.experts",)})["experts"]
    if experts_us <= 0:
        return None
    family, peaks = ctx["family"], ctx["peaks"]
    rows = sum(c["held_assignments"] for c in counted)
    layer_steps = family.moe_layers(ctx["config"], ctx["traffic"]) \
        * ctx["window"]["steps"]
    # the weights are read per layer and step, the rows as counted
    flops, nbytes = family.expert_matmul_work(
        ctx["config"], ctx["traffic"], rows / layer_steps)
    least_s = layer_steps * max(flops / peaks["bf16_flops_per_s"],
                                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (experts_us / 1e6)
