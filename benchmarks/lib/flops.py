"""Peaks of the chips the benchmark knows, and the operations a DLRM
training step requires, from the configuration's shapes.

Kept with the benchmark (not ``bench.py::_model_flops_per_step`` or
``sim/cost_model.TPUMachineModel``), so that no PR which edits the
program can move a utilisation.
"""

from __future__ import annotations

#: keyed by ``jax.devices()[0].device_kind``.  A device that is not here
#: is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add "
                       f"them to benchmarks/lib/flops.py with their source")
    return PEAKS[device_kind]


def train_flops_per_sample(model_shape: dict) -> int:
    """Forward + backward floating-point operations one sample requires
    in the two MLPs (a multiply-add counts 2).  Every dense layer costs
    2*in*out forward and 2*in*out for its weight gradient; the gradient
    with respect to its input costs another 2*in*out, except in the
    bottom MLP's first layer, whose input is data.  The ``cat``
    interaction and a bag of 1 add no multiply-adds.  Recomputation
    would not count; the optimizer's axpy is left out (it is bytes, not
    matmul work)."""
    if model_shape["arch_interaction_op"] != "cat":
        raise ValueError("only the 'cat' interaction is counted here: "
                         f"{model_shape['arch_interaction_op']!r}")
    flops = 0
    for mlp, needs_input_grad in ((model_shape["mlp_bot"], False),
                                  (model_shape["mlp_top"], True)):
        for i, (fan_in, fan_out) in enumerate(zip(mlp[:-1], mlp[1:])):
            passes = 3 if (i > 0 or needs_input_grad) else 2
            flops += 2 * fan_in * fan_out * passes
    return flops
