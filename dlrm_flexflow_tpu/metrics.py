"""Training metrics.

TPU-native equivalent of the reference metrics subsystem
(reference: src/metrics_functions/metrics_functions.{h,cu} — ``PerfMetrics``
struct metrics_functions.h:26-58 with fields {train_all, train_correct, cce,
sparse_cce, mse, rmse, mae}; GPU kernels accumulate with atomicAdd into a
device-side struct, and an UPDATE_METRICS CPU task folds per-part futures
into a running aggregate (model.cc:1182-1205)).

Here PerfMetrics is a small pytree of scalars computed inside the jitted
train step (XLA reduces across the batch; under a sharded mesh the
cross-device reduction is an ICI psum inserted by SPMD — the moral
equivalent of the reference's future-chain fold).  ``MetricsAccumulator``
reproduces the host-side running aggregate + print.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Sequence

import jax.numpy as jnp

ALL_METRICS = ("accuracy", "categorical_crossentropy",
               "sparse_categorical_crossentropy", "mean_squared_error",
               "root_mean_squared_error", "mean_absolute_error")


def compute_metrics(preds, labels, metrics: Sequence[str],
                    loss_type: str) -> Dict[str, jnp.ndarray]:
    """One batch's PerfMetrics (reference metrics_functions.cu:57+).

    Returns sums (not means) plus the sample count, so aggregates fold
    exactly like the reference's running PerfMetrics.
    """
    out = {"train_all": jnp.asarray(preds.shape[0], jnp.float32)}
    sparse = "sparse" in loss_type
    for m in metrics:
        if m == "accuracy":
            if sparse:
                lab = labels
                if lab.ndim == preds.ndim:
                    lab = jnp.squeeze(lab, axis=-1)
                correct = jnp.argmax(preds, axis=-1) == lab.astype(jnp.int64)
            elif preds.shape[-1] == 1:
                # binary accuracy at 0.5 threshold (DLRM sigmoid output;
                # reference dlrm.cc uses MSE + accuracy this way)
                correct = (preds > 0.5) == (labels > 0.5)
                correct = jnp.squeeze(correct, axis=-1)
            else:
                correct = jnp.argmax(preds, axis=-1) == jnp.argmax(labels, axis=-1)
            out["train_correct"] = jnp.sum(correct.astype(jnp.float32))
        elif m in ("categorical_crossentropy", "cce"):
            eps = 1e-12
            out["cce"] = jnp.sum(-labels * jnp.log(preds + eps))
        elif m in ("sparse_categorical_crossentropy", "sparse_cce"):
            lab = labels
            if lab.ndim == preds.ndim:
                lab = jnp.squeeze(lab, axis=-1)
            logp = jnp.log(jnp.take_along_axis(
                preds, lab[..., None].astype(jnp.int32), axis=-1) + 1e-12)
            out["sparse_cce"] = -jnp.sum(logp)
        elif m in ("mean_squared_error", "mse", "root_mean_squared_error", "rmse"):
            out["mse"] = jnp.sum(jnp.square(preds - labels))
        elif m in ("mean_absolute_error", "mae"):
            out["mae"] = jnp.sum(jnp.abs(preds - labels))
    return out


@dataclass
class MetricsAccumulator:
    """Host-side running aggregate (reference UPDATE_METRICS task,
    model.cc:1182-1205) with the same printed report."""

    metrics: Sequence[str] = ()
    totals: Dict[str, float] = field(default_factory=dict)

    def reset(self):
        self.totals = {}

    def update(self, batch_metrics: Dict[str, jnp.ndarray]):
        # accumulate device-side (no float() here: a host sync per step
        # would serialize dispatch and depress measured throughput)
        for k, v in batch_metrics.items():
            if "/" in k:
                # "<op>/<counter>": an op's own counters (ops/moe.py), not
                # per-sample sums; train_epochs' metrics and the
                # op_counters telemetry events carry them
                continue
            self.totals[k] = self.totals.get(k, 0.0) + v

    def _finalized(self):
        """Host-sync totals; returns (totals, normalizer)."""
        self.totals = {k: float(v) for k, v in self.totals.items()}
        return self.totals, max(self.totals.get("train_all", 0.0), 1.0)

    def report(self) -> str:
        _, n = self._finalized()
        parts = []
        if "train_correct" in self.totals:
            parts.append(
                f"accuracy: {100.0 * self.totals['train_correct'] / n:.2f}% "
                f"({int(self.totals['train_correct'])} / {int(n)})")
        if "cce" in self.totals:
            parts.append(f"cce_loss: {self.totals['cce'] / n:.3f}")
        if "sparse_cce" in self.totals:
            parts.append(f"sparse_cce_loss: {self.totals['sparse_cce'] / n:.3f}")
        if "mse" in self.totals:
            parts.append(f"mse_loss: {self.totals['mse'] / n:.3f}")
            if "root_mean_squared_error" in self.metrics or "rmse" in self.metrics:
                parts.append(f"rmse_loss: {(self.totals['mse'] / n) ** 0.5:.3f}")
        if "mae" in self.totals:
            parts.append(f"mae_loss: {self.totals['mae'] / n:.3f}")
        return "[Metrics] " + " ".join(parts) if parts else "[Metrics] (none)"

    def finalized_means(self) -> Dict[str, float]:
        """Host-synced per-sample means of the accumulated sums, plus the
        raw ``train_all`` count — the ``metrics`` payload of telemetry
        ``step`` events (docs/telemetry.md).  Call only after the step's
        device work is fenced: finalizing syncs the scalar totals."""
        totals, n = self._finalized()
        return {k: (v if k == "train_all" else v / n)
                for k, v in totals.items()}

    def get_accuracy(self) -> float:
        """Training accuracy in percent (reference
        PerfMetrics::get_accuracy used by VerifyMetrics callbacks)."""
        totals, n = self._finalized()
        return 100.0 * totals.get("train_correct", 0.0) / n
