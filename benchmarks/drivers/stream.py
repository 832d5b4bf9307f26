"""Driver ``stream``: the samples sit in host memory in the program's
``ArrayDataLoader`` and ONE ``FFModel.fit`` call runs its per-batch loop
(a callback forces that path) until the benchmark's own callback stops
it at an epoch's end.

Traffic keys: ``batch``, ``batches`` (per epoch), ``ids``, ``loader``
(``shuffle``, ``prefetch_depth``), ``traced_units`` (epochs in the
``--trace 1`` window) and ``rate_metric``.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

#: batches of the comparison with the reference, one ``train_step`` each
CHECK_BATCHES = 4
#: ``fit`` trains once on the first batch before its loop (its warm-up)
UNCOUNTED_STEPS = 1


class _Clock:
    """Keras-style callback: counts steps, opens the window at the first
    ``on_batch_begin`` (``fit`` calls ``on_train_begin`` and
    ``on_epoch_begin(0)`` before its own warm-up step and fence, so
    those are too early) and stops ``fit`` at the first epoch end past
    ``seconds`` (or after ``limit`` epochs).  Host spans: ``bench.step``
    from batch begin to batch end (shard, H2D, dispatch), ``bench.load``
    from batch end to the next begin (the loader's slice)."""

    model = None

    def __init__(self, seconds: float, limit=None):
        self.seconds, self.limit = seconds, limit
        self.t0, self.steps, self.epoch_ends = None, 0, []
        self._span = None

    def set_model(self, model):
        self.model = model

    def _switch(self, name):
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span = TraceAnnotation(name) if name else None
        if self._span is not None:
            self._span.__enter__()

    def on_train_begin(self):
        pass

    def on_epoch_begin(self, epoch):
        pass

    def on_batch_begin(self, it):
        if self.t0 is None:
            self.t0 = time.perf_counter()
        self._switch("bench.step")

    def on_batch_end(self, it):
        self.steps += 1
        self._switch("bench.load")

    def on_epoch_end(self, epoch):
        self._switch(None)
        self.epoch_ends.append(time.perf_counter())
        return (self.epoch_ends[-1] - self.t0 >= self.seconds
                or (self.limit is not None
                    and len(self.epoch_ends) >= self.limit))

    def on_train_end(self):
        pass


def prepare(model, state, dataset, traffic: dict, seed: int) -> dict:
    """Build the loader and run a four-batch ``fit`` through the same
    per-batch loop, so that every program the window uses is compiled."""
    from dlrm_flexflow_tpu.data.loader import ArrayDataLoader

    inputs, labels = dataset
    opts = traffic["loader"]
    b = traffic["batch"]
    model.config.prefetch_depth = int(opts["prefetch_depth"])
    warm = ArrayDataLoader({k: v[:4 * b] for k, v in inputs.items()},
                           labels[:4 * b], b, shuffle=opts["shuffle"],
                           seed=seed)
    state, _ = model.fit(state, warm, epochs=1, verbose=False,
                         callbacks=[_Clock(0.0)])
    loader = ArrayDataLoader(inputs, labels, b, shuffle=opts["shuffle"],
                             seed=seed)
    return {"model": model, "state": state, "loader": loader,
            "traffic": traffic}


def run_window(ctx: dict, seconds: float, limit=None) -> dict:
    """One ``fit`` over whole epochs; the window runs from the first
    ``on_batch_begin`` to a fence on ``state.step`` after ``fit`` has
    returned."""
    model, traffic = ctx["model"], ctx["traffic"]
    clock = _Clock(seconds, limit)
    state, _ = model.fit(ctx.pop("state"), ctx["loader"], epochs=10 ** 9,
                         verbose=False, callbacks=[clock])
    jax.block_until_ready(state.step)
    t1 = time.perf_counter()
    ctx["state"] = state
    # the per-batch loop returns no losses; it keeps the last epoch's
    # per-sample metric means (mse among them)
    means = model.get_perf_metrics().finalized_means()
    finite = bool(np.all(np.isfinite(list(means.values()))))
    return {"t0": clock.t0, "wall_s": t1 - clock.t0, "steps": clock.steps,
            "samples": clock.steps * traffic["batch"],
            "failed_steps": 0 if finite else traffic["batches"],
            "dispatch_walls_s": list(np.diff([clock.t0] + clock.epoch_ends))}


def check_steps(model, state, inputs, labels):
    """The measured path on the check's batches: one ``train_step``
    each.  Returns ``(state, losses)``."""
    losses = []
    for i in range(labels.shape[0]):
        state, mets = model.train_step(
            state, {k: v[i] for k, v in inputs.items()}, labels[i])
        losses.append(mets["loss"])
    return state, losses
