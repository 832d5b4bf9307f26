"""Driver ``staged``: the dataset is placed on the device once and the
window chains ``FFModel.train_epochs`` dispatches — what ``fit`` runs
on its staged path (``fit`` itself would stage and lower again on every
call).

The window is a fixed amount of work, timed; not a fixed time, counted
(PR 28).  The traffic file's ``dispatches`` says how many dispatches
it chains (the two cells of ``BENCHMARK.json``: 24 dispatches of 8
epochs, 98,304 steps), and ``--seconds`` is a ceiling only.  So the
state the comparison starts from, and with it ``correct``, is a function
of the seed and the program's arithmetic and never of its speed: the
comparison's error grows with the distance trained (PERF.md section 4),
and a faster program must not train further.

Traffic keys: ``batch``, ``batches`` (per epoch), ``ids``,
``epochs_per_dispatch`` (fixed, so that a dispatch lasts 0.2-1 s),
``dispatches`` (the window's length; required, no default),
``traced_units`` (dispatches in the ``--trace 1`` window) and
``rate_metric`` (the end-to-end name its rate is reported under).
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

#: batches of the comparison with the reference: one scanned
#: ``train_epoch`` of the length PR 21 traced on the chip, whose
#: epilogue takes the row-set kernel
CHECK_BATCHES = 16
#: steps the program runs on its own account before the window
#: (``run.py`` holds ``state.step`` to the steps counted plus these)
UNCOUNTED_STEPS = 0


def _stack(arr, batches: int, batch: int):
    return arr.reshape((batches, batch) + arr.shape[1:])


def _window_dispatches(traffic: dict) -> int:
    count = traffic.get("dispatches")
    if not isinstance(count, int) or isinstance(count, bool) or count < 1:
        raise KeyError(
            "a staged traffic file needs \"dispatches\": the number of "
            "train_epochs dispatches the window chains, a whole number "
            f"from 1 (got {count!r}); the window is fixed work, and "
            "--seconds only a ceiling")
    return count


def prepare(model, state, dataset, traffic: dict, seed: int) -> dict:
    """Place the dataset and run two dispatches of the window's own
    shape to the end (real updates).  Two, because under a mesh the
    second call sees the first one's output shardings in place of
    ``init``'s and may compile once more: the window then starts with
    every program it uses in the jit cache."""
    _window_dispatches(traffic)  # a file without it fails before any compile
    inputs, labels = dataset
    nb, b = traffic["batches"], traffic["batch"]
    staged = model.place_dataset(
        {k: _stack(v, nb, b) for k, v in inputs.items()},
        _stack(labels, nb, b))
    ctx = {"model": model, "staged": staged, "traffic": traffic}
    for _ in range(2):
        state, _ = model.train_epochs(state, *staged,
                                      traffic["epochs_per_dispatch"])
    jax.block_until_ready(state.step)
    ctx["state"] = state
    return ctx


def run_window(ctx: dict, seconds: float, limit=None) -> dict:
    """Chain exactly ``traffic["dispatches"]`` dispatches (``limit``
    of them in the traced window), one in flight behind the one that
    runs, so that the device never waits for the host and the host never
    runs away; close with a fence on ``state.step`` (PR 21:
    ``block_until_ready`` waits for the device; fence one small leaf,
    not the whole state).  ``seconds`` is a ceiling: once it has passed
    nothing more is dispatched, and fewer walls than
    ``dispatches_wanted`` say that the window stopped short."""
    model, staged, traffic = ctx["model"], ctx["staged"], ctx["traffic"]
    epochs = traffic["epochs_per_dispatch"]
    wanted = _window_dispatches(traffic) if limit is None else limit
    state = ctx.pop("state")  # donated by the first dispatch
    pending, losses, done = collections.deque(), [], []
    dispatched = 0
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while dispatched < wanted:
            with TraceAnnotation("bench.dispatch"):
                state, mets = model.train_epochs(state, *staged, epochs)
            dispatched += 1
            pending.append(mets["loss"])
            if len(pending) > 1:
                with TraceAnnotation("bench.wait"):
                    losses.append(np.asarray(pending.popleft()))
                done.append(time.perf_counter())
                if done[-1] - t0 >= seconds:
                    break
        with TraceAnnotation("bench.fence"):
            jax.block_until_ready(state.step)
        t1 = time.perf_counter()
    done.append(t1)
    losses += [np.asarray(x) for x in pending]
    ctx["state"] = state
    steps_each = epochs * traffic["batches"]
    bad = sum(int(np.sum(~np.isfinite(x))) for x in losses)
    return {"t0": t0, "wall_s": t1 - t0,
            "dispatches_wanted": wanted, "steps": dispatched * steps_each,
            "samples": dispatched * steps_each * traffic["batch"],
            "failed_steps": bad * traffic["batches"],
            "dispatch_walls_s": list(np.diff([t0] + done))}


def check_steps(model, state, inputs, labels):
    """The measured path on the check's batches: one scanned
    ``train_epoch`` over all of them.  Returns ``(state, [loss])``."""
    state, mets = model.train_epoch(state, *model.place_dataset(inputs,
                                                                labels))
    return state, [mets["loss"]]
