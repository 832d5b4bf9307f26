"""DLRM as published, plain: forward, MSE loss, gradients and one SGD
step in ``jax.numpy`` and float32, and the comparison that decides the
benchmark's ``correct``.  Imports nothing from ``dlrm_flexflow_tpu``.

The model (Naumov et al., "Deep Learning Recommendation Model for
Personalization and Recommendation Systems", arXiv:1906.00091, as the
FlexFlow example ``examples/cpp/DLRM/dlrm.cc`` builds it): a bottom MLP
over the dense features, one sum-pooled embedding bag per sparse
feature, the ``cat`` interaction (concatenate the bottom MLP's output
with every pooled embedding), a top MLP, a sigmoid on its last layer.
Departures from the paper, both the example's own: the loss is the mean
squared error (``dlrm.cc:150``), not binary cross-entropy, and every
other layer, the bottom MLP's last included, ends in a ReLU.

Layout of ``params``: ``{"bot": [(W, b), ...], "top": [(W, b), ...],
"emb": (T, rows, d)}`` with ``W`` of shape (in, out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Arithmetic.  Everything is float32, with one exception that the
# configuration itself states: a configuration whose ``compute_dtype``
# is bfloat16 runs its matmuls on operands rounded to bfloat16 with
# float32 accumulation (f32 master weights, f32 everything else), and
# the reference then rounds the same operands the same way.  Against a
# pure-f32 reference the updates of one step differ by 10-130% (PR 24,
# on the v5e, with 1e-5 of error in the loss): a ReLU input that bf16
# rounds across zero flips that unit's gradient mask, and one flipped
# unit of ~500 active ones moves a per-sample gradient by ~4%.  No
# tolerance that wide could see a lost update.  With ``compute_dtype``
# float32 (the CPU tests) this is the plain f32 reference at "highest"
# matmul precision.
#
# Tolerances.  What is compared is each tensor's *update* over the K
# check steps (after minus before), not its value: an update is ~1e-5
# of a weight.  Honest differences that remain, as measured on the v5e
# over 39 runs and a million rows (PR 24, PERF.md):
# (1) Each step rounds the updated value to float32 once, so system and
#     reference may land one ulp of the *stored value* apart per step
#     (a table row: per naming) whatever the update's size; a row's
#     update is ~100 ulps.  That slack is taken off first.
# (2) Two XLA programs accumulate the same bf16 matmul in another
#     order, ~1e-5 of a pre-activation apart on the MXU; that still
#     flips the ReLU mask of about one sample in 25 per step.  Most
#     rows then agree to the bit or to 1-2% (median error 0 to 2.3%),
#     the flipped samples' rows are off by 5-40% (largest seen 42%),
#     and the MLPs' updates, which sum the batch, by 3-15%.  A tensor
#     of one element (the last layer's bias) sums +/- terms that can
#     all but cancel: its own relative error read 42% and 117% in two
#     runs whose MLP updates as one vector were 8% apart.  So the MLPs
#     are held as one vector, and the worst tensor is only reported.
# So: no row may be off by more than ROW_MAX (a lost or a doubled
# update is 100%); the median row must be within ROW_MEDIAN (a lower
# precision than the configuration states moves every row); the first
# quartile of the rows named more than once, which the check batches
# hold by construction, within DUP_Q1 (duplicates not summed would put
# all of them 33-50% off; the quartile, not the median, because the
# stream cell has only 32 such rows in 4 batches, and flipped samples
# in two of the batches would move a median); and no row outside the
# named ones may move at all (exact).
ROW_MAX = 0.75       # (max|got - want| - slack) over max|want|, any row
ROW_MEDIAN = 0.05    # the same, median over the named rows
DUP_Q1 = 0.15        # the same, first quartile of rows named more than once
MLP_RTOL = 0.30      # |got - want| over |want|, all MLP updates as one vector
LOSS_RTOL = 2e-3     # largest seen 4.3e-4
EPS32 = float(np.finfo(np.float32).eps)


def _mlp(layers, x, last_sigmoid: bool, dt):
    for i, (w, b) in enumerate(layers):
        x = jnp.matmul(x.astype(dt), w.astype(dt),
                       preferred_element_type=jnp.float32) + b
        last = i == len(layers) - 1
        x = jax.nn.sigmoid(x) if (last and last_sigmoid) else jax.nn.relu(x)
    return x


def _gather(emb, ids):
    """Rows ``(B, T, bag, d)`` of ``emb (T, rows, d)`` for ``ids
    (B, T, bag)``."""
    return emb[jnp.arange(emb.shape[0])[None, :, None], ids]


def loss_from_rows(mlps, rows, dense, labels, dt=jnp.float32):
    bottom = _mlp(mlps["bot"], dense, False, dt)
    pooled = rows.sum(axis=2)                               # (B, T, d)
    z = jnp.concatenate([bottom, pooled.reshape(pooled.shape[0], -1)], 1)
    preds = _mlp(mlps["top"], z, True, dt)
    return jnp.mean(jnp.sum(jnp.square(preds - labels), axis=1))


def sgd_step(params, dense, ids, labels, lr, compute_dtype="float32"):
    """One plain SGD step; returns ``(params, loss)``.  ``compute_dtype``
    is the configuration's (see "Arithmetic" above).  The embedding
    gradient is taken with respect to the gathered rows and applied with
    ``.at[ids].add``: a row hit k times gets the sum of k gradients."""
    with jax.default_matmul_precision("highest"):
        mlps = {"bot": params["bot"], "top": params["top"]}
        rows = _gather(params["emb"], ids)
        loss, (g_mlps, g_rows) = jax.value_and_grad(
            loss_from_rows, argnums=(0, 1))(mlps, rows, dense, labels,
                                            jnp.dtype(compute_dtype))
    new = jax.tree_util.tree_map(lambda p, g: p - lr * g, mlps, g_mlps)
    tables = jnp.arange(params["emb"].shape[0])[None, :, None]
    new["emb"] = params["emb"].at[tables, ids].add(-lr * g_rows)
    return new, loss


def restrict(ids):
    """The rows the host array ``ids (..., T, bag)`` names, and the same
    ids renumbered onto them.  Rows no batch names take no part in the
    arithmetic, so the reference may hold each table restricted to its
    named rows (2 GB of tables as (T, rows, 64) float32 would take 4 GB
    of a TPU's tiled memory, twice over for a step).  Returns ``(tix,
    rix, pos, ids_ref)``: the distinct ``(table, row)`` pairs in
    (table, row) order, each pair's position within its table, and
    ``ids`` as positions, for tables of shape ``(T, max named, d)``."""
    ids = np.asarray(ids)
    t = ids.shape[-2]
    flat = np.moveaxis(ids, -2, 0).reshape(t, -1)
    pairs, touches = np.unique(np.stack(
        [np.repeat(np.arange(t), flat.shape[1]), flat.reshape(-1)], 1),
        axis=0, return_counts=True)
    tix, rix = pairs[:, 0], pairs[:, 1]
    starts = np.searchsorted(tix, np.arange(t))
    pos = np.arange(tix.size) - starts[tix]
    ids_ref = np.empty(ids.shape, np.int32)
    for table in range(t):
        ids_ref[..., table, :] = np.searchsorted(rix[tix == table],
                                                 ids[..., table, :])
    return tix, rix, pos, ids_ref, touches


def _update_error(before, got, want, ulps, axis=None):
    """(max|got - want| - slack) over max|want| of the update, per
    tensor or, with ``axis``, per row; slack = ``ulps`` float32 ulps of
    the largest stored value."""
    d_got, d_want = got - before, want - before
    scale = jnp.max(jnp.abs(d_want), axis=axis)
    slack = ulps * EPS32 * jnp.max(jnp.abs(before), axis=axis)
    err = jnp.maximum(jnp.max(jnp.abs(d_got - d_want), axis=axis) - slack, 0)
    return jnp.where(err == 0, 0.0, err / scale)  # inf where want stood still


def compare(before, got, want, losses_got, losses_want, steps, touches):
    """Hold the system's result (``got``) to the reference's (``want``),
    both started from ``before``, over ``steps`` steps.  ``before`` /
    ``got`` / ``want``: ``{"bot", "top"}`` as in ``params`` plus
    ``"rows"``, the named table rows ``(U, d)`` in ``restrict``'s order,
    each named ``touches`` times; ``got`` also carries
    ``"moved_untouched"``, the number of table rows outside the named
    ones that differ from the copy taken before (exactly the named rows
    may move).  Returns ``(ok, report)``."""
    names, errs, sq_err, sq_want = [], [], 0.0, 0.0
    for name in ("bot", "top"):
        for i, (lb, lg, lw) in enumerate(zip(before[name], got[name],
                                             want[name])):
            for part, b, g, w in zip("Wb", lb, lg, lw):
                names.append(f"{name}{i}.{part}")
                errs.append(float(_update_error(b, g, w, steps)))
                sq_err += float(jnp.sum(jnp.square(g - w)))
                sq_want += float(jnp.sum(jnp.square(w - b)))
    # all MLP updates as one vector: a small tensor whose own update all but
    # cancels (a bias, by chance) must not decide; it is reported by name
    mlp_err = (sq_err / sq_want) ** 0.5
    worst = int(np.argmax(errs))
    touches = np.asarray(touches)
    row_err = np.asarray(_update_error(
        before["rows"], got["rows"], want["rows"],
        jnp.asarray(touches, jnp.float32), axis=1))
    losses_got, losses_want = np.asarray(losses_got), np.asarray(losses_want)
    report = {"mlp_update_err": mlp_err,
              "mlp_worst_tensor": [names[worst], errs[worst]],
              "row_err_max": float(row_err.max()),
              "row_err_median": float(np.median(row_err)),
              "dup_row_err_q1": float(np.quantile(row_err[touches > 1], 0.25)),
              "rows_over_max": int(np.sum(row_err > ROW_MAX)),
              "rows_compared": int(row_err.size),
              "rows_named_twice": int(np.sum(touches > 1)),
              "moved_untouched": int(got["moved_untouched"]),
              "loss_err": float(np.max(np.abs(losses_got - losses_want)
                                       / np.abs(losses_want)))}
    ok = (mlp_err <= MLP_RTOL and report["rows_over_max"] == 0
          and report["row_err_median"] <= ROW_MEDIAN
          and report["dup_row_err_q1"] <= DUP_Q1
          and report["moved_untouched"] == 0
          and report["loss_err"] <= LOSS_RTOL)
    return ok, report
