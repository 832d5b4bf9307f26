"""The DLRM family: everything that knows both the program's DLRM and
the reference's.  ``run.py`` finds this file by the ``family`` key of a
configuration file and calls ``build``, ``make_dataset`` and ``check``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import traffic as traffic_lib
from benchmarks.reference import dlrm_ref


#: each number of ``check``'s report that decides ``correct``, beside the
#: limit it is held to (``reference/dlrm_ref.py`` says why each); ``run.py``
#: prints the pairs last, on stderr and in the result's line
LIMITS = {"mlp_update_err": dlrm_ref.MLP_RTOL,
          "row_err_max": dlrm_ref.ROW_MAX,
          "row_err_median": dlrm_ref.ROW_MEDIAN,
          "dup_row_err_q1": dlrm_ref.DUP_Q1,
          "moved_untouched": 0,
          "loss_err": dlrm_ref.LOSS_RTOL}


def build(config: dict, batch: int, seed: int, devices):
    """The program's model and initial state for a configuration file:
    ``build_dlrm`` -> ``compile`` -> ``init(seed)``, the calls
    ``apps/dlrm.py:setup`` makes.  Returns ``(model, state)``."""
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.config import FFConfig
    from dlrm_flexflow_tpu.optim import SGDOptimizer
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh

    fc = FFConfig(batch_size=batch)
    for key, value in config["ffconfig"].items():
        if not hasattr(fc, key):
            raise AttributeError(f"FFConfig has no field {key!r}")
        setattr(fc, key, value)
    layout = config["layout"]
    mesh = (make_mesh(layout["mesh"], devices=devices) if layout["mesh"]
            else False)
    model = build_dlrm(DLRMConfig(**config["model"]), fc,
                       table_parallel=layout["table_parallel"])
    model.compile(optimizer=SGDOptimizer(fc.learning_rate, 0.0, False,
                                         fc.weight_decay),
                  loss_type=config["loss"],
                  metrics=("accuracy", "mean_squared_error"), mesh=mesh)
    return model, model.init(seed=seed)


def make_dataset(config: dict, traffic: dict, seed: int):
    """``(inputs, labels)`` of ``batches * batch`` samples, unbatched."""
    return traffic_lib.make_samples(
        config["model"], traffic["ids"],
        traffic["batches"] * traffic["batch"], seed)


def _mlps(params, shape):
    """The program's MLP parameters in the reference's layout."""

    def mlp(prefix, n):
        return [(params[f"{prefix}_{i}"]["kernel"],
                 params[f"{prefix}_{i}"]["bias"]) for i in range(n)]

    return {"bot": mlp("bot", len(shape["mlp_bot"]) - 1),
            "top": mlp("top", len(shape["mlp_top"]) - 1)}


def _rows(table, flat_rows, d: int):
    """Rows ``flat_rows`` of the logical ``(T * rows, d)`` table.  The
    program stores it packed; the logical form is a row-major reshape
    (the contract ``FFModel.get_weights`` documents).  Read through
    128-wide lines: a (.., 64) view of 2 GB would be padded to 4 GB in
    a TPU's tiled memory."""
    w = max(d, 128)
    per_line = w // d
    lines = table.reshape(-1, w)[flat_rows // per_line]
    return lines.reshape(-1, per_line, d)[jnp.arange(flat_rows.size),
                                          flat_rows % per_line]


def _stray_rows(after, before, named, d: int):
    """How many logical rows outside ``named`` (a bool per row of the
    logical ``(T * rows, d)`` table) differ between two tables in the
    program's own storage layout.  Line by line, as in ``_rows``."""
    w = max(d, 128)
    per_line = w // d
    differ = after.reshape(-1, w) != before.reshape(-1, w)
    stray = 0
    for slot in range(per_line):
        moved = jnp.any(differ[:, slot * d:(slot + 1) * d], axis=1)
        stray += jnp.sum(moved & ~named[slot::per_line])
    return stray


def _restricted(rows, tix, pos, tables: int):
    """The reference's tables ``(T, most rows named in one, d)`` holding
    the named ``rows (U, d)``, pair ``i`` at ``[tix[i], pos[i]]``
    (``dlrm_ref.restrict``)."""
    return jnp.zeros((tables, int(pos.max()) + 1, rows.shape[1]),
                     jnp.float32).at[tix, pos].set(rows)


def _reference_steps(ref, batches, lr: float, dtype: str):
    """``dlrm_ref.sgd_step`` over ``batches = (dense, ids, labels)``,
    each stacked by step, with matmul operands in ``dtype``.  Returns
    ``(ref, [loss])``."""
    step = jax.jit(dlrm_ref.sgd_step, static_argnums=5)
    losses = []
    for dense, ids, labels in zip(*batches):
        ref, loss = step(ref, dense, ids, labels, lr, dtype)
        losses.append(float(loss))
    return ref, losses


def check(config: dict, traffic: dict, model, state, seed: int, run_steps,
          k: int):
    """Send ``k`` further seeded batches through the reference and
    through ``run_steps(model, state, inputs, labels) -> (state,
    losses)``, the path the cell measures, both from the state the
    window produced, and compare.  The reference runs on the first
    device, on the MLPs and on each table restricted to the rows the
    batches name (``dlrm_ref.restrict``); that no other row moved is
    checked on the whole table, where it lies (sharded or not).
    Returns ``(ok, report, state)``."""
    shape = config["model"]
    sizes, d = shape["embedding_size"], shape["sparse_feature_size"]
    if len(set(sizes)) != 1:
        raise ValueError("this adapter compares uniform tables only")
    dev = jax.devices()[0]
    inputs, labels = traffic_lib.make_check_batches(
        shape, traffic["ids"], traffic["batch"], k, seed)
    tix, rix, pos, ids_ref, touches = dlrm_ref.restrict(
        inputs["sparse"])
    flat_rows = (tix * sizes[0] + rix).astype(np.int32)
    rows_of = jax.jit(_rows, static_argnums=2)

    def small(params):
        """MLPs and named rows, as fresh arrays on the first device."""
        out = jax.device_put(
            dict(_mlps(params, shape),
                 rows=rows_of(params["emb"]["embedding"], flat_rows, d)), dev)
        return jax.tree_util.tree_map(jnp.copy, out)

    before = small(state.params)
    table_before = jnp.copy(state.params["emb"]["embedding"])

    state, losses_got = run_steps(model, state, inputs, labels)

    got = small(state.params)
    named = np.zeros(len(sizes) * sizes[0], bool)
    named[flat_rows] = True
    got["moved_untouched"] = jax.jit(_stray_rows, static_argnums=3)(
        state.params["emb"]["embedding"], table_before, named, d)
    del table_before

    ref = {"bot": before["bot"], "top": before["top"],
           "emb": _restricted(before["rows"], tix, pos, len(sizes))}
    ref, losses_want = _reference_steps(
        ref, (inputs["dense"], ids_ref, labels),
        float(config["ffconfig"]["learning_rate"]),
        config["ffconfig"]["compute_dtype"])
    want = {"bot": ref["bot"], "top": ref["top"], "rows": ref["emb"][tix, pos]}
    # the path folds its steps' losses as it likes (a scanned epoch
    # returns one mean): fold the reference's the same way
    losses_got = [float(x) for x in losses_got]
    losses_want = [float(np.mean(group)) for group in
                   np.array_split(losses_want, len(losses_got))]
    ok, report = dlrm_ref.compare(before, got, want, losses_got, losses_want,
                                  k, touches)
    return ok, report, state


#: the nearest precision below the one a configuration states: what a
#: later PR would be tempted to compute in
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def control_steps(config: dict):
    """The control of the comparison: the reference put in the program's
    place, with its matmul operands rounded to ``LOWER`` of the
    configuration's ``compute_dtype``.  Returns a ``run_steps`` for
    ``check``, which has to come out as not correct (``run.py --control
    1``; the benchmark's own runs never take it).  The steps' results go
    back into the program's state through ``get_weights`` /
    ``set_weights``, the table by way of the host."""
    shape = config["model"]
    lr = float(config["ffconfig"]["learning_rate"])
    dtype = LOWER[config["ffconfig"]["compute_dtype"]]

    def run_steps(model, state, inputs, labels):
        tix, rix, pos, ids_ref, _ = dlrm_ref.restrict(inputs["sparse"])
        table = np.array(model.get_weights(state, "emb", "embedding"))
        ref = dict(_mlps(state.params, shape),
                   emb=_restricted(table[tix, rix], tix, pos, table.shape[0]))
        ref, losses = _reference_steps(
            ref, (inputs["dense"], ids_ref, labels), lr, dtype)
        table[tix, rix] = np.asarray(ref["emb"][tix, pos])
        # in the storage layout already: a (.., 64) view of 2 GB on the
        # device would be padded to 4 GB (see ``_rows``)
        state = model.set_weights(
            state, "emb", "embedding",
            table.reshape(state.params["emb"]["embedding"].shape))
        for name in ("bot", "top"):
            for i, (w, b) in enumerate(ref[name]):
                state = model.set_weights(state, f"{name}_{i}", "kernel", w)
                state = model.set_weights(state, f"{name}_{i}", "bias", b)
        return state, losses

    return run_steps
