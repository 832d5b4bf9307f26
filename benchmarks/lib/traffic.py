"""The one traffic generator: a mix is a data file of parameters under
``benchmarks/traffic/``; this turns it and ``--seed`` into arrays.

The benchmark's own copy of the program's synthetic DLRM data
(``data/loader.py::SyntheticDLRMLoader`` and ``zipf_ids``), so that a PR
which edits the loader cannot move the inputs.  The program receives
only the arrays (or a loader object built from them).
"""

from __future__ import annotations

import numpy as np

#: sub-streams of one ``--seed``: the measured dataset and the check's
#: batches never share draws
DATASET_STREAM, CHECK_STREAM = 0, 1


def _zipf_ids(rng, num_rows: int, n: int, a: float) -> np.ndarray:
    """``n`` ids from Zipf(a) truncated to ``[0, num_rows)`` by rejection,
    the hot head then spread over the row space by a fixed odd multiplier
    (as after a frequency-agnostic hash)."""
    if a <= 1.0:
        raise ValueError(f"zipf exponent must be > 1, got {a}")
    out = np.empty(n, dtype=np.int64)
    have = 0
    while have < n:
        draw = rng.zipf(a, size=max(n - have, 1024))
        draw = draw[draw <= num_rows]
        take = min(draw.size, n - have)
        out[have:have + take] = draw[:take] - 1
        have += take
    mult = 0x9E3779B1 % num_rows
    while np.gcd(mult, num_rows) != 1:
        mult = (mult + 1) % num_rows
    return (out * mult + 12345) % num_rows


def _ids(rng, ids_spec: dict, num_rows: int, n: int) -> np.ndarray:
    dist = ids_spec["dist"]
    if dist == "uniform":
        return rng.integers(0, num_rows, size=n, dtype=np.int64)
    if dist == "zipf":
        return _zipf_ids(rng, num_rows, n, float(ids_spec["a"]))
    raise ValueError(f"unknown id distribution {dist!r}")


def make_samples(model_shape: dict, ids_spec: dict, n: int, seed: int,
                 stream: int = DATASET_STREAM):
    """``n`` samples for the DLRM shape in a configuration file:
    ``({"dense": (n, num_dense) f32, "sparse": (n, T, bag) i64},
    labels (n, 1) f32)`` — standard-normal dense features, ids per table
    by ``ids_spec``, labels 0/1 with equal odds."""
    rng = np.random.default_rng([int(seed), stream])
    tables = model_shape["embedding_size"]
    bag = int(model_shape["embedding_bag_size"])
    dense = rng.standard_normal((n, int(model_shape["mlp_bot"][0])),
                                dtype=np.float32)
    sparse = np.stack([_ids(rng, ids_spec, int(rows), n * bag).reshape(n, bag)
                       for rows in tables], axis=1)
    labels = rng.integers(0, 2, size=(n, 1)).astype(np.float32)
    return {"dense": dense, "sparse": sparse}, labels


def make_check_batches(model_shape: dict, ids_spec: dict, batch: int,
                       k: int, seed: int):
    """``k`` further batches for the comparison with the reference,
    stacked ``(k, batch, ...)``.  By construction every batch repeats
    ids: sample 1 carries sample 0's ids in every table and sample 2
    carries them in table 0, so some rows are hit twice and one three
    times — a lost or doubled update of a duplicate shows."""
    inputs, labels = make_samples(model_shape, ids_spec, k * batch, seed,
                                  stream=CHECK_STREAM)
    inputs = {name: v.reshape((k, batch) + v.shape[1:])
              for name, v in inputs.items()}
    inputs["sparse"][:, 1] = inputs["sparse"][:, 0]
    inputs["sparse"][:, 2, 0] = inputs["sparse"][:, 0, 0]
    return inputs, labels.reshape(k, batch, 1)
