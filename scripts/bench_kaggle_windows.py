"""Criteo-Kaggle throughput vs measurement-window length.

The BENCH_APP=dlrm_kaggle cell uses a short window (batch 64, nb 16,
2 epochs): fixed costs (dispatch, the row-cache build) are a large
share of it.  This script measures the SAME per-step computation
(bench.py's own Kaggle config, via bench._windows) over increasing fused
window lengths so the asymptotic rate is visible: ``train_epochs`` fuses
the whole run into ONE dispatch with ONE row-cache build.

    python scripts/bench_kaggle_windows.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(batch=64, nb=16, reps=3):
    # the anchored bench's exact Kaggle model + inputs (shared helpers —
    # this script can never drift from what bench.py measures)
    from bench import _windows, kaggle_inputs, kaggle_model

    cfg, m = kaggle_model(batch)
    inputs, labels = kaggle_inputs(cfg, batch, nb)

    out = []
    for epochs in (2, 4, 8):
        # fresh state per config: the fused train_epochs donates it
        state = m.init(seed=0)
        thpt, prov = _windows(m, state, inputs, labels, batch, nb, epochs,
                              reps)
        out.append({"epochs": epochs, "samples_per_sec": round(thpt),
                    **prov})
    print(json.dumps({"windows": out}))


if __name__ == "__main__":
    main()
