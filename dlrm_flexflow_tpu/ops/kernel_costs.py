"""Unified kernel-dispatch cost model (PERF.md "Where the cycles go").

Every hand-written pallas kernel in this tree competes with an XLA
emitter path that computes the identical values, and each one needs a
STATIC dispatch gate deciding which implementation a given shape should
run.  Before this module the gate logic lived next to each kernel
(``row_set_wins`` in pallas_scatter.py); with the fused
embedding-bag→interaction kernel (pallas_fused_interact.py) joining the
row-set and row-update kernels, the measured machine constants would
have been copied a third time — so they live here once, and every gate
reads them.

The constants were MEASURED on a TPU v5e, not taken from a datasheet —
but on a previous installation (round 5: another compiler build, a
shared chip), and they have NOT been re-measured on the current one
(ROADMAP S5).  Each records where it was measured so a re-measurement
updates one line:

* ``SET_KERNEL_NS_PER_ROW`` — per-row async-copy cost of the row-set
  kernel's DMA epilogue (round 5, scripts/ab_prologue_layout.py): the
  hybrid epilogue's 8.2k-row writeback measured ~64 ns/row, latency-
  not bandwidth-bound.
* ``EMITTER_SWEEP_GBPS`` — the XLA scatter emitter's full-parent RMW
  sweep rate (round 5: a 2 GB parent swept in ~6.1 ms ≈ 650 GB/s of
  read+write traffic).
* ``GATHER_NS_PER_ROW`` — XLA's fused dynamic-gather pipeline
  (pallas_embedding.py bring-up: 2048 rows in ~19 us ≈ 9 ns/row; the
  gather pipeline batches row fetches where per-row DMAs serialize on
  latency).
* ``HBM_GBPS`` — streamed-intermediate bandwidth for materialized
  tensors bounced through HBM between ops (v5e HBM, de-rated to the
  sweep rate above — both directions of the bounce pay it).
* ``OP_BOUNDARY_NS`` — per-XLA-op fixed cost at the fusion boundaries
  the unfused path cannot cross (gather → pool → reshape/concat →
  matmul each start a new fusion root; the kernel-launch overhead
  measured then was ~2 us per root, sim/cost_model.py
  ``kernel_launch_overhead``).

Both gates apply ``DISPATCH_MARGIN`` the same way ``row_set_wins``
always did: the kernel must win by 2x before the gate leaves the
emitter, so a call near the crossover keeps the battle-tested default.
"""

from __future__ import annotations

#: per-row DMA cost of a hand-written pallas row kernel (ns) — measured
#: round 5 on the row-set epilogue; the fused kernel's per-row fetches
#: are the same make_async_copy machinery.
SET_KERNEL_NS_PER_ROW = 64.0

#: XLA scatter emitter's full-parent RMW sweep rate (GB/s, round 5).
EMITTER_SWEEP_GBPS = 650.0

#: XLA fused dynamic-gather pipeline per-row cost (ns) — measured in
#: the pallas_embedding.py bring-up (19 us / 2048 rows).
GATHER_NS_PER_ROW = 9.0

#: bandwidth charged to intermediates materialized between XLA ops
#: (GB/s; write + read both pay it).
HBM_GBPS = 650.0

#: fixed cost per XLA fusion root the unfused gather→pool→interact
#: chain pays and the fused kernel does not (ns).
OP_BOUNDARY_NS = 2000.0

#: a kernel must beat the emitter by this factor before dispatch flips.
DISPATCH_MARGIN = 2.0

#: ICI link bandwidth per direction (GB/s) — the v5e constant the
#: machine model prices collectives with (sim/cost_model.py
#: TPUMachineModel.ici_bandwidth = 45e9); kernel_costs sits BELOW sim
#: in the layering DAG, so the number is mirrored here with its source.
ICI_GBPS = 45.0

#: effective MXU throughput for the dense-stack estimate (FLOP/ns):
#: f32 peak 49 TFLOP/s at the machine model's 60% utilisation.
MXU_F32_FLOPS_PER_NS = 49e3 * 0.6

#: host<->device link bandwidth (GB/s) a tiered-storage miss stream
#: pays — PCIe-class, ~40x below HBM; the asymmetry is exactly why a
#: hot cache must absorb most lookups before tiering can win.
HOST_LINK_GBPS = 16.0

#: fixed latency to start a host->device copy burst (ns): one
#: start-all-then-wait miss block pays it once regardless of row count
#: (the same amortization the per-row DMA kernels rely on).
HOST_LINK_LATENCY_NS = 2500.0


def row_set_wins(parent_rows: int, dim: int, n: int,
                 itemsize: int) -> bool:
    """Static dispatch gate for the row-SET kernel vs the scatter
    emitter (pallas_scatter._row_set_pallas), from the measured cost
    model (round 5): the emitter's scatter-set costs ~max(parent RMW
    sweep at ~650 GB/s, ~15 ns/row issue) while the kernel pays
    ~64 ns/row.  The kernel therefore wins only in the sweep-bound
    low-density regime; the 2x margin keeps the emitter wherever the
    call is close.  Checked against three measured points: dlrm_hybrid
    epilogue (8.2k rows / 2 GB parent: kernel, measured emitter 6.1 ms
    vs model 6.3), kaggle (26.6k / 411 MB: emitter) and the headline
    (1M / 2 GB: emitter).

    ``n`` from the epilogue caller is the PADDED row count (sentinel
    holes included — the live distinct count is data-dependent), so the
    kernel's cost is an upper bound: near the threshold the slack tips
    the dispatch toward the emitter, never the kernel (advisor r5; the
    measured slack is re-documented in PERF.md "Dispatch gates")."""
    kernel_ns = n * SET_KERNEL_NS_PER_ROW * DISPATCH_MARGIN
    sweep_ns = parent_rows * dim * itemsize * 2.0 / EMITTER_SWEEP_GBPS
    return kernel_ns < sweep_ns


def fused_interact_wins(batch: int, num_tables: int, bag: int, dim: int,
                        itemsize: int, interact: str = "cat") -> bool:
    """Static dispatch gate for the fused embedding-bag→interaction
    kernel (pallas_fused_interact.py) vs the emitter chain (gather →
    pool → reshape/concat [→ batched matmul → flat → concat]).

    Kernel cost: one per-row DMA per looked-up row (the row-set
    kernel's measured ~64 ns/row — latency-bound, so it scales with
    ``batch * num_tables * bag`` regardless of dim).

    Emitter cost: the gather pipeline (~9 ns/row), PLUS the pooled
    ``(batch, num_tables, dim)`` intermediate bounced through HBM
    (write + read — the materialization the fused kernel exists to
    delete; for ``dot`` the ``(batch, F, F)`` pairwise product and its
    flat view bounce too), PLUS one fixed fusion-root cost per op
    boundary XLA cannot fuse across (3 roots for cat: gather+pool,
    reshape, concat; 5 for dot: + batched matmul, flat).

    Regimes this selects (by construction, pinned in
    tests/test_kernels.py): the smallest serving buckets (batch 1-4
    for cat, through 8 for dot, at the run_random.sh table set) are
    boundary-cost dominated — the kernel wins; the training headline
    (batch 256, 8 tables, bag 1) is gather-pipeline dominated and the
    per-row DMAs lose — the emitter keeps it, exactly as the
    pallas_embedding bring-up measured for the bag alone (70 us kernel
    vs 19 us XLA).  The 2x ``DISPATCH_MARGIN`` keeps crossover shapes
    on the emitter."""
    rows = batch * num_tables * bag
    kernel_ns = rows * SET_KERNEL_NS_PER_ROW * DISPATCH_MARGIN
    inter_bytes = 2.0 * batch * num_tables * dim * itemsize
    boundaries = 3
    if interact == "dot":
        f = num_tables + 1
        inter_bytes += 2.0 * batch * f * f * itemsize
        boundaries = 5
    emitter_ns = (rows * GATHER_NS_PER_ROW
                  + inter_bytes / HBM_GBPS
                  + boundaries * OP_BOUNDARY_NS)
    return kernel_ns < emitter_ns


def exchange_overlap_wins(local_batch: int, num_tables: int, dim: int,
                          itemsize: int, model_parallel: int,
                          dense_flops: int, microbatches: int,
                          mode: str = "allgather") -> bool:
    """Static dispatch gate for the microbatched exchange/compute
    pipeline (parallel/overlap.py) vs the serial manual exchange.

    The pipeline hides ``min(exchange, dense)`` of the step behind the
    other rail (per microbatch the step pays ``max`` instead of the
    sum), but splitting into K microbatches costs K-1 extra collective
    launches and K-1 extra dense fusion roots — each ~``OP_BOUNDARY_NS``
    like every other fusion boundary this module prices.  Overlap wins
    when the hidden time beats that added boundary cost by the shared
    2x ``DISPATCH_MARGIN``, so a call near the crossover keeps the
    battle-tested serial exchange.

    ``local_batch`` is the per-data-shard batch (the rows one exchange
    actually moves); ``dense_flops`` the bottom stack's forward FLOPs
    at that batch.  Regimes this selects (pinned in
    tests/test_overlap.py / scripts/check_overlap.py): the
    run_random.sh shape at per-shard batch ~512 and up — exchange
    ~17us and dense ~11us per step, both big enough that hiding one
    clears the margin — overlap wins; per-shard batch 64 (a probe
    shape, dense ~1.4us) keeps the serial exchange, as do K=1 and a
    single model rank."""
    mp = max(int(model_parallel), 1)
    k = max(int(microbatches), 1)
    if mp <= 1 or k <= 1:
        return False
    ex_bytes = float(local_batch) * num_tables * dim * itemsize
    if mode == "all_to_all":
        ex_bytes /= mp  # each rank exchanges ~1/mp of allgather's bytes
    ex_ns = ex_bytes * (mp - 1) / mp / ICI_GBPS
    dense_ns = float(dense_flops) / MXU_F32_FLOPS_PER_NS
    hidden_ns = min(ex_ns, dense_ns)
    boundary_ns = 2.0 * (k - 1) * OP_BOUNDARY_NS
    return hidden_ns > DISPATCH_MARGIN * boundary_ns


def tiered_storage_wins(num_rows: int, dim: int, itemsize: int,
                        hot_rows: int, lookups: int,
                        hit_rate: float) -> bool:
    """Static dispatch gate for the tiered embedding store
    (storage/tiered.py) vs streaming every looked-up row over the host
    link — the fallback a table that doesn't fit device memory would
    otherwise pay.

    Tiered cost per dispatch: every lookup gathers from the hot buffer
    (~9 ns/row, the same fused gather pipeline as a resident table),
    plus ONE start-all-then-wait miss block for the predicted
    ``(1 - hit_rate) * lookups`` misses — one link-latency hit, then
    each missing row pays the link transfer and the ~64 ns/row set-
    kernel write into the hot buffer.

    Streaming cost: the same link latency, then EVERY lookup pays the
    link transfer plus the gather.

    Refusals by construction (pinned in scripts/check_storage.py):
    a table that fits the budget (``hot_rows >= num_rows``) stays
    resident — a cache over a resident table is pure overhead; a
    budget smaller than one batch's worst-case working set
    (``hot_rows < lookups``) cannot pin its own batch and would thrash;
    and a uniform-traffic hit rate (no observed skew) loses to the 2x
    ``DISPATCH_MARGIN`` — the cache only wins on skew there is
    evidence for.  High-skew traffic (hit ~0.9 at the serve_bench
    Zipf default) clears the margin; hit ~0.5 does not."""
    if hot_rows >= num_rows:
        return False  # fits on device: resident always wins
    if lookups <= 0 or hot_rows <= 0:
        return False
    if hot_rows < lookups:
        return False  # cannot pin one batch's worst-case working set
    hit = min(max(float(hit_rate), 0.0), 1.0)
    row_link_ns = float(dim) * itemsize / HOST_LINK_GBPS
    misses = (1.0 - hit) * lookups
    tiered_ns = lookups * GATHER_NS_PER_ROW
    if misses > 0:
        tiered_ns += HOST_LINK_LATENCY_NS \
            + misses * (row_link_ns + SET_KERNEL_NS_PER_ROW)
    stream_ns = HOST_LINK_LATENCY_NS \
        + lookups * (row_link_ns + GATHER_NS_PER_ROW)
    return tiered_ns * DISPATCH_MARGIN < stream_ns
