"""Runtime configuration and CLI flag parsing.

TPU-native equivalent of the reference's ``FFConfig`` / ``DefaultConfig``
(reference: include/config.h:65-103, src/runtime/model.cc:1273-1381).

The reference scans argv by hand for Legion-ish flags (``-ll:gpu``, ``-b``,
``-e``, ``--lr`` ...).  We keep the same user-facing knobs but express the
device axis as a JAX mesh shape instead of processor counts, since placement
on TPU is decided by ``jax.sharding`` rather than a Legion mapper.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass
class FFConfig:
    """Global training configuration.

    Field parity with reference include/config.h:65-103:
      epochs/batchSize/iterations/learningRate/weightDecay  -> same names here
      workersPerNode/numNodes                               -> mesh_shape
      search budget/alpha, import/export strategy files     -> search_*,
                                                               strategy_file
      profiling flag                                        -> profiling
    """

    epochs: int = 1
    batch_size: int = 64
    iterations: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # Device organisation: a logical mesh (data, model) replacing the
    # reference's workersPerNode x numNodes grid (config.h:70-71).
    num_devices: Optional[int] = None  # default: all visible devices
    mesh_shape: Optional[dict] = None  # e.g. {"data": 4, "model": 2}
    # SOAP search (reference config.h:75-78, model.cc:1345-1366)
    search_budget: int = 0
    search_alpha: float = 0.05
    search_overlap_backward_update: bool = False
    import_strategy_file: Optional[str] = None
    export_strategy_file: Optional[str] = None
    # Profiling (reference model.cc:1376-1379)
    profiling: bool = False
    # Simulator workspace (reference config.h:95 simulator_work_space_size)
    simulator_work_space_size: int = 2 * 1024 * 1024 * 1024
    # Numerics
    compute_dtype: str = "float32"  # per-op matmuls may run bf16 on TPU
    # Embedding-table storage dtype.  Big-table gather/scatter lowers to
    # a full-table sweep on TPU backends, so "bfloat16" halves the
    # dominant per-step cost of embedding-heavy models (measured 1.8x on
    # DLRM run_random.sh, PERF.md).  Default float32 matches the
    # reference's fp32 tables bit-for-bit.
    embedding_dtype: str = "float32"
    # Row-sparse embedding updates under plain SGD ("auto"|"on"|"off").
    # "auto" enables them on cpu/gpu (scatter aliases in place) and on
    # single-device tpu where the in-place pallas row-update kernel
    # applies (ops/pallas_scatter.py — XLA's own scatter emitter forces
    # full-table layout copies, see PERF.md).  "on"/"off" force the
    # choice.
    sparse_embedding_updates: str = "auto"
    # Epoch row-cache ("auto"|"on"|"off"): train_epoch pulls the epoch's
    # touched embedding rows into a small cache with one table sweep,
    # scans against the cache, and writes back once — exact numerics,
    # per-step table cost becomes O(touched rows) (PERF.md).  "auto"
    # enables it on TPU; "on" forces it on any backend; "off" disables.
    epoch_row_cache: str = "auto"
    # Scan steps per dispatched chunk when the epoch row-cache is active:
    # the per-step cache sweep scales with the chunk's unique rows while
    # the two table sweeps amortize over it (measured optimum ~256 on the
    # headline config, PERF.md).  0 disables chunking.
    epoch_cache_chunk: int = 256
    # Second, in-graph cache level: every `epoch_cache_inner` scan steps
    # pull their rows from the chunk cache into a block cache (L0) so the
    # per-step sweep scales with the block, not the chunk (measured
    # optimum 8 with chunk 256, PERF.md).  0 disables.
    epoch_cache_inner: int = 8
    # In-graph cache-ladder shape ("auto" | "off" | explicit sizes like
    # "256,32,8", outermost first).  "auto" is the rule of
    # row_cache.py::CachePolicy.ladder_sizes: [8*inner, inner], the leaf
    # level alone when every cached table takes the region layout, a
    # chunk-sized level when inner <= 1 — each level pulls its block's
    # rows from the parent cache, and a level that engages over the
    # whole epoch makes a multi-epoch run one dispatch with one
    # prologue.  "off" restores flat host-side chunking with no
    # in-graph levels.
    epoch_cache_levels: str = "auto"
    # Top-level cache transport unit ("auto"|"on"|"off").  "on"/"auto"
    # fetch and write back the epoch cache in 128-lane VIEW rows
    # (pack = 128/d logical rows each) instead of logical rows: the
    # big-table gather/scatter then runs in the layout every other
    # table op prefers, killing XLA's transposed-table layout choice
    # and its full-table copies + loop transposes around the
    # prologue/epilogue (~180 ms per fused run at the bench shape,
    # scripts/profile_headline.py).  Exact — untouched halves of a
    # touched view row round-trip their original bytes.  "auto" = on
    # for single-device TPU (where the packed per-step view is also
    # active); "on" forces it on any backend (tests); "off" restores
    # logical-row transport.
    epoch_cache_view: str = "auto"
    # BLOCK-MAJOR epoch-cache regions ("auto"|"on"|"off"): lay the epoch
    # cache out as one occurrence-sized region per ladder-top block and
    # STREAM each block's writeback into its own region
    # (dynamic_update_slice — measured 8.4x the scatter emitter's
    # density-scaled RMW sweep at the boundary shape, ab_boundary.py);
    # cross-block coherence moves into the fetch, which reads each
    # position from the row's newest copy at prologue-computed
    # circular-predecessor positions (ops/slotting.py::region_plan),
    # and the epilogue gathers each row's last copy.  In the
    # single-level layout (auto's, whenever every cache op engages) a
    # region holds its FOREIGN rows first — those another block holds
    # too, the only positions whose newest copy is not their own — and
    # the fetch is one dynamic_slice of the block's own region plus a
    # gather of just those rows (region_slots, row_cache.py _region_fetch;
    # on the v5e: 133.9 -> 41 us a block on uniform ids, 57 on
    # Zipf 1.05).  Bit-exact with shared-slot mode (tests).
    # With a two-level ladder the L1 cache is itself L0-region-major
    # (grouped circular plan), so the L0 writebacks stream too; its
    # fetches gather every position.
    # Engages for single-device packed-storage ops when the ladder top
    # level divides the epoch (row_cache.py: region_engages,
    # region_layout).  "auto" = on from 2^18 id occurrences an epoch
    # (round-5 headline A/B: busy 243.5 -> 219.0 ms); "on" at any size;
    # "off" restores shared-slot mode.
    epoch_cache_regions: str = "auto"
    # Physical embedding-table storage ("auto"|"on"|"off").  "auto"/"on"
    # store d<128 tables lane-PACKED as (R/pack, 128) arrays end-to-end
    # (pack = 128/d): the logical (R, d) form's T(8,128) tiling pads
    # half its lanes, so XLA lays big logical tables out transposed and
    # pays full-table shuffles at every gather/scatter/reshape boundary
    # (~180 ms per fused headline run, scripts/profile_headline.py).
    # With packed storage no (R, d<128) array ever exists on device;
    # the epoch row-cache and its ladder then transport whole view rows
    # at every level.  Logical weights appear only at the host boundary
    # (get_weights/set_weights reshape — bit-exact, row-major).  "auto"
    # = single-device TPU; "on" forces it anywhere (tests); "off"
    # restores logical storage.
    packed_tables: str = "auto"
    # Inter-op activation STORAGE dtype ("float32"|"bfloat16").
    # "bfloat16" halves the HBM traffic of every intermediate activation
    # (conv nets are activation-bandwidth-bound on TPU — PERF.md round-3
    # inception decomposition) by declaring intermediate outputs bf16;
    # compute stays mixed-precision (MXU bf16 with f32 accumulation,
    # BatchNorm statistics in f32), and the FINAL output tensor stays
    # float32 so losses/metrics are unchanged in dtype.  Orthogonal to
    # compute_dtype; loss trajectory tracks the f32-activation run
    # (pinned by test).
    activation_dtype: str = "float32"
    # Manual table-parallel exchange for StackedEmbedding under a mesh
    # ("off"|"allgather"|"all_to_all"): route the table-sharded lookup
    # through an explicit shard_map + ICI collective
    # (parallel/table_exchange.py) instead of letting XLA SPMD pick the
    # collectives.  Dense-path only (the row-sparse fast path is
    # disabled for exchanged ops).  "off" (default) = SPMD-automatic.
    table_exchange: str = "off"
    # fit()'s scanned-epoch fast path stages the whole dataset on device;
    # datasets larger than this stay on the streaming per-batch loop
    # (0 disables the fast path entirely)
    fit_scan_max_bytes: int = 2 * 1024 * 1024 * 1024
    # Async input pipeline for the per-batch training loops
    # (data/prefetch.py, docs/pipeline.md): a background thread slices,
    # shards, and device_puts up to this many batches ahead while the
    # current step runs on device, so the host's input work overlaps
    # the device window instead of stalling it.  0 (default) = the
    # synchronous loop; 2 is the double-buffered sweet spot.  Numerics
    # are bit-identical either way (pinned) and checkpoint resume stays
    # cursor-exact (state_dict reports the last batch CONSUMED).
    prefetch_depth: int = 0
    # --- Online serving (serving/, docs/serving.md) -------------------
    # Batch-size buckets the InferenceEngine AOT-compiles; requests pad
    # up to the enclosing bucket so steady-state serving never
    # recompiles (comma-separated sizes, sorted/deduped at parse).
    serve_buckets: str = "1,8,64,256"
    # DynamicBatcher knobs: rows per micro-batch (0 = the top bucket),
    # the max microseconds the oldest queued request waits before a
    # partial batch dispatches, the bounded queue depth (a full queue
    # SHEDS new requests with an explicit Rejected), and the default
    # per-request deadline (0 = none; a request older than its deadline
    # when popped completes with DeadlineExceeded).
    serve_max_batch: int = 0
    serve_max_wait_us: float = 2000.0
    serve_queue_depth: int = 256
    serve_timeout_us: float = 0.0
    # Serving-table quantization (ops/quantized.py, docs/serving.md):
    # "off" serves the f32 training tables bit-exactly; "int8" re-encodes
    # each embedding table at engine load as int8 codes + per-row f32
    # scale (~4x smaller sweep, tolerance-pinned outputs); "bf16" stores
    # bf16 rows (~2x).  Training numerics are never touched.
    serve_quantize: str = "off"
    # Tiered embedding storage (storage/, docs/storage.md): "resident"
    # serves full device-resident tables; "tiered" caches only the
    # hottest ``storage_hot_rows`` rows per table on device and streams
    # misses from host RAM — the serve-tables-bigger-than-HBM mode.
    # The kernel_costs.tiered_storage_wins gate may still refuse and
    # fall back to resident (engine.storage records why); quantize and
    # tiering are mutually exclusive.
    serve_storage: str = "resident"
    storage_hot_rows: int = 4096
    # Live-metrics endpoint (telemetry/exporter.py, docs/telemetry.md):
    # port for the process-wide Prometheus /metrics + /healthz HTTP
    # server, started once at compile().  0 (default) = off — scrapes
    # are pull-only and add no locks to the engine forward path beyond
    # what LatencyStats already takes.
    metrics_port: int = 0
    # Fault-injection spec (resilience/faultinject.py), e.g.
    # "nan_grads@step=3,preempt@step=7" — testing knob proving the
    # recovery paths end-to-end; also settable via the FF_FAULTS env
    # var.  Empty = no injected faults.
    faults: str = ""
    seed: int = 0

    @staticmethod
    def parse_args(argv: Sequence[str]) -> "FFConfig":
        """Parse reference-compatible CLI flags (model.cc:1313-1381)."""
        cfg = FFConfig()
        i = 0
        argv = list(argv)
        while i < len(argv):
            a = argv[i]

            def nxt() -> str:
                nonlocal i
                i += 1
                return argv[i]

            if a in ("-e", "--epochs"):
                cfg.epochs = int(nxt())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(nxt())
            elif a in ("-i", "--iterations"):
                cfg.iterations = int(nxt())
            elif a == "--lr" or a == "--learning-rate":
                cfg.learning_rate = float(nxt())
            elif a == "--wd" or a == "--weight-decay":
                cfg.weight_decay = float(nxt())
            elif a == "--budget" or a == "--search-budget":
                cfg.search_budget = int(nxt())
            elif a == "--alpha" or a == "--search-alpha":
                cfg.search_alpha = float(nxt())
            elif a == "--import":
                cfg.import_strategy_file = nxt()
            elif a == "--export":
                cfg.export_strategy_file = nxt()
            elif a == "--overlap":
                cfg.search_overlap_backward_update = True
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--seed":
                cfg.seed = int(nxt())
            elif a == "--compute-dtype":
                cfg.compute_dtype = nxt()
            elif a == "--embedding-dtype":
                cfg.embedding_dtype = nxt()
            elif a == "--faults":
                cfg.faults = nxt()
            elif a == "--serve-buckets":
                cfg.serve_buckets = nxt()
            elif a == "--serve-max-batch":
                cfg.serve_max_batch = int(nxt())
            elif a == "--serve-max-wait-us":
                cfg.serve_max_wait_us = float(nxt())
            elif a == "--serve-queue-depth":
                cfg.serve_queue_depth = int(nxt())
            elif a == "--serve-timeout-us":
                cfg.serve_timeout_us = float(nxt())
            elif a == "--serve-quantize":
                cfg.serve_quantize = nxt()
            elif a == "--serve-storage":
                cfg.serve_storage = nxt()
            elif a == "--storage-hot-rows":
                cfg.storage_hot_rows = int(nxt())
            elif a == "--metrics-port":
                cfg.metrics_port = int(nxt())
            elif a == "--prefetch":
                cfg.prefetch_depth = int(nxt())
            elif a in ("-d", "--devices", "-ll:gpu"):
                # reference -ll:gpu N => N workers; here: device count
                cfg.num_devices = int(nxt())
            elif a == "--nodes":
                nxt()  # multi-host handled by jax.distributed; flag accepted
            elif a.startswith("-ll:") or a.startswith("-lg:") or a.startswith("-dm:"):
                # Legion low-level flags: accepted and ignored on TPU
                if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
                    i += 1
            i += 1
        return cfg

    def resolved_num_devices(self) -> int:
        if self.num_devices is not None:
            return self.num_devices
        import jax

        return jax.device_count()
