"""DLRM — deep learning recommendation model (the fork's flagship app).

TPU-native equivalent of reference examples/cpp/DLRM/dlrm.cc:
  top_level_task dlrm.cc:77-199 — bottom MLP over dense features, one
  embedding bag per sparse feature (AGGR_SUM), feature interaction
  ("cat" concat; "dot" was a TODO at dlrm.cc:49-65 — implemented here),
  top MLP, sigmoid output, MSE loss + accuracy metrics;
  create_mlp dlrm.cc:103-112, create_emb dlrm.cc:114-120,
  interact_features dlrm.cc:122-138; flags parse_input_args dlrm.cc:201-264.

Parallelization parity with the reference DLRM strategies
(src/runtime/dlrm_strategy.cc:242-296): embeddings table-parallel (stacked
tables sharded over the "model" mesh axis — each chip owns T/m tables in
HBM), MLPs data-parallel; the interaction point's gather is the ICI
all-to-all XLA inserts between the table-sharded embedding output and the
data-sharded MLP.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from ..config import FFConfig
from ..model import FFModel
from ..optim import SGDOptimizer
from ..parallel.parallel_config import ParallelConfig


@dataclass
class DLRMConfig:
    """Flag parity with reference dlrm.cc:201-264 / dlrm.h."""

    sparse_feature_size: int = 64          # --arch-sparse-feature-size
    embedding_size: List[int] = field(     # --arch-embedding-size "1000000-..."
        default_factory=lambda: [1000000] * 8)
    embedding_bag_size: int = 1            # --embedding-bag-size
    mlp_bot: List[int] = field(default_factory=lambda: [64, 512, 512, 64])
    mlp_top: List[int] = field(default_factory=lambda: [576, 1024, 1024, 1024, 1])
    arch_interaction_op: str = "cat"       # --arch-interaction-op {cat,dot}
    # --fused-interaction {off,auto,on}: build the gather->pool->interact
    # chain as ONE FusedEmbedInteract op (ops/fused_interact.py) instead
    # of stacked_embedding -> reshape -> concat/batch_matmul.  "auto"
    # fuses on single-chip TPU — the one place the pallas kernel can
    # engage, and only for feature sizes that are whole 128-lane rows
    # (Mosaic refuses the default d = 64; ops/pallas_fused_interact.py
    # ``kernel_eligible``), everything else runs the op's emitter path;
    # "on" forces the fused graph everywhere (the emitter path runs
    # off-TPU, bit-exact); "off" (default) keeps the classic graph.
    fused_interaction: str = "off"
    # --exchange-overlap {off,auto,on}: build the bottom MLP + stacked
    # embedding as ONE OverlappedEmbedBottom op (ops/overlap_embed.py)
    # so the manual table-parallel exchange (FFConfig.table_exchange)
    # runs as a microbatched pipeline overlapping each microbatch's
    # ICI collective with its bottom-MLP dense slice
    # (parallel/overlap.py).  "auto" builds the overlapped graph when a
    # manual exchange is configured and lets the per-trace cost gate
    # (ops/kernel_costs.exchange_overlap_wins) pick pipeline vs serial;
    # "on" forces the overlapped graph (and the pipeline wherever it
    # can run); "off" (default) keeps the classic separate-ops graph.
    # Numerics: overlap reorders collective reductions — tolerance-
    # pinned vs the serial exchange, so bench anchors carry
    # ":overlap=" (tests/test_overlap.py, telemetry/regress.py).
    exchange_overlap: str = "off"
    # --exchange-microbatches N: the pipeline depth K (>= 2 to overlap;
    # the per-data-shard batch must divide K — and mp*K for the
    # all_to_all exchange form — or the op falls back to the serial
    # exchange for that traced shape).
    exchange_microbatches: int = 2
    loss_threshold: float = 0.0            # --loss-threshold
    sigmoid_bot: int = -1                  # -1 = no sigmoid in bottom MLP
    sigmoid_top: int = -1                  # -1 = sigmoid on the last top layer
    dataset: Optional[str] = None          # --dataset (HDF5 path) or None=synthetic
    data_size: int = -1                    # --data-size

    @staticmethod
    def parse_args(argv: Sequence[str]) -> "DLRMConfig":
        c = DLRMConfig()
        i = 0
        argv = list(argv)
        while i < len(argv):
            a = argv[i]
            def nxt():
                nonlocal i
                i += 1
                return argv[i]
            if a == "--arch-sparse-feature-size":
                c.sparse_feature_size = int(nxt())
            elif a == "--arch-embedding-size":
                c.embedding_size = [int(x) for x in nxt().split("-")]
            elif a == "--embedding-bag-size":
                c.embedding_bag_size = int(nxt())
            elif a == "--arch-mlp-bot":
                c.mlp_bot = [int(x) for x in nxt().split("-")]
            elif a == "--arch-mlp-top":
                c.mlp_top = [int(x) for x in nxt().split("-")]
            elif a == "--arch-interaction-op":
                c.arch_interaction_op = nxt()
            elif a == "--fused-interaction":
                c.fused_interaction = nxt()
            elif a == "--exchange-overlap":
                c.exchange_overlap = nxt()
            elif a == "--exchange-microbatches":
                c.exchange_microbatches = int(nxt())
            elif a == "--loss-threshold":
                c.loss_threshold = float(nxt())
            elif a == "--dataset":
                c.dataset = nxt()
            elif a == "--data-size":
                c.data_size = int(nxt())
            i += 1
        return c


KAGGLE_TABLES = [1396, 550, 1761917, 507795, 290, 21, 11948, 608, 3, 58176,
                 5237, 1497287, 3127, 26, 12153, 1068715, 10, 4836, 2085, 4,
                 1312273, 17, 15, 110946, 91, 72655]
# ^ the 26 Criteo-Kaggle categorical cardinalities
#   (reference examples/cpp/DLRM/run_criteo_kaggle.sh)


def criteo_kaggle_config() -> "DLRMConfig":
    """THE Criteo-Kaggle model shape, shared by the benchmark, the
    criteo example, and the window-scaling script so they always train
    the identical architecture.  run_criteo_kaggle.sh says mlp_top
    224-512-256-1, but with its own cat interaction the width is
    16 + 26*16 = 432 (the reference snapshot is mid-merge and
    inconsistent; SURVEY.md "Repo state warning") — use the consistent
    width."""
    return DLRMConfig(sparse_feature_size=16,
                      embedding_size=list(KAGGLE_TABLES),
                      embedding_bag_size=1,
                      mlp_bot=[13, 512, 256, 64, 16],
                      mlp_top=[16 + 26 * 16, 512, 256, 1])


def _on_single_tpu() -> bool:
    """fused_interaction="auto" regime: one TPU chip (under a mesh the
    pallas kernel cannot engage and the classic graph keeps its proven
    sharding annotations)."""
    import jax

    return jax.default_backend() == "tpu" and jax.device_count() == 1


def _create_mlp(model: FFModel, x, layer_sizes, sigmoid_layer: int,
                prefix: str):
    """reference create_mlp (dlrm.cc:103-112): relu everywhere, sigmoid at
    ``sigmoid_layer`` (the final top layer)."""
    t = x
    for i in range(len(layer_sizes) - 1):
        act = "sigmoid" if i == sigmoid_layer else "relu"
        t = model.dense(t, layer_sizes[i + 1], activation=act,
                        name=f"{prefix}_{i}")
    return t


def _interact_features(model: FFModel, bottom_out, emb_out, cfg: DLRMConfig):
    """reference interact_features (dlrm.cc:122-138) 'cat' path; 'dot' is
    the pairwise-dot interaction the reference left as TODO (dlrm.cc:49-65),
    implemented TPU-style as one batched MXU matmul."""
    if cfg.arch_interaction_op == "cat":
        return model.concat([bottom_out] + emb_out, axis=1)
    if cfg.arch_interaction_op == "dot":
        d = cfg.sparse_feature_size
        feats = [model.reshape(bottom_out, (bottom_out.shape[0], 1, d))]
        for e in emb_out:
            # 2-D (B, T*d) -> (B, T, d); 3-D already (B, T, d)
            feats.append(model.reshape(e, (e.shape[0], e.shape[1] // d, d))
                         if e.ndim == 2 else e)
        z = model.concat(feats, axis=1)                # (B, F, d)
        zz = model.batch_matmul(z, model.transpose(z))  # (B, F, F)
        flatz = model.flat(zz)
        return model.concat([bottom_out, flatz], axis=1)
    raise ValueError(f"unknown interaction op {cfg.arch_interaction_op!r}")


def build_dlrm(cfg: DLRMConfig, ffconfig: Optional[FFConfig] = None,
               stacked_embeddings: Optional[bool] = None,
               table_parallel: bool = False) -> FFModel:
    """Build the DLRM graph (reference top_level_task dlrm.cc:77-153).

    ``stacked_embeddings``: fuse the tables into one sharded weight — the
    TPU-idiomatic table-parallel layout.  Same-size tables stack into a
    (T, rows, dim) weight; different-size tables fuse into one ragged
    (R_total, dim) row space with static offsets (the non-uniform
    per-table placement of dlrm_strategy.cc:251-256 /
    run_criteo_kaggle.sh).  Defaults to True.
    ``table_parallel``: mark embedding + interaction ops with model-axis
    strategies (the hybrid strategy of dlrm_strategy.cc:242-296).

    ``cfg.fused_interaction`` (off/auto/on) swaps the embedding +
    interaction chain for ONE FusedEmbedInteract op (same loader input
    convention as the stacked graph).  "auto" engages on single-chip
    TPU; table-parallel builds always keep the classic graph (the
    model-axis sharding annotates the unfused stacked op).
    """
    ffconfig = ffconfig or FFConfig()
    model = FFModel(ffconfig)
    b = ffconfig.batch_size
    uniform = len(set(cfg.embedding_size)) == 1
    if stacked_embeddings is None:
        stacked_embeddings = True
    t = len(cfg.embedding_size)
    d = cfg.sparse_feature_size

    dense_in = model.create_tensor((b, cfg.mlp_bot[0]), "float32", name="dense")

    fmode = getattr(cfg, "fused_interaction", "off")
    if fmode not in ("off", "auto", "on"):
        raise ValueError(
            f"fused_interaction must be 'off'|'auto'|'on', got {fmode!r}")
    if fmode == "on" and not stacked_embeddings:
        raise ValueError(
            "fused_interaction='on' needs the stacked input convention "
            "(one (B, T, bag) ids tensor); per-table inputs "
            "(stacked_embeddings=False) cannot feed the fused op")
    omode = getattr(cfg, "exchange_overlap", "off")
    if omode not in ("off", "auto", "on"):
        raise ValueError(
            f"exchange_overlap must be 'off'|'auto'|'on', got {omode!r}")
    if omode == "on" and (not stacked_embeddings or not uniform):
        raise ValueError(
            "exchange_overlap='on' needs uniform stacked tables (the "
            "manual table exchange pins whole same-shape tables per "
            "model rank, parallel/table_exchange.py)")
    if omode == "on" and fmode == "on":
        raise ValueError(
            "fused_interaction='on' and exchange_overlap='on' both "
            "replace the embedding chain — pick one graph shape")
    # the overlapped graph replaces bottom-MLP + stacked embedding with
    # ONE op; "auto" engages it only when a manual exchange is actually
    # configured (FFConfig.table_exchange) — without one the op would
    # run its serial fallback for no graph-shape benefit
    xmode = getattr(ffconfig, "table_exchange", "off")
    use_overlap = stacked_embeddings and uniform and (
        omode == "on" or (omode == "auto" and xmode != "off"))
    if use_overlap:
        t0 = cfg.embedding_size[0]
        ids = model.create_tensor((b, t, cfg.embedding_bag_size), "int64",
                                  name="sparse")
        emb, bottom = model.overlapped_embed_bottom(
            ids, dense_in, t, t0, d, cfg.mlp_bot,
            sigmoid_bot=cfg.sigmoid_bot, aggr="sum", overlap=omode,
            microbatches=getattr(cfg, "exchange_microbatches", 2),
            name="emb_bot")
        if table_parallel:
            # shard the table axis of the (T, R, d) weight over "model"
            # (the bottom-MLP weights stay replicated — the op's specs
            # declare them sharded_dim=None)
            model.get_op("emb_bot").parallel_config = ParallelConfig(
                dims=(1, t, 1))
        flat = model.reshape(emb, (b, t * d), name="emb_flat")
        z = _interact_features(model, bottom, [flat], cfg)
        assert z.shape[1] == cfg.mlp_top[0], (
            f"interaction width {z.shape[1]} != mlp_top[0] {cfg.mlp_top[0]}")
        sig = cfg.sigmoid_top if cfg.sigmoid_top >= 0 else len(cfg.mlp_top) - 2
        _create_mlp(model, z, cfg.mlp_top, sig, "top")
        model._dlrm_stacked = True
        return model

    bottom = _create_mlp(model, dense_in, cfg.mlp_bot, cfg.sigmoid_bot, "bot")

    use_fused = stacked_embeddings and not table_parallel and (
        fmode == "on" or (fmode == "auto" and _on_single_tpu()))
    if use_fused:
        ids = model.create_tensor((b, t, cfg.embedding_bag_size), "int64",
                                  name="sparse")
        z = model.fused_embed_interact(
            ids, bottom, list(cfg.embedding_size), d,
            interact=cfg.arch_interaction_op, aggr="sum", name="emb")
        assert z.shape[1] == cfg.mlp_top[0], (
            f"interaction width {z.shape[1]} != mlp_top[0] {cfg.mlp_top[0]}")
        sig = cfg.sigmoid_top if cfg.sigmoid_top >= 0 else len(cfg.mlp_top) - 2
        top = _create_mlp(model, z, cfg.mlp_top, sig, "top")
        model._dlrm_stacked = True
        return model

    emb_out = []
    if stacked_embeddings:
        ids = model.create_tensor((b, t, cfg.embedding_bag_size), "int64",
                                  name="sparse")
        if uniform:
            stacked = model.stacked_embedding(ids, t, cfg.embedding_size[0],
                                              d, aggr="sum", name="emb")
        else:
            stacked = model.ragged_stacked_embedding(
                ids, cfg.embedding_size, d, aggr="sum", name="emb")
        if table_parallel:
            # shard the table axis (dim 1 of (B, T, d)) over "model"
            model.get_op("emb").parallel_config = ParallelConfig(
                dims=(1, t, 1))
        flat = model.reshape(stacked, (b, t * d), name="emb_flat")
        emb_out = [flat]
    else:
        for i, rows in enumerate(cfg.embedding_size):
            ids = model.create_tensor((b, cfg.embedding_bag_size), "int64",
                                      name=f"sparse_{i}")
            emb_out.append(model.embedding(ids, rows, d, aggr="sum",
                                           name=f"emb_{i}"))

    z = _interact_features(model, bottom, emb_out, cfg)
    assert z.shape[1] == cfg.mlp_top[0], (
        f"interaction width {z.shape[1]} != mlp_top[0] {cfg.mlp_top[0]}")
    sig_top = cfg.sigmoid_top if cfg.sigmoid_top >= 0 else len(cfg.mlp_top) - 2
    top = _create_mlp(model, z, cfg.mlp_top, sig_top, "top")
    model._dlrm_stacked = stacked_embeddings
    return model


def setup(argv: Sequence[str] = (), ffconfig: Optional[FFConfig] = None,
          mesh=None, table_parallel: bool = False):
    """Everything the CLI does before ``fit``: parse the flags, build
    and compile the model (MSE loss + accuracy, dlrm.cc:150), create the
    state and the loader.  Returns ``(model, state, loader)``.

    ``ffconfig`` replaces the flag-parsed FFConfig (``chip_smoke.py``
    passes one with a cache/storage mode changed); ``mesh`` and
    ``table_parallel`` go to ``compile`` / ``build_dlrm`` unchanged (the
    CLI leaves both at their defaults: all devices data-parallel)."""
    from ..data.loader import SyntheticDLRMLoader, load_criteo_h5, ArrayDataLoader

    ffconfig = ffconfig or FFConfig.parse_args(argv)
    cfg = DLRMConfig.parse_args(argv)
    model = build_dlrm(cfg, ffconfig, table_parallel=table_parallel)
    model.compile(optimizer=SGDOptimizer(ffconfig.learning_rate, 0.0, False,
                                         ffconfig.weight_decay),
                  loss_type="mean_squared_error",
                  metrics=("accuracy", "mean_squared_error"), mesh=mesh)
    state = model.init()
    stacked = model._dlrm_stacked  # keep loader layout in sync with graph
    if cfg.dataset:
        inputs, labels = load_criteo_h5(cfg.dataset, stacked=stacked)
        loader = ArrayDataLoader(inputs, labels, ffconfig.batch_size)
    else:
        n = cfg.data_size if cfg.data_size > 0 else 16 * ffconfig.batch_size
        loader = SyntheticDLRMLoader(n, cfg.mlp_bot[0], cfg.embedding_size,
                                     cfg.embedding_bag_size,
                                     ffconfig.batch_size, stacked=stacked)
    return model, state, loader


def run(argv: Sequence[str] = ()):  # pragma: no cover - CLI
    """CLI mirroring the reference app (dlrm.cc:77-199)."""
    from ..entrypoint import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(), flush=True)
    model, state, loader = setup(argv)
    ffconfig = model.config
    state, thpt = model.fit(state, loader, epochs=ffconfig.epochs)
    if ffconfig.profiling:
        # reference --profiling wraps every kernel in timing events and
        # prints per-op times (model.cc:1376-1379, linear.cu:499-531)
        from ..profiling import OpTimer
        timer = OpTimer(model)
        print(timer.report(timer.profile(state, None)))
    return thpt


if __name__ == "__main__":  # pragma: no cover
    import sys

    run(sys.argv[1:])
