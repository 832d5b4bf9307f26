"""FusedEmbedInteract operator: embedding bags + feature interaction
as ONE graph node (the fused twin of apps/dlrm.py's stacked-embedding
-> reshape -> concat / batch_matmul chain).

Inputs ``[ids (B, T, bag) int, bottom (B, bot_dim)]``; output the
interaction directly — ``(B, bot_dim + T*d)`` for ``cat``,
``(B, d + (T+1)^2)`` for ``dot``.  The embedding tables are the same
fused flat ``(R_total, d)`` row space as RaggedStackedEmbedding (this
op subclasses it), so the whole row-sparse training machinery —
``flat_ids`` addressing, ``gather_rows``/``scatter_apply``, the epoch
row-cache, packed storage — applies unchanged: the model injects
pre-gathered ``rows__`` and this op pools + interacts them (training
never pays the dense table-shaped backward).

Forward dispatch (no ``rows__``):

* **kernel** — the fused pallas kernel (pallas_fused_interact.py) when
  the compiler accepts the shape (``kernel_eligible``: a plain f32
  table with whole 128-lane rows, d % 128 == 0 — Mosaic refuses the
  app's d = 64) and the cost model says it wins
  (``kernel_costs.fused_interact_wins``) on single-chip TPU.
  ``FF_FUSED_INTERACT`` overrides the cost model only: ``auto``
  (default, cost-gated) | ``kernel`` | ``emitter``.  The backward of a
  kernel forward is the emitter VJP in every compiled program (the
  backward kernel does not compile on the chip —
  ``bwd_kernel_eligible``).
* **emitter** — the reference XLA path otherwise (also the only path
  for packed-storage and quantized serving tables, whose reads go
  through ``view_gather`` / per-row dequant).

Both paths share the dropped-id rule (``mask_local_ids``: negative or
out-of-table-range local ids pool as exact 0.0) and are bit-exact
against each other — pinned by ``tests/test_kernels.py`` and
``scripts/check_kernels.py``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .embedding import RaggedStackedEmbedding
from .pallas_fused_interact import (fused_embed_interact,
                                    interact_width, kernel_eligible,
                                    mask_local_ids, masked_pool_interact)

#: TPU dispatch override (A/B on real hardware): "auto" consults the
#: measured cost model per traced batch size, "kernel"/"emitter" force.
_IMPL = os.environ.get("FF_FUSED_INTERACT", "auto")


class FusedEmbedInteract(RaggedStackedEmbedding):
    op_type = "FusedEmbedInteract"

    def __init__(self, name, ids_tensor, bottom_tensor, row_counts,
                 out_dim: int, interact: str = "cat", aggr: str = "sum",
                 kernel_initializer=None, dtype=jnp.float32,
                 table_dtype=jnp.float32, compute_dtype=None):
        super().__init__(name, ids_tensor, row_counts, out_dim, aggr,
                         kernel_initializer, dtype, table_dtype)
        # the dot interaction's MXU precision — BatchMatmul's cast,
        # mirrored in both the kernel and the emitter tail so toggling
        # fusion never changes numerics at either compute precision
        self.compute_dtype = compute_dtype
        if interact not in ("cat", "dot"):
            raise ValueError(f"unknown interaction op {interact!r}")
        bot_dim = int(bottom_tensor.shape[1])
        if interact == "dot" and bot_dim != out_dim:
            raise ValueError(
                f"dot interaction needs bottom width {out_dim}, "
                f"got {bot_dim}")
        self.interact = interact
        self.bot_dim = bot_dim
        self.inputs = [ids_tensor, bottom_tensor]
        # interpret-mode kernel forcing for the CPU test suite
        self._interpret = False
        b = ids_tensor.shape[0]
        w = interact_width(interact, self.num_tables, out_dim, bot_dim)
        self.outputs = [self._make_output((b, w), dtype)]

    # ------------------------------------------------------------- dispatch
    def _kernel_ok(self, table, qscale, idx) -> bool:
        """Whether THIS traced call runs the fused kernel.  All static
        (shapes, dtypes, backend) — the dispatch is decided per
        compiled program (each serving bucket gates on its own batch),
        never per example."""
        if qscale is not None or self.storage_pack > 1:
            return False  # quantized/packed reads go through the emitter
        if self._mesh is not None:
            return False  # SPMD cannot partition a pallas_call
        bag = idx.shape[-1]
        if not kernel_eligible(table.dtype, self.out_dim, bag,
                               interpret=self._interpret):
            return False
        if self._interpret:
            return True
        if _IMPL == "emitter" or jax.default_backend() != "tpu":
            # the backend check outranks FF_FUSED_INTERACT=kernel: a
            # non-interpret pallas_call cannot compile off-TPU, so the
            # force flag only picks the kernel where one can run
            return False
        if _IMPL == "kernel":
            return True
        from .kernel_costs import fused_interact_wins
        return fused_interact_wins(
            int(idx.shape[0]), self.num_tables, bag, self.out_dim,
            jnp.dtype(table.dtype).itemsize, self.interact)

    # -------------------------------------------------------------- forward
    def forward(self, params, xs, *, training=False, rng=None):
        idx, bottom = xs
        out_dtype = self.outputs[0].dtype
        gids = mask_local_ids(idx, self.offsets, self.row_counts)
        rows = params.get("rows__")  # sparse-update path: (B, T, bag, d)
        if rows is not None:
            # the rows were gathered by the inherited (clip-semantics)
            # gather_rows; masking HERE keeps the dropped-id rule in
            # training too — a dropped slot pools as 0.0 and therefore
            # gets an exact-0.0 row grad, so scatter_apply adds nothing
            # to the clipped foreign row
            return [masked_pool_interact(rows, gids, bottom,
                                         self.interact, self.aggr,
                                         out_dtype, self.compute_dtype)]
        table = params["embedding"]
        qscale = params.get("qscale__")
        if self._kernel_ok(table, qscale, idx):
            out = fused_embed_interact(
                table, gids.astype(jnp.int32), bottom, self.interact,
                self.aggr, True, self._interpret, self.compute_dtype)
            return [out.astype(out_dtype)]
        # emitter path: same masked tail as fused_interact_ref (the
        # kernel's A/B target), forked only for the packed-storage view
        # read and the quantized per-row dequant
        safe = jnp.maximum(gids, 0).astype(jnp.int32)
        if self.storage_pack > 1:
            from .pallas_scatter import view_gather
            rows = view_gather(table, safe, self.out_dim)
        else:
            rows = jnp.take(table, safe, axis=0)
        if qscale is not None:
            from .quantized import dequant_rows
            rows = dequant_rows(rows, qscale, safe)
        return [masked_pool_interact(rows, gids, bottom, self.interact,
                                     self.aggr, out_dtype,
                                     self.compute_dtype)]

    # ------------------------------------------------------------ cost hooks
    def flops(self, batch):
        bag = self.inputs[0].shape[2] if len(self.inputs[0].shape) > 2 else 1
        f = batch * self.num_tables * bag * self.out_dim  # gather + pool
        if self.interact == "dot":
            fdim = self.num_tables + 1
            f += 2 * batch * fdim * fdim * self.out_dim  # pairwise dots
        return f
