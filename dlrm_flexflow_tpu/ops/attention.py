"""Multi-head attention with sequence-parallel (ring) execution.

The reference has **no attention op** (SURVEY §5.7) — its closest analogue
is NMT's per-timestep-block device placement (nmt/rnn.h:58-63).  This
framework treats the sequence axis as a first-class shardable dim of the
SOAP space, so long-context training is native:

- single-device path: fused scaled-dot-product attention (XLA fuses the
  softmax into the two MXU matmuls);
- sequence-parallel path: **ring attention** via ``shard_map`` +
  ``lax.ppermute`` over the mesh's "seq" axis — each chip holds a query
  block and streams K/V blocks around the ICI ring, accumulating with an
  online-softmax (flash-style) update, so memory stays O(seq/devices).

See parallel/ring_attention.py for the ring kernel itself.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..initializers import (ConstantInitializer, DEFAULT_KERNEL_INIT,
                            UniformInitializer)
from ..tensor import ParameterSpec
from . import pallas_attention
from .base import Op, held_heads, matmul
from .deltanet import l2_normalised
from .transformer import rms_norm, rope_half_split, rope_interleaved


def sdpa(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Scaled dot-product attention, (B, H, S, D) layout."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _block_of(seq: int, block: int) -> int:
    """The largest divisor of ``seq`` that is at most ``block``."""
    block = min(block, seq)
    while seq % block:
        block -= 1
    return block


def _causal_mask(i, j, block: int):
    """(block, block) bool: key ``j*block + c`` visible to query
    ``i*block + r``."""
    rows = i * block + jnp.arange(block)[:, None]
    cols = j * block + jnp.arange(block)[None, :]
    return cols <= rows


def _blocks(x, block: int):
    b, h, s, d = x.shape
    return x.reshape(b, h, s // block, block, d)


def _take(xb, i):
    return jax.lax.dynamic_index_in_dim(xb, i, axis=2, keepdims=False)


def _blockwise_fwd(q, k, v, scale: float, block: int):
    """Online-softmax forward.  Returns ``(o (B,H,S,Dv) f32, lse
    (B,H,S) f32)``; the (S, S) logits exist one (block, block) tile at
    a time, and the key blocks above the diagonal are never visited."""
    b, h, s, _ = q.shape
    dv = v.shape[-1]
    nb = s // block
    qb, kb, vb = _blocks(q, block), _blocks(k, block), _blocks(v, block)

    def q_block(i):
        q_i = _take(qb, i)

        def body(j, carry):
            m, l, acc = carry
            logits = jnp.einsum("bhqd,bhkd->bhqk", q_i, _take(kb, j),
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(_causal_mask(i, j, block), logits, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v.dtype), _take(vb, j),
                preferred_element_type=jnp.float32)
            return m_new, l * corr + jnp.sum(p, axis=-1), acc

        init = (jnp.full((b, h, block), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, block), jnp.float32),
                jnp.zeros((b, h, block, dv), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, i + 1, body, init)
        return acc / l[..., None], m + jnp.log(l)

    o, lse = jax.lax.map(q_block, jnp.arange(nb))  # (nb, B, H, block, ..)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, s, dv)
    return o, jnp.moveaxis(lse, 0, 2).reshape(b, h, s)


def _blockwise_bwd(q, k, v, o, lse, do, scale: float, block: int):
    """Backward from the saved output and log-sum-exp: every visited
    tile's probabilities are rebuilt as ``exp(logits - lse)``, so
    nothing of size (S, S) is ever kept.  One pass over the key blocks,
    each over the query blocks at or below it."""
    b, h, s, dk = q.shape
    nb = s // block
    cd = q.dtype
    delta = jnp.sum(do * o, axis=-1)                     # (B, H, S) f32
    qb, kb, vb = _blocks(q, block), _blocks(k, block), _blocks(v, block)
    dob = _blocks(do.astype(cd), block)
    lseb = lse.reshape(b, h, nb, block)
    deltab = delta.reshape(b, h, nb, block)

    def kv_block(dq, j):
        k_j, v_j = _take(kb, j), _take(vb, j)

        def body(i, carry):
            dk_j, dv_j, dq = carry
            q_i, do_i = _take(qb, i), _take(dob, i)
            logits = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(_causal_mask(i, j, block), logits, -jnp.inf)
            p = jnp.exp(logits - _take(lseb, i)[..., None])
            dv_j = dv_j + jnp.einsum("bhqk,bhqd->bhkd", p.astype(cd), do_i,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", do_i, v_j,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - _take(deltab, i)[..., None]) * scale).astype(cd)
            dk_j = dk_j + jnp.einsum("bhqk,bhqd->bhkd", ds, q_i,
                                     preferred_element_type=jnp.float32)
            dq_i = _take(dq, i) + jnp.einsum(
                "bhqk,bhkd->bhqd", ds, k_j,
                preferred_element_type=jnp.float32)
            dq = jax.lax.dynamic_update_index_in_dim(dq, dq_i, i, axis=2)
            return dk_j, dv_j, dq

        dk_j, dv_j, dq = jax.lax.fori_loop(
            j, nb, body, (jnp.zeros(k_j.shape, jnp.float32),
                          jnp.zeros(v_j.shape, jnp.float32), dq))
        return dq, (dk_j, dv_j)

    dq, (dk_, dv_) = jax.lax.scan(
        kv_block, jnp.zeros((b, h, nb, block, dk), jnp.float32),
        jnp.arange(nb))
    unblock = lambda x: jnp.moveaxis(x, 0, 2).reshape(b, h, s, x.shape[-1])
    return dq.reshape(b, h, s, dk), unblock(dk_), unblock(dv_)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blockwise_core(q, k, v, scale, block):
    return _blockwise_fwd(q, k, v, scale, block)[0]


#: what a recomputed run (``FFModel.scope(recompute=...)``) keeps of the
#: core, whichever form ran: its output and log-sum-exp (134 + 1 MB a
#: layer in f32 at 32 heads x 8,192 tokens x 128), so that the backward
#: pass rebuilds the projections but never runs the core's forward (the
#: forward kernel) a second time
CORE_SAVED = ("attention_core_out", "attention_core_lse")


def _blockwise_core_fwd(q, k, v, scale, block):
    o, lse = _blockwise_fwd(q, k, v, scale, block)
    o = checkpoint_name(o, CORE_SAVED[0])
    lse = checkpoint_name(lse, CORE_SAVED[1])
    return o, (q, k, v, o, lse)


def _blockwise_core_bwd(scale, block, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _blockwise_bwd(q, k, v, o, lse, do, scale, block)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blockwise_core.defvjp(_blockwise_core_fwd, _blockwise_core_bwd)


def _heads_flat(x):
    return x.reshape(-1, *x.shape[2:])


@jax.custom_vjp
def _fused_core(q, k, v):
    """The same core through ``ops/pallas_attention.py``: one kernel
    forward, one backward; the scale already folded into ``q``."""
    return _fused_core_fwd(q, k, v)[0]


def _fused_core_fwd(q, k, v):
    # grouped heads: (B, Hkv, S, .) flattens to batch x key/value heads,
    # and query head b * H + h reads (b * H + h) // (H / Hkv)
    o, lse = pallas_attention.forward(_heads_flat(q), _heads_flat(k),
                                      _heads_flat(v))
    o = checkpoint_name(o.reshape(*q.shape[:3], -1), CORE_SAVED[0])
    lse = checkpoint_name(lse.reshape(q.shape[:3]), CORE_SAVED[1])
    return o, (q, k, v, o, lse)


def _fused_core_bwd(res, do):
    q, k, v, o, lse = res
    grads = pallas_attention.backward(
        _heads_flat(q), _heads_flat(k), _heads_flat(v), _heads_flat(o),
        _heads_flat(lse), _heads_flat(do))
    return tuple(g.reshape(x.shape) for g, x in zip(grads, (q, k, v)))


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def core_form(seq: int, qk_dim: int, v_dim: int, dtype) -> str:
    """Which form ``blockwise_causal_attention`` runs at these shapes:
    ``"pallas"`` on a TPU where the kernels take them, else ``"plain"``.
    Decided from what the trace can see, and by nothing else."""
    fused = _on_tpu() and pallas_attention.takes(seq, qk_dim, v_dim, dtype)
    return "pallas" if fused else "plain"


#: key (and query) rows of one tile of the PLAIN blockwise core (the
#: kernels' block is ``pallas_attention.BLOCK``).  On the v5e at 32 heads
#: x 8,192 tokens x 192 / 128, forward + backward: 27.7 ms at 512, 43.0 at
#: 256, 56.3 at 1,024; the kernels that run there since PR 34: 20.6
#: (``scripts/ab_lm_kernels.py attn``)
ATTENTION_BLOCK = 512


def blockwise_causal_attention(q, k, v, scale: Optional[float] = None,
                               block: Optional[int] = None,
                               compute_dtype=None):
    """Causal attention that never builds (B, H, S, S).  ``q``: (B, H,
    S, Dk); ``k``: (B, Hkv, S, Dk); ``v``: (B, Hkv, S, Dv), Dv free of
    Dk, Hkv dividing H (query head ``h`` reads key/value head ``h // (H
    / Hkv)``).  Returns (B, H, S, Dv) f32: the same function as
    ``sdpa(..., causal=True)`` on the key/value heads repeated, up to
    rounding.

    The arithmetic, whichever form runs: the matmul operands go to
    ``compute_dtype`` (the dtype they come in unless given), each
    rounded once (bf16 operands give bf16 x bf16 -> f32 on the MXU);
    logits, softmax, log-sum-exp and every accumulator are f32; the
    probabilities are rounded to the compute dtype before ``P v``.
    Differentiated, it saves its output and log-sum-exp under
    ``CORE_SAVED`` and rebuilds each tile's probabilities from them.

    Two forms, one entry, chosen by ``core_form`` from the backend and
    the shapes alone.  On a TPU, with S a multiple of
    ``pallas_attention.BLOCK`` and head widths Mosaic takes (192 / 128
    in the language model): one Pallas kernel forward and one backward
    (``ops/pallas_attention.py``); the scale is folded into ``q`` here,
    in f32 and before its one rounding, so hand ``q`` over in f32.
    Anywhere else (the CPU, a 12-token sequence, 24-wide heads): the
    plain core, ``jax.numpy`` under ``lax`` loops with a
    ``jax.custom_vjp``: online softmax over key blocks forward, tile by
    tile backward, the logits scaled tile by tile.  ``block`` is the
    plain core's alone: ``ATTENTION_BLOCK`` unless given, cut to the
    largest divisor of S below it.  Grouped heads: the kernels read a
    group's one key/value head in place (16 query heads on 2 key/value
    heads x 16,384 x 256 on the v5e, forward / forward + backward:
    14.20 / 47.45 ms, against 14.58 / 47.47 with k and v written out
    eight times and 30.95 / 80.92 for the plain core: ``scripts/
    ab_lm_kernels.py gqa``); the plain core repeats them."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    cd = jnp.dtype(compute_dtype or q.dtype)
    k, v = k.astype(cd), v.astype(cd)
    if core_form(q.shape[2], q.shape[3], v.shape[3], cd) == "pallas":
        return _fused_core((q.astype(jnp.float32) * scale).astype(cd), k, v)
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    return _blockwise_core(q.astype(cd), k, v, float(scale),
                           _block_of(q.shape[2], block or ATTENTION_BLOCK))


class MultiHeadAttention(Op):
    """Self/cross attention: inputs (B, S, E) -> (B, S, E).

    ``seq_parallel=True`` asks the compiler to run the core via ring
    attention over the mesh "seq"/"context" axis (parallel/ring_attention).
    """

    op_type = "MultiHeadAttention"

    def __init__(self, name, query, key, value, embed_dim: int, num_heads: int,
                 causal: bool = False, kernel_initializer=None,
                 seq_parallel: bool = False, compute_dtype=None):
        super().__init__(name, [query, key, value])
        assert embed_dim % num_heads == 0
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.seq_parallel = seq_parallel
        self.compute_dtype = compute_dtype
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        b, s, _ = query.shape
        self.outputs = [self._make_output((b, s, embed_dim), query.dtype)]

    def param_specs(self):
        e = self.embed_dim
        qdim = self.inputs[0].shape[-1]
        kdim = self.inputs[1].shape[-1]
        vdim = self.inputs[2].shape[-1]
        return [
            ParameterSpec(self.name, "wq", (qdim, e),
                          initializer=self.kernel_initializer, sharded_dim=1),
            ParameterSpec(self.name, "wk", (kdim, e),
                          initializer=self.kernel_initializer, sharded_dim=1),
            ParameterSpec(self.name, "wv", (vdim, e),
                          initializer=self.kernel_initializer, sharded_dim=1),
            ParameterSpec(self.name, "wo", (e, e),
                          initializer=self.kernel_initializer, sharded_dim=0),
        ]

    def forward(self, params, xs, *, training=False, rng=None):
        q_in, k_in, v_in = xs
        cd = jnp.bfloat16 if self.compute_dtype in ("bfloat16", jnp.bfloat16) else None

        def proj(x, w):
            if cd is not None:
                x, w = x.astype(cd), w.astype(cd)
            return jnp.einsum("bse,ef->bsf", x, w,
                              preferred_element_type=jnp.float32)

        b, s, _ = q_in.shape
        h, d = self.num_heads, self.head_dim
        q = proj(q_in, params["wq"]).reshape(b, s, h, d).transpose(0, 2, 1, 3)
        k = proj(k_in, params["wk"]).reshape(b, -1, h, d).transpose(0, 2, 1, 3)
        v = proj(v_in, params["wv"]).reshape(b, -1, h, d).transpose(0, 2, 1, 3)
        if cd is not None:
            q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
        mesh = self._mesh
        if (self.seq_parallel and mesh is not None
                and "seq" in mesh.axis_names and mesh.shape["seq"] > 1):
            from ..parallel.ring_attention import ring_attention_sharded
            o = ring_attention_sharded(q, k, v, mesh, seq_axis="seq",
                                       causal=self.causal)
        else:
            o = sdpa(q, k, v, causal=self.causal)  # (b, h, s, d)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.embed_dim)
        out = proj(o, params["wo"]).astype(self.outputs[0].dtype)
        return [out]

    def flops(self, batch):
        s = self.inputs[0].shape[1]
        e = self.embed_dim
        # 4 projections + 2 attention matmuls
        return batch * (4 * 2 * s * e * e + 2 * 2 * s * s * e)


class LatentAttention(Op):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434
    section 2.1; the layout of the released DeepSeek-V3 code): (B, S, d)
    -> (B, S, d), causal, no biases.

    ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, per head ``nope +
    rope`` wide; ``[c_kv ; k_r] = x W_kva``; ``[k_nope ; v] =
    RMSNorm(c_kv) W_kvb``, per head ``nope + v_dim`` wide; one rotary
    key ``RoPE(k_r)`` for all heads and ``RoPE`` on each head's rotary
    query part, on interleaved pairs; logits ``(q_nope k_nope + q_rope
    k_rope) / sqrt(nope + rope)``; ``out = concat_h(P v) W_o``.  The
    query/key width (``nope + rope``) need not equal ``v_dim``.

    Three variations, each off by default (with them off the parameters
    and the output are what they were before the arguments existed):
    ``q_lora_rank=None``, no query latent, ``q = x W_q`` (DeepSeek-V2-
    Lite's); ``qk_norm``, each head's whole query and whole key ``[k_nope
    ; k_r]`` RMS-normalised over their ``nope + rope`` elements with one
    learned weight each, before the rotary embedding (so the rotary key
    is a head's own); ``gate="head_wise"``, each head's output times
    ``sigmoid(x w_g)``, ``W_g`` (d, heads).  ``heads_held``: this op
    holds that many of the heads (tensor parallelism without its
    all-reduce): ``W_q`` / ``W_qb``, ``W_kvb`` and ``W_g`` have the held
    heads' columns, ``W_o`` their rows, the latent projections and their
    norms are whole, and the output is the held heads' part of the sum
    over all heads.

    The core never builds (B, H, S, S): ``blockwise_causal_attention``,
    which runs as two Pallas kernels on a TPU at shapes they take and
    as the plain blockwise core elsewhere (``core_form`` says which; the
    model's ``program`` events count them).  Everything around the core
    (the projections, norms, RoPE, ``W_o``) is the same either way, and
    so is what a recomputed run keeps: the core's output and
    log-sum-exp (``saved_in_recompute``), so the recomputation rebuilds
    the projections and never the core.  Scopes: ``<phase>.proj`` and
    ``<phase>.core`` (the op's ``phase`` is ``FFModel.scope``'s word,
    ``ff.attn`` without one).
    """

    op_type = "LatentAttention"
    saved_in_recompute = CORE_SAVED
    core_field, core_forms = "attention_core", ("pallas", "plain")

    def __init__(self, name, input_tensor, num_heads: int, q_lora_rank,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, eps: float = 1e-6,
                 kernel_initializer=None, compute_dtype=None,
                 qk_norm: bool = False, gate: Optional[str] = None,
                 heads_held=None):
        super().__init__(name, [input_tensor])
        assert gate in (None, "head_wise"), gate
        self.model_dim = input_tensor.shape[-1]
        # the heads computed here: all of them unless a share is held
        self.num_heads = held_heads(heads_held, int(num_heads))
        self.qk_norm, self.gate = bool(qk_norm), gate
        self.q_lora_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.nope, self.rope, self.v_dim = int(qk_nope_head_dim), \
            int(qk_rope_head_dim), int(v_head_dim)
        self.rope_theta, self.eps = float(rope_theta), float(eps)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        d, h = self.model_dim, self.num_heads
        init, one = self.kernel_initializer, ConstantInitializer(1.0)
        qk = self.nope + self.rope
        if self.q_lora_rank is None:
            query = [ParameterSpec(self.name, "w_q", (d, h * qk),
                                   initializer=init, sharded_dim=1)]
        else:
            query = [
                ParameterSpec(self.name, "w_qa", (d, self.q_lora_rank),
                              initializer=init),
                ParameterSpec(self.name, "q_norm", (self.q_lora_rank,),
                              initializer=one),
                ParameterSpec(self.name, "w_qb", (self.q_lora_rank, h * qk),
                              initializer=init, sharded_dim=1)]
        extra = []
        if self.qk_norm:
            extra += [ParameterSpec(self.name, name, (qk,), initializer=one)
                      for name in ("q_head_norm", "k_head_norm")]
        if self.gate:
            extra.append(ParameterSpec(self.name, "w_gate", (d, h),
                                       initializer=init, sharded_dim=1))
        return query + [
            ParameterSpec(self.name, "w_kva",
                          (d, self.kv_lora_rank + self.rope),
                          initializer=init),
            ParameterSpec(self.name, "kv_norm", (self.kv_lora_rank,),
                          initializer=one),
            ParameterSpec(self.name, "w_kvb",
                          (self.kv_lora_rank, h * (self.nope + self.v_dim)),
                          initializer=init, sharded_dim=1)] + extra + [
            ParameterSpec(self.name, "w_o", (h * self.v_dim, d),
                          initializer=init, sharded_dim=0)]

    def _core_dtype(self):
        return jnp.dtype(jnp.bfloat16 if self.compute_dtype
                         in ("bfloat16", jnp.bfloat16) else jnp.float32)

    def core_form(self) -> str:
        """``"pallas"`` or ``"plain"``: what this op's core runs as
        (``ops/attention.py::core_form`` at its shapes)."""
        return core_form(self.inputs[0].shape[1], self.nope + self.rope,
                         self.v_dim, self._core_dtype())

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        h, nope, rope, vd = self.num_heads, self.nope, self.rope, self.v_dim
        cdt = self.compute_dtype
        positions = jnp.arange(s)
        scope = self.phase or "ff.attn"
        with jax.named_scope(scope + ".proj"):
            if self.q_lora_rank is None:
                q = matmul(x, params["w_q"], cdt)
            else:
                c_q = rms_norm(matmul(x, params["w_qa"], cdt),
                               params["q_norm"], self.eps)
                q = matmul(c_q, params["w_qb"], cdt)
            q = q.reshape(b, s, h, nope + rope)
            kva = matmul(x, params["w_kva"], cdt)
            c_kv, k_r = kva[..., :self.kv_lora_rank], \
                kva[..., self.kv_lora_rank:]
            kv = matmul(rms_norm(c_kv, params["kv_norm"], self.eps),
                        params["w_kvb"], cdt).reshape(b, s, h, nope + vd)
            if self.qk_norm:
                # the norm runs over a head's whole key, so the rotary
                # part is a head's own from here
                q = rms_norm(q, params["q_head_norm"], self.eps)
                k = rms_norm(jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_r[:, :, None, :], (b, s, h, rope))], axis=-1),
                    params["k_head_norm"], self.eps)
                k_all = jnp.concatenate(
                    [k[..., :nope], rope_interleaved(
                        k[..., nope:], positions, self.rope_theta,
                        seq_axis=1)], axis=-1)
            else:
                k_rope = rope_interleaved(k_r, positions, self.rope_theta,
                                          seq_axis=1)
                k_all = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_rope[:, :, None, :], (b, s, h, rope))], axis=-1)
            q_rope = rope_interleaved(q[..., nope:], positions,
                                      self.rope_theta, seq_axis=1)
            q_all = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            heads_first = lambda t: t.transpose(0, 2, 1, 3)
            q_all, k_all, v = heads_first(q_all), heads_first(k_all), \
                heads_first(kv[..., nope:])
        with jax.named_scope(scope + ".core"):
            # f32 in, f32 out; the operands go to the compute dtype
            # inside, each rounded once
            o = blockwise_causal_attention(
                q_all, k_all, v, 1.0 / math.sqrt(nope + rope),
                compute_dtype=self._core_dtype())
        with jax.named_scope(scope + ".proj"):
            o = o.transpose(0, 2, 1, 3)
            if self.gate:
                o = o * jax.nn.sigmoid(
                    matmul(x, params["w_gate"], cdt))[..., None]
            out = matmul(o.reshape(b, s, h * vd), params["w_o"], cdt)
        return [out.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        s, d, h = self.inputs[0].shape[1], self.model_dim, self.num_heads
        qk = self.nope + self.rope
        query = d * h * qk if self.q_lora_rank is None \
            else d * self.q_lora_rank + self.q_lora_rank * h * qk
        proj = (query + (d * h if self.gate else 0)
                + d * (self.kv_lora_rank + self.rope)
                + self.kv_lora_rank * h * (self.nope + self.v_dim)
                + h * self.v_dim * d)
        core = s * h * (self.nope + self.rope + self.v_dim)  # causal: half
        return batch * s * 2 * (proj + core)


class GatedAttention(Op):
    """Softmax attention with grouped key/value heads and an output gate
    (``Qwen3NextAttention`` of Hugging Face's ``modeling_qwen3_next.py``):
    (B, S, d) -> (B, S, d), causal, no biases.

    ``[q | gate] = x W_q`` per head (``num_heads x 2 head_dim``); ``k =
    x W_k``, ``v = x W_v`` (``num_kv_heads x head_dim``, ``num_kv_heads``
    dividing ``num_heads``); ``q`` and ``k`` RMS-normalised over the
    head with a zero-centred scale (``x^ (1 + w)``, ``w`` drawn at 0);
    the rotary embedding in the half-split form on the first
    ``rotary_dim`` elements of each head of q and k; causal softmax
    attention at scale ``head_dim^-1/2``, ``num_heads / num_kv_heads``
    query heads to a key/value head; ``y = (o * sigmoid(gate)) W_o``.

    The core is ``blockwise_causal_attention`` (the Pallas kernels on a
    TPU at shapes they take, reading each group's key/value head in
    place; the plain core elsewhere), and a recomputed run keeps its
    output and log-sum-exp as ``LatentAttention``'s does.  Scopes:
    ``<phase>.proj`` (projections, the two norms, the rotary embedding,
    the gate, ``W_o``) and ``<phase>.core``; ``ff.attn`` without a
    phase.
    """

    op_type = "GatedAttention"
    saved_in_recompute = CORE_SAVED
    core_field, core_forms = "attention_core", ("pallas", "plain")

    def __init__(self, name, input_tensor, num_heads: int, num_kv_heads: int,
                 head_dim: int, rotary_dim: int, rope_theta: float = 10000.0,
                 eps: float = 1e-6, kernel_initializer=None,
                 compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        assert self.num_heads % self.num_kv_heads == 0
        self.head_dim, self.rotary_dim = int(head_dim), int(rotary_dim)
        self.rope_theta, self.eps = float(rope_theta), float(eps)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        d, h, kv, hd = self.model_dim, self.num_heads, self.num_kv_heads, \
            self.head_dim
        init, zero = self.kernel_initializer, ConstantInitializer(0.0)
        return [
            ParameterSpec(self.name, "w_q", (d, h * 2 * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "w_k", (d, kv * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "w_v", (d, kv * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "q_norm", (hd,), initializer=zero),
            ParameterSpec(self.name, "k_norm", (hd,), initializer=zero),
            ParameterSpec(self.name, "w_o", (h * hd, d), initializer=init,
                          sharded_dim=0)]

    _core_dtype = LatentAttention._core_dtype   # reads compute_dtype alone

    def core_form(self) -> str:
        return core_form(self.inputs[0].shape[1], self.head_dim,
                         self.head_dim, self._core_dtype())

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        cdt = self.compute_dtype
        positions = jnp.arange(s)
        scope = self.phase or "ff.attn"
        heads_first = lambda t: t.transpose(0, 2, 1, 3)
        with jax.named_scope(scope + ".proj"):
            q_gate = matmul(x, params["w_q"], cdt).reshape(b, s, h, 2 * hd)
            q, gate = q_gate[..., :hd], q_gate[..., hd:]
            k = matmul(x, params["w_k"], cdt).reshape(b, s, kv, hd)
            v = matmul(x, params["w_v"], cdt).reshape(b, s, kv, hd)
            q = rms_norm(q, 1.0 + params["q_norm"], self.eps)
            k = rms_norm(k, 1.0 + params["k_norm"], self.eps)
            q = rope_half_split(q, positions, self.rope_theta,
                                self.rotary_dim, seq_axis=1)
            k = rope_half_split(k, positions, self.rope_theta,
                                self.rotary_dim, seq_axis=1)
            q, k, v = heads_first(q), heads_first(k), heads_first(v)
        with jax.named_scope(scope + ".core"):
            o = blockwise_causal_attention(
                q, k, v, 1.0 / math.sqrt(hd),
                compute_dtype=self._core_dtype())
        with jax.named_scope(scope + ".proj"):
            o = heads_first(o) * jax.nn.sigmoid(gate)
            out = matmul(o.reshape(b, s, h * hd), params["w_o"], cdt)
        return [out.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        s, d, h, hd = self.inputs[0].shape[1], self.model_dim, \
            self.num_heads, self.head_dim
        proj = d * hd * (2 * h + 2 * self.num_kv_heads) + h * hd * d
        return batch * s * 2 * (proj + s * h * hd)   # the core: causal half


def lagged(x, lag: int):
    """``x`` (B, S, ...) moved ``lag`` tokens later along axis 1: row
    ``t`` holds row ``t - lag``, zeros before the sequence's start."""
    if lag == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (lag, 0)
    return jnp.pad(x[:, :x.shape[1] - lag], pad)


def qk_mean(q_latent, k_latent, q_mixed, k_mixed):
    """CCA's q-k mean: ``(q, k)`` from the latent queries (B, S, H, hd)
    and keys (B, S, Hkv, hd) before the convolutions and the mixed ones
    after.  ``m_h = (q~_h + k~_g(h)) / 2`` for query head ``h`` of key
    head ``g(h) = h // (H / Hkv)``; ``q_h = q''_h + m_h``; ``k_g =
    k''_g`` + the mean of ``m_h`` over g's query heads.  With one query
    head a key head both get the paper's ``(q~ + k~) / 2``."""
    b, s, kv, hd = k_latent.shape
    group = q_latent.shape[2] // kv
    mean = (q_latent + jnp.repeat(k_latent, group, axis=2)) / 2
    return q_mixed + mean, k_mixed + jnp.mean(
        mean.reshape(b, s, kv, group, hd), axis=3)


class CompressedConvAttention(Op):
    """Compressed Convolutional Attention (CCA: Figliolia et al.,
    arXiv:2510.04476; ZAYA1's attention): (B, S, d) -> (B, S, d),
    causal, no biases.  ``docs/CCA_MOE_LM.md`` has the equations.

    ``q~ = x W_q`` (``num_heads`` heads), ``k~ = x W_k`` (``num_kv_heads``
    heads), all ``head_dim`` wide: the attention's latent.  The value
    shift: ``v = x W_v`` over ``num_kv_heads`` heads, of which the last
    half of the channels take the previous token's row (zeros at the
    first).  Two causal convolutions over tokens mix ``c = [q~ ; k~]``:
    ``c' = sum_j a_j * c_(t-j)``, depthwise (``conv0``: taps x channels),
    then ``c''_g = sum_j c'_(t-j),g B_(j,g)``, grouped by head
    (``conv1``: taps x heads x head_dim x head_dim).  The q-k mean, from
    the values before the convolutions: ``m_h = (q~_h + k~_g(h)) / 2``,
    ``q = q''_h + m_h``, ``k = k''_g + mean over g's heads of m_h``.
    Then ``q^ = q / |q|`` and ``k^ = tau_g k / |k|`` (``temperature``,
    one a key head, drawn at ``sqrt(head_dim)``), the rotary embedding
    in the half-split form on the first ``rotary_dim`` elements of each
    head, causal softmax attention at scale 1 (the temperature carries
    it) with ``num_heads / num_kv_heads`` query heads to a key/value
    head, and ``y = o W_o``.

    The core is ``blockwise_causal_attention`` (the Pallas kernels on a
    TPU at shapes they take, reading each group's key/value head in
    place; the plain core elsewhere), handed q and k in f32 with the
    normalisation and the temperature folded in, each rounded once
    there.  A recomputed run keeps the core's output and log-sum-exp as
    ``LatentAttention``'s does.  The convolutions' taps and the
    normalisation are f32; ``conv1`` is a matmul, its operands in the
    compute dtype.  Scopes: ``<phase>.proj`` (``W_q``, ``W_k``,
    ``W_v``), ``<phase>.mix`` (both convolutions, the value shift, the
    q-k mean, the normalisation with the temperature, the rotary
    embedding), ``<phase>.core``, ``<phase>.out`` (``W_o``); ``ff.attn``
    without a phase.
    """

    op_type = "CompressedConvAttention"
    saved_in_recompute = CORE_SAVED
    core_field, core_forms = "attention_core", ("pallas", "plain")

    def __init__(self, name, input_tensor, num_heads: int, num_kv_heads: int,
                 head_dim: int, conv0_taps: int, conv1_taps: int,
                 rotary_dim: int, rope_theta: float = 10000.0,
                 kernel_initializer=None, compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        assert self.num_heads % self.num_kv_heads == 0
        self.head_dim, self.rotary_dim = int(head_dim), int(rotary_dim)
        assert (self.num_kv_heads * self.head_dim) % 2 == 0
        self.conv0_taps, self.conv1_taps = int(conv0_taps), int(conv1_taps)
        self.rope_theta = float(rope_theta)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        d, h, kv, hd = self.model_dim, self.num_heads, self.num_kv_heads, \
            self.head_dim
        init = self.kernel_initializer
        # the convolutions at torch.nn.Conv1d's default: U(+-fan_in^-1/2)
        b0 = self.conv0_taps ** -0.5
        b1 = (self.conv1_taps * hd) ** -0.5
        return [
            ParameterSpec(self.name, "w_q", (d, h * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "w_k", (d, kv * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "w_v", (d, kv * hd), initializer=init,
                          sharded_dim=1),
            ParameterSpec(self.name, "conv0",
                          (self.conv0_taps, (h + kv) * hd),
                          initializer=UniformInitializer(-b0, b0)),
            ParameterSpec(self.name, "conv1",
                          (self.conv1_taps, h + kv, hd, hd),
                          initializer=UniformInitializer(-b1, b1)),
            ParameterSpec(self.name, "temperature", (kv,),
                          initializer=ConstantInitializer(math.sqrt(hd))),
            ParameterSpec(self.name, "w_o", (h * hd, d), initializer=init,
                          sharded_dim=0)]

    _core_dtype = LatentAttention._core_dtype   # reads compute_dtype alone

    def core_form(self) -> str:
        return core_form(self.inputs[0].shape[1], self.head_dim,
                         self.head_dim, self._core_dtype())

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        cdt = self.compute_dtype
        scope = self.phase or "ff.attn"
        heads_first = lambda t: t.transpose(0, 2, 1, 3)
        with jax.named_scope(scope + ".proj"):
            q0 = matmul(x, params["w_q"], cdt)             # (B, S, H hd)
            k0 = matmul(x, params["w_k"], cdt)             # (B, S, Hkv hd)
            v = matmul(x, params["w_v"], cdt)
        with jax.named_scope(scope + ".mix"):
            half = kv * hd // 2
            v = jnp.concatenate([v[..., :half], lagged(v[..., half:], 1)],
                                axis=-1).reshape(b, s, kv, hd)
            c = jnp.concatenate([q0, k0], axis=-1)
            c = sum(lagged(c, j) * params["conv0"][j]
                    for j in range(self.conv0_taps))
            # grouped by head: a (B S, hd) x (hd, hd) matmul a head and
            # tap, with the head axis leading
            c = c.reshape(b, s, h + kv, hd)
            cd = self._core_dtype()
            lead = lambda t: t.transpose(2, 0, 1, 3).reshape(h + kv, -1, hd)
            c = sum(jnp.matmul(lead(lagged(c, j)).astype(cd),
                               params["conv1"][j].astype(cd),
                               preferred_element_type=jnp.float32)
                    for j in range(self.conv1_taps))
            c = c.reshape(h + kv, b, s, hd).transpose(1, 2, 0, 3)
            q, k = qk_mean(q0.reshape(b, s, h, hd), k0.reshape(b, s, kv, hd),
                           c[:, :, :h], c[:, :, h:])
            q = l2_normalised(q)
            k = l2_normalised(k) * params["temperature"][:, None]
            q = rope_half_split(q, jnp.arange(s), self.rope_theta,
                                self.rotary_dim, seq_axis=1)
            k = rope_half_split(k, jnp.arange(s), self.rope_theta,
                                self.rotary_dim, seq_axis=1)
        with jax.named_scope(scope + ".core"):
            o = blockwise_causal_attention(
                heads_first(q), heads_first(k), heads_first(v), 1.0,
                compute_dtype=self._core_dtype())
        with jax.named_scope(scope + ".out"):
            out = matmul(heads_first(o).reshape(b, s, h * hd), params["w_o"],
                         cdt)
        return [out.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        s, d, h, kv, hd = self.inputs[0].shape[1], self.model_dim, \
            self.num_heads, self.num_kv_heads, self.head_dim
        proj = d * hd * (h + 2 * kv) + h * hd * d \
            + self.conv1_taps * (h + kv) * hd * hd
        return batch * s * 2 * (proj + s * h * hd)   # the core: causal half
