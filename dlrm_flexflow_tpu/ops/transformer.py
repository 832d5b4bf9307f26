"""The small pieces of a pre-norm decoder layer: RMSNorm (plain and
zero-centred), the rotary embedding (interleaved pairs, or the half-split
form on the leading part of a head), and the gated (SwiGLU) feed-forward.

No reference analogue (the reference has no sequence model past NMT's
LSTM).  The residual stream and every normalisation stay float32; the
matmuls go through ``base.matmul`` (bf16 operands with f32 accumulation
when the op's ``compute_dtype`` says so, f32 master weights).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..initializers import ConstantInitializer, DEFAULT_KERNEL_INIT
from ..tensor import ParameterSpec
from .base import Op, matmul


def rms_norm(x, scale, eps: float):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in f32."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + eps)
    return xf * inv * scale


def rope_interleaved(x, positions, theta: float, seq_axis: int = -2):
    """Rotary embedding on interleaved pairs: elements ``(2i, 2i+1)`` of
    the last axis turn by ``positions * theta^(-2i/d)``.  ``positions``
    (S,) runs along ``seq_axis`` of ``x``; f32 out."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (S, d/2)
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = angles.shape[0], d // 2
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape)


def rope_half_split(x, positions, theta: float, rotary_dim: int,
                    seq_axis: int = -2):
    """Rotary embedding in the half-split form (``rotate_half``) on the
    first ``rotary_dim`` elements of the last axis, the rest untouched:
    element ``i < rotary_dim / 2`` pairs with ``i + rotary_dim / 2`` and
    both turn by ``positions * theta^(-2i / rotary_dim)``.  ``positions``
    (S,) runs along ``seq_axis`` of ``x``; f32 out."""
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                      / rotary_dim)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (S, half)
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = angles.shape[0], half
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    xf = x.astype(jnp.float32)
    lo, hi = xf[..., :half], xf[..., half:rotary_dim]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin,
                            xf[..., rotary_dim:]], axis=-1)


def swiglu(x, w_gate, w_up, w_down, compute_dtype=None):
    """``(silu(x W_g) * (x W_u)) W_d``; the product is taken in f32."""
    gate = matmul(x, w_gate, compute_dtype)
    up = matmul(x, w_up, compute_dtype)
    return matmul(jax.nn.silu(gate) * up, w_down, compute_dtype)


class RMSNorm(Op):
    """Root-mean-square normalisation over the last axis with a learned
    scale (initialised to 1), computed and emitted in float32.
    ``zero_centred``: the scale is ``1 + w`` with ``w`` initialised to 0
    (Qwen3-Next's form)."""

    op_type = "RMSNorm"

    def __init__(self, name, input_tensor, eps: float = 1e-6,
                 zero_centred: bool = False):
        super().__init__(name, [input_tensor])
        self.eps = float(eps)
        self.zero_centred = bool(zero_centred)
        self.dim = input_tensor.shape[-1]
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        start = 0.0 if self.zero_centred else 1.0
        return [ParameterSpec(self.name, "scale", (self.dim,),
                              initializer=ConstantInitializer(start))]

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        scale = params["scale"]
        if self.zero_centred:
            scale = 1.0 + scale
        return [rms_norm(x, scale, self.eps).astype(self.outputs[0].dtype)]

    def flops(self, batch):
        return 4 * self.inputs[0].numel()


class GatedFFN(Op):
    """SwiGLU feed-forward ``d -> hidden -> d`` without biases."""

    op_type = "GatedFFN"

    def __init__(self, name, input_tensor, hidden_dim: int,
                 kernel_initializer=None, compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.hidden_dim = int(hidden_dim)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_specs(self):
        d, h = self.model_dim, self.hidden_dim
        init = self.kernel_initializer
        return [ParameterSpec(self.name, "w_gate", (d, h), initializer=init,
                              sharded_dim=1),
                ParameterSpec(self.name, "w_up", (d, h), initializer=init,
                              sharded_dim=1),
                ParameterSpec(self.name, "w_down", (h, d), initializer=init,
                              sharded_dim=0)]

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        y = swiglu(x, params["w_gate"], params["w_up"], params["w_down"],
                   self.compute_dtype)
        return [y.astype(self.outputs[0].dtype)]

    def flops(self, batch):
        rows = self.inputs[0].numel() // self.model_dim
        return 6 * rows * self.model_dim * self.hidden_dim


class EmbeddingHead(Op):
    """Logits over a vocabulary from a table laid out as an embedding's:
    ``x E^T``, ``E`` (V, d) named ``embedding``, so that ``FFModel.tie``
    makes the op read an ``Embedding``'s table in place of its own (one
    matrix as input embedding and output head).  No bias; the operands
    in the compute dtype, f32 out."""

    op_type = "EmbeddingHead"

    def __init__(self, name, input_tensor, vocab_size: int,
                 kernel_initializer=None, compute_dtype=None):
        super().__init__(name, [input_tensor])
        self.model_dim = input_tensor.shape[-1]
        self.vocab_size = int(vocab_size)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT
        self.compute_dtype = compute_dtype
        self.outputs = [self._make_output(
            input_tensor.shape[:-1] + (self.vocab_size,), jnp.float32)]

    def param_specs(self):
        return [ParameterSpec(self.name, "embedding",
                              (self.vocab_size, self.model_dim),
                              initializer=self.kernel_initializer,
                              sharded_dim=1)]

    def forward(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        return [matmul(x, params["embedding"].T, self.compute_dtype)]

    def flops(self, batch):
        rows = self.inputs[0].numel() // self.model_dim
        return 2 * rows * self.model_dim * self.vocab_size
