"""A Qwen3-Next-style hybrid decoder language model as ordinary graph
ops: Gated DeltaNet layers with a gated full-attention layer every
``full_attention_interval``-th, each followed by a routed-expert layer
that holds this chip's share of the experts beside a gated shared
expert.

No reference analogue.  ``GdnMoeLmConfig`` takes the keys of the
published ``config.json`` (Qwen3-Next-80B-A3B) under their own names;
``docs/GDN_MOE_LM.md`` has the equations.

    cfg = GdnMoeLmConfig.from_dict(json.load(open("config.json")))
    model = build(cfg, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    model.compile(optimizer=optimizer(cfg), loss_type=token_loss, metrics=())
    state = model.init(seed=0)
    state, mets = model.train_epochs(state, *model.place_dataset(inputs,
                                                                 labels), 1)

Inputs per sample: ``ids`` (S,) the tokens; labels (S, 1) are the tokens
one to the right.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

from ..config import FFConfig
from ..initializers import NormInitializer
from ..model import FFModel
from .lm_common import EMBEDDING_STDDEV, optimizer, token_loss  # noqa: F401


@dataclass
class GdnMoeLmConfig:
    """The published keys, then what a config.json leaves open."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # the gated full-attention layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    # the Gated DeltaNet layers
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # the expert layers
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    # the chip's share of an expert-parallel layer: it holds experts
    # [first_expert_held, first_expert_held + experts_held) of
    # num_experts in every layer (None: all of them)
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    # left open by the config files
    seq_len: int = 4096
    initializer_range: float = 0.02     # every matrix but the embedding
    learning_rate: float = 3e-4
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_epsilon: float = 1e-8
    # recompute each decoder layer in the backward pass
    recompute: bool = True

    @classmethod
    def from_dict(cls, d: dict) -> "GdnMoeLmConfig":
        """From a config.json's dict: the keys this dataclass knows."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def is_full_attention(self, index: int) -> bool:
        return (index + 1) % self.full_attention_interval == 0


def _mixer(model: FFModel, cfg: GdnMoeLmConfig, x, index: int, name: str,
           init):
    """``x + mixer(norm(x))``: gated full attention in every
    ``full_attention_interval``-th layer, Gated DeltaNet in the others;
    the norm zero-centred."""
    eps = cfg.rms_norm_eps
    if cfg.is_full_attention(index):
        with model.scope(phase="ff.lm.attn"):
            a = model.gated_attention(
                model.rms_norm(x, eps, name=f"{name}_attn_norm",
                               zero_centred=True),
                cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, int(cfg.head_dim * cfg.partial_rotary_factor),
                cfg.rope_theta, eps, init, name=f"{name}_attn")
            return model.add(x, a, name=f"{name}_attn_add")
    with model.scope(phase="ff.lm.gdn"):
        a = model.gated_delta_net(
            model.rms_norm(x, eps, name=f"{name}_gdn_norm",
                           zero_centred=True),
            cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, eps, init, name=f"{name}_gdn")
        return model.add(x, a, name=f"{name}_gdn_add")


def _experts(model: FFModel, cfg: GdnMoeLmConfig, x, name: str, init):
    """``x + experts(norm(x))``: softmax routing over all experts, the
    held ones computed, beside the gated shared expert."""
    held = None
    if cfg.experts_held is not None:
        held = (cfg.first_expert_held, cfg.experts_held)
    assert cfg.shared_expert_intermediate_size == cfg.moe_intermediate_size, \
        "the shared expert is one expert's width"
    with model.scope(phase="ff.lm.moe"):
        f = model.held_experts_moe(
            model.rms_norm(x, cfg.rms_norm_eps, name=f"{name}_moe_norm",
                           zero_centred=True),
            cfg.num_experts, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, held, num_shared=1,
            kernel_initializer=init, name=f"{name}_moe",
            score_func="softmax", shared_gated=True)
        return model.add(x, f, name=f"{name}_moe_add")


def build(cfg: Optional[GdnMoeLmConfig] = None,
          ffconfig: Optional[FFConfig] = None) -> FFModel:
    """The graph: embedding, ``num_hidden_layers`` decoder layers, the
    final norm and the untied head, whose logits are the model's output.
    Reads ``batch_size`` and ``compute_dtype`` of ``ffconfig``."""
    cfg = cfg or GdnMoeLmConfig()
    model = FFModel(ffconfig or FFConfig())
    b, s, d, v = model.config.batch_size, cfg.seq_len, cfg.hidden_size, \
        cfg.vocab_size
    init = NormInitializer(stddev=cfg.initializer_range)

    ids = model.create_tensor((b, s), "int32", name="ids")
    with model.scope(phase="ff.lm.embed"):
        x = model.embedding(ids, v, d, aggr="none",
                            kernel_initializer=NormInitializer(
                                stddev=EMBEDDING_STDDEV), name="embed")
    # a layer's two halves are recomputed apart: at 16,384 tokens one
    # half's intermediates are 2-3 GB, and the mixer's need not be
    # rebuilt while the expert layer's are still held
    for index in range(cfg.num_hidden_layers):
        name = f"layer_{index}"
        mixer, experts = ((f"{name}_mixer", f"{name}_experts")
                          if cfg.recompute else (None, None))
        with model.scope(recompute=mixer):
            x = _mixer(model, cfg, x, index, name, init)
        with model.scope(recompute=experts):
            x = _experts(model, cfg, x, name, init)
    with model.scope(phase="ff.lm.head"):
        model.dense(model.rms_norm(x, cfg.rms_norm_eps, name="final_norm",
                                   zero_centred=True), v,
                    use_bias=False, kernel_initializer=init, name="lm_head")
    return model
