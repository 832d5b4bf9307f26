"""A Ling-3.0-style hybrid decoder language model as ordinary graph ops:
Kimi Delta Attention layers (a delta rule with a decay for every key
channel) with a head-wise gated latent-attention layer every
``layer_group_size``-th, leading dense layers, then routed-expert layers
whose sigmoid router chooses group by group, beside one shared expert.
Each mixer may hold this chip's share of the heads, each expert layer
its share of the experts, and the model a run of the published layers.

No reference analogue.  ``KdaMoeLmConfig`` takes the keys of the
published ``config.json`` (Ling-3.0-flash) under their own names;
``docs/KDA_MOE_LM.md`` has the equations.

    cfg = KdaMoeLmConfig.from_dict(json.load(open("config.json")))
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

#: published keys whose other value would switch on what is not built
#: here: ``from_dict`` refuses it by name
_ONLY = {"score_function": "sigmoid", "kda_safe_gate": True,
         "no_kda_lora": True, "use_kda_lora": False,
         "gated_attention_proj_granularity_type": "head_wise",
         "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
         "linear_silu": True, "group_norm_size": 1, "use_nGPT": False,
         "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "use_mla_nope": False}


@dataclass
class KdaMoeLmConfig:
    """The published keys, then what a config.json leaves open."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    layer_group_size: int = 6           # every 6th layer is latent attention
    first_k_dense_replace: int = 2
    intermediate_size: int = 6144
    # both mixers
    num_attention_heads: int = 32
    head_dim: int = 128                 # KDA's key and value head width
    num_kv_heads_for_linear_attn: int = 0   # 0: as many key heads as heads
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # the latent-attention layers
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    rope_theta: float = 6_000_000.0
    # the expert layers
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    expert_swiglu_limit_list: tuple = ()
    share_expert_swiglu_limit_list: tuple = ()
    rms_norm_eps: float = 1e-6
    # the chip's share: experts [first_expert_held, + experts_held) of
    # num_experts in every expert layer, heads_held of
    # num_attention_heads in every mixer (None: all), and the published
    # layers [first_layer_held, + num_hidden_layers)
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    heads_held: Optional[int] = None
    first_layer_held: int = 0
    # left open by the config files
    seq_len: int = 4096
    bias_update_speed: float = 1e-3     # DeepSeek-V3 section 4.2: gamma
    initializer_range: float = 0.02     # every matrix but the embedding
    learning_rate: float = 3e-4
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_epsilon: float = 1e-8
    # recompute each half of a decoder layer in the backward pass
    recompute: bool = True

    @classmethod
    def from_dict(cls, d: dict) -> "KdaMoeLmConfig":
        """From a config.json's dict: the keys this dataclass knows.  A
        key that asks for a variant not built here is refused by name."""
        for key, only in _ONLY.items():
            if key in d and d[key] != only:
                raise ValueError(f"{key}: {d[key]!r} is not built here "
                                 f"(apps/kda_moe_lm.py builds {only!r})")
        known = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            kept[key] = tuple(kept.get(key) or ())
        return cls(**kept)

    def published_index(self, index: int) -> int:
        """The published index of the ``index``-th layer held here."""
        return self.first_layer_held + index

    def is_latent_attention(self, index: int) -> bool:
        """Held layer ``index`` is latent attention, not KDA: published
        layer ``i`` is where ``(i + 1) % layer_group_size == 0``."""
        return (self.published_index(index) + 1) % self.layer_group_size == 0

    def is_dense(self, index: int) -> bool:
        return self.published_index(index) < self.first_k_dense_replace


def _mixer(model: FFModel, cfg: KdaMoeLmConfig, x, index: int, name: str,
           init):
    """``x + mixer(norm(x))``: head-wise gated latent attention in every
    ``layer_group_size``-th published layer, KDA in the others."""
    eps = cfg.rms_norm_eps
    if cfg.is_latent_attention(index):
        with model.scope(phase="ff.lm.mla"):
            a = model.latent_attention(
                model.rms_norm(x, eps, name=f"{name}_mla_norm"),
                cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.rope_theta, eps, init, name=f"{name}_mla",
                qk_norm=cfg.use_qk_norm, gate="head_wise",
                heads_held=cfg.heads_held)
            return model.add(x, a, name=f"{name}_mla_add")
    assert cfg.num_kv_heads_for_linear_attn in (0, cfg.num_attention_heads), \
        "KDA is built with as many key heads as heads"
    with model.scope(phase="ff.lm.kda"):
        a = model.kimi_delta_attention(
            model.rms_norm(x, eps, name=f"{name}_kda_norm"),
            cfg.num_attention_heads, cfg.head_dim, cfg.head_dim,
            cfg.short_conv_kernel_size, cfg.kda_lower_bound, eps,
            cfg.heads_held, init, name=f"{name}_kda")
        return model.add(x, a, name=f"{name}_kda_add")


def _no_swiglu_limit(cfg: KdaMoeLmConfig, index: int):
    """The SwiGLU clamp is not built: a held layer whose published entry
    switches it on is refused by name."""
    at = cfg.published_index(index)
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = getattr(cfg, key)
        if at < len(limits) and limits[at]:
            raise ValueError(
                f"{key}[{at}] = {limits[at]}: the SwiGLU clamp is not built "
                f"(apps/kda_moe_lm.py); hold layers whose entry is 0")


def _ffn(model: FFModel, cfg: KdaMoeLmConfig, x, index: int, name: str, init):
    """``x + FFN(norm(x))``: the dense SwiGLU layer in the published
    layers before ``first_k_dense_replace``, the expert layer after."""
    eps = cfg.rms_norm_eps
    if cfg.is_dense(index):
        with model.scope(phase="ff.lm.ffn"):
            f = model.gated_ffn(
                model.rms_norm(x, eps, name=f"{name}_ffn_norm"),
                cfg.intermediate_size, init, name=f"{name}_ffn")
            return model.add(x, f, name=f"{name}_ffn_add")
    _no_swiglu_limit(cfg, index)
    held = None
    if cfg.experts_held is not None:
        held = (cfg.first_expert_held, cfg.experts_held)
    shared, rest = divmod(cfg.moe_shared_expert_intermediate_size,
                          cfg.moe_intermediate_size)
    assert rest == 0, "the shared expert is whole experts wide"
    with model.scope(phase="ff.lm.moe"):
        f = model.held_experts_moe(
            model.rms_norm(x, eps, name=f"{name}_moe_norm"),
            cfg.num_experts, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, held, shared,
            cfg.routed_scaling_factor, cfg.bias_update_speed, init,
            name=f"{name}_moe", n_group=cfg.n_group,
            topk_group=cfg.topk_group)
        return model.add(x, f, name=f"{name}_moe_add")


def build(cfg: Optional[KdaMoeLmConfig] = None,
          ffconfig: Optional[FFConfig] = None) -> FFModel:
    """The graph: embedding, ``num_hidden_layers`` decoder layers (the
    published layers from ``first_layer_held``), the final norm and the
    untied head, whose logits are the model's output.  Reads
    ``batch_size`` and ``compute_dtype`` of ``ffconfig``."""
    cfg = cfg or KdaMoeLmConfig()
    model = FFModel(ffconfig or FFConfig())
    b, s, d, v = model.config.batch_size, cfg.seq_len, cfg.hidden_size, \
        cfg.vocab_size
    init = NormInitializer(stddev=cfg.initializer_range)

    ids = model.create_tensor((b, s), "int32", name="ids")
    with model.scope(phase="ff.lm.embed"):
        x = model.embedding(ids, v, d, aggr="none",
                            kernel_initializer=NormInitializer(
                                stddev=EMBEDDING_STDDEV), name="embed")
    # a layer's two halves are recomputed apart, as the DeltaNet sibling's:
    # the mixer's intermediates need not be rebuilt while the expert
    # layer's are still held
    for index in range(cfg.num_hidden_layers):
        name = f"layer_{index}"
        mixer, ffn = ((f"{name}_mixer", f"{name}_ffn_half")
                      if cfg.recompute else (None, None))
        with model.scope(recompute=mixer):
            x = _mixer(model, cfg, x, index, name, init)
        with model.scope(recompute=ffn):
            x = _ffn(model, cfg, x, index, name, init)
    with model.scope(phase="ff.lm.head"):
        model.dense(model.rms_norm(x, cfg.rms_norm_eps, name="final_norm"),
                    v, use_bias=False, kernel_initializer=init,
                    name="lm_head")
    return model
