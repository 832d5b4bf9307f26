"""A DeepSeek-style decoder language model as ordinary graph ops:
multi-head latent attention, a leading dense layer, routed-expert layers
that hold this chip's share of the experts, and a multi-token-prediction
module over the model's own embedding and head.

No reference analogue (the reference's one sequence model is NMT's
LSTM).  ``MlaMoeLmConfig`` takes the keys of the published
``config.json`` files of this family (DeepSeek-V2/V3, JoyAI-LLM-Flash)
under their own names; ``docs/MLA_MOE_LM.md`` has the equations.

    cfg = MlaMoeLmConfig.from_dict(json.load(open("config.json")))
    model = build(cfg, FFConfig(batch_size=1, compute_dtype="bfloat16"))
    model.compile(optimizer=optimizer(cfg), loss_type=token_loss, metrics=())
    state = model.init(seed=0)
    state, mets = model.train_epochs(state, *model.place_dataset(inputs,
                                                                 labels), 1)

Inputs per sample: ``ids`` (S,) the tokens, ``next_ids`` (S,) the
tokens one to the right (what the MTP module embeds) and ``mtp_labels``
(S, 1) the tokens two to the right; labels (S, 1) are the tokens one to
the right.
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
class MlaMoeLmConfig:
    """The published keys, then what a config.json leaves open."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32_000_000.0
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1
    # the chip's share of an expert-parallel layer: it holds experts
    # [first_expert_held, first_expert_held + experts_held) of
    # n_routed_experts in every expert layer (None: all of them)
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    # left open by the config files
    seq_len: int = 4096
    mtp_loss_weight: float = 0.3        # DeepSeek-V3 section 4.2: lambda
    bias_update_speed: float = 1e-3     # DeepSeek-V3 section 4.2: gamma
    initializer_range: float = 0.02     # every matrix but the embedding
    learning_rate: float = 3e-4
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_epsilon: float = 1e-8
    # recompute each decoder layer in the backward pass
    recompute: bool = True

    @classmethod
    def from_dict(cls, d: dict) -> "MlaMoeLmConfig":
        """From a config.json's dict: the keys this dataclass knows."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def _decoder_layer(model: FFModel, cfg: MlaMoeLmConfig, x, index: int,
                   name: str, init):
    """``x + MLA(norm(x))``, then ``x + FFN(norm(x))``: the dense gated
    FFN in the first ``first_k_dense_replace`` layers, the expert layer
    after them."""
    eps = cfg.rms_norm_eps
    with model.scope(phase="ff.lm.mla"):
        a = model.latent_attention(
            model.rms_norm(x, eps, name=f"{name}_attn_norm"),
            cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rope_theta, eps, init, name=f"{name}_mla")
        x = model.add(x, a, name=f"{name}_attn_add")
    if index < cfg.first_k_dense_replace:
        with model.scope(phase="ff.lm.ffn"):
            f = model.gated_ffn(
                model.rms_norm(x, eps, name=f"{name}_ffn_norm"),
                cfg.intermediate_size, init, name=f"{name}_ffn")
            return model.add(x, f, name=f"{name}_ffn_add")
    held = None
    if cfg.experts_held is not None:
        held = (cfg.first_expert_held, cfg.experts_held)
    with model.scope(phase="ff.lm.moe"):
        f = model.held_experts_moe(
            model.rms_norm(x, eps, name=f"{name}_moe_norm"),
            cfg.n_routed_experts, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, held, cfg.n_shared_experts,
            cfg.routed_scaling_factor, cfg.bias_update_speed, init,
            name=f"{name}_moe")
        return model.add(x, f, name=f"{name}_moe_add")


def build(cfg: Optional[MlaMoeLmConfig] = None,
          ffconfig: Optional[FFConfig] = None) -> FFModel:
    """The graph: embedding, ``num_hidden_layers`` decoder layers, the
    MTP modules (DeepSeek-V3 section 2.2: ``[norm(h) ; norm(Emb(t+1))]
    W``, one decoder layer, a final norm of its own, the model's own
    embedding and head), and last the final norm and head, whose logits
    are the model's output.  Reads ``batch_size`` and ``compute_dtype``
    of ``ffconfig``."""
    cfg = cfg or MlaMoeLmConfig()
    model = FFModel(ffconfig or FFConfig())
    b, s, d, v = model.config.batch_size, cfg.seq_len, cfg.hidden_size, \
        cfg.vocab_size
    init = NormInitializer(stddev=cfg.initializer_range)
    embed_init = NormInitializer(stddev=EMBEDDING_STDDEV)
    eps = cfg.rms_norm_eps

    ids = model.create_tensor((b, s), "int32", name="ids")
    with model.scope(phase="ff.lm.embed"):
        x = model.embedding(ids, v, d, aggr="none",
                            kernel_initializer=embed_init, name="embed")
    for index in range(cfg.num_hidden_layers):
        name = f"layer_{index}"
        with model.scope(recompute=name if cfg.recompute else None):
            x = _decoder_layer(model, cfg, x, index, name, init)

    trunk = x  # module k reads module k-1's output, the first the trunk's
    for k in range(cfg.num_nextn_predict_layers):
        name = f"mtp_{k}"
        ahead = model.create_tensor((b, s), "int32",
                                    name="next_ids" if k == 0
                                    else f"next_ids_{k}")
        targets = model.create_tensor((b, s, 1), "int32",
                                      name="mtp_labels" if k == 0
                                      else f"mtp_labels_{k}")
        with model.scope(recompute=name if cfg.recompute else None):
            with model.scope(phase="ff.lm.mtp"):
                e = model.tie(model.embedding(
                    ahead, v, d, aggr="none", name=f"{name}_embed"), "embed")
                joined = model.concat(
                    [model.rms_norm(trunk, eps, name=f"{name}_hnorm"),
                     model.rms_norm(e, eps, name=f"{name}_enorm")],
                    axis=2, name=f"{name}_concat")
                m = model.dense(joined, d, use_bias=False,
                                kernel_initializer=init, name=f"{name}_proj")
            trunk = _decoder_layer(model, cfg, m, cfg.num_hidden_layers + k,
                                   name, init)
        with model.scope(phase="ff.lm.head"):
            mtp_logits = model.tie(model.dense(
                model.rms_norm(trunk, eps, name=f"{name}_final_norm"), v,
                use_bias=False, kernel_initializer=init,
                name=f"{name}_head"), "lm_head")
        model.add_aux_loss(mtp_logits, targets, cfg.mtp_loss_weight)

    with model.scope(phase="ff.lm.head"):
        model.dense(model.rms_norm(x, eps, name="final_norm"), v,
                    use_bias=False, kernel_initializer=init, name="lm_head")
    return model
