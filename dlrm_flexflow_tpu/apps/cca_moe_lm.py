"""A ZAYA1-style decoder language model as ordinary graph ops: every
layer a Compressed Convolutional Attention sublayer (attention in a
compressed latent, two causal convolutions on the queries and keys, the
q-k mean, a value shift, L2 normalisation with a learned temperature)
and an expert sublayer whose MLP router carries a state from layer to
layer (exponential depth averaging) and may send a token to a skip slot
that no expert holds; top-1, no shared expert; one matrix serves as the
input embedding and the output head.  Each expert layer may hold this
chip's share of the experts, the model the first of the published
layers.

No reference analogue.  ``CcaMoeLmConfig`` takes the keys of the
published ``config.json`` (ZAYA1-8B) under their own names;
``docs/CCA_MOE_LM.md`` has the equations and every assumption.

    cfg = CcaMoeLmConfig.from_dict(json.load(open("config.json")))
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
from .lm_common import optimizer, token_loss  # noqa: F401

#: published keys whose other value would switch on what is not built
#: here: ``from_dict`` refuses it by name
_ONLY = {"attention_bias": False, "lm_head_bias": False,
         "tie_word_embeddings": True, "hidden_act": "silu",
         "sliding_window": None}


@dataclass
class CcaMoeLmConfig:
    """The published keys, then what a config.json leaves open."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = ()             # () reads as all "hybrid"
    # the CCA sublayers
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                  # taps of the depthwise convolution
    cca_time1: int = 2                  # taps of the grouped convolution
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5_000_000.0     # rope_parameters.hybrid
    # the expert sublayers
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    # the chip's share: experts [first_expert_held, + experts_held) of
    # num_experts in every expert layer (None: all of them); the layers
    # are the published ones from 0, where the router state starts
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    # left open by the config files
    seq_len: int = 4096
    initializer_range: float = 0.02     # every matrix, the tied one too
    learning_rate: float = 3e-4
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_epsilon: float = 1e-8
    # recompute each half of a decoder layer in the backward pass
    recompute: bool = True

    @classmethod
    def from_dict(cls, d: dict) -> "CcaMoeLmConfig":
        """From a config.json's dict: the keys this dataclass knows, and
        the rotary base of ``rope_parameters["hybrid"]``.  A key that asks
        for a variant not built here is refused by name."""
        for key, only in _ONLY.items():
            if key in d and d[key] != only:
                raise ValueError(f"{key}: {d[key]!r} is not built here "
                                 f"(apps/cca_moe_lm.py builds {only!r})")
        known = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        hybrid = (d.get("rope_parameters") or {}).get("hybrid")
        if hybrid:
            kept["rope_theta"] = hybrid["rope_theta"]
            kept["partial_rotary_factor"] = hybrid["partial_rotary_factor"]
        kept["layer_types"] = tuple(kept.get("layer_types") or ())
        return cls(**kept)

    def check_layers(self):
        """Every held layer is a "hybrid" one (CCA and experts): the
        sliding-window layers of the family's larger model are not
        built."""
        for at, kind in enumerate(self.layer_types[:self.num_hidden_layers]):
            if kind != "hybrid":
                raise ValueError(f"layer_types[{at}] = {kind!r} is not "
                                 f"built here (apps/cca_moe_lm.py builds "
                                 f"hybrid)")


def build(cfg: Optional[CcaMoeLmConfig] = None,
          ffconfig: Optional[FFConfig] = None) -> FFModel:
    """The graph: the embedding, ``num_hidden_layers`` decoder layers
    (the published layers from 0), the final norm and
    the head over the embedding's own table, whose logits are the
    model's output.  Reads ``batch_size`` and ``compute_dtype`` of
    ``ffconfig``."""
    cfg = cfg or CcaMoeLmConfig()
    cfg.check_layers()
    model = FFModel(ffconfig or FFConfig())
    b, s, d, v = model.config.batch_size, cfg.seq_len, cfg.hidden_size, \
        cfg.vocab_size
    eps = cfg.rms_norm_eps
    init = NormInitializer(stddev=cfg.initializer_range)
    held = None
    if cfg.experts_held is not None:
        held = (cfg.first_expert_held, cfg.experts_held)

    ids = model.create_tensor((b, s), "int32", name="ids")
    with model.scope(phase="ff.lm.embed"):
        x = model.embedding(ids, v, d, aggr="none",
                            kernel_initializer=init, name="embed")
    state = None    # published layer 0 has no router state before it
    for index in range(cfg.num_hidden_layers):
        name = f"layer_{index}"
        mixer, ffn = ((f"{name}_mixer", f"{name}_ffn_half")
                      if cfg.recompute else (None, None))
        with model.scope(recompute=mixer):
            with model.scope(phase="ff.lm.cca"):
                a = model.compressed_conv_attention(
                    model.rms_norm(x, eps, name=f"{name}_cca_norm"),
                    cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim, cfg.cca_time0, cfg.cca_time1,
                    int(cfg.head_dim * cfg.partial_rotary_factor),
                    cfg.rope_theta, init, name=f"{name}_cca")
                with model.scope(phase="ff.lm.cca.out"):
                    x = model.add(x, a, name=f"{name}_cca_add")
        with model.scope(recompute=ffn):
            with model.scope(phase="ff.lm.moe"):
                f, state = model.held_experts_moe(
                    model.rms_norm(x, eps, name=f"{name}_moe_norm"),
                    cfg.num_experts, cfg.moe_intermediate_size,
                    cfg.num_experts_per_tok, held, kernel_initializer=init,
                    name=f"{name}_moe", router="mlp",
                    router_hidden=cfg.router_hidden_size,
                    router_state=state)
                x = model.add(x, f, name=f"{name}_moe_add")
    with model.scope(phase="ff.lm.head"):
        model.tie(model.embedding_head(
            model.rms_norm(x, eps, name="final_norm"), v, name="lm_head"),
            "embed")
    return model
