"""The Kimi Delta Attention + latent attention + routed-experts
language-model family (Ling-3.0-flash): everything that knows both the
program's model (``dlrm_flexflow_tpu/apps/kda_moe_lm.py``) and the
reference's (``reference/kda_moe_lm_ref.py``).  ``run.py`` finds this
file by the ``family`` key of a configuration file;
``docs/KDA_MOE_LM.md`` has the family's notes.

A configuration file of this family holds the published ``config.json``
keys at its top level, as the catalog has them (``reduced`` names the
ones cut), beside the keys of the chip's share (``experts_held``,
``first_expert_held``, ``heads_held``, ``first_layer_held``), the block
``train`` (what the source leaves open: optimizer, gamma,
initialisation) and the block ``overrides``, empty as run: the CPU rehearsal lays its tiny sizes there
(``tests/benchmark/tiny.kda_moe_lm.json``), and they go over the
top-level keys.  The traffic gives ``batch``, ``seq_len`` and ``ids``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import traffic as traffic_lib
from benchmarks.reference import kda_moe_lm_ref as ref

#: each number of ``check``'s report that decides ``correct``, beside its
#: limit (``reference/kda_moe_lm_ref.py`` says what each is and why)
LIMITS = ref.LIMITS

#: metric group -> the phase scopes it sums (``lib/phases.py`` has the
#: rule).  ``model_rest``: what ``ff.step.model`` holds outside every
#: ``ff.lm.*`` scope (the runs' own plumbing, the metrics' fold)
PHASE_GROUPS = {
    "kda": ("ff.lm.kda",),
    "attn": ("ff.lm.mla",),
    "moe": ("ff.lm.moe",),
    "ffn": ("ff.lm.ffn",),
    "head": ("ff.lm.head", "ff.lm.embed"),
    "dense_update": ("ff.step.dense_update",),
    "model_rest": ("ff.step.model", "ff.step.metrics", "ff.ladder",
                   "ff.cache"),
}

#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}

_KDA = ("w_q", "w_k", "w_v", "w_f", "w_g", "w_beta", "conv_q", "conv_k",
        "conv_v", "a_log", "dt_bias", "norm", "w_out")
_MLA = ("w_q", "w_kva", "kv_norm", "w_kvb", "q_head_norm", "k_head_norm",
        "w_gate", "w_o")
_FFN = ("w_gate", "w_up", "w_down")
_COUNTERS = ("tokens_per_expert", "held_assignments", "padded_rows")


# ------------------------------------------------------- configuration
def model_config(config: dict, traffic: dict):
    """The program's ``KdaMoeLmConfig`` for a configuration file under a
    traffic mix."""
    from dlrm_flexflow_tpu.apps.kda_moe_lm import KdaMoeLmConfig

    keys = {**config, **config["train"], **config["overrides"],
            "seq_len": traffic["seq_len"]}
    return KdaMoeLmConfig.from_dict(keys)


def hyper(cfg, traffic: dict) -> dict:
    """What the reference reads, as plain numbers."""
    hp = {k: v for k, v in dataclasses.asdict(cfg).items()
          if isinstance(v, (int, float)) and not isinstance(v, bool)}
    hp["tokens_per_step"] = traffic["batch"] * traffic["seq_len"]
    if cfg.experts_held is None:
        hp["experts_held"] = cfg.num_experts
    return hp


def _width(config: dict) -> int:
    return 2 if config["ffconfig"]["compute_dtype"] == "bfloat16" else 4


def _heads(cfg) -> int:
    return cfg.heads_held or cfg.num_attention_heads


def _mixer_params(cfg, index: int) -> int:
    """Matrix parameters a token is multiplied with in held layer
    ``index``'s mixer, at the heads held."""
    d, h = cfg.hidden_size, _heads(cfg)
    if cfg.is_latent_attention(index):
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        assert cfg.q_lora_rank is None
        return (d * h * qk + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim
                                          + cfg.v_head_dim)
                + d * h + h * cfg.v_head_dim * d)
    wide = h * cfg.head_dim
    return d * (5 * wide + h) + wide * d


def _ffn_params(cfg, index: int) -> float:
    d = cfg.hidden_size
    if cfg.is_dense(index):
        return 3 * d * cfg.intermediate_size
    held = cfg.experts_held or cfg.num_experts
    routed = cfg.num_experts_per_tok * held / cfg.num_experts
    return d * cfg.num_experts + 3 * d * (
        cfg.moe_intermediate_size * routed
        + cfg.moe_shared_expert_intermediate_size)


def train_flops_per_sample(config: dict, traffic: dict) -> int:
    """Forward + backward operations one sequence requires (a
    multiply-add counts 2; recomputation not counted): 6 x the matrix
    parameters a token is multiplied with, at the heads and experts held
    (the routed experts at ``top_k x held / published``; the embedding
    lookup, the convolutions' taps and the norms are no matmuls), plus
    the causal attention core at half of S x S in every latent-attention
    layer and the KDA recurrence (6 dk dv a token and head: decay, ``S^T
    k``, the rank-one update, ``S^T q``), forward + 2 x forward
    backward.  The chunked form's extra work (the triangular system, the
    sub-blocks' products) is not counted, as recomputed work is not."""
    cfg = model_config(config, traffic)
    d, s, h = cfg.hidden_size, cfg.seq_len, _heads(cfg)
    active = d * cfg.vocab_size
    core = 0.0
    for i in range(cfg.num_hidden_layers):
        active += _mixer_params(cfg, i) + _ffn_params(cfg, i)
        if cfg.is_latent_attention(i):
            core += 3 * 2 * (s * s / 2) * h * (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
        else:
            core += 3 * s * h * 6 * cfg.head_dim * cfg.head_dim
    return int(6 * active * s + core)


# ------------------------------------------------------ model and data
def build(config: dict, traffic: dict, seed: int, devices):
    """``apps/kda_moe_lm.build`` -> ``compile`` (Adam, the token loss)
    -> ``init(seed)`` under one ``jit``.  Returns ``(model, state)``."""
    from dlrm_flexflow_tpu.apps import kda_moe_lm as app
    from dlrm_flexflow_tpu.config import FFConfig

    fc = FFConfig(batch_size=traffic["batch"])
    for key, value in config["ffconfig"].items():
        if not hasattr(fc, key):
            raise AttributeError(f"FFConfig has no field {key!r}")
        setattr(fc, key, value)
    cfg = model_config(config, traffic)
    model = app.build(cfg, fc)
    model.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
                  metrics=(), mesh=False)
    state = jax.jit(lambda s: model.init(seed=s))(np.uint32(seed % 2 ** 32))
    return model, state


def _sequences(config: dict, traffic: dict, n: int, seed: int, stream: int):
    """``n`` sequences of ``seq_len + 1`` token ids from the traffic's
    ``ids`` distribution over the vocabulary slice: the benchmark's one
    generator, read as one table with a bag of that length."""
    cfg = model_config(config, traffic)
    shape = {"embedding_size": [cfg.vocab_size], "mlp_bot": [1],
             "embedding_bag_size": cfg.seq_len + 1}
    inputs, _ = traffic_lib.make_samples(shape, traffic["ids"], n, seed,
                                         stream=stream)
    return inputs["sparse"][:, 0, :].astype(np.int32)


def _split(tokens):
    """Token windows (..., S + 1) as the program's inputs and labels."""
    return {"ids": tokens[..., :-1]}, tokens[..., 1:, None]


def make_dataset(config: dict, traffic: dict, seed: int):
    """``(inputs, labels)`` of ``batches * batch`` sequences, unbatched."""
    return _split(_sequences(config, traffic,
                             traffic["batches"] * traffic["batch"], seed,
                             traffic_lib.DATASET_STREAM))


# ---------------------------------- the program's state, the reference's
def _paths(cfg):
    """``[(path in the reference's params, op, parameter)]``."""
    paths = [(("embed",), "embed", "embedding"),
             (("head",), "lm_head", "kernel"),
             (("final_norm",), "final_norm", "scale")]
    for i in range(cfg.num_hidden_layers):
        at, name = ("layers", i), f"layer_{i}"
        kind, keys = (("mla", _MLA) if cfg.is_latent_attention(i)
                      else ("kda", _KDA))
        paths.append((at + ("mixer_norm",), f"{name}_{kind}_norm", "scale"))
        paths += [(at + (kind, k), f"{name}_{kind}", k) for k in keys]
        if cfg.is_dense(i):
            paths.append((at + ("ffn_norm",), f"{name}_ffn_norm", "scale"))
            paths += [(at + ("ffn", k), f"{name}_ffn", k) for k in _FFN]
            continue
        paths.append((at + ("ffn_norm",), f"{name}_moe_norm", "scale"))
        paths += [(at + ("moe", k), f"{name}_moe", k)
                  for k in ("router",) + _FFN]
        paths += [(at + ("moe", "shared", k), f"{name}_moe",
                   "shared_" + k[2:]) for k in _FFN]
    return paths


def _moe_ops(cfg):
    return [f"layer_{i}_moe" for i in range(cfg.num_hidden_layers)
            if not cfg.is_dense(i)]


def to_reference(by_op: dict, cfg):
    """The program's ``{op: {parameter: array}}`` in the reference's
    layout (the same arrays, renamed)."""
    out = {"layers": [{} for _ in range(cfg.num_hidden_layers)]}
    for path, op, name in _paths(cfg):
        at = out
        for key in path[:-1]:
            at = at.setdefault(key, {}) if isinstance(at, dict) else at[key]
        at[path[-1]] = by_op[op][name]
    return out


def from_reference(tree: dict, cfg) -> dict:
    """``to_reference`` undone."""
    by_op = {}
    for path, op, name in _paths(cfg):
        at = tree
        for key in path:
            at = at[key]
        by_op.setdefault(op, {})[name] = at
    return by_op


def _snapshot(state, cfg) -> dict:
    """What the comparison and the reference need of a ``TrainState``,
    in the reference's layout."""
    moe = [state.bn_state[name] for name in _moe_ops(cfg)]
    return {"params": to_reference(state.params, cfg),
            "m": to_reference(state.opt_state["m"], cfg),
            "v": to_reference(state.opt_state["v"], cfg),
            "step": state.opt_state["step"],
            "biases": [s["bias"] for s in moe],
            "counters": {k: [s[k] for s in moe] for k in _COUNTERS}}


def _reference_steps(snapshot: dict, tokens, hp: dict, dtype: str):
    """``ref.train_steps`` from a snapshot (host or device arrays; it is
    put on the first device and consumed there, and the snapshot gives
    up its second moments, which nothing compares)."""
    dev = jax.devices()[0]
    state = jax.device_put((snapshot["params"], snapshot["m"],
                            snapshot.pop("v"), snapshot["step"]), dev)
    biases = jax.device_put(snapshot["biases"], dev)
    return ref.train_steps(state, biases, tokens, hp, dtype)


def check(config: dict, traffic: dict, model, state, seed: int, run_steps,
          k: int):
    """Send ``k`` further seeded batches through ``run_steps(model,
    state, inputs, labels) -> (state, losses)``, the path the cell
    measures, and through the reference, both from the state the window
    left, and compare (``reference/kda_moe_lm_ref.py::compare``).

    At the published widths two states and the reference's gradients do
    not fit the chip together (3 x 8.2 GB), so the state before goes to
    the host, what is compared of the state after follows it and the
    rest is dropped, only then does the reference run, from the host's
    copy, and the comparison brings one tensor at a time back beside the
    reference's result.  No state is left to return: ``(ok, report,
    None)``."""
    cfg = model_config(config, traffic)
    hp = hyper(cfg, traffic)
    b = traffic["batch"]
    tokens = _sequences(config, traffic, k * b, seed,
                        traffic_lib.CHECK_STREAM).reshape(k, b, -1)
    inputs, labels = _split(tokens)
    t0 = time.perf_counter()
    before = jax.device_get(_snapshot(state, cfg))
    t1 = time.perf_counter()
    state, losses_got = run_steps(model, state, inputs, labels)
    after = _snapshot(state, cfg)
    after.pop("v")      # nothing compares it
    del state
    after = jax.device_get(after)   # and the device's copy is dropped
    t2 = time.perf_counter()
    got = {"params": after["params"], "m": after["m"],
           "biases": after["biases"],
           "losses": [float(x) for x in np.ravel(losses_got)],
           "counts": {name: [np.asarray(a) - np.asarray(b0)
                             for a, b0 in zip(after["counters"][name],
                                              before["counters"][name])]
                      for name in _COUNTERS}}
    (params, m, _v, _step), biases, losses, counts = _reference_steps(
        before, tokens, hp, config["ffconfig"]["compute_dtype"])
    del _v
    want = {"params": params, "m": m, "biases": jax.device_get(biases),
            "losses": losses, "counts": counts}
    t3 = time.perf_counter()
    ok, report = ref.compare(before, got, want, k, hp)
    print(f"check: state before to the host {t1 - t0:.2f} s, {k} steps and "
          f"the state after to the host {t2 - t1:.2f} s, the reference "
          f"{t3 - t2:.2f} s, the comparison {time.perf_counter() - t3:.2f} s",
          flush=True)
    return ok, report, None


def control_steps(config: dict):
    """The control of the comparison: the reference put in the program's
    place, with its matmul operands rounded to ``LOWER`` of the
    configuration's ``compute_dtype``.  Returns a ``run_steps`` for
    ``check``, which has to come out as not correct (``run.py --control
    1``).  The steps' results go back into the program's state."""
    dtype = LOWER[config["ffconfig"]["compute_dtype"]]

    def run_steps(model, state, inputs, labels):
        from dlrm_flexflow_tpu.model import TrainState

        traffic = {"batch": labels.shape[1], "seq_len": labels.shape[2]}
        cfg = model_config(config, traffic)
        hp = hyper(cfg, traffic)
        tokens = np.concatenate([np.asarray(inputs["ids"]),
                                 np.asarray(labels)[..., -1:, 0]], -1)
        snap = _snapshot(state, cfg)
        extra = {k: v for k, v in state.opt_state.items()
                 if k not in ("m", "v", "step")}
        rng, old = state.rng, state.bn_state
        del state
        (params, m, v, step), biases, losses, counts = _reference_steps(
            snap, tokens, hp, dtype)
        per_layer = np.sum([np.stack(c) for c in counts], axis=0)
        first, held = cfg.first_expert_held, hp["experts_held"]
        total = len(counts) * hp["tokens_per_step"] * cfg.num_experts_per_tok
        bn_state = {}
        for i, name in enumerate(_moe_ops(cfg)):
            here = int(per_layer[i][first:first + held].sum())
            bn_state[name] = {
                **old[name], "bias": biases[i],
                "tokens_per_expert": old[name]["tokens_per_expert"]
                + jnp.asarray(per_layer[i], jnp.int32),
                "held_assignments": old[name]["held_assignments"] + here,
                "padded_rows": old[name]["padded_rows"] + (total - here)}
        opt_state = dict(extra, step=step, m=from_reference(m, cfg),
                         v=from_reference(v, cfg))
        return TrainState(from_reference(params, cfg), opt_state, bn_state,
                          rng, jnp.copy(step)), losses  # two buffers: donated

    return run_steps


# ------------------------------------------- what the roofline readers ask
def expert_matmul_work(config: dict, traffic: dict, rows: float):
    """``(operations, bytes)`` of the grouped matmuls of the held
    experts for ``rows`` assignments (gate, up and down projections),
    forward + backward: 6 x rows x the expert's parameters; each row
    read and written once per matmul in the compute dtype, and each held
    expert's weights read once forward and twice backward (the gradient
    with respect to the rows, and the weights' own gradient, written
    once in f32)."""
    cfg = model_config(config, traffic)
    d, h = cfg.hidden_size, cfg.moe_intermediate_size
    held = cfg.experts_held or cfg.num_experts
    weights = held * 3 * d * h
    flops = 6 * rows * 3 * d * h
    row_bytes = 3 * rows * (2 * (d + h) + (h + d)) * _width(config)
    return flops, row_bytes + weights * (3 * _width(config) + 4)


def attention_core_work(config: dict, traffic: dict):
    """``(operations, bytes)`` of one latent-attention layer's causal
    core for one sequence at the heads held, forward + backward: ``Q
    K^T`` and ``P V`` over the lower triangle forward, 2.5 x that
    backward (five matmuls of the forward's two sizes); q, k, v read and
    the output written forward, all four and the output's gradient read
    and three gradients written backward, in the compute dtype."""
    cfg = model_config(config, traffic)
    s, h = cfg.seq_len, _heads(cfg)
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    forward = 2 * (s * s / 2) * h * (qk + cfg.v_head_dim)
    tensors = s * h * (2 * qk + 2 * cfg.v_head_dim)     # q, k, v, o
    return 3.5 * forward, 3 * tensors * _width(config)


def kda_core_work(config: dict, traffic: dict):
    """``(operations, bytes)`` of one KDA layer's recurrence for one
    sequence at the heads held, forward + backward: 6 dk dv a token and
    head forward (decay, ``S^T k``, the rank-one update, ``S^T q``: a
    multiply-add counting 2), twice that backward; q, k, v at the width
    the program holds them (the compute dtype), the output, the dk-wide
    log-decay g and beta (f32) moved once forward, and they and their
    gradients once more each backward.  The same work whatever
    implements it: the chunked form's triangular systems and sub-block
    products are not counted."""
    cfg = model_config(config, traffic)
    s, h, hd = cfg.seq_len, _heads(cfg), cfg.head_dim
    flops = 3 * s * h * 6 * hd * hd
    moved = s * h * (3 * hd * _width(config)     # q, k, v
                     + hd * 4 + hd * 4 + 4)      # o; g, beta
    return flops, 3 * moved


def moe_layers(config: dict, traffic: dict) -> int:
    return len(_moe_ops(model_config(config, traffic)))


def attention_layers(config: dict, traffic: dict) -> int:
    cfg = model_config(config, traffic)
    return sum(cfg.is_latent_attention(i)
               for i in range(cfg.num_hidden_layers))


def kda_layers(config: dict, traffic: dict) -> int:
    cfg = model_config(config, traffic)
    return cfg.num_hidden_layers - attention_layers(config, traffic)
