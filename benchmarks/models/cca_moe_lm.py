"""The Compressed Convolutional Attention + MLP-routed experts language
model family (ZAYA1-8B): everything that knows both the program's model
(``dlrm_flexflow_tpu/apps/cca_moe_lm.py``) and the reference's
(``reference/cca_moe_lm_ref.py``).  ``run.py`` finds this file by the
``family`` key of a configuration file; ``docs/CCA_MOE_LM.md`` has the
family's notes.

A configuration file of this family holds the published ``config.json``
keys at its top level, as the catalog has them (``reduced`` names the
ones cut), beside the keys of the chip's share (``experts_held``,
``first_expert_held``), the block ``train`` (what the source leaves
open: optimizer, initialisation) and the block ``overrides``, empty as
run: the CPU rehearsal lays its tiny sizes there
(``tests/benchmark/tiny.cca_moe_lm.json``), and they go over the
top-level keys.  The traffic gives ``batch``, ``seq_len`` and ``ids``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import traffic as traffic_lib
from benchmarks.reference import cca_moe_lm_ref as ref

#: each number of ``check``'s report that decides ``correct``, beside its
#: limit (``reference/cca_moe_lm_ref.py`` says what each is and why)
LIMITS = ref.LIMITS

#: metric group -> the phase scopes it sums (``lib/phases.py`` has the
#: rule).  ``attn`` is CCA whole: its pre-norm, projections, mixing,
#: core, ``W_o`` and residual add.  ``ladder``: the epoch scan's own
#: instructions (``ff.ladder``, ``model.py::epoch_scan``), under the name
#: DLRM's family gives that scope, and ahead of ``model_rest``, what
#: ``ff.step.model`` holds outside every ``ff.lm.*`` scope: the sibling
#: families hold both in ``model_rest``, and ``lib/phases.py``'s merge
#: of every family's groups keys a group by the first family to name it
PHASE_GROUPS = {
    "attn": ("ff.lm.cca",),
    "moe": ("ff.lm.moe",),
    "head": ("ff.lm.head", "ff.lm.embed"),
    "dense_update": ("ff.step.dense_update",),
    "ladder": ("ff.ladder",),
    "model_rest": ("ff.step.model", "ff.step.metrics"),
}

#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}

_CCA = ("w_q", "w_k", "w_v", "conv0", "conv1", "temperature", "w_o")
_MOE = ("router_down", "router_depth", "router_hidden", "router_out",
        "w_gate", "w_up", "w_down")
_COUNTERS = ("tokens_per_expert", "skipped_tokens", "held_assignments",
             "padded_rows")


# ------------------------------------------------------- configuration
def model_config(config: dict, traffic: dict):
    """The program's ``CcaMoeLmConfig`` for a configuration file under a
    traffic mix."""
    from dlrm_flexflow_tpu.apps.cca_moe_lm import CcaMoeLmConfig

    keys = {**config, **config["train"], **config["overrides"],
            "seq_len": traffic["seq_len"]}
    return CcaMoeLmConfig.from_dict(keys)


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


def _held(cfg) -> int:
    return cfg.experts_held or cfg.num_experts


def train_flops_per_sample(config: dict, traffic: dict) -> int:
    """Forward + backward operations one sequence requires (a
    multiply-add counts 2; recomputation not counted): 6 x the matrix
    parameters a token is multiplied with (the four projections, ``W_o``,
    the grouped convolution's taps, the router's three matrices, the held
    experts at ``top_k x held / (experts + 1)``, an even share over the
    slots with the skip slot among them, and the head; the embedding
    lookup, the depthwise convolution and the norms are no matmuls),
    plus the causal core at half of S x S in every layer, forward + 2 x
    forward backward."""
    cfg = model_config(config, traffic)
    d, s, h, g, dh = (cfg.hidden_size, cfg.seq_len, cfg.num_attention_heads,
                      cfg.num_key_value_heads, cfg.head_dim)
    rh, slots = cfg.router_hidden_size, cfg.num_experts + 1
    routed = cfg.num_experts_per_tok * _held(cfg) / slots
    layer = (d * dh * (h + 2 * g) + h * dh * d
             + cfg.cca_time1 * (h + g) * dh * dh
             + d * rh + rh * rh + rh * slots
             + 3 * d * cfg.moe_intermediate_size * routed)
    core = 3 * 2 * (s * s / 2) * h * 2 * dh
    return int(cfg.num_hidden_layers * (6 * layer * s + core)
               + 6 * d * cfg.vocab_size * s)


# ------------------------------------------------------ model and data
def build(config: dict, traffic: dict, seed: int, devices):
    """``apps/cca_moe_lm.build`` -> ``compile`` (Adam, the token loss)
    -> ``init`` under one ``jit``.  Returns ``(model, state)``.

    The weights are drawn from the configuration's ``train.weights_seed``
    and not from ``seed``, which makes the data alone: a top-1 router at
    random weights sends each draw of the weights its own share of the
    tokens to the held experts, and with it its own work a step, so a
    seed would set the cell's rate as much as the program does."""
    from dlrm_flexflow_tpu.apps import cca_moe_lm as app
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
    state = jax.jit(lambda s: model.init(seed=s))(
        np.uint32(config["train"]["weights_seed"]))
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
    """``[(path in the reference's params, op, parameter)]``; the head
    reads the embedding's table and has none of its own."""
    paths = [(("embed",), "embed", "embedding"),
             (("final_norm",), "final_norm", "scale")]
    for i in range(cfg.num_hidden_layers):
        at, name = ("layers", i), f"layer_{i}"
        for kind, keys in (("cca", _CCA), ("moe", _MOE)):
            paths.append((at + (f"{kind}_norm",), f"{name}_{kind}_norm",
                          "scale"))
            paths += [(at + (kind, k), f"{name}_{kind}", k) for k in keys]
    return paths


def _moe_ops(cfg):
    return [f"layer_{i}_moe" for i in range(cfg.num_hidden_layers)]


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
            "counters": {k: [s[k] for s in moe] for k in _COUNTERS}}


def _reference_steps(snapshot: dict, tokens, hp: dict, dtype: str):
    """``ref.train_steps`` from a snapshot (host or device arrays; it is
    put on the first device and consumed there, and the snapshot gives
    up its second moments, which nothing compares)."""
    state = jax.device_put((snapshot["params"], snapshot["m"],
                            snapshot.pop("v"), snapshot["step"]),
                           jax.devices()[0])
    return ref.train_steps(state, tokens, hp, dtype)


def check(config: dict, traffic: dict, model, state, seed: int, run_steps,
          k: int):
    """Send ``k`` further seeded batches through ``run_steps(model,
    state, inputs, labels) -> (state, losses)``, the path the cell
    measures, and through the reference, both from the state the window
    left, and compare (``reference/cca_moe_lm_ref.py::compare``).

    At the published widths two states and the reference's gradients do
    not fit the chip together (3 x 7.9 GB), so the state before goes to
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
           "losses": [float(x) for x in np.ravel(losses_got)],
           "counts": {name: [np.asarray(a) - np.asarray(b0)
                             for a, b0 in zip(after["counters"][name],
                                              before["counters"][name])]
                      for name in _COUNTERS}}
    (params, m, _v, _step), losses, counts = _reference_steps(
        before, tokens, hp, config["ffconfig"]["compute_dtype"])
    del _v
    want = {"params": params, "m": m, "losses": losses, "counts": counts}
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
        (params, m, v, step), losses, counts = _reference_steps(
            snap, tokens, hp, dtype)
        per_layer = np.sum([np.stack(c) for c in counts], axis=0)
        first, held, e = (cfg.first_expert_held, hp["experts_held"],
                          cfg.num_experts)
        total = len(counts) * hp["tokens_per_step"] * cfg.num_experts_per_tok
        bn_state = {}
        for i, name in enumerate(_moe_ops(cfg)):
            here = int(per_layer[i][first:first + held].sum())
            bn_state[name] = {
                **old[name],
                "tokens_per_expert": old[name]["tokens_per_expert"]
                + jnp.asarray(per_layer[i][:e], jnp.int32),
                "skipped_tokens": old[name]["skipped_tokens"]
                + int(per_layer[i][e]),
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
    weights = _held(cfg) * 3 * d * h
    flops = 6 * rows * 3 * d * h
    row_bytes = 3 * rows * (2 * (d + h) + (h + d)) * _width(config)
    return flops, row_bytes + weights * (3 * _width(config) + 4)


def cca_core_work(config: dict, traffic: dict):
    """``(operations, bytes)`` of one CCA layer's causal core for one
    sequence, forward + backward: ``Q K^T`` and ``P V`` over the lower
    triangle at the query heads forward, 2.5 x that backward (five
    matmuls of the forward's two sizes; the forward computed again is
    not counted); q and the output at the query heads, k and v at the
    key/value heads, read forward and again backward with the output's
    gradient, their gradients written, in the compute dtype."""
    cfg = model_config(config, traffic)
    s, h, g, dh = (cfg.seq_len, cfg.num_attention_heads,
                   cfg.num_key_value_heads, cfg.head_dim)
    forward = 2 * (s * s / 2) * h * 2 * dh
    tensors = s * dh * (2 * h + 2 * g)                  # q, o; k, v
    return 3.5 * forward, 3 * tensors * _width(config)


def moe_layers(config: dict, traffic: dict) -> int:
    return model_config(config, traffic).num_hidden_layers


def cca_layers(config: dict, traffic: dict) -> int:
    return moe_layers(config, traffic)
