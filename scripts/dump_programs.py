"""The three training programs of a few tiny models, as text that two
checkouts can be compared by.

For every case below: the optimized HLO of ``train_step``, ``train_epoch``
and ``train_epochs`` (two epochs) with ``metadata={...}`` and the
stack-frame tables taken out, and ``profiling.hlo_phases`` of the
unstripped text (``{instruction: phase}``, what the benchmark's phase
readers join a trace with).  A refactor that is to leave the programs
as they are shows it so, here on the CPU:

    python scripts/dump_programs.py /tmp/a              # this checkout
    (cd <other checkout> && python scripts/dump_programs.py /tmp/b)
    diff -r /tmp/a /tmp/b && echo SAME

The script imports the package of the checkout it lies in.  The cases:
the tiny DLRM of ``tests/test_region_cache.py`` (packed storage; SGD and
lazy Adam; regions on and off; the auto ladder and the explicit
two-level ``"16,8"``), the same model on logical storage with the
view-row transport on and off, with the row cache off, a dense-only
toy, the tiny language model of ``tests/test_mla_moe_lm.py`` and (PR 35)
the tiny hybrid one of ``tests/test_gdn_moe_lm.py``.
"""

import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import dlrm_flexflow_tpu as ff  # noqa: E402
from dlrm_flexflow_tpu import profiling  # noqa: E402

ROWS = 16384

_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_FRAMES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                     r"\n(?:\d+ .*\n)*\n?", re.M)


def strip(text: str) -> str:
    return _FRAMES.sub("", _METADATA.sub("", text))


def dlrm(opt="sgd", nb=32, inner=2, **fc_kw):
    from dlrm_flexflow_tpu.apps.dlrm import DLRMConfig, build_dlrm
    cfg = DLRMConfig(sparse_feature_size=8, embedding_size=[ROWS] * 4,
                     embedding_bag_size=2, mlp_bot=[4, 16, 8],
                     mlp_top=[8 * 4 + 8, 16, 1])
    fc_kw = {"packed_tables": "on", "epoch_row_cache": "on",
             "epoch_cache_inner": inner, **fc_kw}
    m = build_dlrm(cfg, ff.FFConfig(batch_size=8, **fc_kw))
    o = (ff.AdamOptimizer(lr=0.05, lazy_embeddings=True) if opt == "adam"
         else ff.SGDOptimizer(lr=0.05))
    m.compile(optimizer=o, loss_type="mean_squared_error", metrics=(),
              mesh=False)
    rng = np.random.default_rng(7)
    inputs = {"dense": jnp.asarray(rng.standard_normal(
        (nb, 8, 4)).astype(np.float32)),
        "sparse": jnp.asarray(rng.integers(0, ROWS, size=(nb, 8, 4, 2)),
                              jnp.int32)}
    labels = jnp.asarray(rng.integers(0, 2, size=(nb, 8, 1)), jnp.float32)
    return m, inputs, labels


def toy():
    m = ff.FFModel(ff.FFConfig(batch_size=8))
    x = m.create_tensor((8, 4), name="x")
    m.dense(m.dense(x, 16, activation="relu"), 1)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
              loss_type="mean_squared_error", metrics=(), mesh=False)
    rng = np.random.default_rng(7)
    return (m, {"x": jnp.asarray(rng.standard_normal((4, 8, 4)),
                                 jnp.float32)},
            jnp.asarray(rng.standard_normal((4, 8, 1)), jnp.float32))


def lm():
    from benchmarks.models import mla_moe_lm as family
    from dlrm_flexflow_tpu.apps import mla_moe_lm as app
    from dlrm_flexflow_tpu.ops import attention
    attention.ATTENTION_BLOCK = 8
    cfg = app.MlaMoeLmConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=16, experts_held=4, num_experts_per_tok=4,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6, seq_len=32)
    m = app.build(cfg, ff.FFConfig(batch_size=2))
    m.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
              metrics=(), mesh=False)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 2, cfg.seq_len + 2)).astype(np.int32)
    inputs, labels = family._split(tokens)
    return (m, {k: jnp.asarray(v) for k, v in inputs.items()},
            jnp.asarray(labels))


def gdn_lm():
    from benchmarks.models import gdn_moe_lm as family
    from dlrm_flexflow_tpu.apps import gdn_moe_lm as app
    from dlrm_flexflow_tpu.ops import attention, deltanet
    attention.ATTENTION_BLOCK = deltanet.CHUNK = 8
    cfg = app.GdnMoeLmConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=6, num_experts=16,
        experts_held=4, num_experts_per_tok=4, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, seq_len=32)
    m = app.build(cfg, ff.FFConfig(batch_size=2))
    m.compile(optimizer=app.optimizer(cfg), loss_type=app.token_loss,
              metrics=(), mesh=False)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 2, cfg.seq_len + 1)).astype(np.int32)
    inputs, labels = family._split(tokens)
    return (m, {k: jnp.asarray(v) for k, v in inputs.items()},
            jnp.asarray(labels))


CASES = {
    **{f"dlrm.{opt}.regions-{reg}.levels-{lv or 'auto'}":
       (lambda opt=opt, reg=reg, lv=lv: dlrm(
           opt, epoch_cache_regions=reg,
           **({"epoch_cache_levels": lv, "epoch_cache_inner": 8}
              if lv else {})))
       for opt in ("sgd", "adam") for reg in ("on", "off")
       for lv in (None, "16,8")},
    "dlrm.sgd.logical.view-on": lambda: dlrm(
        packed_tables="off", epoch_cache_view="on"),
    "dlrm.adam.logical.view-off": lambda: dlrm(
        "adam", packed_tables="off", epoch_cache_view="off"),
    "dlrm.sgd.levels-off": lambda: dlrm(epoch_cache_levels="off"),
    "dlrm.sgd.geometric-mid": lambda: dlrm(nb=36, inner=2,
                                           epoch_cache_regions="off"),
    "dlrm.sgd.cache-off": lambda: dlrm(epoch_row_cache="off"),
    "toy.dense": toy,
    "lm.tiny": lm,
    "gdn_lm.tiny": gdn_lm,
}


def dump(outdir: str, only=()) -> None:
    os.makedirs(outdir, exist_ok=True)
    for case, build in CASES.items():
        if only and not any(s in case for s in only):
            continue
        m, inputs, labels = build()
        state = m.init(seed=0)
        one = ({k: v[0] for k, v in inputs.items()}, labels[0])
        programs = {
            "train_step": m._train_step.lower(state, *one),
            "train_epoch": m._train_epoch.lower(state, inputs, labels),
            "train_epochs": m._train_epochs.lower(state, inputs, labels, 2),
        }
        for name, lowered in programs.items():
            text = lowered.compile().as_text()
            with open(os.path.join(outdir, f"{case}.{name}.hlo"), "w") as f:
                f.write(strip(text))
            with open(os.path.join(outdir, f"{case}.{name}.phases.json"),
                      "w") as f:
                json.dump(profiling.hlo_phases(text), f, indent=0,
                          sort_keys=True)
        print(case, flush=True)


if __name__ == "__main__":
    dump(sys.argv[1], sys.argv[2:])
