"""On the chip: the two kernels of the language-model family at the
shapes of ``joyai-flash-ep16.pretrain-8k``, each beside its rival.

    chiprun -- python scripts/ab_lm_kernels.py [attn[:row,row]] [core] [gmm] [rows] [slabs] [gdn[:row,row]] [gdncore] [gqa[:row,row]] [kda[:row,row]] [kdacore]

attn: the causal attention core (32 heads, 8,192 tokens, query/key
width 192, value width 128, bf16), forward and forward + backward, one
row a form tried, its block sizes in the row's name (``attn:own,plain``
runs the rows whose names hold one of the words): the plain blockwise
core of ``ops/attention.py``; the repo's own Pallas kernels
(``ops/pallas_attention.py``, what the program runs on a TPU) at three
blocks; JAX's Pallas splash attention unfused and with its fused
backward, square and rectangular blocks, ``SEQ_MINOR`` keys and values.
Every form takes the operands it multiplies, rounded once (the plain
core the query, the others the query times the scale, folded in f32);
``program's entry`` is ``blockwise_causal_attention`` as
``LatentAttention`` calls it, f32 query in, the fold and the casts
timed with it.  Each row's largest |o, dq, dk, dv| differences from an
f32 ``sdpa(..., causal=True)`` on its own rounded operands, over 4 of
the 32 heads, dq in the unscaled query's units.
PR 31, v5e, forward + backward: blockwise 27.7 ms, splash (unfused,
square 512) 32.1; other blocks, set through ``ATTENTION_BLOCK``: 256
43.0, 1,024 56.3; splash at 1,024 29.4: the plain core stayed.
PR 34, v5e, forward / forward + backward ms: plain 512 8.78 / 27.68;
OWN KERNELS 512 **6.26 / 20.55** (they ship), 256 10.71 / 26.39, 1,024
6.44 / 20.61; the entry with the fold and casts 7.25 / 22.48; splash
unfused 512 8.14 / 31.21, 1,024 7.60 / 28.54; splash fused 512 8.13 /
28.52, 1,024 7.58 / 24.93; fused, forward 512/1024/512 backward
512/1024/512 7.64 / 25.61; 1024/2048/512 and 512/2048/512 7.50 /
25.02; 1024/1024/512 both 7.33 / 24.86 (splash's best); 512/2048/512
and 1024/2048/512 7.63 / 24.98; 1024/2048/512 and 512/2048/512 with
``SEQ_MINOR`` k and v 7.33 / 24.97: no splash form reached the 24.0
the issue set.  Differences from f32 sdpa, |o, dq, dk, dv|: plain
0.0027, 0.0090, 0.0169, 0.0133; own 0.0029, 0.0091, 0.0127, 0.0143;
splash (every form; its output leaves in bf16) 0.0062, 0.0072, 0.0115,
0.0143.
core: what the program chose on this device (the cell's model compiled
and not run; then one dense layer of it trained one step under an event
log): the ``program`` event's ``attention_core`` and every Pallas
kernel of the one layer's optimized HLO beside its phase (PR 34, v5e:
``{"pallas": 6, "plain": 0}``; one ``causal_attention_fwd`` under
``ff.lm.mla.core``, one ``causal_attention_bwd`` under ``.core.bwd``,
none recomputed).
gmm: the grouped matmul of the held experts (16 groups over a buffer of
65,536 rows of which ~4,096 are assigned; 2048 -> 768), forward +
backward, the two forms of ``ops/moe.py::grouped_matmul``:
``jax.lax.ragged_dot`` and megablox ``gmm`` at its tiles, each checked
against a per-group dense matmul (PR 31, v5e: 3.96 ms and 1.95, both
exact; megablox at (128, 128, 128) tiles 6.68, (256, 2048, 768) does
not fit VMEM), and the same over one slab of 8,192 rows (PR 32).
rows: the held-experts layer's row movement (8,192 tokens, 2,048 wide,
top-8: 65,536 assignments of which ~4,096 go to held experts), forward
+ backward: the dispatch as a gather to all 65,536 sorted positions
(the layer before PR 32) against a gather to one slab of ``SHARES``
even shares (``ops/moe.py::slab_rows``), and the combine as a 65,536-row
gather back to assignment order + a sum over each token's 8 against a
scatter-add of the slab's rows into their tokens (PR 32, v5e: PERF.md
section 6 has the numbers).
slabs: the whole routed part of ``HeldExpertsMoE`` (16 of 256 experts
held, no shared expert, bf16), forward + backward, under a bias on the
held experts that sends them about 4 k, more than one slab, and all
65,536 assignments: in slabs of ``SHARES`` even shares, of 4, and in
one slab of all rows (``SHARES`` = 16), with the slabs each took and the
largest differences between the first and the last.
gdn (PR 35): the Gated DeltaNet rule at the shapes of
``qwen3-next-ep16.pretrain-16k`` (16 key heads on 32 value heads,
16,384 tokens, 128 wide, bf16), forward and forward + backward, one row
a form: ``ops/deltanet.py::gated_delta_rule`` as the program runs it
(chunks of 64, the hand-written backward over the chunks' boundary
states) and at chunks of 32 and 128; the same chunks differentiated by
JAX (no ``custom_vjp``: the scan keeps what it likes); ``T`` by
``jax.scipy.linalg.solve_triangular`` instead of the block-doubling
inverse.  Each row's largest |o, dq, dk, dv, dg, dbeta| differences from
the token-by-token recurrence in f32 on the same rounded operands, over
the first 2,048 tokens of 2 key heads.  PERF.md section 6, PR 35 has the
numbers.  PR 36: the Pallas kernels of ``ops/pallas_deltanet.py`` (what
the program runs on a TPU since) at blocks of 256, 512 and 1,024 tokens;
with ``T``'s f32 products done by hand in three bf16 passes (16 bits of
each operand) and in six (what full precision does); with ``T = I - A``
(wrong: what a kernel costs without its inverse); the chunked rows are
the chunked form's whatever the backend.  The differences from the
recurrence are taken on the 2 key heads whose value heads forget most
slowly (PR 35 took the first 2, whose states outlive a token or two).
v5e, forward / forward + backward ms: chunked 23.70 / 52.82; KERNELS
512 **11.04 / 25.35** (they ship), 256 11.26 / 26.64, 1,024 10.91 /
25.15; three passes 8.05 / 19.66, six by hand 10.70 / 25.00, no inverse
5.27 / 14.22; the first form written, one pair of chunks at a time in a
``fori_loop``, 13.98 / 33.29 and unrolled 12.76 / 30.45.
gdncore (PR 36): ``core`` for the hybrid cell: the ``program`` event's
``gdn_core`` and ``core_kept`` and one DeltaNet layer's kernels by phase.
kda (PR 39): the delta rule with a decay per key channel at the shapes
of ``ling3-flash-ep64.pretrain-8k`` (16 heads, 8,192 tokens, 128 wide,
bf16; the gate's decays at the cell's initialisation), forward and
forward + backward: the Pallas kernels of ``ops/pallas_kda.py`` (what
the program runs on a TPU) at blocks of 128, 256 and 512 tokens, and
the chunked form (``kimi_delta_rule`` off the TPU, the parent's), each
row's largest |o, dq, dk, dv, dg, dbeta| differences from the token
recurrence in f32 on the same rounded operands over the first 2,048
tokens of the 2 heads of largest ``A`` (whose slowest channels keep
their state longest).  PERF.md section 6, PR 39 has the numbers.
kdacore (PR 39): ``core`` for the Ling-style cell: the ``program``
event's ``kda_core`` and ``core_kept`` and two KDA layers' kernels by
phase.
gqa (PR 35): grouped-head attention at 16 query heads on 2 key/value
heads, 16,384 tokens, 256 wide, bf16: the repo's kernels reading each
group's key/value head in place (what the program runs), the same
kernels on k and v written out eight times, and the plain core; each
row's differences from f32 ``sdpa`` over 2 query heads.
Prints ms per call; nothing here is read by the benchmark.
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, seq: int, fwd: tuple, bwd: tuple,
                   fused: bool, seq_minor: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    layout = ({"k_layout": kernel.QKVLayout.SEQ_MINOR,
               "v_layout": kernel.QKVLayout.SEQ_MINOR} if seq_minor else {})
    dq = {} if fused else {"block_q_dq": bwd[0], "block_kv_dq": bwd[1]}
    sizes = kernel.BlockSizes(
        block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2],
        block_q_dkv=bwd[0], block_kv_dkv=bwd[1], block_kv_dkv_compute=bwd[2],
        use_fused_bwd_kernel=fused, **dq, **layout)
    mask = masks.MultiHeadMask([masks.CausalMask((seq, seq))] * heads)
    with jax.ensure_compile_time_eval():  # its mask tables are constants
        return kernel.make_splash_mha_single_device(mask=mask,
                                                    block_sizes=sizes)


def splash(fwd, bwd, fused=True, seq_minor=False):
    """JAX's Pallas splash attention at (block_q, block_kv,
    block_kv_compute) forward and backward; the query comes pre-scaled."""
    def core(q, k, v):
        run = _splash_kernel(q.shape[1], q.shape[2], tuple(fwd), tuple(bwd),
                             fused, seq_minor)
        return jax.vmap(run)(q, k, v).astype(jnp.float32)
    return core


def attn(only=(), h=32, s=8192):
    """``only``: substrings of the rows to run (all without)."""
    from dlrm_flexflow_tpu.ops import attention, pallas_attention
    dk, dv = 192, 128
    ref_heads = 4     # whose f32 logits (1 GB) the chip can hold
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    scale = dk ** -0.5
    q32 = jax.random.normal(keys[0], (1, h, s, dk), jnp.float32)
    # the operands each form multiplies, rounded once: the plain core
    # takes q and scales the logits, the others take q * scale
    q = q32.astype(jnp.bfloat16)
    qs = (q32 * scale).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, h, s, dk), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, h, s, dv), jnp.bfloat16)
    w = jax.random.normal(keys[3], (1, h, s, dv), jnp.float32)
    flops_fwd = 2 * s * s * h * (dk + dv) / 2

    def plain(q, k, v):
        attention._on_tpu = lambda: False
        return attention.blockwise_causal_attention(q, k, v, scale)

    def program(q, k, v):   # as LatentAttention calls it: f32 in
        attention._on_tpu = lambda: True
        return attention.blockwise_causal_attention(
            q, k, v, scale, compute_dtype=jnp.bfloat16)

    def pallas(block):      # the repo's kernels at another block
        def core(q, k, v):
            pallas_attention.BLOCK = block
            return attention._fused_core(q, k, v)
        return core

    # name -> (core, its query, whether the scale is folded into it)
    forms = {
        "plain blockwise 512": (plain, q, False),
        "program's entry (f32 q in)": (program, q32, False),
        "pallas own 512": (pallas(512), qs, True),
        "pallas own 256": (pallas(256), qs, True),
        "pallas own 1024": (pallas(1024), qs, True),
        "splash unfused 512/512/512": (
            splash((512,) * 3, (512,) * 3, fused=False), qs, True),
        "splash unfused 1024/1024/1024": (
            splash((1024,) * 3, (1024,) * 3, fused=False), qs, True),
        "splash fused 512/512/512": (
            splash((512,) * 3, (512,) * 3), qs, True),
        "splash fused 1024/1024/1024": (
            splash((1024,) * 3, (1024,) * 3), qs, True),
        "splash fused fwd 512/1024/512 bwd 512/1024/512": (
            splash((512, 1024, 512), (512, 1024, 512)), qs, True),
        "splash fused fwd 1024/2048/512 bwd 512/2048/512": (
            splash((1024, 2048, 512), (512, 2048, 512)), qs, True),
        "splash fused fwd 1024/1024/512 bwd 1024/1024/512": (
            splash((1024, 1024, 512), (1024, 1024, 512)), qs, True),
        "splash fused fwd 512/2048/512 bwd 1024/2048/512": (
            splash((512, 2048, 512), (1024, 2048, 512)), qs, True),
        "splash fused fwd 1024/2048/512 bwd 512/2048/512 k,v SEQ_MINOR": (
            splash((1024, 2048, 512), (512, 2048, 512), seq_minor=True),
            qs, True),
    }
    if only:
        forms = {n: f for n, f in forms.items()
                 if any(part in n for part in only)}

    def reference(folded):
        """f32 ``sdpa`` over the first heads on the operands the form
        multiplies: output and the three gradients."""
        qr = (qs if folded else q)[:, :ref_heads].astype(jnp.float32)
        args = (qr, k[:, :ref_heads].astype(jnp.float32),
                v[:, :ref_heads].astype(jnp.float32))

        def full(q, k, v):
            with jax.default_matmul_precision("highest"):
                return attention.sdpa(q, k, v, causal=True,
                                      scale=1.0 if folded else scale)
        def loss(*operands):
            o = full(*operands)
            return jnp.sum(o * w[:, :ref_heads]), o
        both = jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))
        (_, o), grads = both(*args)
        return [o, *grads]

    refs = {}

    def measure(name, core, query, folded):
        fwd = jax.jit(lambda q, k, v: core(q, k, v))
        both = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) * w), (0, 1, 2)))
        ms_f, o = timed(fwd, query, k, v)
        ms_b, (_, grads) = timed(both, query, k, v)
        print(f"attn {name}: fwd {ms_f:.2f} ms ({flops_fwd / ms_f / 1e9:.1f}"
              f" TFLOP/s causal), fwd+bwd {ms_b:.2f} ms "
              f"({3.5 * flops_fwd / ms_b / 1e9:.1f} TFLOP/s)", flush=True)
        if query is q32:    # its gradient is the plain query's, in f32
            return
        if folded not in refs:
            refs[folded] = reference(folded)
        # dq in the unscaled query's units whichever operand the form took
        units = [1.0, scale if folded else 1.0, 1.0, 1.0]
        errs = [float(jnp.max(jnp.abs(got[:, :ref_heads].astype(jnp.float32)
                                      - want))) * unit
                for got, want, unit in zip([o, *grads], refs[folded], units)]
        print(f"attn {name}: max |o, dq, dk, dv| difference from f32 sdpa "
              f"on its own rounded operands, {ref_heads} heads: "
              + ", ".join(f"{e:.4g}" for e in errs), flush=True)

    for name, (core, query, folded) in forms.items():
        try:
            measure(name, core, query, folded)
        except Exception as e:  # a form the compiler refuses is a finding
            print(f"attn {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:600]}", flush=True)


#: what ``core`` / ``gdncore`` build: the family, its application, the
#: cell's files, the one-layer model's changes, the events' field and the
#: scope whose instructions are counted
_CORES = {
    "core": ("mla_moe_lm", "joyai-flash-ep16", "pretrain-8k",
             {"num_hidden_layers": 1, "num_nextn_predict_layers": 0},
             "attention_core", "ff.lm.mla.core"),
    "gdncore": ("gdn_moe_lm", "qwen3-next-ep16", "pretrain-16k",
                {"num_hidden_layers": 1}, "gdn_core", "ff.lm.gdn.core"),
    "kdacore": ("kda_moe_lm", "ling3-flash-ep64", "pretrain-8k-kda",
                {"num_hidden_layers": 2}, "kda_core", "ff.lm.kda.core"),
}


def core(which="core"):
    """What the program chose on this device, and what one layer
    compiles to: the cell's model built and compiled (never initialised
    or run: ``FFModel.compile`` knows the count its ``program`` events
    carry), then the first decoder layer of the cell with the embedding
    and head (one ``LatentAttention`` / one ``GatedDeltaNet``,
    recomputed as in the cell) trained one step under an event log: its
    ``program`` event (the core's forms and ``core_kept``, the cores
    whose kernels a recomputed run keeps), and every Pallas kernel of the
    optimized HLO beside its phase."""
    import dataclasses
    import importlib
    import json

    from dlrm_flexflow_tpu import profiling
    from dlrm_flexflow_tpu.config import FFConfig
    from dlrm_flexflow_tpu.telemetry import event_log

    name, config_name, traffic_name, one_layer, field, scope = _CORES[which]
    family = importlib.import_module("benchmarks.models." + name)
    app = importlib.import_module("dlrm_flexflow_tpu.apps." + name)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic",
                           traffic_name + ".json")) as f:
        traffic = json.load(f)

    def compiled(cfg):
        fc = FFConfig(batch_size=traffic["batch"])
        for key, value in config["ffconfig"].items():
            setattr(fc, key, value)
        model = app.build(cfg, fc)
        model.compile(optimizer=app.optimizer(cfg),
                      loss_type=app.token_loss, metrics=(), mesh=False)
        return model

    cfg = family.model_config(config, traffic)
    print(f"{which}: the cell's model, compiled and not run: "
          f"{compiled(cfg)._program_fields}", flush=True)
    model = compiled(dataclasses.replace(cfg, **one_layer))
    init = jax.jit(lambda: model.init(seed=0))
    state = init()
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, traffic["batch"], cfg.seq_len + 1))
    inputs = {"ids": tokens[..., :-1].astype(np.int32)}
    labels = tokens[..., 1:, None].astype(np.int32)
    with event_log() as log:
        state, mets = model.train_epochs(state, inputs, labels, 1)
        print(f"{which}: one layer, one step: loss "
              f"{float(np.asarray(mets['loss'])[0]):.4f}", flush=True)
        events = log.events("program")
    print(f"{which}: one layer's program events: "
          f"{[{k: e[k] for k in ('name', field, 'core_kept')}
              for e in events]}",
          flush=True)
    prog = profiling._programs[events[-1]["name"]]
    text = prog.fn().lower(*prog.args).compile().as_text()
    phases = profiling.hlo_phases(text)
    kernels = [m.group(1) for m in map(profiling._INSTRUCTION.match,
                                       text.splitlines())
               if m and "tpu_custom_call" in m.string]
    print(f"{which}: Pallas kernels of the optimized HLO by phase: "
          f"{[(name, phases.get(name)) for name in kernels]}", flush=True)
    per_scope = {}
    for name, phase in phases.items():
        if phase.startswith(scope):
            per_scope[phase] = per_scope.get(phase, 0) + 1
    print(f"{which}: instructions under {scope} by phase: {per_scope}",
          flush=True)


def gmm(m=65536):
    from dlrm_flexflow_tpu.ops import moe as moe_ops
    k, n, g = 2048, 768, 16
    rng = np.random.default_rng(0)
    sizes = rng.multinomial(4096, np.ones(g) / g).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    w = jax.random.normal(keys[1], (g, k, n), jnp.bfloat16) * 0.02
    ct = jax.random.normal(keys[2], (m, n), jnp.float32)
    group_sizes = jnp.asarray(sizes)
    total = int(sizes.sum())
    valid = (jnp.arange(m) < total)[:, None]

    def form_on(tpu: bool):
        def form(x, w):
            # the program's own function, its platform choice forced
            moe_ops._on_tpu = lambda: tpu
            return moe_ops.grouped_matmul(x, w, group_sizes)
        return form

    forms = {"ragged_dot": form_on(False), "megablox": form_on(True)}
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    want = jnp.concatenate([
        jnp.matmul(x[bounds[i]:bounds[i + 1]], w[i],
                   preferred_element_type=jnp.float32) for i in range(g)])
    def measure(name, form):
        fwd = jax.jit(form)
        both = jax.jit(jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.where(valid, form(x, w), 0.0) * ct),
            (0, 1)))
        ms_f, y = timed(fwd, x, w)
        ms_b, (_, (dx, dw)) = timed(both, x, w)
        err = float(jnp.max(jnp.abs(y[:total] - want)))
        print(f"gmm {name}, {m} rows: fwd {ms_f:.3f} ms, fwd+bwd {ms_b:.3f} ms, "
              f"max err vs dense {err:.3e}, |dw| "
              f"{float(jnp.linalg.norm(dw.astype(jnp.float32))):.4f} "
              f"|dx| {float(jnp.linalg.norm(dx.astype(jnp.float32))):.4f}",
              flush=True)

    for name, form in forms.items():
        try:
            measure(name, form)
        except Exception as e:
            print(f"gmm {name}: FAILED {type(e).__name__}: {str(e)[:600]}",
                  flush=True)


def rows():
    from dlrm_flexflow_tpu.ops.moe import slab_rows
    t, d, k, live_n = 8192, 2048, 8, 4096
    a = t * k
    c = slab_rows(a, 16, 256)
    rng = np.random.default_rng(0)
    held = rng.permutation(a)[:live_n]          # assignments to held experts
    rest = np.setdiff1d(np.arange(a), held)
    order = jnp.asarray(np.concatenate([np.sort(held), rest]), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    gates = jax.random.uniform(keys[1], (a,), jnp.float32)
    live = lambda n: (jnp.arange(n) < live_n)[:, None]
    ct_all = jax.random.normal(keys[2], (a, d), jnp.bfloat16)
    y_all = jnp.where(live(a), jax.random.normal(keys[3], (a, d)), 0.0)
    ct_rows, ys = {a: ct_all, c: ct_all[:c]}, {a: y_all, c: y_all[:c]}
    ct_out = jax.random.normal(keys[4], (t, d), jnp.float32)

    # the forms before PR 32: both directions a gather over all A rows
    @jax.custom_vjp
    def spread(x):
        return jnp.take(x, order // k, axis=0)
    spread.defvjp(lambda x: (spread(x), None),
                  lambda _, g: (jnp.take(g, inverse, axis=0)
                                .reshape(t, k, d).sum(axis=1),))

    @jax.custom_vjp
    def unsort(y):
        return jnp.take(y, inverse, axis=0)
    unsort.defvjp(lambda y: (unsort(y), None),
                  lambda _, g: (jnp.take(g, order, axis=0),))

    def dispatch_all(x):
        return jnp.where(live(a), spread(x), 0)

    def dispatch_slab(x):   # its transpose: a scatter-add of c rows
        return jnp.where(live(c), jnp.take(x, order[:c] // k, axis=0), 0)

    def combine_all(y):
        weights = jnp.where(inverse < live_n, gates, 0.0)
        return jnp.sum((unsort(y) * weights[:, None]).reshape(t, k, d), 1)

    def combine_slab(y):    # its transpose: a gather of c rows
        weights = jnp.where(live(c)[:, 0], jnp.take(gates, order[:c]), 0.0)
        return jnp.zeros((t, d), jnp.float32).at[order[:c] // k].add(
            y * weights[:, None])

    outs = {}

    def measure(name, form, arg, ct):
        fwd = jax.jit(form)
        both = jax.jit(jax.value_and_grad(
            lambda v: jnp.sum(form(v).astype(jnp.float32) * ct)))
        ms_f, out = timed(fwd, arg)
        ms_b, (_, grad) = timed(both, arg)
        outs.setdefault(name.split(",")[0], []).append((out, grad))
        print(f"rows {name}: fwd {ms_f:.3f} ms, fwd+bwd {ms_b:.3f} ms",
              flush=True)

    for name, form, arg, ct in (
            ("dispatch, gather to all %d" % a, dispatch_all, x, ct_rows[a]),
            ("dispatch, gather to a slab of %d" % c, dispatch_slab, x,
             ct_rows[c]),
            ("combine, %d-row gather + sum" % a, combine_all, ys[a], ct_out),
            ("combine, %d-row scatter-add" % c, combine_slab, ys[c],
             ct_out)):
        try:
            measure(name, form, arg, ct)
        except Exception as e:
            print(f"rows {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:600]}", flush=True)
    for kind, pair in outs.items():
        if len(pair) == 2:   # the slab's rows are the first c of all a
            diffs = [float(jnp.max(jnp.abs(
                u[:c].astype(jnp.float32) - v[:c].astype(jnp.float32))))
                for u, v in zip(*pair)]
            print(f"rows {kind}: slab against all, max |out, grad| diff "
                  f"{diffs}", flush=True)


def slabs():
    from dlrm_flexflow_tpu.ops import moe as moe_ops
    from dlrm_flexflow_tpu.tensor import Tensor
    t, d = 8192, 2048
    op = moe_ops.HeldExpertsMoE(
        "moe", Tensor((1, t, d), jnp.float32, name="x"), 256, 768, 8,
        (0, 16), 0, 2.5, 0.0, compute_dtype="bfloat16")
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    params = op.init_params(keys[0])
    x = jax.random.normal(keys[1], (1, t, d), jnp.float32)
    ct = jax.random.normal(keys[2], (1, t, d), jnp.float32)

    def step(params, x, bias):
        state = dict(op.init_state(), bias=bias)
        out = op.forward(params, [x], training=True, state=state)[0]
        return jnp.sum(out * ct), (out, {k: op._last_state[k]
                                         for k in moe_ops.COUNTERS})

    shares = moe_ops.SHARES

    def measure(name, n, lift, bias, got):
        moe_ops.SHARES = n
        try:   # a new function: a new trace under this SHARES
            both = jax.jit(jax.value_and_grad(
                lambda p, x, b: step(p, x, b), (0, 1), has_aux=True))
            ms, ((_, (out, counted)), grads) = timed(both, params, x, bias)
        finally:
            moe_ops.SHARES = shares
        got[name] = (out, grads)
        print(f"slabs bias {lift}, {name}: fwd+bwd {ms:.3f} ms, "
              + ", ".join(f"{k} {int(v)}" for k, v in counted.items()),
              flush=True)

    for lift in (0.0, 0.12, 0.3, 10.0):
        bias = jnp.zeros((256,)).at[:16].set(lift)
        got = {}
        for name, n in (("slabs", shares), ("slabs of 4 shares", 4),
                        ("all rows", 16)):
            try:
                measure(name, n, lift, bias, got)
            except Exception as e:
                print(f"slabs {name}: FAILED {type(e).__name__}: "
                      f"{str(e)[:600]}", flush=True)
        if "slabs" in got and "all rows" in got:
            a, b = (jax.tree_util.tree_leaves(got[name])
                    for name in ("slabs", "all rows"))
            print(f"slabs bias {lift}: largest |difference| of the output, "
                  f"the weights' and the input's gradients "
                  f"{max(float(jnp.max(jnp.abs(u - v))) for u, v in zip(a, b)):.3e}"
                  f", largest |value| "
                  f"{max(float(jnp.max(jnp.abs(u))) for u in a):.3e}",
                  flush=True)


def _run_rows(tag, forms, only, measure):
    for name, form in forms.items():
        if only and not any(part in name for part in only):
            continue
        try:
            measure(name, form)
        except Exception as e:  # a form the compiler refuses is a finding
            print(f"{tag} {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:600]}", flush=True)


def gdn(only=(), hk=16, hv=32, s=16384, d=128):
    from benchmarks.reference import gdn_moe_lm_ref as ref
    from dlrm_flexflow_tpu.ops import deltanet, pallas_deltanet
    shipped = pallas_deltanet.BLOCK
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    bf = jnp.bfloat16
    q = (deltanet.l2_normalised(jax.random.normal(keys[0], (1, s, hk, d)))
         * d ** -0.5).astype(bf)
    k = deltanet.l2_normalised(jax.random.normal(keys[1],
                                                 (1, s, hk, d))).astype(bf)
    v = (0.5 * jax.random.normal(keys[2], (1, s, hv, d))).astype(bf)
    # the released initialisation's decays: A = U(0, 16), dt_bias = 1
    a = jax.random.uniform(keys[3], (hv,), minval=1e-3, maxval=16.0)
    g = -a * jax.nn.softplus(jax.random.normal(keys[4], (1, s, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, s, hv)))
    w = jax.random.normal(keys[6], (1, s, hv, d), jnp.float32)
    args = (q, k, v, g, beta)
    flops = 3 * s * hv * 6 * d * d

    def program(chunk):     # the chunked form, whatever the backend
        def rule(*xs):
            deltanet.CHUNK = chunk
            deltanet._on_tpu = lambda: False
            return deltanet.gated_delta_rule(*xs, compute_dtype=bf)
        return rule

    whole_inverses = pallas_deltanet._inverses

    def kernels(block, inverses=whole_inverses):
        """``ops/pallas_deltanet.py`` at another block, or with ``T``
        got another way (the jit callee keeps its traced kernels: every
        row starts from cleared caches)."""
        def rule(*xs):      # the backward kernel is traced after it returns
            pallas_deltanet.BLOCK = block
            pallas_deltanet._inverses = inverses
            deltanet._on_tpu = lambda: True
            return deltanet.gated_delta_rule(*xs, compute_dtype=bf)
        return rule

    def by_hand(pieces, keep):
        """The doubling's f32 products by hand: each operand split into
        ``pieces`` bf16 parts, the products of parts whose indices sum
        to at most ``keep`` (2, 1: three passes, 16 bits of each
        operand; 3, 2: six passes, what full precision does)."""
        def split(x):
            parts = []
            for _ in range(pieces):
                parts.append(x.astype(bf))
                x = x - parts[-1].astype(jnp.float32)
            return parts

        def product(x, y):
            xs, ys = split(x), split(y)
            terms = sorted(((i + j, i, j) for i in range(pieces)
                            for j in range(pieces) if i + j <= keep),
                           reverse=True)          # the small ones first
            return sum(jnp.dot(xs[i], ys[j],
                               preferred_element_type=jnp.float32)
                       for _, i, j in terms)

        def inverses(mats, m):
            eye = jnp.where(m.eye, 1.0, 0.0)
            ds = [eye - jnp.where(m.corner(1), a, 0.0) for a in mats]
            b = 2
            while b < pallas_deltanet.CHUNK:
                lows = [jnp.where(m.corner(b), a, 0.0) for a in mats]
                xs = [product(d, low) for d, low in zip(ds, lows)]
                ds = [d - product(x, d) for x, d in zip(xs, ds)]
                b *= 2
            return ds
        return inverses

    def no_inverse(mats, m):    # a wrong T: what the rest of a kernel costs
        return [jnp.where(m.eye, 1.0, 0.0) - a for a in mats]

    def autodiff(*xs):      # the same chunks, differentiated by JAX
        deltanet.CHUNK = 64
        operands = deltanet._chunk_operands(
            *deltanet._laid_out(*xs, 64), bf)
        return deltanet._tokens_first(deltanet._scan_chunks(operands, bf)[0])

    def solve(*xs):         # T by a triangular solve
        from jax.scipy.linalg import solve_triangular
        real = deltanet.unit_lower_inverse
        eye = jnp.eye(64, dtype=jnp.float32)
        deltanet.unit_lower_inverse = lambda a: solve_triangular(
            eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)
        try:
            return program(64)(*xs)
        finally:
            deltanet.unit_lower_inverse = real

    forms = {"pallas kernels, blocks of %d (the program on a TPU)"
             % shipped: kernels(shipped),
             **{"pallas kernels, blocks of %d" % n: kernels(n)
                for n in (256, 512, 1024) if n != shipped},
             "pallas kernels, T's products in three bf16 passes by hand":
             kernels(shipped, by_hand(2, 1)),
             "pallas kernels, T's products in six bf16 passes by hand":
             kernels(shipped, by_hand(3, 2)),
             "pallas kernels, T = I - A (wrong: the kernels less the "
             "inverse)": kernels(shipped, no_inverse),
             "chunked: chunks of 64, own backward": program(64),
             "chunks of 32, own backward": program(32),
             "chunks of 128, own backward": program(128),
             "chunks of 64, differentiated by JAX": autodiff,
             "chunks of 64, T by solve_triangular": solve}
    heads, tokens = 2, 2048      # of the key heads, for the recurrence
    # the key heads whose value heads forget most slowly: there the state
    # and ``T`` matter (PR 36; PR 35 took the first two, whose states
    # outlive a token or two, so that a wrong ``T`` read the same)
    group = hv // hk
    slow = np.argsort(np.asarray(a).reshape(hk, group).min(axis=1))[:heads]
    own = (slow[:, None] * group + np.arange(group)).reshape(-1)
    part = (q[:, :tokens, slow], k[:, :tokens, slow], v[:, :tokens, own],
            g[:, :tokens, own], beta[:, :tokens, own])
    w_part = w[:, :tokens, own]

    def recurrence(q, k, v, g, beta):
        q, k = (jnp.repeat(x.astype(jnp.float32), group, axis=2)
                for x in (q, k))
        with jax.default_matmul_precision("highest"):
            return jax.vmap(ref.delta_rule)(q, k, v.astype(jnp.float32), g,
                                            beta)
    grads_of = lambda f, w: jax.jit(jax.value_and_grad(
        lambda *xs: (lambda o: (jnp.sum(o * w), o))(f(*xs)),
        tuple(range(5)), has_aux=True))
    (_, o_ref), g_ref = grads_of(recurrence, w_part)(*part)

    def measure(name, rule):
        jax.clear_caches()
        fwd = jax.jit(rule)
        both = jax.jit(jax.value_and_grad(
            lambda *xs: jnp.sum(rule(*xs) * w), tuple(range(5))))
        ms_f, _ = timed(fwd, *args)
        ms_b, _ = timed(both, *args)
        print(f"gdn {name}: fwd {ms_f:.2f} ms, fwd+bwd {ms_b:.2f} ms "
              f"({flops / ms_b / 1e9:.2f} TFLOP/s of the recurrence's "
              f"own work)", flush=True)
        (_, o), grads = grads_of(rule, w_part)(*part)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip([o, *grads], [o_ref, *g_ref])]
        sizes = [float(jnp.max(jnp.abs(b))) for b in [o_ref, *g_ref]]
        print(f"gdn {name}: max |o, dq, dk, dv, dg, dbeta| difference from "
              f"the token recurrence, {tokens} tokens x {heads} key heads: "
              + ", ".join(f"{e:.3g}" for e in errs) + " (of "
              + ", ".join(f"{x:.3g}" for x in sizes) + ")", flush=True)

    _run_rows("gdn", forms, only, measure)
    deltanet.CHUNK = 64
    pallas_deltanet.BLOCK = shipped
    pallas_deltanet._inverses = whole_inverses
    jax.clear_caches()


def kda(only=(), h=16, s=8192, d=128):
    from benchmarks.reference import kda_moe_lm_ref as ref
    from dlrm_flexflow_tpu.ops import deltanet, pallas_kda
    shipped, on_tpu = pallas_kda.BLOCK, deltanet._on_tpu
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    bf = jnp.bfloat16
    q = (deltanet.l2_normalised(jax.random.normal(keys[0], (1, s, h, d)))
         * d ** -0.5).astype(bf)
    k = deltanet.l2_normalised(jax.random.normal(keys[1],
                                                 (1, s, h, d))).astype(bf)
    v = (0.5 * jax.random.normal(keys[2], (1, s, h, d))).astype(bf)
    # the cell's gate at its initialisation: lower_bound sigmoid(exp(A_log)
    # (x W_f + dt_bias)), A = U(0, 16), dt_bias = 1, lower_bound = -5
    a = jax.random.uniform(keys[3], (h,), minval=1e-3, maxval=16.0)
    g = -5.0 * jax.nn.sigmoid(
        a[:, None] * (jax.random.normal(keys[4], (1, s, h, d)) + 1.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, s, h)))
    w = jax.random.normal(keys[6], (1, s, h, d), jnp.float32)
    args = (q, k, v, g, beta)
    flops = 3 * s * h * 6 * d * d

    def chunked(*xs):       # the parent's form, whatever the backend
        deltanet._on_tpu = lambda: False
        return deltanet.kimi_delta_rule(*xs, compute_dtype=bf)

    def kernels(block):
        """``ops/pallas_kda.py`` at another block (the jit callee keeps
        its traced kernels: every row starts from cleared caches)."""
        def rule(*xs):
            pallas_kda.BLOCK = block
            deltanet._on_tpu = lambda: True
            return deltanet.kimi_delta_rule(*xs, compute_dtype=bf)
        return rule

    forms = {"pallas kernels, blocks of %d (the program on a TPU)"
             % shipped: kernels(shipped),
             **{"pallas kernels, blocks of %d" % n: kernels(n)
                for n in (128, 256, 512) if n != shipped},
             "chunked: chunks of 64, sub-blocks of 16, own backward":
             chunked}
    # the heads whose states live longest: at a large A a channel whose
    # x W_f + dt_bias is below 0 has g near 0 and keeps its state
    heads, tokens = 2, min(2048, s)
    slow = np.argsort(np.asarray(a))[-heads:]
    part = (q[:, :tokens, slow], k[:, :tokens, slow], v[:, :tokens, slow],
            g[:, :tokens, slow], beta[:, :tokens, slow])
    w_part = w[:, :tokens, slow]

    def recurrence(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(ref.delta_rule)(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), g, beta)
    grads_of = lambda f, w: jax.jit(jax.value_and_grad(
        lambda *xs: (lambda o: (jnp.sum(o * w), o))(f(*xs)),
        tuple(range(5)), has_aux=True))
    (_, o_ref), g_ref = grads_of(recurrence, w_part)(*part)

    def measure(name, rule):
        jax.clear_caches()
        fwd = jax.jit(rule)
        both = jax.jit(jax.value_and_grad(
            lambda *xs: jnp.sum(rule(*xs) * w), tuple(range(5))))
        ms_f, _ = timed(fwd, *args)
        ms_b, _ = timed(both, *args)
        print(f"kda {name}: fwd {ms_f:.2f} ms, fwd+bwd {ms_b:.2f} ms "
              f"({flops / ms_b / 1e9:.2f} TFLOP/s of the recurrence's "
              f"own work)", flush=True)
        (_, o), grads = grads_of(rule, w_part)(*part)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip([o, *grads], [o_ref, *g_ref])]
        sizes = [float(jnp.max(jnp.abs(b))) for b in [o_ref, *g_ref]]
        print(f"kda {name}: max |o, dq, dk, dv, dg, dbeta| difference from "
              f"the token recurrence, {tokens} tokens x {heads} heads: "
              + ", ".join(f"{e:.3g}" for e in errs) + " (of "
              + ", ".join(f"{x:.3g}" for x in sizes) + ")", flush=True)

    _run_rows("kda", forms, only, measure)
    pallas_kda.BLOCK, deltanet._on_tpu = shipped, on_tpu
    jax.clear_caches()

def gqa(only=(), h=16, kv=2, s=16384, d=256):
    from dlrm_flexflow_tpu.ops import attention
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    scale = d ** -0.5
    q32 = jax.random.normal(keys[0], (1, h, s, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, kv, s, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (1, h, s, d), jnp.float32)
    flops_fwd = 2 * s * s * h * 2 * d / 2

    def entry(tpu, repeated):
        def form(q, k, v):
            attention._on_tpu = lambda: tpu
            if repeated:
                k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1)
                        for x in (k, v))
            return attention.blockwise_causal_attention(
                q, k, v, scale, compute_dtype=jnp.bfloat16)
        return form

    forms = {"program: kernels, a group's k and v read in place":
             entry(True, False),
             "kernels, k and v written out eight times": entry(True, True),
             "plain blockwise 512 (repeats k and v)": entry(False, False)}
    ref_heads = 2            # of group 0: 2 GB of f32 logits

    def full(q, k, v):
        with jax.default_matmul_precision("highest"):
            folded = (q * scale).astype(jnp.bfloat16).astype(jnp.float32)
            rep = lambda x: jnp.repeat(x.astype(jnp.float32), ref_heads,
                                       axis=1)
            return attention.sdpa(folded, rep(k), rep(v), causal=True,
                                  scale=1.0)
    ref_args = (q32[:, :ref_heads], k[:, :1], v[:, :1])

    def with_grads(fn):     # (loss, o), the three gradients, on one group
        return jax.jit(jax.value_and_grad(
            lambda *xs: (lambda o: (jnp.sum(o * w[:, :ref_heads]), o))(
                fn(*xs)), (0, 1, 2), has_aux=True))
    reference = with_grads(full)
    want = reference(*ref_args)
    want = [want[0][1], *want[1]]

    def measure(name, form):
        fwd = jax.jit(form)
        both = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(form(q, k, v) * w), (0, 1, 2)))
        ms_f, _ = timed(fwd, q32, k, v)
        ms_b, _ = timed(both, q32, k, v)
        print(f"gqa {name}: fwd {ms_f:.2f} ms ({flops_fwd / ms_f / 1e9:.1f} "
              f"TFLOP/s causal), fwd+bwd {ms_b:.2f} ms "
              f"({3.5 * flops_fwd / ms_b / 1e9:.1f} TFLOP/s)", flush=True)
        # one group of two query heads on its one key/value head
        grouped = with_grads(form)
        got = grouped(*ref_args)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
                for a, b in zip([got[0][1], *got[1]], want)]
        print(f"gqa {name}: max |o, dq, dk, dv| difference from f32 sdpa on "
              f"{ref_heads} query heads of one group: "
              + ", ".join(f"{e:.4g}" for e in errs), flush=True)

    _run_rows("gqa", forms, only, measure)


if __name__ == "__main__":
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    which = sys.argv[1:] or ["attn", "gmm", "rows", "slabs"]
    for name in which:
        name, _, only = name.partition(":")   # attn:pallas,plain
        if name in _CORES:
            core(name)
            continue
        {"attn": attn, "gmm": gmm, "rows": rows,
         "slabs": slabs, "gdn": gdn, "gqa": gqa, "kda": kda}[name](
            *([only.split(",")] if only else []))
        if name == "gmm":   # and over one slab of the layer (PR 32)
            from dlrm_flexflow_tpu.ops.moe import slab_rows
            gmm(slab_rows(65536, 16, 256))
