"""On the chip: the two kernels of the language-model family at the
shapes of ``joyai-flash-ep16.pretrain-8k``, each beside its rival.

    chiprun -- python scripts/ab_lm_kernels.py [attn] [gmm] [rows] [slabs]

attn: the causal attention core (32 heads, 8,192 tokens, query/key
width 192, value width 128, bf16), forward + backward: the blockwise
core of ``ops/attention.py`` as the program calls it against JAX's
Pallas splash attention at the same block, with the largest differences
between them (PR 31, v5e: blockwise 27.7 ms, splash 32.1; other blocks,
set through ``ATTENTION_BLOCK``: 256 43.0, 1,024 56.3; splash at 1,024
29.4: the plain core stayed).
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
largest differences between the first and the last.  Prints ms per call; nothing here is read
by the benchmark.
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


@functools.lru_cache(maxsize=8)
def _splash_kernel(heads: int, seq: int, block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    mask = masks.MultiHeadMask([masks.CausalMask((seq, seq))] * heads)
    with jax.ensure_compile_time_eval():  # its mask tables are constants
        return kernel.make_splash_mha_single_device(mask=mask,
                                                    block_sizes=sizes)


def splash_causal_attention(q, k, v, scale: float, block: int = 512):
    """The same function as ``blockwise_causal_attention`` through JAX's
    Pallas splash-attention kernel (the query pre-scaled, rounded once)."""
    run = _splash_kernel(q.shape[1], q.shape[2], block)
    q = (q.astype(jnp.float32) * scale).astype(k.dtype)
    return jax.vmap(run)(q, k, v).astype(jnp.float32)


def attn():
    from dlrm_flexflow_tpu.ops.attention import (
        ATTENTION_BLOCK, blockwise_causal_attention)
    h, s, dk, dv = 32, 8192, 192, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, h, s, dk), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, h, s, dk), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, h, s, dv), jnp.bfloat16)
    w = jax.random.normal(keys[3], (1, h, s, dv), jnp.float32)
    scale = dk ** -0.5
    flops_fwd = 2 * s * s * h * (dk + dv) / 2
    outs = {}
    forms = {"blockwise": functools.partial(blockwise_causal_attention,
                                            scale=scale),
             "splash": functools.partial(splash_causal_attention,
                                         scale=scale, block=ATTENTION_BLOCK)}

    def measure(name, core):
        fwd = jax.jit(lambda q, k, v: core(q, k, v))
        both = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) * w), (0, 1, 2)))
        ms_f, o = timed(fwd, q, k, v)
        ms_b, (_, grads) = timed(both, q, k, v)
        outs[name] = (o, grads)
        print(f"attn {name}: fwd {ms_f:.2f} ms ({flops_fwd / ms_f / 1e9:.1f}"
              f" TFLOP/s causal), fwd+bwd {ms_b:.2f} ms "
              f"({3.5 * flops_fwd / ms_b / 1e9:.1f} TFLOP/s)", flush=True)

    for name, core in forms.items():
        try:
            measure(name, core)
        except Exception as e:  # a form the compiler refuses is a finding
            print(f"attn {name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:600]}", flush=True)
    if "blockwise" in outs:
        base_o, base_g = outs["blockwise"]
        for name, (o, grads) in outs.items():
            errs = [float(jnp.max(jnp.abs(o.astype(jnp.float32) - base_o)))]
            errs += [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                     for a, b in zip(grads, base_g)]
            print(f"attn {name} vs blockwise: max |do, dq, dk, dv| diff "
                  f"{errs}", flush=True)


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


if __name__ == "__main__":
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    which = sys.argv[1:] or ["attn", "gmm", "rows", "slabs"]
    for name in which:
        {"attn": attn, "gmm": gmm, "rows": rows, "slabs": slabs}[name]()
        if name == "gmm":   # and over one slab of the layer (PR 32)
            from dlrm_flexflow_tpu.ops.moe import slab_rows
            gmm(slab_rows(65536, 16, 256))
