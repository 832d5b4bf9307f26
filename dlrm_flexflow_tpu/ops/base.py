"""Operator base class for the graph-builder.

TPU-native analogue of the reference's abstract ``Op``
(reference: include/model.h:240-281).  The reference Op owns Legion
regions/partitions and exposes init/forward/backward task launchers; here an
Op is a *pure-functional* node: it declares its parameters (ParameterSpec)
and implements ``forward`` as a jnp function.  Backward comes for free from
JAX autodiff (custom_vjp where the reference hand-writes kernels).

Parallelization: each op carries a ``ParallelConfig`` (parallel/) that the
compiler translates into ``PartitionSpec`` sharding constraints — the moral
equivalent of the reference's per-op strategy map consumed by the FFMapper
(src/mapper/mapper.cc:33-97).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp

from ..tensor import ParameterSpec, Tensor


def _named_scope_forward(fwd):
    """Wrap a subclass ``forward`` in ``jax.named_scope(self.name)`` so
    XLA op metadata (and therefore jax.profiler XPlane traces viewed in
    TensorBoard/Perfetto) attributes device time back to the FRAMEWORK
    op name — the analogue of the reference's per-op Legion profiler
    attribution (telemetry tentpole; docs/telemetry.md).  Trace-time
    only: the scope shapes HLO metadata and adds zero runtime work."""
    @functools.wraps(fwd)
    def wrapper(self, *args, **kwargs):
        import jax

        with jax.named_scope(self.name):
            if self.phase is None:
                return fwd(self, *args, **kwargs)
            # a phase scope (profiling.phase_of): the builder's word on
            # which per-layer metric this op's device time belongs to
            with jax.named_scope(self.phase):
                return fwd(self, *args, **kwargs)

    wrapper.__named_scope_wrapped__ = True
    return wrapper


def _untied_param_specs(specs):
    """Wrap a subclass ``param_specs``: an op that reads another op's
    parameters (``params_of``, set by ``FFModel.tie``) declares none."""
    @functools.wraps(specs)
    def wrapper(self):
        return [] if self.params_of is not None else specs(self)

    wrapper.__untied_wrapped__ = True
    return wrapper


def part_coords(pc, ndim: int, idx: int):
    """Decompose a flat part index into per-dim coordinates of the op's
    N-D part grid (dim 0 fastest — matches the simulator's rect walk)."""
    dims = list(pc.dims) + [1] * (ndim - len(pc.dims))
    coords, rem = [], idx
    for d in range(ndim):
        coords.append(rem % dims[d])
        rem //= dims[d]
    return coords


def rect_of_part(pc, shape, idx: int):
    """The (lo, hi) sub-rectangle of a ``shape``-shaped tensor owned by
    part ``idx`` under ParallelConfig ``pc`` (reference N-D block
    partitioning, config.h:41-50)."""
    dims = list(pc.dims) + [1] * (len(shape) - len(pc.dims))
    coords = part_coords(pc, len(shape), idx)
    lo, hi = [], []
    for d in range(len(shape)):
        nd = max(dims[d], 1)
        sz = shape[d] // nd
        c = coords[d]
        lo.append(c * sz)
        hi.append((c + 1) * sz if c < nd - 1 else shape[d])
    return tuple(lo), tuple(hi)


class Op:
    """One graph node.

    Subclasses set ``self.outputs`` in ``__init__`` and implement
    ``forward``.  ``params`` is a dict param_name -> array, stored in the
    model-level pytree under ``self.name``.
    """

    #: class-level default op-type string (reference uses OperatorType enum)
    op_type: str = "op"
    #: ``ff.*`` phase scope opened around ``forward`` (``FFModel.scope``)
    phase: Optional[str] = None
    #: name of the op whose parameters this op reads in place of its own
    #: (``FFModel.tie``): one tensor, one gradient (the sum), one slot;
    #: ``param_specs`` is then empty
    params_of: Optional[str] = None
    #: tag of the run of ops that is recomputed in the backward pass
    #: (``FFModel.scope(recompute=...)``), or None
    recompute: Optional[str] = None
    #: ``jax.ad_checkpoint.checkpoint_name`` names inside ``forward`` that
    #: a recomputed run keeps instead of computing again
    saved_in_recompute: tuple = ()
    #: runs that hold such an op share ONE checkpoint policy object for
    #: their names: JAX caches a run's partial evaluation of a
    #: ``jax.jit`` callee by the policy's identity, so a callee every
    #: instance calls (``ops/deltanet.py``'s KDA kernels) is partially
    #: evaluated and lowered once a program, not once a run
    shares_recompute_policy: bool = False

    def __init_subclass__(cls, **kwargs):
        # every subclass's forward runs under jax.named_scope(op.name)
        # (trace attribution — see _named_scope_forward); wrapping here
        # covers EVERY forward call site (model._apply, the compat
        # bindings' imperative verbs, OpTimer's isolated jits) without
        # each having to remember the scope.  Subclasses that inherit
        # forward unchanged are already covered by their parent's wrap.
        super().__init_subclass__(**kwargs)
        fwd = cls.__dict__.get("forward")
        if fwd is not None and not getattr(fwd, "__named_scope_wrapped__",
                                           False):
            cls.forward = _named_scope_forward(fwd)
        specs = cls.__dict__.get("param_specs")
        if specs is not None and not getattr(specs, "__untied_wrapped__",
                                             False):
            cls.param_specs = _untied_param_specs(specs)

    def __init__(self, name: str, inputs: Sequence[Tensor]):
        self.name = name
        self.inputs: List[Tensor] = list(inputs)
        self.outputs: List[Tensor] = []
        # SOAP per-op strategy; None = inherit model default (data-parallel),
        # mirroring FFConfig::find_parallel_config fallback (strategy.cc:28-94).
        self.parallel_config = None
        self.profiling = False
        # set by FFModel.compile: the active mesh, for ops that issue manual
        # collectives (e.g. ring attention over the "seq" axis)
        self._mesh = None

    # ---- graph construction -------------------------------------------------
    def _make_output(self, shape, dtype=jnp.float32, idx: int = 0) -> Tensor:
        t = Tensor(shape=shape, dtype=dtype, owner_op=self, owner_idx=idx,
                   name=f"{self.name}:out{idx}")
        return t

    # ---- parameters ---------------------------------------------------------
    def param_specs(self) -> List[ParameterSpec]:
        """Declare weights (reference Op::create_weights)."""
        return []

    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        specs = self.param_specs()
        out = {}
        import jax

        keys = jax.random.split(key, max(1, len(specs)))
        for k, spec in zip(keys, specs):
            init = spec.initializer
            arr = init(k, spec.shape, spec.dtype)
            if spec.storage_shape is not None:
                # physical storage form (e.g. lane-packed embedding
                # tables): drawn at the logical shape so packed and
                # logical storage initialize bit-identically, then
                # reshaped row-major (value-preserving)
                arr = arr.reshape(spec.storage_shape)
            out[spec.param_name] = arr
        return out

    # ---- execution ----------------------------------------------------------
    def forward(self, params: Dict[str, jnp.ndarray], xs: List[jnp.ndarray], *,
                training: bool = False, rng=None) -> List[jnp.ndarray]:
        raise NotImplementedError

    # ---- cost model hooks (used by sim/) -----------------------------------
    def flops(self, batch: int) -> int:
        """Approximate forward FLOPs for the simulator's cost model
        (the reference instead times real kernels, simulator.cc:235-273;
        we support both measured and analytic costs)."""
        return 0

    def input_rect(self, pc, input_idx: int, part_idx: int):
        """The (lo, hi) sub-rectangle of input ``input_idx`` that output
        part ``part_idx`` READS under output ParallelConfig ``pc`` — the
        per-op hook the simulator uses to size comm tasks (the reference
        computes these true input rects when inserting xfer tasks,
        simulator.cc:200-233).

        Default: a batch (dim 0) partition maps through when the input
        shares the output's batch extent; every other input dim is read
        in FULL (e.g. a channel-parallel Linear part holds a weight
        column shard but consumes the whole input row — the replica
        semantics of linear.cu:214-263)."""
        ishape = self.inputs[input_idx].shape
        oshape = self.outputs[0].shape
        lo, hi = [0] * len(ishape), list(ishape)
        nd0 = pc.dims[0] if pc.dims else 1
        if (nd0 > 1 and ishape and oshape and ishape[0] == oshape[0]):
            c = part_coords(pc, len(oshape), part_idx)[0]
            sz = ishape[0] // nd0
            lo[0] = c * sz
            hi[0] = (c + 1) * sz if c < nd0 - 1 else ishape[0]
        return tuple(lo), tuple(hi)

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


def activation_fn(name: Optional[str]):
    """Shared activation table (reference fuses these via cuDNN activation
    descriptors in linear/conv kernels, e.g. linear.cu:432-441)."""
    if name is None or name == "none" or name == "linear":
        return lambda x: x
    import jax

    table = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "elu": jax.nn.elu,
        "gelu": jax.nn.gelu,
        "exp": jnp.exp,
        "softmax": jax.nn.softmax,
        "identity": lambda x: x,
    }
    if name not in table:
        raise ValueError(f"unknown activation {name!r}")
    return table[name]


def matmul(x, w, compute_dtype=None):
    """Matmul helper routed at the MXU.

    On TPU the MXU natively multiplies bf16 with f32 accumulation; when
    ``compute_dtype='bfloat16'`` we cast operands down but keep f32
    accumulation via ``preferred_element_type`` — the TPU-idiomatic
    replacement for the reference's cublasSgemm calls (linear.cu:432-441).
    """
    import jax

    if compute_dtype in ("bfloat16", jnp.bfloat16):
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        x, w,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def held_heads(heads_held, num_heads: int) -> int:
    """How many of a mixer's ``num_heads`` heads an op holds
    (``heads_held`` of ``KimiDeltaAttention`` and ``LatentAttention``):
    all of them for ``None``.  Which ones is the deployment's to say: no
    parameter, and nothing computed, depends on it."""
    count = num_heads if heads_held is None else int(heads_held)
    assert 1 <= count <= num_heads, (heads_held, num_heads)
    return count
