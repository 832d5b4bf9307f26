"""Per-op cost measurement and the TPU machine model.

TPU-native equivalent of the reference's simulator measurement layer
(reference: src/runtime/simulator.cu:21-76 — device/link graph with
hard-coded bandwidths (inter-GPU 20 MB/ms, inter-node 12 MB/ms / nodes,
GPU<->DRAM 16 MB/ms, simulator.cu:27-29); memoized real-kernel timing
``measure_op_forward/backward_time`` simulator.cc:235-273 calling each op's
``measure_compute_time`` e.g. linear.cu:973-1049).

Three cost sources, all memoized:
  * measured   — jit-compile the op's forward/backward on the real device
                 and wall-clock it (the reference's approach);
  * analytic   — roofline estimate max(FLOPs/peak, bytes/HBM-bw), used on
                 CPU test meshes and as a fast fallback;
  * calibrated — the analytic roofline corrected by per-op-class factors
                 fitted from a recorded run's measured-vs-predicted
                 ``op_time`` telemetry (sim/tune.py::Calibration) — the
                 chip-free cost source the ``search-tune`` closed loop
                 re-searches under (docs/tuning.md).

The machine model replaces the GPU constants with TPU numbers: per-chip
HBM bandwidth, MXU peak, ICI link bandwidth (bidirectional ring per mesh
axis), and DCN bandwidth for multi-host hops.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class PodTopology:
    """Two-level pod interconnect shape: ``num_slices`` ICI slices of
    ``chips_per_slice`` chips each, joined by DCN (docs/distributed.md).

    The reference prices inter-node links separately from intra-node
    ones (simulator.cu:27-29: inter-GPU 20 MB/ms vs inter-node
    12 MB/ms); on TPU the analogue is ICI within a slice vs the ~4x
    slower DCN across slices.  Flat device ids map to slices
    contiguously: device ``d`` lives on slice ``d // chips_per_slice``
    — the order ``jax.devices()`` lists a pod.  ``num_slices=1``
    degrades to today's flat model (every transfer is ICI) and is
    priced BIT-identically to a topology-less machine, pinned by
    tests/test_pod.py."""

    num_slices: int = 1
    chips_per_slice: int = 1

    def __post_init__(self):
        if int(self.num_slices) < 1 or int(self.chips_per_slice) < 1:
            raise ValueError(
                f"PodTopology needs >=1 slices of >=1 chips, got "
                f"{self.num_slices}x{self.chips_per_slice}")
        object.__setattr__(self, "num_slices", int(self.num_slices))
        object.__setattr__(self, "chips_per_slice",
                           int(self.chips_per_slice))

    @property
    def num_devices(self) -> int:
        return self.num_slices * self.chips_per_slice

    def slice_of(self, device: int) -> int:
        """The slice a flat device id lives on (ids beyond the pod fold
        modulo, matching the simulator's ``dev % num_devices``)."""
        return (int(device) % self.num_devices) // self.chips_per_slice

    def same_slice(self, a: int, b: int) -> bool:
        return self.slice_of(a) == self.slice_of(b)

    def slices_spanned(self, devices: Sequence[int]) -> int:
        """How many distinct slices a device list touches (>=1)."""
        if not devices:
            return 1
        return len({self.slice_of(d) for d in devices})

    def local_group(self, devices: Sequence[int]) -> int:
        """Largest per-slice participant count of a device list — the
        within-slice group size the hierarchical collectives ring
        over."""
        if not devices:
            return 1
        counts: Dict[int, int] = {}
        for d in devices:
            s = self.slice_of(d)
            counts[s] = counts.get(s, 0) + 1
        return max(counts.values())

    def to_json(self) -> dict:
        return {"num_slices": self.num_slices,
                "chips_per_slice": self.chips_per_slice}

    @staticmethod
    def from_json(d: dict) -> "PodTopology":
        return PodTopology(int(d["num_slices"]),
                           int(d["chips_per_slice"]))

    @staticmethod
    def parse(spec: str) -> "PodTopology":
        """``"<slices>x<chips>"`` (e.g. ``"2x4"``) -> PodTopology."""
        try:
            s, c = spec.lower().split("x")
            return PodTopology(int(s), int(c))
        except (ValueError, AttributeError):
            raise ValueError(
                f"pod topology spec must look like '2x4' "
                f"(slices x chips-per-slice), got {spec!r}") from None


@dataclass
class TPUMachineModel:
    """TPU chip/interconnect constants (defaults ~ v5e).

    Replaces reference simulator.cu:27-29.  All bandwidths bytes/sec,
    compute FLOP/sec.  ``topology`` (a :class:`PodTopology`) makes the
    collective and transfer estimates two-level: ICI within a slice,
    DCN across slices.  ``None`` keeps the flat single-slice model —
    every existing call site prices exactly as before.
    """

    name: str = "tpu-v5e"
    #: ``jax.devices()[0].device_kind`` of the chip these defaults
    #: describe — a measurement divides by them only on that chip
    device_kind: str = "TPU v5 lite"
    peak_flops_bf16: float = 197e12
    peak_flops_f32: float = 49e12
    hbm_bandwidth: float = 819e9
    hbm_bytes: float = 16e9
    ici_bandwidth: float = 45e9       # per link per direction
    ici_links_per_chip: int = 4
    dcn_bandwidth: float = 12.5e9     # per host
    kernel_launch_overhead: float = 2e-6  # fused-step dispatch amortized
    topology: Optional[PodTopology] = None

    def matmul_time(self, flops: float, dtype: str = "bfloat16") -> float:
        peak = (self.peak_flops_bf16 if dtype in ("bfloat16", "bf16")
                else self.peak_flops_f32)
        # MXU utilisation falls off for small ops; simple 60% efficiency
        return flops / (0.6 * peak)

    def memory_time(self, bytes_moved: float) -> float:
        return bytes_moved / self.hbm_bandwidth

    def ici_time(self, bytes_moved: float, hops: int = 1) -> float:
        """One neighbour transfer on the ICI ring (per-axis bidirectional)."""
        return hops * bytes_moved / self.ici_bandwidth

    def xfer_time(self, bytes_moved: float, src: Optional[int] = None,
                  dst: Optional[int] = None) -> float:
        """One point-to-point transfer, routed by the pod topology:
        ICI when ``src``/``dst`` share a slice (or no topology / no
        device info is available — the flat model), DCN when they
        cross slices.  The simulator prices every producer->consumer
        comm task through this, so a cross-slice hop costs the ~4x
        slower link instead of the flat ``ici_time``."""
        t = self.topology
        if (t is None or t.num_slices <= 1 or src is None or dst is None
                or t.same_slice(src, dst)):
            return self.ici_time(bytes_moved)
        return self.dcn_time(bytes_moved)

    # Collective group shape: ``devices`` (when the caller knows the
    # placement — the simulator's grad sync does) pins which slices
    # participate; without it the flat-id contiguity assumption applies:
    # n participants fill ceil(n / chips_per_slice) slices.
    def _group(self, n: int, devices: Optional[Sequence[int]]
               ) -> Tuple[int, int]:
        """(slices_spanned, within_slice_group) for an n-chip collective."""
        t = self.topology
        if t is None or t.num_slices <= 1 or n <= 1:
            return 1, n
        if devices:
            return t.slices_spanned(devices), t.local_group(devices)
        s = min(t.num_slices, -(-n // t.chips_per_slice))  # ceil
        return s, min(n, t.chips_per_slice)

    def all_reduce_time(self, bytes_per_chip: float, n: int,
                        devices: Optional[Sequence[int]] = None) -> float:
        """Ring all-reduce: 2(n-1)/n * bytes over one ICI link when the
        group sits inside one slice.  Spanning slices it goes
        hierarchical (the canonical two-level all-reduce —
        docs/distributed.md): ring reduce-scatter within each slice
        over ICI, a cross-slice all-reduce of the scattered 1/m shard
        over DCN, and the ICI broadcast (all-gather) back."""
        if n <= 1:
            return 0.0
        s, m = self._group(n, devices)
        if s <= 1:
            return self.ici_time(2.0 * (n - 1) / n * bytes_per_chip)
        m = max(m, 1)
        within = 2.0 * self.ici_time((m - 1) / m * bytes_per_chip)
        across = self.dcn_time(2.0 * (s - 1) / s * bytes_per_chip / m)
        return within + across

    def all_gather_time(self, bytes_per_chip: float, n: int,
                        devices: Optional[Sequence[int]] = None) -> float:
        if n <= 1:
            return 0.0
        s, m = self._group(n, devices)
        if s <= 1:
            return self.ici_time((n - 1) / n * bytes_per_chip * n)
        m = max(m, 1)
        # within-slice all-gather, DCN exchange of each slice's block to
        # the s-1 peers, ICI broadcast of the foreign blocks
        within = self.ici_time((m - 1) * bytes_per_chip)
        across = self.dcn_time((s - 1) * m * bytes_per_chip)
        bcast = self.ici_time((s - 1) * m * bytes_per_chip)
        return within + across + bcast

    def all_to_all_time(self, bytes_per_chip: float, n: int,
                        devices: Optional[Sequence[int]] = None) -> float:
        """All-to-all over the ring: each chip sends (n-1)/n of its
        shard; on a pod the cross-slice fraction (n-m)/n rides DCN."""
        if n <= 1:
            return 0.0
        s, m = self._group(n, devices)
        if s <= 1:
            return self.ici_time(bytes_per_chip * (n - 1) / n)
        m = max(m, 1)
        return (self.ici_time(bytes_per_chip * (m - 1) / n)
                + self.dcn_time(bytes_per_chip * (n - m) / n))

    def dcn_time(self, bytes_moved: float) -> float:
        return bytes_moved / self.dcn_bandwidth


def overlapped_exchange_time(machine: "TPUMachineModel", exchange_s: float,
                             dense_s: float, microbatches: int,
                             overlapped: bool = True) -> float:
    """Time for an embedding exchange running NEXT TO a dense stack.

    Serial (``overlapped=False`` or K<=1): the two rails pay their sum
    — the monolithic collective sits fully exposed before the
    interaction.  Pipelined (parallel/overlap.py): the batch splits
    into K microbatches and each microbatch pays
    ``max(exchange/K, dense/K)``, plus one fill term — the first
    exchange (or the last dense slice, whichever rail is shorter) has
    nothing to hide under, so ``min(exchange, dense)/K`` stays
    exposed.  This is the op-class pricing hook
    ``OverlappedEmbedBottom.exchange_overlap_cost`` feeds the
    simulator, so MCMC search under the (calibrated) analytic model
    can rank overlap-winning strategies above serial ones."""
    if not overlapped or microbatches <= 1:
        return exchange_s + dense_s
    k = max(int(microbatches), 1)
    return k * max(exchange_s / k, dense_s / k) + min(exchange_s,
                                                      dense_s) / k


class CostModel:
    """Memoized per-op timing (reference simulator.cc:235-273).

    ``measure=True`` wall-clocks the op's jitted forward and backward on the
    current default JAX device; otherwise analytic roofline from op.flops()
    and tensor byte counts.
    """

    def __init__(self, machine: Optional[TPUMachineModel] = None,
                 measure: bool = False, measure_iters: int = 24,
                 measure_budget_s: float = 300.0, calibration=None):
        self.machine = machine or TPUMachineModel()
        self.measure = measure
        # telemetry-backed correction (sim/tune.py::Calibration): per
        # op-class multipliers applied on top of the ANALYTIC estimate
        # only — measured times are already real and stay untouched
        self.calibration = calibration
        self.measure_iters = measure_iters
        # wall-clock budget for ALL measurement (each distinct op shape
        # costs a compile, ~2-10 s; a big graph could otherwise stall a
        # compile-time search for tens of minutes) — once spent, later
        # ops fall back to the analytic estimate with a warning
        self.measure_budget_s = measure_budget_s
        self._measure_spent = 0.0
        self._budget_warned = False
        # measured-vs-analytic totals over the keys that WERE measured:
        # post-budget analytic estimates are scaled by their ratio so one
        # search never compares raw roofline numbers (v5e peak constants)
        # against real measured times on a slower shared slice
        self._measured_total = 0.0
        self._analytic_total = 0.0
        self._cache: Dict[Tuple, Tuple[float, float]] = {}
        self._null_dispatch: Optional[float] = None  # measured lazily

    # ---- helpers -----------------------------------------------------------
    @staticmethod
    def _op_key(op, num_parts: int) -> Tuple:
        import jax.numpy as jnp

        return (type(op).__name__,
                tuple(t.shape for t in op.inputs),
                tuple(t.shape for t in op.outputs),
                tuple((s.param_name, s.shape) for s in op.param_specs()),
                num_parts)

    def op_times(self, op, num_parts: int = 1) -> Tuple[float, float]:
        """Return (forward_s, backward_s) for one partition of the op when
        its output is split into ``num_parts`` equal parts."""
        key = self._op_key(op, num_parts)
        if key in self._cache:
            return self._cache[key]
        if self.measure and self._measure_spent >= self.measure_budget_s:
            if not self._budget_warned:
                import warnings
                warnings.warn(
                    f"cost-model measurement budget "
                    f"({self.measure_budget_s:.0f}s) spent; remaining ops "
                    "use calibrated analytic estimates", RuntimeWarning)
                self._budget_warned = True
            # scale by the measured/analytic ratio seen so far, so
            # pre- and post-budget keys stay comparable in one search
            scale = (self._measured_total / self._analytic_total
                     if self._analytic_total > 0 else 1.0)
            fwd, bwd = self._analytic_op(op, num_parts)
            fwd, bwd = fwd * scale, bwd * scale
        elif self.measure:
            t0 = time.perf_counter()
            try:
                fwd, bwd = self._measure_op(op, num_parts)
                af, ab = self._analytic_op(op, num_parts)
                self._measured_total += fwd + bwd
                self._analytic_total += af + ab
            except Exception as e:
                # fall back, but LOUDLY — a silent fallback would bias the
                # search with analytic numbers while claiming measured ones
                import warnings
                warnings.warn(
                    f"measured cost for {op.name} ({type(op).__name__}) "
                    f"failed ({type(e).__name__}: {e}); using analytic "
                    "estimate", RuntimeWarning)
                fwd, bwd = self._analytic_op(op, num_parts)
            finally:
                self._measure_spent += time.perf_counter() - t0
        else:
            fwd, bwd = self._analytic_op(op, num_parts)
            if self.calibration is not None:
                sf, sb = self.calibration.scale_for(op)
                fwd, bwd = fwd * sf, bwd * sb
        self._cache[key] = (fwd, bwd)
        return fwd, bwd

    # ---- analytic ----------------------------------------------------------
    @staticmethod
    def _nbytes(dtype) -> int:
        return int(np.dtype(dtype).itemsize)

    def _analytic_op(self, op, num_parts: int) -> Tuple[float, float]:
        m = self.machine
        # overlap-aware op classes price themselves (per-microbatch
        # max(exchange, dense) instead of the roofline sum — see
        # overlapped_exchange_time); calibration still applies on top
        # in op_times, so the fitted per-class correction covers the
        # new class like any other
        hook = getattr(op, "exchange_overlap_cost", None)
        if hook is not None:
            est = hook(m, num_parts)
            if est is not None:
                return est
        batch = op.outputs[0].shape[0] if op.outputs[0].ndim else 1
        flops = op.flops(batch) / max(num_parts, 1)
        compute_dtype = getattr(op, "compute_dtype", None) or "float32"
        in_bytes = sum(self._nbytes(t.dtype) * t.numel()
                       for t in op.inputs) / max(num_parts, 1)
        out_bytes = sum(self._nbytes(t.dtype) * t.numel()
                        for t in op.outputs) / max(num_parts, 1)
        w_bytes = sum(self._nbytes(s.dtype) * int(np.prod(s.shape))
                      for s in op.param_specs())
        fwd = max(m.matmul_time(flops, str(compute_dtype)),
                  m.memory_time(in_bytes + out_bytes + w_bytes))
        fwd += m.kernel_launch_overhead
        # backward ~ 2x forward FLOPs (dgrad+wgrad), same traffic + grads
        bwd = max(m.matmul_time(2 * flops, str(compute_dtype)),
                  m.memory_time(2 * (in_bytes + out_bytes) + 2 * w_bytes))
        bwd += m.kernel_launch_overhead
        return fwd, bwd

    # ---- measured ----------------------------------------------------------
    def _measure_op(self, op, num_parts: int) -> Tuple[float, float]:
        """Time the real op kernels under jit (reference runs the real CUDA
        kernels on simulator scratch, linear.cu:973-1049)."""
        import jax
        import jax.numpy as jnp

        def part_shape(shape):
            if not shape:
                return shape
            b = max(shape[0] // num_parts, 1)
            return (b,) + tuple(shape[1:])

        rng = np.random.default_rng(0)
        xs = []
        for t in op.inputs:
            shp = part_shape(t.shape)
            if "int" in str(np.dtype(t.dtype)):
                hi = getattr(op, "num_entries", 2)
                ids = rng.integers(0, hi, size=shp)
                if not jax.config.jax_enable_x64:
                    ids = ids.astype(np.int32)
                xs.append(jnp.asarray(ids))
            else:
                xs.append(jnp.asarray(
                    rng.standard_normal(shp).astype(np.float32)))
        params = op.init_params(jax.random.PRNGKey(0))

        def fwd_fn(params, xs):
            return op.forward(params, list(xs), training=False)[0]

        # embedding-family ops train through the row-sparse kernels
        # (gather_rows + scatter_apply); their dense-autodiff backward —
        # a table-shaped scatter-add — never runs in training under plain
        # SGD, and its compile is pathological at big-table sizes, so
        # measure the kernels the step actually executes.
        sparse_capable = (hasattr(op, "gather_rows")
                          and hasattr(op, "scatter_apply")
                          and "embedding" in params)

        if sparse_capable:
            def bwd_fn(params, xs):
                tb = params["embedding"]
                rows = op.gather_rows(tb, xs[0])
                return op.scatter_apply(tb, xs[0], rows, -0.01)
        else:
            def loss_fn(params, xs):
                outs = op.forward(params, list(xs), training=False)
                return sum(jnp.sum(o * o) for o in outs
                           if jnp.issubdtype(o.dtype, jnp.floating))

            def bwd_fn(params, xs):
                return jax.grad(loss_fn, argnums=0)(params, xs)

        from ..profiling import device_fence

        # A host->device dispatch costs far more than a sub-ms kernel
        # runs, so per-launch timing would measure the launch.  Chain
        # ``measure_iters`` executions INSIDE one compiled
        # lax.scan (an optimization_barrier threads the carry through the
        # inputs so XLA cannot hoist the loop-invariant computation) and
        # subtract one measured null-dispatch.
        iters = self.measure_iters

        def chained(f):
            # params and inputs are ARGUMENTS of the jitted program: a
            # closed-over 2 GB table is lowered as a 2 GB constant, and
            # compiling (and cache-serializing) that took the host's
            # whole memory on the chip machine
            def run(params, xs):
                def body(c, _):
                    xs_b, c_b = jax.lax.optimization_barrier((xs, c))
                    out = f(params, list(xs_b))
                    leaves = [o for o in jax.tree_util.tree_leaves(out)
                              if hasattr(o, "dtype")
                              and jnp.issubdtype(o.dtype, jnp.floating)]
                    nxt = (jnp.ravel(leaves[0])[0].astype(jnp.float32)
                           if leaves else jnp.float32(0.0))
                    return nxt + 0.0 * c_b, None

                return jax.lax.scan(body, jnp.float32(0.0), None,
                                    length=iters)[0]

            g = jax.jit(run)
            return lambda: g(params, tuple(xs))

        if self._null_dispatch is None:
            null = jax.jit(lambda: jnp.float32(0.0))
            device_fence(null())
            best_null = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                device_fence(null())
                best_null = min(best_null, time.perf_counter() - t0)
            self._null_dispatch = best_null

        def timeit(f):
            g = chained(f)
            device_fence(g())  # compile
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                device_fence(g())
                best = min(best, time.perf_counter() - t0)
            # iters is large enough that kernel time dominates the one
            # dispatch; subtracting the best-case null keeps small ops
            # from being billed the launch overhead
            return max((best - self._null_dispatch) / iters,
                       best / (4 * iters), 1e-9)

        fwd = timeit(fwd_fn)
        bwd = timeit(bwd_fn) if params else fwd
        return fwd, bwd
