"""Grade-partitioned logical & device simulation tiers (paper §III.B, §IV).

*Logical Simulation* in the paper launches Ray actors on k8s nodes, each actor
sequentially simulating several devices.  The TPU-native adaptation is a
**vectorized client engine**: client-local training is expressed as a pure
function of (client params, client batch) and executed for a whole *cohort* of
clients at once via ``jax.vmap`` — sharded over the mesh ``data`` axis with
``shard_map`` when a mesh is supplied (both tiers support the mesh path, so
device cohorts shard across hosts exactly like logical ones).

*Device Simulation* is backed by the calibrated device models of
``core.devicemodel`` (see DESIGN.md §2 for why physical phones cannot exist
here) and — crucially for the Fig. 6 reproduction — executes the *same
operator flow through a numerically different backend* (bf16 accumulation vs
f32), mirroring the paper's PyMNN-vs-C++-MNN operator discrepancy.

**Grade-partitioned round engine.**  The §IV.B allocator splits *each device
grade* between the tiers; the engine mirrors that shape.  A ``RoundPlan``
consumes an ``AllocationResult`` directly — one ``GradePlanEntry`` per grade
carrying the allocator's (x_i logical, y_i physical, q_i benchmarking) split —
and ``HybridSimulation`` holds one ``DeviceTier`` (with its own ``DeviceFleet``)
*per grade*::

    sim = HybridSimulation(logical, tiers={"High": ..., "Low": ...},
                           deviceflow=flow)
    plan = RoundPlan.from_allocation(solve_allocation(specs, runtimes), specs)
    outcome = sim.run_plan_round(task_id, rnd, params, plan,
                                 grade_batches, grade_num_samples, rng)

``run_plan_round`` executes each grade's logical and device cohorts (one
vmapped XLA dispatch per chunk), samples each grade's fleet once (all devices
× 5 Table-I stages), merges the per-grade sampled durations into DeviceFlow
arrival times through the bulk ``submit_many`` Sorter path, materializes
``RoundReport``s for exactly the q_i benchmarking devices the allocator
excluded, and reports a per-grade makespan breakdown in
``FederatedRoundOutcome.per_grade``.  Passing a ``RuntimeCalibrator`` feeds
the sampled durations back into allocation (measured, not hand-coded,
``GradeRuntime``s — the paper's calibration loop).

**Zero-copy round pipeline.**  Model updates are device-resident end-to-end:
cohort outputs stay stacked on device (one ``core.updates.UpdateBuffer`` per
chunk), messages carry ``UpdateHandle`` payloads, and aggregation runs one
fused weighted reduction per buffer (``kernels/fed_reduce``) instead of
walking per-device host pytrees.  Host materialization happens only for the
q_i benchmarking devices and at checkpoint time.  Construct
``HybridSimulation(..., zero_copy=False)`` for the host-materializing
reference path.

The legacy single-grade ``run_round(..., num_logical=...)`` path is kept as a
thin wrapper over the same per-grade execution helper.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.analysis import sanitizers
from repro.analysis.sanitizers import hot_path
from repro.core.allocation import AllocationResult
from repro.core.deviceflow import ArrivalBatch, DeviceFlow, Message
from repro.core.updates import (
    UpdateBuffer,
    UpdateHandle,
    flatten_rows,
    quantize_rows,
    stacked_spec,
)
from repro.core.devicemodel import (
    DeviceFleet,
    DeviceGrade,
    FleetRoundSample,
    RoundReport,
)
from repro.core.task import GradeSpec

Params = Any
Batch = Any

# A client-local training function: (params, batch, rng) -> (params, metrics).
LocalTrainFn = Callable[[Params, Batch, jax.Array], tuple[Params, dict]]


@dataclasses.dataclass(frozen=True)
class CohortResult:
    """Results of one cohort of simultaneously simulated clients."""

    params: Params  # stacked: leaf shape (cohort, ...)
    metrics: dict  # stacked metrics, e.g. loss per client
    num_samples: jax.Array  # (cohort,)


def _stack_params(params: Params, n: int) -> Params:
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), params)


def _shard_over_data(fn, mesh, data_axis: str, n_in: int, n_out: int):
    """Wrap a vmapped fn so every arg/output shards over the mesh data axis.

    The allocator's splits do not divide over the axis in general (a grade's
    last cohort chunk is whatever is left), so a chunk whose rows do not
    divide is padded with zero rows and the padded rows' outputs are
    dropped: every row's result is the one it gets unsharded.  (A constant
    pad partitions cleanly; copies of the last row make the partitioner
    replicate them.)
    """
    from jax.sharding import PartitionSpec as P

    spec = P(data_axis)
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec,) * n_in,
        out_specs=(spec,) * n_out if n_out > 1 else spec,
        check_vma=False,
    )
    shards = int(mesh.shape[data_axis])

    def padded(*args):
        n = jax.tree.leaves(args)[0].shape[0]
        pad = (-n) % shards
        if not pad:
            return sharded(*args)
        grow = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        out = sharded(*jax.tree.map(grow, args))
        return jax.tree.map(lambda x: x[:n], out)

    return padded


@functools.partial(jax.jit, static_argnums=(0, 1))
def _zeros_f32(n: int, sz: int) -> jax.Array:
    # Jitted so the fill constant is baked into the compiled program: an
    # eager ``jnp.zeros`` broadcasts a host scalar — an implicit h2d that
    # trips the hot-path transfer guard (analysis.sanitizers).
    return jnp.zeros((n, sz), jnp.float32)


class _ZeroCopyCohortMixin:
    """Shared zero-copy machinery for the simulation tiers.

    ``run_cohort_zero_copy`` compiles the tier's cohort function with
    ``updates.flatten_rows`` folded onto the output: each update leaf is
    written ONCE, directly in the ``(rows, size)`` ``UpdateBuffer`` layout
    XLA can reduce at matmul speed (an in-graph reshape at aggregation time
    falls off the BLAS/MXU path).  The pytree spec rows materialize to is
    recovered by ``jax.eval_shape`` (abstract — nothing executes) and cached
    per global-params signature.
    """

    _cohort_fn = None  # set by subclasses: (params, batches, rngs) -> (tree, metrics)

    def _zero_copy_machinery(self):
        if getattr(self, "_compiled_zc", None) is None:
            fn = self._cohort_fn

            def zc_fn(global_params, batches, rngs):
                params, metrics = fn(global_params, batches, rngs)
                return flatten_rows(params), metrics

            def zc_fn_recycle(scratch, global_params, batches, rngs):
                # ``scratch`` (a retired round's buffer leaves) is donated:
                # XLA aliases the new update leaves onto its pages, so
                # steady-state rounds allocate nothing buffer-sized — no
                # fresh-page (mmap+zero) cost per round.  ``keep_unused``
                # is REQUIRED: the default jit prunes arguments the traced
                # function never reads, which would silently drop the
                # donation (no aliasing, no invalidation).
                del scratch
                return zc_fn(global_params, batches, rngs)

            self._compiled_zc = jax.jit(zc_fn)
            self._compiled_zc_recycle = jax.jit(
                zc_fn_recycle, donate_argnums=(0,), keep_unused=True)
            self._spec_cache = {}
        return self._compiled_zc

    def run_cohort_zero_copy(
        self,
        global_params: Params,
        batches: Batch,  # leaves shaped (cohort, ...)
        rngs: jax.Array,  # (cohort, key)
        recycle: UpdateBuffer | None = None,
    ) -> tuple[UpdateBuffer, dict]:
        """One fused dispatch producing the chunk's device-resident
        ``UpdateBuffer`` (rows in device order) and stacked metrics.

        ``recycle`` donates a retired buffer of the same layout so the new
        update is written in place of it (see ``HybridSimulation``
        ``recycle_buffers``); the donated buffer's arrays are invalidated.
        """
        compiled = self._zero_copy_machinery()
        spec = self._update_spec(global_params, batches, rngs)
        treedef, shapes, dtypes = spec
        if recycle is not None and not (
                recycle.num_rows == int(rngs.shape[0])
                and recycle.treedef == treedef
                and recycle.shapes == list(shapes)
                and recycle.dtypes == list(dtypes)):
            recycle = None  # layout changed: fall back to fresh allocation
        if recycle is not None:
            donated_leaves = tuple(recycle.leaves2d)
            if sanitizers.enabled():
                # After this dispatch the retired buffer's leaves are dead
                # XLA buffers; poison the object so any late access raises
                # UseAfterDonateError instead of failing deep in XLA.
                sanitizers.poison_donated(recycle)
            leaves2d, metrics = self._compiled_zc_recycle(
                donated_leaves, global_params, batches, rngs)
        else:
            leaves2d, metrics = compiled(global_params, batches, rngs)
        return UpdateBuffer(jax.tree.leaves(leaves2d), *spec), metrics

    def _quantized_machinery(self):
        if getattr(self, "_compiled_q", None) is None:
            fn = self._cohort_fn

            def q_fn(global_params, batches, rngs, residuals):
                # Quantization is fused into the cohort jit: the update
                # leaves are written ONCE, as int8 (rows, size) matrices +
                # f32 (rows,) scale columns — the quantized wire format —
                # and the dense f32 stack never round-trips through HBM.
                # ``residuals`` (None, or one f32 (rows, size) array per
                # leaf) is the error-feedback memory: the previous round's
                # quantization error joins this round's update before
                # quantizing, and the new error is returned to be carried
                # device-resident into the next round.
                params, metrics = fn(global_params, batches, rngs)
                leaves = jax.tree.leaves(flatten_rows(params))
                if residuals is not None:
                    leaves = [l.astype(jnp.float32) + r
                              for l, r in zip(leaves, residuals)]
                q, s, res = quantize_rows(
                    leaves, compute_residual=residuals is not None)
                return tuple(q), tuple(s), res, metrics

            # One jit covers both EF variants: passing residuals=None (an
            # empty pytree) traces the residual-free graph.
            self._compiled_q = jax.jit(q_fn)
        return self._compiled_q

    def run_cohort_quantized(
        self,
        global_params: Params,
        batches: Batch,  # leaves shaped (cohort, ...)
        rngs: jax.Array,  # (cohort, key)
        *,
        residual: "tuple | None" = None,
        error_feedback: bool = True,
    ) -> "tuple[UpdateBuffer, dict, tuple | None]":
        """One fused dispatch producing the chunk's *quantized*
        ``UpdateBuffer`` (``wire="int8"``: int8 leaves + per-row scale
        columns) and, with ``error_feedback``, the device-resident residual
        tuple to carry into this chunk's next round (pass it back as
        ``residual``).  Round 0 (or a layout change) starts from zero
        residuals."""
        self._zero_copy_machinery()  # ensures the spec cache exists
        compiled = self._quantized_machinery()
        spec = self._update_spec(global_params, batches, rngs)
        treedef, shapes, dtypes = spec
        n = int(rngs.shape[0])
        if error_feedback:
            sizes = [int(np.prod(s)) if s else 1 for s in shapes]
            if residual is None or not (
                    len(residual) == len(sizes)
                    and all(tuple(r.shape) == (n, sz)
                            for r, sz in zip(residual, sizes))):
                residual = tuple(_zeros_f32(n, sz) for sz in sizes)
        else:
            residual = None
        q, s, res, metrics = compiled(global_params, batches, rngs, residual)
        buf = UpdateBuffer(list(q), treedef, shapes, dtypes,
                           wire="int8", scales=list(s))
        return buf, metrics, (tuple(res) if error_feedback else None)

    def _update_spec(self, global_params, batches, rngs):
        key = (jax.tree.structure(global_params),) + tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree.leaves(global_params))
        spec = self._spec_cache.get(key)
        if spec is None:
            out = jax.eval_shape(self._cohort_fn, global_params, batches, rngs)
            spec = stacked_spec(out[0])
            self._spec_cache[key] = spec
        return spec

class LogicalTier(_ZeroCopyCohortMixin):
    """Vectorized logical-simulation tier: the f32 side of the operator
    discrepancy, its matmuls at full precision on every backend."""

    def __init__(
        self,
        local_train: LocalTrainFn,
        *,
        mesh: jax.sharding.Mesh | None = None,
        data_axis: str = "data",
        cohort_size: int = 64,
        dtype: Any = jnp.float32,
    ):
        self.local_train = local_train
        self.mesh = mesh
        self.data_axis = data_axis
        self.cohort_size = cohort_size
        self.dtype = dtype
        self._compiled = None

        vmapped = jax.vmap(self.local_train, in_axes=(0, 0, 0))
        if self.mesh is not None:
            vmapped = _shard_over_data(vmapped, self.mesh, self.data_axis, 3, 2)

        def cohort(global_params, batches, rngs):
            # Stack INSIDE the compiled function: XLA fuses the cohort
            # broadcast into the consumers instead of materializing an
            # O(cohort x params) copy of the global params per chunk (the
            # eager broadcast was the round engine's largest hidden
            # allocation at big-model scale).
            n = jax.tree.leaves(batches)[0].shape[0]
            cast = lambda x: (x.astype(self.dtype)
                              if jnp.issubdtype(x.dtype, jnp.floating) else x)
            stacked = jax.tree.map(cast, _stack_params(global_params, n))
            # Matmuls at the tier's own dtype: a TPU's default f32 matmul is
            # one bf16 pass, which would erase the operator discrepancy
            # between this tier and the bf16 device tier.
            with jax.default_matmul_precision("highest"):
                return vmapped(stacked, batches, rngs)

        self._cohort_fn = cohort

    def run_cohort(
        self,
        global_params: Params,
        batches: Batch,  # leaves shaped (cohort, ...)
        rng: jax.Array,
        num_samples: np.ndarray,
    ) -> CohortResult:
        if self._compiled is None:
            self._compiled = jax.jit(self._cohort_fn)
        n = int(jax.tree.leaves(batches)[0].shape[0])
        rngs = jax.random.split(rng, n)
        params, metrics = self._compiled(global_params, batches, rngs)
        return CohortResult(
            params=params, metrics=metrics, num_samples=jnp.asarray(num_samples)
        )


class DeviceTier(_ZeroCopyCohortMixin):
    """Calibrated device-simulation tier for ONE device grade.

    Runs the same local computation through a numerically distinct backend
    dtype (the paper's operator discrepancy) and charges virtual time/energy
    via a persistent ``DeviceFleet`` — one vectorized Table-I sample per
    round, per-device RNG streams that *survive* across rounds (a fresh
    ``DeviceModel`` per call would restart every device's jitter every round).

    ``run_cohort`` is the batched execution path: one vmapped XLA dispatch
    simulates a whole chunk of devices, sharded over the mesh ``data`` axis
    with ``shard_map`` when a ``mesh`` is supplied (same contract as
    ``LogicalTier``); ``run_device`` remains as the single-device view (same
    numerics, same fleet).
    """

    def __init__(
        self,
        local_train: LocalTrainFn,
        grade: DeviceGrade,
        *,
        dtype: Any = jnp.bfloat16,
        seed: int = 0,
        train_cost_scale: float = 1.0,
        cohort_size: int = 256,
        jitter: float = 0.08,
        mesh: jax.sharding.Mesh | None = None,
        data_axis: str = "data",
    ):
        self.grade = grade
        self.dtype = dtype
        self.seed = seed
        self.train_cost_scale = train_cost_scale
        self.cohort_size = cohort_size
        self.local_train = local_train
        self.mesh = mesh
        self.data_axis = data_axis
        self._jit = jax.jit(self._device_step)
        self._vjit = None
        self.fleet = DeviceFleet(grade, 0, seed=seed, jitter=jitter)
        self.reports: list[RoundReport] = []

        vmapped = jax.vmap(self._device_step, in_axes=(0, 0, 0))
        if self.mesh is not None:
            vmapped = _shard_over_data(vmapped, self.mesh, self.data_axis, 3, 2)

        def cohort(global_params, batches, rngs):
            n = jax.tree.leaves(batches)[0].shape[0]
            return vmapped(_stack_params(global_params, n), batches, rngs)

        self._cohort_fn = cohort

    # -- numerically-distinct backend: cast in, compute, cast back ---------
    def _device_step(self, global_params: Params, batch: Batch, rng: jax.Array):
        cast_in = lambda x: (
            x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
        )
        p = jax.tree.map(cast_in, global_params)
        b = jax.tree.map(cast_in, batch)
        new_p, metrics = self.local_train(p, b, rng)
        new_p = jax.tree.map(
            lambda x, ref: x.astype(ref.dtype)
            if jnp.issubdtype(ref.dtype, jnp.floating)
            else x,
            new_p,
            global_params,
        )
        return new_p, metrics

    def run_cohort(
        self,
        global_params: Params,
        batches: Batch,  # leaves shaped (cohort, ...)
        rngs: jax.Array,  # (cohort, key)
    ) -> tuple[Params, dict]:
        """One XLA dispatch simulating a whole device cohort (bf16 backend)."""
        if self._vjit is None:
            self._vjit = jax.jit(self._cohort_fn)
        return self._vjit(global_params, batches, rngs)

    def sample_round(self, device_ids: np.ndarray, round_idx: int
                     ) -> "FleetRoundSample":
        """Vectorized Table-I behavior sample for ``device_ids`` this round."""
        rows = self.fleet.rows_for(np.asarray(device_ids))
        return self.fleet.run_round(
            round_idx, train_cost_scale=self.train_cost_scale, rows=rows)

    def run_device(
        self,
        device_id: int,
        global_params: Params,
        batch: Batch,
        rng: jax.Array,
        round_idx: int,
        *,
        benchmark: bool = False,
    ) -> tuple[Params, dict, RoundReport | None]:
        new_p, metrics = self._jit(global_params, batch, rng)
        report = None
        if benchmark:
            sample = self.sample_round(np.array([device_id]), round_idx)
            report = sample.report(0)
            self.reports.append(report)
        return new_p, metrics, report


# --------------------------------------------------------------------------- #
# Round plans — the allocator's split as an executable object
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class GradePlanEntry:
    """One grade's share of a round: the allocator's (x_i, y_i, q_i)."""

    grade: str
    num_logical: int  # x_i — devices emulated on the logical tier
    num_physical: int  # N_i - q_i - x_i — devices on the device tier
    num_benchmarking: int = 0  # q_i — measured devices (device tier, reports)

    def __post_init__(self):
        if min(self.num_logical, self.num_physical, self.num_benchmarking) < 0:
            raise ValueError("plan entry counts must be non-negative")

    @property
    def num_devices(self) -> int:
        """Total devices of this grade simulated in the round (x + y + q)."""
        return self.num_logical + self.num_physical + self.num_benchmarking


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """Executable per-grade split of one federated round.

    Built directly from the §IV.B allocator's output — ``from_allocation``
    carries each grade's benchmarking count q_i over from its ``GradeSpec``,
    so the devices producing ``RoundReport``s are exactly the ones the
    allocator excluded from the split.
    """

    entries: tuple[GradePlanEntry, ...]

    def __post_init__(self):
        seen = set()
        for e in self.entries:
            if e.grade in seen:
                raise ValueError(f"duplicate grade {e.grade!r} in plan")
            seen.add(e.grade)

    @classmethod
    def from_allocation(cls, result: AllocationResult,
                        specs: Sequence[GradeSpec]) -> "RoundPlan":
        by_grade = {s.grade: s for s in specs}
        entries = []
        for ga in result.per_grade:
            spec = by_grade.get(ga.grade)
            entries.append(GradePlanEntry(
                grade=ga.grade,
                num_logical=ga.logical_devices,
                num_physical=ga.physical_devices,
                num_benchmarking=(spec.benchmarking_devices
                                  if spec is not None else 0),
            ))
        return cls(tuple(entries))

    def entry(self, grade: str) -> GradePlanEntry:
        for e in self.entries:
            if e.grade == grade:
                return e
        raise KeyError(f"grade {grade!r} not in plan")

    @property
    def grades(self) -> tuple[str, ...]:
        return tuple(e.grade for e in self.entries)

    @property
    def total_devices(self) -> int:
        return sum(e.num_devices for e in self.entries)


@dataclasses.dataclass(frozen=True)
class GradeRoundBreakdown:
    """Per-grade outcome of one round (makespan accounting, paper Fig. 7)."""

    grade: str
    num_logical: int
    num_physical: int
    num_benchmarking: int
    makespan_s: float  # slowest sampled device-round completion of the grade
    mean_duration_s: float  # mean sampled round duration across the grade


class ArrivalMessageView:
    """Scalar-``Message`` compat adapter over mixed round emissions.

    Columnar rounds emit ``ArrivalBatch``es (plus scalar q_i benchmarking
    messages); consumers of ``FederatedRoundOutcome.messages`` — launch
    scripts, fault injection, tests — still see one ``Message`` per device.
    Materialization is lazy and cached: the hot path (DeviceFlow submission,
    aggregation) never touches it, so reading ``.messages`` is the only
    thing that pays the per-row object cost.
    """

    __slots__ = ("_emissions", "_mat")

    def __init__(self, emissions: "list[Message | ArrivalBatch]"):
        self._emissions = emissions
        self._mat: list[Message] | None = None

    def _materialize(self) -> list[Message]:
        if self._mat is None:
            out: list[Message] = []
            for e in self._emissions:
                if isinstance(e, ArrivalBatch):
                    out.extend(e.messages())
                else:
                    out.append(e)
            self._mat = out
        return self._mat

    def __len__(self) -> int:
        return sum(e.n if isinstance(e, ArrivalBatch) else 1
                   for e in self._emissions)

    def __iter__(self):
        return iter(self._materialize())

    def __getitem__(self, i):
        return self._materialize()[i]

    def __repr__(self) -> str:
        return f"ArrivalMessageView(n={len(self)})"


@dataclasses.dataclass
class FederatedRoundOutcome:
    num_logical: int
    num_physical: int
    messages: "list[Message] | ArrivalMessageView"
    reports: list[RoundReport]
    arrival_times: np.ndarray | None = None  # per-message virtual times
    per_grade: dict[str, GradeRoundBreakdown] = dataclasses.field(
        default_factory=dict)
    client_metrics: list = dataclasses.field(default_factory=list)
    # Columnar rounds: the raw ArrivalBatch emissions (empty on the scalar
    # plane).  ``messages`` adapts them back to per-row Message views.
    batches: list[ArrivalBatch] = dataclasses.field(default_factory=list)

    @property
    def makespan_s(self) -> float:
        """Round makespan: the slowest grade's slowest sampled device."""
        return max((b.makespan_s for b in self.per_grade.values()), default=0.0)


class HybridSimulation:
    """Drives one federated round across both tiers and feeds DeviceFlow.

    This is the composition point of the paper: allocation decides the
    per-grade split, every grade's tiers execute the same operator flow, and
    results become DeviceFlow messages whose *dispatch* to the cloud follows
    the task's traffic strategy.

    ``tiers`` maps grade name to that grade's ``DeviceTier`` (each with its
    own fleet).  A single ``DeviceTier`` may still be passed positionally for
    the one-grade case; it is wrapped as ``{tier.grade.name: tier}`` and
    remains reachable as ``sim.device``.

    **Zero-copy rounds** (default): cohort outputs stay stacked on device —
    each chunk's result becomes one ``UpdateBuffer`` and every message
    carries an ``UpdateHandle`` (buffer ref + row) instead of a materialized
    host pytree, so the cohort loop never blocks on ``jax.device_get`` and
    chunk k+1 dispatches while chunk k still computes.  Host pytrees are
    materialized only for the q_i benchmarking devices (whose updates ride
    next to their ``RoundReport`` telemetry) — and at checkpoint time, by
    ``Checkpointer`` itself.  ``zero_copy=False`` keeps the PR 2
    host-materializing path as the correctness/perf reference.

    ``stream_chunks=True`` submits each cohort chunk's messages through
    DeviceFlow *as the chunk dispatches* instead of once at round end — the
    feed for streaming aggregation (``AggregationService(streaming=True)``):
    chunk k's ``fed_reduce`` partial fires while chunk k+1 still computes.
    Trade-off: streamed messages are stamped at the clock's current time, so
    per-message arrival-time fidelity (fleet-sampled queuing delay) is
    traded for pipeline overlap; round makespans and ``round_complete``
    timing still come from the fleet sample.  Benchmarking (q_i) rows are
    held back until their handles materialize and submitted last.

    ``recycle_buffers=True`` additionally donates round k's update buffers
    into round k+1's cohort dispatches: XLA writes the new updates in place
    of the retired ones, so steady-state rounds allocate no buffer-sized
    memory at all (at big-model scale, fresh multi-GB allocations cost a
    kernel page-zeroing pass per round).  Only enable it when every handle
    from round k is consumed before round k+1 runs (realtime dispatch with
    an in-round trigger, as in the quickstart); a handle that outlives its
    round would see its buffer invalidated by the donation.

    ``wire="int8"`` makes quantization a property of the wire: every cohort
    chunk's update is quantized *inside* the cohort jit
    (``run_cohort_quantized``) and emitted as an int8 ``UpdateBuffer`` with
    per-row, per-leaf scale columns — DeviceFlow byte accounting sees the
    true ~4x-smaller quantized footprint, and aggregation dequantizes
    in-reduction (``fed_reduce(..., scales=...)``) without ever
    materializing a dense f32 stack.  ``error_feedback=True`` (default)
    keeps convergence honest: each chunk's quantization error stays
    device-resident and is added back into the same chunk's next-round
    update before quantizing (EF-SGD memory, keyed per task/tier/row-range;
    cleared automatically if the chunking or layout changes).
    ``recycle_buffers`` applies only to the f32 wire (int8 leaves have a
    different storage layout than the donated f32 scratch).

    ``payload_transform`` (a callable ``emission -> emission`` over
    ``Message``/``ArrivalBatch``) rewrites every emission *before* it is
    submitted to DeviceFlow — the hook host-side transforms (e.g. top-k
    compression in ``launch/train.py``) use to ride the columnar plane
    instead of bypassing it.  Transforms must preserve ``device_ids`` /
    row counts (arrival times are indexed through them).

    ``workers=N`` (with ``worker_spec=WorkerSpec(factory, ...)``) shards
    cohort-chunk execution across N spawned worker processes
    (``runtime.workers.FleetWorkerPool``), each running its own jitted
    cohort loop; chunk results return as shared-memory-backed
    ``UpdateBuffer``s and re-enter the emission pipeline unchanged, so
    pooled rounds are bit-identical to in-process ones (both wires,
    error-feedback included) while this coordinator keeps DeviceFlow, fleet
    sampling, and aggregation on the authoritative clock.  Call ``close()``
    (or use the context-manager form) to stop the pool and release its
    segments.  Requires ``zero_copy`` rounds; ``worker_pool=`` injects a
    pre-built (e.g. delay-instrumented) pool instead.
    """

    def __init__(
        self,
        logical: LogicalTier,
        device: "DeviceTier | Mapping[str, DeviceTier] | None" = None,
        deviceflow: DeviceFlow | None = None,
        *,
        tiers: Mapping[str, DeviceTier] | None = None,
        zero_copy: bool = True,
        recycle_buffers: bool = False,
        stream_chunks: bool = False,
        columnar: bool = True,
        wire: str = "f32",
        error_feedback: bool = True,
        payload_transform: "Callable | None" = None,
        workers: int = 0,
        worker_spec=None,
        worker_pool=None,
    ):
        if wire not in ("f32", "int8"):
            raise ValueError(f"unknown wire format {wire!r}")
        if wire == "int8" and not zero_copy:
            raise ValueError(
                "wire='int8' requires zero_copy rounds (quantization is "
                "fused into the cohort jit)")
        # Multi-process fleet execution (runtime.workers): cohort chunks run
        # in N worker processes; this coordinator keeps DeviceFlow, fleet
        # sampling and aggregation on the authoritative clock.  The results
        # come back as the same columnar UpdateBuffers (shared-memory
        # backed), so everything downstream is unchanged.
        self._pool = worker_pool
        if workers and worker_pool is None:
            if worker_spec is None:
                raise ValueError(
                    "workers=N requires worker_spec=WorkerSpec(factory, ...)"
                    " — a picklable module-level factory rebuilding "
                    "(logical, tiers) inside each worker process")
            if not zero_copy:
                raise ValueError(
                    "workers=N requires zero_copy rounds (the transport "
                    "ships UpdateBuffer leaves)")
            from repro.runtime.workers import FleetWorkerPool

            self._pool = FleetWorkerPool(worker_spec, workers)
        self.zero_copy = zero_copy
        self.recycle_buffers = recycle_buffers
        self.stream_chunks = stream_chunks
        self.wire = wire
        self.error_feedback = error_feedback
        self.payload_transform = payload_transform
        # Error-feedback memory: (task, tier, global row range) -> residual
        # leaf tuple, device-resident across rounds.
        self._ef_residuals: dict = {}
        # Columnar message plane: zero-copy chunks emit ONE ArrivalBatch per
        # cohort chunk (struct-of-arrays columns + the chunk's UpdateBuffer)
        # instead of one Message object per device — the difference between
        # O(devices) Python and O(chunks) at the 10^6-device scale.  Only
        # meaningful with zero_copy (batches vectorize UpdateHandle rows);
        # ``columnar=False`` keeps the scalar plane as reference.
        self.columnar = columnar
        self._retired: dict = {}  # (tier id, rows) -> [UpdateBuffer]
        self._staged: dict = {}
        self.logical = logical
        if tiers is not None and device is not None:
            raise ValueError("pass either device or tiers, not both")
        if tiers is None:
            if device is None:
                raise ValueError(
                    "pass a DeviceTier or tiers={grade: DeviceTier}")
            tiers = (device if not isinstance(device, DeviceTier)
                     else {device.grade.name: device})
        self.tiers: dict[str, DeviceTier] = dict(tiers)
        if not self.tiers:
            raise ValueError("at least one device tier is required")
        self.deviceflow = deviceflow

    @property
    def device(self) -> DeviceTier:
        """Legacy single-grade view of ``tiers``."""
        if len(self.tiers) != 1:
            raise ValueError(
                f"{len(self.tiers)} device tiers configured; "
                "use sim.tiers[grade]")
        return next(iter(self.tiers.values()))

    @property
    def pool(self):
        """The ``FleetWorkerPool`` driving multi-process rounds (or None)."""
        return self._pool

    @property
    def fleets(self) -> "dict[str, DeviceFleet]":
        """Per-grade fleets, keyed by grade name — the shape
        ``TaskEngine.state_dict(fleets=...)`` folds into the one-manifest
        runtime checkpoint (fleet RNG counters travel with the engine)."""
        return {name: tier.fleet for name, tier in self.tiers.items()}

    def close(self) -> None:
        """Shut down the worker pool (no-op for single-process rounds)."""
        if self._pool is not None:
            self._pool.close()

    def __enter__(self) -> "HybridSimulation":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- shared per-grade execution ----------------------------------------
    @hot_path
    def _run_split(
        self,
        tier: DeviceTier,
        task_id: int,
        round_idx: int,
        global_params: Params,
        client_batches: Batch,
        num_samples: np.ndarray,
        num_logical: int,
        rng: jax.Array,
        *,
        id_offset: int = 0,
        metrics_out: list | None = None,
        materialize_rows: Sequence[int] = (),
    ) -> "tuple[list[Message | ArrivalBatch], jax.Array]":
        """Run one grade's split: [0, num_logical) through the logical tier,
        the rest through ``tier``'s device backend.  Returns the emitted
        arrivals (``device_id`` offset by ``id_offset``) and the advanced rng.

        Zero-copy mode emits ONE columnar ``ArrivalBatch`` per cohort chunk
        (the chunk's device-resident ``UpdateBuffer`` + struct-of-array
        columns); ``materialize_rows`` names the grade-local rows (the q_i
        benchmarking devices) that are instead emitted as scalar ``Message``s
        whose payloads are materialized to host pytrees *after* every chunk
        has been dispatched, so benchmarking never stalls the cohort
        pipeline.  ``columnar=False`` (or the host path) emits one Message
        per device, as before.
        """
        n_total = int(jax.tree.leaves(client_batches)[0].shape[0])
        if not 0 <= num_logical <= n_total:
            raise ValueError("num_logical out of range")

        def take(tree, lo, hi):
            # Static-bound slice for device leaves: eager ``x[lo:hi]``
            # dispatches a dynamic_slice whose start index ships to device
            # as a runtime scalar — an implicit h2d that trips the
            # @hot_path transfer guard.  ``lax.slice_in_dim`` bakes the
            # bounds into the compiled op instead.
            return jax.tree.map(
                lambda x: jax.lax.slice_in_dim(x, lo, hi)
                if isinstance(x, jax.Array) else x[lo:hi], tree)
        emissions: "list[Message | ArrivalBatch]" = []
        # User extension point: transforms may legitimately move data
        # between host and device, so they run outside the hot-path
        # transfer guard (no-op wrapper when sanitizers are off).
        transform = sanitizers.exempt(self.payload_transform)
        mat_set = set(materialize_rows)
        columnar = self.columnar and self.zero_copy
        bench_pos: dict[int, int] = {}  # grade-local row -> emission index

        def emit_batch(buf: UpdateBuffer, lo, hi):
            # Columnar plane: the whole chunk is ONE struct-of-arrays record
            # sharing the chunk's UpdateBuffer — no per-device objects.  The
            # q_i benchmarking rows split out as scalar Messages (their
            # payloads materialize to host pytrees post-round).
            num_samples_arr = np.asarray(num_samples[lo:hi], np.int64)
            bench = sorted(r for r in mat_set if lo <= r < hi)
            prev = lo
            for r in bench + [hi]:
                if r > prev:
                    emissions.append(ArrivalBatch(
                        task_id, round_idx,
                        rows=np.arange(prev - lo, r - lo, dtype=np.int32),
                        num_samples=num_samples_arr[prev - lo:r - lo],
                        device_ids=np.arange(id_offset + prev,
                                             id_offset + r, dtype=np.int64),
                        buffer=buf))
                if r < hi:
                    bench_pos[r] = len(emissions)
                    emissions.append(Message(
                        task_id=task_id,
                        device_id=id_offset + r,
                        round_idx=round_idx,
                        payload=buf.handle(r - lo),
                        num_samples=int(num_samples[r]),
                    ))
                prev = r + 1

        def emit_handles(buf: UpdateBuffer, lo, hi):
            # Zero-copy scalar plane: the chunk's update buffer stays on
            # device; messages carry (buffer, row) handles.  No device_get,
            # no host pytrees — the next chunk dispatches while this one
            # still computes.
            if columnar:
                emit_batch(buf, lo, hi)
                return
            for j in range(hi - lo):
                emissions.append(
                    Message(
                        task_id=task_id,
                        device_id=id_offset + lo + j,
                        round_idx=round_idx,
                        payload=buf.handle(j),
                        num_samples=int(num_samples[lo + j]),
                    )
                )

        def emit_host(stacked_params, lo, hi):
            # Host reference path (PR 2): block on device_get, flatten once
            # per chunk, per-device payloads as cheap leaf-index views.
            host_params = jax.device_get(stacked_params)
            leaves, treedef = jax.tree.flatten(host_params)
            for j in range(hi - lo):
                emissions.append(
                    Message(
                        task_id=task_id,
                        device_id=id_offset + lo + j,
                        round_idx=round_idx,
                        payload=treedef.unflatten([leaf[j] for leaf in leaves]),
                        num_samples=int(num_samples[lo + j]),
                    )
                )

        stream = self.stream_chunks and self.deviceflow is not None

        def stream_chunk(n_before: int) -> None:
            # Streaming feed: this chunk's arrivals enter DeviceFlow now, so
            # a streaming aggregation service fires the chunk's fed_reduce
            # partial while the next chunk's cohort is still computing.  The
            # q_i benchmarking rows are held back until materialization.
            held = set(bench_pos.values()) if columnar else mat_set
            if transform is not None:
                for i in range(n_before, len(emissions)):
                    if i not in held:
                        emissions[i] = transform(emissions[i])
            fresh = [e for i, e in enumerate(emissions[n_before:],
                                             start=n_before)
                     if i not in held]
            if not fresh:
                return
            if any(isinstance(e, ArrivalBatch) for e in fresh):
                self.deviceflow.submit_arrivals(fresh)
            else:
                self.deviceflow.submit_many(fresh)

        def run_chunk(sim_tier, lo, hi, sub):
            # Same per-device rng derivation in both modes (run_cohort splits
            # the chunk key identically), so zero_copy is numerics-preserving.
            # The h2d transfer of the chunk's batch is EXPLICIT (jnp.asarray;
            # free for already-device leaves): _run_split is a @hot_path, so
            # a numpy leaf reaching the cohort jit directly would be an
            # implicit transfer and trip transfer_guard("disallow").
            chunk = jax.tree.map(jnp.asarray, take(client_batches, lo, hi))
            rngs = jax.random.split(sub, hi - lo)
            if self.zero_copy and self.wire == "int8":
                # Quantized wire: the chunk quantizes inside the cohort jit
                # and its error-feedback residual stays device-resident,
                # keyed by (task, tier, global row range) so the same
                # devices' residual carries into their next round.
                ef_key = (task_id, id(sim_tier), id_offset + lo,
                          id_offset + hi)
                buf, metrics, new_res = sim_tier.run_cohort_quantized(
                    global_params, chunk, rngs,
                    residual=self._ef_residuals.get(ef_key),
                    error_feedback=self.error_feedback)
                if self.error_feedback:
                    self._ef_residuals[ef_key] = new_res
                emit_handles(buf, lo, hi)
            elif self.zero_copy:
                # The chunk's stacked output never leaves the device; the
                # next chunk dispatches while this one still computes.
                prev = None
                key = (id(sim_tier), hi - lo)
                if self.recycle_buffers and self._retired.get(key):
                    prev = self._retired[key].pop()
                buf, metrics = sim_tier.run_cohort_zero_copy(
                    global_params, chunk, rngs, recycle=prev)
                if self.recycle_buffers:
                    self._staged.setdefault(key, []).append(buf)
                emit_handles(buf, lo, hi)
            elif sim_tier is self.logical:
                res = sim_tier.run_cohort(
                    global_params, chunk, sub, num_samples[lo:hi])
                metrics = res.metrics
                emit_host(res.params, lo, hi)
            else:
                out_params, metrics = sim_tier.run_cohort(
                    global_params, chunk, rngs)
                emit_host(out_params, lo, hi)
            if metrics_out is not None:
                metrics_out.append(metrics)

        # The chunk plan IS the rng contract: logical cohorts (chunked by
        # cohort_size) then device cohorts, one ``jax.random.split`` per
        # chunk — walked identically whether chunks run inline or across a
        # worker pool, so multi-process rounds stay bit-identical.
        chunk_plan: list[tuple] = []
        idx = 0
        while idx < num_logical:
            hi = min(idx + self.logical.cohort_size, num_logical)
            rng, sub = jax.random.split(rng)
            chunk_plan.append((self.logical, "logical", idx, hi, sub))
            idx = hi
        # Device tier: vectorized cohorts through the bf16 backend — one
        # vmapped dispatch per chunk instead of one jit call per device.
        idx = num_logical
        while idx < n_total:
            hi = min(idx + tier.cohort_size, n_total)
            rng, sub = jax.random.split(rng)
            chunk_plan.append((tier, tier.grade.name, idx, hi, sub))
            idx = hi

        if self._pool is not None and self.zero_copy:
            # Multi-process path: ship the plan to the worker pool; chunk
            # results come back as shared-memory-backed UpdateBuffers and
            # re-enter the exact emission pipeline below.  Without
            # streaming, emissions assemble in CHUNK order (bit-identical
            # to inline); with streaming, in COMPLETION order, overlapping
            # fed_reduce partials with still-running worker shards.
            from repro.runtime.workers import ChunkSpec

            specs_by_kind: dict[str, tuple] = {}
            for sim_tier, kind, lo, hi, _ in chunk_plan:
                if kind in specs_by_kind:
                    continue
                sim_tier._zero_copy_machinery()  # ensures the spec cache
                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        (hi - lo,) + tuple(x.shape[1:]), x.dtype),
                    client_batches)
                specs_by_kind[kind] = sim_tier._update_spec(
                    global_params, abstract,
                    jax.ShapeDtypeStruct((hi - lo, 2), np.uint32))
            wchunks = [
                ChunkSpec(i, kind, lo, hi,
                          np.asarray(sub),  # simcheck: ok[R003] key -> worker
                          id_offset=id_offset)
                for i, (_, kind, lo, hi, sub) in enumerate(chunk_plan)]

            def finish(i, buf, metrics):
                _, _, lo, hi, _ = chunk_plan[i]
                n_before = len(emissions)
                with tracing.span("fl.chunk", round_idx=round_idx):
                    emit_handles(buf, lo, hi)
                if metrics_out is not None:
                    metrics_out.append(metrics)
                if stream:
                    stream_chunk(n_before)

            pooled = self._pool.run_chunks(
                task_id=task_id, round_idx=round_idx, params=global_params,
                batches=client_batches, chunks=wchunks,
                specs_by_kind=specs_by_kind, wire=self.wire,
                error_feedback=self.error_feedback,
                on_result=finish if stream else None)
            if not stream:
                for i, (buf, metrics) in enumerate(pooled):
                    finish(i, buf, metrics)
        else:
            for sim_tier, _, lo, hi, sub in chunk_plan:
                n_before = len(emissions)
                with tracing.span("fl.chunk", round_idx=round_idx):
                    run_chunk(sim_tier, lo, hi, sub)
                if stream:
                    stream_chunk(n_before)

        # Deferred host materialization: only the q_i benchmarking devices'
        # updates become host pytrees, after the whole grade has dispatched.
        # (Columnar mode: bench rows live at ``bench_pos[r]``; scalar mode:
        # emission index == grade-local row.)
        with tracing.span("fl.materialize", round_idx=round_idx):
            for r in materialize_rows:
                i = bench_pos.get(r, r)
                m = emissions[i]
                if isinstance(m.payload, UpdateHandle):
                    emissions[i] = dataclasses.replace(
                        m, payload=m.payload.materialize())
        if transform is not None:
            if stream:
                # Streamed chunks transformed at submit time; only the
                # held-back benchmarking rows remain.
                for r in mat_set:
                    i = bench_pos.get(r, r)
                    emissions[i] = transform(emissions[i])
            else:
                emissions = [transform(e) for e in emissions]
        if stream and mat_set:
            self.deviceflow.submit_many(
                [emissions[bench_pos.get(r, r)] for r in sorted(mat_set)])
        return emissions, rng

    # -- grade-partitioned rounds (allocator-driven) -----------------------
    def run_plan_round(
        self,
        task_id: int,
        round_idx: int,
        global_params: Params,
        plan: RoundPlan,
        grade_batches: Mapping[str, Batch],  # per grade: leaves (N_i, ...)
        grade_num_samples: Mapping[str, np.ndarray],  # per grade: (N_i,)
        rng: jax.Array,
        *,
        calibrator=None,
    ) -> FederatedRoundOutcome:
        """Execute one allocator-planned round across every grade.

        Per grade ``g``: rows ``[0, x_g)`` of ``grade_batches[g]`` run on the
        logical tier, rows ``[x_g, x_g + y_g + q_g)`` through grade ``g``'s
        ``DeviceTier``; the LAST ``q_g`` rows are the benchmarking devices and
        materialize ``RoundReport``s.  Each grade's fleet is sampled once;
        the sampled durations become DeviceFlow arrival times (merged across
        grades) and the per-grade makespan breakdown.  ``calibrator``
        (a ``calibration.RuntimeCalibrator``) observes every grade's sample,
        closing the measurement loop back into ``solve_allocation``.

        The plan may change between rounds of one task: an elastic or
        preemptive ``TaskEngine`` re-solves the allocation mid-task (grant
        top-ups and refreeze-downs), which moves devices between tiers but
        never changes a grade's total — batches stay shaped ``(N_i, ...)``
        across every re-plan.
        """
        with tracing.span("fl.round", round_idx=round_idx):
            return self._plan_round(
                task_id, round_idx, global_params, plan, grade_batches,
                grade_num_samples, rng, calibrator=calibrator)

    def _plan_round(self, task_id, round_idx, global_params, plan,
                    grade_batches, grade_num_samples, rng, *,
                    calibrator) -> FederatedRoundOutcome:
        # Validate the whole plan up front: a failure mid-plan would leave
        # earlier grades' tiers, rng, and the calibrator polluted with a
        # half-executed round.
        per_grade_inputs: list[tuple[GradePlanEntry, Any, np.ndarray, int]] = []
        for entry in plan.entries:
            if entry.grade not in self.tiers:
                raise KeyError(
                    f"plan contains grade {entry.grade!r} but HybridSimulation "
                    f"has tiers for {sorted(self.tiers)}")
            try:
                batches = grade_batches[entry.grade]
                n_samples = np.asarray(grade_num_samples[entry.grade])
            except KeyError:
                raise KeyError(
                    f"grade_batches/grade_num_samples missing grade "
                    f"{entry.grade!r}") from None
            n_total = int(jax.tree.leaves(batches)[0].shape[0])
            if n_total != entry.num_devices:
                raise ValueError(
                    f"grade {entry.grade!r}: batches carry {n_total} devices "
                    f"but the plan requires {entry.num_devices} "
                    f"(x={entry.num_logical} + y={entry.num_physical} + "
                    f"q={entry.num_benchmarking})")
            per_grade_inputs.append((entry, batches, n_samples, n_total))

        emissions: "list[Message | ArrivalBatch]" = []
        reports: list[RoundReport] = []
        arrivals: list[np.ndarray] = []
        breakdown: dict[str, GradeRoundBreakdown] = {}
        client_metrics: list = []
        base = 0.0 if self.deviceflow is None else self.deviceflow.clock.now
        offset = 0
        for entry, batches, n_samples, n_total in per_grade_inputs:
            tier = self.tiers[entry.grade]
            if n_total == 0:
                breakdown[entry.grade] = GradeRoundBreakdown(
                    entry.grade, 0, 0, 0, 0.0, 0.0)
                continue
            grade_emissions, rng = self._run_split(
                tier, task_id, round_idx, global_params, batches, n_samples,
                entry.num_logical, rng, id_offset=offset,
                metrics_out=client_metrics,
                materialize_rows=range(
                    n_total - entry.num_benchmarking, n_total),
            )
            emissions.extend(grade_emissions)

            # Behavioral side: one fleet sample covers the grade (sampled
            # under grade-LOCAL ids so per-device RNG streams stay stable
            # across rounds whatever the plan); the last q_i rows — the
            # allocator-excluded benchmarking devices — also materialize full
            # RoundReports (paper §IV.C) re-stamped with the same global
            # device ids their messages carry.
            with tracing.span("fl.fleet_sample", round_idx=round_idx):
                sample = tier.sample_round(np.arange(n_total), round_idx)
                for k in range(n_total - entry.num_benchmarking, n_total):
                    rep = dataclasses.replace(
                        sample.report(k), device_id=offset + k)
                    reports.append(rep)
                    tier.reports.append(rep)
                if calibrator is not None:
                    calibrator.observe_fleet(sample)
                offsets_s = sample.arrival_offsets_s()
            arrivals.append(base + offsets_s)
            breakdown[entry.grade] = GradeRoundBreakdown(
                grade=entry.grade,
                num_logical=entry.num_logical,
                num_physical=entry.num_physical,
                num_benchmarking=entry.num_benchmarking,
                makespan_s=float(offsets_s.max()),
                mean_duration_s=float(offsets_s.mean()),
            )
            offset += n_total

        arrival_times = (np.concatenate(arrivals) if arrivals else None)
        batches = [e for e in emissions if isinstance(e, ArrivalBatch)]
        if self.deviceflow is not None and emissions:
            if not self.stream_chunks:  # streamed rounds already submitted
                if batches:
                    # Columnar plane: per-row arrival times indexed straight
                    # from the batch's device_ids column — no per-row objects.
                    ts = np.concatenate([
                        arrival_times[e.device_ids]
                        if isinstance(e, ArrivalBatch)
                        else arrival_times[e.device_id:e.device_id + 1]
                        for e in emissions])
                    self.deviceflow.submit_arrivals(emissions, ts=ts)
                else:
                    self.deviceflow.submit_many(emissions, ts=arrival_times)
            # The round ends when the slowest device reports, not at clock.now.
            self.deviceflow.round_complete(
                task_id, t=float(np.max(arrival_times)))
        if self.recycle_buffers:
            self._retired, self._staged = self._staged, {}
        return FederatedRoundOutcome(
            num_logical=sum(e.num_logical for e in plan.entries),
            num_physical=sum(e.num_physical + e.num_benchmarking
                             for e in plan.entries),
            messages=(ArrivalMessageView(emissions) if batches
                      else emissions),
            batches=batches,
            reports=reports,
            arrival_times=arrival_times,
            per_grade=breakdown,
            client_metrics=client_metrics,
        )

    # -- legacy single-grade path ------------------------------------------
    def run_round(
        self,
        task_id: int,
        round_idx: int,
        global_params: Params,
        client_batches: Batch,  # leaves (num_clients, ...)
        num_samples: np.ndarray,  # (num_clients,)
        num_logical: int,
        rng: jax.Array,
        *,
        benchmark_devices: int = 0,
        arrival_times: np.ndarray | None = None,
    ) -> FederatedRoundOutcome:
        """Single-grade round against ``sim.device`` (legacy shape).

        Unlike the plan path, ``benchmark_devices`` picks the FIRST n
        device-tier rows and does not reduce ``num_physical`` — the historic
        ``HybridSimulation(logical, device)`` contract.
        """
        tier = self.device
        n_total = int(jax.tree.leaves(client_batches)[0].shape[0])
        n_bench_rows = min(max(benchmark_devices, 0), n_total - num_logical)
        metrics: list = []
        emissions, _ = self._run_split(
            tier, task_id, round_idx, global_params, client_batches,
            np.asarray(num_samples), num_logical, rng, metrics_out=metrics,
            materialize_rows=range(num_logical, num_logical + n_bench_rows))
        reports: list[RoundReport] = []

        # Behavioral side: one vectorized fleet sample covers every simulated
        # device this round — Table-I durations become arrival times, and the
        # benchmarking subset materializes full RoundReports (paper §IV.C).
        sample: FleetRoundSample | None = None
        if n_total > 0:
            sample = tier.sample_round(np.arange(n_total), round_idx)
        n_bench = min(benchmark_devices, n_total - num_logical)
        for k in range(n_bench):
            rep = sample.report(num_logical + k)
            reports.append(rep)
            tier.reports.append(rep)

        breakdown: dict[str, GradeRoundBreakdown] = {}
        if arrival_times is None and sample is not None:
            base = 0.0 if self.deviceflow is None else self.deviceflow.clock.now
            arrival_times = base + sample.arrival_offsets_s()
        if sample is not None:
            offsets_s = sample.arrival_offsets_s()
            breakdown[tier.grade.name] = GradeRoundBreakdown(
                grade=tier.grade.name,
                num_logical=num_logical,
                num_physical=n_total - num_logical,
                num_benchmarking=n_bench,
                makespan_s=float(offsets_s.max()),
                mean_duration_s=float(offsets_s.mean()),
            )

        batches = [e for e in emissions if isinstance(e, ArrivalBatch)]
        if self.deviceflow is not None:
            if not self.stream_chunks:  # streamed rounds already submitted
                if batches:
                    ts = (None if arrival_times is None else np.concatenate([
                        arrival_times[e.device_ids]
                        if isinstance(e, ArrivalBatch)
                        else arrival_times[e.device_id:e.device_id + 1]
                        for e in emissions]))
                    self.deviceflow.submit_arrivals(emissions, ts=ts)
                else:
                    self.deviceflow.submit_many(emissions, ts=arrival_times)
            # The round ends when the slowest device reports, not at clock.now.
            t_end = (float(np.max(arrival_times))
                     if arrival_times is not None and len(arrival_times)
                     else None)
            self.deviceflow.round_complete(task_id, t=t_end)
        if self.recycle_buffers:
            self._retired, self._staged = self._staged, {}
        return FederatedRoundOutcome(
            num_logical=num_logical,
            num_physical=n_total - num_logical,
            messages=(ArrivalMessageView(emissions) if batches
                      else emissions),
            batches=batches,
            reports=reports,
            arrival_times=arrival_times,
            per_grade=breakdown,
            client_metrics=metrics,
        )
