"""Cloud-side aggregation service (paper §II.A, §VI.C).

Implements the device-cloud collaborative objective
``min_w F(w) = sum_k p_k F_k(w; D_k)`` with FedAvg/FedProx aggregation, plus
the two aggregation *triggers* the paper evaluates (Fig. 9):

* **sample threshold** — aggregate as soon as the accumulated number of client
  samples reaches a threshold;
* **scheduled** — aggregate at fixed virtual-time intervals with whatever has
  arrived.

Beyond-paper: an **async buffered (FedBuff-style)** mode with staleness
discounting — the natural straggler-mitigation extension once DeviceFlow
exposes arrival times.

**Zero-copy aggregation.**  When every pending payload is an
``updates.UpdateHandle`` (the round engine's device-resident stacked buffers),
``aggregate`` never materializes host pytrees: ``fused_fedavg_delta`` groups
the handles by buffer, scatters the staleness-discounted weights into one
per-row weight vector per buffer, and runs a single fused weighted reduction
over each stacked buffer (the ``kernels/fed_reduce`` Pallas kernel on TPU, a
fused ``tensordot`` elsewhere).  The per-message host path below
(``weighted_average``/``fedavg_delta``) is kept as the correctness reference
and still serves mixed/host payloads.

**Streaming chunk aggregation** (``streaming=True``): instead of holding
every pending message until the trigger fires and reducing in one shot, the
service accumulates per-buffer weight vectors as handle deliveries land and
fires a ``fed_reduce`` *partial* the moment a cohort chunk's ``UpdateBuffer``
is fully referenced — FedBuff-style running weighted partial sums, dispatched
asynchronously so reduction overlaps the remaining chunks' compute instead of
serializing after the round.  At trigger time the partials (plus any
incomplete chunks and host-path stragglers) fold into the same server-delta
update the one-shot fused path applies, matching ``fused_fedavg_delta``
numerics to ~1e-6 across chunk orderings and staleness weights.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.deviceflow import (
    ArrivalBatch,
    Delivery,
    Message,
    decode_arrival_batches,
    encode_arrival_batches,
)
from repro.core.updates import UpdateHandle, materialize_handles
from repro.kernels.fed_reduce.ops import fed_reduce

Params = Any  # pytree


def _dev_f32(v) -> jax.Array:
    """Explicit device_put of a host f32 scalar.  A bare ``jnp.float32``/
    numpy scalar reaching a jit is an *implicit* h2d transfer and trips the
    hot-path ``transfer_guard("disallow")`` (analysis.sanitizers)."""
    return jax.device_put(np.float32(v))


def weighted_average(updates: list[Params], weights: list[float]) -> Params:
    """FedAvg: ``sum_k p_k w_k`` with ``p_k`` normalized weights."""
    if not updates:
        raise ValueError("no updates to aggregate")
    tot = float(sum(weights))
    if tot <= 0:
        raise ValueError("weights must sum to a positive value")
    ws = [w / tot for w in weights]

    def avg(*leaves):
        out = leaves[0] * ws[0]
        for leaf, w in zip(leaves[1:], ws[1:]):
            out = out + leaf * w
        return out

    return jax.tree.map(avg, *updates)


def fedavg_delta(global_params: Params, updates: list[Params],
                 weights: list[float], *, server_lr: float = 1.0) -> Params:
    """Server update: ``w <- w + lr * avg_k p_k (w_k - w)`` (equivalent to
    FedAvg at lr=1 but supports server-side learning rates)."""
    avg = weighted_average(updates, weights)
    return jax.tree.map(lambda g, a: g + server_lr * (a - g), global_params, avg)


def _fused_reduce_apply(global_params: Params, buf_leaves: tuple,
                        buf_scales: tuple, wvecs: tuple,
                        inv_total: jax.Array, lr: jax.Array,
                        *, impl: str, mesh=None) -> Params:
    # buf_leaves: one tuple of (rows, size) matrices per buffer, leaf order
    # matching global_params.  Keeping operands 2-D end-to-end is what lets
    # every weighted row-reduction lower to a BLAS/MXU matmul.  ``mesh``
    # (static, a jax.sharding.Mesh) shards every row-reduction over its
    # ``dp`` axis — see ``kernels.fed_reduce.ops.fed_reduce``.
    # ``buf_scales``: per buffer, either None (f32 wire) or one (rows,) f32
    # scale column per leaf (int8 wire) — fed_reduce folds the scales into
    # the weight vector, so quantized buffers reduce without ever
    # materializing a dense f32 copy of the stack.
    weighted_sum = None  # list of (size,) f32 unnormalized weighted sums
    for leaves2d, scales, w in zip(buf_leaves, buf_scales, wvecs):
        parts = [fed_reduce(leaf, w,
                            scales=None if scales is None else scales[k],
                            impl=impl, mesh=mesh)
                 for k, leaf in enumerate(leaves2d)]
        weighted_sum = parts if weighted_sum is None else [
            a + b for a, b in zip(weighted_sum, parts)]
    g_leaves, treedef = jax.tree.flatten(global_params)
    out = [(g + lr * (s.reshape(g.shape) * inv_total - g)).astype(g.dtype)
           for g, s in zip(g_leaves, weighted_sum)]
    return jax.tree_util.tree_unflatten(treedef, out)


# One XLA dispatch per aggregation: every buffer's per-leaf weighted
# row-reduction, the cross-buffer sum, and the server update fuse into a
# single jitted call (eager per-leaf dispatch overhead would otherwise
# dominate).  Two jit instances so donation is a call-site choice, not a
# retrace: the donated variant invalidates the *old* global-params buffer,
# reusing it for the new round's parameters (zero allocation churn between
# rounds).
_FUSED_REDUCE_APPLY = jax.jit(
    _fused_reduce_apply, static_argnames=("impl", "mesh"))
_FUSED_REDUCE_APPLY_DONATED = jax.jit(
    _fused_reduce_apply, static_argnames=("impl", "mesh"),
    donate_argnums=(0,), keep_unused=True)


def _partial_reduce(buf_leaves: tuple, buf_scales, wvec: jax.Array,
                    *, impl: str, mesh=None) -> tuple:
    # One chunk's streaming partial: the weighted row-sum of every leaf of
    # one UpdateBuffer (``buf_scales`` carries the int8 wire's per-leaf
    # scale columns, or None).  Dispatched the moment the chunk fully
    # lands, so the reduction runs (async) while later chunks are still
    # computing.
    return tuple(
        fed_reduce(leaf, wvec,
                   scales=None if buf_scales is None else buf_scales[k],
                   impl=impl, mesh=mesh)
        for k, leaf in enumerate(buf_leaves))


_PARTIAL_REDUCE = jax.jit(_partial_reduce, static_argnames=("impl", "mesh"))


def _apply_weighted_sum(global_params: Params, sum_leaves: tuple,
                        inv_total: jax.Array, lr: jax.Array) -> Params:
    # Trigger-time fold of the streaming partials: same server update the
    # one-shot fused path applies, over pre-reduced weighted sums.
    g_leaves, treedef = jax.tree.flatten(global_params)
    out = [(g + lr * (s.reshape(g.shape) * inv_total - g)).astype(g.dtype)
           for g, s in zip(g_leaves, sum_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


_APPLY_WEIGHTED_SUM = jax.jit(_apply_weighted_sum)
_APPLY_WEIGHTED_SUM_DONATED = jax.jit(
    _apply_weighted_sum, donate_argnums=(0,), keep_unused=True)


@dataclasses.dataclass
class _StreamChunk:
    """Accumulation state for one in-flight cohort chunk (one UpdateBuffer)."""

    buffer: Any  # updates.UpdateBuffer
    weights: np.ndarray  # per-row staleness-discounted weights (f32)
    hits: np.ndarray  # per-row delivery counts (uniform-weight fallback)
    clients: int = 0
    filled: int = 0  # distinct rows seen — O(1) completion test

    def alive(self) -> bool:
        """False once the buffer's arrays were invalidated (e.g. donated by
        ``HybridSimulation(recycle_buffers=True)`` into a later round)."""
        if getattr(type(self.buffer), "__simdc_donated__", False):
            # Sanitizer-poisoned buffer (analysis.sanitizers.poison_donated):
            # leaf access would raise UseAfterDonateError, and by definition
            # a donated buffer is dead.  Probe the class marker instead.
            return False
        return not any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in self.buffer.leaves2d)


def _scales_of(buf) -> "tuple | None":
    """A buffer's per-leaf scale columns as a hashable-by-structure tuple
    (None for the f32 wire) — the ``buf_scales`` pytree fed to the fused
    reduce jits.  Quantized and f32 buffers may mix freely in one
    aggregation; each reduces with its own wire format."""
    scales = getattr(buf, "scales", None)
    return None if scales is None else tuple(scales)


def handles_align(global_params: Params, payloads: list) -> bool:
    """True when every payload is an ``UpdateHandle`` whose buffer layout
    matches ``global_params`` (same treedef, same leaf shapes) — the
    precondition for the fused zero-copy aggregation path.  Quantized
    (``wire="int8"``) buffers align exactly like f32 ones: ``shapes`` always
    describes what rows *materialize* to, and the fused path dequantizes
    in-reduction via the buffer's scale columns."""
    if not payloads or not all(isinstance(p, UpdateHandle) for p in payloads):
        return False
    leaves, treedef = jax.tree.flatten(global_params)
    shapes = [tuple(g.shape) for g in leaves]
    seen: set[int] = set()
    for p in payloads:
        if id(p.buffer) in seen:
            continue
        seen.add(id(p.buffer))
        if p.buffer.treedef != treedef or p.buffer.shapes != shapes:
            return False
    return True


def fused_fedavg_delta(
    global_params: Params,
    handles: list[UpdateHandle],
    weights: list[float],
    *,
    server_lr: float = 1.0,
    impl: str = "auto",
    donate: bool = False,
    mesh=None,
) -> Params:
    """``fedavg_delta`` over device-resident handle payloads, fused.

    Groups ``handles`` by their stacked update buffer, scatters ``weights``
    into one per-row f32 weight vector per buffer (rows not referenced weigh
    zero), reduces each buffer with one ``fed_reduce`` weighted row-sum per
    leaf (the Pallas kernel on TPU), sums the per-buffer partials, and
    applies the server update — without ever materializing a per-device host
    pytree, in one XLA dispatch.  Matches the host ``fedavg_delta``
    reference within accumulation tolerance.

    ``donate=True`` additionally donates the old global-params buffer to the
    server update (the caller's previous reference is invalidated).
    """
    if not handles:
        raise ValueError("no updates to aggregate")
    if not handles_align(global_params, handles):
        raise ValueError(
            "handle buffers do not align with global_params (treedef/shape "
            "mismatch) — materialize and use fedavg_delta instead")
    return _fused_fedavg_delta_validated(
        global_params, handles, weights, server_lr=server_lr, impl=impl,
        donate=donate, mesh=mesh)


def _fused_fedavg_delta_validated(global_params, handles, weights, *,
                                  server_lr, impl, donate, mesh=None):
    # Core of fused_fedavg_delta, after handles_align: the aggregation
    # service calls this directly so the O(pending) alignment pass runs
    # once per aggregation, not twice.
    if not handles:
        raise ValueError("no updates to aggregate")
    total = float(sum(weights))
    if total <= 0:
        raise ValueError("weights must sum to a positive value")
    groups: dict[int, tuple[Any, np.ndarray]] = {}
    for h, w in zip(handles, weights):
        key = id(h.buffer)
        if key not in groups:
            groups[key] = (h.buffer, np.zeros(h.buffer.num_rows, np.float32))
        groups[key][1][h.row] += w
    buf_leaves = tuple(tuple(buf.leaves2d) for buf, _ in groups.values())
    buf_scales = tuple(_scales_of(buf) for buf, _ in groups.values())
    wvecs = tuple(jnp.asarray(wvec) for _, wvec in groups.values())
    apply = _FUSED_REDUCE_APPLY_DONATED if donate else _FUSED_REDUCE_APPLY
    return apply(global_params, buf_leaves, buf_scales, wvecs,
                 _dev_f32(1.0 / total), _dev_f32(server_lr), impl=impl,
                 mesh=mesh)


@dataclasses.dataclass
class AggregationEvent:
    t: float
    round_idx: int
    num_clients: int
    num_samples: int
    global_params: Params
    # Mean shelf-queuing delay of the aggregated updates: delivery time minus
    # ``Message.created_t`` (stamped by DeviceFlow at submit from the fleet's
    # sampled round durations).  Zero only when updates arrive instantly.
    mean_latency_s: float = 0.0


class AggregationService:
    """The paper's *Cloud Service*: consumes DeviceFlow deliveries, fires
    aggregation on a trigger, tracks history for the GUI/metrics stream."""

    def __init__(
        self,
        global_params: Params,
        *,
        trigger: "Trigger",
        server_lr: float = 1.0,
        staleness_discount: Callable[[int], float] | None = None,
        on_aggregate: Callable[[AggregationEvent], None] | None = None,
        reduce_impl: str = "auto",
        donate_params: bool = False,
        streaming: bool = False,
        mesh=None,
    ):
        self.global_params = global_params
        self.trigger = trigger
        self.server_lr = server_lr
        self.staleness_discount = staleness_discount
        self.on_aggregate = on_aggregate
        # Zero-copy path knobs: ``reduce_impl`` selects the fed_reduce
        # backend for handle payloads; ``donate_params`` recycles the old
        # global-params buffer each aggregation.  Donation invalidates the
        # params stored on the *previous* AggregationEvent — leave it off
        # when history params are read back (e.g. per-round eval curves).
        self.reduce_impl = reduce_impl
        self.donate_params = donate_params
        # Streaming chunk aggregation (module docstring): aligned handle
        # payloads accumulate per-buffer weight vectors; each chunk's
        # fed_reduce partial fires as soon as its buffer is fully referenced.
        # Non-handle payloads still take the pending-message path and are
        # folded in at trigger time.
        self.streaming = streaming
        # ``mesh`` (jax.sharding.Mesh with a ``dp`` axis, or None) shards the
        # fused weighted row-reductions across fleet shards — one round's
        # aggregation spans multiple devices/hosts.
        self.mesh = mesh
        self._pending: list[Message] = []
        # Columnar plane: pending ArrivalBatches ride whole (struct-of-array
        # columns, shared buffer) until the trigger fires — no per-row
        # objects.  ``_pending_batch_rows`` keeps client counts O(1).
        self._pending_batches: list[ArrivalBatch] = []
        self._pending_batch_rows = 0
        self._pending_samples = 0
        self._pending_latency = 0.0
        self._chunks: dict[int, _StreamChunk] = {}  # open, by id(buffer)
        self._fired: list[_StreamChunk] = []  # kept for uniform fallback
        self._partials: list[tuple[tuple, float]] = []  # (leaves, weight sum)
        self._stream_clients = 0
        self._g_sig = None  # cached (treedef, shapes) of global_params
        self.round_idx = 0
        self.history: list[AggregationEvent] = []

    # DeviceFlow delivery callback -----------------------------------------
    def __call__(self, d: Delivery) -> None:
        if d.batch is not None:
            self._on_batch(d.t, d.batch)
        else:
            m = d.message
            self._pending_samples += m.num_samples
            # created_t is None for messages delivered without passing
            # through a DeviceFlow Sorter (direct service calls): no
            # queuing, zero latency.
            if m.created_t is not None:
                self._pending_latency += max(0.0, d.t - m.created_t)
            if (self.streaming and isinstance(m.payload, UpdateHandle)
                    and self._stream_aligned(m.payload.buffer)):
                self._stream_add(m)
            else:
                self._pending.append(m)
        if self.trigger.should_fire(self, d.t):
            self.aggregate(d.t)

    def _on_batch(self, t: float, b: ArrivalBatch) -> None:
        """Columnar intake: one ArrivalBatch slice, all accounting
        vectorized — the 10^6-messages/s path never touches per-row
        objects.  A one-row slice (threshold-1 dispatch delivers every
        device alone) does the same accounting on scalars: numpy's
        per-call cost would dwarf one row's work."""
        if b.buffer is None:
            raise ValueError(
                "AggregationService needs buffer-backed ArrivalBatches "
                "(metadata-only batches carry no model update)")
        if b.n == 1:
            self._pending_samples += int(b.num_samples[0])
            created = float(b.created_t[0])
            if created == created:  # NaN: unstamped
                self._pending_latency += max(0.0, t - created)
        else:
            self._pending_samples += b.total_samples
            stamped = ~np.isnan(b.created_t)
            if stamped.any():
                self._pending_latency += float(
                    np.clip(t - b.created_t[stamped], 0.0, None).sum())
        # An open chunk exists only for an aligned buffer.
        if self.streaming and (id(b.buffer) in self._chunks
                               or self._stream_aligned(b.buffer)):
            self._stream_add_batch(b)
        else:
            self._pending_batches.append(b)
            self._pending_batch_rows += b.n

    # -- streaming accumulation --------------------------------------------
    def _weight(self, m: Message) -> float:
        w = float(m.num_samples)
        if self.staleness_discount is not None:
            w *= self.staleness_discount(max(0, self.round_idx - m.round_idx))
        return w

    def _weights_of(self, b: ArrivalBatch) -> np.ndarray:
        """Per-row aggregation weights of a batch (vectorized ``_weight32``)."""
        w = b.num_samples.astype(np.float32)
        if self.staleness_discount is not None:
            w = w * np.float32(self.staleness_discount(
                max(0, self.round_idx - b.round_idx)))
        return w

    def _weight32(self, num_samples, round_idx: int) -> np.float32:
        """One row's streaming weight, in f32 exactly as ``_weights_of``
        computes it, so every intake path leaves bit-identical chunk
        weights for ``fed_reduce``."""
        w = np.float32(num_samples)
        if self.staleness_discount is not None:
            w = w * np.float32(self.staleness_discount(
                max(0, self.round_idx - round_idx)))
        return w

    def _stream_aligned(self, buffer) -> bool:
        sig = self._g_sig
        if sig is None:
            leaves, treedef = jax.tree.flatten(self.global_params)
            sig = self._g_sig = (treedef, [tuple(g.shape) for g in leaves])
        return buffer.treedef == sig[0] and buffer.shapes == sig[1]

    def _stream_add(self, m: Message) -> None:
        h = m.payload
        self._stream_add_row(h.buffer, h.row,
                             self._weight32(m.num_samples, m.round_idx))

    def _chunk(self, buffer) -> _StreamChunk:
        ch = self._chunks.get(id(buffer))
        if ch is None:
            ch = self._chunks[id(buffer)] = _StreamChunk(
                buffer,
                np.zeros(buffer.num_rows, np.float32),
                np.zeros(buffer.num_rows, np.float32))
        return ch

    def _stream_add_row(self, buffer, row: int, w: np.float32) -> None:
        """One row into its chunk, in O(1): the scalar form of
        ``_stream_add_batch``."""
        ch = self._chunk(buffer)
        ch.weights[row] += w
        if ch.hits[row] == 0.0:
            ch.filled += 1
        ch.hits[row] += 1.0
        self._stream_landed(ch, 1)

    def _stream_add_batch(self, b: ArrivalBatch) -> None:
        """Vectorized ``_stream_add_row``: one scatter per batch slice, its
        cost proportional to the slice's rows, never to the chunk's."""
        if b.n == 1:
            self._stream_add_row(b.buffer, int(b.rows[0]),
                                 self._weight32(b.num_samples[0], b.round_idx))
            return
        ch = self._chunk(b.buffer)
        # Rows first seen in this slice, a row repeated in it once.
        ch.filled += np.unique(b.rows[ch.hits[b.rows] == 0.0]).size
        np.add.at(ch.weights, b.rows, self._weights_of(b))
        np.add.at(ch.hits, b.rows, np.float32(1.0))
        self._stream_landed(ch, b.n)

    def _stream_landed(self, ch: _StreamChunk, n: int) -> None:
        ch.clients += n
        self._stream_clients += n
        if ch.filled == ch.buffer.num_rows:
            # The chunk has fully landed: fire its fed_reduce partial now —
            # the (async) reduction overlaps the remaining chunks' compute.
            self._fire_chunk(id(ch.buffer))

    def _fire_chunk(self, key: int) -> None:
        ch = self._chunks.pop(key)
        leaves = _PARTIAL_REDUCE(tuple(ch.buffer.leaves2d),
                                 _scales_of(ch.buffer),
                                 jnp.asarray(ch.weights),
                                 impl=self.reduce_impl, mesh=self.mesh)
        self._partials.append((leaves, float(ch.weights.sum())))
        self._fired.append(ch)

    def tick(self, t: float) -> None:
        """Clock hook for scheduled triggers."""
        if self.trigger.should_fire_on_tick(self, t):
            self.aggregate(t)

    def aggregate(self, t: float) -> AggregationEvent | None:
        with tracing.span("agg.apply", round_idx=self.round_idx):
            return self._aggregate(t)

    def _aggregate(self, t: float) -> AggregationEvent | None:
        n_stream = self._stream_clients
        n_batch = self._pending_batch_rows
        if not self._pending and not n_stream and not n_batch:
            return None
        num_clients = len(self._pending) + n_stream + n_batch
        updates = [m.payload for m in self._pending]
        weights = [self._weight(m) for m in self._pending]
        if n_stream:
            # Streaming mode: pending batches here have foreign buffer
            # layouts (aligned ones streamed into chunks on arrival) —
            # fold them in through the scalar adapter.
            for b in self._pending_batches:
                for m in b.messages():
                    updates.append(m.payload)
                    weights.append(self._weight(m))
            self.global_params = self._aggregate_streaming(updates, weights)
        else:
            # Partition the columnar batches: buffer layouts matching the
            # global params ride the fused path whole; foreign layouts
            # spill through the scalar adapter.
            aligned: list[ArrivalBatch] = []
            for b in self._pending_batches:
                if self._stream_aligned(b.buffer):
                    aligned.append(b)
                else:
                    for m in b.messages():
                        updates.append(m.payload)
                        weights.append(self._weight(m))
            if aligned and updates and not handles_align(
                    self.global_params, updates):
                # Host payloads in the mix demote the whole aggregation to
                # the host reference path (scalar-plane contract): batches
                # join row-by-row via the adapter.
                for b in aligned:
                    for m in b.messages():
                        updates.append(m.payload)
                        weights.append(self._weight(m))
                aligned = []
            if aligned:
                bvecs = [self._weights_of(b) for b in aligned]
                total = (float(sum(weights))
                         + float(sum(v.sum() for v in bvecs)))
                if total <= 0.0:
                    # Uniform fallback, spanning both planes.
                    weights = [1.0] * len(updates)
                    bvecs = [np.ones(b.n, np.float32) for b in aligned]
                    total = float(len(updates)
                                  + sum(b.n for b in aligned))
                self.global_params = self._fused_mixed(
                    aligned, bvecs, updates, weights, total)
            else:
                if sum(weights) <= 0.0:
                    # An aggressive staleness_discount can zero every pending
                    # weight; fall back to uniform weights instead of
                    # crashing the delivery callback mid-flow.
                    weights = [1.0] * len(updates)
                if handles_align(self.global_params, updates):
                    # Zero-copy path: one fused weighted reduction per
                    # stacked buffer, no host materialization.
                    self.global_params = _fused_fedavg_delta_validated(
                        self.global_params, updates, weights,
                        server_lr=self.server_lr, impl=self.reduce_impl,
                        donate=self.donate_params, mesh=self.mesh)
                else:
                    # Host reference path (serves host payloads; stray
                    # handles in a mixed batch are materialized rather than
                    # crashing).
                    updates = [u.materialize() if isinstance(u, UpdateHandle)
                               else u for u in updates]
                    self.global_params = fedavg_delta(
                        self.global_params, updates, weights,
                        server_lr=self.server_lr)
        ev = AggregationEvent(
            t=t,
            round_idx=self.round_idx,
            num_clients=num_clients,
            num_samples=self._pending_samples,
            global_params=self.global_params,
            mean_latency_s=self._pending_latency / num_clients,
        )
        self.history.append(ev)
        self._pending = []
        self._pending_batches = []
        self._pending_batch_rows = 0
        self._pending_samples = 0
        self._pending_latency = 0.0
        self._chunks = {}
        self._fired = []
        self._partials = []
        self._stream_clients = 0
        self.round_idx += 1
        if self.on_aggregate is not None:
            self.on_aggregate(ev)
        return ev

    def _fused_mixed(self, batches: list[ArrivalBatch],
                     bvecs: list[np.ndarray], handles: list[UpdateHandle],
                     weights: list[float], total: float) -> Params:
        """One fused reduction over columnar batches *and* scalar handles:
        both scatter into the same per-buffer weight vectors (a batch is
        just the vectorized form of its rows' handles), then one jitted
        reduce-and-apply dispatch."""
        groups: dict[int, tuple[Any, np.ndarray]] = {}

        def wvec(buf) -> np.ndarray:
            key = id(buf)
            if key not in groups:
                groups[key] = (buf, np.zeros(buf.num_rows, np.float32))
            return groups[key][1]

        for b, v in zip(batches, bvecs):
            np.add.at(wvec(b.buffer), b.rows, v)
        for h, w in zip(handles, weights):
            wvec(h.buffer)[h.row] += w
        buf_leaves = tuple(tuple(buf.leaves2d) for buf, _ in groups.values())
        buf_scales = tuple(_scales_of(buf) for buf, _ in groups.values())
        wvecs = tuple(jnp.asarray(v) for _, v in groups.values())
        apply = (_FUSED_REDUCE_APPLY_DONATED if self.donate_params
                 else _FUSED_REDUCE_APPLY)
        return apply(self.global_params, buf_leaves, buf_scales, wvecs,
                     _dev_f32(1.0 / total), _dev_f32(self.server_lr),
                     impl=self.reduce_impl, mesh=self.mesh)

    def _aggregate_streaming(self, host_updates: list,
                             host_weights: list[float]) -> Params:
        """Fold fired partials + leftover chunks + host stragglers into the
        server update (same math as ``fused_fedavg_delta``)."""
        for key in list(self._chunks):  # chunks the dispatcher cut short
            self._fire_chunk(key)
        total = (sum(w for _, w in self._partials) + sum(host_weights))
        if total <= 0.0:
            # Uniform fallback: re-reduce every chunk with its delivery
            # counts.  Needs the chunk buffers, which are retained until
            # aggregation exactly for this case — but a retained buffer may
            # have been invalidated meanwhile (``recycle_buffers`` donation)
            # and a restored service has none at all (see ``state_dict``);
            # the fallback covers whatever is still alive and keeps the
            # params unchanged when nothing is, instead of crashing the
            # delivery callback on dead device memory.
            alive = [ch for ch in self._fired if ch.alive()]
            if not alive and not host_updates:
                return self.global_params
            self._partials = [
                (_PARTIAL_REDUCE(tuple(ch.buffer.leaves2d),
                                 _scales_of(ch.buffer),
                                 jnp.asarray(ch.hits), impl=self.reduce_impl,
                                 mesh=self.mesh),
                 float(ch.hits.sum()))
                for ch in alive]
            host_weights = [1.0] * len(host_updates)
            total = (sum(w for _, w in self._partials) + sum(host_weights))
        summed = None
        for leaves, _ in self._partials:
            summed = (list(leaves) if summed is None
                      else [a + b for a, b in zip(summed, leaves)])
        if host_updates:
            # Host-path stragglers (non-handle payloads): their f32 weighted
            # sum joins the partials as one extra term.
            host_updates = [u.materialize() if isinstance(u, UpdateHandle)
                            else u for u in host_updates]
            hs = None
            for u, w in zip(host_updates, host_weights):
                leaves = [np.asarray(l, np.float32).reshape(-1)
                          * np.float32(w) for l in jax.tree.leaves(u)]
                hs = (leaves if hs is None
                      else [a + b for a, b in zip(hs, leaves)])
            summed = (list(map(jnp.asarray, hs)) if summed is None
                      else [a + jnp.asarray(b) for a, b in zip(summed, hs)])
        apply = (_APPLY_WEIGHTED_SUM_DONATED if self.donate_params
                 else _APPLY_WEIGHTED_SUM)
        return apply(self.global_params, tuple(summed),
                     _dev_f32(1.0 / total), _dev_f32(self.server_lr))

    # -- checkpointing -------------------------------------------------------
    def state_dict(self) -> dict:
        """Resume-safe aggregation state.

        Open streaming chunks are flushed to partials first, and partial
        sums are materialized to host arrays, so the result holds no live
        device references.  (A restored service cannot apply the
        uniform-weight fallback for pre-checkpoint partials — the chunk
        buffers are gone; it keeps the params unchanged in that edge case.)
        """
        for key in list(self._chunks):
            self._fire_chunk(key)

        def enc_msg(m: Message) -> dict:
            return {"task_id": m.task_id, "device_id": m.device_id,
                    "round_idx": m.round_idx, "num_samples": m.num_samples,
                    "created_t": m.created_t, "size_bytes": m.size_bytes,
                    "payload": materialize_handles(m.payload)}

        return {
            "round_idx": self.round_idx,
            "pending": [enc_msg(m) for m in self._pending],
            # Columnar plane: pending batches round-trip as struct-of-array
            # state (host columns + deduplicated buffer snapshots), so a
            # mid-round snapshot with in-flight batches restores to the
            # identical aggregation timeline.
            "pending_batches": encode_arrival_batches(self._pending_batches),
            "pending_samples": self._pending_samples,
            "pending_latency": self._pending_latency,
            "stream_clients": self._stream_clients,
            "partials": [
                {"leaves": [np.asarray(l) for l in leaves], "weight": w}
                for leaves, w in self._partials],
        }

    def load_state_dict(self, d: dict) -> None:
        self.round_idx = int(d["round_idx"])
        self._pending = [Message(**m) for m in d["pending"]]
        self._pending_batches = decode_arrival_batches(
            d.get("pending_batches", {}))
        self._pending_batch_rows = sum(b.n for b in self._pending_batches)
        self._pending_samples = int(d["pending_samples"])
        self._pending_latency = float(d["pending_latency"])
        self._stream_clients = int(d.get("stream_clients", 0))
        self._partials = [
            (tuple(jnp.asarray(l) for l in p["leaves"]), float(p["weight"]))
            for p in d.get("partials", ())]
        self._chunks = {}
        self._fired = []
        self._g_sig = None

    @property
    def pending_samples(self) -> int:
        return self._pending_samples

    @property
    def pending_clients(self) -> int:
        return (len(self._pending) + self._stream_clients
                + self._pending_batch_rows)


class Trigger:
    def should_fire(self, svc: AggregationService, t: float) -> bool:
        return False

    def should_fire_on_tick(self, svc: AggregationService, t: float) -> bool:
        return False


@dataclasses.dataclass
class SampleThresholdTrigger(Trigger):
    """Aggregate when accumulated edge training samples reach a threshold."""

    threshold: int

    def should_fire(self, svc: AggregationService, t: float) -> bool:
        return svc.pending_samples >= self.threshold


@dataclasses.dataclass
class ClientCountTrigger(Trigger):
    """Aggregate when K client updates have arrived (FedBuff buffer size)."""

    k: int

    def should_fire(self, svc: AggregationService, t: float) -> bool:
        return svc.pending_clients >= self.k


@dataclasses.dataclass
class ScheduledTrigger(Trigger):
    """Aggregate every ``period`` virtual seconds (paper: scheduled times)."""

    period: float
    _last: float = 0.0

    def should_fire_on_tick(self, svc: AggregationService, t: float) -> bool:
        if t - self._last >= self.period - 1e-9 and svc.pending_clients > 0:
            # Snap forward on the fixed grid rather than re-anchoring to the
            # tick's arrival time — aggregation stays on the paper's
            # "scheduled times" instead of drifting by the tick jitter.  The
            # max(1, ...) guards the fire-condition tolerance: a tick landing
            # a hair below the grid point must still advance the grid.
            self._last += self.period * max(1, math.floor(
                (t - self._last + 1e-9) / self.period))
            return True
        return False


def polynomial_staleness(alpha: float = 0.5) -> Callable[[int], float]:
    """FedBuff-style ``(1 + s)^-alpha`` staleness discount."""
    return lambda s: (1.0 + s) ** (-alpha)
