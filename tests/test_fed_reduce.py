"""Zero-copy round pipeline: fed_reduce kernel, handles, donation, sizes."""
import dataclasses
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.deviceflow import (
    ArrivalBatch, DeviceFlow, Delivery, Message, payload_nbytes)
from repro.core.devicemodel import GRADES
from repro.core.federation import (
    AggregationService,
    ClientCountTrigger,
    fedavg_delta,
    fused_fedavg_delta,
    handles_align,
    polynomial_staleness,
)
from repro.core.simulation import DeviceTier, HybridSimulation, LogicalTier
from repro.core.strategies import AccumulatedStrategy
from repro.core.updates import UpdateBuffer, UpdateHandle, materialize_handles
from repro.kernels.fed_reduce.ops import fed_reduce
from repro.models import ctr as ctr_lib


def _rand_tree(rng, n, dtype):
    return {
        "w": jnp.asarray(rng.standard_normal((n, 4, 8)), dtype),
        "b": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32),
    }


# --------------------------------------------------------------------------- #
# Kernel vs host reference (interpret mode — the CPU CI path)
# --------------------------------------------------------------------------- #
@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 12), seed=st.integers(0, 10_000),
       use_bf16=st.integers(0, 1), weight_scale=st.floats(0.1, 50.0))
def test_fused_fedavg_matches_host_reference(n, seed, use_bf16, weight_scale):
    """Property: the Pallas fed-reduce path (interpret mode) reproduces the
    host per-message ``fedavg_delta`` chain across dtypes and weights."""
    rng = np.random.default_rng(seed)
    dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    stacked = _rand_tree(rng, n, dtype)
    global_params = {
        "w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(3), jnp.float32),
    }
    weights = (rng.random(n) * weight_scale + 1e-3).tolist()

    host_updates = [
        jax.tree.map(lambda x: np.asarray(x[i], np.float32), stacked)
        for i in range(n)
    ]
    want = fedavg_delta(global_params, host_updates, weights, server_lr=0.7)

    buf = UpdateBuffer.from_stacked(stacked)
    got = fused_fedavg_delta(global_params, buf.handles(), weights,
                             server_lr=0.7, impl="pallas_interpret")
    tol = 3e-2 if use_bf16 else 1e-5
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 64), d=st.integers(1, 300), seed=st.integers(0, 999))
def test_fed_reduce_kernel_matches_ref_impl(n, d, seed):
    rng = np.random.default_rng(seed)
    stack = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.random(n), jnp.float32)
    ref = fed_reduce(stack, w, impl="ref")
    pal = fed_reduce(stack, w, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_zero_staleness_weights_fall_back_to_uniform():
    """All-zero staleness weights must hit the uniform fallback on the
    zero-copy path too (not crash the delivery callback)."""
    stacked = {"w": jnp.asarray([[2.0], [4.0]])}
    buf = UpdateBuffer.from_stacked(stacked)
    svc = AggregationService(
        {"w": jnp.zeros(1)},
        trigger=ClientCountTrigger(2),
        staleness_discount=lambda s: 0.0,
    )
    for i, h in enumerate(buf.handles()):
        svc(Delivery(t=0.0, message=Message(0, i, 0, h, num_samples=i + 1)))
    assert len(svc.history) == 1
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]), [3.0])


def test_fused_rejects_misaligned_handles():
    stacked = {"other": jnp.ones((2, 3))}
    buf = UpdateBuffer.from_stacked(stacked)
    g = {"w": jnp.zeros(3)}
    assert not handles_align(g, buf.handles())
    with pytest.raises(ValueError, match="align"):
        fused_fedavg_delta(g, buf.handles(), [1.0, 1.0])


def test_service_materializes_mixed_payload_batch():
    """A mixed handle/host pending set must aggregate via the host reference
    path (handles materialized), not crash."""
    buf = UpdateBuffer.from_stacked({"w": jnp.asarray([[2.0]])})
    svc = AggregationService({"w": jnp.zeros(1)},
                             trigger=ClientCountTrigger(2))
    svc(Delivery(t=0.0, message=Message(0, 0, 0, buf.handle(0),
                                        num_samples=1)))
    svc(Delivery(t=0.0, message=Message(0, 1, 0, {"w": np.array([4.0])},
                                        num_samples=1)))
    assert len(svc.history) == 1
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]), [3.0])


# --------------------------------------------------------------------------- #
# Donation — the old global-params buffer is actually invalidated
# --------------------------------------------------------------------------- #
def test_donation_invalidates_old_global_params():
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)}
    buf = UpdateBuffer.from_stacked(stacked)
    keep = {"w": jnp.asarray(rng.standard_normal(8), jnp.float32)}
    out = fused_fedavg_delta(keep, buf.handles(), [1.0] * 4, donate=False)
    assert not keep["w"].is_deleted()

    donated = {"w": jnp.asarray(rng.standard_normal(8), jnp.float32)}
    out2 = fused_fedavg_delta(donated, buf.handles(), [1.0] * 4, donate=True)
    assert donated["w"].is_deleted()
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(out2["w"]),
                               atol=2e-6)


def test_recycle_buffers_donates_retired_round_buffers():
    """``recycle_buffers=True`` must actually donate: round k's update
    buffers are invalidated when round k+1 writes in their place (guards
    against jit pruning the unused donated arg — keep_unused)."""
    from repro.core.federation import SampleThresholdTrigger

    local, params, batches, counts = _round_setup()
    svc = AggregationService(
        jax.tree.map(jnp.array, params),
        trigger=SampleThresholdTrigger(int(counts.sum())))
    flow = DeviceFlow(svc)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(LogicalTier(local, cohort_size=16),
                           DeviceTier(local, GRADES["High"]),
                           deviceflow=flow, zero_copy=True,
                           recycle_buffers=True)
    out0 = sim.run_round(0, 0, svc.global_params, batches, counts, 12,
                         jax.random.PRNGKey(0))
    bufs0 = {id(m.payload.buffer): m.payload.buffer for m in out0.messages}
    assert all(not leaf.is_deleted()
               for b in bufs0.values() for leaf in b.leaves2d)
    sim.run_round(0, 1, svc.global_params, batches, counts, 12,
                  jax.random.PRNGKey(1))

    # Round 1 recycled round 0's retired buffers: their arrays are gone.
    # Under SIMDC_SANITIZE the donated buffers are class-poisoned instead
    # (leaf access raises UseAfterDonateError), which proves the same thing.
    def donated(b):
        return (getattr(type(b), "__simdc_donated__", False)
                or all(leaf.is_deleted() for leaf in b.leaves2d))

    assert all(donated(b) for b in bufs0.values())


def test_service_donate_params_recycles_buffers():
    buf = UpdateBuffer.from_stacked({"w": jnp.asarray([[1.0], [3.0]])})
    svc = AggregationService({"w": jnp.zeros(1)},
                             trigger=ClientCountTrigger(2),
                             donate_params=True)
    g0 = svc.global_params
    for i, h in enumerate(buf.handles()):
        svc(Delivery(t=0.0, message=Message(0, i, 0, h, num_samples=1)))
    assert len(svc.history) == 1
    assert g0["w"].is_deleted()  # donated into the new round's params
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]), [2.0])


# --------------------------------------------------------------------------- #
# Round engine: zero-copy path reproduces the host-materializing path
# --------------------------------------------------------------------------- #
def _round_setup(n=12, rpd=8, dim=16):
    from repro.data.synthetic_ctr import make_federated_ctr
    data = make_federated_ctr(num_devices=n, records_per_device=rpd,
                              dim=dim, seed=0)
    local = ctr_lib.make_local_train_fn(lr=1e-2, epochs=2)
    params = ctr_lib.lr_init(jax.random.PRNGKey(0), dim)
    X, Y, counts = data.stacked_shards(np.arange(n), rpd)
    mask = (np.arange(rpd)[None] < counts[:, None]).astype(np.float32)
    batches = {"x": jnp.asarray(X), "y": jnp.asarray(Y),
               "mask": jnp.asarray(mask)}
    return local, params, batches, counts


@pytest.mark.parametrize("num_logical", [12, 7, 0])
def test_zero_copy_round_matches_host_round(num_logical):
    from repro.core.federation import SampleThresholdTrigger

    def run(zero_copy):
        local, params, batches, counts = _round_setup()
        svc = AggregationService(
            jax.tree.map(jnp.array, params),
            trigger=SampleThresholdTrigger(int(counts.sum())))
        flow = DeviceFlow(svc)
        flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
        sim = HybridSimulation(LogicalTier(local, cohort_size=5),
                               DeviceTier(local, GRADES["High"],
                                          cohort_size=4),
                               deviceflow=flow, zero_copy=zero_copy)
        for rnd in range(2):
            out = sim.run_round(0, rnd, svc.global_params, batches, counts,
                                num_logical, jax.random.PRNGKey(rnd),
                                benchmark_devices=2)
        return svc.global_params, out

    (pa, outa), (pb, outb) = run(True), run(False)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # Zero-copy: handle payloads except the benchmarking devices' rows,
    # which materialize to host pytrees (and only those).
    n_handles = sum(isinstance(m.payload, UpdateHandle)
                    for m in outa.messages)
    n_host = sum(isinstance(m.payload, dict) for m in outa.messages)
    n_bench = min(2, 12 - num_logical)
    assert n_host == n_bench and n_handles == 12 - n_bench
    # Host path: everything materialized.
    assert all(isinstance(m.payload, dict) for m in outb.messages)
    # Handle payloads report the real per-row update size.
    if n_handles:
        h = next(m for m in outa.messages
                 if isinstance(m.payload, UpdateHandle))
        ref = next(m for m in outb.messages)
        assert h.size_bytes == ref.size_bytes > 0


def test_plan_round_materializes_only_benchmarking_tail():
    """Grade-partitioned rounds: the q_i allocator-excluded tail rows carry
    host pytrees; every other message carries a handle."""
    from repro.core.simulation import GradePlanEntry, RoundPlan

    local, params, batches, counts = _round_setup(n=10)
    plan = RoundPlan((GradePlanEntry("High", 4, 4, 2),))
    sim = HybridSimulation(
        LogicalTier(local, cohort_size=4),
        tiers={"High": DeviceTier(local, GRADES["High"], cohort_size=4)})
    out = sim.run_plan_round(0, 0, params, plan, {"High": batches},
                             {"High": counts}, jax.random.PRNGKey(0))
    by_id = {m.device_id: m.payload for m in out.messages}
    for dev in range(8):
        assert isinstance(by_id[dev], UpdateHandle)
    for dev in (8, 9):  # q_i tail
        assert isinstance(by_id[dev], dict)


# --------------------------------------------------------------------------- #
# Message slots / auto size accounting / Shelf byte counters
# --------------------------------------------------------------------------- #
def test_message_is_slotted_weakrefable_and_sizes_payloads():
    m = Message(0, 1, 2, {"w": np.zeros((4, 4), np.float32),
                          "b": np.zeros(3)})
    assert not hasattr(m, "__dict__")
    assert weakref.ref(m)() is m
    assert m.size_bytes == 4 * 4 * 4 + 3 * 8
    # replace() keeps the computed size; explicit size wins over payload.
    assert dataclasses.replace(m, created_t=1.0).size_bytes == m.size_bytes
    assert Message(0, 0, 0, None, size_bytes=77).size_bytes == 77
    assert Message(0, 0, 0, payload=5).size_bytes == 0
    assert payload_nbytes([np.zeros(2), {"x": np.zeros(3)}]) == 2 * 8 + 3 * 8


def test_shelf_tracks_real_traffic_bytes():
    got = []
    flow = DeviceFlow(got.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(2,)))
    buf = UpdateBuffer.from_stacked({"w": jnp.zeros((3, 5), jnp.float32)})
    for i in range(3):
        flow.submit(Message(0, i, 0, buf.handle(i)), t=1.0)
    shelf = flow.shelf(0)
    assert shelf.total_bytes_received == 3 * 20
    assert shelf.total_bytes_dispatched == 2 * 20  # one message still shelved
    state = flow.state_dict()
    restored = DeviceFlow(got.append)
    restored.register_task(0, AccumulatedStrategy(thresholds=(2,)))
    restored.load_state_dict(state)
    assert restored.shelf(0).total_bytes_received == 3 * 20


# --------------------------------------------------------------------------- #
# Checkpointing materializes handles
# --------------------------------------------------------------------------- #
def test_checkpointer_materializes_handles(tmp_path):
    stacked = {"w": jnp.asarray(np.arange(6, dtype=np.float32).reshape(3, 2))}
    buf = UpdateBuffer.from_stacked(stacked)
    tree = {"pending": buf.handle(1), "step": jnp.asarray(4)}
    ck = Checkpointer(tmp_path)
    ck.save(1, tree)
    like = {"pending": {"w": np.zeros(2, np.float32)},
            "step": np.asarray(0)}
    restored, _ = ck.restore(like)
    np.testing.assert_array_equal(restored["pending"]["w"], [2.0, 3.0])

    host = materialize_handles({"a": [buf.handle(0)], "b": buf})
    np.testing.assert_array_equal(host["a"][0]["w"], [0.0, 1.0])
    assert host["b"]["w"].shape == (3, 2)


# --------------------------------------------------------------------------- #
# Streaming chunk aggregation matches the one-shot fused path
# --------------------------------------------------------------------------- #
def _stream_tree(rng, n):
    return {
        "w": jnp.asarray(rng.standard_normal((n, 4, 8)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32),
    }


@settings(max_examples=15, deadline=None)
@given(chunks=st.lists(st.integers(1, 6), min_size=1, max_size=4),
       seed=st.integers(0, 10_000), alpha=st.floats(0.0, 2.0),
       order_seed=st.integers(0, 10_000))
def test_streaming_matches_one_shot_across_chunk_orderings(
        chunks, seed, alpha, order_seed):
    """Property: streaming per-chunk partial aggregation reproduces the
    one-shot ``fused_fedavg_delta`` result to 1e-6, whatever the chunk
    sizes, global delivery order, and staleness weights."""
    from repro.core.federation import polynomial_staleness

    rng = np.random.default_rng(seed)
    global_params = {
        "w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(3), jnp.float32),
    }
    buffers = [UpdateBuffer.from_stacked(_stream_tree(rng, n))
               for n in chunks]
    msgs = [Message(0, dev, int(rng.integers(0, 4)), buf.handle(row),
                    num_samples=int(rng.integers(1, 6)))
            for dev, (buf, row) in enumerate(
                (b, r) for b in buffers for r in range(b.num_rows))]

    def run(streaming, order):
        svc = AggregationService(
            jax.tree.map(jnp.array, global_params),
            trigger=ClientCountTrigger(len(msgs)),
            staleness_discount=polynomial_staleness(alpha),
            streaming=streaming)
        svc.round_idx = 3  # message round_idx in [0, 3] -> staleness > 0
        for i in order:
            svc(Delivery(t=float(i), message=msgs[i]))
        assert len(svc.history) == 1
        return svc.global_params

    one_shot = run(False, range(len(msgs)))
    perm = np.random.default_rng(order_seed).permutation(len(msgs))
    streamed = run(True, perm)
    for a, b in zip(jax.tree.leaves(streamed), jax.tree.leaves(one_shot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_streaming_fires_partials_before_trigger():
    """The point of streaming: a chunk's fed_reduce partial fires as soon as
    the chunk's buffer has fully landed — not at trigger time."""
    bufs = [UpdateBuffer.from_stacked({"w": jnp.ones((3, 2))}),
            UpdateBuffer.from_stacked({"w": jnp.full((2, 2), 2.0)})]
    svc = AggregationService({"w": jnp.zeros(2)},
                             trigger=ClientCountTrigger(5), streaming=True)
    for i, h in enumerate(bufs[0].handles()):
        svc(Delivery(t=0.0, message=Message(0, i, 0, h, num_samples=1)))
    assert len(svc._partials) == 1  # chunk 0 complete -> partial fired
    assert len(svc.history) == 0  # trigger has not fired yet
    assert svc.pending_clients == 3
    for i, h in enumerate(bufs[1].handles()):
        svc(Delivery(t=0.0, message=Message(0, 3 + i, 0, h, num_samples=1)))
    assert len(svc.history) == 1
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]),
                               [1.4, 1.4])  # (3*1 + 2*2) / 5


def test_streaming_zero_weights_fall_back_to_uniform():
    buf = UpdateBuffer.from_stacked({"w": jnp.asarray([[2.0], [4.0]])})
    svc = AggregationService(
        {"w": jnp.zeros(1)}, trigger=ClientCountTrigger(2),
        staleness_discount=lambda s: 0.0, streaming=True)
    for i, h in enumerate(buf.handles()):
        svc(Delivery(t=0.0, message=Message(0, i, 0, h, num_samples=i + 1)))
    assert len(svc.history) == 1
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]), [3.0])


def test_streaming_folds_in_host_path_stragglers():
    """Non-handle payloads delivered alongside streamed chunks join the fold
    as a host-side weighted sum."""
    buf = UpdateBuffer.from_stacked({"w": jnp.asarray([[2.0]])})
    svc = AggregationService({"w": jnp.zeros(1)},
                             trigger=ClientCountTrigger(2), streaming=True)
    svc(Delivery(t=0.0, message=Message(0, 0, 0, buf.handle(0),
                                        num_samples=1)))
    svc(Delivery(t=0.0, message=Message(0, 1, 0, {"w": np.array([4.0])},
                                        num_samples=3)))
    assert len(svc.history) == 1
    np.testing.assert_allclose(np.asarray(svc.global_params["w"]),
                               [(2.0 + 3 * 4.0) / 4.0])


def test_streaming_state_dict_roundtrip():
    """Partially-aggregated streaming state survives save/load: restored
    partials fold into the same aggregate."""
    bufs = [UpdateBuffer.from_stacked({"w": jnp.asarray([[2.0], [4.0]])}),
            UpdateBuffer.from_stacked({"w": jnp.asarray([[6.0]])})]

    def feed(svc, upto):
        handles = [(b, r) for b in bufs for r in range(b.num_rows)]
        for i, (b, r) in enumerate(handles[:upto]):
            svc(Delivery(t=0.0, message=Message(0, i, 0, b.handle(r),
                                                num_samples=1)))

    ref = AggregationService({"w": jnp.zeros(1)},
                             trigger=ClientCountTrigger(3), streaming=True)
    feed(ref, 3)

    svc1 = AggregationService({"w": jnp.zeros(1)},
                              trigger=ClientCountTrigger(3), streaming=True)
    feed(svc1, 2)  # chunk 0 fired, trigger not yet
    state = svc1.state_dict()
    svc2 = AggregationService({"w": jnp.zeros(1)},
                              trigger=ClientCountTrigger(3), streaming=True)
    svc2.load_state_dict(state)
    svc2(Delivery(t=0.0, message=Message(0, 2, 0, bufs[1].handle(0),
                                         num_samples=1)))
    assert len(svc2.history) == 1
    np.testing.assert_allclose(np.asarray(svc2.global_params["w"]),
                               np.asarray(ref.global_params["w"]))

    # The scalar path: one-row slices over two rounds, snapshotted with
    # chunk 1 partly filled, restore to the uninterrupted run's timeline.
    bufs = [UpdateBuffer.from_stacked({"w": jnp.asarray([[2.0], [4.0]])}),
            UpdateBuffer.from_stacked({"w": jnp.asarray([[6.0], [8.0],
                                                         [10.0]])})]
    order = [(0, 0), (1, 0), (0, 1), (1, 1), (1, 2)]
    deliveries = [
        Delivery(float(4 * r + i), batch=ArrivalBatch(
            0, r, [row], created_t=[float(i) if i % 2 else np.nan],
            num_samples=[1 + row], buffer=bufs[k]))
        for r in range(2) for i, (k, row) in enumerate(order)]

    def timeline(svc):
        return [(ev.t, ev.round_idx, ev.num_clients, ev.num_samples,
                 ev.mean_latency_s, np.asarray(ev.global_params["w"]))
                for ev in svc.history]

    ref = AggregationService({"w": jnp.zeros(1)},
                             trigger=ClientCountTrigger(5), streaming=True)
    for d in deliveries:
        ref(d)
    svc1 = AggregationService({"w": jnp.zeros(1)},
                              trigger=ClientCountTrigger(5), streaming=True)
    for d in deliveries[:4]:
        svc1(d)
    assert svc1._chunks[id(bufs[1])].filled == 2  # of 3 rows
    svc2 = AggregationService({"w": jnp.zeros(1)},
                              trigger=ClientCountTrigger(5), streaming=True)
    svc2.load_state_dict(svc1.state_dict())
    for d in deliveries[4:]:
        svc2(d)
    got, want = timeline(svc2), timeline(ref)
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        assert g[:5] == w[:5]
        np.testing.assert_array_equal(g[5], w[5])


def _stream_rows(rng, n_rows, n_extra):
    """One chunk's delivery order: every row once, plus ``n_extra``
    repeats of rows already seen, with the completing row last.  (A row
    repeated after the chunk completed opens a new chunk, which a slice
    holding both rows cannot mirror, so every path is fed repeats only
    before completion.)"""
    perm = rng.permutation(n_rows)
    seq = list(perm[:-1])
    for _ in range(n_extra if n_rows > 1 else 0):
        row = perm[int(rng.integers(0, n_rows - 1))]
        first = seq.index(row)
        seq.insert(int(rng.integers(first + 1, len(seq) + 1)), row)
    return np.asarray(seq + [perm[-1]], np.int32)


def _intake_state(svc):
    """Everything the streaming intake accumulates, in exact (byte) form."""
    return (
        svc._pending_samples, svc._pending_latency, svc._stream_clients,
        svc.round_idx,
        {key: (ch.weights.tobytes(), ch.hits.tobytes(), ch.filled,
               ch.clients) for key, ch in svc._chunks.items()},
        [id(ch.buffer) for ch in svc._fired],
        [(w, [np.asarray(leaf).tobytes() for leaf in leaves])
         for leaves, w in svc._partials],
        [(ev.t, ev.round_idx, ev.num_clients, ev.num_samples,
          ev.mean_latency_s,
          [np.asarray(v).tobytes() for v in jax.tree.leaves(ev.global_params)])
         for ev in svc.history],
    )


@settings(max_examples=12, deadline=None)
@given(chunks=st.lists(st.integers(1, 6), min_size=1, max_size=3),
       schedule=st.lists(st.integers(1, 7), min_size=1, max_size=3),
       alpha=st.sampled_from([None, 0.5, 1.5]),
       stamping=st.sampled_from(["none", "all", "some"]),
       wire=st.sampled_from(["f32", "int8"]),
       seed=st.integers(0, 10_000))
def test_streaming_intake_paths_bit_identical(chunks, schedule, alpha,
                                              stamping, wire, seed):
    """Property: the same rows fed as one-row ``Delivery`` slices (the
    scalar path), as multi-row slices cut by a threshold ``schedule`` (the
    vectorized path) and as scalar ``Message``s leave bit-identical chunk
    weights, hits and filled counts, pending samples and latency, fired
    partials, aggregation events and params at every slice boundary.
    Creation and delivery times lie on a grid of quarter seconds, so every
    sum of latencies is exact in f64 and summation order cannot show."""
    rng = np.random.default_rng(seed)
    make = (UpdateBuffer.quantized_from_stacked if wire == "int8"
            else UpdateBuffer.from_stacked)
    bufs = [make(_stream_tree(rng, n)) for n in chunks]
    g0 = {"w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
          "b": jnp.asarray(rng.standard_normal(3), jnp.float32)}
    n_extra = [int(x) for x in rng.integers(0, 3, len(bufs))]
    per_round = sum(n + (e if n > 1 else 0) for n, e in zip(chunks, n_extra))
    plan = []  # per round: (t, slice) in delivery order
    for r in range(2):
        cuts = []
        for buf, extra in zip(bufs, n_extra):
            rows = _stream_rows(rng, buf.num_rows, extra)
            n = len(rows)
            created = rng.integers(0, 400, n) / 4.0
            if stamping != "all":
                created[rng.random(n) < (1.0 if stamping == "none" else 0.5)] \
                    = np.nan
            batch = ArrivalBatch(0, int(rng.integers(0, r + 7)), rows,
                                 created_t=created,
                                 num_samples=rng.integers(1, 41, n),
                                 buffer=buf)
            own, lo = [], 0
            while lo < n:
                hi = min(n, lo + schedule[len(own) % len(schedule)])
                own.append(batch.islice(lo, hi))
                lo = hi
            cuts.append(own)
        order = rng.permutation(
            np.repeat(np.arange(len(bufs)), [len(c) for c in cuts]))
        nxt = [iter(c) for c in cuts]
        plan.append([(rng.integers(0, 400) / 4.0, next(nxt[k]))
                     for k in order])

    def service():
        svc = AggregationService(
            jax.tree.map(jnp.array, g0),
            trigger=ClientCountTrigger(per_round),
            staleness_discount=(None if alpha is None
                                else polynomial_staleness(alpha)),
            streaming=True)
        svc.round_idx = 5  # batch round_idx in [0, 7]: staleness 0 to 5
        return svc

    one, multi, msg = service(), service(), service()
    for r, deliveries in enumerate(plan):
        for t, b in deliveries:
            multi(Delivery(t, batch=b))
            for i in range(b.n):
                one(Delivery(t, batch=b.islice(i, i + 1)))
                msg(Delivery(t, message=b.message(i)))
            want = _intake_state(multi)
            assert _intake_state(one) == want
            assert _intake_state(msg) == want
        assert multi.round_idx == 5 + r + 1


@pytest.mark.parametrize("intake,slices", [
    ("batch", [[0], [0], [1], [2], [3]]),
    ("message", [[0], [0], [1], [2], [3]]),
    ("batch", [[0, 0, 1], [1, 2], [3]]),
    ("batch", [[2, 0, 2], [0], [1, 3]]),
])
def test_streaming_counts_rows_once_and_fires_on_completion(intake, slices):
    """A row delivered twice, inside one slice or across two deliveries,
    counts once in ``filled`` and twice in ``hits``; the chunk's partial
    fires at exactly the delivery that completes it."""
    buf = UpdateBuffer.from_stacked(
        {"w": jnp.arange(8.0, dtype=jnp.float32).reshape(4, 2)})
    svc = AggregationService({"w": jnp.zeros(2)},
                             trigger=ClientCountTrigger(99), streaming=True)
    batch = ArrivalBatch(0, 0, np.concatenate(slices), buffer=buf)
    hits = np.zeros(4, np.float32)
    lo = 0
    for k, rows in enumerate(slices):
        part = batch.islice(lo, lo + len(rows))
        lo += len(rows)
        if intake == "message":
            svc(Delivery(0.0, message=part.message(0)))
        else:
            svc(Delivery(0.0, batch=part))
        np.add.at(hits, rows, 1.0)
        last = k == len(slices) - 1
        assert len(svc._partials) == int(last)
        ch = svc._fired[0] if last else svc._chunks[id(buf)]
        assert ch.filled == np.count_nonzero(hits)
        np.testing.assert_array_equal(ch.hits, hits)
        np.testing.assert_array_equal(ch.weights, hits)
    assert svc._partials[0][1] == float(hits.sum())


def test_update_buffer_validation_and_repr():
    with pytest.raises(ValueError):
        UpdateBuffer.from_stacked({"a": jnp.zeros((2, 3)), "b": jnp.zeros((4, 3))})
    buf = UpdateBuffer.from_stacked({"a": jnp.zeros((2, 3), jnp.float32)})
    assert buf.row_nbytes == 12
    assert "rows=2" in repr(buf)
    with pytest.raises(IndexError):
        buf.handle(2)
    h = buf.handle(1)
    assert h.nbytes == 12 and "row=1" in repr(h)


# --------------------------------------------------------------------------- #
# Mesh-sharded fed_reduce: shard_map + psum over the fleet "dp" axis
# --------------------------------------------------------------------------- #
def test_fed_reduce_mesh_single_shard_matches_local():
    from repro.distribution.sharding import make_fleet_mesh

    rng = np.random.default_rng(0)
    stack = jnp.asarray(rng.standard_normal((6, 4, 8)), jnp.float32)
    weights = jnp.asarray(rng.random(6), jnp.float32)
    mesh = make_fleet_mesh(1)
    assert mesh.axis_names == ("dp", "mp")
    out = fed_reduce(stack, weights, impl="ref", mesh=mesh)
    ref = fed_reduce(stack, weights, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_make_fleet_mesh_validates():
    from repro.distribution.sharding import make_fleet_mesh

    n_dev = len(jax.devices())
    with pytest.raises(ValueError):
        make_fleet_mesh(n_dev + 1)  # more shards than devices
    mesh = make_fleet_mesh()  # all devices on the dp axis
    assert int(mesh.shape["dp"]) * int(mesh.shape["mp"]) <= n_dev


def test_fed_reduce_mesh_multi_shard_with_padding(tmp_path):
    """dp=4 over forced host devices; rows not divisible by shards exercise
    the zero-weight padding path.  Runs in a subprocess because
    XLA_FLAGS must be set before jax initializes."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distribution.sharding import make_fleet_mesh
        from repro.kernels.fed_reduce.ops import fed_reduce

        assert len(jax.devices()) == 4, jax.devices()
        rng = np.random.default_rng(3)
        stack = jnp.asarray(rng.standard_normal((10, 3, 5)), jnp.float32)
        weights = jnp.asarray(rng.random(10), jnp.float32)
        mesh = make_fleet_mesh(4)
        out = fed_reduce(stack, weights, impl="ref", mesh=mesh)
        ref = fed_reduce(stack, weights, impl="ref")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-6)
        print("MESH_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "MESH_OK" in proc.stdout
