"""Multi-process fleet execution: pool/inline equivalence (the PR's core
property — a sharded round is bit-identical to the single-process columnar
round), worker-death re-dispatch, shared-memory segment recycling, the
fed_reduce block autotune table, and the one-manifest runtime checkpoint."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.deviceflow import DeviceFlow
from repro.core.devicemodel import GRADES, DeviceFleet
from repro.core.federation import AggregationService, SampleThresholdTrigger
from repro.core.scheduler import ResourceManager, ResourcePool, TaskEngine
from repro.core.simulation import DeviceTier, HybridSimulation, LogicalTier
from repro.core.strategies import AccumulatedStrategy
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.synthetic_ctr import make_federated_ctr
from repro.kernels.fed_reduce.ops import fed_reduce, tuned_blocks
from repro.models import ctr as ctr_lib
from repro.runtime.fault_tolerance import WorkerFailure, redispatch_chunks
from repro.runtime.workers import ChunkSpec, WorkerSpec, segment_layout

N, RPD, DIM = 24, 8, 16


def make_tiers(cohort=4, seed=7):
    """Module-level so spawn'ed workers can unpickle it by reference."""
    local = ctr_lib.make_local_train_fn(lr=1e-2, epochs=2)
    return (LogicalTier(local, cohort_size=cohort),
            {"High": DeviceTier(local, GRADES["High"], seed=seed,
                                cohort_size=cohort)})


class RecordingSink:
    """Forwarding sink that records dispatch-group membership + stamps."""

    def __init__(self, svc):
        self.svc = svc
        self.groups = []

    def __call__(self, d):
        if d.batch is not None:
            self.groups.append((d.t, tuple(d.batch.device_ids.tolist()),
                                tuple(d.batch.created_t.tolist())))
        else:
            m = d.message
            self.groups.append((d.t, (m.device_id,), (m.created_t,)))
        self.svc(d)


def _run_world(wire, workers, *, rounds=2, delay=None, poison=None):
    """Run ``rounds`` full rounds; return the observable world state."""
    data = make_federated_ctr(num_devices=N, records_per_device=RPD,
                              dim=DIM, seed=0)
    params = ctr_lib.lr_init(jax.random.PRNGKey(0), DIM)
    X, Y, counts = data.stacked_shards(np.arange(N), RPD)
    mask = (np.arange(RPD)[None] < counts[:, None]).astype(np.float32)
    batches = {"x": jnp.asarray(X), "y": jnp.asarray(Y),
               "mask": jnp.asarray(mask)}
    svc = AggregationService(
        params, trigger=SampleThresholdTrigger(int(counts.sum())))
    sink = RecordingSink(svc)
    flow = DeviceFlow(sink)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    logical, tiers = make_tiers()
    kw = {}
    if workers and delay is not None:
        from repro.runtime.workers import FleetWorkerPool
        kw = dict(worker_pool=FleetWorkerPool(
            WorkerSpec(make_tiers), workers, debug_delay_s=delay))
    elif workers:
        kw = dict(workers=workers, worker_spec=WorkerSpec(make_tiers))
    sim = HybridSimulation(logical, tiers=tiers, deviceflow=flow,
                           wire=wire, **kw)
    stats = failures = None
    try:
        for rnd in range(rounds):
            if poison is not None and rnd == poison[0]:
                sim.pool.poison_worker(poison[1],
                                       fail_after_chunks=poison[2])
            sim.run_round(task_id=0, round_idx=rnd,
                          global_params=svc.global_params,
                          client_batches=batches, num_samples=counts,
                          num_logical=10, rng=jax.random.PRNGKey(rnd))
            flow.run(1e12)
            svc.tick(flow.clock.now)
        if sim.pool is not None:
            stats = dict(sim.pool.stats)
            failures = list(sim.pool.failures)
            alive = list(sim.pool.alive_workers)
        else:
            alive = None
    finally:
        sim.close()
    shelf = flow.shelf(0)
    return {
        "params": jax.device_get(svc.global_params),
        "bytes_received": shelf.total_bytes_received,
        "bytes_dispatched": shelf.total_bytes_dispatched,
        "aggregations": len(svc.history),
        "groups": sink.groups,
        "stats": stats,
        "failures": failures,
        "alive": alive,
    }


_REF_CACHE = {}


def _inline_ref(wire):
    if wire not in _REF_CACHE:
        _REF_CACHE[wire] = _run_world(wire, 0)
    return _REF_CACHE[wire]


def _assert_equivalent(ref, got):
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert got["bytes_received"] == ref["bytes_received"]
    assert got["bytes_dispatched"] == ref["bytes_dispatched"]
    assert got["aggregations"] == ref["aggregations"]
    # Dispatch-group membership and created_t stamps match group-for-group.
    assert len(got["groups"]) == len(ref["groups"])
    for (t0, ids0, ct0), (t1, ids1, ct1) in zip(ref["groups"],
                                                got["groups"]):
        assert t0 == t1 and ids0 == ids1
        np.testing.assert_array_equal(np.asarray(ct0), np.asarray(ct1))


@pytest.mark.parametrize("wire,workers,delay", [
    ("f32", 2, None),       # even shard count
    ("int8", 3, (0.0, 0.03, 0.01)),  # odd shards + jittered interleaving
    ("int8", 1, None),      # degenerate pool: every chunk on one worker
])
def test_pool_round_bit_identical(wire, workers, delay):
    """The property at the heart of the PR: a multi-process round — any
    shard count, any worker completion interleaving, quantized wire
    included — is bit-identical to the single-process columnar round:
    same params, same exact byte counters, same dispatch groups, same
    created_t stamps (the int8 case also proves error-feedback residuals
    stay with their shard across rounds)."""
    ref = _inline_ref(wire)
    got = _run_world(wire, workers, delay=delay)
    _assert_equivalent(ref, got)
    # Transport accounting: segments were created, then recycled in round 2.
    st = got["stats"]
    assert st["chunks"] == 2 * 7  # 3 logical + 4 device chunks per round
    assert st["segments_created"] >= 1 and st["bytes_shipped"] > 0
    assert st["redispatched_chunks"] == 0 and got["failures"] == []


def test_pool_workers_never_take_the_accelerator(monkeypatch):
    """Workers run on the CPU backend whatever the coordinator's
    environment says: on a TPU host the coordinator holds the chip, and a
    child that tried to initialise it would fail.  Here the coordinator's
    environment names a TPU a CPU-only host does not have, so a child that
    inherited it would die at start; the pooled round still completes
    bit-identical to the inline one."""
    import os

    ref = _inline_ref("f32")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    got = _run_world("f32", 2)
    _assert_equivalent(ref, got)
    assert got["failures"] == []
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # coordinator env untouched


def test_pool_segment_ring_recycles():
    """Round 2 reuses round 1's shared-memory segments (the donation-style
    ring): segment creations stay bounded while reuses accrue."""
    got = _run_world("f32", 2, rounds=3)
    st = got["stats"]
    assert st["segment_reuses"] > 0
    assert st["segments_created"] <= st["chunks"]


def test_worker_death_mid_round_redispatch():
    """Kill a worker mid-round (after it ships one chunk): the coordinator
    re-dispatches its remaining chunks to survivors and the round still
    completes bit-identical to the inline reference."""
    ref = _inline_ref("f32")
    got = _run_world("f32", 3, poison=(1, 1, 1))  # round 1, worker 1
    _assert_equivalent(ref, got)
    assert got["alive"] is not None and len(got["alive"]) == 2
    assert 1 not in got["alive"]
    assert len(got["failures"]) == 1
    f = got["failures"][0]
    assert isinstance(f, WorkerFailure) and f.worker_id == 1
    assert f.chunks and set(f.survivors) == set(got["alive"])
    assert got["stats"]["redispatched_chunks"] == len(f.chunks)


def test_redispatch_chunks_round_robin():
    got = redispatch_chunks([7, 3, 5], survivors=[0, 2])
    assert got == {0: [3, 7], 2: [5]}
    with pytest.raises(RuntimeError):
        redispatch_chunks([1], survivors=[])


def test_segment_layout_alignment_and_wire():
    layout, total = segment_layout(
        [(100,), (7,)], ["float32", "float32"], 3, "int8")
    # int8 wire: leaves stored int8, then one f32 scale column per leaf.
    assert [d for _, _, d in layout] == ["int8", "int8",
                                        "float32", "float32"]
    assert all(off % 64 == 0 for off, _, _ in layout)
    assert layout[2][1] == (3,) and total >= layout[-1][0] + 12
    f_layout, _ = segment_layout([(100,)], ["float32"], 3, "f32")
    assert f_layout == [(0, (3, 100), "float32")]


def test_tuned_blocks_table_and_override(monkeypatch):
    # Large stacks: int8 rows stream 1 byte/elem, affording taller tiles.
    assert tuned_blocks(4096, 65536, np.float32) == (256, 512)
    assert tuned_blocks(4096, 65536, np.int8) == (512, 1024)
    # Small stacks clamp to the padded shape — no 8x over-padding.
    assert tuned_blocks(24, 16, np.float32) == (32, 128)
    assert tuned_blocks(100, 1000, np.float32)[0] <= 128
    monkeypatch.setenv("FED_REDUCE_BLOCKS", "128,256")
    assert tuned_blocks(4096, 65536, np.float32) == (128, 256)
    monkeypatch.setenv("FED_REDUCE_BLOCKS", "garbage")
    with pytest.raises(ValueError):
        tuned_blocks(4096, 65536, np.float32)
    # An override the TPU tiling refuses is rejected here, not handed to
    # the chip compiler.
    for bad in ("64,256", "128,200", "0,128"):
        monkeypatch.setenv("FED_REDUCE_BLOCKS", bad)
        with pytest.raises(ValueError, match="tiling"):
            tuned_blocks(4096, 65536, np.float32)


def test_tuned_blocks_drive_pallas_kernel(monkeypatch):
    """The tuned (and overridden) blockings agree with the ref reduction
    through the interpreted kernel path."""
    k = jax.random.PRNGKey(3)
    stack = jax.random.normal(k, (150, 300))
    w = jax.random.uniform(jax.random.PRNGKey(4), (150,))
    ref = fed_reduce(stack, w, impl="ref")
    got = fed_reduce(stack, w, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    monkeypatch.setenv("FED_REDUCE_BLOCKS", "128,128")  # 2x3 grid
    got2 = fed_reduce(stack, w, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref), atol=1e-5)


def test_one_manifest_runtime_checkpoint(tmp_path):
    """Satellite: fleet RNG counters and streaming-aggregation partials ride
    the SAME ``Checkpointer.save(runtime_state=...)`` manifest as the engine
    + DeviceFlow snapshot — one atomic unit, one restore call."""
    fleet = DeviceFleet(GRADES["High"], 6, seed=11)
    fleet.run_round(0)  # advance the per-device counters past zero

    params = {"w": jnp.zeros(DIM)}
    svc = AggregationService(params, trigger=SampleThresholdTrigger(10**9),
                             streaming=True)
    flow = DeviceFlow(svc)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    rm = ResourceManager(ResourcePool({"High": 8}, {"High": 2}))
    eng = TaskEngine(rm, lambda t: [])

    state = eng.state_dict(deviceflow=flow, fleets={"High": fleet},
                           services={0: svc})
    assert set(state["fleets"]) == {"High"}
    assert set(state["aggregation"]) == {0}

    ck = Checkpointer(tmp_path)
    ck.save(3, params, runtime_state=state)
    # Consumed AFTER the snapshot — the restore must replay this exact draw.
    ref_next = fleet.run_round(1)
    manifest_sections = sorted(state)
    restored = ck.restore_runtime_state()
    assert sorted(restored) == manifest_sections
    import json
    manifest = json.loads(
        (tmp_path / "step_0000000003" / "manifest.json").read_text())
    assert "fleets" in manifest["runtime_sections"]
    assert "aggregation" in manifest["runtime_sections"]

    # Restore into a fresh world: fleet RNG resumes exactly where it left
    # off (the round-1 draw replays bit-identically).
    fleet2 = DeviceFleet(GRADES["High"], 6, seed=11)
    svc2 = AggregationService(params, trigger=SampleThresholdTrigger(10**9),
                              streaming=True)
    flow2 = DeviceFlow(svc2)
    flow2.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    rm2 = ResourceManager(ResourcePool({"High": 8}, {"High": 2}))
    eng2 = TaskEngine(rm2, lambda t: [])
    eng2.load_state_dict(restored, tasks=[], deviceflow=flow2,
                         fleets={"High": fleet2}, services={0: svc2})
    replay = fleet2.run_round(1)
    np.testing.assert_array_equal(replay.stage_duration_min,
                                  ref_next.stage_duration_min)
    # Legacy engine states (no fleets/aggregation sections) still load.
    legacy = {k: v for k, v in restored.items()
              if k not in ("fleets", "aggregation")}
    eng2.load_state_dict(legacy, tasks=[], deviceflow=flow2,
                         fleets={"High": fleet2}, services={0: svc2})
