"""Batched round engine: fleet fidelity, cohort numerics, arrival times."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocation import GradeRuntime, solve_allocation
from repro.core.calibration import RuntimeCalibrator
from repro.core.deviceflow import DeviceFlow, Message
from repro.core.devicemodel import GRADES, DeviceFleet, Stage
from repro.core.federation import AggregationService, SampleThresholdTrigger
from repro.core.simulation import (
    DeviceTier,
    GradePlanEntry,
    HybridSimulation,
    LogicalTier,
    RoundPlan,
)
from repro.core.strategies import AccumulatedStrategy
from repro.core.task import GradeSpec
from repro.data.synthetic_ctr import make_federated_ctr
from repro.models import ctr as ctr_lib


def _ctr_setup(n_clients=12, rpd=8, dim=16, seed=0):
    data = make_federated_ctr(num_devices=n_clients, records_per_device=rpd,
                              dim=dim, seed=seed)
    local = ctr_lib.make_local_train_fn(lr=1e-2, epochs=2)
    params = ctr_lib.lr_init(jax.random.PRNGKey(0), dim)
    X, Y, counts = data.stacked_shards(np.arange(n_clients), rpd)
    mask = (np.arange(rpd)[None] < counts[:, None]).astype(np.float32)
    batches = {"x": jnp.asarray(X), "y": jnp.asarray(Y),
               "mask": jnp.asarray(mask)}
    return local, params, batches, counts


# --------------------------------------------------------------------------- #
# DeviceFleet — vectorized Table-I sampling with persistent per-device RNG
# --------------------------------------------------------------------------- #
def test_fleet_round_to_round_variation():
    """Regression: the seed rebuilt DeviceModel(seed) per call, so every
    round replayed identical jitter — fleet streams must persist."""
    fleet = DeviceFleet(GRADES["High"], 4, seed=0)
    s0, s1 = fleet.run_round(0), fleet.run_round(1)
    for i in range(4):
        assert s0.report(i).total_duration_min != s1.report(i).total_duration_min
        assert s0.report(i).total_power_mah != s1.report(i).total_power_mah


def test_device_tier_benchmark_reports_vary_across_rounds():
    local, params, batches, _ = _ctr_setup()
    tier = DeviceTier(local, GRADES["High"])
    take = jax.tree.map(lambda x: x[0], batches)
    _, _, r0 = tier.run_device(0, params, take, jax.random.PRNGKey(0), 0,
                               benchmark=True)
    _, _, r1 = tier.run_device(0, params, take, jax.random.PRNGKey(1), 1,
                               benchmark=True)
    assert r0.device_id == r1.device_id == 0
    assert r0.total_duration_min != r1.total_duration_min
    assert len(tier.reports) == 2


def test_fleet_mean_preserving_and_deterministic():
    fleet = DeviceFleet(GRADES["Low"], 4000, seed=9)
    s = fleet.run_round(0)
    mean_dur = sum(GRADES["Low"].cost(st).duration_min for st in Stage)
    assert s.total_duration_min.mean() == pytest.approx(mean_dur, rel=0.02)
    # Same seed, fresh fleet -> identical draws (composition-independent).
    again = DeviceFleet(GRADES["Low"], 4000, seed=9).run_round(0)
    np.testing.assert_array_equal(s.comm_kb, again.comm_kb)


def test_fleet_matches_grade_ordering():
    hi = DeviceFleet(GRADES["High"], 256, seed=1).run_round(0)
    lo = DeviceFleet(GRADES["Low"], 256, seed=1).run_round(0)
    assert hi.total_power_mah.mean() < lo.total_power_mah.mean()
    assert hi.arrival_offsets_s().mean() < lo.arrival_offsets_s().mean()


def test_fleet_checkpoint_resumes_streams():
    fleet = DeviceFleet(GRADES["High"], 8, seed=2)
    fleet.run_round(0)
    state = fleet.state_dict()
    expect = fleet.run_round(1)
    restored = DeviceFleet(GRADES["High"], 8, seed=2)
    restored.load_state_dict(state)
    got = restored.run_round(1)
    np.testing.assert_array_equal(expect.stage_duration_min,
                                  got.stage_duration_min)


def test_fleet_restore_into_fresh_lazily_grown_tier():
    """DeviceTier builds its fleet empty and grows it on demand: restoring a
    checkpoint into a *fresh* tier must adopt the saved layout, not require
    the restorer to pre-size the fleet."""
    local, params, batches, _ = _ctr_setup()
    tier = DeviceTier(local, GRADES["High"], seed=4)
    tier.sample_round(np.arange(6), 0)  # grows the fleet to 6
    state = tier.fleet.state_dict()
    expect = tier.sample_round(np.arange(6), 1)
    fresh = DeviceTier(local, GRADES["High"], seed=4)  # fleet size 0
    fresh.fleet.load_state_dict(state)
    got = fresh.sample_round(np.arange(6), 1)
    np.testing.assert_array_equal(expect.stage_duration_min,
                                  got.stage_duration_min)
    with pytest.raises(ValueError):  # wrong seed -> streams would diverge
        DeviceTier(local, GRADES["High"], seed=5).fleet.load_state_dict(state)


# --------------------------------------------------------------------------- #
# DeviceTier — vmapped cohorts reproduce the per-device loop
# --------------------------------------------------------------------------- #
def test_cohort_matches_per_device_loop():
    local, params, batches, _ = _ctr_setup(n_clients=6)
    tier = DeviceTier(local, GRADES["High"], dtype=jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    stacked, _ = tier.run_cohort(params, batches, keys)
    for j in range(6):
        single, _, _ = tier.run_device(
            j, params, jax.tree.map(lambda x: x[j], batches), keys[j], 0)
        for a, b in zip(jax.tree.leaves(
                jax.tree.map(lambda x: x[j], stacked)),
                jax.tree.leaves(single)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-2, rtol=2e-2)


# --------------------------------------------------------------------------- #
# HybridSimulation — arrival-time contract with DeviceFlow
# --------------------------------------------------------------------------- #
def test_hybrid_round_derives_arrivals_and_stamps_created_t():
    local, params, batches, counts = _ctr_setup()
    deliveries = []
    flow = DeviceFlow(deliveries.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(LogicalTier(local, cohort_size=8),
                           DeviceTier(local, GRADES["High"], cohort_size=4),
                           deviceflow=flow)
    out = sim.run_round(
        task_id=0, round_idx=0, global_params=params, client_batches=batches,
        num_samples=counts, num_logical=8, rng=jax.random.PRNGKey(1),
        benchmark_devices=2)
    assert out.arrival_times is not None and len(out.arrival_times) == 12
    assert (out.arrival_times > 0).all()
    assert len(deliveries) == 12
    for d in deliveries:
        assert d.message.created_t > 0.0  # stamped at submit time
        assert d.t >= d.message.created_t - 1e-9
    assert len(out.reports) == 2 and len(sim.device.reports) == 2


def test_hybrid_round_respects_caller_arrival_times():
    local, params, batches, counts = _ctr_setup()
    svc = AggregationService(
        ctr_lib.lr_init(jax.random.PRNGKey(0), 16),
        trigger=SampleThresholdTrigger(int(counts.sum())))
    flow = DeviceFlow(svc)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(LogicalTier(local, cohort_size=8),
                           DeviceTier(local, GRADES["High"]),
                           deviceflow=flow)
    ts = np.linspace(5.0, 16.0, 12)
    out = sim.run_round(
        task_id=0, round_idx=0, global_params=params, client_batches=batches,
        num_samples=counts, num_logical=6, rng=jax.random.PRNGKey(1),
        arrival_times=ts)
    np.testing.assert_array_equal(out.arrival_times, ts)
    assert len(svc.history) == 1
    # Latency accounting sees the stamps (realtime dispatch -> ~0 queuing).
    assert svc.history[0].mean_latency_s == pytest.approx(0.0, abs=1e-9)
    assert flow.conservation_ok(0)


def test_hybrid_round_all_logical_still_gets_arrivals():
    local, params, batches, counts = _ctr_setup()
    got = []
    flow = DeviceFlow(got.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(LogicalTier(local, cohort_size=8),
                           DeviceTier(local, GRADES["High"]),
                           deviceflow=flow)
    out = sim.run_round(
        task_id=0, round_idx=0, global_params=params, client_batches=batches,
        num_samples=counts, num_logical=12, rng=jax.random.PRNGKey(1))
    assert out.num_physical == 0
    assert out.arrival_times is not None and (out.arrival_times > 0).all()
    assert len(got) == 12


# --------------------------------------------------------------------------- #
# Grade-partitioned round engine — RoundPlan + multi-grade rounds
# --------------------------------------------------------------------------- #
def _two_grade_setup(n_high=10, n_low=8, rpd=8, dim=16):
    local = ctr_lib.make_local_train_fn(lr=1e-2, epochs=2)
    params = ctr_lib.lr_init(jax.random.PRNGKey(0), dim)
    gb, gs = {}, {}
    for i, (g, n) in enumerate((("High", n_high), ("Low", n_low))):
        data = make_federated_ctr(num_devices=n, records_per_device=rpd,
                                  dim=dim, seed=i)
        X, Y, counts = data.stacked_shards(np.arange(n), rpd)
        mask = (np.arange(rpd)[None] < counts[:, None]).astype(np.float32)
        gb[g] = {"x": jnp.asarray(X), "y": jnp.asarray(Y),
                 "mask": jnp.asarray(mask)}
        gs[g] = counts
    return local, params, gb, gs


def _two_grade_specs(n_high=10, n_low=8, q_high=2, q_low=1):
    return [
        GradeSpec("High", n_high, benchmarking_devices=q_high,
                  logical_bundles=4, bundles_per_device=2,
                  physical_devices=3),
        GradeSpec("Low", n_low, benchmarking_devices=q_low,
                  logical_bundles=2, bundles_per_device=1,
                  physical_devices=2),
    ]


def test_round_plan_from_allocation_carries_benchmarking():
    """Satellite: q_i flows from GradeSpec through the allocator to the plan,
    so the devices producing RoundReports are the allocator-excluded ones."""
    specs = _two_grade_specs()
    res = solve_allocation(specs, [GradeRuntime(2.0, 3.0, 1.0)] * 2)
    plan = RoundPlan.from_allocation(res, specs)
    for spec, ga in zip(specs, res.per_grade):
        e = plan.entry(spec.grade)
        assert e.num_benchmarking == spec.benchmarking_devices
        assert e.num_logical == ga.logical_devices
        assert e.num_physical == ga.physical_devices
        assert e.num_devices == spec.num_devices  # x + y + q == N
    assert plan.total_devices == sum(s.num_devices for s in specs)
    with pytest.raises(KeyError):
        plan.entry("Mid")


def test_multi_grade_round_end_to_end():
    """High+Low fleets in one round: allocator split respected, per-grade
    makespans reported, arrival durations monotone in grade beta."""
    local, params, gb, gs = _two_grade_setup()
    specs = _two_grade_specs()
    cal = RuntimeCalibrator()
    res = solve_allocation(specs, cal.runtimes_for(specs))  # Table-I prior
    plan = RoundPlan.from_allocation(res, specs)
    deliveries = []
    flow = DeviceFlow(deliveries.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(
        LogicalTier(local, cohort_size=8),
        tiers={g: DeviceTier(local, GRADES[g], cohort_size=4)
               for g in ("High", "Low")},
        deviceflow=flow)
    out = sim.run_plan_round(0, 0, params, plan, gb, gs,
                             jax.random.PRNGKey(1), calibrator=cal)
    n_total = 18
    assert len(out.messages) == n_total and len(deliveries) == n_total
    assert out.arrival_times is not None and len(out.arrival_times) == n_total
    assert (out.arrival_times > 0).all()
    assert flow.conservation_ok(0)
    # Allocator split respected per grade.
    for spec, ga in zip(specs, res.per_grade):
        b = out.per_grade[spec.grade]
        assert (b.num_logical, b.num_physical) == (
            ga.logical_devices, ga.physical_devices)
        assert b.num_benchmarking == spec.benchmarking_devices
        assert b.makespan_s > 0
    # q_i benchmarking devices -> exactly that many RoundReports per grade.
    per_grade_reports = {g: [r for r in out.reports if r.grade == g]
                         for g in ("High", "Low")}
    assert len(per_grade_reports["High"]) == 2
    assert len(per_grade_reports["Low"]) == 1
    assert len(sim.tiers["High"].reports) == 2
    assert len(sim.tiers["Low"].reports) == 1
    # Arrival durations monotone in grade beta: Low (beta_Low > beta_High)
    # devices finish later on average.
    assert (out.per_grade["Low"].mean_duration_s
            > out.per_grade["High"].mean_duration_s)
    assert out.makespan_s == max(b.makespan_s
                                 for b in out.per_grade.values())
    # Device ids are globally unique across the grades.
    ids = [m.device_id for m in out.messages]
    assert len(set(ids)) == n_total
    # Calibrator observed both grades' fleets this round.
    assert cal.num_observations("High") == 10
    assert cal.num_observations("Low") == 8


def test_multi_grade_benchmarking_devices_are_device_tier_rows():
    """The q_i report rows are the LAST rows of the grade — the device-tier
    tail the allocator excluded, never logical-tier rows — and carry the same
    global device ids as their messages."""
    local, params, gb, gs = _two_grade_setup()
    specs = _two_grade_specs()
    res = solve_allocation(specs, RuntimeCalibrator().runtimes_for(specs))
    plan = RoundPlan.from_allocation(res, specs)
    sim = HybridSimulation(
        LogicalTier(local, cohort_size=8),
        tiers={g: DeviceTier(local, GRADES[g]) for g in ("High", "Low")})
    out = sim.run_plan_round(0, 0, params, plan, gb, gs, jax.random.PRNGKey(0))
    offset = 0
    for spec in specs:
        e = plan.entry(spec.grade)
        got = sorted(r.device_id for r in out.reports
                     if r.grade == spec.grade)
        want = list(range(offset + e.num_devices - e.num_benchmarking,
                          offset + e.num_devices))
        assert got == want  # the grade's global tail rows
        offset += e.num_devices
    # Report ids join 1:1 onto message ids (global, unique across grades).
    msg_ids = {m.device_id for m in out.messages}
    assert all(r.device_id in msg_ids for r in out.reports)


def test_run_plan_round_validates_batch_sizes():
    local, params, gb, gs = _two_grade_setup()
    plan = RoundPlan((GradePlanEntry("High", 4, 3, 1),))  # needs 8, gb has 10
    sim = HybridSimulation(
        LogicalTier(local, cohort_size=8),
        tiers={"High": DeviceTier(local, GRADES["High"])})
    with pytest.raises(ValueError, match="plan requires"):
        sim.run_plan_round(0, 0, params, plan, gb, gs, jax.random.PRNGKey(0))
    missing = RoundPlan((GradePlanEntry("Mid", 1, 0, 0),))
    with pytest.raises(KeyError):
        sim.run_plan_round(0, 0, params, missing, gb, gs,
                           jax.random.PRNGKey(0))


def test_single_device_tier_still_exposes_legacy_device_attr():
    local, params, gb, gs = _two_grade_setup()
    sim = HybridSimulation(LogicalTier(local),
                           DeviceTier(local, GRADES["High"]))
    assert sim.device.grade.name == "High"
    multi = HybridSimulation(
        LogicalTier(local),
        tiers={g: DeviceTier(local, GRADES[g]) for g in ("High", "Low")})
    with pytest.raises(ValueError):
        _ = multi.device


def test_device_tier_mesh_cohort_matches_unsharded():
    """DeviceTier shards cohorts over the mesh data axis like LogicalTier."""
    local, params, batches, _ = _ctr_setup(n_clients=8)
    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    plain = DeviceTier(local, GRADES["High"])
    mesh = jax.make_mesh((1,), ("data",))
    sharded = DeviceTier(local, GRADES["High"], mesh=mesh)
    p0, _ = plain.run_cohort(params, batches, keys)
    p1, _ = sharded.run_cohort(params, batches, keys)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_fleet_mesh_round_pads_uneven_chunks():
    """A round on a 4-way fleet mesh whose cohort chunks do not divide over
    the shards (13 logical + 10 device rows, cohorts of 6) matches the same
    round unsharded, and every update leaf spans the mesh.  Runs in a
    subprocess because XLA_FLAGS must be set before jax initializes."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core.deviceflow import DeviceFlow
        from repro.core.devicemodel import GRADES
        from repro.core.federation import (AggregationService,
                                           SampleThresholdTrigger)
        from repro.core.simulation import (DeviceTier, GradePlanEntry,
                                           HybridSimulation, LogicalTier,
                                           RoundPlan)
        from repro.core.strategies import AccumulatedStrategy
        from repro.distribution.sharding import make_fleet_mesh
        from test_simulation import _ctr_setup

        assert len(jax.devices()) == 4, jax.devices()
        local, params, batches, counts = _ctr_setup(n_clients=23)
        plan = RoundPlan((GradePlanEntry("High", 13, 10),))

        def round_on(mesh):
            svc = AggregationService(
                params, trigger=SampleThresholdTrigger(int(counts.sum())),
                mesh=mesh)
            flow = DeviceFlow(svc)
            flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
            kw = dict(cohort_size=6, mesh=mesh, data_axis="dp")
            sim = HybridSimulation(
                LogicalTier(local, **kw),
                tiers={"High": DeviceTier(local, GRADES["High"], **kw)},
                deviceflow=flow)
            out = sim.run_plan_round(0, 0, params, plan, {"High": batches},
                                     {"High": counts}, jax.random.PRNGKey(3))
            flow.run()
            assert len(svc.history) == 1
            return out, svc.global_params

        _, ref = round_on(None)
        out, got = round_on(make_fleet_mesh(4))
        assert sorted(b.n for b in out.batches) == [1, 4, 6, 6, 6], [
            b.n for b in out.batches]
        spans = {len(leaf.sharding.device_set) for b in out.batches
                 for leaf in b.buffer.leaves2d}
        assert spans == {4}, spans
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        print("MESH_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "MESH_OK" in proc.stdout


# --------------------------------------------------------------------------- #
# DeviceFlow — bulk Sorter path and backlog draining
# --------------------------------------------------------------------------- #
def _msgs(n, task_id=0):
    return [Message(task_id, i, 0, payload=i) for i in range(n)]


def test_submit_many_equivalent_to_sequential_submit():
    ts = np.array([3.0, 1.0, 2.0, 5.0, 4.0, 6.0, 8.0, 7.0, 9.0, 10.0])
    seq_got, bulk_got = [], []
    seq = DeviceFlow(seq_got.append, seed=5)
    seq.register_task(0, AccumulatedStrategy(thresholds=(2, 3)))
    order = np.argsort(ts)
    for i in order:  # per-message submit in time order
        seq.submit(_msgs(10)[i], t=float(ts[i]))
    bulk = DeviceFlow(bulk_got.append, seed=5)
    bulk.register_task(0, AccumulatedStrategy(thresholds=(2, 3)))
    bulk.submit_many(_msgs(10), ts=ts)
    assert [(d.t, d.message.device_id) for d in bulk_got] == \
           [(d.t, d.message.device_id) for d in seq_got]
    # created_t is each message's own arrival; delivery happens at the
    # threshold-crossing message's arrival, never earlier than creation.
    assert all(d.message.created_t == ts[d.message.device_id] for d in bulk_got)
    assert all(d.t >= d.message.created_t for d in bulk_got)
    assert bulk.conservation_ok(0)


def test_submit_many_routes_multiple_tasks():
    got = []
    flow = DeviceFlow(got.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(2,)))
    flow.register_task(1, AccumulatedStrategy(thresholds=(1,)))
    msgs = _msgs(4, task_id=0) + _msgs(3, task_id=1)
    flow.submit_many(msgs, ts=np.arange(7, dtype=float) + 1.0)
    assert flow.conservation_ok(0) and flow.conservation_ok(1)
    assert len(got) == 7


def test_backlog_above_threshold_drains_fully():
    """Regression: one-batch-per-insertion stranded bulk backlogs forever."""
    got = []
    flow = DeviceFlow(got.append)
    flow.register_task(0, AccumulatedStrategy(thresholds=(3,)))
    # Simulate a bulk restore: 9 messages land on the shelf at once.
    state = {0: {"task_id": 0, "buf": _msgs(9), "received": 9,
                 "dispatched": 0, "dropped": 0}}
    flow.load_state_dict(state)
    flow.submit(Message(0, 99, 0, payload="x"), t=1.0)
    assert len(got) == 9  # 3 batches of 3 drained, 1 message pending
    assert len(flow.shelf(0)) == 1
    assert flow.conservation_ok(0)


# --------------------------------------------------------------------------- #
# Columnar message plane: batch emissions end-to-end through the round engine
# --------------------------------------------------------------------------- #
from repro.core.deviceflow import ArrivalBatch  # noqa: E402
from repro.core.federation import ClientCountTrigger  # noqa: E402
from repro.core.simulation import ArrivalMessageView  # noqa: E402


def test_columnar_round_matches_scalar_plane_numerics():
    """columnar=True (batch emissions) and columnar=False (per-device
    messages) aggregate identical f32 cohort outputs — the global params
    must match to float tolerance and both planes conserve rows."""
    local, params, batches, counts = _ctr_setup()
    finals = {}
    for columnar in (True, False):
        svc = AggregationService(
            ctr_lib.lr_init(jax.random.PRNGKey(0), 16),
            trigger=ClientCountTrigger(12))
        flow = DeviceFlow(svc)
        flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
        sim = HybridSimulation(LogicalTier(local, cohort_size=8),
                               DeviceTier(local, GRADES["High"],
                                          cohort_size=4),
                               deviceflow=flow, columnar=columnar)
        out = sim.run_round(
            task_id=0, round_idx=0, global_params=params,
            client_batches=batches, num_samples=counts, num_logical=8,
            rng=jax.random.PRNGKey(1))
        assert flow.conservation_ok(0)
        assert len(svc.history) == 1
        assert bool(out.batches) is columnar
        finals[columnar] = jax.device_get(svc.global_params)
    for a, b in zip(jax.tree.leaves(finals[True]),
                    jax.tree.leaves(finals[False])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_columnar_outcome_exposes_messages_view():
    """outcome.messages stays a per-device sequence (lazy adapter) while
    outcome.batches carries the columnar emissions; device ids cover the
    cohort exactly once across both."""
    local, params, batches, counts = _ctr_setup()
    sim = HybridSimulation(LogicalTier(local, cohort_size=8),
                           DeviceTier(local, GRADES["High"], cohort_size=4))
    out = sim.run_round(
        task_id=0, round_idx=0, global_params=params, client_batches=batches,
        num_samples=counts, num_logical=8, rng=jax.random.PRNGKey(1),
        benchmark_devices=2)
    assert isinstance(out.messages, ArrivalMessageView)
    assert len(out.messages) == 12
    ids = sorted(m.device_id for m in out.messages)
    assert ids == list(range(12))
    batch_ids = np.concatenate([b.device_ids for b in out.batches])
    bench_ids = {8, 9}  # first 2 device-tier rows materialize reports
    assert set(batch_ids.tolist()) == set(range(12)) - bench_ids
    # Benchmarking devices' payloads materialized to host pytrees; batch
    # rows stay as shared-buffer references.
    by_id = {m.device_id: m for m in out.messages}
    assert isinstance(by_id[8].payload, dict)
    assert all(b.buffer is not None for b in out.batches)
