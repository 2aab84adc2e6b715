"""Federated LM training driver — SimDC end-to-end on the LM substrate.

The cloud model is one of the assigned architectures; simulated device cohorts
produce update messages that flow through **DeviceFlow** under a configurable
traffic strategy; the **aggregation trigger** (sample-threshold or scheduled)
gates the global update; the cloud-side trainer runs distributed
``train_step``s with checkpoint/restart.

Two modes:
  --mode cloud      pure datacenter pretraining loop (no federation) — the
                    substrate driver used by examples/lm_pretrain.py.
  --mode federated  full SimDC loop (default).

At container scale use ``--smoke`` (reduced configs, CPU-sized cohorts); on a
real cluster the same flags ride on the production mesh.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, ShapeConfig, choose_mesh_plan
from repro.configs.registry import get_config
from repro.checkpoint.checkpointer import Checkpointer
from repro.core.allocation import solve_allocation
from repro.core.calibration import RuntimeCalibrator
from repro.core.deviceflow import ArrivalBatch, DeviceFlow, Message
from repro.core.devicemodel import GRADES
from repro.core.federation import (
    AggregationService,
    ClientCountTrigger,
    SampleThresholdTrigger,
    ScheduledTrigger,
)
from repro.core.scheduler import ResourceManager, ResourcePool, TaskEngine
from repro.core.simulation import (
    DeviceTier,
    HybridSimulation,
    LogicalTier,
    RoundPlan,
)
from repro.core.strategies import AccumulatedStrategy, TimeIntervalStrategy
from repro.core.task import GradeSpec, OperatorFlow, Task
from repro.core.traffic_curves import right_tailed_normal
from repro.core.updates import UpdateBuffer, UpdateHandle
from repro.data.tokens import TokenPipeline
from repro.distribution.sharding import derive_logical_mesh, make_fleet_mesh
from repro.distribution.steps import build_train_step, init_train_state
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.registry import get_model
from repro.optim.compression import (
    topk_compress,
    topk_compress_rows,
    topk_init,
)
from repro.runtime.fault_tolerance import TrainingSupervisor


def make_small_shape(cfg, *, seq_len=128, global_batch=8, microbatches=2):
    return ShapeConfig("local", seq_len, global_batch, "train",
                       microbatches=microbatches)


def _make_local_train(api, cfg, client_lr):
    """One SGD epoch on the client model — shared between the coordinator's
    tiers and spawned workers so pooled chunks stay bit-identical."""

    def local_train(params, batch, _rng):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, cfg)[0])(params)
        new = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - client_lr * g.astype(jnp.float32)
                          ).astype(p.dtype), params, grads)
        return new, loss

    return local_train


def _federated_worker_tiers(*, arch, grades, seed, client_lr, cohort):
    """Module-level ``WorkerSpec`` factory (spawn pickles it by reference):
    rebuilds the coordinator's tiers from plain kwargs inside each worker."""
    cfg = get_config(arch, smoke=True)
    api = get_model(cfg)
    local_train = _make_local_train(api, cfg, client_lr)
    return (LogicalTier(local_train, cohort_size=cohort),
            {g: DeviceTier(local_train, GRADES[g], seed=seed)
             for g in grades})


def cloud_training(args) -> dict:
    """Datacenter pretraining loop with checkpoint/restart."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        shape = make_small_shape(cfg)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
    else:
        shape = SHAPES[args.shape]
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    plan = choose_mesh_plan(cfg, model_axis=mesh.devices.shape[-1])
    lmesh = derive_logical_mesh(mesh, plan)
    step_fn, in_sh, out_sh, _ = build_train_step(cfg, lmesh, shape)

    with lmesh.mesh:
        jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0,), keep_unused=True)
        state = init_train_state(cfg, seed=args.seed)
        pipe = TokenPipeline(cfg.vocab_size, shape.seq_len,
                             shape.global_batch, seed=args.seed)
        ckpt = Checkpointer(args.checkpoint_dir)
        losses = []

        def one_step(state, step):
            b = next(pipe)
            n, mb = shape.microbatches, shape.global_batch // shape.microbatches
            batch = {
                "tokens": b.tokens.reshape(n, mb, -1),
                "targets": b.targets.reshape(n, mb, -1),
                "mask": b.mask.reshape(n, mb, -1),
            }
            state, metrics = jitted(state, batch)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e}", flush=True)
            return state

        sup = TrainingSupervisor(ckpt, checkpoint_every=args.checkpoint_every)
        state, _ = sup.run(state, one_step, args.steps,
                           extra_fn=lambda: {"pipeline": pipe.state_dict()})
    return {"final_loss": losses[-1] if losses else None, "losses": losses}


def federated_training(args) -> dict:
    """SimDC federated loop: grade-partitioned rounds -> DeviceFlow -> FedAvg.

    Clients are split across the requested device grades; each round the
    hybrid allocator re-solves the per-grade logical/device split on
    *fleet-calibrated* runtimes (Table-I priors seed round 0, every round's
    fleet samples re-measure them), and ``HybridSimulation.run_plan_round``
    executes the plan — per-grade cohorts, fleet-sampled arrival times.
    """
    cfg = get_config(args.arch, smoke=True)  # clients train the reduced model
    api = get_model(cfg)
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    global_params = api.init(key, cfg)

    trigger = (
        SampleThresholdTrigger(args.sample_threshold)
        if args.trigger == "samples"
        else ScheduledTrigger(args.trigger_period)
    )
    # --fleet-shards N shards cohort execution and the fused fed_reduce over
    # an explicit ("dp", "mp") fleet mesh (redco-style data parallelism
    # across fleet shards).
    fleet_mesh = (make_fleet_mesh(args.fleet_shards)
                  if args.fleet_shards else None)
    svc = AggregationService(global_params, trigger=trigger, mesh=fleet_mesh)
    flow = DeviceFlow(svc, seed=args.seed)
    task_id = 0
    if args.traffic == "realtime":
        flow.register_task(task_id, AccumulatedStrategy(
            thresholds=(1,), failure_prob=args.dropout))
    else:
        flow.register_task(task_id, TimeIntervalStrategy(
            curve=right_tailed_normal(args.sigma), interval=args.round_seconds,
            failure_prob=args.dropout))

    local_train = _make_local_train(api, cfg, args.client_lr)

    # Grade partition: clients split evenly across the requested grades, one
    # DeviceTier (with its own behavioral fleet) per grade.
    grade_names = [g.strip() for g in args.grades.split(",") if g.strip()]
    cohort = args.clients_per_round
    per_grade = [cohort // len(grade_names)] * len(grade_names)
    per_grade[0] += cohort - sum(per_grade)
    specs = [
        GradeSpec(g, n, logical_bundles=max(1, n // 2), bundles_per_device=1,
                  physical_devices=max(1, n // 4))
        for g, n in zip(grade_names, per_grade)
    ]
    # Every round flows through the columnar plane: run_plan_round submits
    # one ArrivalBatch per cohort chunk straight into DeviceFlow.  Top-k
    # compression rides it as a ``payload_transform`` (per-emission host
    # hook) instead of bypassing the plane with a manual scalar submit loop.
    comp_residuals: dict = {}

    def compress_emission(e):
        if isinstance(e, ArrivalBatch) and e.buffer is not None:
            # Bench splits leave multiple batches sharing one buffer with
            # disjoint row ranges — slice this batch's rows out first.
            stacked = e.buffer.materialize()
            stacked = jax.tree.map(
                lambda l: l[np.asarray(e.rows)], stacked)
            # Error-feedback memory keyed by the chunk identity (first
            # device id + width is stable across rounds for a fixed plan).
            key = (e.task_id, int(e.device_ids[0]), e.n)
            kept, res, nnz = topk_compress_rows(
                stacked, comp_residuals.get(key),
                fraction=args.compress_fraction)
            comp_residuals[key] = res
            # Wire size per row = kept (value, int32 index) pairs; floor at
            # one entry so nbytes=0 never reads as "unset".
            return ArrivalBatch(
                e.task_id, e.round_idx,
                rows=np.arange(e.n, dtype=np.int64),
                created_t=e.created_t,
                nbytes=np.maximum(nnz, 1) * 8,
                num_samples=e.num_samples, device_ids=e.device_ids,
                buffer=UpdateBuffer.from_stacked(kept))
        if isinstance(e, Message):
            payload = (e.payload.materialize()
                       if isinstance(e.payload, UpdateHandle)
                       else e.payload)
            kept, _, stats = topk_compress(
                payload, topk_init(payload),
                fraction=args.compress_fraction)
            return dataclasses.replace(
                e, payload=kept,
                size_bytes=max(stats["nonzero"], 1) * 8)
        return e

    # --workers N shards cohort execution across N spawned processes
    # (runtime.workers): each worker runs its own jitted cohort loop and
    # ships chunk results back through shared-memory segments.  Process
    # sharding and mesh sharding are alternative scale-out axes — pick one.
    worker_kw = {}
    if args.workers:
        if args.fleet_shards:
            raise SystemExit(
                "--workers is incompatible with --fleet-shards: process "
                "sharding and fleet-mesh sharding are alternative scale-out "
                "axes")
        from repro.runtime.workers import WorkerSpec
        worker_kw = dict(
            workers=args.workers,
            worker_spec=WorkerSpec(
                _federated_worker_tiers,
                kwargs=dict(arch=args.arch, grades=tuple(grade_names),
                            seed=args.seed, client_lr=args.client_lr,
                            cohort=cohort)))
    sim = HybridSimulation(
        LogicalTier(local_train, cohort_size=cohort,
                    mesh=fleet_mesh, data_axis="dp"),
        tiers={g: DeviceTier(local_train, GRADES[g], seed=args.seed,
                             mesh=fleet_mesh, data_axis="dp")
               for g in grade_names},
        deviceflow=flow,
        wire=args.wire_format,
        error_feedback=(args.error_feedback == "on"),
        payload_transform=compress_emission if args.compress else None,
        **worker_kw)
    cal = RuntimeCalibrator()  # Table-I prior until fleets report in

    losses = []
    seq = 64
    for rnd in range(args.rounds):
        # Re-solve the split on the latest measured runtimes (paper §IV.B/C).
        plan = RoundPlan.from_allocation(
            solve_allocation(specs, cal.runtimes_for(specs)), specs)
        grade_batches, grade_counts = {}, {}
        for spec in specs:
            toks = rng.integers(
                1, cfg.vocab_size,
                size=(spec.num_devices, seq + 1)).astype(np.int32)
            grade_batches[spec.grade] = {
                "tokens": jnp.asarray(toks[:, None, :-1]),
                "targets": jnp.asarray(toks[:, None, 1:]),
                "mask": jnp.ones((spec.num_devices, 1, seq), jnp.float32),
            }
            grade_counts[spec.grade] = np.full(spec.num_devices, seq)
        outcome = sim.run_plan_round(
            task_id, rnd, svc.global_params, plan, grade_batches,
            grade_counts, jax.random.PRNGKey(rnd), calibrator=cal)
        # Per-device losses, flattened across chunks — chunks have unequal
        # sizes, so averaging chunk means would bias toward small chunks.
        losses.append(float(np.concatenate(
            [np.asarray(jax.tree.leaves(m)[0]).reshape(-1)
             for m in outcome.client_metrics]).mean()))

        # Columnar plane: run_plan_round already submitted the round's
        # ArrivalBatches (+ bench messages) with fleet-sampled times;
        # --compress and --wire-format int8 both ride it.
        round_end = float(np.max(outcome.arrival_times))
        # Rule-based dispatch points extend up to round_seconds past the
        # round end (= the slowest arrival); the run window must cover them
        # or the round's deliveries slip into the next window.
        flow.run(round_end + args.round_seconds)
        svc.tick(flow.clock.now)
        lat = svc.history[-1].mean_latency_s if svc.history else 0.0
        print(f"round {rnd:3d} client-loss {losses[-1]:.4f} "
              f"aggregations {len(svc.history)} "
              f"mean-latency {lat:.1f}s "
              f"shelf {len(flow.shelf(task_id))}", flush=True)
    # Drain capacity-spill dispatches scheduled past the last window.
    flow.run()
    svc.tick(flow.clock.now)
    shelf = flow.shelf(task_id)
    out = {"losses": losses, "aggregations": len(svc.history),
           "wire_bytes_received": shelf.total_bytes_received,
           "wire_bytes_dispatched": shelf.total_bytes_dispatched}
    if sim.pool is not None:
        st = sim.pool.stats
        print(f"workers: {args.workers} chunks {st['chunks']} "
              f"segments {st['segments_created']} "
              f"(reused {st['segment_reuses']}) "
              f"shipped {st['bytes_shipped'] / 1e6:.1f}MB "
              f"redispatched {st['redispatched_chunks']}", flush=True)
        out["worker_chunks"] = st["chunks"]
        out["worker_segment_reuses"] = st["segment_reuses"]
    sim.close()  # workers are daemonic — explicit close just recycles shm now
    return out


class _TaskRouter:
    """DeviceFlow deliver callback fanning out to per-task services."""

    def __init__(self):
        self.services: dict[int, AggregationService] = {}

    def __call__(self, d):
        # Delivery.task_id spans both planes (scalar message or columnar
        # batch) without materializing per-row adapter objects.
        self.services[d.task_id](d)


def multi_task_federated(args) -> dict:
    """``--tasks N``: event-driven multi-task rounds on one shared pool.

    N federated CTR-style LM tasks contend for a resource pool sized to fit
    roughly half of them at full demand; the ``TaskEngine`` interleaves
    their rounds on the shared ``VirtualClock`` (elastic grants let tasks
    run on a partial share and top back up as others finish), each round
    executes through ``HybridSimulation.run_plan_round`` with chunk
    streaming, and every task aggregates through its own *streaming*
    ``AggregationService``.  Reports per-task completion times plus the
    interleaved makespan vs the serial (back-to-back) estimate.
    """
    cfg = get_config(args.arch, smoke=True)
    api = get_model(cfg)
    rng = np.random.default_rng(args.seed)
    seq = 64
    n_clients = args.clients_per_round

    def local_train(params, batch, _rng):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, cfg)[0])(params)
        new = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - args.client_lr * g.astype(jnp.float32)
                          ).astype(p.dtype), params, grads)
        return new, loss

    spec = GradeSpec("High", n_clients, logical_bundles=max(1, n_clients // 2),
                     bundles_per_device=1,
                     physical_devices=max(1, n_clients // 4))
    # --priorities "5,1,1" pins per-task scheduling priorities (cycled to
    # --tasks length); default keeps the earlier-submitted-is-more-urgent
    # ordering.  With --preemptive, a later high-priority arrival reclaims
    # lower-priority grants at their round boundaries instead of waiting.
    if args.priorities:
        prios = [int(p) for p in args.priorities.split(",") if p.strip()]
        priorities = [prios[i % len(prios)] for i in range(args.tasks)]
    else:
        priorities = [args.tasks - i for i in range(args.tasks)]
    tasks = [Task(OperatorFlow(("train",)), (spec,), rounds=args.rounds,
                  priority=priorities[i]) for i in range(args.tasks)]
    # Pool fits about half the fleet at full demand (plus a spare bundle for
    # elastic partial grants): later tasks run on what is free and rebalance
    # up as earlier ones finish.
    fit = max(1, -(-args.tasks // 2))
    rm = ResourceManager(ResourcePool(
        {"High": spec.logical_bundles * fit + 1},
        {"High": spec.physical_devices * fit}))

    router = _TaskRouter()
    flow = DeviceFlow(router, seed=args.seed)
    for task in tasks:
        router.services[task.task_id] = AggregationService(
            api.init(jax.random.PRNGKey(args.seed + task.task_id), cfg),
            trigger=ClientCountTrigger(n_clients), streaming=True)
        flow.register_task(task.task_id, AccumulatedStrategy(
            thresholds=(1,), failure_prob=args.dropout))

    sim = HybridSimulation(
        LogicalTier(local_train, cohort_size=max(2, n_clients // 2)),
        tiers={"High": DeviceTier(local_train, GRADES["High"],
                                  seed=args.seed)},
        deviceflow=flow, stream_chunks=True)
    cal = RuntimeCalibrator()

    measured_total = [0.0]  # Σ measured round durations = serial makespan

    def round_runner(task, round_idx, allocation, t):
        svc = router.services[task.task_id]
        plan = RoundPlan.from_allocation(allocation, task.grades)
        toks = rng.integers(1, cfg.vocab_size,
                            size=(n_clients, seq + 1)).astype(np.int32)
        batches = {"tokens": jnp.asarray(toks[:, None, :-1]),
                   "targets": jnp.asarray(toks[:, None, 1:]),
                   "mask": jnp.ones((n_clients, 1, seq), jnp.float32)}
        outcome = sim.run_plan_round(
            task.task_id, round_idx, svc.global_params, plan,
            {"High": batches}, {"High": np.full(n_clients, seq)},
            jax.random.PRNGKey(1000 * task.task_id + round_idx),
            calibrator=cal)
        measured_total[0] += outcome.makespan_s
        return outcome.makespan_s  # measured duration times the next event

    engine = TaskEngine(rm, cal, round_runner=round_runner,
                        clock=flow.clock, elastic=True,
                        preemptive=args.preemptive)
    t0 = time.perf_counter()
    for i, task in enumerate(tasks):
        # Staggered arrivals (--arrival-gap) make priority meaningful: a
        # high-priority task arriving late must preempt, not just sort first.
        engine.submit(task, at=i * args.arrival_gap or None)
    result = engine.drain()
    wall_s = time.perf_counter() - t0
    serial_est = measured_total[0]  # back-to-back = sum of round durations
    for ex in result:
        print(f"task {ex.task.task_id}: prio={ex.task.priority} "
              f"rounds={ex.rounds_done} "
              f"start={ex.started_t:.0f}s finish={ex.finished_t:.0f}s "
              f"queue-delay={ex.queueing_delay_s:.0f}s "
              f"grant-util={ex.grant_utilization:.2f} "
              f"reallocations={ex.reallocations} "
              f"preemptions={ex.preemptions} "
              f"aggregations={len(router.services[ex.task.task_id].history)}",
              flush=True)
    print(f"interleaved makespan {engine.makespan:.0f}s vs serial estimate "
          f"{serial_est:.0f}s ({serial_est / max(engine.makespan, 1e-9):.2f}x)"
          f"; stranded={len(result.stranded)}; wall {wall_s:.1f}s", flush=True)
    top_prio = max(priorities)
    hi_delays = [ex.queueing_delay_s for ex in result
                 if ex.task.priority == top_prio]
    return {"makespan_s": engine.makespan, "serial_estimate_s": serial_est,
            "completed": len(result), "stranded": len(result.stranded),
            "top_priority_queueing_delay_s": max(hi_delays, default=0.0)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_3b")
    ap.add_argument("--mode", choices=("cloud", "federated"), default="federated")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tasks", type=int, default=1,
                    help="number of contending federated tasks; >1 runs the "
                         "event-driven multi-task engine on one shared pool")
    ap.add_argument("--priorities", default="",
                    help="comma-separated per-task scheduling priorities "
                         "(cycled to --tasks), e.g. '5,1,1'")
    ap.add_argument("--preemptive", action="store_true",
                    help="let higher-priority tasks refreeze lower-priority "
                         "grants down at round boundaries")
    ap.add_argument("--arrival-gap", type=float, default=0.0,
                    help="virtual seconds between successive task arrivals "
                         "(task i submits at i*gap)")
    ap.add_argument("--clients-per-round", type=int, default=8)
    ap.add_argument("--grades", default="High",
                    help="comma-separated device grades, e.g. High,Low")
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--trigger", choices=("samples", "scheduled"),
                    default="samples")
    ap.add_argument("--sample-threshold", type=int, default=256)
    ap.add_argument("--trigger-period", type=float, default=30.0)
    ap.add_argument("--traffic", choices=("realtime", "curve"),
                    default="realtime")
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--round-seconds", type=float, default=60.0)
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--workers", type=int, default=0,
                    help="shard cohort execution across N worker processes "
                         "(shared-memory columnar transport; 0 = in-process); "
                         "federated single-task mode only")
    ap.add_argument("--fleet-shards", type=int, default=0,
                    help="shard cohorts + fed_reduce over a ('dp','mp') "
                         "fleet mesh with this many data shards (0 = off)")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--compress-fraction", type=float, default=0.01)
    ap.add_argument("--wire-format", choices=("f32", "int8"), default="f32",
                    help="update wire format: int8 fuses symmetric per-row "
                         "quantization into the cohort jit (~4x fewer bytes "
                         "per round) with dequantize-and-reduce aggregation")
    ap.add_argument("--error-feedback", choices=("on", "off"), default="on",
                    help="carry int8 quantization residuals device-resident "
                         "across rounds (EF-SGD); only affects "
                         "--wire-format int8")
    ap.add_argument("--checkpoint-dir", default="artifacts/ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "cloud":
        out = cloud_training(args)
    elif args.tasks > 1:
        out = multi_task_federated(args)
    else:
        out = federated_training(args)
    print("DONE", {k: v for k, v in out.items() if k != "losses"})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
