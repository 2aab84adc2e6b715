"""Smoke run of SimDC's main paths on a TPU, with their correctness checks.

    python chip_smoke.py              # phases (a) and (b), one chip
    python chip_smoke.py --chips 4    # phase (c) only, four chips

(a) Federated rounds: the paper's CTR campaign at its own scale.  The
    avazu-lr model (``configs/avazu_lr.py``: dim 256, lr 1e-3, 10 local
    epochs) trains on 100 000 devices split over the High and Low grades,
    20 synthetic records each (2 M records, made from ``--seed``).  Rounds
    run the allocator's plan (fig7's resources, a nonzero physical split)
    through ``HybridSimulation.run_plan_round`` -> ``DeviceFlow`` ->
    ``AggregationService``, wired as in ``examples/quickstart.py``, with
    aggregation on the compiled ``fed_reduce`` Pallas kernel.  Three rounds
    on the f32 wire, three on the int8 wire with error feedback.  Each
    round's new global params must match a float64 host FedAvg of the same
    update rows (int8: dequantize, then reduce), DeviceFlow must conserve
    its rows, and client losses must be finite.  One more f32 round runs its
    cohorts in two worker processes on the host CPUs (``FleetWorkerPool``)
    and must conserve its rows and bytes.  The logical tier's local
    training is checked on its own: the first 512 logical devices' trained
    params must match a float64 NumPy run of the same local SGD.
(b) Serving: ``launch/serve.py``'s continuous mode on granite-moe-3b-a800m
    at published widths (bf16 weights from the seed), 16 slots, prompt 128,
    32 decode tokens, 32 requests -- once with the Pallas decode-attention
    kernel and once with the jnp reference.  Every request must finish, and
    one Pallas decode-attention call at the served arena's shape must match
    ``decode_attention_ref`` within bf16 tolerance, and so must one Pallas
    flash-attention call at a 4 x 128-token granite prefill against
    ``attention_ref`` (no served path takes that kernel).  The share of
    decoded tokens the two serving runs agree on is printed.
(c) ``--chips 4``: one round of the same campaign on a 4-way fleet mesh
    (``make_fleet_mesh(4)``: sharded cohorts and ``fed_reduce(mesh=)``, the
    path of ``train.py --fleet-shards``) against the same round on one chip,
    both on the allocator's own split, whose last cohort chunk of each tier
    does not divide over the four shards.

Compile seconds, each phase's wall time and every check print as they
happen; the last line is one JSON object naming the device.  The script
exits non-zero, without that line, when the JAX backend is not a TPU or a
check fails.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, compiled programs
are cached there (else in ``<repo>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.configs.avazu_lr import CONFIG as CTR  # noqa: E402
from repro.core import (  # noqa: E402
    AccumulatedStrategy, AggregationService, DeviceFlow, GradeSpec,
    RoundPlan, RuntimeCalibrator, SampleThresholdTrigger, solve_allocation,
)
from repro.core.devicemodel import GRADES, DeviceFleet  # noqa: E402
from repro.core.simulation import (  # noqa: E402
    DeviceTier, HybridSimulation, LogicalTier,
)
from repro.data.synthetic_ctr import make_federated_ctr  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import ctr  # noqa: E402

FLEET = {"High": 50_000, "Low": 50_000}  # 100 000 devices
RECORDS = 20  # per device: 2 M records in all
ROUNDS = 3
# Per-grade resources of the allocation cell (benchmarks' fig7): logical
# bundles f_i, bundles per emulated device k_i, physical phones m_i.
RESOURCES = {
    "High": dict(logical_bundles=200, bundles_per_device=8, physical_devices=17),
    "Low": dict(logical_bundles=200, bundles_per_device=2, physical_devices=13),
}
COHORT = 16_384  # devices per cohort dispatch, both tiers
# 16 slots; a 10 ms dispatch window lands the 32 requests together, so the
# slots fill and retire and refill.
SERVE_ARGS = ["--arch", "granite_moe_3b_a800m", "--mode", "continuous",
              "--batch-size", "16", "--prompt-len", "128",
              "--decode-tokens", "32", "--requests", "32",
              "--interval", "0.01"]
# A 10^5-row f32 weighted sum carries ~sqrt(n)*eps relative rounding; 1e-4
# of the largest parameter bounds it with margin and still catches a bf16
# (8-bit mantissa) reduction.
F32_RTOL = 1e-4
BF16_TOL = 2e-2  # tests/test_kernels.py's bf16 tolerance
LOCAL_ROWS = 512  # devices checked against the float64 local-train reference


class CompileClock:
    """Sums the backend-compile and compile-cache-read seconds JAX reports."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_read_s = 0.0

    def __call__(self, event: str, duration_s: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_s
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_read_s += duration_s


class Checks:
    """Prints every check as it is made; ``failed`` names the failures."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def ctr_tiers(*, seed: int, cohort: int = COHORT, mesh=None):
    """The campaign's ``(LogicalTier, {grade: DeviceTier})``; also the
    ``WorkerSpec`` factory, so pooled chunks run the same tiers."""
    local_train = ctr.make_local_train_fn(lr=CTR.lr, epochs=CTR.local_epochs)
    return (LogicalTier(local_train, cohort_size=cohort, mesh=mesh,
                        data_axis="dp"),
            {g: DeviceTier(local_train, GRADES[g], seed=seed,
                           cohort_size=cohort, mesh=mesh, data_axis="dp")
             for g in FLEET})


def campaign_data(fleet: dict, records: int, dim: int, seed: int):
    """Per-grade client batches on the device and per-device sample counts."""
    batches, counts = {}, {}
    for i, (grade, n) in enumerate(fleet.items()):
        data = make_federated_ctr(num_devices=n, records_per_device=records,
                                  dim=dim, seed=seed + i)
        x, y, c = data.stacked_shards(np.arange(n), records)
        mask = (np.arange(records)[None] < c[:, None]).astype(np.float32)
        batches[grade] = {"x": jnp.asarray(x), "y": jnp.asarray(y),
                          "mask": jnp.asarray(mask)}
        counts[grade] = c
    return batches, counts


def campaign_plan(fleet: dict) -> RoundPlan:
    """The allocator's split on fleet-calibrated runtimes."""
    specs = [GradeSpec(g, n, **RESOURCES[g]) for g, n in fleet.items()]
    cal = RuntimeCalibrator()
    for g in fleet:
        probe = DeviceFleet(GRADES[g], 64, seed=7)
        for r in range(3):
            cal.observe_fleet(probe.run_round(r))
    return RoundPlan.from_allocation(
        solve_allocation(specs, cal.runtimes_for(specs)), specs)


def host_fedavg(params, batches) -> list[np.ndarray]:
    """float64 host FedAvg of a round's update rows (server lr 1): the
    sample-weighted mean of every row, int8 rows dequantized first."""
    sums, total = None, 0.0
    for b in batches:
        buf = b.buffer
        w = np.asarray(b.num_samples, np.float64)
        leaves = [np.asarray(leaf)[b.rows].astype(np.float64)
                  for leaf in buf.leaves2d]
        if buf.scales is not None:
            leaves = [leaf * np.asarray(s, np.float64)[b.rows, None]
                      for leaf, s in zip(leaves, buf.scales)]
        part = [w @ leaf for leaf in leaves]
        sums = part if sums is None else [a + p for a, p in zip(sums, part)]
        total += w.sum()
    return [(s / total).reshape(np.shape(g))
            for g, s in zip(jax.tree.leaves(params), sums)]


def host_local_train(params, batch, *, lr: float, epochs: int) -> list:
    """float64 NumPy of ``ctr.make_local_train_fn`` on a stack of devices:
    ``epochs`` full-batch steps of the masked mean BCE.  Returns the trained
    ``[b, w]`` rows in ``jax.tree.leaves`` order."""
    x = np.asarray(batch["x"], np.float64)  # (n, records, dim)
    y = np.asarray(batch["y"], np.float64)
    mask = np.asarray(batch["mask"], np.float64)
    n = x.shape[0]
    w = np.tile(np.asarray(params["w"], np.float64), (n, 1))
    b = np.full(n, float(params["b"]))
    denom = np.maximum(mask.sum(1), 1.0)[:, None]
    for _ in range(epochs):
        logits = np.einsum("nrd,nd->nr", x, w) + b[:, None]
        g = (1.0 / (1.0 + np.exp(-logits)) - y) * mask / denom
        w -= lr * np.einsum("nrd,nr->nd", x, g)
        b -= lr * g.sum(1)
    return [b[:, None], w]


def update_rows(batches, lo: int, hi: int) -> list[np.ndarray]:
    """Update rows of global devices ``[lo, hi)``, which one batch holds."""
    for b in batches:
        ids = np.asarray(b.device_ids)
        if ids[0] <= lo and hi - 1 <= ids[-1]:
            r0 = int(np.asarray(b.rows)[lo - ids[0]])
            return [np.asarray(leaf[r0:r0 + hi - lo], np.float64)
                    for leaf in b.buffer.leaves2d]
    raise LookupError(f"no batch holds devices [{lo}, {hi})")


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, across parameter leaves."""
    got = [np.asarray(g, np.float64) for g in jax.tree.leaves(got)]
    ref = [np.asarray(r, np.float64) for r in jax.tree.leaves(ref)]
    err = max(float(np.abs(g - r).max()) for g, r in zip(got, ref))
    scale = max(float(np.abs(r).max()) for r in ref)
    return err / max(scale, np.finfo(np.float32).tiny)


def run_campaign(name: str, sim: HybridSimulation, svc: AggregationService,
                 flow: DeviceFlow, plan: RoundPlan, batches, counts, *,
                 rounds: int, seed: int, check: Checks,
                 clock: CompileClock, check_local: bool = False):
    """``rounds`` planned rounds, each checked against the host reference;
    returns the last round's outcome.  ``check_local`` also checks the last
    round's first ``LOCAL_ROWS`` logical devices against
    ``host_local_train`` (the f32 wire only: int8 rows are quantized)."""
    n_dev = plan.total_devices
    for rnd in range(rounds):
        c0, t0 = clock.compile_s, time.perf_counter()
        prev = svc.global_params
        out = sim.run_plan_round(0, rnd, prev, plan, batches, counts,
                                 jax.random.PRNGKey(seed * 1000 + rnd))
        flow.run()
        jax.block_until_ready(svc.global_params)
        wall = time.perf_counter() - t0
        losses = np.concatenate([np.asarray(m["loss"]).reshape(-1)
                                 for m in out.client_metrics])
        print(f"{name} round {rnd}: wall {wall:.3f} s (compile "
              f"{clock.compile_s - c0:.3f} s), {len(out.batches)} chunks, "
              f"mean client loss {losses.mean():.6f}", flush=True)
        check(f"{name} round {rnd} aggregated once over every device",
              len(svc.history) == rnd + 1
              and svc.history[-1].num_clients == n_dev
              and sum(b.n for b in out.batches) == n_dev,
              f"aggregations={len(svc.history)} "
              f"clients={svc.history[-1].num_clients if svc.history else 0}")
        err = rel_err(svc.global_params, host_fedavg(prev, out.batches))
        check(f"{name} round {rnd} params match float64 host FedAvg",
              err <= F32_RTOL, f"rel_err={err:.3e} <= {F32_RTOL:g}")
        check(f"{name} round {rnd} losses finite",
              bool(np.isfinite(losses).all()) and losses.size == n_dev,
              f"{losses.size} losses")
        if check_local and rnd == rounds - 1:
            check_local_train(name, prev, plan, out, batches, check)
    shelf = flow.shelf(0)
    check(f"{name} DeviceFlow conserves rows", flow.conservation_ok(0)
          and shelf.total_received == rounds * n_dev,
          f"received={shelf.total_received} "
          f"dispatched={shelf.total_dispatched} "
          f"dropped={shelf.total_dropped}")
    return out


def check_local_train(name: str, params, plan: RoundPlan, out, batches,
                      check: Checks) -> None:
    """The first grade's first ``LOCAL_ROWS`` devices run on the logical
    tier; their trained params must match float64 NumPy at f32 tolerance.
    The device tier's first rows are measured against the same reference
    and printed: its bf16 backend is meant to miss it."""
    first = plan.entries[0]
    tiers = (("logical", 0, first.num_logical),
             ("device", first.num_logical, first.num_devices))
    for tier, lo, hi in tiers:
        hi = min(lo + LOCAL_ROWS, hi)
        batch = {k: np.asarray(v[lo:hi]) for k, v in
                 batches[first.grade].items()}
        ref = host_local_train(params, batch, lr=CTR.lr,
                               epochs=CTR.local_epochs)
        err = rel_err(update_rows(out.batches, lo, hi), ref)
        if tier == "logical":
            check(f"{name} logical local training matches float64 NumPy",
                  hi - lo == LOCAL_ROWS and err <= F32_RTOL,
                  f"{hi - lo} devices, rel_err={err:.3e} <= {F32_RTOL:g}")
        else:
            print(f"{name}: bf16 device tier against the same reference, "
                  f"{hi - lo} devices: rel_err={err:.3e}", flush=True)


def federated_service(params, counts, *, mesh=None):
    """Aggregation behind realtime dispatch: one aggregation per round,
    fired when every device's samples have landed."""
    total = sum(int(c.sum()) for c in counts.values())
    svc = AggregationService(params, trigger=SampleThresholdTrigger(total),
                             reduce_impl="pallas", mesh=mesh)
    flow = DeviceFlow(svc)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    return svc, flow


def phase_federated(seed: int, check: Checks, clock: CompileClock) -> None:
    from repro.runtime.workers import WorkerSpec

    t0 = time.perf_counter()
    batches, counts = campaign_data(FLEET, RECORDS, CTR.dim, seed)
    plan = campaign_plan(FLEET)
    jax.block_until_ready(batches)
    print(f"a: {plan.total_devices} devices, "
          f"{sum(int(c.sum()) for c in counts.values())} records, dim "
          f"{CTR.dim}; data set-up {time.perf_counter() - t0:.3f} s; plan "
          + ", ".join(f"{e.grade}: {e.num_logical} logical / "
                      f"{e.num_physical} physical" for e in plan.entries),
          flush=True)
    check("a plan has a physical split",
          sum(e.num_physical for e in plan.entries) > 0,
          "physical per grade "
          + str({e.grade: e.num_physical for e in plan.entries}))
    final = None
    for wire in ("f32", "int8"):
        t0 = time.perf_counter()
        svc, flow = federated_service(ctr.lr_init(None, CTR.dim), counts)
        logical, tiers = ctr_tiers(seed=seed)
        sim = HybridSimulation(logical, tiers=tiers, deviceflow=flow,
                               wire=wire, error_feedback=(wire == "int8"))
        run_campaign(f"a/{wire}", sim, svc, flow, plan, batches, counts,
                     rounds=ROUNDS, seed=seed, check=check, clock=clock,
                     check_local=(wire == "f32"))
        print(f"a/{wire}: {ROUNDS} rounds in "
              f"{time.perf_counter() - t0:.3f} s, "
              f"{flow.shelf(0).total_bytes_dispatched} wire bytes",
              flush=True)
        if wire == "f32":
            final = svc.global_params

    # One f32 round with its cohorts in two CPU worker processes.
    t0 = time.perf_counter()
    svc, flow = federated_service(final, counts)
    logical, tiers = ctr_tiers(seed=seed)
    spec = WorkerSpec(ctr_tiers, kwargs=dict(seed=seed))
    with HybridSimulation(logical, tiers=tiers, deviceflow=flow,
                          workers=2, worker_spec=spec) as sim:
        out = sim.run_plan_round(0, ROUNDS, final, plan, batches, counts,
                                 jax.random.PRNGKey(seed * 1000 + ROUNDS))
        flow.run()
        chunks = sim.pool.stats["chunks"]
    shelf = flow.shelf(0)
    n_dev = plan.total_devices
    row_nbytes = out.batches[0].buffer.row_nbytes
    print(f"a/workers: 1 round over 2 CPU workers in "
          f"{time.perf_counter() - t0:.3f} s, {chunks} chunks", flush=True)
    check("a/workers round conserves rows and bytes",
          flow.conservation_ok(0) and shelf.total_received == n_dev
          and shelf.total_dispatched == n_dev
          and shelf.total_bytes_dispatched == n_dev * row_nbytes
          and len(svc.history) == 1 and svc.history[0].num_clients == n_dev,
          f"rows={shelf.total_dispatched}/{n_dev} "
          f"bytes={shelf.total_bytes_dispatched}/{n_dev * row_nbytes}")


def phase_serving(seed: int, check: Checks, clock: CompileClock) -> None:
    from repro.configs.registry import get_config
    from repro.kernels.decode_attention.ops import (
        decode_attention, decode_attention_ref, tuned_block_k)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.launch import serve

    tokens = {}
    for impl in ("pallas", "ref"):
        args = serve.parse_args(SERVE_ARGS + ["--attn-impl", impl,
                                              "--seed", str(seed)])
        c0, t0 = clock.compile_s, time.perf_counter()
        rep = serve.run(args)["continuous"]
        wall = time.perf_counter() - t0
        done = [r for r in rep.records if r.finish_t is not None
                and len(r.tokens) == args.decode_tokens + 1]
        print(f"b/{impl}: {args.requests} requests in {wall:.3f} s "
              f"(compile {clock.compile_s - c0:.3f} s)", flush=True)
        check(f"b/{impl} every request finished",
              len(done) == len(rep.records) == args.requests,
              f"{len(done)}/{args.requests} with "
              f"{args.decode_tokens + 1} tokens")
        tokens[impl] = {r.request_id: r.tokens for r in rep.records}
        del rep
        gc.collect()  # free this run's weights before the next one's
    same = sum(a == b for rid in tokens["pallas"]
               for a, b in zip(tokens["pallas"][rid], tokens["ref"][rid]))
    total = sum(len(t) for t in tokens["pallas"].values())
    print(f"b: pallas and ref attention agree on {same}/{total} tokens "
          f"({same / total:.4f})", flush=True)

    # One decode-attention call at the served arena's shape.
    cfg = get_config(args.arch, smoke=args.smoke)
    slots = args.batch_size
    max_len = args.prompt_len + args.decode_tokens + 1
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(k0, (slots, cfg.num_heads, cfg.head_dim), dt)
    kv_shape = (slots, cfg.num_kv_heads, max_len, cfg.head_dim)
    kc = jax.random.normal(k1, kv_shape, dt)
    vc = jax.random.normal(k2, kv_shape, dt)
    lens = jax.random.randint(k3, (slots,), 0, max_len + 1).at[0].set(0)
    block_k = tuned_block_k(max_len, head_dim=cfg.head_dim)
    got = decode_attention(q, kc, vc, lens, impl="pallas", block_k=block_k)
    ref = decode_attention_ref(q.astype(jnp.float32), kc.astype(jnp.float32),
                               vc.astype(jnp.float32), lens)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max())
    check("b pallas decode attention matches decode_attention_ref",
          bool(np.allclose(got, ref, atol=BF16_TOL, rtol=BF16_TOL)),
          f"max abs err {err:.3e}, bf16 tol {BF16_TOL:g}")

    # One flash-attention call at a 4 x 128-token granite prefill.
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(k0, (4, 128, cfg.num_heads, cfg.head_dim), dt)
    kv_shape = (4, 128, cfg.num_kv_heads, cfg.head_dim)
    k = jax.random.normal(k1, kv_shape, dt)
    v = jax.random.normal(k2, kv_shape, dt)
    got = flash_attention(q, k, v, causal=True, impl="pallas")
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max())
    check("b pallas flash attention matches attention_ref",
          bool(np.allclose(got, ref, atol=BF16_TOL, rtol=BF16_TOL)),
          f"max abs err {err:.3e}, bf16 tol {BF16_TOL:g}")


def phase_sharded(seed: int, check: Checks, clock: CompileClock,
                  shards: int = 4) -> None:
    from repro.distribution.sharding import make_fleet_mesh

    batches, counts = campaign_data(FLEET, RECORDS, CTR.dim, seed)
    plan = campaign_plan(FLEET)
    mesh = make_fleet_mesh(shards)
    params = {}
    for name, m in (("one chip", None), (f"{shards}-way mesh", mesh)):
        svc, flow = federated_service(ctr.lr_init(None, CTR.dim), counts,
                                      mesh=m)
        logical, tiers = ctr_tiers(seed=seed, mesh=m)
        sim = HybridSimulation(logical, tiers=tiers, deviceflow=flow)
        out = run_campaign(f"c/{name}", sim, svc, flow, plan, batches,
                           counts, rounds=1, seed=seed, check=check,
                           clock=clock)
        params[name] = svc.global_params
        if m is not None:
            spans = {len(leaf.sharding.device_set) for b in out.batches
                     for leaf in b.buffer.leaves2d}
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.devices()]
            print(f"c: peak bytes in use per device {peaks}", flush=True)
            check("c every cohort update is sharded over the mesh",
                  spans == {shards}, f"devices per update leaf {spans}")
    err = rel_err(params[f"{shards}-way mesh"], params["one chip"])
    check(f"c {shards}-way mesh round matches the one-chip round",
          err <= F32_RTOL, f"rel_err={err:.3e} <= {F32_RTOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet-sharded round (phase c)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: the JAX backend is {jax.default_backend()!r}, "
              "not a TPU", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}", flush=True)
    check = Checks()
    phases = ([("c", phase_sharded)] if args.chips == 4 else
              [("a", phase_federated), ("b", phase_serving)])
    t_all = time.perf_counter()
    for name, phase in phases:
        c0, t0 = clock.compile_s, time.perf_counter()
        phase(args.seed, check, clock)
        print(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, "
              f"compile {clock.compile_s - c0:.3f} s", flush=True)
    print(f"total: wall {time.perf_counter() - t_all:.3f} s, compile "
          f"{clock.compile_s:.3f} s, compile-cache reads "
          f"{clock.cache_read_s:.3f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
