"""A federated campaign on the program's normal path.

Build: the configuration's fleet and per-device records are made on the chip
from the seed (the reference module's ``make_data``); the allocator splits
each grade between the logical tier and the device tier; rounds run through
``HybridSimulation.run_plan_round`` -> ``DeviceFlow`` ->
``AggregationService`` with aggregation on the ``fed_reduce`` kernel.

Traffic (``kind: fl_rounds``): a closed loop of rounds.  ``warmup_rounds``
run in set-up; in the window rounds start back to back while it is open, and
it closes when the last started round's new global params are ready.
``fl_devices_per_s`` is every device of those rounds over that span.
``wire``, ``fleet_shards`` and ``stream_chunks`` select the program's
options.

Check: every window round's new global params against the reference's
FedAvg of its own local training from the same previous params, and the last
round's trained rows of each tier against the reference's rows.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from repro.core import (
    AccumulatedStrategy, AggregationService, DeviceFlow, GradeSpec,
    RoundPlan, RuntimeCalibrator, SampleThresholdTrigger, solve_allocation,
)
from repro.core.devicemodel import GRADES, DeviceFleet
from repro.core.simulation import DeviceTier, HybridSimulation, LogicalTier
from repro.models import ctr


def rel_err(got: np.ndarray, ref: np.ndarray, scale: float) -> float:
    """Largest absolute gap over ``scale`` (the reference's largest move)."""
    return float(np.abs(np.asarray(got, np.float64) - ref).max()
                 / max(scale, 1e-30))


class System:
    def __init__(self, config: dict, traffic: dict, *, seed: int, devices,
                 spans, reference, overrides: dict):
        self.cfg = {**config, **overrides.get("config", {})}
        self.traffic = {**traffic, **overrides.get("traffic", {})}
        self.seed = seed
        self.devices = devices
        self.spans = spans
        self.ref = reference

    # -- set-up -------------------------------------------------------------
    def plan(self) -> RoundPlan:
        """The allocator's split on fleet-calibrated runtimes."""
        fleet, res = self.cfg["fleet"], self.cfg["resources"]
        specs = [GradeSpec(g, n, **res[g]) for g, n in fleet.items()]
        cal = RuntimeCalibrator()
        for g in fleet:
            probe = DeviceFleet(GRADES[g], 64, seed=7)
            for r in range(3):
                cal.observe_fleet(probe.run_round(r))
        return RoundPlan.from_allocation(
            solve_allocation(specs, cal.runtimes_for(specs)), specs)

    def setup(self) -> None:
        cfg, tr, m = self.cfg, self.traffic, self.cfg["model"]
        with self.spans("setup.data"):
            self.batches, self.counts = self.ref.make_data(
                self.seed, cfg["fleet"], cfg["records_per_device"], m["dim"],
                cfg["data_recipe"])
            jax.block_until_ready(self.batches)
        self.plan_ = self.plan()
        mesh = None
        if tr["fleet_shards"] > 1:
            from repro.distribution.sharding import make_fleet_mesh
            mesh = make_fleet_mesh(tr["fleet_shards"])
        local = ctr.make_local_train_fn(lr=m["lr"], epochs=m["local_epochs"])
        cohort = cfg["cohort_size"]
        logical = LogicalTier(local, cohort_size=cohort, mesh=mesh,
                              data_axis="dp")
        tiers = {g: DeviceTier(local, GRADES[g], seed=self.seed,
                               cohort_size=cohort, mesh=mesh, data_axis="dp")
                 for g in cfg["fleet"]}
        total = sum(int(c.sum()) for c in self.counts.values())
        self.svc = AggregationService(
            ctr.lr_init(None, m["dim"]), trigger=SampleThresholdTrigger(total),
            reduce_impl=cfg["aggregation"]["reduce_impl"], mesh=mesh,
            streaming=tr["stream_chunks"])
        self.flow = DeviceFlow(self.svc)
        self.flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
        self.sim = HybridSimulation(
            logical, tiers=tiers, deviceflow=self.flow, wire=tr["wire"],
            error_feedback=tr["wire"] == "int8",
            stream_chunks=tr["stream_chunks"])
        self.key = self.ref.seed_key(self.seed)
        self.round_idx = 0
        self.rounds: list[tuple] = []  # (prev params, new params)
        with self.spans("setup.warmup"):
            for _ in range(tr["warmup_rounds"]):
                self.one_round()
        self.rounds.clear()

    def one_round(self):
        prev = self.svc.global_params
        with self.spans("fl.round"):
            with self.spans("fl.sim"):
                out = self.sim.run_plan_round(
                    0, self.round_idx, prev, self.plan_, self.batches,
                    self.counts, jax.random.fold_in(self.key, self.round_idx))
            with self.spans("fl.flow_drain"):
                self.flow.run()
                jax.block_until_ready(self.svc.global_params)
        self.round_idx += 1
        self.rounds.append((prev, self.svc.global_params))
        self.last = out
        return out

    # -- measured window ----------------------------------------------------
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        end = t0 + seconds
        aggregations = len(self.svc.history)
        while time.perf_counter() < end:
            self.one_round()
        span = time.perf_counter() - t0
        n_dev = self.plan_.total_devices
        done = len(self.svc.history) - aggregations
        rounds = len(self.rounds)
        m = self.cfg["model"]
        counters = {
            "rounds": rounds, "devices": n_dev, "span_s": span,
            "records": self.cfg["records_per_device"], "dim": m["dim"],
            "epochs": m["local_epochs"], "fleet_shards":
                self.traffic["fleet_shards"],
            # One fed_reduce call per leaf of each chunk's update buffer.
            "fed_reduce_calls": [
                (int(leaf.shape[0]), int(leaf.shape[1]),
                 leaf.dtype.itemsize, b.buffer.scales is not None)
                for b in self.last.batches for leaf in b.buffer.leaves2d],
        }
        return {"metrics": {"fl_devices_per_s": rounds * n_dev / span},
                "attempted": rounds, "failed": rounds - done,
                "counters": counters}

    # -- check --------------------------------------------------------------
    def release(self) -> None:
        """Keep what the check needs (each window round's params, the last
        round's rows on the host), free the program's state."""
        n = self.plan_.total_devices
        dim = self.cfg["model"]["dim"]
        rows = np.full((n, 1 + dim), np.nan)
        for b in self.last.batches:
            ids = np.asarray(b.device_ids)
            leaves = [np.asarray(leaf)[np.asarray(b.rows)]
                      for leaf in b.buffer.leaves2d]
            if b.buffer.scales is not None:
                leaves = [leaf * np.asarray(s)[np.asarray(b.rows), None]
                          for leaf, s in zip(leaves, b.buffer.scales)]
            rows[ids] = np.concatenate(leaves, axis=1)
        self.rows = rows
        self.window_rounds = [
            ({k: np.asarray(v) for k, v in p.items()},
             {k: np.asarray(v) for k, v in q.items()})
            for p, q in self.rounds]
        self.split = [(e.grade, e.num_logical) for e in self.plan_.entries]
        del self.sim, self.svc, self.flow, self.last, self.rounds
        gc.collect()

    def reference_outputs(self, tiers: dict) -> tuple[list, np.ndarray]:
        """Each window round's FedAvg and the last round's trained rows, as
        the reference computes them in ``tiers``' precision."""
        m = self.cfg["model"]
        weights = np.concatenate([self.counts[g] for g, _ in self.split])
        params = []
        for prev, _ in self.window_rounds:
            rows = self.ref.round_rows(prev, self.batches, self.split,
                                       lr=m["lr"], epochs=m["local_epochs"],
                                       tiers=tiers)
            params.append(self.ref.fedavg(rows, weights))
        return params, rows

    def compare(self, got_params: list, got_rows: np.ndarray,
                ref_params: list, ref_rows: np.ndarray) -> dict:
        """Each gap over the reference's own move from the previous params:
        the worst window round's aggregate, and the last round's rows of each
        tier."""
        flat = lambda p: np.concatenate([np.ravel(p["b"]), np.ravel(p["w"])])
        prevs = [flat(p) for p, _ in self.window_rounds]
        out = {"agg_err": max(
            rel_err(g, r, np.abs(r - p0).max())
            for g, r, p0 in zip(got_params, ref_params, prevs))}
        logical = np.zeros(len(ref_rows), bool)
        off = 0
        for grade, n_logical in self.split:
            logical[off:off + n_logical] = True
            off += len(self.counts[grade])
        for name, sel in (("logical_rows_err", logical),
                          ("device_rows_err", ~logical)):
            if sel.any():
                scale = np.abs(ref_rows[sel] - prevs[-1]).max()
                out[name] = rel_err(got_rows[sel], ref_rows[sel], scale)
        return out

    def program_outputs(self) -> tuple[list, np.ndarray]:
        flat = lambda p: np.concatenate([np.ravel(p["b"]), np.ravel(p["w"])])
        return [flat(q) for _, q in self.window_rounds], self.rows

    def check(self) -> dict:
        self.ref_out = self.reference_outputs(self.cfg["tiers"])
        values = self.compare(*self.program_outputs(), *self.ref_out)
        limits = self.cfg["limits"]
        return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}

    def control(self) -> dict:
        """The same numbers with the control in the program's place: the
        reference one precision step below the configuration (after
        ``check``, whose reference it reuses)."""
        low = self.reference_outputs(self.cfg["control_tiers"])
        return self.compare(*low, *self.ref_out)
