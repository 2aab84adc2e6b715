"""Program tracing: spans and counters observe the round and the serving
step without changing them, and land on the profiler's host plane."""
import glob
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.analysis import lint
from repro.configs.registry import get_config
from repro.core.deviceflow import DeviceFlow, Message
from repro.core.devicemodel import GRADES
from repro.core.federation import AggregationService, SampleThresholdTrigger
from repro.core.serving import ContinuousBatchingEngine
from repro.core.simulation import (
    DeviceTier,
    HybridSimulation,
    LogicalTier,
    RoundPlan,
)
from repro.core.strategies import AccumulatedStrategy
from repro.core.task import GradeSpec
from repro.data.synthetic_ctr import make_federated_ctr
from repro.models import ctr as ctr_lib

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


@pytest.fixture(autouse=True)
def fresh_tracing():
    tracing.recorder().clear()
    yield
    tracing.recorder().clear()


@pytest.fixture
def profiled(tmp_path):
    """``profiled()``: a ``jax.profiler`` trace, the one switch that turns
    the program's tracing on."""
    return lambda: jax.profiler.trace(str(tmp_path))


def _spin(ns: int) -> None:
    end = time.perf_counter_ns() + ns
    while time.perf_counter_ns() < end:
        pass


def test_off_span_is_the_shared_noop_and_records_nothing():
    assert not tracing.on()
    a, b = tracing.span("fl.chunk", round_idx=1), tracing.span("agg.apply")
    assert a is b
    with a:
        tracing.count("flow.deliveries", 5)
    with tracing.span("fl.round", round_idx=0) as r:
        assert r is a
    assert tracing.recorder().spans == []


def test_nested_spans_record_parent_counters_and_self_time(profiled):
    timed = tracing.Timed(_spin)
    with profiled():
        assert tracing.on()
        t0 = time.perf_counter()
        with tracing.span("fl.round", round_idx=3):
            _spin(2_000_000)
            with tracing.span("flow.submit"):
                with tracing.span("flow.dispatch"):
                    timed(1_000_000)
                    timed(0)
                    tracing.count("flow.deliveries", timed.n)
                    tracing.count("flow.deliver_ns", timed.ns)
                tracing.count("flow.deliveries", 1)
            with tracing.span("fl.chunk"):
                _spin(1_000_000)
        tracing.count("flow.deliveries", 10)  # outside any span: dropped
        t1 = time.perf_counter()
    assert not tracing.on()
    rec = tracing.recorder()
    by = {s.name: s for s in rec.spans}
    assert [s.name for s in rec.spans] == [
        "flow.dispatch", "flow.submit", "fl.chunk", "fl.round"]
    rnd, sub, disp, chunk = (by[n] for n in (
        "fl.round", "flow.submit", "flow.dispatch", "fl.chunk"))
    assert rnd.parent is None and rnd.args == {"round_idx": 3}
    assert sub.parent == rnd.id and chunk.parent == rnd.id
    assert disp.parent == sub.id
    assert [a.name for a in rec.ancestors(disp)] == ["flow.submit",
                                                     "fl.round"]
    assert rec.self_ns(rnd) == rnd.ns - sub.ns - chunk.ns
    assert rec.self_ns(rnd) >= 2_000_000
    assert rec.self_ns(disp) == disp.ns
    # Counters belong to the innermost open span.
    assert disp.counters["flow.deliveries"] == 2 == timed.n
    assert 1_000_000 <= disp.counters["flow.deliver_ns"] <= disp.ns
    assert sub.counters == {"flow.deliveries": 1}
    assert rnd.counters == chunk.counters == {}
    tree = rec.subtree(sub)
    assert {s.name for s in tree} == {"flow.submit", "flow.dispatch"}
    assert rec.counted(tree, "flow.deliveries") == 3
    assert rec.outermost(rec.spans, "flow.") == [sub]
    assert rec.window(t0, t1) == rec.spans
    assert rec.window(t0, t0) == []


def test_spans_land_on_the_host_plane_under_the_profiler(tmp_path):
    """While a profiler trace collects the program's spans are ``simdc.``
    events of ``/host:CPU``; after it stops a span is off again."""
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with tracing.span("serve.step", step=7):
        with tracing.span("serve.decode", step=7):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    with tracing.span("serve.step", step=8) as off:
        assert not tracing.on() and off is tracing.span("serve.step")
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(files[0])
    names = [e.name for p in data.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events
             if e.name.startswith(tracing.PREFIX)]
    assert sorted(names) == ["simdc.serve.decode", "simdc.serve.step"]
    assert [s.name for s in tracing.recorder().spans] == [
        "serve.decode", "serve.step"]


# --------------------------------------------------------------------------- #
# The federated round and the serving step, with tracing on and off
# --------------------------------------------------------------------------- #
def _streamed_rounds(rounds: int = 2):
    """Two grades, logical and device tiers, streamed into a streaming
    service through a threshold-1 DeviceFlow: the benchmark's FL path."""
    dim, rpd = 8, 6
    local = ctr_lib.make_local_train_fn(lr=1e-2, epochs=2)
    gb, gs = {}, {}
    for i, (g, n) in enumerate((("High", 11), ("Low", 7))):
        data = make_federated_ctr(num_devices=n, records_per_device=rpd,
                                  dim=dim, seed=i)
        X, Y, counts = data.stacked_shards(np.arange(n), rpd)
        mask = (np.arange(rpd)[None] < counts[:, None]).astype(np.float32)
        gb[g] = {"x": jnp.asarray(X), "y": jnp.asarray(Y),
                 "mask": jnp.asarray(mask)}
        gs[g] = counts
    specs = [GradeSpec("High", 11, benchmarking_devices=2,
                       logical_bundles=4, bundles_per_device=2,
                       physical_devices=3),
             GradeSpec("Low", 7, benchmarking_devices=1, logical_bundles=2,
                       bundles_per_device=1, physical_devices=2)]
    from repro.core.allocation import GradeRuntime, solve_allocation

    plan = RoundPlan.from_allocation(
        solve_allocation(specs, [GradeRuntime(2.0, 3.0, 1.0)] * 2), specs)
    total = sum(int(c.sum()) for c in gs.values())
    svc = AggregationService(ctr_lib.lr_init(None, dim),
                             trigger=SampleThresholdTrigger(total),
                             reduce_impl="ref", streaming=True)
    flow = DeviceFlow(svc)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    sim = HybridSimulation(
        LogicalTier(local, cohort_size=4),
        tiers={g: DeviceTier(local, GRADES[g], seed=5, cohort_size=3)
               for g in gs},
        deviceflow=flow, stream_chunks=True)
    key = jax.random.PRNGKey(9)
    outs = []
    for r in range(rounds):
        outs.append(sim.run_plan_round(0, r, svc.global_params, plan, gb, gs,
                                       jax.random.fold_in(key, r)))
        flow.run()
    return plan, svc, flow, sim, outs


def _round_fingerprint(svc, flow, sim, outs):
    out = {"params": [np.asarray(v) for ev in svc.history
                      for v in jax.tree.leaves(ev.global_params)],
           "history": [(ev.t, ev.num_clients, ev.num_samples,
                        ev.mean_latency_s) for ev in svc.history],
           "fleets": [(g, t.fleet.state_dict()["counters"].tolist())
                      for g, t in sorted(sim.tiers.items())]}
    flow_state = flow.state_dict()[0]
    out["dispatcher"] = repr(flow_state["dispatcher"])
    out["shelf"] = {k: v for k, v in flow_state["shelf"].items()
                    if k not in ("buf", "buffers")}
    out["emissions"] = [
        (o.arrival_times.tolist(),
         [(b.device_ids.tolist(), b.rows.tolist(), b.num_samples.tolist(),
           b.created_t.tolist()) for b in o.batches],
         [repr(r) for r in o.reports],
         [np.asarray(leaf).tolist() for b in o.batches
          for leaf in b.buffer.leaves2d])
        for o in outs]
    return out


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        if k == "params":
            assert len(a[k]) == len(b[k])
            for x, y in zip(a[k], b[k]):
                np.testing.assert_array_equal(x, y)
        else:  # repr: exact for floats, and NaN stamps compare equal
            assert repr(a[k]) == repr(b[k]), k


def test_streamed_round_is_bit_identical_with_tracing_on(profiled):
    off = _round_fingerprint(*_streamed_rounds()[1:])
    with profiled():
        plan, *rest = _streamed_rounds()
    on = _round_fingerprint(*rest)
    _assert_same(off, on)
    names = {s.name for s in tracing.recorder().spans}
    assert {"fl.round", "fl.chunk", "fl.fleet_sample", "fl.materialize",
            "flow.submit", "flow.dispatch", "agg.apply"} <= names


def test_threshold_one_delivers_once_per_device(profiled):
    """Under a threshold of 1 the message plane makes one delivery per
    device: the counters show it, per round."""
    with profiled():
        plan, svc, flow, sim, outs = _streamed_rounds(rounds=2)
    rec = tracing.recorder()
    rounds = [s for s in rec.spans if s.name == "fl.round"]
    assert len(rounds) == 2 and len(svc.history) == 2
    for r in rounds:
        tree = rec.subtree(r)
        for name in ("flow.deliveries", "flow.rows_dispatched"):
            assert rec.counted(tree, name) == plan.total_devices, name
        assert 0 < rec.counted(tree, "flow.deliver_ns") < r.ns
        # The aggregation fires inside the last delivery of the round.
        apply, = [s for s in tree if s.name == "agg.apply"]
        assert any(a.name == "flow.dispatch" for a in rec.ancestors(apply))


def test_deliveries_are_timed_only_while_tracing_is_on(profiled):
    """Off, each delivery reaches the callback straight, with nothing in
    between.  On, one dispatch call times its deliveries through one wrapper
    and counts them once; a dispatch made from inside a delivery is counted
    by the outer call alone.  Either way the callback is put back."""
    seen, flow = [], None

    def deliver(d):
        disp = flow._dispatchers[0]
        seen.append((d.message.device_id, disp.deliver))
        if d.message.device_id == 1:  # a delivery that submits again
            flow.submit_many([Message(0, 9, 0, None, num_samples=1)])
        if d.message.device_id == 9:
            _spin(3_000_000)

    flow = DeviceFlow(deliver)
    flow.register_task(0, AccumulatedStrategy(thresholds=(1,)))
    msgs = [Message(0, i, 0, None, num_samples=1) for i in range(3)]
    flow.submit_many(msgs, ts=[0.1, 0.2, 0.3])
    assert sorted(i for i, _ in seen) == [0, 1, 2, 9]
    assert all(cb is deliver for _, cb in seen)
    seen.clear()
    with profiled():
        flow.submit_many(msgs, ts=[0.4, 0.5, 0.6])
    assert flow._dispatchers[0].deliver is deliver
    assert sorted(i for i, _ in seen) == [0, 1, 2, 9]
    assert all(isinstance(cb, tracing.Timed) and cb.fn is deliver
               for _, cb in seen)
    rec = tracing.recorder()
    outer = rec.outermost(rec.spans, "flow.")
    assert [s.name for s in outer] == ["flow.submit"]
    tree = rec.subtree(outer[0])
    assert rec.counted(tree, "flow.deliveries") == 4
    assert rec.counted(tree, "flow.rows_dispatched") == 4
    # The nested delivery's 3 ms are counted once, inside the outer one's.
    assert 3_000_000 < rec.counted(tree, "flow.deliver_ns") < outer[0].ns


def _engine_tokens(steps: int = 9):
    cfg = get_config("llama3_2_3b", smoke=True)
    eng = ContinuousBatchingEngine(cfg, slots=3, prompt_len=6,
                                   decode_tokens=4, seed=3)
    prompts = np.random.default_rng(4).integers(1, cfg.vocab_size, (5, 6))
    for i, p in enumerate(prompts):
        eng.submit(i, p, t=0.0)
    t = 0.0
    for _ in range(steps):
        t += eng.step(t)
    report = eng.report()
    return ([(r.request_id, r.slot, r.tokens, r.first_token_t, r.finish_t)
             for r in report.records],
            [dataclass_tuple(it) for it in eng.iterations],
            np.asarray(eng.arena["lengths"]))


def dataclass_tuple(it):
    return (it.t, it.duration_s, it.admitted, it.n_active, it.queue_depth)


def test_serving_steps_are_identical_with_tracing_on(profiled):
    recs_off, its_off, len_off = _engine_tokens()
    with profiled():
        recs_on, its_on, len_on = _engine_tokens()
    assert recs_off == recs_on and its_off == its_on
    np.testing.assert_array_equal(len_off, len_on)
    rec = tracing.recorder()
    steps = [s for s in rec.spans if s.name == "serve.step"]
    assert [s.args["step"] for s in steps] == list(range(9))
    prefills = [s for s in rec.spans if s.name == "serve.prefill"]
    decodes = [s for s in rec.spans if s.name == "serve.decode"]
    assert len(prefills) == sum(it[2] > 0 for it in its_on)
    assert len(decodes) == sum(it[3] > 0 for it in its_on)
    for s in steps:
        kids = rec.children(s)
        assert {k.name for k in kids} <= {"serve.prefill", "serve.decode"}
        assert rec.self_ns(s) == s.ns - sum(k.ns for k in kids) >= 0


def test_instrumented_modules_lint_clean_and_tracing_stays_out_of_core():
    """Wall-clock reads live in ``repro/tracing.py``, outside ``core/``;
    under ``core/`` R002 would refuse it, and no R002 suppression exists."""
    findings = lint.lint_paths([str(SRC)])
    assert findings == [], "\n".join(map(str, findings))
    source = (SRC / "repro" / "tracing.py").read_text()
    moved = lint.lint_source(source, "src/repro/core/tracing.py",
                             rules=["R002"])
    assert {f.rule for f in moved} == {"R002"}
    for path in (SRC / "repro").rglob("*.py"):
        assert "ok[R002" not in path.read_text(), path
