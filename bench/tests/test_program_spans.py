"""The readers of the program's own spans and counters, and how those spans
sit beside the harness's in a profiler trace."""

import jax
import jax.numpy as jnp
import pytest

import run
import trace_reduce as tr
from conftest import CPU_PEAKS, SEED, SMALL
from repro import tracing
from repro.tracing import Recorder, Span

MS = 1_000_000  # ns


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


class HandRun:
    """A window from 0 to 1 s and whatever the recorder holds."""

    def __init__(self):
        self.spans = run.Spans()
        self.spans.records.append(("window", 0.0, 1.0))


@pytest.fixture
def hand_recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "_recorder", rec)
    return rec


def span(rec, id, parent, name, t0_ms, t1_ms, **counters):
    rec.spans.append(Span(id, parent, name, int(t0_ms * MS),
                          int(t1_ms * MS), {}, counters))


def two_rounds(rec):
    """Two rounds of 100 ms and 60 ms.  The first submits twice: 30 ms
    (dispatch 20 ms holding 12 ms of intake over 3 deliveries of 3 rows in
    all, and an aggregation of 5 ms inside a delivery) and 10 ms (dispatch
    8 ms, 4 ms of intake, one delivery of 1 row).  The second submits once,
    20 ms (dispatch 15 ms, 6 ms of intake, 2 deliveries of 4 rows), and an
    aggregation of 7 ms follows, outside any delivery.  A round before the
    window does not count."""
    span(rec, 0, None, "fl.round", -50, -1)
    span(rec, 3, 2, "agg.apply", 25, 30)
    span(rec, 2, 1, "flow.dispatch", 12, 32, **{
        "flow.deliver_ns": 12 * MS,
        "flow.deliveries": 3, "flow.rows_dispatched": 3})
    span(rec, 1, 4, "flow.submit", 10, 40)
    span(rec, 6, 5, "flow.dispatch", 51, 59, **{
        "flow.deliver_ns": 4 * MS,
        "flow.deliveries": 1, "flow.rows_dispatched": 1})
    span(rec, 5, 4, "flow.submit", 50, 60)
    span(rec, 7, 4, "fl.chunk", 0, 10)
    span(rec, 4, None, "fl.round", 0, 100)
    span(rec, 10, 9, "flow.dispatch", 201, 216, **{
        "flow.deliver_ns": 6 * MS,
        "flow.deliveries": 2, "flow.rows_dispatched": 4})
    span(rec, 9, 8, "flow.submit", 200, 220)
    span(rec, 11, 8, "agg.apply", 250, 257)
    span(rec, 8, None, "fl.round", 200, 260)


def test_fl_readers_on_a_hand_made_recorder(hand_recorder):
    two_rounds(hand_recorder)
    r = HandRun()
    # Rounds less their flow spans: (100 - 30 - 10) + (60 - 20).
    assert reader("fl.round_self_ms").read(r) == pytest.approx(100 / 2)
    # Flow spans less the intake inside: (30 - 12) + (10 - 4) + (20 - 6).
    assert reader("flow.dispatch_host_ms").read(r) == pytest.approx(38 / 2)
    # All intake, and the one aggregation outside a delivery: 22 + 7.
    assert reader("agg.intake_host_ms").read(r) == pytest.approx(29 / 2)
    assert reader("flow.rows_per_delivery").read(r) == pytest.approx(8 / 6)
    # The three add up to the rounds' time, and the lone aggregation.
    assert 100 / 2 + 38 / 2 + 29 / 2 == pytest.approx((100 + 60 + 7) / 2)


def test_serve_reader_on_a_hand_made_recorder(hand_recorder):
    """Two steps: 10 ms with a 3 ms prefill and a 5 ms decode, 8 ms with
    a 7 ms decode; only the dispatches come off."""
    span(hand_recorder, 1, 0, "serve.prefill", 1, 4)
    span(hand_recorder, 2, 0, "serve.decode", 4, 9)
    span(hand_recorder, 0, None, "serve.step", 0, 10)
    span(hand_recorder, 4, 3, "serve.decode", 10.5, 17.5)
    span(hand_recorder, 3, None, "serve.step", 10, 18)
    assert reader("serve.engine_host_ms").read(HandRun()) == pytest.approx(
        (2 + 1) / 2)


@pytest.mark.parametrize("name", ["fl.round_self_ms", "flow.dispatch_host_ms",
                                  "agg.intake_host_ms",
                                  "flow.rows_per_delivery",
                                  "serve.engine_host_ms"])
def test_readers_find_nothing_without_program_spans(hand_recorder, name,
                                                    monkeypatch):
    assert reader(name).read(HandRun()) is None
    span(hand_recorder, 0, None, "fl.round", 2000, 2100)  # past the window
    assert reader(name).read(HandRun()) is None
    # A program without repro.tracing (the parent of this reader).
    import builtins

    real = builtins.__import__

    def no_tracing(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro" and fromlist and "tracing" in fromlist:
            raise ImportError("cannot import name 'tracing'")
        return real(mod, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_tracing)
    assert reader(name).read(HandRun()) is None


def test_program_spans_leave_the_harness_view_of_a_trace_alone(tmp_path):
    """In a recorded CPU trace the program's ``simdc.`` spans sit on the
    host plane beside the harness's ``bench.`` spans, and ``load`` returns
    the harness's spans and window exactly as the plane holds them, so
    every existing reader and the idle gaps read what they read before."""
    from jax.profiler import ProfileData

    spans = run.Spans()
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans("window"):
        for i in range(3):
            with spans("serve.step"), tracing.span("serve.step", step=i):
                with tracing.span("serve.decode", step=i):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    data = ProfileData.from_file(tr.xplane_file(str(tmp_path)))
    host = [(e.name, e.start_ns, e.duration_ns)
            for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]
    ours = [e for e in host if e[0].startswith("simdc.")]
    assert sorted(n for n, _, _ in ours) == (["simdc.serve.decode"] * 3
                                             + ["simdc.serve.step"] * 3)
    trace = tr.load(str(tmp_path))
    harness = [e for e in host if e[0].startswith("bench.")]
    assert sorted(trace.spans) == sorted(harness)
    assert trace.window == tr.span_window(harness, "bench.window")
    ops = [("op", s + d // 3, d // 3) for n, s, d in ours
           if n == "simdc.serve.decode"]
    gaps = tr.idle_gaps(ops, trace.window, trace.spans)
    assert {label for label, _ in gaps} <= {"bench.serve.step",
                                            "outside any harness span"}
    # The harness's own reader is unchanged; the program's reads the steps.
    r = run.Run(run.Cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                         "granite_serve.decode"), spans, {}, trace, {})
    assert reader("serve.step_host_ms").read(r) == pytest.approx(
        sum(spans.in_window("serve.step")) / 3 * 1e3)
    engine = reader("serve.engine_host_ms").read(r)
    assert 0 < engine < reader("serve.step_host_ms").read(r)
    tracing.recorder().clear()


def test_a_small_traced_fl_window_accounts_for_its_rounds(bench, tmp_path):
    """The benchmark's FL cell at its CPU size under a profiler trace: the
    three host layers add up to the harness's round and drain spans, and
    the threshold-1 plane delivers one row at a time."""
    cell = run.Cell(bench, "ctr_campaign.f32")
    over = SMALL["fl_campaign"]
    system = run.load_module(cell.system_path)
    spans = run.Spans()
    sut = system.System(cell.config, cell.traffic, seed=SEED,
                        devices=jax.devices()[:1], spans=spans,
                        reference=run.load_module(cell.reference_path),
                        overrides=over)
    sut.setup()
    tracing.recorder().clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans("window"):
            window = sut.window(1.0)
    finally:
        jax.profiler.stop_trace()
    r = run.Run(cell, spans, window["counters"], None, CPU_PEAKS)
    got = {m: reader(m).read(r) for m in (
        "fl.round_self_ms", "flow.dispatch_host_ms", "agg.intake_host_ms",
        "flow.rows_per_delivery")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["flow.rows_per_delivery"] == 1.0
    parts = (got["fl.round_self_ms"] + got["flow.dispatch_host_ms"]
             + got["agg.intake_host_ms"])
    whole = (reader("fl.sim_host_ms").read(r)
             + reader("fl.flow_drain_ms").read(r))
    assert parts == pytest.approx(whole, rel=0.10)
    tracing.recorder().clear()
