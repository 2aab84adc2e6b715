"""The yardstick's arithmetic: trace reduction, operation and byte counts,
the peaks table."""
import glob
import os

import pytest

import flops
import run
import trace_reduce as tr


def test_union_and_idle_of_hand_made_intervals():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 31, 2)]
    assert tr.union_ns(evs) == 20  # [0, 15) and [30, 35)
    t = tr.Trace({0: evs, 1: [("a", 0, 40)]}, [("bench.window", 0, 40),
                                              ("bench.step", 14, 20)],
                 (0, 40))
    assert t.window_s == pytest.approx(40e-9)
    assert tr.busy_s(t) == pytest.approx((20 + 40) / 2 * 1e-9)
    gaps = tr.idle_gaps(t.ops(0), t.window, t.spans)
    # [15, 30) lies in bench.step; [35, 40) in no span but the window.
    assert gaps == [["bench.step", pytest.approx(15e-9)],
                    ["outside any harness span", pytest.approx(5e-9)]]
    assert tr.op_seconds(evs, "^a$") == (pytest.approx(12e-9), 2)
    assert tr.top_ops(evs, 2) == [["a", pytest.approx(12e-9)],
                                  ["b", pytest.approx(10e-9)]]


def test_ops_clip_to_the_window():
    t = tr.Trace({0: [("x", 0, 100), ("y", 150, 10)]}, [], (50, 155))
    assert t.ops() == [("x", 50, 50), ("y", 150, 5)]


def test_recorded_cpu_trace(tmp_path):
    """A profiler trace of a tiny jitted program: the harness's spans and
    window come back from the host plane, and the reduction of the CPU's
    XLA op events (standing in for a chip's) stays inside the window."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    spans = run.Spans()
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans("window"):
        for _ in range(3):
            with spans("step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(str(tmp_path))
    assert trace.device_ops == {}  # the CPU has no /device:TPU plane
    names = [n for n, _, _ in trace.spans]
    assert names.count("bench.step") == 3 and "bench.window" in names
    assert len(spans.in_window("step")) == 3
    data = ProfileData.from_file(tr.xplane_file(str(tmp_path)))
    ops = [(e.name, e.start_ns, e.duration_ns)
           for p in data.planes if p.name == "/host:CPU"
           for line in p.lines for e in line.events
           if "hlo_op" in dict(e.stats)]
    assert ops
    t = tr.Trace({0: ops}, trace.spans, trace.window)
    assert 0 < tr.busy_s(t) <= t.window_s
    assert sum(d for _, d in tr.top_ops(t.ops(), 50)) == pytest.approx(
        sum(d for _, _, d in t.ops()) * 1e-9)


def test_fed_reduce_cost_by_hand():
    # 3 rows x 4 values f32: 12 multiplies + 12 adds; 48 B stack, 12 B
    # weights, 16 B sum; an int8 stack adds a 12 B scale column.
    assert flops.fed_reduce_cost(3, 4, 4) == (24.0, 76.0)
    assert flops.fed_reduce_cost(3, 4, 1, scaled=True) == (24.0, 52.0)


def test_decode_attention_cost_by_hand():
    # 4 heads over 2 KV heads of 8, bf16; slots of length 3 and 0.
    f, b = flops.decode_attention_cost([3, 0], 4, 2, 8, 2)
    assert f == 4 * 4 * 3 * 8  # q.K and p.V, 2 * L * d each per head
    assert b == 2 * 3 * 2 * 8 * 2 + 2 * 4 * 8 * 2  # K, V; q and o


def test_lr_round_flops_by_hand():
    # 2 devices, 3 records, dim 5, 2 epochs: 4 * dim a record an epoch,
    # then 2 * devices * (dim + 1) for the weighted reduction.
    assert flops.lr_round_flops(2, 3, 5, 2) == 2 * 2 * 3 * 20 + 2 * 2 * 6


def test_moe_token_flops_by_hand():
    m = dict(num_layers=2, d_model=4, num_heads=2, num_kv_heads=1,
             head_dim=2, num_experts=3, experts_per_token=2, d_ff=5,
             vocab_size=7)
    proj = 2 * 4 * (2 * 2 + 2 * 1 * 2) + 2 * 2 * 2 * 4  # q, k, v; o
    router = 2 * 4 * 3
    experts = 2 * 3 * 2 * 4 * 5  # two SwiGLU experts of three matmuls
    attn = 4 * 2 * 2 * 6  # over 6 positions
    assert flops.moe_token_flops(m, 6) == 2 * (proj + router + experts
                                               + attn) + 2 * 4 * 7


def test_granite_decode_token_flops_is_the_active_parameter_count():
    """At context 0 a token costs two operations per active parameter
    (attention projections, router, 8 of 40 experts, head)."""
    m = run.load_json(run.BENCH / "configs" /
                      "granite_moe_3b_a800m_serve.json")["model"]
    d, L = m["d_model"], m["num_layers"]
    active = L * (d * (24 * 64 + 2 * 8 * 64) + 24 * 64 * d + d * 40
                  + 8 * 3 * d * 512) + d * m["vocab_size"]
    assert flops.moe_token_flops(m, 0) == 2 * active
    assert 1.5e9 < flops.moe_token_flops(m, 0) < 2.0e9


def test_peaks_table_names_its_source_and_refuses_unknown_chips():
    table = run.load_json(run.BENCH / "peaks.json")
    assert "TPU v5e" in table["source"]
    assert run.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.device_peaks("cpu")


def test_no_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.xplane_file(str(tmp_path))
    assert not glob.glob(os.path.join(str(tmp_path), "*"))


def test_program_readers_tell_decode_from_prefill():
    """Three steps: two admit (prefill, a small scatter, then decode), one
    only decodes.  Decode programs are those that run the kernel."""

    class Run:
        counters = {"steps": [(0, 0, 0, 1, []), (0, 0, 0, 1, []),
                              (0, 0, 0, 0, [])]}
        trace = tr.Trace(
            {0: [("decode_attention.6", 115, 1), ("decode_attention.6", 365, 1),
                 ("decode_attention.6", 405, 1)]}, [], (0, 1000),
            {0: [("jit__lambda", 0, 100), ("jit__lambda", 100, 5),
                 ("jit__lambda", 110, 20), ("jit__lambda", 200, 150),
                 ("jit__lambda", 350, 5), ("jit__lambda", 360, 20),
                 ("jit__lambda", 400, 30)]})

    dec = run.load_module(run.BENCH / "metrics" / "decode_step_ms.py")
    pre = run.load_module(run.BENCH / "metrics" / "prefill_step_ms.py")
    assert dec.read(Run) == pytest.approx((20 + 20 + 30) / 3 * 1e-6)
    assert pre.read(Run) == pytest.approx((100 + 150) / 2 * 1e-6)


def test_collective_reader_counts_each_chip_once_per_round():
    """Two chips, two rounds: 4 ns and 2 ns of collectives on one chip,
    6 ns on the other, 12 ns in all; the fusion does not count."""

    class Run:
        chips = 2
        counters = {"rounds": 2}
        trace = tr.Trace(
            {0: [("all-reduce.1", 0, 4), ("fusion.3", 4, 50),
                 ("all-reduce-start", 60, 2)],
             1: [("all-gather.2", 0, 6)]}, [], (0, 100))

    col = run.load_module(run.BENCH / "metrics" / "fl.collective_ms.py")
    assert col.read(Run) == pytest.approx(12 / 2 / 2 * 1e-6)
    Run.trace = tr.Trace({0: [("fusion.3", 0, 5)]}, [], (0, 100))
    assert col.read(Run) is None
