"""The names the kernel readers match are the names the chip's trace shows.

A TPU trace's ``XLA Ops`` events carry the whole HLO instruction of the
optimized program (``%decode_attention.6 = bf16[...] custom-call(...)``),
and a Pallas kernel's instruction is named after the innermost jit around
its ``pallas_call``.  So the program's own jits are compiled here for a
described v5e (no chip needed) and every ``tpu_custom_call`` instruction of
each, as the trace shows it and as ``trace_reduce`` keeps it, must match its
reader's pattern: a rename in the program fails this test before it leaves
a gap in a run on the chip.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import run
import trace_reduce

CUSTOM_CALL = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*custom_call_target="
                         r"\"tpu_custom_call\".*)$", re.M)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def chip(topo, monkeypatch):
    import repro.kernels

    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def kernel_names(fn, *args) -> list[str]:
    """Each kernel instruction as a trace event names it, and as the
    reduction keeps that name."""
    lines = CUSTOM_CALL.findall(jax.jit(fn).lower(*args).compile().as_text())
    return [n for line in lines for n in (line, trace_reduce.short_name(line))]


def reader_pattern(metric: str) -> str:
    return run.load_module(run.BENCH / "metrics" / f"{metric}.py").KERNEL


def test_fed_reduce_reader_sees_the_streaming_partial_reduce(chip):
    from repro.core import federation

    names = kernel_names(
        lambda b, w, v: federation._PARTIAL_REDUCE((b, w), None, v,
                                                   impl="pallas"),
        chip((1024, 1), jnp.float32), chip((1024, 256), jnp.float32),
        chip((1024,), jnp.float32))
    assert len(names) == 2 * 2  # one weighted row-sum per leaf
    pattern = reader_pattern("fed_reduce_roofline")
    assert all(re.search(pattern, n) for n in names), names


def test_decode_readers_see_the_engine_decode_step(chip):
    """The engine's decode step as it jits it, granite widths at 2 layers."""
    from repro.configs.registry import get_config
    from repro.core.serving import arena_decode, init_arena, init_params

    cfg = dataclasses.replace(get_config("granite_moe_3b_a800m"),
                              num_layers=2)
    as_chip = lambda tree: jax.tree.map(lambda a: chip(a.shape, a.dtype),
                                        tree)
    params = as_chip(jax.eval_shape(lambda: init_params(cfg, 0)))
    arena = as_chip(jax.eval_shape(lambda: init_arena(cfg, 32, 577)))
    names = kernel_names(
        lambda p, t, a, ar: arena_decode(p, t, a, ar, cfg,
                                         attn_impl="pallas"),
        params, chip((32,), jnp.int32), chip((32,), jnp.bool_), arena)
    assert names
    for metric in ("decode_attention_roofline", "decode_step_ms",
                   "prefill_step_ms"):
        pattern = reader_pattern(metric)
        assert all(re.search(pattern, n) for n in names), (metric, names)
