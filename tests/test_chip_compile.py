"""The main path's kernels compiled for a described TPU v5e, no chip attached.

The TPU compiler ships with jaxlib's TPU plugin and compiles for a topology
that is only described; it refuses what interpret mode accepts (blocks that
break the (8, 128) tiling, fast memory over budget, programs that do not
fit).  Each test compiles at published widths and asserts the Pallas kernel
is in the program (``tpu_custom_call``).  The topology is described inside a
module fixture, never at import: only one process at a time may load the
TPU library.  The kernels' ``impl="pallas"`` entry points raise off a TPU,
so each test steers ``repro.kernels.on_tpu`` to take the chip's branch.
"""
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.configs.registry import get_config
from repro.core.serving import arena_decode, init_arena, init_params
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.fed_reduce.ops import fed_reduce
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd_scan.ops import ssd_decode

GRANITE = get_config("granite_moe_3b_a800m")
GRANITE4H = get_config("granite_4_0_h_small")
METRICS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "metrics"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU plugin / compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def chip(topo, monkeypatch):
    """``ShapeDtypeStruct`` factory on one described chip; ``impl="pallas"``
    compiles the Mosaic kernel instead of raising off a TPU."""
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_fed_reduce_compiles_at_fleet_scale(chip, wire):
    """One 10^5-device update leaf (dim 256 + bias), f32 or int8 + scales."""
    rows, size = 100_000, 257
    if wire == "f32":
        _assert_kernel(lambda s, w: fed_reduce(s, w, impl="pallas"),
                       chip((rows, size), jnp.float32),
                       chip((rows,), jnp.float32))
    else:
        _assert_kernel(
            lambda s, w, sc: fed_reduce(s, w, scales=sc, impl="pallas"),
            chip((rows, size), jnp.int8), chip((rows,), jnp.float32),
            chip((rows,), jnp.float32))


def test_decode_attention_compiles_at_granite_widths(chip):
    """16 slots x 1024 cached tokens, 24 query heads over 8 KV heads x 64."""
    b, s, d = 16, 1024, GRANITE.head_dim
    kv = chip((b, GRANITE.num_kv_heads, s, d), jnp.bfloat16)
    _assert_kernel(lambda q, k, v, n: decode_attention(q, k, v, n,
                                                       impl="pallas"),
                   chip((b, GRANITE.num_heads, d), jnp.bfloat16), kv, kv,
                   chip((b,), jnp.int32))


def test_flash_attention_compiles_for_granite_prefill(chip):
    """A 4 x 128-token prefill at granite's heads."""
    b, s, d = 4, 128, GRANITE.head_dim
    kv = chip((b, s, GRANITE.num_kv_heads, d), jnp.bfloat16)
    _assert_kernel(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   impl="pallas"),
                   chip((b, s, GRANITE.num_heads, d), jnp.bfloat16), kv, kv)


def test_arena_decode_step_compiles_at_granite_widths(chip):
    """One continuous-batching decode step, granite widths cut to 2 layers:
    the Pallas decode kernel inside the layer scan, the arena scatters
    around it."""
    cfg = dataclasses.replace(GRANITE, num_layers=2)
    slots, max_len = 16, 161
    as_chip = lambda tree: jax.tree.map(lambda a: chip(a.shape, a.dtype),
                                        tree)
    params = as_chip(jax.eval_shape(lambda: init_params(cfg, 0)))
    arena = as_chip(jax.eval_shape(lambda: init_arena(cfg, slots, max_len)))
    _assert_kernel(
        lambda p, t, a, ar: arena_decode(p, t, a, ar, cfg, attn_impl="pallas"),
        params, chip((slots,), jnp.int32), chip((slots,), jnp.bool_), arena)


def test_ssd_decode_compiles_at_granite4h_widths(chip):
    """128 slots' state update: 128 heads of 64, d_state 128, f32 state in
    the folded layout, donated and aliased."""
    b, h, p, n = 128, GRANITE4H.ssm_heads, GRANITE4H.ssm_head_dim, 128
    compiled = _assert_kernel(
        lambda x, dt, A, B, C, s, a: ssd_decode(x, dt, A, B, C, s, a,
                                                impl="pallas"),
        chip((b, h, p), jnp.bfloat16), chip((b, h), jnp.float32),
        chip((h,), jnp.float32), chip((b, 1, n), jnp.bfloat16),
        chip((b, 1, n), jnp.bfloat16),
        chip((b, h // 2, n, 2 * p), jnp.float32), chip((b,), jnp.bool_))
    assert "ssd_decode" in compiled.as_text()


def test_hybrid_arena_decode_step_compiles_at_granite4h_widths(chip):
    """One granite-4.0-h-small decode step, published widths cut to a Mamba
    and an attention layer with 9 of 72 experts held: both decode kernels,
    the held-expert grouped matmuls and the arena's two kinds of state."""
    cfg = dataclasses.replace(GRANITE4H, num_layers=2,
                              layer_types=("mamba", "attention"),
                              experts_held=9, ssm_decode_impl="pallas")
    slots, max_len = 16, 161
    as_chip = lambda tree: jax.tree.map(lambda a: chip(a.shape, a.dtype),
                                        tree)
    params = as_chip(jax.eval_shape(lambda: init_params(cfg, 0)))
    arena = as_chip(jax.eval_shape(lambda: init_arena(cfg, slots, max_len)))
    text = _assert_kernel(
        lambda p, t, a, ar: arena_decode(p, t, a, ar, cfg, attn_impl="pallas"),
        params, chip((slots,), jnp.int32), chip((slots,), jnp.bool_),
        arena).as_text()
    assert "decode_attention" in text
    # The benchmark's readers find the state update by the name a trace
    # gives its instruction (the part before " = "), with or without "%".
    names = re.findall(r"^\s*(?:ROOT\s+)?(%ssd_decode\S*) = ", text, re.M)
    assert len(names) == 1  # one Mamba layer
    for metric in ("ssd_decode_roofline", "hybrid_decode_step_ms"):
        src = (METRICS / f"{metric}.py").read_text()
        pattern = re.search(r'^KERNEL = r"(.*)"', src, re.M).group(1)
        assert all(re.search(pattern, n) and re.search(pattern, n[1:])
                   for n in names), (metric, names)
