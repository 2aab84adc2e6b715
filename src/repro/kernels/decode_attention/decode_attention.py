"""Pallas TPU flash-decoding kernel: one query token vs a long KV cache.

Decode is memory-bound: the entire KV cache must stream HBM→VMEM once per
step, and the MXU work per block is tiny.  The TPU adaptation therefore
optimizes for *streaming*:

* the cache is **head-major** — ``(batch, kv_heads, seq, head_dim)`` — so
  one grid step's K or V tile is a contiguous ``(block_k, head_dim)`` slab
  whose last two dimensions satisfy the chip's (8, 128) tiling rule (a
  sequence-major ``(batch, seq, kv_heads, head_dim)`` cache would need a
  ``(1, head_dim)`` head slice per row, which the TPU compiler refuses);
* grid ``(batch, kv_heads, num_kv_blocks)`` — KV blocks innermost so the
  (m, l, acc) online-softmax state for all ``g = h/kv`` grouped query heads
  rides in 2-D VMEM scratch across the stream;
* all ``g`` query heads of a KV group are processed together as the rows of a
  single ``(g, d) x (d, block_k)`` MXU op, amortizing each streamed KV block
  over the whole group (the GPU flash-decoding equivalent splits over SMs and
  combines in a second pass — on TPU the sequential grid does the combine for
  free within a core, while the *cross-shard* combine for a sequence-sharded
  cache is a 3-scalar psum handled in ``distribution.steps``);
* variable cache lengths arrive as a scalar-prefetch operand (the whole
  ``(batch,)`` vector in SMEM) and are masked in-kernel, so padded cache
  tail blocks contribute exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, nk: int, block_k: int, scale: float,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[...]  # (g, d)
        k = k_ref[...]  # (block_k, d)
        v = v_ref[...]
        s = jax.lax.dot_general(
            (q * scale).astype(q.dtype), k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (g, block_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]  # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,  # (b, h, d)
    k_cache: jax.Array,  # (b, kv, s, d) — head-major
    v_cache: jax.Array,  # (b, kv, s, d)
    lengths: jax.Array,  # (b,) int32
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, kvh, s, _ = k_cache.shape
    assert h % kvh == 0
    g = h // kvh
    scale = (d ** -0.5) if scale is None else scale
    block_k = min(block_k, s)
    nk = -(-s // block_k)
    s_p = nk * block_k
    if s_p != s:
        pad = ((0, 0), (0, 0), (0, s_p - s), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    qg = q.reshape(b, kvh, g, d)

    kernel = functools.partial(
        _decode_kernel, nk=nk, block_k=block_k, scale=scale
    )
    head_block = pl.BlockSpec((None, None, g, d),
                              lambda bi, hi, ki, lens: (bi, hi, 0, 0))
    kv_block = pl.BlockSpec((None, None, block_k, d),
                            lambda bi, hi, ki, lens: (bi, hi, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, nk),
            in_specs=[head_block, kv_block, kv_block],
            out_specs=head_block,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
