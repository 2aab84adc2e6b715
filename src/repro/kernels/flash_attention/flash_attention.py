"""Pallas TPU flash attention (GQA, causal) — prefill/training kernel.

TPU adaptation notes (vs the CUDA FlashAttention algorithm):
* the kernel runs on **head-major** operands: the wrapper transposes q to
  ``(batch, heads, seq, head_dim)`` and k/v to ``(batch, kv_heads, seq,
  head_dim)`` so every tile is a contiguous ``(block, head_dim)`` slab whose
  last two dimensions satisfy the chip's (8, 128) tiling rule — a
  ``(1, head_dim)`` head slice of a sequence-major array is refused by the
  TPU compiler;
* the grid is ``(batch, q_heads, num_q_blocks, num_kv_blocks)`` with the KV
  block dimension innermost — TPU grids execute sequentially over the last
  axis, so the online-softmax running state (m, l, acc) lives in 2-D **VMEM
  scratch** that persists across KV steps (no atomics / shared-memory
  reductions as on GPU);
* block shapes are MXU-aligned: ``block_q x head_dim`` and
  ``block_k x head_dim`` tiles feed the 128x128 systolic array directly;
* GQA is expressed in the BlockSpec ``index_map`` — the kv-head index is
  ``q_head // group_size``, so no materialized ``repeat`` of K/V ever leaves
  HBM.

VMEM budget per grid step (bf16 inputs, f32 scratch):
``block_q*d*2 + 2*block_k*d*2 + block_q*block_k*4 (transient) +
block_q*(4 + 4 + 4*d)`` — at the default 128/128 blocks and d=128 this is
~0.33 MB, far under the ~16 MB/core VMEM limit, leaving room for Mosaic's
double-buffering of the K/V streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, causal: bool, scale: float, nk: int, block_q: int, block_k: int,
    q_offset: int, kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + qi * block_q
    k_start = ki * block_k
    if causal:
        # Skip KV blocks strictly above the causal diagonal.
        should_compute = k_start <= q_start + block_q - 1
    else:
        should_compute = k_start < kv_len

    @pl.when(should_compute)
    def _compute():
        q = q_ref[...]  # (block_q, d)
        k = k_ref[...]  # (block_k, d)
        v = v_ref[...]
        s = jax.lax.dot_general(
            (q * scale).astype(q.dtype), k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = kpos < kv_len
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            valid &= qpos >= kpos
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]  # (block_q, 1)
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


def flash_attention_pallas(
    q: jax.Array,  # (b, sq, h, d)
    k: jax.Array,  # (b, sk, kv, d)
    v: jax.Array,  # (b, sk, kv, d)
    *,
    causal: bool = True,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    assert h % kvh == 0, "q heads must be a multiple of kv heads"
    g = h // kvh
    scale = (d ** -0.5) if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    sq_p, sk_p = nq * block_q, nk * block_k
    # Head-major operands (module docstring), padded to block multiples.
    q = jnp.pad(q.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    k = jnp.pad(k.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    v = jnp.pad(v.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        nk=nk,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_len=sk,
    )
    q_block = pl.BlockSpec((None, None, block_q, d),
                           lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_block = pl.BlockSpec((None, None, block_k, d),
                            lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq].transpose(0, 2, 1, 3)
