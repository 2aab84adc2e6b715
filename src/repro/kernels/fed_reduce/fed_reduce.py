"""Pallas TPU kernel for the staleness-weighted federated update reduction.

One round of FedAvg-style aggregation over a *device-resident* stacked update
buffer is a weighted segment-sum: ``out[d] = sum_i w[i] * U[i, d]`` with the
per-row weights ``w`` carrying the normalized sample counts x staleness
discounts (zero for rows not selected into this aggregation).  The host path
walks a Python list of per-device pytrees leaf-by-leaf; this kernel replaces
that chain with a single fused reduction per leaf:

* grid ``(d_tiles, n_chunks)`` — row chunks innermost and *sequential*, so the
  ``(1, block_d)`` f32 accumulator lives in the output VMEM block across chunk
  steps (the classic matmul accumulation pattern — zero extra HBM traffic for
  the running sum);
* the inner product is one MXU ``(1, block_n) @ (block_n, block_d)`` matmul
  per grid step, accumulated in f32 whatever the stack dtype (bf16 updates
  still reduce exactly like the f32 host reference within tolerance);
* rows are padded with zero *weights* (not zero rows), so padding never
  contributes to the sum and the caller can slice the column padding off.

The same kernel serves the **fused dequantize-and-reduce** path: an int8
stack (quantized ``UpdateBuffer`` leaves) streams HBM→VMEM at 1 byte/element
and is cast to f32 per ``(block_n, block_d)`` block at the MXU input — the
per-row scales arrive pre-folded into the weight vector (``ops.fed_reduce``
``scales=``), so dequantization costs zero extra passes and no dense f32
copy of the stack ever exists.  block_n=256 / block_d=512 are multiples of
the int8 (32, 128) min tile, so the quantized path keeps the same blocking.

VMEM per step: ``block_n * block_d * 4`` stack bytes + ``block_n * 4`` weight
bytes + ``block_d * 4`` accumulator ≈ 0.5 MB at block_n=256, block_d=512
(4x less stack traffic from HBM when the stack is int8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fed_reduce_kernel(w_ref, x_ref, o_ref):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)  # (1, block_n)
    x = x_ref[...].astype(jnp.float32)  # (block_n, block_d)
    # HIGHEST: the MXU's default single bf16 pass would round the f32 weights
    # and updates to 8 significant bits; the reduction must stay f32-exact.
    o_ref[...] += jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def fed_reduce_pallas(
    stack: jax.Array,  # (n, d)
    weights: jax.Array,  # (n,)
    *,
    block_n: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Weighted row-sum ``weights @ stack`` -> (d,) float32."""
    n, d = stack.shape
    # Pad rows to a chunk multiple (zero weights -> no contribution) and
    # columns to a lane-aligned tile multiple (sliced off below).
    n_pad = -n % block_n
    d_pad = -d % block_d
    if n_pad:
        stack = jnp.pad(stack, ((0, n_pad), (0, 0)))
    if d_pad:
        stack = jnp.pad(stack, ((0, 0), (0, d_pad)))
    w = jnp.pad(weights.astype(jnp.float32), (0, n_pad)).reshape(1, -1)
    gn = (n + n_pad) // block_n
    gd = (d + d_pad) // block_d

    out = pl.pallas_call(
        _fed_reduce_kernel,
        grid=(gd, gn),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda di, ni: (0, ni)),
            pl.BlockSpec((block_n, block_d), lambda di, ni: (ni, di)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda di, ni: (0, di)),
        out_shape=jax.ShapeDtypeStruct((1, d + d_pad), jnp.float32),
        interpret=interpret,
    )(w, stack)
    return out[0, :d]
