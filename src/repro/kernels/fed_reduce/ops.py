"""Public entry point for the fused federated update reduction.

``fed_reduce`` reduces one stacked ``(rows, ...)`` leaf to an *unnormalized*
weighted sum, so partial reductions over several buffers can be combined
before dividing by the total weight (see ``federation.fused_fedavg_delta``,
which maps it over every ``(rows, size)`` leaf of an ``UpdateBuffer``).

Implementations:

* ``pallas`` — the compiled TPU kernel (MXU matmul accumulation, f32);
  raises off a TPU;
* ``pallas_interpret`` — the same kernel under the Pallas interpreter, the
  CPU-CI correctness path;
* ``ref`` — fused jnp ``tensordot`` (also the fast CPU execution path);
* ``auto`` — ``pallas`` on TPU, ``ref`` elsewhere.

**Mesh sharding.**  ``fed_reduce(..., mesh=...)`` shards the row dimension
over the mesh's ``dp`` axis with ``shard_map`` + ``psum``: each fleet shard
reduces its slice of the stacked rows with the selected implementation, then
the per-shard partial sums combine across the axis.  Rows are zero-weight
padded up to shard divisibility — padding contributes exactly 0 to the
weighted sum, so the sharded result matches the unsharded one bit-for-bit
per shard and within accumulation tolerance across shards.

**Fused dequantize-and-reduce.**  ``fed_reduce(stack, weights, scales=...)``
consumes a *quantized* int8 stack (``UpdateBuffer(wire="int8")`` leaves):
``out[d] = sum_i weights[i] * scales[i] * stack[i, d]``.  Because symmetric
per-row quantization is linear per row, the per-row scales fold straight
into the weight vector (``weights * scales``) **before** the reduction — the
MXU/BLAS matmul consumes the int8 rows directly (cast per-block in VMEM on
the kernel path, convert-fused-into-dot on the jnp ref path), and no dense
f32 copy of the stack is ever materialized.  The mesh path pads the folded
weights with zeros exactly like the unquantized path, so padding rows still
contribute exactly 0.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import kernels
from repro.analysis.sanitizers import hot_path
from repro.kernels.fed_reduce.fed_reduce import fed_reduce_pallas
from repro.kernels.fed_reduce.ref import fed_reduce_ref

__all__ = ["fed_reduce", "fed_reduce_ref", "tuned_blocks"]

# int8 min tile on TPU is (32, 128); f32/bf16 tiles are coarser but (32, 128)
# stays legal for every dtype the wire formats produce, so it is the blocking
# floor everywhere.
_MIN_BLOCK_N = 32
_MIN_BLOCK_D = 128
# The kernel's weight operand is a (1, rows) row vector tiled (1, block_n):
# once the rows span several blocks, block_n is its lane dimension and must
# be a multiple of 128.  A multiple of 128 is also a multiple of every wire
# dtype's sublane tile (8 f32, 16 bf16, 32 int8), so one rule covers both
# operands for any stack an override may meet.
_LANE = 128


def _check_blocks(block_n: int, block_d: int) -> tuple[int, int]:
    """Reject ``(block_n, block_d)`` pairs the TPU tiling rule refuses."""
    if block_n < 1 or block_d < 1 or block_n % _LANE or block_d % _LANE:
        raise ValueError(
            f"fed_reduce blocks ({block_n}, {block_d}) break the TPU tiling: "
            f"block_n and block_d must be positive multiples of {_LANE}")
    return block_n, block_d


def tuned_blocks(rows: int, size: int, dtype,
                 *, vmem_budget_bytes: int = 1 << 20) -> tuple[int, int]:
    """Pick ``(block_n, block_d)`` for ``fed_reduce_pallas`` from the stack
    shape and wire dtype (mirrors ``decode_attention.ops.tuned_block_k``).

    Each grid step streams one ``(block_n, block_d)`` stack tile at its
    *wire* width — 1 byte/element for a quantized int8 stack, 2 for bf16,
    4 for f32 — plus the f32 weight slice and accumulator.  Pick the largest
    power-of-two blocks whose tile fits the budget (default 1 MiB — a
    conservative slice of the ~16 MiB VMEM leaving room for
    double-buffering; f32 lands on the kernel's historical (256, 512)
    default), growing ``block_n`` first: taller tiles amortize the
    f32 accumulator re-read across more rows, and an int8 stack affords a
    4x taller tile than f32 for the same HBM traffic.  Blocks clamp to the
    padded stack shape so small cohorts stay a single tile instead of
    padding rows/columns 8x past the data.

    ``FED_REDUCE_BLOCKS="<block_n>,<block_d>"`` in the environment overrides
    the table outright (bench sweeps, regression pinning); both must be
    multiples of 128, or it raises.
    """
    override = os.environ.get("FED_REDUCE_BLOCKS")
    if override:
        try:
            bn, bd = (int(v) for v in override.split(","))
        except ValueError:
            raise ValueError(
                f"FED_REDUCE_BLOCKS must be 'block_n,block_d', "
                f"got {override!r}") from None
        return _check_blocks(bn, bd)
    if rows < 1 or size < 1:
        raise ValueError(f"need rows, size >= 1, got ({rows}, {size})")
    itemsize = jnp.dtype(dtype).itemsize
    block_n, block_d = _MIN_BLOCK_N, _MIN_BLOCK_D
    grow_n = True  # alternate, rows first
    while True:
        cand_n, cand_d = (2 * block_n, block_d) if grow_n \
            else (block_n, 2 * block_d)
        tile = cand_n * cand_d * itemsize + cand_n * 4 + cand_d * 4
        if tile > vmem_budget_bytes or cand_n > 1024 or cand_d > 2048:
            if grow_n:  # rows capped out; try one more column doubling
                grow_n = False
                continue
            break
        block_n, block_d = cand_n, cand_d
        grow_n = not grow_n
    pad_n = max(_MIN_BLOCK_N, 1 << (rows - 1).bit_length())
    pad_d = max(_MIN_BLOCK_D, 1 << (size - 1).bit_length())
    return min(block_n, pad_n), min(block_d, pad_d)


def _fed_reduce_local(stack: jax.Array, weights: jax.Array,
                      impl: str) -> jax.Array:
    if impl == "ref":
        return fed_reduce_ref(stack, weights)
    if impl in ("pallas", "pallas_interpret"):
        n = stack.shape[0]
        flat = stack.reshape(n, -1)
        bn, bd = tuned_blocks(n, flat.shape[1], stack.dtype)
        out = fed_reduce_pallas(
            flat, weights, block_n=bn, block_d=bd,
            interpret=kernels.pallas_interpret(impl))
        return out.reshape(stack.shape[1:])
    raise ValueError(f"unknown impl {impl!r}")


@hot_path
def fed_reduce(stack: jax.Array, weights: jax.Array, *,
               scales: jax.Array | None = None,
               impl: str = "auto", mesh=None,
               axis: str = "dp") -> jax.Array:
    """Weighted row-sum ``sum_i weights[i] * stack[i]`` -> f32 ``stack[0]``
    shape.  ``stack``: (n, ...); ``weights``: (n,).

    ``scales`` (f32 ``(n,)``, from a quantized ``UpdateBuffer`` scale
    column) selects the fused dequantize-and-reduce variant:
    ``sum_i weights[i] * scales[i] * stack[i]`` over an int8 stack, with the
    scales folded into the weight vector so the reduction itself is
    unchanged (module docstring).

    ``mesh`` (a ``jax.sharding.Mesh`` containing ``axis``) distributes the
    row reduction across fleet shards; ``None`` keeps the single-device
    path.
    """
    # Explicit h2d up front: callers may hand numpy stacks (tests, host
    # emission paths), and the reduction must stay implicit-transfer-free
    # under transfer_guard("disallow").
    stack = jnp.asarray(stack)
    weights = jnp.asarray(weights)
    if scales is not None:
        scales = jnp.asarray(scales)
    if stack.ndim < 1 or stack.shape[0] != weights.shape[0]:
        raise ValueError(
            f"stack rows {stack.shape} must match weights {weights.shape}")
    if scales is not None:
        if scales.shape != weights.shape:
            raise ValueError(
                f"scales {scales.shape} must match weights {weights.shape}")
        # Per-row dequantization is linear, so it folds into the MXU weight
        # vector; a zero weight still zeroes the whole row.
        weights = weights.astype(jnp.float32) * scales.astype(jnp.float32)
    if impl == "auto":
        impl = "pallas" if kernels.on_tpu() else "ref"
    if mesh is None:
        return _fed_reduce_local(stack, weights, impl)
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    shards = int(mesh.shape[axis])
    n = int(stack.shape[0])
    pad = (-n) % shards
    if pad:
        # Zero-weight rows contribute exactly 0 to the weighted sum.  The
        # pad rows are built on host and device_put explicitly: an eager
        # jnp.zeros broadcasts a host scalar, an implicit transfer under
        # the @hot_path guard.
        stack = jnp.concatenate(
            [stack,
             jnp.asarray(np.zeros((pad,) + stack.shape[1:], stack.dtype))])
        weights = jnp.concatenate(
            [weights, jnp.asarray(np.zeros((pad,), weights.dtype))])
    row_spec = P(axis, *([None] * (stack.ndim - 1)))
    # Shard the operands onto the mesh EXPLICITLY: letting shard_map
    # reshard a single-device operand is an implicit transfer and trips
    # the @hot_path transfer guard.
    stack = jax.device_put(stack, NamedSharding(mesh, row_spec))
    weights = jax.device_put(weights, NamedSharding(mesh, P(axis)))

    def _shard_reduce(s, w):
        return jax.lax.psum(_fed_reduce_local(s, w, impl), axis)

    # check_vma=False: a pallas_call's output carries no varying-axes
    # annotation, which the checker requires; the psum makes it replicated.
    return jax.shard_map(
        _shard_reduce, mesh=mesh, in_specs=(row_spec, P(axis)),
        out_specs=P(*([None] * (stack.ndim - 1))),
        check_vma=False)(stack, weights)
