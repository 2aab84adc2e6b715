"""Pallas TPU kernel for the Mamba2 decode step: one token's state update
for every serving slot, in place.

Per slot and head, with the f32 state ``S (P x N)``::

    S' = exp(dt A) S + dt x (x) B        y = S' C

Decode streams each active slot's whole state through the chip once per
token (read and written back), so the kernel is bound by HBM bandwidth and
is laid out for streaming:

* the state is kept **transposed and head-folded**, ``(slots, h/f, N, f*P)``
  with ``f = 128 / P`` heads side by side on the 128 lanes (``P = 64`` folds
  two heads): a block of ``hb`` folded heads is ``hb`` dense ``(N, 128)``
  f32 slabs, with no lane padding in HBM or VMEM;
* the per-head decay ``exp(dt A)`` and input ``dt x`` arrive as lane rows
  (f32, ``(h/f, f*P)`` per slot), and ``B`` and ``C`` as ``(N, 1)`` columns,
  so the update is one row-broadcast multiply-add per slab and ``y`` one
  sublane reduction: no transposes and no matmuls;
* the state is aliased input to output, and a slot that is not active is
  neither read nor written: its grid steps map the state block to the one
  the previous step used (the first active slot's for leading ones), so the
  pipeline neither fetches nor writes back anything for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 1 << 20  # one state block; in and out, double-buffered: 4 MiB


def fold(heads: int, head_dim: int) -> int:
    """Heads folded onto the 128 lanes of the decode state layout."""
    f = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    while heads % f:
        f //= 2
    return f


def to_decode_layout(state: jax.Array) -> jax.Array:
    """(b, h, p, n) -> (b, h/f, n, f*p)."""
    b, h, p, n = state.shape
    f = fold(h, p)
    return (state.reshape(b, h // f, f, p, n).transpose(0, 1, 4, 2, 3)
            .reshape(b, h // f, n, f * p))


def from_decode_layout(state: jax.Array, head_dim: int) -> jax.Array:
    """(b, h/f, n, f*p) -> (b, h, p, n)."""
    b, hf, n, fp = state.shape
    f = fp // head_dim
    return (state.reshape(b, hf, n, f, head_dim).transpose(0, 1, 3, 4, 2)
            .reshape(b, hf * f, head_dim, n))


def _heads_per_block(hf: int, slab_bytes: int, rep: int) -> int:
    """Folded heads a grid step takes: the most that divide ``hf`` and a
    group's ``rep`` folded heads, are a multiple of 8 (or all ``hf``), and
    fit ``BLOCK_BYTES``."""
    fits = [hb for hb in range(1, hf + 1)
            if hf % hb == 0 and rep % hb == 0 and (hb % 8 == 0 or hb == hf)
            and hb * slab_bytes <= BLOCK_BYTES]
    if not fits:
        raise ValueError(f"no block of the {hf} folded heads fits")
    return max(fits)


def _kernel(act_ref, src_ref, blk_ref, any_ref, u_ref, a_ref, b_ref, c_ref,
            s_ref, y_ref, o_ref, *, hb: int):
    bi = pl.program_id(0)
    del src_ref, blk_ref  # used by the index maps only

    @pl.when(act_ref[bi] != 0)
    def _update():
        bcol = b_ref[...]  # (n, 1)
        ccol = c_ref[...]
        for j in range(hb):
            s = (a_ref[pl.ds(j, 1), :] * s_ref[j]
                 + bcol * u_ref[pl.ds(j, 1), :])  # (n, f*p)
            o_ref[j] = s
            y_ref[pl.ds(j, 1), :] = jnp.sum(s * ccol, axis=0, keepdims=True)

    @pl.when(act_ref[bi] == 0)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

        # With no slot active every step maps to block (0, 0), which the
        # pipeline writes back once at the end: keep its state as it was.
        @pl.when(any_ref[0] == 0)
        def _keep():
            o_ref[...] = s_ref[...]


def ssd_decode_pallas(u: jax.Array, a: jax.Array, bt: jax.Array,
                      ct: jax.Array, state: jax.Array, active: jax.Array,
                      *, rep: int, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """``u = dt x`` and ``a = exp(dt A)`` as folded lane rows (b, h/f, f*p)
    f32; ``bt``, ``ct`` (b, g, n, 1) f32 columns; ``state`` (b, h/f, n,
    f*p) f32, updated in place for the slots where ``active``; ``rep``
    folded heads share a group.  Returns (y (b, h/f, f*p) f32, state)."""
    b, hf, n, fp = state.shape
    hb = _heads_per_block(hf, n * fp * 4, rep)
    nblk = hf // hb
    act = active.astype(jnp.int32)
    idx = jnp.arange(b, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(act > 0, idx, -1))
    nxt = jax.lax.cummin(jnp.where(act > 0, idx, b), reverse=True)
    src = jnp.where(act > 0, idx,
                    jnp.where(prev >= 0, prev, jnp.where(nxt < b, nxt, 0)))
    blk = jnp.where(prev >= 0, nblk - 1, 0).astype(jnp.int32)
    any_active = jnp.max(act, keepdims=True)

    def state_map(bi, ji, act, src, blk, _):
        on = act[bi]
        return src[bi], on * ji + (1 - on) * blk[bi], 0, 0

    row = pl.BlockSpec((None, hb, fp), lambda bi, ji, *_: (bi, ji, 0))
    col = pl.BlockSpec((None, None, n, 1),
                       lambda bi, ji, *_: (bi, ji * hb // rep, 0, 0))
    slab = pl.BlockSpec((None, hb, n, fp), state_map)
    y, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nblk),
            in_specs=[row, row, col, col, slab],
            out_specs=[row, slab],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hf, fp), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={8: 1},
        interpret=interpret,
    )(act, src, blk, any_active, u, a, bt, ct, state)
    return y, state
