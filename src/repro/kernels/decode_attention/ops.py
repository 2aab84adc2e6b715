"""Public entry point for decode attention (single-token, KV cache).

``decode_attention`` and its jnp reference ``decode_attention_ref`` take
**head-major** caches ``(b, kv, s, d)`` — the layout of the
continuous-batching KV arena (``core.serving``), chosen so the Pallas
kernel's K/V tiles are contiguous ``(block_k, d)`` slabs.

Besides the attention op itself this module carries the KV-*arena* slot
paths used by continuous batching: a fixed-capacity cache of shape
``(slots, kv, max_len, d)`` where each slot row is one request's cache
residency.  Slot writes use out-of-bounds indices as padding sentinels
(``mode="drop"``), so the jitted update has one static shape regardless of
how many requests were admitted this iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.decode_attention.ref import (
    combine_partials,
    decode_attention_partial,
    decode_attention_ref,
)

__all__ = [
    "decode_attention",
    "decode_attention_partial",
    "combine_partials",
    "decode_attention_ref",
    "scatter_prefill_rows",
    "scatter_decode_token",
    "gather_slots",
    "tuned_block_k",
]


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "impl"))
def decode_attention(
    q: jax.Array,  # (b, h, d)
    k_cache: jax.Array,  # (b, kv, s, d) — head-major
    v_cache: jax.Array,
    lengths: jax.Array,  # (b,)
    *,
    scale: float | None = None,
    block_k: int = 512,
    impl: str = "auto",
) -> jax.Array:
    if impl == "auto":
        impl = "pallas" if kernels.on_tpu() else "ref"
    if impl in ("pallas", "pallas_interpret"):
        return decode_attention_pallas(
            q, k_cache, v_cache, lengths, scale=scale, block_k=block_k,
            interpret=kernels.pallas_interpret(impl),
        )
    if impl == "ref":
        return decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")


# --------------------------------------------------------------------------- #
# KV-arena slot paths (continuous batching)
# --------------------------------------------------------------------------- #
def scatter_prefill_rows(cache: jax.Array, rows: jax.Array,
                         slot_ids: jax.Array) -> jax.Array:
    """Write freshly prefilled K/V rows into their arena slots.

    ``cache`` is ``(slots, kv, max_len, d)``; ``rows`` is ``(m, s, kv, d)``
    (the projection's sequence-major layout) with ``s <= max_len``;
    ``slot_ids`` is ``(m,) int32``.  Entries with
    ``slot_ids[i] >= slots`` are padding — their writes drop, so a single
    jitted shape serves any number of admissions.  Rows ``[s:max_len)`` of a
    reused slot keep the previous occupant's stale K/V; they are dead by
    construction because the slot's length counter is reset to ``s``.
    """
    s = rows.shape[1]
    return cache.at[slot_ids, :, :s].set(rows.swapaxes(1, 2), mode="drop")


def scatter_decode_token(cache: jax.Array, kv_tok: jax.Array,
                         write_pos: jax.Array) -> jax.Array:
    """Write one decoded token's K/V at each slot's own cache position.

    ``cache`` is ``(slots, kv, max_len, d)``; ``kv_tok`` is ``(slots, kv, d)``;
    ``write_pos`` is ``(slots,) int32`` — per-slot ragged positions.  Inactive
    slots pass ``write_pos >= max_len`` and their writes drop.
    """
    slots = cache.shape[0]
    return cache.at[jnp.arange(slots, dtype=jnp.int32), :, write_pos].set(
        kv_tok, mode="drop")


def gather_slots(cache: jax.Array, slot_ids: jax.Array) -> jax.Array:
    """Gather ``(m, kv, max_len, d)`` slot rows (e.g. to migrate or inspect a
    request's cache residency); out-of-bounds ids fill with zeros."""
    return cache.at[slot_ids].get(mode="fill", fill_value=0)


def tuned_block_k(max_len: int, *, head_dim: int = 128,
                  vmem_budget_bytes: int = 1 << 18) -> int:
    """Pick the flash-decoding K-block for an arena-scale cache.

    At arena scale the cache is ``slots * max_len`` rows; each grid step
    streams one ``(block_k, d)`` K tile plus its V tile through VMEM.  Pick
    the largest power-of-two block whose two f32 tiles fit the budget
    (default 256 KiB — conservative slice of the ~16 MiB VMEM so the q/o
    tiles and double-buffering fit alongside), clamped to the padded cache
    length so short caches stay a single block.
    """
    if max_len < 1:
        raise ValueError("max_len must be >= 1")
    best = 128
    for cand in (256, 512, 1024):
        if 2 * cand * head_dim * 4 <= vmem_budget_bytes:
            best = cand
    padded = max(128, 1 << (max_len - 1).bit_length())
    return min(best, padded)
