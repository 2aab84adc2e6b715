"""Pure-jnp oracle for single-token decode attention over a KV cache.

Caches are head-major ``(b, kv, s, d)``, the layout of the Pallas kernel and
of the continuous-batching KV arena; the fixed-batch model caches are
sequence-major and swap their axes at the call."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def decode_attention_ref(
    q: jax.Array,  # (b, h, d) — one new token per sequence
    k_cache: jax.Array,  # (b, kv, s, d) — head-major
    v_cache: jax.Array,  # (b, kv, s, d)
    lengths: jax.Array,  # (b,) int32 — valid cache entries per sequence
    *,
    scale: float | None = None,
) -> jax.Array:
    b, h, d = q.shape
    kv = k_cache.shape[1]
    g = h // kv
    scale = (d ** -0.5) if scale is None else scale
    return _decode_scoped(q, k_cache, v_cache, lengths, scale, b, kv, g, d)


def _decode_scoped(q, k_cache, v_cache, lengths, scale, b, kv, g, d):
    """Kernel-region scope: executes as the Pallas flash-decoding kernel on
    TPU (scores in VMEM; HBM traffic = one cache stream + q/o)."""
    import jax
    with jax.named_scope("pallas_kernel_region"):
        return _decode_impl(q, k_cache, v_cache, lengths, scale, b, kv, g, d)


def _decode_impl(q, k_cache, v_cache, lengths, scale, b, kv, g, d):
    # Keep the cache in its storage dtype; accumulate in f32 on the MXU —
    # casting the cache to f32 would triple decode HBM traffic (§Perf).
    qg = (q.reshape(b, kv, g, d) * scale).astype(q.dtype)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    mask = jnp.arange(k_cache.shape[2])[None] < lengths[:, None]  # (b, s)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p.astype(q.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, kv * g, d).astype(q.dtype)
    # length-0 rows (a retired / never-filled KV-arena slot): the all-masked
    # softmax degenerates to uniform weights over garbage — return exact
    # zeros instead, matching the Pallas kernel's empty-accumulator output.
    return jnp.where(lengths[:, None, None] > 0, o, jnp.zeros_like(o))


def decode_attention_partial(
    q: jax.Array,  # (b, h, d)
    k_cache: jax.Array,  # (b, kv, s_shard, d) — one *shard* of the cache
    v_cache: jax.Array,
    lengths: jax.Array,  # (b,) valid entries in THIS shard
    *,
    scale: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash-decoding partial results for cross-shard combination.

    Returns ``(o_partial, m, l)`` where the final output across shards is
    ``sum_i o_i * exp(m_i - m) * l_i / sum_i exp(m_i - m) * l_i`` — the
    sequence-parallel decode combine used by ``distribution.steps`` (psum over
    the ``sp`` axis).  o_partial is the *unnormalized-but-locally-normalized*
    softmax output of this shard.
    """
    b, h, d = q.shape
    kv = k_cache.shape[1]
    g = h // kv
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, kv, g, d).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(k_cache.shape[2])[None] < lengths[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = s.max(axis=-1)  # (b, kv, g)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    return (
        o.reshape(b, h, d),
        m.reshape(b, h),
        l.reshape(b, h),
    )


def combine_partials(
    os: jax.Array,  # (n_shards, b, h, d)
    ms: jax.Array,  # (n_shards, b, h)
    ls: jax.Array,  # (n_shards, b, h)
    out_dtype=None,
) -> jax.Array:
    m = ms.max(axis=0)  # (b, h)
    w = jnp.exp(ms - m[None])  # (n, b, h)
    l = (ls * w).sum(axis=0)
    o = (os * w[..., None]).sum(axis=0)
    out = o / jnp.maximum(l, 1e-37)[..., None]
    return out.astype(out_dtype or os.dtype)
