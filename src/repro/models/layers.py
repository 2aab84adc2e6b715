"""Shared neural building blocks (pure JAX, init/apply style).

Conventions:
* params are nested dicts of jnp arrays; init fns take a PRNG key + config;
* compute dtype follows the input (bf16 end-to-end), with f32 accumulation
  inside softmax/normalization/logits (mixed-precision production recipe);
* every block is shape-polymorphic over batch/sequence so the same code path
  serves train, prefill and decode.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distribution.ctx import constrain
from repro.kernels.decode_attention.ops import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

Params = Any


def truncated_normal_init(key, shape, dtype, scale: float = 0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# Normalization
# --------------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rmsnorm_gated(x: jax.Array, z: jax.Array, w: jax.Array,
                  eps: float = 1e-5) -> jax.Array:
    """Mamba2 gated RMSNorm: norm(x * silu(z)) * w."""
    xf = (x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)).astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# --------------------------------------------------------------------------- #
# Rotary position embeddings
# --------------------------------------------------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# --------------------------------------------------------------------------- #
# Attention block (GQA + RoPE), shared by all attention-bearing families
# --------------------------------------------------------------------------- #
def attention_init(key, cfg: ModelConfig, dtype) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {}
    if cfg.fuse_qkv:
        # Beyond-paper: one fused projection (D, (H + 2KV) * hd) — fewer HLO
        # ops / fewer weight all-gathers under FSDP (see §Perf).
        p["wqkv"] = truncated_normal_init(ks[0], (D, (H + 2 * KV) * hd), dtype)
    else:
        p["wq"] = truncated_normal_init(ks[0], (D, H * hd), dtype)
        p["wk"] = truncated_normal_init(ks[1], (D, KV * hd), dtype)
        p["wv"] = truncated_normal_init(ks[2], (D, KV * hd), dtype)
    p["wo"] = truncated_normal_init(ks[3], (H * hd, D), dtype,
                                    scale=0.02 / (2 * cfg.num_layers) ** 0.5)
    if cfg.qkv_bias:
        zeros = lambda n: jnp.zeros((n * hd,), dtype)
        if cfg.fuse_qkv:
            p["bqkv"] = zeros(H + 2 * KV)
        else:
            p["bq"], p["bk"], p["bv"] = zeros(H), zeros(KV), zeros(KV)
    return p


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig):
    b, s, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.fuse_qkv:
        qkv = x @ p["wqkv"]
        if cfg.qkv_bias:
            qkv = qkv + p["bqkv"]
        q, k, v = jnp.split(qkv, [H * hd, (H + KV) * hd], axis=-1)
    else:
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, s, H, hd), k.reshape(b, s, KV, hd),
            v.reshape(b, s, KV, hd))


def _attend(q, k, v, cfg: ModelConfig, *, causal: bool, q_offset: int = 0,
            scale: float | None = None):
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "chunked" if q.shape[1] * k.shape[1] > 2048 * 2048 else "einsum"
    if impl == "einsum":
        return attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                             scale=scale)
    return flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, impl=impl, scale=scale,
        block_k=min(cfg.attention_kv_chunk, k.shape[1]),
    )


def attention_apply(
    p: Params,
    x: jax.Array,  # (b, s, d)
    cfg: ModelConfig,
    positions: jax.Array,  # (b, s)
    *,
    causal: bool = True,
    use_rope: bool = True,
    kv_override: tuple[jax.Array, jax.Array] | None = None,  # cross-attention
) -> jax.Array:
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if kv_override is not None:
        k, v = kv_override
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = rope(k, positions, cfg.rope_theta)
    # Context parallelism: q stays sequence-sharded; k/v are constrained to
    # sequence-replicated, which GSPMD realizes as the per-layer KV all-gather
    # over the sp axis.
    q = constrain(q, "act_q")
    k = constrain(k, "act_kv")
    v = constrain(v, "act_kv")
    o = _attend(q, k, v, cfg, causal=causal)
    return o.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["wo"]


def attention_decode(
    p: Params,
    x: jax.Array,  # (b, 1, d) — one new token
    cfg: ModelConfig,
    cache: dict,  # {"k": (b, S, KV, hd), "v": ..., "pos": scalar int32}
    *,
    use_rope: bool = True,
) -> tuple[jax.Array, dict]:
    b = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(p, x, cfg)
    pos = cache["pos"]
    if use_rope:
        pos2d = jnp.full((b, 1), pos, jnp.int32)
        q = rope(q, pos2d, cfg.rope_theta)
        k = rope(k, pos2d, cfg.rope_theta)
    # One-hot masked cache write: elementwise, so GSPMD keeps the cache
    # sequence-sharded (a dynamic-update-slice on a sharded dim would
    # replicate the whole cache).
    seq_iota = jnp.arange(cache["k"].shape[1], dtype=jnp.int32)
    write = (seq_iota == pos)[None, :, None, None]
    k_cache = jnp.where(write, k.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(write, v.astype(cache["v"].dtype), cache["v"])
    k_cache = constrain(k_cache, "cache_kv")
    v_cache = constrain(v_cache, "cache_kv")
    lengths = jnp.full((b,), pos + 1, jnp.int32)
    # Plain masked softmax over the (sequence-sharded) cache: GSPMD lowers the
    # softmax reductions over the sharded axis into the flash-decoding
    # max/sum combine (psum over sp); the Pallas kernel is the on-chip analogue.
    o = decode_attention_ref(q[:, 0], k_cache.swapaxes(1, 2),
                             v_cache.swapaxes(1, 2), lengths)
    out = o.reshape(b, 1, H * hd) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache, "pos": pos + 1}


# --------------------------------------------------------------------------- #
# MLP block (dense)
# --------------------------------------------------------------------------- #
def mlp_init(key, cfg: ModelConfig, dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    down_scale = 0.02 / (2 * cfg.num_layers) ** 0.5
    if cfg.mlp_activation == "swiglu":
        if cfg.fuse_qkv:
            return {
                "w_gate_up": truncated_normal_init(ks[0], (D, 2 * F), dtype),
                "w_down": truncated_normal_init(ks[2], (F, D), dtype, down_scale),
            }
        return {
            "w_gate": truncated_normal_init(ks[0], (D, F), dtype),
            "w_up": truncated_normal_init(ks[1], (D, F), dtype),
            "w_down": truncated_normal_init(ks[2], (F, D), dtype, down_scale),
        }
    return {
        "w_up": truncated_normal_init(ks[0], (D, F), dtype),
        "w_down": truncated_normal_init(ks[2], (F, D), dtype, down_scale),
    }


def mlp_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp_activation == "swiglu":
        if "w_gate_up" in p:
            gu = x @ p["w_gate_up"]
            gate, up = jnp.split(gu, 2, axis=-1)
        else:
            gate, up = x @ p["w_gate"], x @ p["w_up"]
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.mlp_activation == "sq_relu":
        h = x @ p["w_up"]
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_activation == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(f"unknown activation {cfg.mlp_activation}")
    return h @ p["w_down"]


# --------------------------------------------------------------------------- #
# Embedding / unembedding
# --------------------------------------------------------------------------- #
def embed_init(key, cfg: ModelConfig, dtype, padded_vocab_size: int) -> Params:
    ks = jax.random.split(key, 2)
    p = {"embedding": truncated_normal_init(
        ks[0], (padded_vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = truncated_normal_init(
            ks[1], (cfg.d_model, padded_vocab_size), dtype)
    return p


def embed_apply(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed_apply(p: Params, x: jax.Array) -> jax.Array:
    w = p["lm_head"] if "lm_head" in p else p["embedding"].T
    return (x @ w).astype(jnp.float32)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array, vocab_size: int) -> jax.Array:
    """Token-mean CE in f32; padded vocab tail columns are masked out."""
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > vocab_size:
        col = jnp.arange(logits.shape[-1])
        logits = jnp.where(col < vocab_size, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
