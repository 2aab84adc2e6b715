"""Decoder-only transformer LM (dense / MoE / VLM-backbone families).

init/apply with optional ``lax.scan`` over homogeneous layers (compact HLO,
production compile times); the roofline analyzer multiplies scan-body costs by
the trip count.  The same layer code serves train, prefill, and cached decode.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab
from repro.distribution import ctx as shard_ctx
from repro.distribution.ctx import constrain
from repro.models import moe as moe_lib
from repro.models.layers import (
    attention_apply,
    attention_decode,
    attention_init,
    cross_entropy,
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    unembed_apply,
)

Params = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def layer_init(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dt),
        "attn": attention_init(k1, cfg, dt),
        "ln2": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.num_experts:
        p["moe"] = moe_lib.moe_init(k2, cfg, dt)
    else:
        p["mlp"] = mlp_init(k2, cfg, dt)
    return p


def layer_apply(p: Params, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    h = attention_apply(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
                        positions, causal=True)
    x = x + h
    hn = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        impl = shard_ctx.moe_impl() or moe_lib.moe_apply
        h, aux = impl(p["moe"], hn, cfg)
    else:
        h, aux = mlp_apply(p["mlp"], hn, cfg), jnp.zeros((), jnp.float32)
    return constrain(x + h, "act_btd"), aux


def layer_decode(p: Params, x: jax.Array, cfg: ModelConfig,
                 cache: dict) -> tuple[jax.Array, dict]:
    h, cache = attention_decode(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps),
                                cfg, cache)
    x = x + h
    hn = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        impl = shard_ctx.moe_impl() or moe_lib.moe_apply
        h, _ = impl(p["moe"], hn, cfg)
    else:
        h = mlp_apply(p["mlp"], hn, cfg)
    return x + h, cache


def init(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    ke, kl = jax.random.split(key)
    vp = padded_vocab(cfg.vocab_size)
    params = {
        "embed": embed_init(ke, cfg, dt, vp),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    layer_keys = jax.random.split(kl, cfg.num_layers)
    if cfg.scan_layers:
        # One layer at a time: a vmap would hold every layer's f32 RNG
        # temporaries at once (tens of GB for a published-width MoE).
        params["layers"] = jax.lax.map(lambda k: layer_init(k, cfg), layer_keys)
    else:
        params["layers"] = [layer_init(k, cfg) for k in layer_keys]
    return params


def _run_stack(params: Params, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array, remat: bool) -> tuple[jax.Array, jax.Array]:
    f = layer_apply
    if remat:
        f = jax.checkpoint(f, static_argnums=(2,))
    if cfg.scan_layers:
        def body(carry, lp):
            h, aux = f(lp, carry[0], cfg, positions)
            return (h, carry[1] + aux), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return x, aux
    aux = jnp.zeros((), jnp.float32)
    for lp in params["layers"]:
        x, a = f(lp, x, cfg, positions)
        aux = aux + a
    return x, aux


def apply(
    params: Params,
    tokens: jax.Array,  # (b, s) int32
    cfg: ModelConfig,
    *,
    prefix_embeds: jax.Array | None = None,  # (b, n, d) VLM patch embeddings
    remat: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (b, s_total, padded_vocab) f32, aux_loss)."""
    x = embed_apply(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, "act_btd")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, aux = _run_stack(params, x, cfg, positions, remat)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return constrain(unembed_apply(params["embed"], x), "logits"), aux


def loss_fn(params: Params, batch: dict, cfg: ModelConfig, *,
            remat: bool = True, aux_weight: float = 0.01) -> tuple[jax.Array, dict]:
    logits, aux = apply(
        params, batch["tokens"], cfg,
        prefix_embeds=batch.get("prefix_embeds"), remat=remat,
    )
    if batch.get("prefix_embeds") is not None:
        logits = logits[:, batch["prefix_embeds"].shape[1]:]
    ce = cross_entropy(logits, batch["targets"], batch["mask"], cfg.vocab_size)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------- #
# Serving
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> list | dict:
    dt = _dtype(cfg)
    def one():
        return {
            "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dt),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.scan_layers:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one()
        )
    return [one() for _ in range(cfg.num_layers)]


def prefill(
    params: Params,
    tokens: jax.Array,  # (b, s)
    cfg: ModelConfig,
    max_len: int,
    *,
    prefix_embeds: jax.Array | None = None,
) -> tuple[jax.Array, Any]:
    """Full-sequence forward that also populates the KV cache.

    Returns (last-position logits (b, padded_vocab), caches).
    """
    dt = _dtype(cfg)
    x = embed_apply(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    pad = max_len - s

    def run_layer(lp, h):
        from repro.models.layers import _project_qkv, rope  # local reuse
        hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        from repro.models.layers import _attend
        o = _attend(q, k, v, cfg, causal=True)
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        hn = rmsnorm(h, lp["ln2"], cfg.norm_eps)
        if cfg.num_experts:
            impl = shard_ctx.moe_impl() or moe_lib.moe_apply
            m, _ = impl(lp["moe"], hn, cfg)
        else:
            m = mlp_apply(lp["mlp"], hn, cfg)
        cache = {
            "k": jnp.pad(k.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))),
            "pos": jnp.asarray(s, jnp.int32),
        }
        return h + m, cache

    if cfg.scan_layers:
        def body(h, lp):
            h, cache = run_layer(lp, h)
            return h, cache
        x, caches = jax.lax.scan(body, x, params["layers"])
    else:
        caches = []
        for lp in params["layers"]:
            x, c = run_layer(lp, x)
            caches.append(c)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed_apply(params["embed"], x[:, -1])
    return logits, caches


def decode_step(
    params: Params,
    token: jax.Array,  # (b,) int32 — last sampled token
    cfg: ModelConfig,
    caches: Any,
) -> tuple[jax.Array, Any]:
    """One-token decode: returns (logits (b, padded_vocab), caches)."""
    x = embed_apply(params["embed"], token[:, None])
    if cfg.scan_layers:
        def body(h, xs):
            lp, cache = xs
            h, cache = layer_decode(lp, h, cfg, cache)
            return h, cache
        x, caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
        new = []
        for lp, cache in zip(params["layers"], caches):
            x, c = layer_decode(lp, x, cfg, cache)
            new.append(c)
        caches = new
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x[:, 0]), caches
