"""Encoder-decoder transformer (seamless-m4t-medium backbone).

The speech/text frontend is a stub per the assignment: ``input_specs()``
supplies precomputed frame embeddings ``(b, s_src, d)`` for the encoder.
Decoder layers add cross-attention against the encoder memory; serving
precomputes the cross KV once at prefill (standard enc-dec serving layout).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab
from repro.models.layers import (
    _attend,
    _project_qkv,
    attention_init,
    cross_entropy,
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rope,
    truncated_normal_init,
    unembed_apply,
)
from repro.models.transformer import layer_apply, layer_decode, layer_init

Params = Any


def _cross_attn_init(key, cfg: ModelConfig, dt) -> Params:
    # Same projection structure as self-attention (never fused: KV comes from
    # the encoder memory at a different time).
    import dataclasses
    return attention_init(key, dataclasses.replace(cfg, fuse_qkv=False, qkv_bias=False), dt)


def init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ke, kenc, kdec, kx = jax.random.split(key, 4)
    vp = padded_vocab(cfg.vocab_size)
    enc_keys = jax.random.split(kenc, cfg.num_encoder_layers)
    dec_keys = jax.random.split(kdec, cfg.num_layers)
    x_keys = jax.random.split(kx, cfg.num_layers)

    def enc_layer(k):
        return layer_init(k, cfg)

    def dec_layer(k, kx_):
        p = layer_init(k, cfg)
        p["ln_x"] = jnp.ones((cfg.d_model,), dt)
        p["cross"] = _cross_attn_init(kx_, cfg, dt)
        return p

    params = {
        "embed": embed_init(ke, cfg, dt, vp),
        "ln_enc": jnp.ones((cfg.d_model,), dt),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.scan_layers:
        params["encoder"] = jax.vmap(enc_layer)(enc_keys)
        params["decoder"] = jax.vmap(dec_layer)(dec_keys, x_keys)
    else:
        params["encoder"] = [enc_layer(k) for k in enc_keys]
        params["decoder"] = [dec_layer(k, kk) for k, kk in zip(dec_keys, x_keys)]
    return params


def encode(params: Params, src_embeds: jax.Array, cfg: ModelConfig,
           *, remat: bool = False) -> jax.Array:
    """src_embeds: (b, s_src, d) precomputed frontend embeddings."""
    x = src_embeds.astype(jnp.dtype(cfg.dtype))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def enc_apply(lp, h):
        hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg)
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
        o = _attend(q, k, v, cfg, causal=False)  # bidirectional
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        return h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg)

    f = jax.checkpoint(enc_apply) if remat else enc_apply
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda h, lp: (f(lp, h), None), x, params["encoder"])
    else:
        for lp in params["encoder"]:
            x = f(lp, x)
    return rmsnorm(x, params["ln_enc"], cfg.norm_eps)


def _dec_layer_full(lp, x, memory, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    # Self-attention (causal).
    hn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(lp["attn"], hn, cfg)
    q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
    o = _attend(q, k, v, cfg, causal=True)
    x = x + o.reshape(b, s, -1) @ lp["attn"]["wo"]
    # Cross-attention (no RoPE, full memory).
    hn = rmsnorm(x, lp["ln_x"], cfg.norm_eps)
    qc = (hn @ lp["cross"]["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    mk = (memory @ lp["cross"]["wk"]).reshape(
        b, memory.shape[1], cfg.num_kv_heads, cfg.head_dim)
    mv = (memory @ lp["cross"]["wv"]).reshape(
        b, memory.shape[1], cfg.num_kv_heads, cfg.head_dim)
    oc = _attend(qc, mk, mv, cfg, causal=False)
    x = x + oc.reshape(b, s, -1) @ lp["cross"]["wo"]
    # MLP.
    return x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps), cfg)


def decode_train(params: Params, tokens: jax.Array, memory: jax.Array,
                 cfg: ModelConfig, *, remat: bool = False) -> jax.Array:
    x = embed_apply(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    f = jax.checkpoint(_dec_layer_full, static_argnums=(3,)) if remat else _dec_layer_full
    if cfg.scan_layers:
        x, _ = jax.lax.scan(
            lambda h, lp: (f(lp, h, memory, cfg, positions), None),
            x, params["decoder"])
    else:
        for lp in params["decoder"]:
            x = f(lp, x, memory, cfg, positions)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig,
            *, remat: bool = True) -> tuple[jax.Array, dict]:
    memory = encode(params, batch["src_embeds"], cfg, remat=remat)
    logits = decode_train(params, batch["tokens"], memory, cfg, remat=remat)
    ce = cross_entropy(logits, batch["targets"], batch["mask"], cfg.vocab_size)
    return ce, {"ce": ce}


# --------------------------------------------------------------------------- #
# Serving: cross-KV precomputed at prefill, self-KV cached per decoder layer
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int, src_len: int) -> Any:
    dt = jnp.dtype(cfg.dtype)
    KV, hd = cfg.num_kv_heads, cfg.head_dim

    def one():
        return {
            "k": jnp.zeros((batch, max_len, KV, hd), dt),
            "v": jnp.zeros((batch, max_len, KV, hd), dt),
            "pos": jnp.zeros((), jnp.int32),
            "xk": jnp.zeros((batch, src_len, KV, hd), dt),
            "xv": jnp.zeros((batch, src_len, KV, hd), dt),
        }

    if cfg.scan_layers:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one()
        )
    return [one() for _ in range(cfg.num_layers)]


def prefill(params: Params, src_embeds: jax.Array, tokens: jax.Array,
            cfg: ModelConfig, max_len: int) -> tuple[jax.Array, Any]:
    """Encode the source, run the decoder prompt, build all caches."""
    dt = jnp.dtype(cfg.dtype)
    memory = encode(params, src_embeds, cfg)
    x = embed_apply(params["embed"], tokens)
    b, s, _ = x.shape
    s_src = memory.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    pad = max_len - s

    def run_layer(lp, h):
        hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg)
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
        o = _attend(q, k, v, cfg, causal=True)
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        hn = rmsnorm(h, lp["ln_x"], cfg.norm_eps)
        qc = (hn @ lp["cross"]["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        mk = (memory @ lp["cross"]["wk"]).reshape(b, s_src, cfg.num_kv_heads, cfg.head_dim)
        mv = (memory @ lp["cross"]["wv"]).reshape(b, s_src, cfg.num_kv_heads, cfg.head_dim)
        oc = _attend(qc, mk, mv, cfg, causal=False)
        h = h + oc.reshape(b, s, -1) @ lp["cross"]["wo"]
        h = h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg)
        cache = {
            "k": jnp.pad(k.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))),
            "pos": jnp.asarray(s, jnp.int32),
            "xk": mk.astype(dt),
            "xv": mv.astype(dt),
        }
        return h, cache

    if cfg.scan_layers:
        x, caches = jax.lax.scan(lambda h, lp: run_layer(lp, h), x,
                                 params["decoder"])
    else:
        caches = []
        for lp in params["decoder"]:
            x, c = run_layer(lp, x)
            caches.append(c)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x[:, -1]), caches


def decode_step(params: Params, token: jax.Array, cfg: ModelConfig,
                caches: Any) -> tuple[jax.Array, Any]:
    from repro.models.layers import attention_decode

    x = embed_apply(params["embed"], token[:, None])
    b = x.shape[0]

    def run_layer(lp, h, cache):
        h_attn, sa = attention_decode(
            lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg,
            {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]},
        )
        h = h + h_attn
        hn = rmsnorm(h, lp["ln_x"], cfg.norm_eps)
        qc = (hn @ lp["cross"]["wq"]).reshape(b, 1, cfg.num_heads, cfg.head_dim)
        from repro.kernels.decode_attention.ops import decode_attention_ref
        s_src = cache["xk"].shape[1]
        lengths = jnp.full((b,), s_src, jnp.int32)
        oc = decode_attention_ref(qc[:, 0], cache["xk"].swapaxes(1, 2),
                                  cache["xv"].swapaxes(1, 2), lengths)
        h = h + oc.reshape(b, 1, -1) @ lp["cross"]["wo"]
        h = h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg)
        new_cache = dict(sa, xk=cache["xk"], xv=cache["xv"])
        return h, new_cache

    if cfg.scan_layers:
        def body(h, xs):
            lp, cache = xs
            h, c = run_layer(lp, h, cache)
            return h, c
        x, caches = jax.lax.scan(body, x, (params["decoder"], caches))
    else:
        new = []
        for lp, cache in zip(params["decoder"], caches):
            x, c = run_layer(lp, x, cache)
            new.append(c)
        caches = new
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x[:, 0]), caches
