"""Granite 4.0-H (``granitemoehybrid``): Mamba2 and NoPE GQA mixers, each
layer followed by a sparse MoE and a shared SwiGLU expert.

``cfg.layer_types`` names each layer's mixer (``"mamba"`` or
``"attention"``); the stack is unrolled, since the kinds differ.  With
``m`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier`` and ``c``
= ``logits_scaling``::

    h0 = m E[tok]
    u  = h + r mixer(rmsnorm(h))                  Mamba2 or GQA attention
    h  = u + r (moe(v) + shared(v)),  v = rmsnorm(u)
    logits = rmsnorm(h) E^T / c                   tied embeddings

Attention scores are scaled by ``attention_multiplier`` in place of
``1/sqrt(head_dim)``, without rotary embeddings when ``use_rope`` is off.
The MoE is ``moe.held_moe_apply``: the router spans all experts, the
weights hold ``experts_held`` of them.

Serving state, one entry per layer (``init_state``): attention layers keep
head-major K/V ``(slots, kv, max_len, hd)`` as the transformer's arena
does; Mamba layers keep their conv tails and the f32 SSM state in the
decode kernel's layout (``kernels.ssd_scan.ops.to_decode_layout``).
``prefill_slots`` overwrites every piece of state of the slots it fills, so
a reused slot keeps nothing of its last occupant; ``decode_slots`` leaves
the state of inactive slots as it was.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab
from repro.kernels.decode_attention.ops import (
    decode_attention,
    scatter_decode_token,
    scatter_prefill_rows,
    tuned_block_k,
)
from repro.kernels.ssd_scan.ops import ssd_decode, to_decode_layout
from repro.kernels.ssd_scan.ssd_decode import fold
from repro.models import mamba2
from repro.models import moe as moe_lib
from repro.models.layers import (
    _attend,
    _project_qkv,
    attention_init,
    cross_entropy,
    embed_apply,
    embed_init,
    mlp_apply,
    rmsnorm,
    rope,
    truncated_normal_init,
    unembed_apply,
)

Params = Any

# Prefill runs the admitted rows through the model in groups of at most
# this many tokens, so the SSD chunk matrices, the attention scores and the
# expert rows of one group bound its temporaries.
PREFILL_GROUP_TOKENS = 4096


def init(key, cfg: ModelConfig) -> Params:
    if len(cfg.layer_types) != cfg.num_layers:
        raise ValueError(f"{cfg.name}: {len(cfg.layer_types)} layer types "
                         f"for {cfg.num_layers} layers")
    dt = jnp.dtype(cfg.dtype)
    D, Fs = cfg.d_model, cfg.shared_expert_ff
    ke, kl = jax.random.split(key)
    layers = []
    for k, kind in zip(jax.random.split(kl, cfg.num_layers), cfg.layer_types):
        km, kf, ks = jax.random.split(k, 3)
        lp = {"ln1": jnp.ones((D,), dt), "ln2": jnp.ones((D,), dt),
              "moe": moe_lib.held_moe_init(kf, cfg, dt)}
        if kind == "mamba":
            lp["mamba"] = mamba2.mixer_init(km, cfg)
        else:
            lp["attn"] = attention_init(km, cfg, dt)
        if Fs:
            k1, k2, k3 = jax.random.split(ks, 3)
            lp["shared"] = {
                "w_gate": truncated_normal_init(k1, (D, Fs), dt),
                "w_up": truncated_normal_init(k2, (D, Fs), dt),
                "w_down": truncated_normal_init(
                    k3, (Fs, D), dt, 0.02 / (2 * cfg.num_layers) ** 0.5)}
        layers.append(lp)
    return {"embed": embed_init(ke, cfg, dt, padded_vocab(cfg.vocab_size)),
            "ln_f": jnp.ones((D,), dt), "layers": layers}


def _embed(params, tokens, cfg):
    x = embed_apply(params["embed"], tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _logits(params, x, cfg):
    logits = unembed_apply(params["embed"],
                           rmsnorm(x, params["ln_f"], cfg.norm_eps))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _residual(h, y, cfg):
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return h + y


def _ffn(lp, h, cfg):
    """The layer's second half: MoE (held experts) plus the shared expert."""
    v = rmsnorm(h, lp["ln2"], cfg.norm_eps)
    y = moe_lib.held_moe_apply(lp["moe"], v, cfg)
    if "shared" in lp:
        y = y + mlp_apply(lp["shared"], v, cfg)
    return _residual(h, y, cfg)


def _attention(lp, hn, cfg):
    """Causal GQA over a whole sequence: (output, k, v)."""
    b, s, _ = hn.shape
    q, k, v = _project_qkv(lp["attn"], hn, cfg)
    if cfg.use_rope:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    o = _attend(q, k, v, cfg, causal=True, scale=cfg.attention_multiplier)
    return o.reshape(b, s, -1) @ lp["attn"]["wo"], k, v


@functools.partial(jax.jit, static_argnames=("cfg", "remat"))
def apply(params: Params, tokens: jax.Array, cfg: ModelConfig,
          *, remat: bool = False) -> tuple[jax.Array, jax.Array]:
    del remat
    x = _embed(params, tokens, cfg)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        hn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if kind == "mamba":
            y, _ = mamba2.mixer_prefill(lp["mamba"], hn, cfg,
                                        impl=cfg.ssm_impl)
        else:
            y, _, _ = _attention(lp, hn, cfg)
        x = _ffn(lp, _residual(x, y, cfg), cfg)
    return _logits(params, x, cfg), jnp.zeros((), jnp.float32)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig,
            *, remat: bool = True) -> tuple[jax.Array, dict]:
    logits, _ = apply(params, batch["tokens"], cfg, remat=remat)
    ce = cross_entropy(logits, batch["targets"], batch["mask"], cfg.vocab_size)
    return ce, {"ce": ce}


# --------------------------------------------------------------------------- #
# Serving: per-slot state, prefill into slots, one decode step for all slots
# --------------------------------------------------------------------------- #
def init_state(cfg: ModelConfig, slots: int, max_len: int) -> list[dict]:
    dt = jnp.dtype(cfg.dtype)
    di, g, n, h, _ = mamba2._dims(cfg)
    p, tail = cfg.ssm_head_dim, cfg.ssm_conv_width - 1
    f = fold(h, p) if h else 1
    state = []
    for kind in cfg.layer_types:
        if kind == "mamba":
            state.append({
                "conv_x": jnp.zeros((slots, tail, di), dt),
                "conv_BC": jnp.zeros((slots, tail, 2 * g * n), dt),
                "ssm": jnp.zeros((slots, h // f, n, f * p), jnp.float32)})
        else:
            shape = (slots, cfg.num_kv_heads, max_len, cfg.head_dim)
            state.append({"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)})
    return state


def _rows_per_group(m: int, s: int) -> int:
    return max(g for g in range(1, m + 1)
               if m % g == 0 and (g * s <= PREFILL_GROUP_TOKENS or g == 1))


def _prefill_rows(params, tokens, slot_ids, state, cfg):
    x = _embed(params, tokens, cfg)
    put = lambda arr, rows: arr.at[slot_ids].set(rows.astype(arr.dtype),
                                                 mode="drop")
    new = []
    for lp, kind, st in zip(params["layers"], cfg.layer_types, state):
        hn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if kind == "mamba":
            y, c = mamba2.mixer_prefill(lp["mamba"], hn, cfg,
                                        impl=cfg.ssm_impl)
            st = {"conv_x": put(st["conv_x"], c["conv_x"]),
                  "conv_BC": put(st["conv_BC"], c["conv_BC"]),
                  "ssm": put(st["ssm"], to_decode_layout(c["ssm"]))}
        else:
            y, k, v = _attention(lp, hn, cfg)
            st = {"k": scatter_prefill_rows(st["k"], k.astype(st["k"].dtype),
                                            slot_ids),
                  "v": scatter_prefill_rows(st["v"], v.astype(st["v"].dtype),
                                            slot_ids)}
        x = _ffn(lp, _residual(x, y, cfg), cfg)
        new.append(st)
    return _logits(params, x[:, -1], cfg), new


def prefill_slots(params: Params, tokens: jax.Array, slot_ids: jax.Array,
                  state: list[dict], cfg: ModelConfig
                  ) -> tuple[jax.Array, list[dict]]:
    """Prefill prompts ``tokens`` (m, s) into slots ``slot_ids`` (m,) (ids
    past the last slot are padding whose writes drop), in groups of rows
    (``PREFILL_GROUP_TOKENS``).  Returns the last position's logits (m, V)
    f32 and the state."""
    m, s = tokens.shape
    g = _rows_per_group(m, s)

    def group(st, rows):
        logits, st = _prefill_rows(params, rows[0], rows[1], st, cfg)
        return st, logits

    state, logits = jax.lax.scan(
        group, state, (tokens.reshape(m // g, g, s), slot_ids.reshape(-1, g)))
    return logits.reshape(m, -1), state


def decode_slots(params: Params, tok: jax.Array, active: jax.Array,
                 lengths: jax.Array, state: list[dict], cfg: ModelConfig, *,
                 attn_impl: str = "auto", block_k: int | None = None
                 ) -> tuple[jax.Array, list[dict]]:
    """One token for every active slot: ``tok`` (slots,) the last tokens,
    ``lengths`` (slots,) the tokens each slot holds.  ``attn_impl`` picks
    ``decode_attention``'s implementation, ``cfg.ssm_decode_impl``
    ``ssd_decode``'s.  Returns logits (slots, V) f32 and the state; inactive slots' state is
    left as it was."""
    slots = tok.shape[0]
    x = _embed(params, tok[:, None], cfg)
    keep = active[:, None, None]
    write_pos = lens_att = None
    new = []
    for lp, kind, st in zip(params["layers"], cfg.layer_types, state):
        hn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if kind == "mamba":
            y, c = mamba2.mixer_decode(
                lp["mamba"], hn, cfg, st,
                state_update=lambda *a: ssd_decode(
                    *a, active, impl=cfg.ssm_decode_impl))
            st = {"conv_x": jnp.where(keep, c["conv_x"], st["conv_x"]),
                  "conv_BC": jnp.where(keep, c["conv_BC"], st["conv_BC"]),
                  "ssm": c["ssm"]}
        else:
            max_len = st["k"].shape[2]
            if write_pos is None:
                write_pos = jnp.where(active, lengths, max_len)  # OOB: drops
                lens_att = lengths + active.astype(jnp.int32)
            q, k, v = _project_qkv(lp["attn"], hn, cfg)
            if cfg.use_rope:
                q = rope(q, lengths[:, None], cfg.rope_theta)
                k = rope(k, lengths[:, None], cfg.rope_theta)
            kc = scatter_decode_token(st["k"], k[:, 0].astype(st["k"].dtype),
                                      write_pos)
            vc = scatter_decode_token(st["v"], v[:, 0].astype(st["v"].dtype),
                                      write_pos)
            o = decode_attention(
                q[:, 0], kc, vc, lens_att, scale=cfg.attention_multiplier,
                impl=attn_impl, block_k=block_k or tuned_block_k(
                    max_len, head_dim=cfg.head_dim))
            y = o.reshape(slots, 1, -1) @ lp["attn"]["wo"]
            st = {"k": kc, "v": vc}
        x = _ffn(lp, _residual(x, y, cfg), cfg)
        new.append(st)
    return _logits(params, x[:, 0], cfg), new


# The registry's batch API: every row is a slot, all active.
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return {"layers": init_state(cfg, batch, max_len),
            "lengths": jnp.zeros((batch,), jnp.int32)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            max_len: int) -> tuple[jax.Array, dict]:
    b, s = tokens.shape
    logits, layers = prefill_slots(params, tokens,
                                   jnp.arange(b, dtype=jnp.int32),
                                   init_state(cfg, b, max_len), cfg)
    return logits, {"layers": layers,
                    "lengths": jnp.full((b,), s, jnp.int32)}


@functools.partial(jax.jit, static_argnums=(2,))
def decode_step(params: Params, token: jax.Array, cfg: ModelConfig,
                cache: dict) -> tuple[jax.Array, dict]:
    active = jnp.ones(token.shape, bool)
    logits, layers = decode_slots(params, token, active, cache["lengths"],
                                  cache["layers"], cfg)
    return logits, {"layers": layers, "lengths": cache["lengths"] + 1}
