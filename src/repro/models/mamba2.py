"""Mamba2 (SSD — state-space duality) language model.

Block layout follows the Mamba2 reference: fused ``in_proj`` producing
``[z, x, B, C, dt]``, short causal depthwise conv over ``[x, B, C]``, SSD scan
(chunked; Pallas kernel on TPU), gated RMSNorm, ``out_proj``.  Decode carries
an O(1) recurrent state per layer — this is what makes the ``long_500k``
cell feasible.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, padded_vocab
from repro.distribution.ctx import constrain
from repro.kernels.ssd_scan.ops import ssd_decode_step, ssd_scan
from repro.models.layers import (
    cross_entropy,
    embed_apply,
    embed_init,
    rmsnorm,
    rmsnorm_gated,
    truncated_normal_init,
    unembed_apply,
)

Params = Any


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    g, n = cfg.ssm_groups, cfg.ssm_state
    h = cfg.ssm_heads
    conv_dim = di + 2 * g * n
    return di, g, n, h, conv_dim


def block_init(key, cfg: ModelConfig) -> Params:
    return {"ln": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype)),
            **mixer_init(key, cfg)}


def mixer_init(key, cfg: ModelConfig) -> Params:
    """The Mamba2 mixer's weights, without the block's pre-norm."""
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    di, g, n, h, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 4)
    # dt bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 default).
    u = jax.random.uniform(ks[2], (h,), jnp.float32)
    dt_init = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    kz, kx, kbc, kdt = jax.random.split(ks[0], 4)
    kcx, kcbc = jax.random.split(ks[1])
    # Projections are stored separately so each can carry its own sharding:
    # z/x/dt outputs are head-sharded over tp; B/C are per-group (replicated
    # when groups < tp).  Functionally identical to the fused in_proj.
    return {
        "in_z": truncated_normal_init(kz, (D, di), dt),
        "in_x": truncated_normal_init(kx, (D, di), dt),
        "in_BC": truncated_normal_init(kbc, (D, 2 * g * n), dt),
        "in_dt": truncated_normal_init(kdt, (D, h), dt),
        "conv_x_w": truncated_normal_init(kcx, (cfg.ssm_conv_width, di), dt,
                                          scale=0.5 / cfg.ssm_conv_width),
        "conv_x_b": jnp.zeros((di,), dt),
        "conv_BC_w": truncated_normal_init(kcbc, (cfg.ssm_conv_width, 2 * g * n), dt,
                                           scale=0.5 / cfg.ssm_conv_width),
        "conv_BC_b": jnp.zeros((2 * g * n,), dt),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias,
        "D_skip": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.ones((di,), dt),
        "out_proj": truncated_normal_init(
            ks[3], (di, D), dt, scale=0.02 / (2 * cfg.num_layers) ** 0.5),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 *, tail: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv along seq.  xbc (b, l, c); w (width, c).

    ``tail`` is the (b, width-1, c) left-context carried by the decode cache.
    """
    width = w.shape[0]
    if tail is None:
        xbc_p = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xbc_p = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    out = jnp.zeros_like(xbc)
    for i in range(width):  # width is 4: unrolled elementwise adds
        out = out + xbc_p[:, i : i + xbc.shape[1]] * w[i]
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xbc.dtype)


def _project(p: Params, hn: jax.Array):
    return hn @ p["in_z"], hn @ p["in_x"], hn @ p["in_BC"], hn @ p["in_dt"]


def block_apply(p: Params, x: jax.Array, cfg: ModelConfig,
                *, impl: str = "auto") -> jax.Array:
    b, l, D = x.shape
    di, g, n, h, conv_dim = _dims(cfg)
    hn = rmsnorm(x, p["ln"], cfg.norm_eps)
    z, xp, BC_raw, dt_raw = _project(p, hn)
    z, xp = constrain(z, "ssm_inner"), constrain(xp, "ssm_inner")
    BC_raw = constrain(BC_raw, "ssm_bc")
    xs = _causal_conv(xp, p["conv_x_w"], p["conv_x_b"])
    BC = _causal_conv(BC_raw, p["conv_BC_w"], p["conv_BC_b"])
    B, C = jnp.split(BC, 2, axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, _ = ssd_scan(
        xs.reshape(b, l, h, cfg.ssm_head_dim),
        dt, A,
        B.reshape(b, l, g, n), C.reshape(b, l, g, n),
        chunk=min(cfg.ssm_chunk, l), impl=impl,
    )
    y = y + p["D_skip"][None, None, :, None] * xs.reshape(b, l, h, cfg.ssm_head_dim).astype(jnp.float32)
    y = constrain(y.reshape(b, l, di).astype(x.dtype), "ssm_inner")
    y = rmsnorm_gated(y, z, p["norm_w"], cfg.norm_eps)
    return constrain(x + y @ p["out_proj"], "act_btd")


def block_prefill(p: Params, x: jax.Array, cfg: ModelConfig,
                  *, impl: str = "auto") -> tuple[jax.Array, dict]:
    """Like block_apply but returns the decode cache (conv tail + ssm state)."""
    y, cache = mixer_prefill(p, rmsnorm(x, p["ln"], cfg.norm_eps), cfg,
                             impl=impl)
    return x + y, cache


def mixer_prefill(p: Params, hn: jax.Array, cfg: ModelConfig,
                  *, impl: str = "auto") -> tuple[jax.Array, dict]:
    """The mixer over a normed sequence ``hn`` (b, l, d): its output (no
    residual) and the decode cache (conv tails, f32 ssm state (b,h,p,n))."""
    b, l, D = hn.shape
    di, g, n, h, conv_dim = _dims(cfg)
    width = cfg.ssm_conv_width
    z, xp, BC_raw, dt_raw = _project(p, hn)
    xs = _causal_conv(xp, p["conv_x_w"], p["conv_x_b"])
    BC = _causal_conv(BC_raw, p["conv_BC_w"], p["conv_BC_b"])
    B, C = jnp.split(BC, 2, axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, state = ssd_scan(
        xs.reshape(b, l, h, cfg.ssm_head_dim),
        dt, A,
        B.reshape(b, l, g, n), C.reshape(b, l, g, n),
        chunk=min(cfg.ssm_chunk, l), impl=impl,
    )
    y = y + p["D_skip"][None, None, :, None] * xs.reshape(b, l, h, cfg.ssm_head_dim).astype(jnp.float32)
    y = y.reshape(b, l, di).astype(hn.dtype)
    y = rmsnorm_gated(y, z, p["norm_w"], cfg.norm_eps)
    cache = {
        "conv_x": xp[:, l - (width - 1):].astype(hn.dtype),
        "conv_BC": BC_raw[:, l - (width - 1):].astype(hn.dtype),
        "ssm": state,
    }
    return y @ p["out_proj"], cache


def block_decode(p: Params, x: jax.Array, cfg: ModelConfig,
                 cache: dict) -> tuple[jax.Array, dict]:
    """One-token recurrent update: x (b, 1, d)."""
    y, cache = mixer_decode(p, rmsnorm(x, p["ln"], cfg.norm_eps), cfg, cache)
    return x + y, cache


def mixer_decode(p: Params, hn: jax.Array, cfg: ModelConfig, cache: dict,
                 *, state_update=None) -> tuple[jax.Array, dict]:
    """The mixer's one-token step over a normed ``hn`` (b, 1, d): its output
    (no residual) and the cache advanced one token.  ``state_update(x, dt,
    A, B, C, state) -> (y (b,h,p), state)`` advances ``cache["ssm"]``; it
    defaults to ``ssd_decode_step`` on the (b, h, p, n) state."""
    b = hn.shape[0]
    di, g, n, h, conv_dim = _dims(cfg)
    z, xp, BC_raw, dt_raw = _project(p, hn)
    conv_x_in = jnp.concatenate([cache["conv_x"], xp], axis=1)  # (b, width, di)
    conv_BC_in = jnp.concatenate([cache["conv_BC"], BC_raw], axis=1)
    cx = (conv_x_in * p["conv_x_w"]).sum(axis=1, keepdims=True) + p["conv_x_b"]
    cbc = (conv_BC_in * p["conv_BC_w"]).sum(axis=1, keepdims=True) + p["conv_BC_b"]
    xs = jax.nn.silu(cx.astype(jnp.float32)).astype(hn.dtype)[:, 0]
    BC = jax.nn.silu(cbc.astype(jnp.float32)).astype(hn.dtype)[:, 0]
    B, C = jnp.split(BC, 2, axis=-1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, state = (state_update or ssd_decode_step)(
        xs.reshape(b, h, cfg.ssm_head_dim), dt, A,
        B.reshape(b, g, n), C.reshape(b, g, n), cache["ssm"],
    )
    y = y + p["D_skip"][None, :, None] * xs.reshape(b, h, cfg.ssm_head_dim).astype(jnp.float32)
    y = y.reshape(b, 1, di).astype(hn.dtype)
    y = rmsnorm_gated(y, z, p["norm_w"], cfg.norm_eps)
    new_cache = {"conv_x": conv_x_in[:, 1:], "conv_BC": conv_BC_in[:, 1:],
                 "ssm": state}
    return y @ p["out_proj"], new_cache


# --------------------------------------------------------------------------- #
# Full model
# --------------------------------------------------------------------------- #
def init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ke, kl = jax.random.split(key)
    vp = padded_vocab(cfg.vocab_size)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    params = {
        "embed": embed_init(ke, cfg, dt, vp),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.scan_layers:
        params["layers"] = jax.vmap(lambda k: block_init(k, cfg))(layer_keys)
    else:
        params["layers"] = [block_init(k, cfg) for k in layer_keys]
    return params


def apply(params: Params, tokens: jax.Array, cfg: ModelConfig,
          *, remat: bool = False) -> tuple[jax.Array, jax.Array]:
    x = constrain(embed_apply(params["embed"], tokens), "act_btd")
    f = block_apply
    if remat:
        f = jax.checkpoint(f, static_argnums=(2,))
    if cfg.scan_layers:
        def body(h, lp):
            return f(lp, h, cfg), None
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for lp in params["layers"]:
            x = f(lp, x, cfg)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return constrain(unembed_apply(params["embed"], x), "logits"), jnp.zeros((), jnp.float32)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig,
            *, remat: bool = True) -> tuple[jax.Array, dict]:
    logits, _ = apply(params, batch["tokens"], cfg, remat=remat)
    ce = cross_entropy(logits, batch["targets"], batch["mask"], cfg.vocab_size)
    return ce, {"ce": ce}


def init_cache(cfg: ModelConfig, batch: int, max_len: int = 0) -> Any:
    """max_len unused: SSM decode state is O(1)."""
    dt = jnp.dtype(cfg.dtype)
    di, g, n, h, conv_dim = _dims(cfg)
    def one():
        return {
            "conv_x": jnp.zeros((batch, cfg.ssm_conv_width - 1, di), dt),
            "conv_BC": jnp.zeros((batch, cfg.ssm_conv_width - 1, 2 * g * n), dt),
            "ssm": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
        }
    if cfg.scan_layers:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one()
        )
    return [one() for _ in range(cfg.num_layers)]


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            max_len: int = 0) -> tuple[jax.Array, Any]:
    x = embed_apply(params["embed"], tokens)
    if cfg.scan_layers:
        def body(h, lp):
            h, cache = block_prefill(lp, h, cfg)
            return h, cache
        x, caches = jax.lax.scan(body, x, params["layers"])
    else:
        caches = []
        for lp in params["layers"]:
            x, c = block_prefill(lp, x, cfg)
            caches.append(c)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x[:, -1]), caches


def decode_step(params: Params, token: jax.Array, cfg: ModelConfig,
                caches: Any) -> tuple[jax.Array, Any]:
    x = embed_apply(params["embed"], token[:, None])
    if cfg.scan_layers:
        def body(h, xs):
            lp, cache = xs
            h, cache = block_decode(lp, h, cfg, cache)
            return h, cache
        x, caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
        new = []
        for lp, cache in zip(params["layers"], caches):
            x, c = block_decode(lp, x, cfg, cache)
            new.append(c)
        caches = new
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return unembed_apply(params["embed"], x[:, 0]), caches
