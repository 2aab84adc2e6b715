"""Mixture-of-Experts MLP blocks.

``moe_apply`` (GShard/Switch-style capacity dispatch): routing is expressed
as dense one-hot dispatch/combine einsums over an ``(experts, capacity)``
buffer, so under GSPMD the token→expert shuffle lowers to a single pair of
all-to-alls on the ``ep``-sharded expert axis (no scatter/gather emulation,
no dynamic shapes).  Dropped tokens (over capacity) fall through the
residual connection, standard for capacity-factor routing.

``held_moe_apply`` is the expert-parallel shard of a dropless MoE: the
router spans all ``num_experts``, this chip holds ``experts_held`` of them
from ``expert_offset``, and it computes only their part of the output for
the tokens routed to them.  Its dispatch sorts the (token, expert) pairs by
held expert and runs each expert's rows as one group of a grouped matmul
(``jax.lax.ragged_dot``), so its memory grows with the routed pairs, not
with tokens x capacity.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import truncated_normal_init

Params = Any


def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    down_scale = 0.02 / (2 * cfg.num_layers) ** 0.5
    p = {
        "router": truncated_normal_init(ks[0], (D, E), jnp.float32),
        "w_down": truncated_normal_init(ks[3], (E, F, D), dtype, down_scale),
    }
    if cfg.mlp_activation == "swiglu":
        p["w_gate"] = truncated_normal_init(ks[1], (E, D, F), dtype)
        p["w_up"] = truncated_normal_init(ks[2], (E, D, F), dtype)
    else:
        p["w_up"] = truncated_normal_init(ks[2], (E, D, F), dtype)
    return p


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    cap = int(num_tokens * cfg.experts_per_token * cfg.capacity_factor
              / cfg.num_experts)
    return max(cap, cfg.experts_per_token)


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig
              ) -> tuple[jax.Array, jax.Array]:
    """Returns (output (b, s, d), aux_loss scalar)."""
    b, s, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = b * s
    C = _capacity(T, cfg)
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch): E * sum_e f_e * P_e.
    me = probs.mean(axis=0)
    one_hot_all = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, K, E)
    fe = one_hot_all.sum(axis=(0, 1)) / (T * K)
    aux_loss = E * jnp.sum(fe * me)

    # Capacity-based positions: rank of each (token, slot) within its expert.
    flat_expert = expert_idx.reshape(-1)  # (T*K,) in token-major order
    oh = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_expert = (jnp.cumsum(oh, axis=0) - 1) * oh  # (T*K, E)
    pos = pos_in_expert.max(axis=-1)  # (T*K,)
    keep = pos < C
    gates_flat = gate_vals.reshape(-1) * keep.astype(jnp.float32)

    # Dispatch/combine one-hots: (T, K, E, C) contracted immediately.
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=x.dtype)  # (T*K, C)
    disp = (oh.astype(x.dtype)[..., None] * pos_oh[:, None, :])  # (T*K, E, C)
    disp = disp.reshape(T, K, E, C)
    comb = disp.astype(jnp.float32) * gates_flat.reshape(T, K, 1, 1)

    # Expert inputs: (E, C, D) — the all-to-all boundary under GSPMD.
    ein = jnp.einsum("tkec,td->ecd", disp, xt)
    if cfg.mlp_activation == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", ein, p["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", ein, p["w_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.mlp_activation == "sq_relu":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", ein, p["w_up"])))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein, p["w_up"]))
    eout = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), p["w_down"])

    out = jnp.einsum("tkec,ecd->td", comb.astype(x.dtype), eout)
    return out.reshape(b, s, D), aux_loss


def held_moe_init(key, cfg: ModelConfig, dtype) -> Params:
    """A router over all ``num_experts`` and the held experts' SwiGLUs."""
    D, F, E, H = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    ks = jax.random.split(key, 4)
    return {
        "router": truncated_normal_init(ks[0], (D, E), jnp.float32),
        "w_gate": truncated_normal_init(ks[1], (H, D, F), dtype),
        "w_up": truncated_normal_init(ks[2], (H, D, F), dtype),
        "w_down": truncated_normal_init(
            ks[3], (H, F, D), dtype, 0.02 / (2 * cfg.num_layers) ** 0.5),
    }


def held_moe_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The held experts' part of a top-k MoE over x (b, s, d): the gates
    are the softmax of each token's top ``experts_per_token`` router logits
    over all experts; no token is dropped."""
    b, s, D = x.shape
    K, H, o = cfg.experts_per_token, cfg.held_experts, cfg.expert_offset
    T = b * s
    xt = x.reshape(T, D)
    top, idx = jax.lax.top_k(xt.astype(jnp.float32) @ p["router"], K)
    gates = jax.nn.softmax(top, axis=-1).reshape(-1)  # (T*K,) token-major
    local = (idx - o).reshape(-1)
    key = jnp.where((local >= 0) & (local < H), local, H)
    # A token takes each expert once, so at most min(K, H) of its pairs are
    # held: the first T * min(K, H) pairs in held-expert order hold them all.
    order = jnp.argsort(key, stable=True)[: T * min(K, H)]
    held = key[order] < H
    sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
    tok = order // K
    rows = xt[tok]
    gate = jax.lax.ragged_dot(rows, p["w_gate"], sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(rows, p["w_up"], sizes,
                            preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jax.lax.ragged_dot(h, p["w_down"], sizes,
                             preferred_element_type=jnp.float32)
    out = jnp.where(held[:, None], out * gates[order][:, None], 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(out)
    return y.reshape(b, s, D).astype(x.dtype)
