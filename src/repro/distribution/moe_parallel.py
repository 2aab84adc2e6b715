"""Expert-parallel MoE via shard_map (explicit all-to-all).

Under GSPMD alone, GShard-style dispatch one-hots would be built at *global*
token count — ``(T_global, E, C_global)`` is astronomically large as an HLO
value.  The production formulation dispatches **per data-shard**: each
``(data, sp)`` cell routes its local tokens with a local capacity, and the
token↔expert shuffle is an explicit ``all_to_all`` over the ``tp`` (= expert
parallel) axis.  FSDP weight shards are all-gathered over ``data`` inside the
region (explicit ZeRO-3 gather).

Autodiff flows through shard_map/all_to_all, so the same code path serves
training.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distribution.sharding import LogicalMesh


def _local_moe(xl, router, wg, wu, wd, cfg: ModelConfig, ep_axis: str | None,
               fsdp_axis: str | None, avg_axes: tuple = ()):
    b, s, D = xl.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = b * s
    C = max(int(T * K * cfg.capacity_factor / E), K)
    xt = xl.reshape(T, D)

    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)

    logits = xt.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    oh_all = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    fe = oh_all.sum(axis=(0, 1)) / (T * K)
    aux = E * jnp.sum(fe * me)
    if avg_axes:
        # Each (data, sp) cell routed different tokens: average the balance
        # loss across them so the out_spec's "replicated" claim holds.
        aux = jax.lax.pmean(aux, avg_axes)

    flat_e = expert_idx.reshape(-1)  # (T*K,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = ((jnp.cumsum(oh, axis=0) - 1) * oh).max(axis=-1)
    keep = pos < C
    gates_flat = gate_vals.reshape(-1) * keep.astype(jnp.float32)

    # Scatter-based dispatch: O(T*K*D) work and O(E*C*D) memory — the GShard
    # dispatch-einsum (kept as the reference formulation in models/moe.py)
    # materializes an O(T*E*C) one-hot, which explodes at prefill token
    # counts (measured: 89 GB/dev on granite prefill_32k — §Perf).
    pos_c = jnp.where(keep, pos, C)  # row C = overflow slot, dropped below
    ein = jnp.zeros((E, C + 1, D), xl.dtype)
    ein = ein.at[flat_e, pos_c].add(
        jnp.repeat(xt, K, axis=0), mode="drop")
    ein = ein[:, :C]  # (E, C, D) local tokens
    if ep_axis is not None:
        # (E, C, D) -> (E/ep, C*ep, D): experts scatter, token-slots gather.
        ein = jax.lax.all_to_all(ein, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
    if cfg.mlp_activation == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", ein, wg)
        up = jnp.einsum("ecd,edf->ecf", ein, wu)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(xl.dtype) * up
    elif cfg.mlp_activation == "sq_relu":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", ein, wu)))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein, wu))
    eout = jnp.einsum("ecf,efd->ecd", h.astype(xl.dtype), wd)
    if ep_axis is not None:
        eout = jax.lax.all_to_all(eout, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)
    # Combine: gather each (token, slot)'s expert output, weight, sum over K.
    gathered = eout[flat_e, jnp.minimum(pos_c, C - 1)]  # overflow rows read
    gathered = gathered * gates_flat[:, None].astype(xl.dtype)  # junk, but are zero-gated
    y = gathered.reshape(T, K, D).sum(axis=1)
    return y.reshape(b, s, D), aux


def make_moe_sharded(cfg: ModelConfig, lmesh: LogicalMesh, *, train: bool,
                     seq_sharded: bool = True, batch_shardable: bool = True):
    """Returns a drop-in replacement for ``models.moe.moe_apply``.

    ``seq_sharded=False`` for decode (seq=1 cannot shard over sp);
    ``batch_shardable=False`` when global_batch < the dp axis size.
    """
    plan = lmesh.plan
    mesh = lmesh.mesh
    dp = lmesh.dp if batch_shardable else None
    ep_axis = "tp" if plan.tp > 1 else None
    fsdp_axis = "data" if (train and plan.fsdp) else None
    if ep_axis is not None and cfg.num_experts % plan.tp != 0:
        raise ValueError(
            f"{cfg.name}: experts {cfg.num_experts} not divisible by tp={plan.tp}"
        )

    # Tokens must be sharded over EVERY axis participating in expert
    # parallelism: with x replicated over tp, all tp ranks route identical
    # tokens and the all-to-all ships tp duplicate slot sets — measured 8x
    # (granite) / 16x (phi3.5) expert-FLOP waste (§Perf iteration 2).  The
    # sequence therefore shards over (sp, tp) for dispatch; decode (seq=1)
    # keeps tp replication (its MoE compute is negligible).
    seq_axes = []
    if plan.sp > 1 and seq_sharded:
        seq_axes.append("sp")
    if plan.tp > 1 and seq_sharded:
        seq_axes.append("tp")
    sp = tuple(seq_axes) if seq_axes else None

    x_spec = P(dp, sp, None)
    router_spec = P(None, None)
    wgu_spec = P(ep_axis, fsdp_axis, None)
    wd_spec = P(ep_axis, None, fsdp_axis)

    avg_axes = tuple(a for a in (dp if isinstance(dp, tuple) else (dp,))
                     if a) + (sp if isinstance(sp, tuple) else
                              ((sp,) if sp else ()))
    fn = functools.partial(_local_moe, cfg=cfg, ep_axis=ep_axis,
                           fsdp_axis=fsdp_axis, avg_axes=avg_axes)
    smapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(x_spec, router_spec, wgu_spec, wgu_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )

    def moe_apply_sharded(p: Any, x: jax.Array, cfg_: ModelConfig):
        wg = p.get("w_gate", p["w_up"])
        y, aux = smapped(x, p["router"], wg, p["w_up"], p["w_down"])
        return y, aux

    return moe_apply_sharded
