"""Operations and bytes of the granite-4.0-h-small period, from shapes.

The numerators of ``ssd_decode_roofline`` and ``mfu.serve_hybrid``: what
the algorithm needs, not what a kernel happens to do.  ``m`` is the
configuration's ``model`` dict.  Kept with the benchmark, beside
``flops.py``, so that no change to the program can change the yardstick.
"""
from __future__ import annotations


def _mamba_dims(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    heads = di // m["ssm_head_dim"]
    return di, heads, m["ssm_groups"] * m["ssm_state"]


def ssd_decode_cost(active: int, m: dict) -> tuple[float, float]:
    """One decode step's SSM state update in every Mamba layer for
    ``active`` slots: per slot and head, ``S' = exp(dt A) S + dt x B^T`` (a
    multiply, a multiply and an add per state element) and ``y = S' C`` (a
    multiply and an add); the f32 state read and written once, x (bf16) and
    dt (f32) per head and B, C (bf16) per group read, y (f32) written."""
    _, heads, gn = _mamba_dims(m)
    p, n = m["ssm_head_dim"], m["ssm_state"]
    layers = m["layer_types"].count("mamba")
    state = heads * p * n
    flops = 5.0 * state
    nbytes = (2 * state * 4 + heads * p * 2 + heads * 4 + 2 * gn * 2
              + heads * p * 4)
    return layers * active * flops, float(layers * active * nbytes)


def hybrid_token_flops(m: dict, context: int) -> float:
    """Forward operations of one token that attends over ``context``
    positions (its own included): each Mamba layer's projections, conv and
    state update; each attention layer's q, k, v, o projections and the
    attention itself; in every layer the router over all experts, the held
    experts' share of the top-k (``experts_per_token * experts_held /
    num_experts`` SwiGLUs a token on average) and the shared expert; and
    the tied unembedding over the vocabulary."""
    d, f = m["d_model"], m["d_ff"]
    di, heads, gn = _mamba_dims(m)
    held = m["experts_held"] or m["num_experts"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    mamba = (2.0 * d * (2 * di + 2 * gn + heads) + 2.0 * di * d
             + 2.0 * m["ssm_conv_width"] * (di + 2 * gn)
             + 5.0 * heads * m["ssm_head_dim"] * m["ssm_state"])
    attn = (2.0 * d * (h * hd + 2 * kv * hd) + 2.0 * h * hd * d
            + 4.0 * h * hd * context)
    ffn = (2.0 * d * m["num_experts"]
           + m["experts_per_token"] * held / m["num_experts"] * 6.0 * d * f
           + 6.0 * d * m["shared_expert_ff"])
    kinds = m["layer_types"]
    return (kinds.count("mamba") * mamba + kinds.count("attention") * attn
            + len(kinds) * ffn + 2.0 * d * m["vocab_size"])
