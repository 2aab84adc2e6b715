"""Operations and bytes that the benchmark's work requires, from shapes.

These are the numerators of the roofline shares and of ``mfu``: what the
algorithm needs, not what a kernel happens to do (padding, recomputed or
masked-out work does not count).  Kept with the benchmark so that no change
to the program can change the yardstick.
"""
from __future__ import annotations


def fed_reduce_cost(rows: int, size: int, itemsize: int, *,
                    scaled: bool = False) -> tuple[float, float]:
    """One weighted row-sum ``sum_i w_i x_i`` over a ``(rows, size)`` stack:
    a multiply and an add per element; the stack, the f32 weights (and the
    f32 scale column of an int8 stack) read once, the f32 sum written."""
    flops = 2.0 * rows * size
    nbytes = rows * size * itemsize + rows * 4 + size * 4
    if scaled:
        nbytes += rows * 4
    return flops, float(nbytes)


def decode_attention_cost(lengths, heads: int, kv_heads: int, head_dim: int,
                          itemsize: int) -> tuple[float, float]:
    """One decode-attention call: each slot's query against its own ``L``
    cached keys and values.  ``q @ K^T`` and ``p @ V`` are ``2 * L * d``
    each per head; K and V are read up to each slot's length, q read and o
    written once.  Slots of length 0 need nothing."""
    flops = nbytes = 0.0
    for n in lengths:
        if n <= 0:
            continue
        flops += 4.0 * heads * n * head_dim
        nbytes += 2.0 * n * kv_heads * head_dim * itemsize
        nbytes += 2.0 * heads * head_dim * itemsize
    return flops, nbytes


def lr_round_flops(devices: int, records: int, dim: int,
                   epochs: int) -> float:
    """One federated round of the CTR model: every device runs ``epochs``
    full-batch steps over its ``records`` (a ``dim``-wide dot for the logit
    and one for the gradient, two operations per element each), then the
    server's weighted row-sum over the ``dim + 1`` parameters."""
    train = devices * epochs * records * 4.0 * dim
    reduce = 2.0 * devices * (dim + 1)
    return train + reduce


def moe_token_flops(m: dict, context: int) -> float:
    """Forward operations of one token of a GQA + top-k MoE decoder that
    attends over ``context`` positions (its own included): q, k, v and o
    projections, the router, ``experts_per_token`` SwiGLU experts, the
    attention itself, and the unembedding over the published vocabulary."""
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    proj = 2.0 * d * (h * hd + 2 * kv * hd) + 2.0 * h * hd * d
    router = 2.0 * d * m["num_experts"]
    experts = m["experts_per_token"] * 3 * 2.0 * d * m["d_ff"]
    attn = 4.0 * h * hd * context
    return m["num_layers"] * (proj + router + experts + attn) \
        + 2.0 * d * m["vocab_size"]
