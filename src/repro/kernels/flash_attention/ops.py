"""Public entry point for flash attention.

Dispatch: the compiled Pallas kernel on TPU backends (``impl="pallas"``
raises elsewhere; ``"pallas_interpret"`` interprets it for validation), the
lowerable chunked-jnp implementation elsewhere (CPU dry-runs, grad
support).  The chunked implementation is the same online-softmax math, so
the two paths are interchangeable bit-for-tolerance (tests enforce this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_chunked, attention_ref


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "q_offset", "scale", "block_q", "block_k", "impl"
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    impl: str = "auto",  # auto | pallas | pallas_interpret | chunked | ref
) -> jax.Array:
    """Multi-head/GQA attention: q (b,sq,h,d), k/v (b,sk,kv,d) -> (b,sq,h,d)."""
    if impl == "auto":
        impl = "pallas" if kernels.on_tpu() else "chunked"
    if impl in ("pallas", "pallas_interpret"):
        return flash_attention_pallas(
            q, k, v, causal=causal, q_offset=q_offset, scale=scale,
            block_q=block_q, block_k=block_k,
            interpret=kernels.pallas_interpret(impl),
        )
    if impl == "chunked":
        # No q-chunking on the lowerable path: a python loop of static
        # q-slices over the sp-sharded sequence dim makes GSPMD emit a
        # collective-permute/all-to-all per slice (§Perf iteration 5:
        # 188 GB/step of cp+a2a on phi3-medium train_4k).  The kv-chunk scan
        # alone bounds the working set; on-chip q-blocking lives in the
        # Pallas kernel where it belongs.
        return attention_chunked(
            q, k, v, causal=causal, q_offset=q_offset, scale=scale,
            q_chunk=q.shape[1], kv_chunk=block_k * 8,
        )
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, q_offset=q_offset, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")
