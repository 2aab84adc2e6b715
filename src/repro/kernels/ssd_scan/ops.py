"""Public entry points for the Mamba2 SSD scan and the serving decode step."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.ssd_scan.ref import (
    ssd_chunked,
    ssd_decode_ref,
    ssd_decode_step,
    ssd_ref,
)
from repro.kernels.ssd_scan.ssd_decode import (
    from_decode_layout,
    ssd_decode_pallas,
    to_decode_layout,
)
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

__all__ = ["ssd_scan", "ssd_decode", "ssd_decode_step", "ssd_decode_ref",
           "ssd_ref", "ssd_chunked", "to_decode_layout",
           "from_decode_layout"]


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_scan(
    x: jax.Array,  # (b, l, h, p)
    dt: jax.Array,  # (b, l, h) positive
    A: jax.Array,  # (h,) negative
    B: jax.Array,  # (b, l, g, n)
    C: jax.Array,  # (b, l, g, n)
    *,
    chunk: int = 64,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (b,l,h,p), final_state (b,h,p,n))."""
    if impl == "auto":
        impl = "pallas" if kernels.on_tpu() else "chunked"
    l = x.shape[1]
    chunk = min(chunk, l)
    if l % chunk:
        # Pad to a chunk multiple with identity steps: dt=0 gives decay
        # exp(0)=1 and zero input contribution, so y/state are exact.
        pad = chunk - l % chunk
        padt = lambda a: jax.numpy.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        y, s = ssd_scan(padt(x), padt(dt), A, padt(B), padt(C),
                        chunk=chunk, impl=impl)
        return y[:, :l], s
    if impl in ("pallas", "pallas_interpret"):
        return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                               interpret=kernels.pallas_interpret(impl))
    if impl == "chunked":
        return ssd_chunked(x, dt, A, B, C, chunk=chunk)
    if impl == "ref":
        return ssd_ref(x, dt, A, B, C)
    raise ValueError(f"unknown impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("impl",))
def ssd_decode(
    x: jax.Array,  # (b, h, p)
    dt: jax.Array,  # (b, h) positive
    A: jax.Array,  # (h,) negative
    B: jax.Array,  # (b, g, n)
    C: jax.Array,  # (b, g, n)
    state: jax.Array,  # (b, h/f, n, f*p) f32: to_decode_layout
    active: jax.Array,  # (b,) bool
    *,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """One token's state update for the active slots of a serving arena;
    the state of the others is left as it is (by the kernel: not touched).
    Returns (y (b, h, p) f32, state)."""
    if impl == "auto":
        impl = "pallas" if kernels.on_tpu() else "ref"
    if impl == "ref":
        return ssd_decode_ref(x, dt, A, B, C, state, active)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    b, h, p = x.shape
    g = B.shape[1]
    hf, _, fp = state.shape[1:]
    dt = dt.astype(jnp.float32)
    u = (dt[..., None] * x.astype(jnp.float32)).reshape(b, hf, fp)
    a = jnp.repeat(jnp.exp(dt * A[None]), p, axis=1).reshape(b, hf, fp)
    y, state = ssd_decode_pallas(
        u, a, B.astype(jnp.float32)[..., None],
        C.astype(jnp.float32)[..., None], state, active,
        rep=(h // g) // (fp // p), interpret=kernels.pallas_interpret(impl))
    return y.reshape(b, h, p), state
