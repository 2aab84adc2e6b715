"""Public entry point for the Mamba2 SSD scan."""
from __future__ import annotations

import functools

import jax

from repro import kernels
from repro.kernels.ssd_scan.ref import ssd_chunked, ssd_decode_step, ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

__all__ = ["ssd_scan", "ssd_decode_step", "ssd_ref", "ssd_chunked"]


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
