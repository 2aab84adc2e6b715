"""Pure-jnp oracles for the Mamba2 SSD (state-space duality) scan.

Per head ``h`` with state ``S in R^{P x N}`` (P = head dim, N = state dim):

    a_t = exp(dt_t * A_h)                       (scalar decay, A_h < 0)
    S_t = a_t * S_{t-1} + dt_t * x_t (x) B_t     (outer product update)
    y_t = S_t @ C_t  (+ D_h * x_t skip)

``ssd_ref`` is the sequential-scan oracle; ``ssd_chunked`` is the chunked
(SSD) algorithm — quadratic within a chunk, linear across chunks — which is
what the Pallas kernel implements and what the model code lowers on non-TPU
backends.  ``ssd_decode_step`` is the O(1) single-token state update used by
``serve_step``; ``ssd_decode_ref`` is the same update on the serving arena's
state layout (``ssd_decode.py``) for the active slots only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_ref(
    x: jax.Array,  # (b, l, h, p)
    dt: jax.Array,  # (b, l, h) — positive (post-softplus)
    A: jax.Array,  # (h,) — negative
    B: jax.Array,  # (b, l, g, n)
    C: jax.Array,  # (b, l, g, n)
    *,
    init_state: jax.Array | None = None,  # (b, h, p, n)
) -> tuple[jax.Array, jax.Array]:
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)  # (b, l, h, n)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    S0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def step(S, inp):
        xt, dtt, Bt, Ct = inp  # (b,h,p), (b,h), (b,h,n), (b,h,n)
        a = jnp.exp(dtt * A[None])  # (b, h)
        S = a[..., None, None] * S + (dtt[..., None] * xt)[..., None] * Bt[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", S, Ct)
        return S, y

    xs = (
        xf.transpose(1, 0, 2, 3),
        dtf.transpose(1, 0, 2),
        Bh.transpose(1, 0, 2, 3),
        Ch.transpose(1, 0, 2, 3),
    )
    S_fin, ys = jax.lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), S_fin.astype(jnp.float32)


def ssd_chunked(
    x: jax.Array,  # (b, l, h, p)
    dt: jax.Array,  # (b, l, h)
    A: jax.Array,  # (h,)
    B: jax.Array,  # (b, l, g, n)
    C: jax.Array,  # (b, l, g, n)
    *,
    chunk: int = 64,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD: O(L/Q) sequential steps of O(Q^2) intra-chunk work.

    Wrapped in the ``pallas_kernel_region`` scope: the TPU target runs this as
    the ssd_scan Pallas kernel (state + decay matrices VMEM-resident).
    """
    with jax.named_scope("pallas_kernel_region"):
        return _ssd_chunked_impl(x, dt, A, B, C, chunk=chunk,
                                 init_state=init_state)


def _ssd_chunked_impl(x, dt, A, B, C, *, chunk, init_state):
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert l % chunk == 0, "length must be a multiple of the chunk size"
    nc, q = l // chunk, chunk
    xf = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, q, h)
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32).reshape(b, nc, q, h, n)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32).reshape(b, nc, q, h, n)
    S0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def chunk_step(S, inp):
        xc, dtc, Bc, Cc = inp  # (b,q,h,p), (b,q,h), (b,q,h,n), (b,q,h,n)
        alog = dtc * A[None, None]  # (b, q, h) — log decay per step
        L = jnp.cumsum(alog, axis=1)  # inclusive cumsum
        # Intra-chunk: M[t,s] = (C_t . B_s) exp(L_t - L_s) dt_s  for s <= t.
        CB = jnp.einsum("bqhn,bshn->bhqs", Cc, Bc)
        decay = jnp.exp(L.transpose(0, 2, 1)[:, :, :, None]
                        - L.transpose(0, 2, 1)[:, :, None, :])
        causal = jnp.tril(jnp.ones((q, q), bool))
        M = jnp.where(causal[None, None], CB * decay, 0.0)
        M = M * dtc.transpose(0, 2, 1)[:, :, None, :]
        y = jnp.einsum("bhqs,bshp->bqhp", M, xc)
        # Inter-chunk: y_t += exp(L_t) * (S0 @ C_t).
        y += jnp.exp(L)[..., None] * jnp.einsum("bhpn,bqhn->bqhp", S, Cc)
        # State update: S' = exp(L_Q) S + sum_s exp(L_Q - L_s) dt_s x_s (x) B_s.
        Lq = L[:, -1]  # (b, h)
        w = jnp.exp(Lq[:, None] - L) * dtc  # (b, q, h)
        S_new = jnp.exp(Lq)[..., None, None] * S + jnp.einsum(
            "bqhp,bqhn->bhpn", w[..., None] * xc, Bc
        )
        return S_new, y

    xs = (
        xf.transpose(1, 0, 2, 3, 4),
        dtf.transpose(1, 0, 2, 3),
        Bh.transpose(1, 0, 2, 3, 4),
        Ch.transpose(1, 0, 2, 3, 4),
    )
    S_fin, ys = jax.lax.scan(chunk_step, S0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, l, h, p)
    return y.astype(x.dtype), S_fin


def ssd_decode_step(
    x: jax.Array,  # (b, h, p)
    dt: jax.Array,  # (b, h)
    A: jax.Array,  # (h,)
    B: jax.Array,  # (b, g, n)
    C: jax.Array,  # (b, g, n)
    state: jax.Array,  # (b, h, p, n)
) -> tuple[jax.Array, jax.Array]:
    """Single-token SSD update (serving decode path)."""
    h = x.shape[1]
    rep = h // B.shape[1]
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    a = jnp.exp(dt.astype(jnp.float32) * A[None])
    state = a[..., None, None] * state + (
        (dt.astype(jnp.float32)[..., None] * x.astype(jnp.float32))[..., None]
        * Bh[..., None, :]
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state


def ssd_decode_ref(
    x: jax.Array,  # (b, h, p)
    dt: jax.Array,  # (b, h)
    A: jax.Array,  # (h,)
    B: jax.Array,  # (b, g, n)
    C: jax.Array,  # (b, g, n)
    state: jax.Array,  # (b, h/f, n, f*p) f32: ssd_decode.to_decode_layout
    active: jax.Array,  # (b,) bool
) -> tuple[jax.Array, jax.Array]:
    """Single-token update of the active slots' folded state; the others'
    state is returned unchanged and their y is 0.  Returns (y (b, h, p) f32,
    state)."""
    b, h, p = x.shape
    g = B.shape[1]
    hf, n, fp = state.shape[1:]
    f = fp // p
    dt = dt.astype(jnp.float32)
    u = (dt[..., None] * x.astype(jnp.float32)).reshape(b, hf, fp)
    a = jnp.repeat(jnp.exp(dt * A[None]), p, axis=1).reshape(b, hf, fp)
    grp = jnp.arange(hf) * f // (h // g)
    Bp = B.astype(jnp.float32)[:, grp]  # (b, hf, n)
    Cp = C.astype(jnp.float32)[:, grp]
    s = a[:, :, None, :] * state + Bp[..., None] * u[:, :, None, :]
    y = jnp.sum(s * Cp[..., None], axis=2)
    y = jnp.where(active[:, None, None], y, 0.0)
    s = jnp.where(active[:, None, None, None], s, state)
    return y.reshape(b, h, p), s
