"""Plain reference of the served granite-4.0-h-small period, and its weights.

Nothing here comes from the program under test.  ``init_weights`` makes
random bf16 weights from the seed on the chip, one layer at a time, in the
parameter layout the serving engine takes: a tied token embedding, a final
RMSNorm weight, and per layer two RMSNorm weights, the mixer (Mamba2: the
projections ``in_z in_x in_BC in_dt``, the depthwise conv weights and
biases of x and of B, C, ``A_log``, ``dt_bias``, ``D_skip``, the gated
norm's weight and ``out_proj``; attention: ``wq wk wv wo``), an f32 router
over all ``num_experts`` with the SwiGLU weights of the ``experts_held``
experts from ``expert_offset``, and the shared SwiGLU expert.  ``A_log`` is
``log(1..heads)`` and ``dt_bias`` the inverse softplus of a log-uniform draw
in [1e-3, 0.1], as Mamba2 initialises them.

``logits`` is the forward pass over one whole sequence in f32 at full
matmul precision, one layer at a time, from the GraniteMoeHybrid
equations::

    h0 = embedding_multiplier * E[tok]
    u  = h + r * mixer(rmsnorm(h))                 r = residual_multiplier
    h  = u + r * (moe(v) + shared(v)),  v = rmsnorm(u)
    logits = rmsnorm(h) E^T / logits_scaling

Mamba2: ``z, x, B, C, dt`` projected from the normed input; ``x, B, C``
through a causal depthwise conv of width ``ssm_conv_width`` with bias and a
SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then the
recurrence, run as a sequential scan over time (no chunks):
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
out = ``out_proj(rmsnorm(y * silu(z)) * norm_w)``.  Attention: causal GQA,
scores ``q k^T * attention_multiplier``, no rotary embedding.  The MoE:
the router's top ``experts_per_token`` logits over all experts, their
softmax as gates; every held expert's SwiGLU is computed for every token
and weighted by its gate (0 where the token did not choose it); experts not
held add nothing, as on the chip that holds this share.

``mode="fp8"`` is the control: every matmul's operands are rounded to fp8
(e4m3) first, each row of an activation and each output column of a weight
with its own scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one beyond 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _spec(m: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def _dims(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    heads = di // m["ssm_head_dim"]
    gn = m["ssm_groups"] * m["ssm_state"]
    return di, heads, gn


@functools.partial(jax.jit, static_argnames=("spec", "kind", "std"))
def _init_layer(key, *, spec: tuple, kind: str, std: float):
    m = dict(spec)
    d, f, fs = m["d_model"], m["d_ff"], m["shared_expert_ff"]
    held = m["experts_held"] or m["num_experts"]
    dt = jnp.dtype(m["dtype"])
    small = std / (2 * m["num_layers"]) ** 0.5  # output projections

    def normal(k, shape, scale=std, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    ks = jax.random.split(key, 16)
    layer = {
        "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
        "moe": {"router": normal(ks[0], (d, m["num_experts"]),
                                 dtype=jnp.float32),
                "w_gate": normal(ks[1], (held, d, f)),
                "w_up": normal(ks[2], (held, d, f)),
                "w_down": normal(ks[3], (held, f, d), small)},
        "shared": {"w_gate": normal(ks[4], (d, fs)),
                   "w_up": normal(ks[5], (d, fs)),
                   "w_down": normal(ks[6], (fs, d), small)},
    }
    if kind == "attention":
        h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        layer["attn"] = {"wq": normal(ks[7], (d, h * hd)),
                         "wk": normal(ks[8], (d, kv * hd)),
                         "wv": normal(ks[9], (d, kv * hd)),
                         "wo": normal(ks[10], (h * hd, d), small)}
        return layer
    di, heads, gn = _dims(m)
    w = m["ssm_conv_width"]
    u = jax.random.uniform(ks[11], (heads,), jnp.float32)
    dt0 = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    layer["mamba"] = {
        "in_z": normal(ks[7], (d, di)), "in_x": normal(ks[8], (d, di)),
        "in_BC": normal(ks[9], (d, 2 * gn)),
        "in_dt": normal(ks[10], (d, heads)),
        "conv_x_w": normal(ks[12], (w, di), 0.5 / w),
        "conv_x_b": normal(ks[13], (di,)),
        "conv_BC_w": normal(ks[14], (w, 2 * gn), 0.5 / w),
        "conv_BC_b": normal(ks[15], (2 * gn,)),
        "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),  # inverse softplus
        "D_skip": jnp.ones((heads,), jnp.float32),
        "norm_w": jnp.ones((di,), dt),
        "out_proj": normal(jax.random.fold_in(key, 1), (di, d), small),
    }
    return layer


@functools.partial(jax.jit, static_argnames=("spec", "std"))
def _init_embed(key, *, spec: tuple, std: float):
    m = dict(spec)
    dt = jnp.dtype(m["dtype"])
    e = jax.random.normal(key, (m["vocab_size"], m["d_model"]), jnp.float32)
    e = e * 2 * std / m["d_model"] ** 0.5
    return {"embed": {"embedding": e.astype(dt)},
            "ln_f": jnp.ones((m["d_model"],), dt)}


def init_weights(m: dict, seed: int, *, std: float):
    """The served weights, made on the chip from the seed: normal with
    standard deviation ``std`` (output projections ``std / sqrt(2 L)``,
    conv weights ``0.5 / width``), the embedding ``2 std / sqrt(d_model)``.

    The embedding is tied to the output and multiplied by 12 on the way
    in, so a token's own logit stands ``12 sqrt(d) sigma / rms(L)`` standard
    deviations above the others, where ``L`` is what the layers add to the
    residual stream (``rms(L)`` about ``20 std`` at the published widths).
    Drawn with ``sigma = std`` that is about 40, and greedy decoding would
    only repeat the last token; at ``2 std / sqrt(d)`` it is about 1, and
    the first layer's RMSNorm still sees its input well above ``norm_eps``.
    """
    key, spec = seed_key(seed), _spec(m)
    params = _init_embed(jax.random.fold_in(key, 0), spec=spec, std=std)
    params["layers"] = [
        _init_layer(jax.random.fold_in(key, i + 1), spec=spec, kind=kind,
                    std=std)
        for i, kind in enumerate(m["layer_types"])]
    return params


def _fp8(x, axis):
    """Round to fp8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a, b, fp8: bool, a_axis: int, b_axis: int):
    """f32 einsum at full precision; the control first rounds ``a`` along
    ``a_axis`` and ``b`` along ``b_axis`` (their contracted axes) to fp8."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _conv(x, w, b):
    """Causal depthwise conv over time: x (s, c), w (width, c), b (c)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i: i + x.shape[0]] * w[i].astype(jnp.float32)
              for i in range(width))
    return jax.nn.silu(out + b.astype(jnp.float32))


def _mamba(p, hn, m, fp8, bf16_state):
    s = hn.shape[0]
    di, heads, gn = _dims(m)
    pd, n, g = m["ssm_head_dim"], m["ssm_state"], m["ssm_groups"]
    z = _mm("sd,dk->sk", hn, p["in_z"], fp8, 1, 0)
    xs = _conv(_mm("sd,dk->sk", hn, p["in_x"], fp8, 1, 0),
               p["conv_x_w"], p["conv_x_b"])
    bc = _conv(_mm("sd,dk->sk", hn, p["in_BC"], fp8, 1, 0),
               p["conv_BC_w"], p["conv_BC_b"])
    dt = jax.nn.softplus(_mm("sd,dk->sk", hn, p["in_dt"], fp8, 1, 0)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(s, heads, pd)
    grp = jnp.arange(heads) * g // heads
    B = bc[:, :gn].reshape(s, g, n)[:, grp]  # (s, heads, n)
    C = bc[:, gn:].reshape(s, g, n)[:, grp]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if bf16_state:  # a round trip through astype may be elided
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hpn,hn->hp", S, C_t, precision=HIGHEST)

    S, y = jax.lax.scan(step, jnp.zeros((heads, pd, n), jnp.float32),
                        (xh, dt, B, C))
    y = (y + p["D_skip"][None, :, None] * xh).reshape(s, di)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm_w"], m["norm_eps"])
    return _mm("sk,kd->sd", y, p["out_proj"], fp8, 1, 0), S


def _attention(p, hn, m, fp8):
    s = hn.shape[0]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _mm("sd,dk->sk", hn, p["wq"], fp8, 1, 0).reshape(s, kv, h // kv, hd)
    k = _mm("sd,dk->sk", hn, p["wk"], fp8, 1, 0).reshape(s, kv, hd)
    v = _mm("sd,dk->sk", hn, p["wv"], fp8, 1, 0).reshape(s, kv, hd)
    scale = m["attention_multiplier"] or hd ** -0.5
    sc = _mm("qkgd,tkd->kgqt", q * scale, k, fp8, 3, 2)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm("kgqt,tkd->qkgd", pr, v, fp8, 3, 0).reshape(s, h * hd)
    return _mm("sk,kd->sd", o, p["wo"], fp8, 1, 0)


def _swiglu(w, v, fp8, eq, w_axis):
    gate = _mm(eq, v, w["w_gate"], fp8, 1, w_axis)
    up = _mm(eq, v, w["w_up"], fp8, 1, w_axis)
    return jax.nn.silu(gate) * up


def _ffn(lp, v, m, fp8):
    s = v.shape[0]
    e = lp["moe"]
    k, o = m["experts_per_token"], m["expert_offset"]
    held = m["experts_held"] or m["num_experts"]
    top, idx = jax.lax.top_k(_mm("sd,de->se", v, e["router"], fp8, 1, 0), k)
    gates = jnp.zeros((s, m["num_experts"]), jnp.float32).at[
        jnp.arange(s)[:, None], idx].set(jax.nn.softmax(top, -1))
    act = _swiglu(e, v, fp8, "sd,edf->sef", 1)
    y = _mm("sef,efd->sed", act, e["w_down"], fp8, 2, 1)
    moe = jnp.einsum("se,sed->sd", gates[:, o: o + held], y, precision=HIGHEST)
    sh = lp["shared"]
    shared = _mm("sf,fd->sd", _swiglu(sh, v, fp8, "sd,df->sf", 0),
                 sh["w_down"], fp8, 1, 0)
    return moe + shared


@functools.partial(jax.jit,
                   static_argnames=("spec", "kind", "fp8", "bf16_state"))
def _layer(x, lp, *, spec: tuple, kind: str, fp8: bool, bf16_state: bool):
    """The layer's output and, for a Mamba layer, its final SSM state."""
    m = dict(spec)
    r, eps = m["residual_multiplier"], m["norm_eps"]
    hn = _rmsnorm(x, lp["ln1"], eps)
    if kind == "mamba":
        mix, S = _mamba(lp["mamba"], hn, m, fp8, bf16_state)
    else:
        mix, S = _attention(lp["attn"], hn, m, fp8), None
    x = x + r * mix
    return x + r * _ffn(lp, _rmsnorm(x, lp["ln2"], eps), m, fp8), S


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _head(x, params, *, spec: tuple, fp8: bool):
    m = dict(spec)
    x = _rmsnorm(x, params["ln_f"], m["norm_eps"])
    logits = _mm("sd,vd->sv", x, params["embed"]["embedding"], fp8, 1, 1)
    return logits / m["logits_scaling"]


def _forward(params, tokens, m, *, fp8: bool, bf16_state: bool):
    """The last layer's output (s, d_model) and each Mamba layer's final
    SSM state."""
    spec = _spec(m)
    x = jnp.take(params["embed"]["embedding"],
                 jnp.asarray(tokens, jnp.int32), axis=0)
    x = x.astype(jnp.float32) * m["embedding_multiplier"]
    states = []
    for lp, kind in zip(params["layers"], m["layer_types"]):
        x, S = _layer(x, lp, spec=spec, kind=kind, fp8=fp8,
                      bf16_state=bf16_state)
        if S is not None:
            states.append(S)
    return x, states


def logits(params, tokens: np.ndarray, m: dict, *,
           mode: str = "f32") -> jax.Array:
    """(s, vocab_size) f32 next-token logits at every position of one
    sequence."""
    fp8 = mode == "fp8"
    with jax.default_matmul_precision("highest"):
        x, _ = _forward(params, tokens, m, fp8=fp8, bf16_state=False)
        return _head(x, params, spec=_spec(m), fp8=fp8)


def ssm_states(params, tokens: np.ndarray, m: dict, *,
               mode: str = "f32") -> list[jax.Array]:
    """Each Mamba layer's SSM state (heads, ssm_head_dim, ssm_state) f32
    after the whole sequence.  ``mode="bf16"`` is the state's control: the
    state rounded to bf16 after every token, all else in f32."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, m, fp8=False,
                        bf16_state=mode == "bf16")[1]
