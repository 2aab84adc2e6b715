"""Plain reference of the served granite-3.0-3b-a800m, and its weights.

Nothing here comes from the program under test.  ``init_weights`` makes
random bf16 weights from the seed on the chip, one layer at a time inside
one jitted call, in the parameter layout the serving engine takes: a
token embedding and a separate output head, and per layer (stacked on a
leading layer axis) two RMSNorm weights, GQA projections ``wq wk wv wo``
and a top-k MoE of SwiGLU experts with an f32 router.

``logits`` is the model's forward pass over one whole sequence in f32 at
full matmul precision, one layer at a time, written from the architecture's
equations: RMSNorm, rotary embeddings on the two halves of each head,
causal softmax attention where each group of query heads shares one key and
value head, a residual add, RMSNorm, a router softmax over all experts whose
top ``experts_per_token`` gates are renormalised to sum to one, every
expert's SwiGLU, the gated sum of the chosen ones (no capacity: no token is
dropped), a residual add, a final RMSNorm and the output head.

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


@functools.partial(jax.jit, static_argnames=("spec", "std"))
def _init(key, spec: tuple, std: float):
    m = dict(spec)
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    e, f, layers = m["num_experts"], m["d_ff"], m["num_layers"]
    dt = jnp.dtype(m["dtype"])
    vp = m["vocab_size"]
    small = std / (2 * layers) ** 0.5  # output projections, as in GPT-2

    def normal(k, shape, scale=std, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 8)
        return {
            "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
            "attn": {"wq": normal(ks[0], (d, h * hd)),
                     "wk": normal(ks[1], (d, kv * hd)),
                     "wv": normal(ks[2], (d, kv * hd)),
                     "wo": normal(ks[3], (h * hd, d), small)},
            "moe": {"router": normal(ks[4], (d, e), dtype=jnp.float32),
                    "w_gate": normal(ks[5], (e, d, f)),
                    "w_up": normal(ks[6], (e, d, f)),
                    "w_down": normal(ks[7], (e, f, d), small)},
        }

    ke, kh, kl = jax.random.split(key, 3)
    return {
        "embed": {"embedding": normal(ke, (vp, d)),
                  "lm_head": normal(kh, (d, vp))},
        "ln_f": jnp.ones((d,), dt),
        "layers": jax.lax.map(layer, jax.random.split(kl, layers)),
    }


def init_weights(m: dict, seed: int, *, std: float):
    """The served weights, made on the chip from the seed: normal with
    standard deviation ``std`` (output projections ``std / sqrt(2 L)``)."""
    return _init(seed_key(seed), tuple(sorted(m.items())), std)


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


def _rope(x, theta):
    """x (s, heads, hd) at positions 0..s-1; rotates the two halves."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _layer(x, layers, i, *, spec: tuple, fp8: bool):
    m = dict(spec)
    lp = jax.tree.map(lambda a: a[i], layers)
    s = x.shape[0]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    g = h // kv
    a = lp["attn"]
    hn = _rmsnorm(x, lp["ln1"], m["norm_eps"])
    q = _mm("sd,dk->sk", hn, a["wq"], fp8, 1, 0).reshape(s, h, hd)
    k = _mm("sd,dk->sk", hn, a["wk"], fp8, 1, 0).reshape(s, kv, hd)
    v = _mm("sd,dk->sk", hn, a["wv"], fp8, 1, 0).reshape(s, kv, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    qg = q.reshape(s, kv, g, hd) * hd ** -0.5
    sc = _mm("qkgd,tkd->kgqt", qg, k, fp8, 3, 2)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm("kgqt,tkd->qkgd", p, v, fp8, 3, 0).reshape(s, h * hd)
    x = x + _mm("sk,kd->sd", o, a["wo"], fp8, 1, 0)

    e = lp["moe"]
    hn = _rmsnorm(x, lp["ln2"], m["norm_eps"])
    probs = jax.nn.softmax(_mm("sd,de->se", hn, e["router"], fp8, 1, 0), -1)
    top, idx = jax.lax.top_k(probs, m["experts_per_token"])
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(top)
    gate = _mm("sd,edf->sef", hn, e["w_gate"], fp8, 1, 1)
    up = _mm("sd,edf->sef", hn, e["w_up"], fp8, 1, 1)
    act = jax.nn.silu(gate) * up
    y = _mm("sef,efd->sed", act, e["w_down"], fp8, 2, 1)
    return x + jnp.einsum("se,sed->sd", gates, y, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _head(x, params, *, spec: tuple, fp8: bool):
    m = dict(spec)
    x = _rmsnorm(x, params["ln_f"], m["norm_eps"])
    return _mm("sd,dv->sv", x, params["embed"]["lm_head"], fp8, 1, 0)


def logits(params, tokens: np.ndarray, m: dict, *,
           mode: str = "f32") -> jax.Array:
    """(s, vocab_size) f32 next-token logits at every position of one
    sequence."""
    spec, fp8 = tuple(sorted(m.items())), mode == "fp8"
    x = jnp.take(params["embed"]["embedding"],
                 jnp.asarray(tokens, jnp.int32), axis=0).astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), spec=spec, fp8=fp8)
    return _head(x, params, spec=spec, fp8=fp8)
