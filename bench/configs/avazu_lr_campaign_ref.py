"""Plain reference of the avazu-lr campaign, and the data both sides use.

Nothing here comes from the program under test.  ``make_data`` follows the
recipe of the program's synthetic Avazu generator (hashed multi-hot fields,
segment preferences, a sparse true logit vector), written anew in
``jax.numpy`` so that the 2 M records are made on the chip from the seed.
``round_rows`` is the federated round's local training written out as
plain masked-mean logistic-regression SGD, one block of devices at a time;
``fedavg`` is the sample-weighted mean of the trained rows.

Precision, as the configuration states it: the logical tier trains in f32
with every matmul at full precision, the device tier in bf16 (inputs and
parameters cast to bf16, logits back to f32 for the loss: the paper's
operator discrepancy between emulated and physical phones).  The control
lowers each by one step: the logical tier to ``high`` (three bf16 passes),
the device tier to fp8 (e4m3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16384  # devices per reference block


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one beyond 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@functools.partial(jax.jit, static_argnames=("devices", "records", "dim",
                                             "recipe"))
def _grade_data(key, *, devices: int, records: int, dim: int,
                recipe: tuple):
    r = dict(recipe)
    fields, n_seg = r["raw_fields"], r["segments"]
    n = devices * records
    ks = jax.random.split(key, 6)
    prefs = jax.random.randint(ks[0], (n_seg, fields), 0, r["pref_range"])
    seg = jax.random.randint(ks[1], (n,), 0, n_seg)
    raw = prefs[seg] + jax.random.randint(ks[2], (n, fields), 0,
                                          r["field_noise"])
    # The recipe's hash, (raw * 2654435761 + f * 97) mod dim, in uint32:
    # dim divides 2**32, so the wrap-around leaves the residue unchanged.
    raw = raw.astype(jnp.uint32)
    iota = jnp.arange(dim, dtype=jnp.uint32)
    feats = jnp.zeros((n, dim), jnp.float32)
    for f in range(fields):
        h = (raw[:, f] * jnp.uint32(2654435761) + jnp.uint32(f * 97)) \
            % jnp.uint32(dim)
        feats = feats + (h[:, None] == iota[None, :]).astype(jnp.float32)
    feats = feats / np.float32(np.sqrt(fields))
    w_true = (jax.random.normal(ks[3], (dim,)) * r["w_true_std"]
              * (jax.random.uniform(ks[4], (dim,)) < r["w_true_density"]))
    logits = jnp.dot(feats, w_true, precision="highest") + r["logit_offset"]
    labels = (jax.random.uniform(ks[5], (n,)) < jax.nn.sigmoid(logits))
    return {"x": feats.reshape(devices, records, dim),
            "y": labels.astype(jnp.float32).reshape(devices, records),
            "mask": jnp.ones((devices, records), jnp.float32)}


def make_data(seed: int, fleet: dict, records: int, dim: int,
              recipe: dict):
    """Per-grade client batches on the chip and per-device sample counts.
    Each grade draws its own segment preferences and true logit vector."""
    key = seed_key(seed)
    batches, counts = {}, {}
    for i, (grade, n) in enumerate(fleet.items()):
        data = _grade_data(jax.random.fold_in(key, i), devices=n,
                           records=records, dim=dim,
                           recipe=tuple(sorted(recipe.items())))
        batches[grade] = data
        counts[grade] = np.full(n, records, np.int64)
    return batches, counts


def _cast(x, dtype):
    if dtype == "float8_e4m3fn":
        # fp8 keeps a per-tensor scale so that the values use its range.
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    return x.astype(dtype)


def _bce(params, x, y, mask, precision):
    """Masked mean binary cross-entropy of a logistic model, in its stable
    form; the logits are taken to f32 before the loss."""
    logits = (jnp.dot(x, params["w"], precision=precision)
              + params["b"]).astype(jnp.float32)
    per = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@functools.partial(jax.jit, static_argnames=("lr", "epochs", "dtype",
                                             "precision"))
def local_train(w, b, x, y, mask, *, lr: float, epochs: int, dtype: str,
                precision: str):
    """``epochs`` full-batch SGD steps of ``_bce`` on each device of a
    block: ``x`` (n, records, dim), ``w`` (dim,), ``b`` ().  Inputs and
    parameters are held in ``dtype``, matmuls run at ``precision``.
    Returns the trained ``(b (n,), w (n, dim))`` as f32."""
    fp8 = dtype == "float8_e4m3fn"
    hold = (lambda a: _cast(a, dtype)) if fp8 else \
        (lambda a: a.astype(dtype))

    def one(x, y, mask):
        p = {"w": hold(w), "b": hold(b)}
        x, y, mask = hold(x), hold(y), hold(mask)

        def step(p, _):
            g = jax.grad(_bce)(p, x, y, mask, precision)
            p = jax.tree.map(lambda a, ga: a - lr * ga, p, g)
            return (jax.tree.map(hold, p) if fp8 else p), None

        p, _ = jax.lax.scan(step, p, None, length=epochs)
        return p["b"].astype(jnp.float32), p["w"].astype(jnp.float32)

    return jax.vmap(one)(x, y, mask)


def round_rows(prev: dict, batches: dict, split: list, *, lr: float,
               epochs: int, tiers: dict) -> np.ndarray:
    """Trained ``[b, w]`` rows of every device of one round, in global
    device order (grades in plan order).  ``split`` lists ``(grade,
    num_logical)``; a grade's first ``num_logical`` devices run as
    ``tiers["logical"]`` states, the rest as ``tiers["device"]``.  Each tier
    is ``{"dtype": ..., "precision": ...}``."""
    w0 = jnp.asarray(prev["w"], jnp.float32)
    b0 = jnp.asarray(prev["b"], jnp.float32)
    out = []
    for grade, n_logical in split:
        data = batches[grade]
        n = data["x"].shape[0]
        for lo in range(0, n, BLOCK):
            hi = min(lo + BLOCK, n)
            parts = [(lo, min(hi, n_logical), tiers["logical"]),
                     (max(lo, n_logical), hi, tiers["device"])]
            for a, z, tier in parts:
                if z <= a:
                    continue
                blk = {k: v[a:z] for k, v in data.items()}
                b, w = local_train(w0, b0, blk["x"], blk["y"], blk["mask"],
                                   lr=lr, epochs=epochs, dtype=tier["dtype"],
                                   precision=tier["precision"])
                out.append(np.concatenate(
                    [np.asarray(b, np.float64)[:, None],
                     np.asarray(w, np.float64)], axis=1))
    return np.concatenate(out)


def fedavg(rows: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """Sample-weighted mean of the rows, in float64."""
    w = np.asarray(weights, np.float64)
    return (w @ rows) / w.sum()
