"""Faults planted under the timed path.

``correct`` has to come out false under each fault a cell can have.  The
benchmark's own runs never plant one; ``bench/tests/test_faults.py`` plants
each at a small size, and ``bench/calibrate.py --fault <name>`` reads a
fault's numbers at the cell's own size on the chip.

    with planted("fl.half_batch"):
        ...  # build the system, run its window

A fault is planted before the system is built (some are read when the
program compiles its step) and taken out, with JAX's caches cleared, after.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def fl_state_unchanged():
    """Every round's aggregation returns the global params it was given."""
    from repro.core import federation

    keep = jax.jit(lambda g, *_: g)
    return [(federation, "_APPLY_WEIGHTED_SUM", keep),
            (federation, "_APPLY_WEIGHTED_SUM_DONATED", keep)]


def fl_half_batch():
    """Each chunk's second half of rows weighs nothing, in its partial sum
    and in the total: the mean is taken over the rest."""
    from repro.core import federation

    real = federation.AggregationService._fire_chunk

    def half(self, key):
        w = self._chunks[key].weights
        w[len(w) // 2:] = 0.0
        return real(self, key)

    return [(federation.AggregationService, "_fire_chunk", half)]


def fl_answer_altered():
    """Local training adds 1e-3 to the first weight of every device."""
    from repro.models import ctr

    make = ctr.make_local_train_fn

    def altered(**kw):
        local = make(**kw)

        def train(params, batch, rng):
            p, m = local(params, batch, rng)
            return dict(p, w=p["w"].at[0].add(1e-3)), m
        return train

    return [(ctr, "make_local_train_fn", altered)]


def fl_exchange_left_out():
    """The fleet mesh's sum over shards is left out: each chip keeps its own
    partial sum."""
    return [(jax.lax, "psum", lambda x, axis_name, **kw: x)]


def _decode(change):
    from repro.core import serving

    real = serving.arena_decode

    def broken(params, tok, active, arena, cfg, **kw):
        return change(real, params, tok, active, arena, cfg, **kw)

    return [(serving, "arena_decode", broken)]


def serve_token_altered():
    """Every active slot's next token is the one after the chosen one."""
    def change(real, params, tok, active, arena, cfg, **kw):
        nxt, arena = real(params, tok, active, arena, cfg, **kw)
        return jnp.where(active, (nxt + 1) % cfg.vocab_size, nxt), arena
    return _decode(change)


def serve_state_unchanged():
    """A decode step returns the arena it was given."""
    def change(real, params, tok, active, arena, cfg, **kw):
        nxt, _ = real(params, tok, active, arena, cfg, **kw)
        return nxt, arena
    return _decode(change)


def serve_half_batch():
    """Decode runs the first half of the slots only; the others' lengths
    still advance."""
    def change(real, params, tok, active, arena, cfg, **kw):
        keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
        nxt, new = real(params, tok, active & keep, arena, cfg, **kw)
        return nxt, dict(new, lengths=arena["lengths"] + active)
    return _decode(change)


FAULTS = {
    "fl.state_unchanged": fl_state_unchanged,
    "fl.half_batch": fl_half_batch,
    "fl.answer_altered": fl_answer_altered,
    "fl.exchange_left_out": fl_exchange_left_out,
    "serve.token_altered": serve_token_altered,
    "serve.state_unchanged": serve_state_unchanged,
    "serve.half_batch": serve_half_batch,
}


@contextlib.contextmanager
def planted(name: str):
    patches = FAULTS[name]()
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    jax.clear_caches()
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
        jax.clear_caches()
