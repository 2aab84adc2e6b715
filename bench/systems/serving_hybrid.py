"""Continuous-batching serving of a model that keeps recurrent state in the
arena (Granite 4.0-H): the ``serving`` system, whose build, traffic and
logit-gap check it runs as they are, plus a check of the precision the
program holds its SSM state at.

``ssm_state_short_share``: after the window, one slot still serving is drawn
from the seed in each of ``check_requests`` equal groups of slots.  Every
Mamba layer's SSM state of those slots (each arena entry named ``ssm``, as
the program holds it, widened to f32) is read, and the number is the share
of its entries whose 8 lowest mantissa bits are all zero.  A state held in
f32 reads about 1/256; one held in bf16 or fp16, or in any format with 15 or
fewer mantissa bits, reads 1.  The configuration states an f32 state.

The logit gaps cannot see that precision: against the f32 reference, the
program's own bf16 activations (which the configuration states) move the
state by as much as rounding it to bf16 each token does, so neither the
gaps nor a norm of the state's difference from the reference's sets the
two apart (``PERF.md``).  Its control is the reference's own state for the
same slots' sequences, held in bf16 (``ssm_states(mode="bf16")``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from systems import serving

STATE = "ssm_state_short_share"


def short_share(states: list[np.ndarray]) -> float:
    """The share of entries whose f32 value has its 8 lowest mantissa bits
    zero (infinite for no entries)."""
    bits = [np.ascontiguousarray(s, np.float32).view(np.uint32)
            for s in states]
    total = sum(b.size for b in bits)
    short = sum(int(np.count_nonzero((b & 0xFF) == 0)) for b in bits)
    return short / total if total else float("inf")


class System(serving.System):
    def __init__(self, *args, seed: int, **kw):
        super().__init__(*args, seed=seed, **kw)
        self.rng_state = np.random.default_rng([seed, 3])

    def release(self) -> None:
        """Read the drawn live slots' SSM state before the engine goes."""
        eng = self.engine
        k, slots = self.traffic["check_requests"], self.traffic["slots"]
        n = min(k, slots)
        groups: dict[int, list] = {}
        for rec in eng.slot_owner:
            if rec is not None:
                groups.setdefault(rec.slot * n // slots, []).append(rec)
        self.state_recs = [g[self.rng_state.integers(len(g))]
                           for _, g in sorted(groups.items())]
        ids = jnp.asarray([r.slot for r in self.state_recs], jnp.int32)
        self.states = [
            np.asarray(leaf[ids], np.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(eng.arena)
            if getattr(path[-1], "key", None) == "ssm"]
        del eng  # the engine and its arena go in ``serving.release``
        super().release()  # fetches every token, the live slots' too

    def check(self) -> dict:
        limits = self.cfg["limits"]
        if not self.sample or not self.states:
            return {"finished_sampled": {"value": float("inf"),
                                         "limit": min(limits.values())}}
        got = {**self.summary(self.gaps()), STATE: short_share(self.states)}
        return {k: {"value": got[k], "limit": v} for k, v in limits.items()}

    def control(self) -> dict:
        """The fp8 reference's logit gaps, and the share of the reference's
        state held in bf16 for the drawn slots' sequences."""
        ref = [np.asarray(s) for rec in self.state_recs
               for s in self.ref.ssm_states(
                   self.params, np.concatenate([rec.prompt, rec.tokens[:-1]]),
                   self.model, mode="bf16")]
        return {**super().control(), STATE: short_share(ref)}
