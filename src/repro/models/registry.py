"""Family → (init, loss_fn, serving fns) dispatch."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

from repro.configs.base import ModelConfig
from repro.models import encdec, granite_hybrid, hybrid, mamba2, transformer


@dataclasses.dataclass(frozen=True)
class ModelApi:
    init: Callable
    loss_fn: Callable
    apply: Callable | None = None
    init_cache: Callable | None = None
    prefill: Callable | None = None
    decode_step: Callable | None = None
    # Serving arena (``core.serving``).  ``gqa_arena``: the engine's own
    # GQA attention + MLP/MoE block runs this family as it is.  Otherwise
    # the model brings slot steps over its own per-layer state, which the
    # engine donates to each step when ``donate_state`` is set.
    gqa_arena: bool = False
    init_state: Callable | None = None
    prefill_slots: Callable | None = None
    decode_slots: Callable | None = None
    donate_state: bool = False


def get_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family in ("dense", "moe", "vlm"):
        return ModelApi(
            init=transformer.init,
            loss_fn=transformer.loss_fn,
            apply=transformer.apply,
            init_cache=transformer.init_cache,
            prefill=transformer.prefill,
            decode_step=transformer.decode_step,
            gqa_arena=True,
        )
    if cfg.family == "ssm":
        return ModelApi(
            init=mamba2.init,
            loss_fn=mamba2.loss_fn,
            apply=mamba2.apply,
            init_cache=mamba2.init_cache,
            prefill=mamba2.prefill,
            decode_step=mamba2.decode_step,
        )
    if cfg.family == "hybrid":
        return ModelApi(
            init=hybrid.init,
            loss_fn=hybrid.loss_fn,
            apply=hybrid.apply,
            init_cache=hybrid.init_cache,
            prefill=hybrid.prefill,
            decode_step=hybrid.decode_step,
        )
    if cfg.family == "granite_hybrid":
        return ModelApi(
            init=granite_hybrid.init,
            loss_fn=granite_hybrid.loss_fn,
            apply=granite_hybrid.apply,
            init_cache=granite_hybrid.init_cache,
            prefill=granite_hybrid.prefill,
            decode_step=granite_hybrid.decode_step,
            init_state=granite_hybrid.init_state,
            prefill_slots=granite_hybrid.prefill_slots,
            decode_slots=granite_hybrid.decode_slots,
            donate_state=True,
        )
    if cfg.family == "audio":
        return ModelApi(
            init=encdec.init,
            loss_fn=encdec.loss_fn,
            apply=None,
            init_cache=encdec.init_cache,
            prefill=encdec.prefill,
            decode_step=encdec.decode_step,
        )
    raise ValueError(f"unknown family {cfg.family}")
