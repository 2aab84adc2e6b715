"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + no NaNs, plus prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import choose_mesh_plan, padded_vocab
from repro.configs.registry import get_config, lm_arch_ids
from repro.models.registry import get_model


def make_batch(cfg, b=2, s=32):
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "targets": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.frontend_tokens, cfg.d_model)) * 0.01,
            jnp.bfloat16)
    if cfg.family == "audio":
        batch["src_embeds"] = jnp.asarray(
            rng.standard_normal((b, s, cfg.d_model)) * 0.01, jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", lm_arch_ids())
def test_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        api.loss_fn, has_aux=True)(params, batch, cfg)
    assert np.isfinite(float(loss))
    # At random init, loss ~= ln(vocab).
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", lm_arch_ids())
def test_smoke_logits_shape(arch):
    cfg = get_config(arch, smoke=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    b, s = 2, 16
    batch = make_batch(cfg, b, s)
    if cfg.family == "audio":
        from repro.models import encdec
        mem = encdec.encode(params, batch["src_embeds"], cfg)
        logits = encdec.decode_train(params, batch["tokens"], mem, cfg)
        assert logits.shape == (b, s, padded_vocab(cfg.vocab_size))
    else:
        logits, _ = api.apply(params, batch["tokens"], cfg,
                              **({"prefix_embeds": batch["prefix_embeds"]}
                                 if cfg.family == "vlm" else {}))
        expect_s = s + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
        assert logits.shape == (b, expect_s, padded_vocab(cfg.vocab_size))
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ["phi3_medium_14b", "mamba2_1_3b",
                                  "zamba2_1_2b", "granite_moe_3b_a800m",
                                  "granite_4_0_h_small"])
def test_prefill_decode_matches_full_forward(arch):
    """Greedy continuation via prefill+decode equals full-sequence forward."""
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, attention_impl="einsum")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    b, s = 2, 16
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s + 1)), jnp.int32)
    # Full forward logits at position s-1 predict token s.
    logits_full, _ = api.apply(params, toks, cfg)
    want = logits_full[:, s - 1, : cfg.vocab_size]
    # Prefill on first s tokens -> same logits for the next token.
    out = api.prefill(params, toks[:, :s], cfg, s + 8)
    logits_pre = out[0][:, : cfg.vocab_size]
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(want), atol=0.1, rtol=0.1)
    # One decode step consumes token s and matches full forward at position s.
    logits_dec, _ = api.decode_step(params, toks[:, s], cfg, out[1])
    want2 = logits_full[:, s, : cfg.vocab_size]
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, : cfg.vocab_size]), np.asarray(want2),
        atol=0.1, rtol=0.1)


def test_mesh_plans_cover_all_archs():
    for arch in lm_arch_ids():
        cfg = get_config(arch)
        plan = choose_mesh_plan(cfg)
        assert plan.tp * plan.sp == 16
        if cfg.family != "ssm":
            assert cfg.num_heads % plan.tp == 0
            assert (cfg.num_kv_heads % plan.tp == 0
                    or plan.tp % cfg.num_kv_heads == 0)


def test_param_counts_match_targets():
    """Config param counts sit near the published sizes (backbone-only for
    vlm/audio — the stubbed frontends carry the remaining params)."""
    targets = {
        "phi3_medium_14b": (13e9, 16e9),
        "llama3_2_3b": (3.0e9, 4.2e9),
        "qwen2_7b": (7e9, 8.5e9),
        "nemotron_4_15b": (14e9, 17e9),
        "zamba2_1_2b": (1.0e9, 1.4e9),
        "mamba2_1_3b": (1.2e9, 1.6e9),
        "granite_moe_3b_a800m": (3.0e9, 3.8e9),
        "granite_4_0_h_small": (31e9, 33e9),  # "32B-A9B"
        "phi3_5_moe_42b_a6_6b": (40e9, 44e9),
    }
    for arch, (lo, hi) in targets.items():
        n = get_config(arch).num_params()
        assert lo <= n <= hi, (arch, n)


@pytest.mark.parametrize("arch,lo,hi", [
    ("phi3_5_moe_42b_a6_6b", 5e9, 9e9),  # "a6.6b"
    ("granite_4_0_h_small", 8e9, 10e9),  # "A9B"
])
def test_moe_active_params_fraction(arch, lo, hi):
    act = get_config(arch).active_params()
    assert lo <= act <= hi
