"""Granite 4.0-H served through the continuous-batching arena (CPU, small
widths): prefill and decode against the plain reference's full forward,
expert shares against the uncut layer, slot reuse and idle slots, and the
``ssd_decode`` kernel against its reference."""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.serving import (
    ContinuousBatchingEngine,
    arena_decode,
    arena_prefill,
    init_arena,
)
from repro.kernels.ssd_scan.ops import (
    from_decode_layout,
    ssd_decode,
    ssd_decode_step,
    to_decode_layout,
)
from repro.models import granite_hybrid, moe
from repro.models.layers import mlp_apply

ROOT = pathlib.Path(__file__).resolve().parents[1]
# The published config's kinds and scalars at widths a CPU test holds.
MODEL = dict(
    name="granite-4.0-h-test", family="granite_hybrid", num_layers=3,
    layer_types=["mamba", "attention", "mamba"], d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=512,
    mlp_activation="swiglu", num_experts=16, experts_per_token=4,
    experts_held=0, expert_offset=0, shared_expert_ff=48, ssm_state=16,
    ssm_head_dim=16, ssm_expand=2, ssm_groups=1, ssm_conv_width=4,
    ssm_chunk=16, ssm_impl="chunked", use_rope=False, rope_theta=10000.0,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=16.0, norm_eps=1e-5,
    tie_embeddings=True, scan_layers=False, attention_impl="auto",
    dtype="bfloat16")
# Weights as large for the width as the published width's:
# 0.02 * sqrt(4096 / 64).
STD = 0.16


def reference():
    path = ROOT / "bench" / "configs" / "granite_4_0_h_small_serve_ref.py"
    spec = importlib.util.spec_from_file_location("granite4h_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return reference()


# The model's slot steps and the arena functions, jitted as the engine does.
prefill_slots = jax.jit(granite_hybrid.prefill_slots, static_argnames="cfg")
decode_slots = jax.jit(granite_hybrid.decode_slots,
                       static_argnames=("cfg", "attn_impl"))
prefill = jax.jit(arena_prefill, static_argnames="cfg")
decode = jax.jit(arena_decode, static_argnames=("cfg", "attn_impl"))


def setup(ref, seed=3, **model):
    m = dict(MODEL, **model)
    return m, ModelConfig(**m), ref.init_weights(m, seed, std=STD)


def test_arena_prefill_and_decode_match_the_reference(ref):
    """Three prompts prefilled into slots 2, 0, 3 of four (one padding row),
    then five decode steps with slot 1 idle: every position's logits equal
    the reference's full forward over the prompt and the fed tokens."""
    m, cfg, params = setup(ref, experts_held=10, expert_offset=3,
                           ssm_decode_impl="pallas_interpret")
    slots, s, steps = 4, 12, 5
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, m["vocab_size"], (3, s)).astype(np.int32)
    arena = granite_hybrid.init_state(cfg, slots, s + steps + 1)
    rows = jnp.asarray(np.concatenate([prompts, np.zeros((1, s), np.int32)]))
    sids = jnp.asarray([2, 0, 3, slots], jnp.int32)
    logits, arena = prefill_slots(params, rows, sids, arena, cfg=cfg)
    got = {i: [np.asarray(logits[i])] for i in range(3)}
    fed = {i: [] for i in range(3)}
    tok = np.zeros(slots, np.int32)
    lengths = np.zeros(slots, np.int32)
    for i, slot in enumerate((2, 0, 3)):
        lengths[slot] = s
    active = jnp.asarray([True, False, True, True])
    for _ in range(steps):
        for i, slot in enumerate((2, 0, 3)):
            tok[slot] = rng.integers(0, m["vocab_size"])
            fed[i].append(tok[slot])
        logits, arena = decode_slots(
            params, jnp.asarray(tok), active, jnp.asarray(lengths), arena,
            cfg=cfg, attn_impl="pallas_interpret")
        lengths[np.asarray(active)] += 1
        for i, slot in enumerate((2, 0, 3)):
            got[i].append(np.asarray(logits[slot]))
    for i in range(3):
        seq = np.concatenate([prompts[i], fed[i]])
        want = np.asarray(ref.logits(params, seq, m))[s - 1:]
        have = np.stack(got[i])[:, : m["vocab_size"]]
        err = np.abs(have - want) / want.std()
        # bf16 weights and activations against the f32 reference: sound
        # prefills read a mean error of 0.3-0.6% of the logits' spread here
        # (at most 2.6% at a logit), the fp8 control 3.1-4.8% (12-20%).
        assert err.mean() < 0.015 and err.max() < 0.1, i


def test_engine_serves_the_reference_tokens(ref):
    """Seven requests through three slots (slots reused): every served
    token is the reference's first choice, or within bf16 rounding of it."""
    m, cfg, params = setup(ref, ssm_decode_impl="pallas_interpret")
    prompt_len, decode_tokens = 10, 6
    eng = ContinuousBatchingEngine(cfg, slots=3, prompt_len=prompt_len,
                                   decode_tokens=decode_tokens, params=params,
                                   attn_impl="pallas_interpret")
    rng = np.random.default_rng(1)
    for rid in range(7):
        eng.submit(rid, rng.integers(0, m["vocab_size"], prompt_len), 0.0)
    t = 0.0
    while eng.has_work:
        t += eng.step(t)
    gaps = []
    for rec in eng.report().records:
        assert len(rec.tokens) == decode_tokens + 1
        seq = np.concatenate([rec.prompt, rec.tokens[:-1]])
        want = np.asarray(ref.logits(params, seq, m))[prompt_len - 1:]
        gaps.append(want.max(-1) - want[np.arange(len(want)), rec.tokens])
    gaps = np.concatenate(gaps) / want.std()
    assert gaps.max() < 0.05 and gaps.mean() < 1e-3


def test_expert_shares_sum_to_the_uncut_layer(ref):
    """Eight shares of two experts each, as eight chips of an expert-parallel
    deployment hold them, plus the shared expert counted once, add up to
    the reference's uncut MoE + shared expert."""
    m, cfg, params = setup(ref)
    lp = params["layers"][0]
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 9, m["d_model"]),
                          jnp.float32).astype(jnp.bfloat16)
    want = ref._ffn(lp, v.reshape(-1, m["d_model"]).astype(jnp.float32), m,
                    False)
    total = mlp_apply(lp["shared"], v, cfg).astype(jnp.float32)
    for k in range(8):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * k)
        held = {"router": lp["moe"]["router"],
                **{w: lp["moe"][w][2 * k: 2 * k + 2]
                   for w in ("w_gate", "w_up", "w_down")}}
        total = total + moe.held_moe_apply(held, v, share).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), atol=0.02 * float(
                                   jnp.abs(want).max()))


def _slot_state(arena, slot):
    return [jax.tree.map(lambda a: np.asarray(a[slot]), st)
            for st in arena["layers"]]


def test_a_reused_slot_equals_a_fresh_one(ref):
    """Prefill overwrites the recurrent state of the slot it fills: a slot
    that served another request first holds the same state and gives the
    same logits as a fresh arena's."""
    m, cfg, params = setup(ref)
    s, max_len = 8, 16
    rng = np.random.default_rng(2)
    first, second = (jnp.asarray(rng.integers(0, m["vocab_size"], (1, s)),
                                 jnp.int32) for _ in range(2))
    sid = jnp.asarray([1], jnp.int32)
    used = init_arena(cfg, 2, max_len)
    _, used = prefill(params, first, sid, used, cfg=cfg)
    tok = jnp.asarray([0, 7], jnp.int32)
    for _ in range(3):
        tok, used = decode(params, tok, jnp.asarray([False, True]), used,
                           cfg=cfg, attn_impl="ref")
    t_used, used = prefill(params, second, sid, used, cfg=cfg)
    t_new, fresh = prefill(params, second, sid, init_arena(cfg, 2, max_len),
                           cfg=cfg)
    assert int(t_used[0]) == int(t_new[0])
    for a, b, kind in zip(_slot_state(used, 1), _slot_state(fresh, 1),
                          cfg.layer_types):
        if kind == "mamba":  # K/V past the prompt is stale, and masked
            jax.tree.map(np.testing.assert_array_equal, a, b)
    tok = jnp.asarray([0, int(t_new[0])], jnp.int32)
    act = jnp.asarray([False, True])
    n_used, _ = decode(params, tok, act, used, cfg=cfg, attn_impl="ref")
    n_new, _ = decode(params, tok, act, fresh, cfg=cfg, attn_impl="ref")
    assert int(n_used[1]) == int(n_new[1])


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_idle_slots_state_is_bit_unchanged(ref, impl):
    m, cfg, params = setup(ref, ssm_decode_impl=impl)
    s, slots = 8, 5
    rng = np.random.default_rng(4)
    arena = init_arena(cfg, slots, 12)
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], (slots, s)),
                       jnp.int32)
    _, arena = prefill(params, toks, jnp.arange(slots, dtype=jnp.int32),
                       arena, cfg=cfg)
    active = np.array([False, True, False, True, False])
    tok = jnp.asarray(rng.integers(0, m["vocab_size"], slots), jnp.int32)
    before = [_slot_state(arena, i) for i in range(slots)]
    nxt, after = decode(params, tok, jnp.asarray(active), arena, cfg=cfg,
                        attn_impl=impl)
    for i in np.flatnonzero(~active):
        jax.tree.map(np.testing.assert_array_equal, _slot_state(after, i),
                     before[i])
        assert int(nxt[i]) == int(tok[i])
    moved = _slot_state(after, 1)[0]["ssm"]
    assert not np.array_equal(moved, before[1][0]["ssm"])


@pytest.mark.parametrize("b,h,p,n,g", [(5, 8, 16, 16, 1), (4, 8, 64, 128, 1),
                                       (3, 32, 64, 32, 2)])
def test_ssd_decode_kernel_matches_its_reference(b, h, p, n, g):
    """The Pallas kernel (interpreted) against ``ref`` and the model's own
    single-token update, with idle slots first, inside and last."""
    ks = jax.random.split(jax.random.PRNGKey(b * h), 6)
    x = jax.random.normal(ks[0], (b, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, g, n), jnp.bfloat16)
    C = jax.random.normal(ks[4], (b, g, n), jnp.bfloat16)
    S = jax.random.normal(ks[5], (b, h, p, n))
    state = to_decode_layout(S)
    np.testing.assert_array_equal(np.asarray(from_decode_layout(state, p)),
                                  np.asarray(S))
    act = np.array([False, True, False, True, False][:b])
    want_y, want_s = ssd_decode(x, dt, A, B, C, state, jnp.asarray(act),
                                impl="ref")
    y, s = ssd_decode(x, dt, A, B, C, state, jnp.asarray(act),
                      impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[~act],
                                  np.asarray(state)[~act])
    y0, s0 = ssd_decode_step(x, dt, A, B, C, S)
    np.testing.assert_allclose(
        np.asarray(from_decode_layout(s, p))[act], np.asarray(s0)[act],
        rtol=1e-5, atol=1e-5)
    _, none = ssd_decode(x, dt, A, B, C, state, jnp.zeros((b,), bool),
                         impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(none), np.asarray(state))
