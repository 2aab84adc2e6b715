"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import on_tpu
from repro.kernels.decode_attention.ops import (
    combine_partials,
    decode_attention,
    decode_attention_partial,
    decode_attention_ref,
    scatter_decode_token,
    scatter_prefill_rows,
    tuned_block_k,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ops import ssd_decode_step, ssd_ref, ssd_scan

RNG = np.random.default_rng(0)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


FLASH_CASES = [
    # (b, sq, sk, h, kv, d, causal, q_offset)
    (2, 256, 256, 4, 2, 64, True, 0),
    (1, 128, 384, 8, 8, 128, False, 0),
    (2, 96, 200, 6, 2, 64, True, 104),  # ragged + offset
    (1, 1, 256, 4, 1, 64, True, 255),  # single-token append
    (1, 512, 512, 2, 1, 32, True, 0),  # MQA
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["pallas_interpret", "chunked"])
def test_flash_attention_matches_oracle(case, dtype, impl):
    b, sq, sk, h, kv, d, causal, off = case
    q = jnp.asarray(RNG.standard_normal((b, sq, h, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, sk, kv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, sk, kv, d)), dtype)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal, q_offset=off)
    out = flash_attention(q, k, v, causal=causal, q_offset=off, impl=impl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("blocks", [(64, 64), (128, 256), (32, 128)])
def test_flash_attention_block_shape_invariance(blocks):
    bq, bk = blocks
    q = jnp.asarray(RNG.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, impl="pallas_interpret",
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


DECODE_CASES = [
    (2, 256, 8, 2, 64),
    (1, 512, 4, 4, 128),
    (3, 300, 6, 1, 64),
    (2, 64, 16, 16, 32),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(case, dtype):
    b, s, h, kv, d = case
    q = jnp.asarray(RNG.standard_normal((b, h, d)), dtype)
    kc = jnp.asarray(RNG.standard_normal((b, kv, s, d)), dtype)
    vc = jnp.asarray(RNG.standard_normal((b, kv, s, d)), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32)
    ref = decode_attention_ref(
        q.astype(jnp.float32), kc.astype(jnp.float32),
        vc.astype(jnp.float32), lens)
    out = decode_attention(q, kc, vc, lens, impl="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


def test_decode_partial_combine_equals_full():
    """Sequence-sharded flash-decoding: shard partials + combine == full."""
    b, s, h, kv, d, nsh = 2, 512, 8, 2, 64, 8
    q = jnp.asarray(RNG.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((b, kv, s, d)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((b, kv, s, d)), jnp.float32)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32)
    ref = decode_attention_ref(q, kc, vc, lens)
    ssh = s // nsh
    os_, ms_, ls_ = [], [], []
    for i in range(nsh):
        shard_len = jnp.clip(lens - i * ssh, 0, ssh)
        o, m, l = decode_attention_partial(
            q, kc[:, :, i * ssh:(i + 1) * ssh],
            vc[:, :, i * ssh:(i + 1) * ssh],
            shard_len)
        os_.append(o), ms_.append(m), ls_.append(l)
    out = combine_partials(jnp.stack(os_), jnp.stack(ms_), jnp.stack(ls_))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# --------------------------------------------------------------------------- #
# KV-arena slot paths (continuous batching): ragged per-slot lengths,
# slot retirement + reuse, stale-KV isolation.
# --------------------------------------------------------------------------- #
DECODE_IMPLS = ["pallas", "pallas_interpret", "ref"]


def _pallas_refused_off_tpu(impl, q, kc, vc, lens) -> bool:
    """``impl="pallas"`` is the compiled kernel: off a TPU it must raise, not
    fall back to the interpreter.  True when that case was checked here."""
    if impl != "pallas" or on_tpu():
        return False
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        decode_attention(q, kc, vc, lens, impl=impl, block_k=16)
    return True


@pytest.mark.parametrize("impl", DECODE_IMPLS)
def test_decode_attention_slot_reuse_ignores_stale_kv(impl):
    """Retire a slot mid-stream, prefill a shorter request into it, and
    assert attention NEVER reads the retired request's stale KV rows: the
    reused (dirty) arena must attend identically to a zero-scrubbed one."""
    slots, s, h, kv, d = 4, 96, 8, 2, 32
    old_k = jnp.asarray(RNG.standard_normal((slots, kv, s, d)), jnp.float32)
    old_v = jnp.asarray(RNG.standard_normal((slots, kv, s, d)), jnp.float32)
    # Slot 2 retires; a new 24-token request prefills into its rows [0:24).
    new_len = 24
    rows_k = jnp.asarray(RNG.standard_normal((1, new_len, kv, d)), jnp.float32)
    rows_v = jnp.asarray(RNG.standard_normal((1, new_len, kv, d)), jnp.float32)
    sid = jnp.asarray([2], jnp.int32)
    dirty_k = scatter_prefill_rows(old_k, rows_k, sid)
    dirty_v = scatter_prefill_rows(old_v, rows_v, sid)
    clean_k = dirty_k.at[2, :, new_len:].set(0.0)
    clean_v = dirty_v.at[2, :, new_len:].set(0.0)
    # Stale rows really are still there (reuse, not a wipe) ...
    assert np.abs(np.asarray(dirty_k[2, :, new_len:])).max() > 0
    lens = jnp.asarray([s, 13, new_len, s], jnp.int32)
    q = jnp.asarray(RNG.standard_normal((slots, h, d)), jnp.float32)
    if _pallas_refused_off_tpu(impl, q, dirty_k, dirty_v, lens):
        return
    for block_k in (16, 64, 512):
        out_dirty = decode_attention(q, dirty_k, dirty_v, lens,
                                     impl=impl, block_k=block_k)
        out_clean = decode_attention(q, clean_k, clean_v, lens,
                                     impl=impl, block_k=block_k)
        # ... yet outputs match the scrubbed cache bit-for-bit tight.
        np.testing.assert_allclose(np.asarray(out_dirty),
                                   np.asarray(out_clean), atol=1e-6)


@pytest.mark.parametrize("impl", DECODE_IMPLS)
def test_decode_attention_zero_length_slot_outputs_zero(impl):
    """A retired / never-filled slot (length 0) must return exact zeros in
    every impl — not the degenerate uniform average over garbage."""
    slots, s, h, kv, d = 3, 64, 4, 2, 16
    kc = jnp.asarray(RNG.standard_normal((slots, kv, s, d)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((slots, kv, s, d)), jnp.float32)
    q = jnp.asarray(RNG.standard_normal((slots, h, d)), jnp.float32)
    lens = jnp.asarray([0, 5, 0], jnp.int32)
    if _pallas_refused_off_tpu(impl, q, kc, vc, lens):
        return
    out = np.asarray(decode_attention(q, kc, vc, lens, impl=impl, block_k=16))
    assert (out[0] == 0).all() and (out[2] == 0).all()
    assert np.abs(out[1]).max() > 0


def test_scatter_slot_helpers_drop_padding():
    """Out-of-bounds slot ids / write positions are padding sentinels: their
    writes drop, real slots are untouched."""
    cache = jnp.zeros((3, 2, 8, 4))  # head-major (slots, kv, max_len, d)
    rows = jnp.ones((2, 5, 2, 4))  # (m, s, kv, d) from the projection
    out = scatter_prefill_rows(cache, rows, jnp.asarray([1, 3], jnp.int32))
    assert (np.asarray(out[1, :, :5]) == 1).all()
    assert (np.asarray(out[1, :, 5:]) == 0).all()
    assert (np.asarray(out[0]) == 0).all() and (np.asarray(out[2]) == 0).all()
    tok = jnp.full((3, 2, 4), 7.0)
    out2 = scatter_decode_token(out, tok, jnp.asarray([5, 8, 0], jnp.int32))
    assert (np.asarray(out2[0, :, 5]) == 7.0).all()
    assert (np.asarray(out2[2, :, 0]) == 7.0).all()
    assert (np.asarray(out2[1]) == np.asarray(out[1])).all()  # OOB dropped


def test_tuned_block_k_arena_scale():
    """Short caches stay one block; long caches cap at the VMEM budget."""
    assert tuned_block_k(17) == 128
    assert tuned_block_k(64) == 128
    assert tuned_block_k(4096, head_dim=128) == 256
    assert tuned_block_k(4096, head_dim=64) == 512
    with pytest.raises(ValueError):
        tuned_block_k(0)


SSD_CASES = [
    # (b, l, h, p, g, n, chunk)
    (2, 128, 4, 32, 1, 16, 32),
    (1, 256, 8, 64, 2, 64, 64),
    (2, 64, 2, 16, 2, 8, 16),
    (1, 128, 4, 64, 1, 128, 128),  # mamba2-1.3b-like dims
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("impl", ["pallas_interpret", "chunked"])
def test_ssd_scan_matches_sequential_oracle(case, impl):
    b, l, h, p, g, n, chunk = case
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, l, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(h)) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    y_ref, s_ref = ssd_ref(x, dt, A, B, C)
    y, s = ssd_scan(x, dt, A, B, C, chunk=chunk, impl=impl)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=3e-4)


def test_ssd_decode_step_continues_scan():
    b, l, h, p, g, n = 1, 64, 4, 32, 2, 16
    x = jnp.asarray(RNG.standard_normal((b, l + 1, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, l + 1, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(h)) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l + 1, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l + 1, g, n)) * 0.3, jnp.float32)
    y_full, s_full = ssd_ref(x, dt, A, B, C)
    _, s_pre = ssd_ref(x[:, :l], dt[:, :l], A, B[:, :l], C[:, :l])
    y_step, s_step = ssd_decode_step(
        x[:, l], dt[:, l], A, B[:, l], C[:, l], s_pre)
    np.testing.assert_allclose(
        np.asarray(y_step), np.asarray(y_full[:, l]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_step), np.asarray(s_full),
                               atol=1e-4)


def test_ssd_chunk_invariance():
    """Result is independent of the chunk size (kernel tiling invariant)."""
    b, l, h, p, g, n = 1, 128, 2, 16, 1, 8
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, l, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(h)) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    outs = [np.asarray(ssd_scan(x, dt, A, B, C, chunk=c, impl="chunked")[0])
            for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-4)
