"""The granite-4.0-h-small serving cell at a size a CPU test holds: a small
run is correct and reports its metrics, its controls are not correct, and
neither is the program with its SSM state held in bf16.
(``tests/test_chip_compile.py`` checks the kernel readers' names against the
hybrid decode step compiled for a described v5e.)"""
import jax
import pytest

import calibrate
import run
from conftest import CPU_PEAKS, SEED

CELL = "granite4h_serve.decode"
# The cell's kinds, scalars and cut (a third of the experts held, from an
# offset) at small widths.  At d_model 64 the tied embedding's own-token
# logit would dominate (``granite_4_0_h_small_serve_ref.init_weights``):
# with the published residual multiplier 0.22 every served token repeats
# the last, which no check can tell from anything else; 1.0 lets the layers
# outweigh it, as at the published width.
SMALL = {
    "model": dict(num_layers=3, layer_types=["mamba", "attention", "mamba"],
                  residual_multiplier=1.0,
                  d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                  d_ff=32, vocab_size=512, num_experts=12,
                  experts_per_token=4, experts_held=4, expert_offset=4,
                  shared_expert_ff=48, ssm_state=16, ssm_head_dim=16,
                  ssm_chunk=16, attention_multiplier=0.0625),
    # Weights as large for the width as the published width's:
    # 0.02 * sqrt(4096 / 64).
    "weights": {"init_std": 0.16},
    # This size's own limit, set as the cell's is: over seeds 2**31 + 12345
    # and 1 to 4 on the CPU, sound runs read at most 2.6e-06 and the fp8
    # control at least 5.6e-05; 2.6e-06**(1/3) * 5.6e-05**(2/3) = 2.0e-05.
    # The state's share of short entries is the cell's own limit: it reads
    # the precision the state is held at, whatever the widths.
    "config": {"limits": {"mean_logit_gap": 2e-5,
                          "ssm_state_short_share": 0.0625}},
    "traffic": {"slots": 4, "prompt_len": 8, "decode_tokens": 12,
                "check_requests": 8},
}


def test_a_small_run_is_correct_and_reports_its_metrics(bench):
    cell = run.Cell(bench, CELL)
    res = run.execute(cell, seed=SEED, seconds=2.0, trace=False,
                      devices=jax.devices()[:1], peaks=CPU_PEAKS,
                      overrides=SMALL)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_the_fp8_control_is_not_correct(bench):
    cell = run.Cell(bench, CELL)
    out = calibrate.readings(cell, seed=SEED, seconds=2.0, control=True,
                             devices=jax.devices()[:1], overrides=SMALL)
    limits = SMALL["config"]["limits"]
    assert all(out["program"][k] <= limits[k] for k in out["program"])
    assert any(out["control"][k] > limits[k] for k in limits), out


def test_a_bf16_state_is_not_correct(bench, monkeypatch):
    """The program with its SSM state rounded to bf16 wherever it is stored
    (prefill and every decode step) fails the state's precision."""
    from repro.models import granite_hybrid

    def bf16(state):  # XLA may elide a round trip through astype
        return jax.lax.reduce_precision(state, exponent_bits=8,
                                        mantissa_bits=7)

    update, layout = granite_hybrid.ssd_decode, granite_hybrid.to_decode_layout

    def rounded_update(*a, **kw):
        y, state = update(*a, **kw)
        return y, bf16(state)

    monkeypatch.setattr(granite_hybrid, "ssd_decode", rounded_update)
    monkeypatch.setattr(granite_hybrid, "to_decode_layout",
                        lambda s: bf16(layout(s)))
    try:
        res = run.execute(run.Cell(bench, CELL), seed=SEED, seconds=2.0,
                          trace=False, devices=jax.devices()[:1],
                          peaks=CPU_PEAKS, overrides=SMALL)
    finally:
        jax.clear_caches()
    assert not res["correct"]
    assert res["compared"]["ssm_state_short_share"]["value"] == 1.0
