"""Shared set-up of the benchmark's own tests (CPU, small sizes).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The CPU backend shows four devices, so that the four-chip cell's fleet mesh
runs here too.
"""
import os
import pathlib
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# Each configuration at a size a CPU test holds; the shapes' kinds stay.
SMALL = {
    "fl_campaign": {"config": {
        "fleet": {"High": 700, "Low": 500}, "cohort_size": 256,
        "aggregation": {"reduce_impl": "ref"},
        # This size's own limits: on the CPU, sound runs read agg_err
        # 1.2e-05 and 2.0e-05 and device_rows_err 0, the control 1.5e-03
        # and 2.6e-03, and 0.061 and 0.149.
        "limits": {"agg_err": 3e-4, "logical_rows_err": 1e-4,
                   "device_rows_err": 3e-2},
        "resources": {
            "High": {"logical_bundles": 40, "bundles_per_device": 8,
                     "physical_devices": 3},
            "Low": {"logical_bundles": 40, "bundles_per_device": 2,
                    "physical_devices": 2}}}},
    "serving": {
        # Both experts serve every token: with a choice among four, a bf16
        # router's near-tie picks another expert than the f32 reference's
        # on some seeds (sound runs then read up to 0.51, the control's
        # range), and which requests the check samples depends on timing.
        "model": dict(num_layers=2, d_model=96, num_heads=6, num_kv_heads=2,
                      head_dim=16, d_ff=64, vocab_size=512, num_experts=2,
                      experts_per_token=2, capacity_factor=2.0),
        # Logits as widely spread as the published width's: 0.02 * sqrt(1536)
        # = std * sqrt(96).
        "weights": {"init_std": 0.08},
        # This size's own limit, set as the cells' are: over seeds
        # 2**31 + 12345 and 5 to 9 of both serving cells on the CPU, sound
        # runs read at most 0.023 and the fp8 control at least 0.169;
        # 0.023**(1/3) * 0.169**(2/3) = 0.087.
        "config": {"limits": {"max_logit_gap": 0.08}},
        "traffic": {"slots": 4, "prompt_len": 8, "decode_tokens": 12,
                    "check_requests": 16, "lead_s": 1,
                    "arrivals": {"process": "poisson", "rate_per_s": 400.0,
                                 "gap_seed": 0}}},
}
CPU_PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
             "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
SEED = 2**31 + 12345  # wider than 32 signed bits, as the checks' seeds are


# Cells whose files are ready under bench/ but which BENCHMARK.json does not
# list yet (no chip run has proven them): the tests run them all the same.
LATER = {
    "workloads": [
        {"name": "granite_serve.open", "config": "granite_moe_3b_a800m_serve",
         "traffic": "serve_open_short", "chips": 1},
        {"name": "ctr_campaign.mesh4", "config": "avazu_lr_campaign",
         "traffic": "fl_rounds_stream_mesh4", "chips": 4},
    ],
    "end_to_end": [
        {"name": "serve_ttft_p90_ms", "unit": "ms",
         "workloads": ["granite_serve.open"]},
        {"name": "serve_itl_p99_ms", "unit": "ms",
         "workloads": ["granite_serve.open"]},
        {"name": "fl_devices_per_s", "unit": "devices/s",
         "workloads": ["ctr_campaign.mesh4"]},
    ],
    "per_layer": [
        {"name": "prefill_step_ms", "moves": "serve_ttft_p90_ms",
         "workloads": ["granite_serve.open"]},
        {"name": "device_idle.serve_open", "moves": "serve_ttft_p90_ms",
         "workloads": ["granite_serve.open"]},
        {"name": "fl.collective_ms", "moves": "fl_devices_per_s",
         "workloads": ["ctr_campaign.mesh4"]},
        {"name": "serve.step_host_ms", "moves": "serve_ttft_p90_ms",
         "workloads": ["granite_serve.open"]},
    ],
}


@pytest.fixture(scope="session")
def bench():
    """BENCHMARK.json, with the cells of ``LATER`` that it does not list."""
    import run

    b = run.load_json(ROOT / "BENCHMARK.json")
    have = {w["name"] for w in b["workloads"]}
    for w in LATER["workloads"]:
        if w["name"] not in have:
            b["workloads"].append(w)
            for key in ("end_to_end", "per_layer"):
                named = {m["name"]: m for m in b[key]}
                for m in LATER[key]:
                    if w["name"] not in m["workloads"]:
                        continue
                    if m["name"] in named:
                        named[m["name"]]["workloads"].append(w["name"])
                    else:
                        b[key].append(dict(m, workloads=[w["name"]]))
    return b


def small_run(bench, cell_name: str, *, seconds: float = 2.0,
              seed: int = SEED, trace: bool = False):
    """One whole run of a cell at its small size, the chip look skipped."""
    import jax
    import run

    cell = run.Cell(bench, cell_name)
    return run.execute(cell, seed=seed, seconds=seconds, trace=trace,
                       devices=jax.devices()[:cell.chips], peaks=CPU_PEAKS,
                       overrides=SMALL[cell.config["system"]])
