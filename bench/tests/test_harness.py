"""The harness: what it refuses, what it finds by name, what a run prints."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import BENCH, ROOT, small_run


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ctr_campaign.f32",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_off_a_tpu_it_exits_non_zero_without_a_result():
    p = _run_cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_every_name_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = run.Cell(bench, w["name"])
        assert cell.system_path.is_file() and cell.reference_path.is_file()
        ends = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in ends and len(ends) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert cell.reader(m["name"]).is_file()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


@pytest.mark.parametrize("cell_name", ["ctr_campaign.f32",
                                       "ctr_campaign.mesh4",
                                       "granite_serve.decode",
                                       "granite_serve.open"])
def test_a_small_run_is_correct_and_reports_its_metrics(bench, cell_name):
    res = small_run(bench, cell_name)
    assert list(res)[-1] == "compared"
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert res["correct"], res["compared"]
    cell = run.Cell(bench, cell_name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]


def test_the_same_seed_gives_the_same_inputs():
    import numpy as np

    ref = run.load_module(BENCH / "configs" / "avazu_lr_campaign_ref.py")
    recipe = run.load_json(BENCH / "configs" /
                           "avazu_lr_campaign.json")["data_recipe"]
    a, _ = ref.make_data(2**31 + 7, {"High": 30}, 4, 256, recipe)
    b, _ = ref.make_data(2**31 + 7, {"High": 30}, 4, 256, recipe)
    c, _ = ref.make_data(2**31 + 8, {"High": 30}, 4, 256, recipe)
    assert np.array_equal(a["High"]["x"], b["High"]["x"])
    assert not np.array_equal(a["High"]["x"], c["High"]["x"])


@pytest.mark.parametrize("process", ["poisson", "on_off"])
def test_arrivals_keep_the_rate_and_permute_one_draw(process):
    import numpy as np

    serving = run.load_module(BENCH / "systems" / "serving.py")
    arr = {"process": process, "rate_per_s": 5.0, "gap_seed": 0,
           "on_s": 2.0, "off_s": 2.0}
    a = serving.arrival_times(arr, 10.0, 210.0, np.random.default_rng(1))
    b = serving.arrival_times(arr, 10.0, 210.0, np.random.default_rng(2))
    assert a.min() >= 10.0 and a.max() < 210.0
    assert np.all(np.diff(a) > 0)
    assert abs(len(a) / 200.0 - 5.0) < 1.0 and abs(len(b) - len(a)) < 40
    assert not np.array_equal(a, b)
    if process == "on_off":  # nothing arrives in the silent seconds
        assert np.all(((a - 10.0) % 4.0) < 2.0)
    else:  # the same gaps, in another order
        gaps = np.diff(np.concatenate([[10.0], a]))
        other = np.diff(np.concatenate([[10.0], b]))
        k = min(len(gaps), len(other)) - 5
        assert len(set(np.round(gaps, 9)) & set(np.round(other, 9))) > k // 2


def test_a_metric_that_finds_nothing_is_left_out_and_named(bench):
    """A decode cell's trace without the decode kernel: its readers find
    nothing, so those metrics are left out (never read as 0) and named."""
    import trace_reduce as tr

    cell = run.Cell(bench, "granite_serve.decode")
    trace = tr.Trace({0: [("fusion.1", 10, 5)]}, [], (0, 100),
                     {0: [("jit_step", 0, 50)]})
    counters = {"steps": [(0.0, 1.0, 32, 0, [65] * 32)]}
    r = run.Run(cell, run.Spans(), counters, trace, {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    metrics, missing = run.per_layer(cell, r)
    assert {"decode_step_ms", "decode_attention_roofline"} <= set(missing)
    assert not set(missing) & set(metrics)
    assert "device_idle.serve_decode" in metrics
