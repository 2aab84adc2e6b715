"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a trailing roofline summary
derived from the dry-run artifacts when present).

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig9] [--quick]
                                               [--json BENCH_PR3.json]

``--quick`` is the CI smoke mode: reduced device counts, restricted to the
cohort-engine perf benchmarks (``fig8_device_tier_batched``,
``multi_grade_round``, ``round_pipeline``), and a non-zero exit when any claim
row reports ``ok=False`` — so the round-engine perf path can't silently break.

``--json PATH`` persists every row to a machine-readable artifact.  The repo
commits one ``BENCH_PR<N>.json`` per PR; when a previous artifact exists, the
harness prints ``bench_diff/...`` rows comparing throughput metrics
(devices_per_s, speedup, ...) against it, so the perf trajectory across PRs
is diffable by machines and reviewers alike.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks import common  # noqa: E402
from benchmarks.paper_benchmarks import ALL_BENCHMARKS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

QUICK_BENCHMARKS = ("fig8_device_tier_batched", "multi_grade_round",
                    "round_pipeline", "million_device_round",
                    "quantized_wire", "workers_round",
                    "multi_task_schedule", "multi_task_preemption",
                    "continuous_serving")

# Throughput-ish metrics worth tracking across PRs (higher is better except
# slowdown/makespan_s/queueing_delay_s; the diff just reports the ratio
# either way).
DIFF_METRICS = ("devices_per_s", "device_messages_per_s",
                "worker_device_messages_per_s", "speedup",
                "slowdown", "per_device_us", "makespan_s",
                "queueing_delay_s", "bytes_per_round", "loss_drift_pct",
                "p99_latency_s", "goodput_rps")


def parse_derived(derived: str) -> dict:
    """``k1=v1;k2=v2`` -> dict with floats where they parse."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def previous_artifact(out_path: pathlib.Path) -> pathlib.Path | None:
    """Newest committed ``BENCH_PR<N>.json`` that isn't the output file."""
    best, best_n = None, -1
    for p in out_path.resolve().parent.glob("BENCH_PR*.json"):
        if p.resolve() == out_path.resolve():
            continue
        m = re.fullmatch(r"BENCH_PR(\d+)\.json", p.name)
        if m and int(m.group(1)) > best_n:
            best, best_n = p, int(m.group(1))
    return best


def diff_rows(prev: dict, cur_rows: list[dict]) -> list[str]:
    """CSV lines comparing tracked metrics against a previous artifact."""
    prev_rows = {r["name"]: r for r in prev.get("rows", ())}
    lines = []
    for r in cur_rows:
        p = prev_rows.get(r["name"])
        if p is None:
            continue
        pm, cm = parse_derived(p["derived"]), parse_derived(r["derived"])
        for k in DIFF_METRICS:
            pv, cv = pm.get(k), cm.get(k)
            if isinstance(pv, float) and isinstance(cv, float) and pv:
                lines.append(
                    f"bench_diff/{r['name']},0.0,"
                    f"metric={k};prev={pv:g};now={cv:g};ratio={cv / pv:.3f}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: reduced scales, perf benchmarks only, "
                         "fail on ok=False claim rows")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="persist rows to a JSON artifact and diff tracked "
                         "metrics against the newest BENCH_PR*.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    common.QUICK = args.quick

    print("name,us_per_call,derived")
    failures = 0
    collected: list[dict] = []
    for bench in ALL_BENCHMARKS:
        if args.only and args.only not in bench.__name__:
            continue
        if args.quick and not args.only and \
                bench.__name__ not in QUICK_BENCHMARKS:
            continue
        try:
            for row in bench():
                print(row.csv(), flush=True)
                rec = {"name": row.name,
                       "us_per_call": float(row.us_per_call),
                       "derived": row.derived}
                if isinstance(row.us_per_call, common.TimedStat):
                    # %std + iteration count ride into the artifact so a
                    # diff reader can weigh noisy means appropriately.
                    rec["pstd"] = row.us_per_call.pstd
                    rec["iters"] = row.us_per_call.iters
                collected.append(rec)
                if args.quick and "ok=False" in row.derived:
                    failures += 1
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"{bench.__name__},0.0,ERROR={type(e).__name__}:{e}",
                  flush=True)

    # Roofline summary rows from dry-run artifacts, if present.
    art = pathlib.Path("artifacts/dryrun")
    if art.exists():
        try:
            from benchmarks.roofline_report import summary_rows
            for row in summary_rows(art):
                print(row.csv(), flush=True)
        except Exception as e:
            print(f"roofline_summary,0.0,ERROR={type(e).__name__}:{e}")

    if args.json:
        out_path = pathlib.Path(args.json)
        out_path.write_text(json.dumps(
            {"quick": args.quick, "only": args.only, "rows": collected},
            indent=1))
        prev = previous_artifact(out_path)
        if prev is not None:
            try:
                prev_data = json.loads(prev.read_text())
                if bool(prev_data.get("quick")) != bool(args.quick):
                    # Quick and full runs use different scales; a ratio
                    # between them would read as a phantom regression.
                    print(f"bench_diff,0.0,SKIPPED=scale_mismatch:"
                          f"{prev.name}")
                else:
                    for line in diff_rows(prev_data, collected):
                        print(line, flush=True)
            except (json.JSONDecodeError, KeyError) as e:
                print(f"bench_diff,0.0,ERROR={type(e).__name__}:{e}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
