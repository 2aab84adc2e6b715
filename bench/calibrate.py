"""Readings that the correctness limits are set from, many seeds in one
process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 \
        [--control 1] [--fault <name>]

For each seed: build the cell as a run does, measure a short window at the
cell's own load and sizes, and print one JSON line with the numbers its
check compares (``program``) and, with ``--control 1``, the same numbers
with the control in the program's place (``control``): the reference
computed one precision step below what the configuration states.  With
``--fault`` the program runs with that fault of ``faults.py`` planted under
its timed path.  The benchmark's own runs never run the control or a fault.
Needs the chip the cell asks for, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as R  # noqa: E402
from faults import FAULTS, planted  # noqa: E402


def readings(cell, *, seed: int, seconds: float, control: bool, devices,
             overrides: dict | None = None, fault: str | None = None) -> dict:
    system = R.load_module(cell.system_path)
    reference = R.load_module(cell.reference_path)
    t0 = time.perf_counter()
    with planted(fault) if fault else contextlib.nullcontext():
        sut = system.System(cell.config, cell.traffic, seed=seed,
                            devices=devices, spans=R.Spans(),
                            reference=reference, overrides=overrides or {})
        sut.setup()
        window = sut.window(seconds)
        sut.release()
    out = {"seed": seed, "setup_s": time.perf_counter() - t0 - seconds,
           "metrics": window["metrics"], "attempted": window["attempted"],
           "program": {k: v["value"] for k, v in sut.check().items()}}
    if control:
        out["control"] = sut.control()
    del sut
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    cell = R.Cell(R.load_json(R.ROOT / "BENCHMARK.json"), args.workload)
    jax = R.start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed=seed, seconds=args.seconds,
                                  control=bool(args.control),
                                  devices=devices[:cell.chips],
                                  fault=args.fault)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
