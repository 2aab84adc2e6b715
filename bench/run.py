"""SimDC chip benchmark: one cell, one seed, one measured window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``; the
harness finds everything else by those names:

* ``bench/configs/<config>.json``: the configuration as it is run.  Its
  ``system`` key names ``bench/systems/<system>.py``, which builds the
  program under test from the configuration, the traffic and the seed,
  drives the measured window, and compares what the window produced with
  the plain reference ``bench/configs/<config>_ref.py``.
* ``bench/traffic/<traffic>.json``: the parameters of the traffic mix, which
  the system's generator reads.
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, which
  takes its number from the harness's spans, the system's counters and the
  profiler trace (``--trace 1``).  Where that file is missing, the reader
  of the name's part before its first dot serves (``device_idle.fl`` ->
  ``device_idle.py``).  A reader that finds nothing returns ``None``; the
  metric is then left out of the result line, and standard error names it.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a traced window of the
traffic's ``trace_seconds``.  Set-up (``setup_s``) runs from the start of
this script to the start of the window: imports, data and weights made on
the chip from the seed, compilation or compile-cache reads, and warm-up.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared``: every number the correctness check
compared, beside its limit).  The same numbers are the last lines of
standard error.  Without a TPU, with fewer chips than the cell asks for, or
on a chip missing from ``peaks.json``, the script exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: pathlib.Path):
    """Import a benchmark file by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with what it names."""

    def __init__(self, bench: dict, name: str, root: pathlib.Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        bdir = root / "bench"
        self.config = load_json(bdir / "configs" / f"{self.entry['config']}.json")
        self.traffic = load_json(
            bdir / "traffic" / f"{self.entry['traffic']}.json")
        self.system_path = bdir / "systems" / f"{self.config['system']}.py"
        self.reference_path = bdir / "configs" / f"{self.entry['config']}_ref.py"
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]
        self.metric_dir = bdir / "metrics"

    def reader(self, metric: str) -> pathlib.Path:
        """``metrics/<metric>.py``, else the reader of the name's first
        part: ``device_idle.fl`` and ``device_idle.serve_decode`` share
        ``metrics/device_idle.py``."""
        own = self.metric_dir / f"{metric}.py"
        if own.is_file():
            return own
        return self.metric_dir / f"{metric.split('.')[0]}.py"


class Spans:
    """Harness spans: host-clock intervals kept in memory, and the same
    names (prefixed ``bench.``) written into the profiler's trace."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def in_window(self, name: str) -> list[float]:
        """Seconds of each ``name`` span inside the measured window."""
        w = [(t0, t1) for n, t0, t1 in self.records if n == "window"]
        if not w:
            return []
        w0, w1 = w[-1]
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= w0 and t1 <= w1]


class CompileLog:
    """Host-clock times of JAX's backend compiles and compile-cache reads,
    so that a run can say what compiled in set-up and in the window."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
    _installed = None

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def __call__(self, event: str, duration_s: float, **_) -> None:
        if event in (self.COMPILE, self.CACHE_READ):
            self.events.append((event, time.perf_counter()))

    @classmethod
    def install(cls) -> "CompileLog":
        """The process's one log (JAX keeps its listeners for good)."""
        if cls._installed is None:
            import jax

            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed)
        return cls._installed

    def count(self, event: str, lo: float, hi: float) -> int:
        return sum(1 for e, t in self.events if e == event and lo <= t <= hi)


class Run:
    """What a per-layer metric reader may read."""

    def __init__(self, cell: Cell, spans: Spans, counters: dict, trace,
                 peaks: dict):
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.spans = spans
        self.counters = counters
        self.trace = trace
        self.peaks = peaks


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table['devices'])})")
    return table["devices"][kind]


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def per_layer(cell: Cell, run: Run) -> tuple[dict, list[str]]:
    """The cell's per-layer metrics read from ``run``, and the names of those
    whose reader found nothing to read (left out of the result: a kernel or
    a span that went out of sight shows as a gap, never as 0)."""
    metrics, missing = {}, []
    for m in cell.per_layer:
        value = load_module(cell.reader(m["name"])).read(run)
        if value is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, missing


def execute(cell: Cell, *, seed: int, seconds: float, trace: bool,
            devices, peaks: dict, overrides: dict | None = None,
            keep_trace: str | None = None) -> dict:
    """Build the cell's system from the seed, warm it up, measure one
    window, read the metrics, then check the window's output against the
    reference.  Returns the result object."""
    import jax

    compiles = CompileLog.install()
    t_setup = time.perf_counter()
    system = load_module(cell.system_path)
    reference = load_module(cell.reference_path)
    spans = Spans()
    sut = system.System(cell.config, cell.traffic, seed=seed,
                        devices=devices, spans=spans, reference=reference,
                        overrides=overrides or {})
    sut.setup()
    window_s = (min(seconds, cell.traffic["trace_seconds"]) if trace
                else seconds)
    trace_dir = (keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
                 if trace else None)
    if trace:
        # Device operations and the harness's spans; no Python function
        # tracing, which slows a host-bound loop by more than half.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    try:
        with spans("window"):
            window = sut.window(window_s)
    finally:
        if trace:
            jax.profiler.stop_trace()
    mem_peak = memory_peak_bytes(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    metrics, breakdown, missing = {}, None, []
    if trace:
        from trace_reduce import busy_s, idle_gaps, load as load_trace, top_ops

        tr = load_trace(trace_dir)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = busy_s(tr)
        device["window_s"] = tr.window_s
        metrics, missing = per_layer(
            cell, Run(cell, spans, window["counters"], tr, peaks))
        first = min(tr.device_ops)
        breakdown = {"device_ops": top_ops(tr.ops()),
                     "idle_gaps": idle_gaps(tr.ops(first), tr.window,
                                            tr.spans)}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"cell {cell.name} does not produce the "
                               f"end-to-end metric {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    (_, w0, w1), = [r for r in spans.records if r[0] == "window"]
    notes = [f"{where}: {compiles.count(compiles.COMPILE, lo, hi)} programs "
             f"compiled, {compiles.count(compiles.CACHE_READ, lo, hi)} read "
             "from the compile cache"
             for where, lo, hi in (("set-up", t_setup, w0),
                                   ("window", w0, w1))]
    if missing:
        notes.append(f"per-layer metrics {missing} found nothing to read "
                     "and are left out")
    for line in notes + window.get("notes", []):
        print(f"bench: {line}", file=sys.stderr)
    sut.release()
    compared = sut.check()
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values()) and bool(compared)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="write the profiler trace to DIR and keep it")
    return ap.parse_args(argv)


def start_jax():
    """Import JAX with its persistent compilation cache at the fixed
    ``<checkout>/.jax_cache`` (whatever the environment said), caching every
    program, also the many that compile in under a second."""
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(BENCH))
    cell = Cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    jax = start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = device_peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = execute(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices[:cell.chips],
                     peaks=peaks, keep_trace=args.keep_trace)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
