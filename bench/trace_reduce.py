"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.  Each
TPU is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds one
event per operation that ran on the chip, with a start and a duration in
nanoseconds.  A TPU trace names each such event by its whole HLO instruction
(``%decode_attention.6 = bf16[32,8,3,64]{...} custom-call(...)``); only the
instruction's name, the part before `` = ``, is kept.  The harness's own spans (``jax.profiler.TraceAnnotation``,
names starting ``bench.``) are events of the ``/host:CPU`` plane, on the
same clock.

Everything below the extraction works on plain ``(name, start_ns, dur_ns)``
tuples, so the tests check it on a recorded CPU trace and on hand-made
intervals.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."

Event = tuple  # (name, start_ns, dur_ns)


@dataclasses.dataclass
class Trace:
    """Device operations per chip, harness spans, and the traced window."""

    device_ops: dict[int, list[Event]]
    spans: list[Event]
    window: tuple[float, float]  # (start_ns, end_ns) of the bench.window span
    modules: dict[int, list[Event]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device: int | None = None) -> list[Event]:
        """Operations clipped to the window, of one chip or of all."""
        return self._clip(self.device_ops, device)

    def programs(self, device: int | None = None) -> list[Event]:
        """Whole compiled-program executions clipped to the window."""
        return self._clip(self.modules, device)

    def _clip(self, planes: dict, device: int | None) -> list[Event]:
        devs = planes if device is None else {device: planes[device]}
        lo, hi = self.window
        return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                for evs in devs.values() for n, s, d in evs
                if s < hi and s + d > lo]


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str, *, window_span: str = "bench.window") -> Trace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_file(trace_dir))
    device_ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line_name, out in ((OPS_LINE, device_ops),
                                   (MODULES_LINE, modules)):
                out[int(m.group(1))] = [
                    (short_name(e.name), e.start_ns, e.duration_ns)
                    for line in plane.lines if line.name == line_name
                    for e in line.events]
        elif plane.name == "/host:CPU":
            spans.extend((e.name, e.start_ns, e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops, spans, span_window(spans, window_span), modules)


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def span_window(spans: list[Event], name: str) -> tuple[float, float]:
    win = [(s, s + d) for n, s, d in spans if n == name]
    if not win:
        raise LookupError(f"the trace holds no {name!r} span")
    return min(w[0] for w in win), max(w[1] for w in win)


def union_ns(events: list[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.device_ops:
        raise LookupError("the trace holds no device plane")
    per = [union_ns(trace.ops(d)) for d in trace.device_ops]
    return sum(per) / len(per) * 1e-9


def containing(outer: list[Event], inner: list[Event]) -> list[Event]:
    """The ``outer`` events inside which some ``inner`` event starts."""
    starts = sorted(s for _, s, _ in inner)
    return [e for e in outer
            if bisect.bisect_left(starts, e[1]) <
            bisect.bisect_right(starts, e[1] + e[2])]


def matching(events: list[Event], pattern: str) -> list[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def op_seconds(events: list[Event], pattern: str) -> tuple[float, int]:
    """Summed device seconds and count of the events matching ``pattern``."""
    hits = matching(events, pattern)
    return sum(d for _, _, d in hits) * 1e-9, len(hits)


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` operation names that took the most device time."""
    by: dict[str, float] = {}
    for name, _, d in events:
        by[name] = by.get(name, 0.0) + d
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(events: list[Event], window: tuple[float, float],
              spans: list[Event], n: int = 10) -> list[list]:
    """The ``n`` longest stretches of the window with no operation running,
    each named by the innermost harness span that covers its middle."""
    gaps, end = [], window[0]
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > end:
            gaps.append((end, s))
        end = max(end, s + d)
    if window[1] > end:
        gaps.append((end, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for lo, hi in gaps[:n]:
        mid = (lo + hi) / 2
        cover = [(d, name) for name, s, d in spans
                 if s <= mid <= s + d and name != "bench.window"]
        label = min(cover)[1] if cover else "outside any harness span"
        out.append([label, (hi - lo) * 1e-9])
    return out
