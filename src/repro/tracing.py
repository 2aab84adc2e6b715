"""Program spans and counters: where a round's or a step's host time goes.

    import jax
    from repro import tracing

    with jax.profiler.trace("/tmp/simdc-trace"):
        sim.run_plan_round(...)
    rec = tracing.recorder()
    for s in rec.spans:
        print(s.name, s.ns, rec.self_ns(s))

A span marks one batch-level call of a layer (a round, a cohort chunk, a
DeviceFlow submit or dispatch, an aggregation, a serving step), never one
device or one delivery.  Tracing is on while a ``jax.profiler`` trace
collects, and only then; there is no other switch.  On, ``span(name,
**args)`` does two things:

* it opens ``jax.profiler.TraceAnnotation("simdc." + name, **args)``, so the
  span lands on the profiler's ``/host:CPU`` plane, on the same clock as the
  device's operations;
* it records ``(name, parent, t0, t1)`` in memory on ``time.perf_counter_ns``
  (``recorder()``), with the counters added while it was the innermost open
  span.

Off, ``span`` returns one shared no-op after asking the profiler whether it
collects: no clock read, nothing recorded.

Spans and counters only observe.  They never touch virtual time, state
dicts, outputs or random streams, and they never wait on the device: a span
around a dispatch measures the host's time in it, which includes time the
runtime blocks the host when too many programs are in flight.
"""
from __future__ import annotations

import itertools
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

PREFIX = "simdc."


def on() -> bool:
    """Whether spans and counters record: while a profiler trace collects."""
    return TraceAnnotation.is_enabled()


class Span(NamedTuple):
    """One closed span.  ``counters`` holds what was counted while this span
    was the innermost open one (not its children's counts)."""

    id: int
    parent: int | None
    name: str
    t0_ns: int
    t1_ns: int
    args: dict
    counters: dict

    @property
    def ns(self) -> int:
        return self.t1_ns - self.t0_ns


class Recorder:
    """Closed spans in the order they closed."""

    def __init__(self):
        self.spans: list[Span] = []
        self._open: list[_Open] = []
        self._ids = itertools.count()
        self._maps_n = -1

    def clear(self) -> None:
        self.spans.clear()
        self._maps_n = -1

    # -- reading ---------------------------------------------------------
    def window(self, t0_s: float, t1_s: float) -> list[Span]:
        """Spans that lie wholly in ``[t0_s, t1_s]``, in seconds of
        ``time.perf_counter``."""
        lo, hi = t0_s * 1e9, t1_s * 1e9
        return [s for s in self.spans if s.t0_ns >= lo and s.t1_ns <= hi]

    def _maps(self) -> tuple[dict[int, Span], dict[int, list[Span]]]:
        if self._maps_n != len(self.spans):
            kids: dict[int, list[Span]] = {}
            for s in self.spans:
                if s.parent is not None:
                    kids.setdefault(s.parent, []).append(s)
            self._cache = ({s.id: s for s in self.spans}, kids)
            self._maps_n = len(self.spans)
        return self._cache

    def ancestors(self, span: Span) -> list[Span]:
        """The spans enclosing ``span``, innermost first."""
        by_id, out = self._maps()[0], []
        p = span.parent
        while p is not None and p in by_id:
            out.append(by_id[p])
            p = by_id[p].parent
        return out

    def children(self, span: Span) -> list[Span]:
        return list(self._maps()[1].get(span.id, ()))

    def subtree(self, span: Span) -> list[Span]:
        """``span`` and every span opened inside it."""
        kids = self._maps()[1]
        out, todo = [], [span]
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(kids.get(s.id, ()))
        return out

    def self_ns(self, span: Span) -> int:
        """``span``'s time less its direct children's."""
        return span.ns - sum(s.ns for s in self.children(span))

    def outermost(self, spans: list[Span], prefix: str) -> list[Span]:
        """Those of ``spans`` named ``prefix...`` that no other span named
        ``prefix...`` encloses."""
        return [s for s in spans if s.name.startswith(prefix)
                and not any(a.name.startswith(prefix)
                            for a in self.ancestors(s))]

    @staticmethod
    def counted(spans: list[Span], name: str) -> int:
        """Counter ``name`` summed over ``spans``."""
        return sum(s.counters.get(name, 0) for s in spans)


_recorder = Recorder()


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _Noop()


class _Open:
    __slots__ = ("id", "parent", "name", "args", "counters", "t0",
                 "_annotation")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.counters: dict[str, int] = {}

    def __enter__(self):
        stack = _recorder._open
        self.id = next(_recorder._ids)
        self.parent = stack[-1].id if stack else None
        self._annotation = TraceAnnotation(PREFIX + self.name, **self.args)
        self._annotation.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        _recorder._open.pop()
        self._annotation.__exit__(*exc)
        _recorder.spans.append(Span(self.id, self.parent, self.name, self.t0,
                                    t1, self.args, self.counters))


_timing = False  # inside a Timed call


class Timed:
    """``fn``, counting its calls (``n``) and adding up their nanoseconds
    (``ns``): for calls too many for a span each.  A call made inside
    another ``Timed`` call is counted, and its time left to the outer one."""

    __slots__ = ("fn", "n", "ns")

    def __init__(self, fn):
        self.fn, self.n, self.ns = fn, 0, 0

    def __call__(self, *args):
        global _timing
        self.n += 1
        if _timing:
            return self.fn(*args)
        _timing = True
        t0 = time.perf_counter_ns()
        try:
            return self.fn(*args)
        finally:
            self.ns += time.perf_counter_ns() - t0
            _timing = False


def span(name: str, **args):
    """A span named ``"simdc." + name``; ``args`` (a round or step number)
    go on the profiler's event."""
    if not TraceAnnotation.is_enabled():
        return _NOOP
    return _Open(name, args)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span; outside any
    span, or with tracing off, nothing is recorded."""
    if _recorder._open:
        own = _recorder._open[-1].counters
        own[name] = own.get(name, 0) + n


def recorder() -> Recorder:
    return _recorder
