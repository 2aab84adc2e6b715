"""The program's own spans and counters (``repro.tracing``) inside the
harness's measured window, for the readers of the per-layer metrics that
split a round or a step by layer.

The program records them in memory while a profiler trace collects, so they
exist in ``--trace 1`` runs only.  A program without ``repro.tracing``, or
one that recorded nothing in the window, gives ``None``: its readers then
find nothing to read.
"""


def in_window(run):
    """``(recorder, spans wholly inside the window)``, or ``None``."""
    try:
        from repro import tracing
    except ImportError:
        return None
    windows = [(t0, t1) for n, t0, t1 in run.spans.records if n == "window"]
    if not windows:
        return None
    rec = tracing.recorder()
    spans = rec.window(*windows[-1])
    return (rec, spans) if spans else None


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]
