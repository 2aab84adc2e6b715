"""Host milliseconds per ``ContinuousBatchingEngine.step`` outside its
prefill and decode dispatches: each ``simdc.serve.step`` span less its
``simdc.serve.prefill`` and ``simdc.serve.decode`` children (admission,
building the padded prompt rows and the active mask, retiring slots)."""
from program_spans import in_window, named

DISPATCHES = ("serve.prefill", "serve.decode")


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    steps = named(spans, "serve.step")
    if not steps:
        return None
    ns = sum(s.ns - sum(k.ns for k in rec.children(s) if k.name in DISPATCHES)
             for s in steps)
    return ns / len(steps) * 1e-6
