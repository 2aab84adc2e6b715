"""Host milliseconds per ``ContinuousBatchingEngine.step`` of a
``granite_hybrid`` model outside its prefill and decode dispatches: each
``simdc.serve.step`` span whose dispatches carry ``family`` granite_hybrid,
less its ``simdc.serve.prefill`` and ``simdc.serve.decode`` children
(admission, building the padded prompt rows and the active mask, retiring
slots)."""
from program_spans import in_window, named

DISPATCHES = ("serve.prefill", "serve.decode")
FAMILY = "granite_hybrid"


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    ns = []
    for s in named(spans, "serve.step"):
        kids = [k for k in rec.children(s) if k.name in DISPATCHES]
        if kids and all(k.args.get("family") == FAMILY for k in kids):
            ns.append(s.ns - sum(k.ns for k in kids))
    if not ns:
        return None
    return sum(ns) / len(ns) * 1e-6
