"""Host milliseconds per round in the DeviceFlow message plane: the
outermost ``simdc.flow.*`` spans (``simdc.flow.submit``, which holds the
sorter, the shelf and ``simdc.flow.dispatch``) less the time spent in the
delivery callback inside them (counter ``flow.deliver_ns``, the aggregation
service's intake), over the number of ``simdc.fl.round`` spans."""
from program_spans import in_window, named


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    rounds = named(spans, "fl.round")
    flow = rec.outermost(spans, "flow.")
    if not rounds or not flow:
        return None
    ns = sum(f.ns - rec.counted(rec.subtree(f), "flow.deliver_ns")
             for f in flow)
    return ns / len(rounds) * 1e-6
