"""Host milliseconds per round in the aggregation service: the time the
DeviceFlow dispatcher spends in its delivery callback, the service's intake
(counter ``flow.deliver_ns``, which holds the aggregation when a delivery
fires it), plus each ``simdc.agg.apply`` span that no ``simdc.flow.dispatch``
encloses (an aggregation outside any delivery), over the number of
``simdc.fl.round`` spans."""
from program_spans import in_window, named


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    rounds = named(spans, "fl.round")
    if not rounds or not rec.counted(spans, "flow.deliveries"):
        return None
    apart = sum(s.ns for s in named(spans, "agg.apply")
                if not any(a.name == "flow.dispatch"
                           for a in rec.ancestors(s)))
    return (rec.counted(spans, "flow.deliver_ns") + apart) / len(rounds) * 1e-6
