"""Milliseconds per round from ``DeviceFlow.run()`` until the new global
params are ready: the message plane's dispatch and the aggregation service's
fused ``fed_reduce`` reduce-and-apply, from the harness's ``fl.flow_drain``
span."""


def read(run):
    s = run.spans.in_window("fl.flow_drain")
    return sum(s) / len(s) * 1e3 if s else None
