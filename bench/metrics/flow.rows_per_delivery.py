"""Rows the DeviceFlow dispatcher hands the aggregation service per
delivery, from its counters ``flow.rows_dispatched`` and
``flow.deliveries``: 1.0 means one delivery, and one intake call, per
device."""
from program_spans import in_window


def read(run):
    got = in_window(run)
    if got is None:
        return None
    rec, spans = got
    deliveries = rec.counted(spans, "flow.deliveries")
    if not deliveries:
        return None
    return rec.counted(spans, "flow.rows_dispatched") / deliveries
