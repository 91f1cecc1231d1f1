"""Idle share (%) of the busiest device over the traced window: 1 - the
union of the intervals in which an operation ran there, over the window.
Nothing where the trace has no device plane."""

import trace_reduce


def read(record: dict, arg: dict):
    if record["trace"] is None:
        return None
    busy = trace_reduce.busy_seconds(record["trace"])
    if not busy:
        return None
    return 100.0 * (1.0 - max(busy) / (record["traced_ns"] / 1e9))
