"""Layer: sharding. Time of all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute ops (issue and wait) on the first chip
over its busy time in the traced slice. Nothing to read on one chip."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    if cell["chips"] < 2:
        return None
    t0, t1 = cell["window"]
    plane = tr.device_planes(events)[0]
    busy = sum(b - a for a, b in tr.busy_intervals(events, plane, t0, t1))
    if not busy:
        return None
    return 100.0 * tr.collective_seconds(events, t0, t1, plane) * 1e9 / busy
