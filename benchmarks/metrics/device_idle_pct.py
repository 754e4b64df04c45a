"""Layer: device. Share of the traced slice in which no XLA op ran,
averaged over the chips used: 1 - union of `XLA Ops` intervals / slice."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    return tr.idle_pct(events, *cell["window"])
