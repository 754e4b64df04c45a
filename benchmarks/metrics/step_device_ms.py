"""Layer: trainer. Median device duration of the step's XLA module (the
module with most device time in the slice) on the first chip."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    return tr.module_median_ms(events, *cell["window"])
