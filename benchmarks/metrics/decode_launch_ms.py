"""Layer: decode runtime. Median `serve.decode_launch` span in the traced
slice: the dispatch of one decode step for all slots and the array
building that belongs to it, apart from the wait for its tokens
(`serve.decode_read`); both are children of `serve.decode_step`."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    return tr.span_median_ms(events, "serve.decode_launch", *cell["window"])
