"""Layer: decode runtime. Median `serve.decode_step` span in the traced
slice: one decode dispatch for all slots and the wait for its tokens."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    return tr.span_median_ms(events, "serve.decode_step", *cell["window"])
