"""Layer: decode runtime. Median `serve.prefill` span in the traced
slice: one request's encoder pass and cross-attention K/V, dispatch
included."""
from ..lib import trace_reduce as tr


def reduce(events, spans, counters, cell):
    return tr.span_median_ms(events, "serve.prefill", *cell["window"])
