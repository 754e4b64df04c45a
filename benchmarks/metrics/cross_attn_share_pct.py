"""Layer: kernels. Device time of the translation server's decode
program's ops that hold `mx_cross_attn` (the query projection, attention
over a slot's encoder memory in both memory buffers, the output
projection with its residual and norm) over the traced slice's busy time
on the first chip (`lib/program_share.py`)."""
from ..lib import program_share


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.scope_pct(("mx_cross_attn",), "decode")
