"""Layer: expert layer. Device time of the ops, of BOTH serving programs
and each joined in its own module's map, that hold `mx_moe_dispatch` (the
layout and the scatter of rows into the tiles) or `mx_moe_combine` (the
gather back, the select, the weighted sum) over the traced slice's busy
time on the first chip (`lib/program_share.py`): what the expert layer
spends moving rows, not multiplying them. An upper bound, as every scope
share: a fusion that holds the scope counts whole."""
from ..lib import program_share

SCOPES = ("mx_moe_dispatch", "mx_moe_combine")


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.scope_pct(SCOPES)
