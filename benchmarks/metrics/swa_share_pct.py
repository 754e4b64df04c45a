"""Layer: kernels. Device time of the ops, of BOTH serving programs and
each joined in its own module's map, that hold `mx_swa` (a sliding-window
layer's decode turn: projection, rotation, the ring's write,
`mxtpu_rpa_ring`, W_o) or `mx_swa_seq` (the same layer over a prompt: the
windowed flash kernel and the ring's fill) over the traced slice's busy
time on the first chip (`lib/program_share.py`). An upper bound, as every
scope share: a fusion that holds the scope counts whole."""
from ..lib import program_share

SCOPES = ("mx_swa", "mx_swa_seq")


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.scope_pct(SCOPES)
