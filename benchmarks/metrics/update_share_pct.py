"""Layer: trainer. Device time of the step's ops that hold the
`mx_update` scope (the optimizer update inside the captured program)
over the traced slice's busy time on the first chip; a fusion that mixes
the update with a neighbour counts whole (`lib/scope_share.py`)."""
from ..lib import scope_share


def reduce(events, spans, counters, cell):
    return scope_share.reduce(events, cell["window"], "mx_update")
