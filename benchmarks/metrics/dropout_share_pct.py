"""Layer: kernels. Device time of the step's ops that hold the
`mx_dropout` scope (the random bits and the mask multiply, forward and
backward) over the traced slice's busy time on the first chip; a fusion
that mixes dropout with a neighbour counts whole
(`lib/scope_share.py`)."""
from ..lib import scope_share


def reduce(events, spans, counters, cell):
    return scope_share.reduce(events, cell["window"], "mx_dropout")
