"""Layer: kernels. Device time of the ops, of BOTH serving programs and
each joined in its own module's map, that hold `mx_par_ssm` (a parallel
layer's Mamba-2 half in a decode turn: its input and output multipliers,
the projection, convolution, `mxtpu_ssd_step`, gated norm and W_out) or
`mx_par_seq_ssm` (the same half over a prompt: the chunked scan) over the
traced slice's busy time on the first chip (`lib/program_share.py`). An
upper bound, as every scope share: a fusion that holds the scope counts
whole."""
from ..lib import program_share

SCOPES = ("mx_par_ssm", "mx_par_seq_ssm")


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.scope_pct(SCOPES)
