"""Layer: kernels. Device time of the ops, of BOTH serving programs and
each joined in its own module's map, that hold `mx_par_attn` (a parallel
layer's attention half in a decode turn: its multipliers, the projection,
rotation, the page write, `mxtpu_rpa_flat`, W_o) or `mx_par_seq_attn`
(the same half over a prompt: the flash kernel) over the traced slice's
busy time on the first chip (`lib/program_share.py`), as
`par_ssm_share_pct` reads the other half."""
from ..lib import program_share

SCOPES = ("mx_par_attn", "mx_par_seq_attn")


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.scope_pct(SCOPES)
