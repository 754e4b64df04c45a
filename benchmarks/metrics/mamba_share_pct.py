"""Layer: kernels. Device time of the decode program's ops that hold the
`mx_mamba` scope (a Mamba-2 layer's input projection, convolution, step
sizes, `mxtpu_ssd_step`, gated norm and output projection, for every
slot) over the traced slice's busy time on the first chip
(`lib/scope_share.py`, as `kda_share_pct`).

The map is the decode program's, so this reader hides the other modules'
runs from the join, as `mla_share_pct` does and says why (PERF.md section
7 (iv)): the share is always the decode program's ops, whichever program
dominates the slice; the busy time under it is everything the device
did."""
from ..lib import scope_share, trace_reduce as tr

DECODE = "_decode_program"


def reduce(events, spans, counters, cell):
    scopes = scope_share.step_scopes("serve_lm_decode")
    if scopes is None:
        return None
    mine = [e for e in events if e[1] != tr.MODULES or DECODE in e[2]]
    return scope_share.share_pct(mine, *cell["window"], scopes, "mx_mamba")
