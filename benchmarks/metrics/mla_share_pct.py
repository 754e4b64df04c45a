"""Layer: kernels. Device time of the decode program's ops that hold the
`mx_mla` scope (a latent-attention layer's projections, norms, rotation,
the latent row's write, the absorbed queries, `mxtpu_mla_decode` and the
value and output projections, for every slot) over the traced slice's
busy time on the first chip (`lib/scope_share.py`, as `kda_share_pct`).

`scope_share.share_pct` joins op names to scopes inside the slice's
DOMINANT module, and in this cell the prefill program (57 ms at 0.5 a
turn) and the decode program (30 ms) take about equal device time, so
which one dominates flips from run to run (PERF.md section 7). The map
is the decode program's: this reader hides the other modules' runs from
the join, so the share is always the decode program's ops; the busy time
under it is still everything the device did."""
from ..lib import scope_share, trace_reduce as tr

DECODE = "_decode_program"


def reduce(events, spans, counters, cell):
    scopes = scope_share.step_scopes("serve_lm_decode")
    if scopes is None:
        return None
    mine = [e for e in events if e[1] != tr.MODULES or DECODE in e[2]]
    return scope_share.share_pct(mine, *cell["window"], scopes, "mx_mla")
