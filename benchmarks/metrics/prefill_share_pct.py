"""Layer: decode runtime. The prefill program's share of the device: the
merged runs, on `XLA Modules`, of the executable whose name ends in
`prefill` (`serve_prefill`, `serve_lm_prefill`) over the traced slice's
busy time on the first chip (`lib/program_share.py`). What is left of
busy is the decode program's. It differs from slice to slice with the
prefills a slice of 1.5 s happens to hold: read `decode_turn_ms.serve`
and the scope shares of one run beside it."""
from ..lib import program_share


def reduce(events, spans, counters, cell):
    shares = program_share.reduce(events, *cell["window"])
    return shares and shares.program_pct("prefill")
