"""Layer: server. The traced slice's length over the decode turns the
scheduler made in it (its own `decode_turns` tally: one
`profiler.record_dispatch("serve_decode")` a turn): what one turn costs
with the admissions, prefills and host work between dispatches."""


def reduce(events, spans, counters, cell):
    turns = counters.get("slice_decode_turns")
    if not turns:
        return None
    t0, t1 = cell["window"]
    return (t1 - t0) / 1e6 / turns
