"""Layer: server. The share of a decode turn in which the scheduler's
thread works and does not wait for the device: 100 x (1 - the summed
`serve.decode_read` spans of the slice's whole turns / their summed
`serve.turn`). The slice's first and last turn are left out
(`lib/span_reduce.py` says why). With a turn in flight the read's wait is
the host's slack: at 100 the host sets the pace again."""
from ..lib import span_reduce as sr


def reduce(events, spans, counters, cell):
    reads = sr.named(spans, "serve.decode_read")
    # (a whole turn that reads, the microseconds it waited in its reads)
    turns = [(t, sum(r[2] for r in mine))
             for t in sr.named(spans, "serve.turn")[1:-1]
             if (mine := sr.inside(reads, t))]
    if not turns:
        return None
    return 100.0 * (1.0 - sum(w for _, w in turns)
                    / sum(t[2] for t, _ in turns))
