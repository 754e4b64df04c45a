"""Layer: server. Median `queue_wait_ms` of the traced slice's
`serve.admitted` instants: from `submit` to holding a decode slot (the
request's own prefill dispatch included), measured by the scheduler
where the request leaves the queue. Time to first token is this plus
the rest of that turn."""
import statistics


def reduce(events, spans, counters, cell):
    waits = [s[3]["queue_wait_ms"] for s in spans
             if s[0] == "serve.admitted" and s[3]]
    return statistics.median(waits) if waits else None
