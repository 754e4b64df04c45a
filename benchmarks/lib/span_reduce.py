"""The program tracer's spans, reduced by containment in time.

Works on the `(name, start_us, dur_us, args)` tuples that
`lib/tracing.pair_spans` hands every reader (instants have duration 0).
A span's children are the spans that lie wholly inside it in time; its
self time is its duration minus the part of it that children cover
(`choosing-metrics` section 4). The tuples carry no thread: the serve
turn's spans all come from the scheduler's thread, and what other threads
record inside a turn are instants. The tracer runs only inside the traced
slice, so nothing here needs clipping; a span the slice's edge cut is
either missing (the tracer started after it began) or closed early by the
tracer's repair (it stopped before the span ended), which is why a reader
of whole turns leaves out the slice's first and last.
"""
from __future__ import annotations

from .trace_reduce import union


def named(spans, name):
    """The spans called `name`, by start."""
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def inside(spans, parent):
    """The spans, other than `parent` itself, wholly inside it in time."""
    t0, t1 = parent[1], parent[1] + parent[2]
    return [s for s in spans
            if s is not parent and t0 <= s[1] and s[1] + s[2] <= t1]


def covered_us(spans):
    """Microseconds covered by the union of the spans' intervals."""
    return sum(b - a for a, b in union((s[1], s[1] + s[2]) for s in spans))


def self_us(parent, spans):
    """`parent`'s duration minus what its children among `spans` cover."""
    return parent[2] - covered_us(inside(spans, parent))

