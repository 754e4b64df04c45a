"""Layer: compile. Seconds jax spent compiling or loading programs from
the persistent cache during set-up (its own monitoring stream)."""


def reduce(events, spans, counters, cell):
    return counters["setup"]["compile_s"]
