"""Layer: server. What a decode turn costs the host beyond its decode
dispatch: mean, over the traced slice's `serve.turn` spans that hold a
`serve.decode_step`, of the turn's duration minus that child: admissions
with their prefill dispatches, planning, the commit loop, the deadline
sweep and the array building. The slice's first and last turn are left
out (`lib/span_reduce.py` says why). The run's log gets the turn's
phases and how many events the tracer recorded in the slice."""
from ..lib import span_reduce as sr

PHASES = ("serve.admit", "serve.plan", "serve.decode_step", "serve.commit")


def reduce(events, spans, counters, cell):
    steps = sr.named(spans, "serve.decode_step")
    turns = [t for t in sr.named(spans, "serve.turn")[1:-1]
             if sr.inside(steps, t)]
    if not turns:
        return None
    n = len(turns)
    kids = [s for t in turns for s in sr.inside(spans, t)]
    ms = {p: sum(s[2] for s in kids if s[0] == p) / n / 1e3
          for p in PHASES + ("serve.prefill",)}
    whole = sum(t[2] for t in turns) / n / 1e3
    prefills = sum(1 for s in kids if s[0] == "serve.prefill") / n
    from mxnet_tpu.observability import tracer
    print(f"[bench {cell.get('workload')}] mean ms over the slice's {n} "
          f"whole turns: turn {whole:.3f} = "
          + " + ".join(f"{p[6:]} {ms[p]:.3f}" for p in PHASES)
          + f" + the turn's own {whole - sum(ms[p] for p in PHASES):.3f}; "
          f"admit holds {prefills:.2f} prefills, {ms['serve.prefill']:.3f} "
          f"ms together; the tracer recorded {tracer.events_recorded()} "
          f"events in the slice", flush=True)
    return sum(sr.self_us(t, steps) for t in turns) / n / 1e3
