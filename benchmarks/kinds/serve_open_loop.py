"""Kind `serve_open_loop`: interactive translation. A generator thread
submits requests on a seeded Poisson schedule at the traffic file's fixed
`rate_rps` and never waits for a reply; arrivals start `lead_s` seconds
before the window so it opens in steady state and go on after it until
the sample has finished. The sample is the requests DUE inside the
window; time to first token counts from when a request was due, so a late
generator or a full queue shows in the tail and not as a faster server.
The window runs over a settled heap (`serving.settled_heap`): a tail is
what this kind judges, and one full collection of set-up's objects is two
decode turns long.
"""
from __future__ import annotations

import threading
import time

from ..lib import harness, serving


def generator(srv, reqs, dues, t_base, log, stop, errors):
    """Submit request i at t_base + dues[i]; `log` gets (due, sent,
    handle or None if refused, tokens asked for). Any other error ends
    the arrivals and is reported."""
    from mxnet_tpu.serve import ServeOverloaded
    try:
        for i, due in enumerate(dues):
            target = t_base + due
            # one wake-up an arrival: the thread takes the interpreter
            # from the scheduler's no more often than a client would
            while (wait := target - time.perf_counter()) > 0:
                if stop.wait(wait):
                    return
            src, out = reqs[i % len(reqs)]
            try:
                h = srv.submit(src, max_new_tokens=out)
            except ServeOverloaded:
                h = None
            log.append((target, time.perf_counter(), h, out))
    except Exception as e:      # the thread's boundary: report, not die
        errors.append(repr(e))


def schedule(cfg, traffic, seed, seconds):
    """(requests, due times): arrival i takes request i. The same
    requests and gaps before, in and after the window for every seed."""
    dues, counts = serving.arrival_times(
        traffic, seed, (traffic["lead_s"], seconds,
                        traffic["sample_timeout_s"]))
    return serving.corpus(traffic, seed, cfg["vocab_size"],
                          counts[:2]), dues


def open_loop(srv, reqs, dues, traffic, seconds, trace=False):
    """One window of the schedule. Returns a dict of what was seen; the
    caller judges it."""
    lead = traffic["lead_s"]
    log, errors, stop = [], [], threading.Event()
    t_base = time.perf_counter() + 0.05
    th = threading.Thread(target=generator, name="bench-generator",
                          daemon=True,
                          args=(srv, reqs, dues, t_base, log, stop, errors))
    th.start()
    try:
        t0, t1 = t_base + lead, t_base + lead + seconds
        time.sleep(max(0.0, t0 - time.perf_counter()))
        n0 = srv.scheduler.decode_turns
        ts = n_slice = None
        if trace:
            ts, n_slice = serving.trace_slice_at(t0, traffic, srv)
        time.sleep(max(0.0, t1 - time.perf_counter()))
        n1 = srv.scheduler.decode_turns
        queued_at_close = sum(1 for _, _, h, _ in list(log)
                              if h is not None and h.t_first_token is None)
        # arrivals go on until the sample is done, so its last requests
        # decode under the same load as its first
        deadline = time.perf_counter() + traffic["sample_timeout_s"]
        # the sample is whole once an arrival due after the window is in
        # the log; then only its unfinished requests are looked at
        while (not errors and time.perf_counter() < deadline
               and (not log or log[-1][0] < t1)):
            time.sleep(0.01)
        unfinished = [h for due, _, h, _ in list(log)
                      if t0 <= due < t1 and h is not None]
        while unfinished and not errors and time.perf_counter() < deadline:
            time.sleep(0.05)
            unfinished = [h for h in unfinished if not h.done()]
    finally:
        stop.set()
        th.join(timeout=30)
    sample = [e for e in log if t0 <= e[0] < t1]
    done = [(due, h) for due, _, h, _ in sample
            if h is not None and h.state == "done"]
    ok, failed, wrong = serving.tally([h for _, _, h, _ in sample],
                                      [w for _, _, _, w in sample])
    ttft = [1e3 * (h.t_first_token - due) for due, h in done]
    tpot = [1e3 * (h.t_done - h.t_first_token) / (len(h.tokens) - 1)
            for _, h in done if len(h.tokens) > 1]
    late = [1e3 * (sent - due) for due, sent, _, _ in sample]
    return {
        "t0": t0, "t1": t1, "log": log, "alive": th.is_alive(),
        "errors": errors,
        "attempted": len(sample), "ok": ok, "failed": failed,
        "wrong": wrong, "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
        "queued_at_close": queued_at_close, "decode_turns": n1 - n0,
        "tokens": sum(len(h.tokens) for _, h in done),
        "trace": ts, "slice_decode_turns": n_slice,
    }


def describe(w, say):
    p = serving.percentile
    say(f"{w['attempted']} requests due in {w['t1'] - w['t0']:.1f} s "
        f"({w['attempted'] / (w['t1'] - w['t0']):.1f} a second): {w['ok']} "
        f"right, {w['failed']} failed, {w['wrong']} of the wrong length; "
        f"{w['queued_at_close']} without a first token when the window "
        f"closed; {w['decode_turns']} decode turns")
    if w["ttft_ms"] and w["tpot_ms"]:
        say(f"time to first token from due, ms: p50 "
            f"{p(w['ttft_ms'], 50):.2f}, p95 {p(w['ttft_ms'], 95):.2f}, "
            f"max {max(w['ttft_ms']):.2f} over {len(w['ttft_ms'])}; time "
            f"per output token, ms: p50 {p(w['tpot_ms'], 50):.2f}, p95 "
            f"{p(w['tpot_ms'], 95):.2f} over {len(w['tpot_ms'])}")
        say(f"the generator sent late by, ms: p50 "
            f"{p(w['late_ms'], 50):.3f}, p95 {p(w['late_ms'], 95):.3f}, "
            f"max {max(w['late_ms']):.3f}")


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    compiles = harness.CompileWatch()
    model, srv = serving.build_server(cfg, ctx["seed"],
                                      traffic["max_queue"])
    reqs, dues = schedule(cfg, traffic, ctx["seed"], ctx["seconds"])
    t = time.perf_counter()
    serving.warm(srv, reqs, traffic["warm_requests"])
    say(f"warm requests (both executables compiled) "
        f"{time.perf_counter() - t:.2f} s")
    setup = compiles.since()
    compiles.mark()
    pauses = []
    with serving.settled_heap(pauses):
        w = open_loop(srv, reqs, dues, traffic, ctx["seconds"],
                      ctx["trace"])
    in_window = compiles.since()
    describe(w, say)
    full = [s for g, s in pauses if g == 2]
    say(f"the collector ran {len(pauses)} times from the first arrival to "
        f"the sample's end, {len(full)} of them full; longest pause "
        f"{1e3 * max([s for _, s in pauses], default=0.0):.2f} ms, longest "
        f"full one {1e3 * max(full, default=0.0):.2f} ms")
    problems = list(w["errors"])
    if w["alive"]:
        problems.append("the generator did not stop")
    if w["failed"] or w["wrong"] or not w["ok"]:
        problems.append(f"{w['failed']} failed, {w['wrong']} of the wrong "
                        f"length, {w['ok']} right")
    if harness.compiled(in_window):
        problems.append(f"compilation inside the window: {in_window}")
    serving.finish(srv, model, cfg, traffic, ctx["seed"],
                   [h for _, _, h, _ in w["log"]], say, problems)
    srv.close()
    p = serving.percentile
    return {
        "problems": problems, "attempted": w["attempted"],
        # the lead belongs to set-up: the window opens lead_s after the
        # first arrival
        "failed": w["failed"], "setup_s": w["t0"] - ctx["t_start"],
        "end_to_end": {
            "serve_ttft_p95_ms": p(w["ttft_ms"], 95),
            "serve_tpot_p95_ms": p(w["tpot_ms"], 95)},
        "counters": {"setup": setup, "window": in_window,
                     "decode_turns": w["decode_turns"],
                     "window_s": w["t1"] - w["t0"],
                     "slice_decode_turns": w["slice_decode_turns"]},
        "trace": w["trace"],
    }
