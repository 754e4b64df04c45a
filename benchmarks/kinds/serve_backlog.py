"""Kind `serve_backlog`: offline batch translation through
`mx.serve.Server`. A feeder thread keeps `queued_slots` x slots requests
outstanding beyond the running ones, so every slot is always busy; the
window opens `warm_s` seconds after the feeder starts (the slots' first
synchronized wave has spread out by then). The end-to-end number is the
output tokens generated in the whole decode turns from the window's
opening to the first turn end at or after `--seconds`, over that time
(`lib.serving.tokens_in_whole_turns`), as the training window closes on a
whole step.
"""
from __future__ import annotations

import threading
import time

from ..lib import harness, serving


def feeder(srv, reqs, keep, log, stop, errors):
    """Keep `keep` requests outstanding (running + queued); `log` gets
    (handle, tokens asked for). A refusal ends the feed and is reported."""
    live, i = [], 0
    try:
        while not stop.is_set():
            live = [h for h in live if not h.done()]
            while len(live) < keep:
                src, out = reqs[i % len(reqs)]
                h = srv.submit(src, max_new_tokens=out)
                live.append(h)
                log.append((h, out))
                i += 1
            time.sleep(0.004)
    except Exception as e:      # the thread's boundary: report, not die
        errors.append(repr(e))


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    slots = cfg["server"]["slots"]
    keep = (1 + traffic["queued_slots"]) * slots
    compiles = harness.CompileWatch()
    model, srv = serving.build_server(cfg, ctx["seed"], 2 * keep)
    reqs = serving.corpus(traffic, ctx["seed"], cfg["vocab_size"])
    t = time.perf_counter()
    serving.warm(srv, reqs, traffic["warm_requests"])
    say(f"warm requests (both executables compiled) "
        f"{time.perf_counter() - t:.2f} s")
    log, stop, problems = [], threading.Event(), []
    th = threading.Thread(target=feeder, name="bench-feeder", daemon=True,
                          args=(srv, reqs, keep, log, stop, problems))
    th.start()
    try:
        time.sleep(traffic["warm_s"])
        setup = compiles.since()
        compiles.mark()
        t0, n0 = time.perf_counter(), srv.scheduler.decode_turns
        setup_s = t0 - ctx["t_start"]
        ts = n_slice = None
        if ctx["trace"]:
            ts, n_slice = serving.trace_slice_at(t0, traffic, srv)
        time.sleep(max(0.0, t0 + ctx["seconds"] - time.perf_counter()))
        t1, n1 = time.perf_counter(), srv.scheduler.decode_turns
        in_window = compiles.since()
    finally:
        stop.set()
        th.join(timeout=30)
    if th.is_alive():
        problems.append("the feeder did not stop")
    serving.finish(srv, model, cfg, traffic, ctx["seed"],
                   [h for h, _ in log], say, problems)

    inside = [(h, w) for h, w in log
              if h.t_done is not None and t0 <= h.t_done <= t1]
    ok, failed, wrong = serving.tally(*zip(*inside)) if inside else (0,) * 3
    tokens, span = serving.tokens_in_whole_turns(
        [h for h, _ in log], t0, ctx["seconds"])
    rate = tokens / span
    say(f"{len(inside)} requests finished inside {t1 - t0:.3f} s ({ok} "
        f"right, {failed} failed, {wrong} of the wrong length), "
        f"{sum(len(h.tokens) for h, _ in inside)} tokens theirs; "
        f"{n1 - n0} decode turns, {1e3 * (t1 - t0) / max(n1 - n0, 1):.2f} "
        f"ms a turn")
    say(f"{tokens} tokens generated in the {span:.3f} s of whole decode "
        f"turns from the window's opening: {rate:.1f} tokens/s")
    if failed or wrong or not ok:
        problems.append(f"{failed} failed, {wrong} of the wrong length, "
                        f"{ok} right")
    if harness.compiled(in_window):
        problems.append(f"compilation inside the window: {in_window}")
    srv.close()
    return {
        "problems": problems, "attempted": len(inside), "failed": failed,
        "setup_s": setup_s, "end_to_end": {"serve_tokens_per_s": rate},
        "counters": {"setup": setup, "window": in_window,
                     "decode_turns": n1 - n0, "window_s": t1 - t0,
                     "slice_decode_turns": n_slice},
        "trace": ts,
    }
