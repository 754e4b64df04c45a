"""Kind `serve_afmoe_backlog`: offline batch generation over long inputs
from a decoder-only model of QK-normed, gated sliding-window and NoPE full
attention layers mixed, in sandwich norms, with a leading dense layer and
sigmoid-routed experts with a selection bias and a shared expert, through
`mx.serve.Server`. The feeder, the corpus, the expert counters and the
slice accounting are `serve_lm_backlog`'s; the window layers' counts, the
check against the plain reference (logits, expert ids, the keys the
slots' rings and pages hold) and its limits are `serve_swa_backlog`'s,
whose sequences are `serve_mla_backlog`'s: all imported, not copied.
What differs is the builder (`lib/lm_afmoe.py`) and the prefills' own
always-on counts (`LMRuntime.prefill_counters()`, among them the keys
the window layers' prefill attention had to read) taken at the traced
slice's edges beside the expert and window layers'.
"""
from __future__ import annotations

import threading
import time

from ..lib import harness, lm_afmoe, serving
from .serve_backlog import feeder
from .serve_lm_backlog import corpus, moe_since, slice_accounting
from .serve_swa_backlog import finish, window_since

__all__ = ["run", "corpus", "finish", "prefill_since", "trace_slice_at"]


def prefill_since(rt, before):
    """The runtime's prefill counts since `before` (its
    `prefill_counters()` then), the rungs left out."""
    now = rt.prefill_counters()
    return {k: now[k] - before[k] for k in now if k != "by_rung"}


def trace_slice_at(t_open, traffic, srv):
    """`serve_swa_backlog.trace_slice_at`, and the prefills' counts in the
    slice beside the expert and window layers': (slice, turns, expert
    counts, window counts, prefill counts)."""
    from ..lib.tracing import TraceSlice
    time.sleep(max(0.0, t_open + traffic["trace_after_s"]
                   - time.perf_counter()))
    rt = srv.runtime
    with TraceSlice() as ts:
        n0, m0, w0, p0 = (srv.scheduler.decode_turns, rt.moe_counters(),
                          rt.window_counters(), rt.prefill_counters())
        time.sleep(traffic["trace_s"])
        n1, moe, ring, pre = (srv.scheduler.decode_turns, moe_since(rt, m0),
                              window_since(rt, w0), prefill_since(rt, p0))
    return ts, n1 - n0, moe, ring, pre


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    slots = cfg["server"]["slots"]
    keep = (1 + traffic["queued_slots"]) * slots
    compiles = harness.CompileWatch()
    t = time.perf_counter()
    model, srv = lm_afmoe.build_server(cfg, ctx["seed"], 2 * keep)
    rt = srv.runtime
    say(f"model and server built {time.perf_counter() - t:.2f} s; the "
        f"window layers' rings {rt.ring_cache_bytes() / 1e9:.3f} GB "
        f"({rt.ring} pages a slot and layer, blocks of {rt.ring_block} "
        f"rows), the full layers' KV pool "
        f"{srv.pool.num_pages * rt.kv_bytes_per_page() / 1e9:.3f} GB")
    reqs = corpus(traffic, ctx["seed"], cfg["vocab_size"])
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
        e0, w0, p0 = (rt.moe_counters(), rt.window_counters(),
                      rt.prefill_counters())
        setup_s = t0 - ctx["t_start"]
        ts = n_slice = slice_moe = slice_ring = slice_prefill = None
        if ctx["trace"]:
            ts, n_slice, slice_moe, slice_ring, slice_prefill = \
                trace_slice_at(t0, traffic, srv)
            say(slice_accounting(ts, n_slice, slice_moe))
        time.sleep(max(0.0, t0 + ctx["seconds"] - time.perf_counter()))
        t1, n1 = time.perf_counter(), srv.scheduler.decode_turns
        window_moe, window_ring, window_prefill = (
            moe_since(rt, e0), window_since(rt, w0), prefill_since(rt, p0))
        in_window = compiles.since()
    finally:
        stop.set()
        th.join(timeout=30)
    if th.is_alive():
        problems.append("the feeder did not stop")
    handles = [h for h, _ in log]
    t = time.perf_counter()
    finish(srv, model, cfg, traffic, ctx["seed"], handles, say, problems)
    longest = max((h.t_done - h.t_admit for h in handles
                   if h.t_done and h.t_admit), default=0.0)
    say(f"drain and check {time.perf_counter() - t:.2f} s")

    inside = [(h, w) for h, w in log
              if h.t_done is not None and t0 <= h.t_done <= t1]
    ok, failed, wrong = serving.tally(*zip(*inside)) if inside else (0,) * 3
    tokens, span = serving.tokens_in_whole_turns(handles, t0, ctx["seconds"])
    rate = tokens / span
    turns = max(window_ring["turns"], 1)
    say(f"{len(inside)} requests finished inside {t1 - t0:.3f} s ({ok} "
        f"right, {failed} failed, {wrong} of the wrong length); "
        f"{n1 - n0} decode turns, {1e3 * (t1 - t0) / max(n1 - n0, 1):.2f} "
        f"ms a turn; the longest request held its slot {longest:.2f} s "
        f"(warm_s {traffic['warm_s']})")
    say(f"{tokens} tokens generated in the {span:.3f} s of whole decode "
        f"turns from the window's opening: {rate:.1f} tokens/s; a window "
        f"layer read {window_ring['ring_tokens'] / turns:.0f} keys a turn "
        f"and its kernel fetched {window_ring['ring_rows'] / turns:.0f} "
        f"ring rows, over {window_ring['turns']} turns; "
        f"{window_prefill['prefills']} prefills of "
        f"{window_prefill['prompt_tokens']} positions run at "
        f"{window_prefill['rung_tokens']}, a window layer's attention "
        f"{window_prefill['window_keys']} keys")
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
                     "slice_decode_turns": n_slice,
                     "window_moe": window_moe, "slice_moe": slice_moe,
                     "window_ring": window_ring, "slice_ring": slice_ring,
                     "window_prefill": window_prefill,
                     "slice_prefill": slice_prefill},
        "trace": ts,
    }
