"""Kind `serve_ssm_backlog`: offline batch generation from a decoder-only
language model of ONE sub-layer a layer (Mamba-2 state-space layers,
expert layers, attention) through `mx.serve.Server`, decode-heavy with
long answers. The window, the feeder, the corpus, the counters, the
traced slice, the check's sequences and its figures are
`serve_lm_backlog`'s (imported, not copied, as `serve_mla_backlog`
does). What differs is what `lib/lm.py` and `serve_lm_backlog.py` weld to
the KDA-hybrid configuration: the builder (`lib/lm_ssm.py`) and where the
check reads the slots' recurrent state (`LMRuntime.ssm_state`, kept
(H, P, N) as the reference keeps it).
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from ..lib import harness, lm, lm_ssm, serving
from .serve_backlog import feeder
from . import serve_lm_backlog as lm_kind
from .serve_lm_backlog import (FIGURES, check_sequences, corpus, figures,
                               moe_since, reference_readings,
                               slice_accounting, trace_slice_at)


def program_readings(srv, seqs, plen, steps):
    """`serve_lm_backlog.program_readings` (each prompt but its last
    token through `runtime.prefill`, then `steps` teacher-forced turns
    of `runtime.decode`, the server idle; `tails` are the runtime's
    `conv_tails`, which hold the Mamba-2 layers' too), with "state" the
    slots' Mamba-2 state, an (n, H, P, N) array a layer as the slots
    hold them after the last turn."""
    got = lm_kind.program_readings(srv, seqs, plen, steps)
    got["state"] = [np.asarray(s[:len(plen)]) for s in srv.runtime.ssm_state]
    return got


def reference_check(srv, model, cfg, seed, check, control=None):
    """The figures of `serve_lm_backlog.figures` for the cell's subject:
    the server's timed path, or with `control` ({"low": ...} or
    {"leave_out": ...}) the reference itself computed that way, which the
    same limits have to fail. The reference is forced onto the subject's
    own expert ids, as `serve_lm_backlog.reference_check` says why."""
    import jax
    ref = importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    weights, dims = lm_ssm.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("low", "leave_out"))

    def forward(tokens, n, routing, **how):
        return jitted(weights, dims, tokens, n, routing, **how)

    steps = check["positions"]
    seqs, plen = check_sequences(srv.runtime, cfg["vocab_size"], seed, check)
    got = (reference_readings(forward, seqs, plen, steps, **control)
           if control else program_readings(srv, seqs, plen, steps))
    want = reference_readings(forward, seqs, plen, steps, got["routing"])
    return figures(got, want)


def finish(srv, model, cfg, traffic, seed, handles, say, problems,
           control=None):
    """After the window: drain, the program's invariants, the check
    against the reference (of the server's timed path, or of `control`,
    `reference_check`'s). Appends to `problems`."""
    if not serving.drain(srv, handles, timeout=600):
        problems.append("the server did not drain")
    check = traffic["logit_check"]
    read = reference_check(srv, model, cfg, seed, check, control)
    rt = srv.runtime            # the window's traffic and the check's
    if rt.decode_traces != 1 or rt.prefill_traces != 1:
        problems.append(f"decode traced {rt.decode_traces}x, prefill "
                        f"{rt.prefill_traces}x")
    limits = check["limits"]
    say(f"{'the program' if control is None else control} against the "
        f"float32 reference on its expert ids, largest of "
        f"{check['requests']} requests x {check['positions']} positions "
        f"(limit): " + ", ".join(f"{k} {read[k]:.2e} ({limits[k]})"
                                 for k in FIGURES)
        + f"; logits at the median position {read['logits_mid']:.2e}")
    for k in FIGURES:
        if not read[k] <= limits[k]:
            problems.append(f"{k} off the reference: {read[k]:.2e} over "
                            f"the limit {limits[k]}")
    if srv.pool.in_use() != 0:
        problems.append(f"{srv.pool.in_use()} KV pages still in use "
                        f"after the drain")
    return read


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    slots = cfg["server"]["slots"]
    keep = (1 + traffic["queued_slots"]) * slots
    compiles = harness.CompileWatch()
    t = time.perf_counter()
    model, srv = lm_ssm.build_server(cfg, ctx["seed"], 2 * keep)
    rt = srv.runtime
    say(f"model and server built {time.perf_counter() - t:.2f} s; slot "
        f"state {rt.slot_state_bytes() / 1e9:.3f} GB, KV pool "
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
        e0 = rt.moe_counters()
        setup_s = t0 - ctx["t_start"]
        ts = n_slice = slice_moe = None
        if ctx["trace"]:
            ts, n_slice, slice_moe = trace_slice_at(t0, traffic, srv)
            say(slice_accounting(ts, n_slice, slice_moe))
        time.sleep(max(0.0, t0 + ctx["seconds"] - time.perf_counter()))
        t1, n1 = time.perf_counter(), srv.scheduler.decode_turns
        window_moe = moe_since(rt, e0)
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
    say(f"{len(inside)} requests finished inside {t1 - t0:.3f} s ({ok} "
        f"right, {failed} failed, {wrong} of the wrong length); "
        f"{n1 - n0} decode turns, {1e3 * (t1 - t0) / max(n1 - n0, 1):.2f} "
        f"ms a turn; the longest request held its slot {longest:.2f} s "
        f"(warm_s {traffic['warm_s']})")
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
                     "slice_decode_turns": n_slice,
                     "window_moe": window_moe, "slice_moe": slice_moe},
        "trace": ts,
    }
