"""Kind `serve_swa_backlog`: offline batch generation from a decoder-only
language model of sliding-window and full attention layers mixed through
`mx.serve.Server`, long prompts and answers of hundreds of tokens. The
window, the feeder, the corpus, the expert counters and the slice
accounting are `serve_lm_backlog`'s (imported, not copied, as
`serve_mla_backlog` and `serve_ssm_backlog` do; the check's sequences are
`serve_mla_backlog`'s). What differs is the builder (`lib/lm_swa.py`), the
window layers' always-on counts read at the window's and the slice's
edges beside the expert layers' (`LMRuntime.window_counters()`), and the
check against the plain reference, which here reads the KEYS the slots'
rings and pages hold (no recurrent state) beside the logits and the
expert ids chosen.
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from ..lib import harness, lm, lm_swa, serving
from .serve_backlog import feeder
from .serve_lm_backlog import corpus, moe_since, slice_accounting
from .serve_mla_backlog import check_sequences

FIGURES = ("logits", "routing", "ring", "pages")


def program_readings(srv, seqs, plen, steps):
    """What the timed path gives for the sequences: each prompt but its
    last token through `runtime.prefill` (the full layers' K/V into
    granted pages, the window layers' into the slots' rings, slots 0..),
    then `steps` teacher-forced turns of `runtime.decode` through those
    pages and rings, the server idle. {"logits" (n, steps, V); "routing"
    (n, layers, T, k), the expert ids each position chose; "keys": a
    list with an (n, T, Hkv * dh) array a layer, the keys the slot's ring
    (a window layer: the last `window` positions, each from its ring
    row) or pages (a full layer: every position) hold after the last
    turn, zeros elsewhere}."""
    from mxnet_tpu.serve.kv_pages import NULL_PAGE
    rt, pool = srv.runtime, srv.pool
    n, total = seqs.shape
    layers, k = len(rt.spec.pattern), rt.spec.top_k
    routing = np.full((n, layers, total, k), -1, np.int32)
    tables = np.full((rt.slots, rt.max_pages_per_slot), NULL_PAGE, np.int32)
    pages = []
    for i, p in enumerate(plen):
        pages.append(pool.alloc(pool.pages_for(p + steps - 1)))
        tables[i, :len(pages[i])] = pages[i]
        rt.prefill(i, seqs[i, :p], pages[i])
        routing[i, :, :p - 1] = np.asarray(rt.routing["prefill"])[:, :p - 1]
    active = np.zeros((rt.slots,), np.int32)
    active[:n] = 1
    cur = np.zeros((rt.slots,), np.int32)
    lens = np.zeros((rt.slots,), np.int32)
    logits = []
    for t in range(steps):
        cur[:n] = seqs[np.arange(n), plen - 1 + t]
        lens[:n] = plen - 1 + t
        _, lg = rt.decode(tables, lens, cur, active)
        logits.append(np.asarray(lg[:n]))
        routing[np.arange(n), :, plen - 1 + t] = np.asarray(
            rt.routing["decode"])[:, :n].swapaxes(0, 1)
    rings, paged = iter(rt.ring_pages), iter(rt.kv_pages)
    rows = rt.ring * rt.page_size             # a slot's ring, in rows
    keys = []
    for kind in rt.spec.pattern:
        k_pool = next(rings if kind == "swa" else paged)[0]
        held = np.zeros((n, total, k_pool.shape[-1]), np.float32)
        for i, p in enumerate(plen):
            end = p + steps - 1               # positions the slot holds
            if kind == "swa":
                ring = np.asarray(k_pool[i * rt.ring:(i + 1) * rt.ring],
                                  np.float32).reshape(rows, -1)
                pos = np.arange(max(0, end - rt.spec.window), end)
                held[i, pos] = ring[pos % rows]
            else:
                mine = np.asarray(k_pool[np.asarray(pages[i])], np.float32)
                held[i, :end] = mine.reshape(-1, mine.shape[-1])[:end]
        keys.append(held)
    for p in pages:
        pool.free(p)
    return {"logits": np.stack(logits, 1), "routing": routing, "keys": keys}


def reference_readings(forward, seqs, plen, steps, window, kinds,
                       routing=None, **control):
    """The same readings from the plain reference's full forward, a
    sequence at a time: a window layer's keys at the last `window`
    positions a slot holds, a full layer's at all of them. `routing`
    forces the expert ids (the float32 reference against a subject);
    `control` computes it below the configuration's precision or with a
    term left out (a subject that has to fail)."""
    out = []
    at = np.arange(seqs.shape[1])
    for i, p in enumerate(plen):
        end = p + steps - 1
        r = forward(seqs[i], p - 1, None if routing is None else routing[i],
                    **control)
        real = at < end
        recent = real & (at >= end - window)
        out.append({
            "logits": np.asarray(r["logits"], np.float32),
            "routing": np.where(real[:, None], np.asarray(r["routing"]), -1),
            "slack": np.asarray(r["slack"])[:, :end].max(),
            "keys": [np.where((recent if kind == "swa" else real)[:, None],
                              np.asarray(a, np.float32), 0)
                     for kind, a in zip(kinds, r["keys"])]})
    return {"logits": np.stack([r["logits"] for r in out]),
            "routing": np.stack([r["routing"] for r in out]),
            "slack": max(r["slack"] for r in out),
            "keys": [np.stack(a) for a in zip(*(r["keys"] for r in out))]}


def figures(got, want, kinds):
    """A subject's readings against the float32 reference's, forced onto
    the subject's expert ids. Each is the LARGEST over the requests and
    what it names. "logits": a position's largest difference over the
    reference's largest logit, over every checked position ("logits_mid":
    the median position, logged); "ring": a request's and window layer's
    ring rows put back in position order against the reference's rotated
    keys at the last positions a query may read, largest difference over
    the reference's largest value (one wrong row of a thousand reads as
    large as all wrong: it judges the rotation and the placement at
    once); "pages": the same for a full layer's pages, every position,
    which judges the scaled table; "routing": how far the lowest
    probability the subject used lies under the reference's k-th largest
    (0 where the subject chose the reference's top-k; the scores are one
    softmax over all experts)."""
    scale = np.abs(want["logits"]).max()
    off = np.abs(got["logits"] - want["logits"]).max(-1) / scale

    def keys_off(which):
        return max((float((np.abs(a - b).max((1, 2))
                           / np.abs(b).max((1, 2))).max())
                    for kind, a, b in zip(kinds, got["keys"], want["keys"])
                    if kind == which), default=0.0)

    return {"logits": float(off.max()), "logits_mid": float(np.median(off)),
            "routing": float(want["slack"]), "ring": keys_off("swa"),
            "pages": keys_off("gqa")}


def reference_check(srv, model, cfg, seed, check, control=None):
    """The figures of `figures` for the cell's subject: the server's timed
    path, or with `control` ({"low": ...} or {"leave_out": ...}) the
    reference itself computed that way, which the same limits have to
    fail. The reference is forced onto the subject's own expert ids, as
    `serve_lm_backlog.reference_check` says why, every position is
    judged, and what the forcing cost is a figure of its own."""
    import jax
    ref = importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    weights, dims = lm_swa.reference_weights(model), lm.dims(model.spec)
    steps = check["positions"]
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("low", "leave_out", "head_rows"))

    def forward(tokens, head_from, routing, **how):
        return jitted(weights, dims, tokens, None, routing,
                      head_from=head_from, head_rows=steps, **how)

    spec = model.spec
    seqs, plen = check_sequences(srv.runtime, cfg["vocab_size"], seed, check)
    got = (reference_readings(forward, seqs, plen, steps, spec.window,
                              spec.pattern, **control)
           if control else program_readings(srv, seqs, plen, steps))
    want = reference_readings(forward, seqs, plen, steps, spec.window,
                              spec.pattern, got["routing"])
    return figures(got, want, spec.pattern)


def window_since(rt, before):
    """The runtime's window-layer counts since `before` (its
    `window_counters()` then)."""
    now = rt.window_counters()
    return {k: now[k] - before[k] for k in now}


def trace_slice_at(t_open, traffic, srv):
    """`serve_lm_backlog.trace_slice_at`, and the window layers' counts
    in the slice beside the expert layers': (slice, turns, expert counts,
    window counts)."""
    from ..lib.tracing import TraceSlice
    time.sleep(max(0.0, t_open + traffic["trace_after_s"]
                   - time.perf_counter()))
    rt = srv.runtime
    with TraceSlice() as ts:
        n0, m0, w0 = (srv.scheduler.decode_turns, rt.moe_counters(),
                      rt.window_counters())
        time.sleep(traffic["trace_s"])
        n1, moe, ring = (srv.scheduler.decode_turns, moe_since(rt, m0),
                         window_since(rt, w0))
    return ts, n1 - n0, moe, ring


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
    model, srv = lm_swa.build_server(cfg, ctx["seed"], 2 * keep)
    rt = srv.runtime
    say(f"model and server built {time.perf_counter() - t:.2f} s; the "
        f"window layers' rings {rt.ring_cache_bytes() / 1e9:.3f} GB "
        f"({rt.ring} pages a slot and layer), the full layers' KV pool "
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
        e0, w0 = rt.moe_counters(), rt.window_counters()
        setup_s = t0 - ctx["t_start"]
        ts = n_slice = slice_moe = slice_ring = None
        if ctx["trace"]:
            ts, n_slice, slice_moe, slice_ring = trace_slice_at(
                t0, traffic, srv)
            say(slice_accounting(ts, n_slice, slice_moe))
        time.sleep(max(0.0, t0 + ctx["seconds"] - time.perf_counter()))
        t1, n1 = time.perf_counter(), srv.scheduler.decode_turns
        window_moe, window_ring = moe_since(rt, e0), window_since(rt, w0)
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
        f"turns from the window's opening: {rate:.1f} tokens/s; a window "
        f"layer read {window_ring['ring_tokens'] / max(window_ring['turns'], 1):.0f} "
        f"keys a turn over {window_ring['turns']} turns")
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
                     "window_ring": window_ring, "slice_ring": slice_ring},
        "trace": ts,
    }
