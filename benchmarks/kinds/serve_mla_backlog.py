"""Kind `serve_mla_backlog`: offline batch generation from a
latent-attention decoder-only language model through `mx.serve.Server`,
decode-heavy with long answers. The window, the feeder, the corpus, the
counters and the traced slice are `serve_lm_backlog`'s (imported, not
copied: the feeder keeps `queued_slots` x slots requests queued beyond
the running ones, the window opens `warm_s` seconds after it starts, the
end-to-end number is the output tokens of whole decode turns). What
differs is what `lib/lm.py` and `serve_lm_backlog.py` weld to the
KDA-hybrid configuration: the builder (`lib/lm_mla.py`) and the check
against the plain reference, which here reads the LATENT rows the slots'
pages hold (no recurrent state, no convolution tails) beside the logits
and the expert ids chosen.
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from ..lib import harness, lm, lm_mla, serving
from .serve_backlog import feeder
from .serve_lm_backlog import (corpus, moe_since, slice_accounting,
                               trace_slice_at)

FIGURES = ("logits", "latent_c", "latent_rope", "routing")


def check_sequences(rt, vocab, seed, check):
    """(token ids (n, longest), prompt lengths (n,)): `check['requests']`
    seeded sequences with prompts from `check['prompt_from']` tokens to
    the server's longest, each followed by the `check['positions'] - 1`
    tokens that are forced after it; zeros beyond."""
    n, steps = check["requests"], check["positions"]
    rng = np.random.default_rng(int(seed) + 2)
    plen = np.linspace(min(check["prompt_from"], rt.max_src_len),
                       rt.max_src_len, n).astype(int)
    seqs = np.zeros((n, rt.max_src_len + steps - 1), np.int32)
    for i, p in enumerate(plen):
        seqs[i, :p + steps - 1] = rng.integers(4, vocab, p + steps - 1)
    return seqs, plen


def program_readings(srv, seqs, plen, steps):
    """What the timed path gives for the sequences: each prompt but its
    last token through `runtime.prefill` (latent rows into granted pages,
    slots 0..), then `steps` teacher-forced turns of `runtime.decode`
    through those pages, the server idle. {"logits" (n, steps, V);
    "routing" (n, layers, T, k), the expert ids each position chose, -1
    where it ran none; "latent": a list with an (n, T, kv_rank + r) array
    a layer, the rows the slots' pages hold after the last turn, zeros
    past a sequence's end}."""
    from mxnet_tpu.serve.kv_pages import NULL_PAGE
    rt, pool = srv.runtime, srv.pool
    n, total = seqs.shape
    layers, k = len(rt.spec.pattern), rt.spec.top_k
    width = rt.spec.kv_rank + rt.spec.rope_dim
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
    latent = []
    for pool_j in rt.latent_pages:
        rows = np.zeros((n, total, width), np.float32)
        for i, p in enumerate(plen):
            held = np.asarray(pool_j[np.asarray(pages[i])], np.float32)
            rows[i, :p + steps - 1] = held.reshape(
                -1, held.shape[-1])[:p + steps - 1, :width]
        latent.append(rows)
    for p in pages:
        pool.free(p)
    return {"logits": np.stack(logits, 1), "routing": routing,
            "latent": latent}


def reference_readings(forward, seqs, plen, steps, routing=None, **control):
    """The same readings from the plain reference's full forward, a
    sequence at a time. `routing` forces the expert ids (the float32
    reference against a subject); `control` computes it below the
    configuration's precision or with a term left out (a subject that has
    to fail)."""
    out = []
    for i, p in enumerate(plen):
        end = p + steps - 1
        r = forward(seqs[i], end, None if routing is None else routing[i],
                    **control)
        real = np.arange(seqs.shape[1]) < end
        out.append({
            "logits": np.asarray(r["logits"], np.float32)[p - 1:end],
            "routing": np.where(real[:, None], np.asarray(r["routing"]), -1),
            "slack": np.asarray(r["slack"])[:, :end].max(),
            "latent": [np.where(real[:, None], np.asarray(a, np.float32), 0)
                       for a in r["latent"]]})
    return {"logits": np.stack([r["logits"] for r in out]),
            "routing": np.stack([r["routing"] for r in out]),
            "slack": max(r["slack"] for r in out),
            "latent": [np.stack(a) for a in zip(*(r["latent"]
                                                  for r in out))]}


def figures(got, want, kv_rank):
    """A subject's readings against the float32 reference's, forced onto
    the subject's expert ids. Each is the LARGEST over the requests and
    what it names. "logits": a position's largest difference over the
    reference's largest logit, over every checked position ("logits_mid":
    the median position, logged); "latent_c" and "latent_rope": a
    request's and layer's cached rows, their `c_kv` and their `k_rope`
    values apart, largest difference over the reference's largest value
    (one wrong row of a thousand reads as large as all wrong);
    "routing": how far the lowest score the subject used lies under the
    reference's k-th largest (0 where the subject chose the reference's
    top-k; the scores are sigmoids)."""
    scale = np.abs(want["logits"]).max()
    off = np.abs(got["logits"] - want["logits"]).max(-1) / scale

    def part_off(part):
        return max(float((np.abs(a[..., part] - b[..., part]).max((1, 2))
                          / np.abs(b[..., part]).max((1, 2))).max())
                   for a, b in zip(got["latent"], want["latent"]))

    return {"logits": float(off.max()), "logits_mid": float(np.median(off)),
            "latent_c": part_off(slice(None, kv_rank)),
            "latent_rope": part_off(slice(kv_rank, None)),
            "routing": float(want["slack"])}


def reference_check(srv, model, cfg, seed, check, control=None):
    """The figures of `figures` for the cell's subject: the server's timed
    path, or with `control` ({"low": ...} or {"leave_out": ...}) the
    reference itself computed that way, which the same limits have to
    fail. The reference is forced onto the subject's own expert ids, as
    `serve_lm_backlog.reference_check` says why, every position is
    judged, and what the forcing cost is a figure of its own."""
    import jax
    ref = importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    weights, dims = lm_mla.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("low", "leave_out"))

    def forward(tokens, n, routing, **how):
        return jitted(weights, dims, tokens, n, routing, **how)

    steps = check["positions"]
    seqs, plen = check_sequences(srv.runtime, cfg["vocab_size"], seed, check)
    got = (reference_readings(forward, seqs, plen, steps, **control)
           if control else program_readings(srv, seqs, plen, steps))
    want = reference_readings(forward, seqs, plen, steps, got["routing"])
    return figures(got, want, model.spec.kv_rank)


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
        problems.append(f"{srv.pool.in_use()} latent pages still in use "
                        f"after the drain")
    return read


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    slots = cfg["server"]["slots"]
    keep = (1 + traffic["queued_slots"]) * slots
    compiles = harness.CompileWatch()
    t = time.perf_counter()
    model, srv = lm_mla.build_server(cfg, ctx["seed"], 2 * keep)
    rt = srv.runtime
    say(f"model and server built {time.perf_counter() - t:.2f} s; latent "
        f"pools {rt.latent_cache_bytes() / 1e9:.3f} GB as kept, "
        f"{srv.pool.num_pages * rt.kv_bytes_per_page() / 1e9:.3f} GB of "
        f"values; slot state {rt.slot_state_bytes()} bytes")
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
