"""Kind `serve_par_backlog`: offline batch generation from a decoder-only
language model of PARALLEL hybrid layers (a Mamba-2 and an attention
mixer side by side in every layer, then a dense SwiGLU) through
`mx.serve.Server`, prompts and answers of hundreds of tokens. The window,
the feeder, the corpus, the counters, the traced slice and the check's
figures are `serve_lm_backlog`'s (imported, not copied, as
`serve_ssm_backlog` does; the check's sequences are `serve_mla_backlog`'s).
What differs is the builder (`lib/lm_par.py`), the check, which reads
BOTH kinds of state a layer keeps (the slots' Mamba-2 state and tails
and the keys their pages hold) and computes its controls as traced knobs
of one compiled reference, and the state shape the decode kernel walks,
recorded for `par_ssd_roofline` (`counters["ssm_shape"]`).
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from ..lib import harness, lm, lm_par, serving
from .serve_backlog import feeder
from . import serve_lm_backlog as lm_kind
from .serve_lm_backlog import corpus, moe_since, slice_accounting, \
    trace_slice_at
from .serve_mla_backlog import check_sequences

FIGURES = ("logits", "state", "tails", "pages", "state_bf16_share")


def program_readings(srv, seqs, plen, steps):
    """What the timed path gives for the sequences: each prompt but its
    last token through `runtime.prefill` (pages and slot state, slots
    0..), then `steps` teacher-forced turns of `runtime.decode` through
    both, the server idle. {"logits" (n, steps, V); "state", "tails": a
    list with an (n, ...) array a layer, as the slots hold them after the
    last turn; "keys": a list with an (n, T, Hkv * dh) array a layer, the
    keys the slot's pages hold, zeros past a sequence's end}."""
    from mxnet_tpu.serve.kv_pages import NULL_PAGE
    rt, pool = srv.runtime, srv.pool
    n, total = seqs.shape
    tables = np.full((rt.slots, rt.max_pages_per_slot), NULL_PAGE, np.int32)
    pages = []
    for i, p in enumerate(plen):
        pages.append(pool.alloc(pool.pages_for(p + steps - 1)))
        tables[i, :len(pages[i])] = pages[i]
        rt.prefill(i, seqs[i, :p], pages[i])
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
    keys = []
    for k_pool, _ in rt.kv_pages:
        held = np.zeros((n, total, k_pool.shape[-1]), np.float32)
        for i, p in enumerate(plen):
            mine = np.asarray(k_pool[np.asarray(pages[i])], np.float32)
            end = p + steps - 1               # positions the slot holds
            held[i, :end] = mine.reshape(-1, mine.shape[-1])[:end]
        keys.append(held)
    for p in pages:
        pool.free(p)
    return {"logits": np.stack(logits, 1),
            "state": [np.asarray(s[:n]) for s in rt.ssm_state],
            "tails": [np.asarray(c[:n], np.float32) for c in rt.conv_tails],
            "keys": keys}


def reference_readings(forward, seqs, plen, steps, **control):
    """The same readings from the plain reference's full forward, a
    sequence at a time (`control` computes it below the configuration's
    precision or with a term left out: a subject that has to fail)."""
    out = []
    at = np.arange(seqs.shape[1])
    for i, p in enumerate(plen):
        end = p + steps - 1
        r = forward(seqs[i], end, p - 1, **control)
        out.append({
            "logits": np.asarray(r["logits"], np.float32),
            "state": [np.asarray(a, np.float32) for a in r["state"]],
            "tails": [np.asarray(a, np.float32) for a in r["tails"]],
            "keys": [np.where((at < end)[:, None], np.asarray(a, np.float32),
                              0) for a in r["keys"]]})
    return {k: np.stack([r[k] for r in out]) if k == "logits" else
            [np.stack(a) for a in zip(*(r[k] for r in out))]
            for k in ("logits", "state", "tails", "keys")}


def figures(got, want):
    """`serve_lm_backlog.figures` (logits, state, tails,
    state_bf16_share; no routing here), and "pages": a request's and
    layer's cached keys against the reference's rotated keys times
    `key_multiplier`, every position the slot holds, largest difference
    over the reference's largest value (one wrong row reads as large as
    all wrong: it judges the rotation, the multiplier and the placement
    at once)."""
    read = lm_kind.figures(got, dict(want, slack=0.0))
    read["pages"] = max(float((np.abs(a - b).max((1, 2))
                               / np.abs(b).max((1, 2))).max())
                        for a, b in zip(got["keys"], want["keys"]))
    return read


def reference_check(srv, model, cfg, seed, check, control=None):
    """The figures of `figures` for the cell's subject: the server's timed
    path, or with `control` ({"low": ...} and / or {"knobs": {name:
    value}}, the reference's `knobs`) the reference itself computed that
    way, which the same limits have to fail. The knobs are traced: every
    control but a lower precision runs the one compiled forward."""
    import jax
    ref = importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    weights, dims = lm_par.reference_weights(model), lm.dims(model.spec)
    steps = check["positions"]
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("low", "head_rows"))
    published = ref.published_knobs(dict(dims))

    def forward(tokens, n, head_from, low=None, knobs=None):
        kn = {k: np.asarray(v, np.float32)
              for k, v in dict(published, **(knobs or {})).items()}
        return jitted(weights, dims, tokens, n, kn, low=low,
                      head_from=head_from, head_rows=steps)

    seqs, plen = check_sequences(srv.runtime, cfg["vocab_size"], seed, check)
    got = (reference_readings(forward, seqs, plen, steps, **control)
           if control else program_readings(srv, seqs, plen, steps))
    want = reference_readings(forward, seqs, plen, steps)
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
        f"float32 reference, largest of {check['requests']} requests x "
        f"{check['positions']} positions (limit): "
        + ", ".join(f"{k} {read[k]:.2e} ({limits[k]})" for k in FIGURES)
        + f"; logits at the median position {read['logits_mid']:.2e}")
    for k in FIGURES:
        if not read[k] <= limits[k]:
            problems.append(f"{k} off the reference: {read[k]:.2e} over "
                            f"the limit {limits[k]}")
    if srv.pool.in_use() != 0:
        problems.append(f"{srv.pool.in_use()} KV pages still in use "
                        f"after the drain")
    return read


def ssm_shape(rt):
    """The state shape `mxtpu_ssd_step` walks a slot and layer, from the
    runtime's own state: {heads, head_dim, state, groups}."""
    _, heads, head_dim, state = rt.ssm_state[0].shape
    return {"heads": heads, "head_dim": head_dim, "state": state,
            "groups": rt.spec.ssm_groups}


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    slots = cfg["server"]["slots"]
    keep = (1 + traffic["queued_slots"]) * slots
    compiles = harness.CompileWatch()
    t = time.perf_counter()
    model, srv = lm_par.build_server(cfg, ctx["seed"], 2 * keep)
    rt = srv.runtime
    say(f"model and server built {time.perf_counter() - t:.2f} s; slot "
        f"state {rt.slot_state_bytes() / 1e9:.3f} GB, KV pool "
        f"{srv.pool.num_pages * rt.kv_bytes_per_page() / 1e9:.3f} GB; "
        f"the decode kernel walks {ssm_shape(rt)} a slot and layer")
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
    shape = ssm_shape(rt)
    srv.close()
    return {
        "problems": problems, "attempted": len(inside), "failed": failed,
        "setup_s": setup_s, "end_to_end": {"serve_tokens_per_s": rate},
        "counters": {"setup": setup, "window": in_window,
                     "decode_turns": n1 - n0, "window_s": t1 - t0,
                     "slice_decode_turns": n_slice,
                     "window_moe": window_moe, "slice_moe": slice_moe,
                     "ssm_shape": shape},
        "trace": ts,
    }
