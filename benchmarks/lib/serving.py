"""What the serving kinds share: the server from a configuration, the
seeded corpus, the warm-up, the drain, percentiles, and the
teacher-forced logit check against the plain reference.

The corpus is a FIXED set of (source length, output length) pairs drawn
from the traffic file's own `corpus_seed`; `--seed` only reorders it and
draws the token ids, so every seed gives the server the same set of sizes
in another order. Arrival gaps are treated the same way, and an open-loop
window gets the same requests and the same gaps whatever the seed: the
seed reorders those before the window, those in it and those after it
among themselves.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import math
import time

import numpy as np

from . import models


def build_server(cfg, seed, max_queue):
    import mxnet_tpu as mx
    model = models.build_nmt(cfg, seed, cfg["max_position_embeddings"])
    # the configuration's `server` group is the Server's keyword arguments
    return model, mx.serve.Server(model, max_queue=max_queue,
                                  **cfg["server"])


def corpus(traffic, seed, vocab, blocks=()):
    """[(source tokens, output length)] of `corpus_size` requests. The
    seed reorders the first `blocks[0]` of the fixed set among
    themselves, then the next `blocks[1]`, ..., then the rest."""
    c = traffic["lengths"]
    fixed = np.random.default_rng(traffic["corpus_seed"])
    n = traffic["corpus_size"]
    lo, hi = c["clip"]
    src = np.clip(np.rint(fixed.lognormal(
        math.log(c["src_median"]), c["src_sigma"], n)), lo, hi).astype(int)
    ratio = fixed.uniform(c["out_ratio"][0], c["out_ratio"][1], n)
    out = np.clip(np.rint(src * ratio), lo, hi).astype(int)
    if sum(blocks) > n:
        raise ValueError(f"blocks {blocks} exceed the corpus of {n}")
    rng = np.random.default_rng(int(seed))
    edges = np.cumsum([0, *blocks, n - sum(blocks)])
    order = np.concatenate([lo + rng.permutation(hi - lo)
                            for lo, hi in zip(edges[:-1], edges[1:])])
    tokens = rng.integers(4, vocab, (n, hi)).astype(np.int32)
    return [(tokens[i, :src[j]], int(out[j]))
            for i, j in enumerate(order)]


def arrival_times(traffic, seed, spans):
    """(due times in seconds, arrivals in each span) of a Poisson process
    at `rate_rps` over consecutive `spans` (seconds: before the window, the
    window, after it). Each span gets round(rate x span) exponential gaps
    from the fixed set, scaled to fill it exactly and reordered by the
    seed within it: every seed puts the same number of arrivals, with the
    same gaps, into each span. A span's last arrival would fall on its
    edge: all are due a microsecond early, so it stays inside."""
    rate = traffic["rate_rps"]
    fixed = np.random.default_rng(traffic["corpus_seed"] + 1)
    rng = np.random.default_rng(int(seed) + 1)
    blocks = []
    for span in spans:
        gaps = fixed.exponential(1.0 / rate, max(1, round(rate * span)))
        gaps *= span / gaps.sum()
        rng.shuffle(gaps)
        blocks.append(gaps)
    dues = np.cumsum(np.concatenate(blocks)) - 1e-6
    return dues, [len(b) for b in blocks]


def warm(srv, reqs, n, timeout=1100):
    """Compile and load both executables: `n` requests to their end."""
    hs = [srv.submit(src, max_new_tokens=min(out, 8)) for src, out
          in reqs[:n]]
    for h in hs:
        h.result(timeout=timeout)


def drain(srv, handles, timeout=120):
    ok = srv.wait(handles, timeout=timeout)
    return ok and srv.wait(timeout=timeout)


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def tokens_in_whole_turns(handles, t0, seconds):
    """(output tokens generated in a window of whole decode turns, the
    window's length in seconds).

    The scheduler stamps a turn's first tokens with one clock reading at
    the turn's end and its completions just after, so every stamp is a
    turn end. The window runs from the first stamp at or after `t0` to
    the first at or after `t0 + seconds`. A request yields one token a
    turn, so its n tokens lie evenly from its first-token stamp to its
    completion; each is counted if it falls after the opening edge and
    not after the closing one, judged half a turn of its own off the
    edges, where no token falls. Counting whole turns leaves out both
    things a clock-cut window of completions adds: a turn more or less,
    and the credit for tokens made before it opened."""
    done = [h for h in handles if h is not None and h.state == "done"]
    first = np.array([h.t_first_token for h in done])
    last = np.array([h.t_done for h in done])
    n = np.array([len(h.tokens) for h in done])
    stamps = np.sort(np.concatenate([first, last]))
    a, b = np.searchsorted(stamps, [t0, t0 + seconds])
    if b >= len(stamps):
        raise RuntimeError("no decode turn ended after the window")
    many = n > 1
    turn = np.median((last[many] - first[many]) / (n[many] - 1))
    gap = np.where(many, (last - first) / np.maximum(n - 1, 1), turn)
    # token j of a request is at first + j * gap; j is inside the window
    # if lo < j <= hi
    lo = np.floor((stamps[a] - first) / gap + 0.5)
    hi = np.floor((stamps[b] - first) / gap + 0.5)
    inside = np.minimum(hi, n - 1) - np.maximum(lo + 1, 0) + 1
    return (int(np.clip(inside, 0, None).sum()),
            float(stamps[b] - stamps[a]))


def tally(handles, want_len):
    """(ok, failed, wrong_length) over finished handles; `want_len[i]`
    is what handle i asked for (eos_id -1: every request runs its
    budget)."""
    ok = failed = wrong = 0
    for h, n in zip(handles, want_len):
        if h is None or h.state != "done":
            failed += 1
        elif len(h.tokens) != n:
            wrong += 1
        else:
            ok += 1
    return ok, failed, wrong


def logit_check(srv, model, cfg, seed, check):
    """Teacher-forced logits of `check['requests']` seeded requests x
    `check['positions']` positions through `DecodeRuntime.prefill` /
    `.decode` and the page pool (slots 0.., server idle) against the
    plain reference's full forward on the same weights. Returns the
    largest absolute difference over the reference's largest logit."""
    import jax
    import jax.numpy as jnp
    ref = importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    rt, pool = srv.runtime, srv.pool
    n, steps = check["requests"], check["positions"]
    rng = np.random.default_rng(int(seed) + 2)
    max_src = rt.max_src_len
    src_len = np.linspace(5, max_src, n).astype(np.int32)
    src = np.zeros((n, max_src), np.int32)
    for i, k in enumerate(src_len):
        src[i, :k] = rng.integers(4, cfg["vocab_size"], k)
    tgt = rng.integers(4, cfg["vocab_size"], (n, steps)).astype(np.int32)
    tgt[:, 0] = 2                                         # BOS
    tables = np.zeros((rt.slots, rt.max_pages_per_slot), np.int32)
    pages = []
    for i in range(n):
        pages.append(pool.alloc(pool.pages_for(steps)))
        tables[i, :len(pages[i])] = pages[i]
        rt.prefill(i, src[i, :src_len[i]])
    active = np.zeros((rt.slots,), np.int32)
    active[:n] = 1
    cur = np.zeros((rt.slots,), np.int32)
    lens = np.zeros((rt.slots,), np.int32)
    got = np.zeros((n, steps, cfg["vocab_size"]), np.float32)
    for t in range(steps):
        cur[:n] = tgt[:, t]
        lens[:n] = t
        _, lg = rt.decode(tables, lens, cur, active)
        got[:, t] = np.asarray(lg[:n])
    for p in pages:
        pool.free(p)
    want = np.asarray(jax.jit(ref.logits, static_argnums=(1, 2))(
        models.nmt_reference_weights(model), cfg["attention_heads"],
        cfg["layer_norm_eps"], jnp.asarray(src), jnp.asarray(src_len),
        jnp.asarray(tgt)))
    return float(np.abs(got - want).max() / np.abs(want).max())


def finish(srv, model, cfg, traffic, seed, handles, say, problems):
    """After the window: drain, the program's invariants, the logit
    check. Appends to `problems`."""
    if not drain(srv, [h for h in handles if h is not None]):
        problems.append("the server did not drain")
    if srv.runtime.decode_traces != 1:
        problems.append(f"decode traced {srv.runtime.decode_traces}x")
    err = logit_check(srv, model, cfg, seed, traffic["logit_check"])
    tol = traffic["logit_check"]["tolerance"]
    say(f"teacher-forced logits against the float32 reference: largest "
        f"difference {err:.2e} of the largest logit (limit {tol})")
    if not err <= tol:
        problems.append(f"logits off the reference by {err:.2e}")
    if srv.pool.in_use() != 0:
        problems.append(f"{srv.pool.in_use()} KV pages still in use "
                        f"after the drain")


@contextlib.contextmanager
def settled_heap(pauses):
    """Collect once, then keep what set-up built (jax, the program, the
    corpus: some 2e5 objects) out of the collector while the caller's
    requests run, and log each collection into `pauses` as (generation,
    seconds). A full collection of that heap holds the interpreter, the
    scheduler's thread with it, for as long as two decode turns, about
    once in 4000 requests: in one window of four, which then reads a
    longer tail. What the server allocates while it runs is collected as
    always, so a program that makes more garbage still pays for it."""
    began = []

    def on_gc(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            pauses.append((info["generation"],
                           time.perf_counter() - began.pop()))

    gc.collect()
    gc.freeze()
    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        gc.unfreeze()


def trace_slice_at(t_open, traffic, srv):
    """Sleep to `trace_after_s` into the window, trace `trace_s`
    seconds; returns (slice, decode turns the scheduler made in it)."""
    from .tracing import TraceSlice
    time.sleep(max(0.0, t_open + traffic["trace_after_s"]
                   - time.perf_counter()))
    with TraceSlice() as ts:
        n0 = srv.scheduler.decode_turns
        time.sleep(traffic["trace_s"])
        n1 = srv.scheduler.decode_turns
    return ts, n1 - n0
