"""Serving benchmark (ISSUE 6): request latency percentiles + aggregate
tokens/s under Poisson arrivals, continuous vs static batching.

ISSUE 12 extension — the `--fastpath` arm (also folded into bench.py's
bench.py fields) measures the serving fast path on a shared-system-
prompt Poisson mix: the SAME prompted trace runs warm (content-hashed
radix prefix cache on — later requests adopt cached prompt pages and
skip that prefill) vs cold (cache disabled), and once more with
speculative_k=3 (n-gram drafts verified by one widened dispatch per
turn). Headlines: `prefix_speedup` (wall tokens/s, warm over cold) with
`{warm,cold}_decode_turns` as the deterministic witness, and
`spec_turns_per_token` vs `control_turns_per_token` for speculation.

ISSUE 7 extension — the `--background-train` arm replays the same trace
while a sustained background engine flood (prefetch/checkpoint stand-in
tasks) contends for the engine workers, once with QoS priorities on and
once with `engine.set_qos(False)` (pure FIFO): the contended p99 pair is
what the priority classes + aging actually buy a serving tenant sharing
chips with training. `p99_contended_ms` rides bench.py's JSON line as
`serve_p99_contended_ms`.

The workload is a mixed-length open-loop arrival process: exponential
inter-arrival times (Poisson process, seeded), source lengths and token
budgets drawn from a spread so a static batch always carries stragglers.
The same request trace is replayed twice through the SAME model:

  * continuous — `serve.Server` default: admissions fill freed slots
    every step, so short requests never wait for the batch's longest;
  * static    — `static_batching=True`: admission only into an empty
    batch (the classic serve-batch-drain loop) — the baseline continuous
    batching must beat on any mixed-length workload.

Reports p50/p95/p99 end-to-end latency, p50 TTFT and tokens/s for both
policies plus the speedup. Prints exactly ONE JSON line on stdout
(standalone); `measure()` returns the dict for bench.py's JSON line
contract (`serve_tokens_per_s` / `serve_p99_ms` ride the headline
metric). Off the driver line by default only in --smoke runs; disable
with BENCH_SERVE=0.
"""
from __future__ import annotations

import json
import sys
import time

# service-bound load: arrivals fast enough that slots stay contended —
# an arrival-bound trace would let both policies idle between requests
# and hide the straggler cost static batching pays
N_REQUESTS = 48
RATE_HZ = 400.0         # mean arrival rate of the Poisson process
SLOTS = 4


def _build_server(static):
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(7)
    model = TransformerNMT(64, units=32, hidden=64, num_layers=2,
                           num_heads=4, max_length=64, dropout=0.0)
    model.initialize()
    return mx.serve.Server(model, slots=SLOTS, page_size=8,
                           max_src_len=16, max_new_tokens=32,
                           max_queue=N_REQUESTS,
                           static_batching=static, engine_driven=True)


def _workload(seed=0, n=N_REQUESTS):
    import numpy as np
    rng = np.random.RandomState(seed)
    reqs = []
    for _ in range(n):
        src = rng.randint(4, 64, (int(rng.randint(4, 16)),))
        # mixed token budgets: the straggler spread static batching eats
        max_new = int(rng.choice([4, 8, 16, 32]))
        gap = float(rng.exponential(1.0 / RATE_HZ))
        reqs.append((src.astype(np.int32), max_new, gap))
    return reqs


def _run(policy_static, reqs):
    import numpy as np

    from mxnet_tpu import profiler

    srv = _build_server(policy_static)
    handles = []
    try:
        # warm outside the timed window: the first request compiles the
        # prefill + decode executables (seconds of XLA work that would
        # otherwise masquerade as queueing latency)
        srv.submit(np.arange(4, 12, dtype=np.int32),
                   max_new_tokens=4).result(timeout=300)
        turns0 = profiler.dispatch_count("serve_decode")
        t0 = time.perf_counter()
        for src, max_new, gap in reqs:
            time.sleep(gap)
            handles.append(srv.submit(src, max_new_tokens=max_new))
        for h in handles:
            h.result(timeout=300)
    finally:
        srv.close()
    wall = time.perf_counter() - t0
    lats = sorted(h.latency for h in handles)
    ttfts = sorted(h.ttft for h in handles)
    toks = sum(len(h.tokens) for h in handles)

    def pct(sorted_vals, q):
        i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
        return sorted_vals[i]

    return {
        "tokens": toks,
        "tokens_per_s": toks / wall,
        "wall_s": wall,
        "decode_turns": profiler.dispatch_count("serve_decode") - turns0,
        "p50_ms": pct(lats, 0.50) * 1e3,
        "p95_ms": pct(lats, 0.95) * 1e3,
        "p99_ms": pct(lats, 0.99) * 1e3,
        "ttft_p50_ms": pct(ttfts, 0.50) * 1e3,
    }


def measure_contended(reqs, qos=True):
    """One continuous-batching pass under the background-train flood
    (`bench_util.BackgroundEngineLoad`, the same generator the
    check_qos gate floods with), with or without priority scheduling
    (engine.set_qos)."""
    from mxnet_tpu import engine
    from bench_util import BackgroundEngineLoad

    prev = engine.set_qos(qos)
    try:
        with BackgroundEngineLoad(engine.num_workers() * 32, task_s=0.01):
            time.sleep(0.2)             # let the backlog build
            return _run(policy_static=False, reqs=reqs)
    finally:
        engine.set_qos(prev)
        engine.wait_for_all()


def _contended_fields(reqs):
    """The QoS-vs-FIFO contended arm, one pass each (the deterministic
    decode-turn witness makes repeats unnecessary): decode p99 while a
    background-train flood contends for the engine, with and without
    priority scheduling. One source for both the bench.py JSON
    fields in measure() and the standalone --background-train line."""
    qos = measure_contended(reqs, qos=True)
    fifo = measure_contended(reqs, qos=False)
    return {
        "p99_contended_ms": round(qos["p99_ms"], 2),
        "p99_contended_fifo_ms": round(fifo["p99_ms"], 2),
        "contended_p99_ratio_fifo_over_qos": round(
            fifo["p99_ms"] / max(qos["p99_ms"], 1e-9), 3),
        "tokens_per_s_contended": round(qos["tokens_per_s"], 2),
    }


def _build_fast_server(speculative_k=0, prefix_cache=True, **kw):
    """The fast-path server (ISSUE 12): prompt budget for the shared
    system prompts, optional speculative width. Same model/seed as the
    headline arms so the executables compare like for like. Extra
    keywords (kv_dtype / weight_dtype, ISSUE 14) pass through to
    `Server`."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(7)
    model = TransformerNMT(64, units=32, hidden=64, num_layers=2,
                           num_heads=4, max_length=64, dropout=0.0)
    model.initialize()
    return mx.serve.Server(model, slots=SLOTS, page_size=8,
                           max_src_len=16, max_new_tokens=24,
                           max_prompt_len=32,
                           speculative_k=speculative_k,
                           prefix_cache=prefix_cache,
                           max_queue=N_REQUESTS, engine_driven=True,
                           **kw)


def _prefix_workload(seed=1, n=N_REQUESTS, templates=3):
    """Shared-system-prompt Poisson mix: every request draws one of
    `templates` (source, 24-token system prompt) pairs — the radix-
    shareable material — plus a short unique prompt suffix on some
    requests (partial-prefix hits) and a mixed generation budget."""
    import numpy as np
    rng = np.random.RandomState(seed)
    temps = [(rng.randint(4, 64, (int(rng.randint(6, 16)),)
                          ).astype(np.int32),
              rng.randint(4, 64, (24,)).astype(np.int32))
             for _ in range(templates)]
    reqs = []
    for _ in range(n):
        src, sys_prompt = temps[int(rng.randint(templates))]
        prompt = sys_prompt
        if rng.rand() < 0.4:
            prompt = np.concatenate(
                [sys_prompt,
                 rng.randint(4, 64, (int(rng.randint(1, 5)),))]
            ).astype(np.int32)
        max_new = int(rng.choice([4, 8, 16, 24]))
        gap = float(rng.exponential(1.0 / RATE_HZ))
        reqs.append((src, prompt, max_new, gap))
    return reqs


def _run_fast(reqs, speculative_k=0, prefix_cache=True, **kw):
    """One pass of the prompted trace; returns wall tokens/s plus the
    deterministic witnesses: decode turns, committed tokens, prefix hit
    rate, draft acceptance and the per-request token outputs (the
    accuracy-contract comparison material)."""
    srv = _build_fast_server(speculative_k=speculative_k,
                             prefix_cache=prefix_cache, **kw)
    handles = []
    try:
        # warm-up compiles prefill + (widened) decode outside the clock
        srv.submit(list(range(4, 12)), max_new_tokens=4,
                   prompt_tokens=list(range(4, 10))).result(timeout=300)
        sched = srv.scheduler
        turns0, toks0 = sched.decode_turns, sched.tokens_generated
        t0 = time.perf_counter()
        for src, prompt, max_new, gap in reqs:
            time.sleep(gap)
            handles.append(srv.submit(src, max_new_tokens=max_new,
                                      prompt_tokens=prompt))
        for h in handles:
            h.result(timeout=300)
        wall = time.perf_counter() - t0
        turns = sched.decode_turns - turns0
        toks = sched.tokens_generated - toks0
        cache = srv.prefix_cache
        hit_rate = (cache.hits / max(cache.hits + cache.misses, 1)
                    if cache is not None else 0.0)
        saved = cache.tokens_saved if cache is not None else 0
        accept = (sched.spec_accepted / max(sched.spec_drafted, 1)
                  if speculative_k else 0.0)
        outputs = [list(h.tokens) for h in handles]
    finally:
        srv.close()
    return {
        "tokens": toks,
        "tokens_per_s": toks / wall,
        "wall_s": wall,
        "decode_turns": turns,
        "turns_per_token": turns / max(toks, 1),
        "prefix_hit_rate": hit_rate,
        "prefix_tokens_saved": saved,
        "spec_accept_rate": accept,
        "outputs": outputs,
    }


def measure_fastpath(seed=1, repeats=2):
    """The ISSUE 12 arms. Prefix-heavy: the same shared-system-prompt
    trace warm (radix cache on) vs cold (cache disabled) — the headline
    is wall tokens/s speedup, with prefill-turns-saved as the
    deterministic witness. Speculative: the same trace with k=3 n-gram
    drafts per turn vs the 1-wide control — the witness is decode turns
    per committed token."""
    reqs = _prefix_workload(seed)
    warm = min((_run_fast(reqs, prefix_cache=True)
                for _ in range(repeats)), key=lambda r: r["wall_s"])
    cold = min((_run_fast(reqs, prefix_cache=False)
                for _ in range(repeats)), key=lambda r: r["wall_s"])
    spec = _run_fast(reqs, speculative_k=3, prefix_cache=True)
    return {
        "metric": "serve_fastpath",
        "unit": "tokens/sec",
        "value": round(warm["tokens_per_s"], 2),
        "requests": len(reqs),
        "prefix_hit_rate": round(warm["prefix_hit_rate"], 4),
        "prefix_tokens_saved": warm["prefix_tokens_saved"],
        "prefix_speedup": round(
            warm["tokens_per_s"] / max(cold["tokens_per_s"], 1e-9), 3),
        "cold_tokens_per_s": round(cold["tokens_per_s"], 2),
        "warm_decode_turns": warm["decode_turns"],
        "cold_decode_turns": cold["decode_turns"],
        "spec_accept_rate": round(spec["spec_accept_rate"], 4),
        "spec_turns_per_token": round(spec["turns_per_token"], 4),
        "control_turns_per_token": round(cold["turns_per_token"], 4),
        "spec_tokens_per_s": round(spec["tokens_per_s"], 2),
    }


def _token_match(ref_outputs, outputs):
    """Position-wise greedy token-match rate vs the fp32 reference
    (length mismatches count as mismatches) — the accuracy number every
    low-precision speed claim ships with (ISSUE 14)."""
    matched = total = 0
    for a, b in zip(ref_outputs, outputs):
        total += max(len(a), len(b))
        matched += sum(1 for x, y in zip(a, b) if x == y)
    return matched / max(total, 1)


def _logit_mse(kv_dtype=None, weight_dtype=None, steps=8, seed=5):
    """Teacher-forced decode-logit MSE vs the fp32 runtime: both
    runtimes prefill the same source and decode the same forced token
    sequence, so the per-position logits compare like for like."""
    import numpy as np

    def drive(srv):
        rng = np.random.RandomState(seed)
        src = rng.randint(4, 64, (8,)).astype(np.int32)
        toks = rng.randint(4, 64, (steps,)).astype(np.int32)
        rt = srv.runtime
        pool = srv.pool
        pages = pool.alloc(pool.pages_for(steps))
        tables = np.full((rt.slots, rt.max_pages_per_slot), 0, np.int32)
        tables[0, :len(pages)] = pages
        rt.prefill(0, src)
        active = np.zeros((rt.slots,), np.int32)
        active[0] = 1
        cur = np.zeros((rt.slots,), np.int32)
        lens = np.zeros((rt.slots,), np.int32)
        logits = []
        for t in range(steps):
            cur[0] = toks[t]
            lens[0] = t
            _, lg = rt.decode(tables, lens, cur, active)
            logits.append(np.asarray(lg[0], np.float64))
        pool.free(pages)
        srv.close()
        return np.stack(logits)

    ref = drive(_build_fast_server())
    got = drive(_build_fast_server(kv_dtype=kv_dtype,
                                   weight_dtype=weight_dtype))
    return float(np.mean((ref - got) ** 2))


def measure_int8kv(seed=2):
    """The ISSUE 14 arm: the same shared-system-prompt trace through an
    int8-KV server vs the fp32 twin. Headlines: wall tokens/s ratio
    (honest — on the CPU mesh the quantise/requantise work is not free,
    so the ratio can sit below 1; the bandwidth win needs a chip) and
    the CAPACITY witnesses (tokens + concurrent full-size requests a
    fixed HBM byte budget holds — deterministic, hardware-independent,
    ~3.5x vs fp32 pages). Every speed number ships with its accuracy
    contract: greedy token-match rate + teacher-forced logit MSE vs
    fp32."""
    from mxnet_tpu.serve.quant import kv_page_bytes, token_capacity

    reqs = _prefix_workload(seed)
    fp = _run_fast(reqs, prefix_cache=True)
    q = _run_fast(reqs, prefix_cache=True, kv_dtype="int8")
    match = _token_match(fp["outputs"], q["outputs"])
    mse = _logit_mse(kv_dtype="int8")
    # capacity at a fixed byte budget (the bench model's KV geometry:
    # 2 layers x 4 heads x 8 head-dim, page_size 8)
    geo = dict(n_layers=2, page_size=8, num_heads=4, head_dim=8)
    budget = 256 * kv_page_bytes(kv_dtype="float32", **geo)
    cap_fp = token_capacity(budget, kv_dtype="float32", **geo)
    cap_q = token_capacity(budget, kv_dtype="int8", **geo)
    return {
        "metric": "serve_int8_kv",
        "unit": "tokens/sec",
        "value": round(q["tokens_per_s"], 2),
        "fp_tokens_per_s": round(fp["tokens_per_s"], 2),
        "speedup_vs_fp": round(
            q["tokens_per_s"] / max(fp["tokens_per_s"], 1e-9), 3),
        "token_match": round(match, 4),
        "logit_mse": mse,
        "capacity_tokens_ratio": round(cap_q / cap_fp, 3),
        "tokens_at_budget_int8": cap_q,
        "tokens_at_budget_fp32": cap_fp,
        "concurrent_slots_int8": cap_q // (32 + 24),
        "concurrent_slots_fp32": cap_fp // (32 + 24),
        "decode_turns": q["decode_turns"],
        "fp_decode_turns": fp["decode_turns"],
    }


def measure(seed=0, repeats=2, background_train=True):
    """Best-of-`repeats` per policy: shared-box wall clocks are noisy at
    this scale, so each arm keeps its best run — and the DETERMINISTIC
    witness rides along: `decode_turns` (one shared dispatch per serving
    turn) is what continuous batching actually saves, independent of the
    scheduler's timing luck."""
    reqs = _workload(seed)
    cont = min((_run(policy_static=False, reqs=reqs)
                for _ in range(repeats)), key=lambda r: r["wall_s"])
    stat = min((_run(policy_static=True, reqs=reqs)
                for _ in range(repeats)), key=lambda r: r["wall_s"])
    contended = {}
    if background_train:
        try:
            contended = _contended_fields(reqs)
        except Exception as exc:
            # The contended arm runs AFTER cont/stat: a failure here must
            # not discard the uncontended serve fields already measured
            # (bench.py's per-field guard can then still see them).
            print(f"[bench_serve] contended arm failed: {exc!r}",
                  file=sys.stderr)
    return {
        "metric": "serve_throughput",
        "unit": "tokens/sec",
        "value": round(cont["tokens_per_s"], 2),
        "requests": len(reqs),
        "slots": SLOTS,
        "p50_ms": round(cont["p50_ms"], 2),
        "p95_ms": round(cont["p95_ms"], 2),
        "p99_ms": round(cont["p99_ms"], 2),
        "ttft_p50_ms": round(cont["ttft_p50_ms"], 2),
        "decode_turns": cont["decode_turns"],
        "static_tokens_per_s": round(stat["tokens_per_s"], 2),
        "static_p99_ms": round(stat["p99_ms"], 2),
        "static_decode_turns": stat["decode_turns"],
        "speedup_vs_static": round(
            cont["tokens_per_s"] / max(stat["tokens_per_s"], 1e-9), 3),
        "turns_ratio_vs_static": round(
            stat["decode_turns"] / max(cont["decode_turns"], 1), 3),
        **contended,
    }


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--fastpath" in argv:
        # ISSUE 12 arms only: prefix-heavy warm-vs-cold + speculative
        print(json.dumps(measure_fastpath()), flush=True)
        return 0
    if "--int8-kv" in argv:
        # ISSUE 14 arm only: int8-KV tokens/s + capacity-at-fixed-budget
        # vs fp32, with the accuracy contract riding along
        print(json.dumps(measure_int8kv()), flush=True)
        return 0
    if "--background-train" in argv:
        # contended arm only: decode p99 under background-train load,
        # QoS vs FIFO
        fields = _contended_fields(_workload())
        print(json.dumps({
            "metric": "serve_p99_contended",
            "unit": "ms",
            "value": fields.pop("p99_contended_ms"),
            **fields,
        }), flush=True)
        return 0
    print(json.dumps(measure()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
