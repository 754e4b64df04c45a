"""MLP-on-MNIST training throughput (BASELINE.json config 1: "MLP on
MNIST (Gluon nn.Sequential, imperative NDArray)").

Measures BOTH execution modes on the same 784-512-256-10 MLP (batch
512, synthetic MNIST):
  * imperative — eager NDArray dispatch per op, the reference's default
    mode. Every op is its own dispatch, so this number is latency- not
    compute-bound; it is reported because the reference config names
    it, and the hybridized ratio IS the CachedOp speedup story the
    reference documents.
  * hybridized — the whole train step as one jitted program (the
    framework's CachedOp equivalent), which is how anyone trains for
    real.

Baseline denominator: an MLP this small is pure overhead measurement —
an A100-class chip sustains ~1e6 samples/s on the compute; the
practical reference number is dispatch-bound far below that. We use
500k samples/s (hybridized-class) so vs_baseline stays meaningful for
the headline (hybridized) number; the imperative number is reported as
an extra field, not against a baseline.

Off by default; BENCH_MLP=1 adds it to bench.py's extra_metrics.
Standalone: `python bench_mlp.py` prints ONE JSON line.
`--trace [path]` additionally captures a Chrome-trace of a few training
steps (mx.profiler + observability tracer; open in Perfetto) and reports
the tracer's overhead against an untraced run of the same loop.
`--prefetch` measures the input pipeline instead: host-prefetch vs
device-resident prefetch feeding a captured step on an input-bound
configuration (ISSUE 5; also via BENCH_PREFETCH=1 in bench.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_SAMPLES_S = 500_000.0


def _setup():
    """Shared bench fixture: (batch, steps, X, y, lossf, build) for the
    784-512-256-10 MLP — ONE definition for measure(), measure_captured()
    and the trace mode, so the compared numbers always run the same
    model and data."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon

    on_tpu = jax.default_backend() == "tpu"
    batch = 512 if on_tpu else 64
    steps = 30 if on_tpu else 3

    rng = np.random.RandomState(0)
    X = nd.array(rng.randn(batch, 784).astype(np.float32))
    y = nd.array(rng.randint(0, 10, batch).astype(np.float32))

    def build():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(512, activation="relu"),
                gluon.nn.Dense(256, activation="relu"),
                gluon.nn.Dense(10))
        net.initialize(mx.init.Xavier())
        net(X)  # materialise
        return net

    return batch, steps, X, y, gluon.loss.SoftmaxCrossEntropyLoss(), build


def _run_imperative(net, n, batch, X, y, lossf, fused=True):
    """n timed record/backward/step() iterations after a 2-step warmup
    (compile on the hybridized path, fused-kernel cache on the imperative
    one); also reports ONE steady-state step()'s trainer-issued
    dispatches (allreduce + guard + optimizer updates)."""
    from mxnet_tpu import autograd, gluon, profiler
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       fused=fused)
    # warm past every lazy compile: hybridized forward, fused-kernel
    # cache, AND the cached jitted backward (which only compiles once a
    # tape structure has repeated _VJP_COMPILE_AFTER times — fewer warmup
    # steps would land that compile inside the timed loop)
    for _ in range(max(2, autograd._VJP_COMPILE_AFTER + 1)):
        with autograd.record():
            L = lossf(net(X), y).mean()
        L.backward()
        tr.step(batch)
    float(L.asnumpy())
    with autograd.record():
        L = lossf(net(X), y).mean()
    L.backward()
    profiler.reset_dispatches()
    tr.step(batch)
    step_dispatches = profiler.dispatch_count()
    t0 = time.monotonic()
    for _ in range(n):
        with autograd.record():
            L = lossf(net(X), y).mean()
        L.backward()
        tr.step(batch)
    final = float(L.asnumpy())
    dt = time.monotonic() - t0
    return batch * n / dt, n / dt, step_dispatches, final


def _run_captured(net, n, batch, X, y, lossf):
    """The whole step as ONE executable (Trainer.capture): steps/s and
    trainer-issued dispatches/step against the PR-1 fused baseline, plus
    the first-call compile cost and whether it hit the persistent
    compilation cache (ISSUE 11 bench.py JSON fields)."""
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.observability import compilex
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    hits0 = compilex.compile_cache_stats()[0]
    t0 = time.monotonic()
    step(X, y)                               # compile
    # the instrumented executable times its own compiling dispatch
    # BEFORE the HLO-inspection recompile, so this is the cost a
    # training loop actually pays; the raw first-call wall clock (which
    # would fold the inspection in) is only the fallback
    compile_s = step.last_compile_seconds or (time.monotonic() - t0)
    cache_hit = compilex.compile_cache_stats()[0] > hits0
    step(X, y)                               # warm
    profiler.reset_dispatches()
    step(X, y)
    step_dispatches = profiler.dispatch_count()
    fallback = step.last_fallback_reason
    t0 = time.monotonic()
    for _ in range(n):
        L = step(X, y)
    final = float(L.asnumpy())
    dt = time.monotonic() - t0
    fallback = fallback or step.last_fallback_reason
    if fallback is not None:
        print(f"[bench_mlp] WARNING: captured step fell back "
              f"({fallback})", file=sys.stderr)
    return (batch * n / dt, n / dt, step_dispatches, final, fallback,
            compile_s, cache_hit)


def measure(on_result=None, trace=None):
    from mxnet_tpu import autograd, gluon

    batch, steps, X, y, lossf, build = _setup()
    imp_steps = max(3, steps // 5)   # imperative is slow; fewer steps

    def run(net, n, fused=True):
        return _run_imperative(net, n, batch, X, y, lossf, fused=fused)

    def run_captured(net, n):
        return _run_captured(net, n, batch, X, y, lossf)

    imp_s, imp_steps_s, imp_disp, imp_loss = run(build(), imp_steps)
    print(f"[bench_mlp] imperative fused: {imp_s:.0f} samples/s "
          f"({imp_steps_s:.2f} steps/s, {imp_disp} step dispatches, "
          f"loss {imp_loss:.4f})", file=sys.stderr)

    unf_s, unf_steps_s, unf_disp, unf_loss = run(build(), imp_steps,
                                                 fused=False)
    print(f"[bench_mlp] imperative unfused: {unf_s:.0f} samples/s "
          f"({unf_steps_s:.2f} steps/s, {unf_disp} step dispatches, "
          f"loss {unf_loss:.4f}, fused is {imp_s / unf_s:.2f}x)",
          file=sys.stderr)

    (cap_s, cap_steps_s, cap_disp, cap_loss, _, cap_compile_s,
     cap_cache_hit) = run_captured(build(), steps)
    print(f"[bench_mlp] captured: {cap_s:.0f} samples/s "
          f"({cap_steps_s:.2f} steps/s, {cap_disp} dispatches/step, "
          f"loss {cap_loss:.4f}, {cap_s / imp_s:.2f}x the fused "
          f"imperative baseline; compile {cap_compile_s:.2f}s, "
          f"cache {'hit' if cap_cache_hit else 'miss'})", file=sys.stderr)

    hyb_net = build()
    hyb_net.hybridize()
    hyb_s, hyb_steps_s, _, hyb_loss = run(hyb_net, steps)
    print(f"[bench_mlp] hybridized: {hyb_s:.0f} samples/s "
          f"(loss {hyb_loss:.4f}, {hyb_s / imp_s:.1f}x the imperative "
          "path — the CachedOp story)", file=sys.stderr)

    res = {
        "metric": "mlp_mnist_train_throughput",
        "value": round(hyb_s, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(hyb_s / BASELINE_SAMPLES_S, 4),
        "imperative_samples_s": round(imp_s, 1),
        "imperative_steps_s_fused": round(imp_steps_s, 3),
        "imperative_steps_s_unfused": round(unf_steps_s, 3),
        "imperative_samples_s_unfused": round(unf_s, 1),
        "step_dispatches_fused": int(imp_disp),
        "step_dispatches_unfused": int(unf_disp),
        "captured_samples_s": round(cap_s, 1),
        "captured_steps_s": round(cap_steps_s, 3),
        "captured_dispatches_per_step": int(cap_disp),
        "captured_vs_fused": round(cap_s / imp_s, 3),
        "compile_seconds": round(cap_compile_s, 3),
        "compile_cache_hit": bool(cap_cache_hit),
    }
    if trace:
        from mxnet_tpu import profiler

        def timed_loop(net, tr, n):
            t0 = time.monotonic()
            for _ in range(n):
                with autograd.record():
                    L = lossf(net(X), y).mean()
                L.backward()
                tr.step(batch)
            float(L.asnumpy())
            return time.monotonic() - t0

        from mxnet_tpu.observability import tracer
        net = build()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        timed_loop(net, tr, 2)                       # warm the caches
        # the artifact: full capture (host spans + jax device trace)
        profiler.set_config(filename=trace)
        profiler.start()
        timed_loop(net, tr, imp_steps)
        profiler.stop()
        trace_file = profiler.dump()
        n_events = tracer.events_recorded()
        # overhead: HOST tracer alone (the always-on subsystem), warm —
        # the jax device trace above is capture-time-only cost; more
        # steps than the throughput loops, or noise swamps the signal
        n_ov = max(10, imp_steps)
        ons, offs = [], []
        for _ in range(3):                 # alternate + take mins: robust
            tracer.start()                 # to scheduler noise on shared
            timed_loop(net, tr, 1)         # boxes (warm grad-norm jit)
            ons.append(timed_loop(net, tr, n_ov))
            tracer.stop()
            tracer.clear()
            offs.append(timed_loop(net, tr, n_ov))
        t_on, t_off = min(ons), min(offs)
        overhead_pct = (t_on - t_off) / t_off * 100.0
        print(f"[bench_mlp] trace: {trace_file} ({n_events} host events; "
              f"host-tracer overhead {overhead_pct:+.1f}% on {n_ov} "
              "imperative steps)", file=sys.stderr)
        res["trace_file"] = trace_file
        res["trace_overhead_pct"] = round(overhead_pct, 2)
    if on_result is not None:
        on_result(res)
    return res


def measure_captured(on_result=None):
    """Captured-step-only bench (the `--captured` mode): steps/s and
    dispatches/step for the one-executable `Trainer.capture` step against
    the PR-1 fused imperative baseline on the same MLP (shared `_setup`
    fixture and loop helpers — identical model/protocol to measure()).
    Cheap enough for bench.py to record `captured_step_throughput`
    alongside the headline metric on every run."""
    batch, steps, X, y, lossf, build = _setup()
    steps = max(5, steps)
    # same budget split as measure(): the imperative twin is the slow
    # side, so it gets the reduced step count
    imp_steps = max(3, steps // 5)

    (_, cap_steps_s, disp, _, fallback, compile_s,
     cache_hit) = _run_captured(build(), steps, batch, X, y, lossf)
    _, fused_steps_s, _, _ = _run_imperative(
        build(), imp_steps, batch, X, y, lossf)

    res = {
        "metric": "captured_step_throughput",
        "value": round(cap_steps_s * batch, 1),
        "unit": "samples/sec/chip",
        "captured_steps_s": round(cap_steps_s, 3),
        "fused_imperative_steps_s": round(fused_steps_s, 3),
        "captured_vs_fused": round(cap_steps_s / fused_steps_s, 3),
        "captured_dispatches_per_step": int(disp),
        "fallback": fallback,
        # ISSUE 11: first-compile cost + persistent-cache outcome ride
        # bench.py's JSON line so the perf trajectory records compile
        # cost alongside steps/s
        "compile_seconds": round(compile_s, 3),
        "compile_cache_hit": bool(cache_hit),
    }
    print(f"[bench_mlp] captured-only: {cap_steps_s:.2f} steps/s "
          f"({disp} dispatch/step, {res['captured_vs_fused']}x the fused "
          f"imperative loop; compile {compile_s:.2f}s, cache "
          f"{'hit' if cache_hit else 'miss'})", file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def measure_autotune(on_result=None, trials=5):
    """The `--autotune` mode (ISSUE 20): run the compile-space search on
    the bench MLP's own captured step — median warm step time per XLA
    flag candidate, guard stack live — and report the measured winner.
    `autotune_speedup` is baseline_ms / winner_ms (1.0 when the default
    build wins: the search proved the defaults, not a regression);
    `autotune_trials` is the per-candidate trial count. bench.py records
    both as first-class bench.py fields — OMITTED when the search
    fails, never faked."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, tune

    batch, steps, X, y, lossf, build = _setup()
    net = build()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(X, y)                        # warm: compile outside the search
    with tune.capture_workload("captured_step") as caught:
        step(X, y)
    wl = caught.get("captured_step")
    if wl is None:
        raise RuntimeError("captured_step dispatch was not recorded "
                           f"(fallback: {step.last_fallback_reason})")
    res = tune.search(wl, trials=trials)
    searched = [r for r in res.candidates
                if not r.candidate.is_baseline]
    out = {
        "metric": "autotune_speedup",
        "value": round(res.speedup, 4),
        "unit": "x vs untuned captured step",
        "autotune_trials": trials,
        "baseline_ms": round(res.baseline.score_ms, 4),
        "winner_ms": round(res.winner.score_ms, 4),
        "winner": res.winner.candidate.name,
        "improved": res.improved,
        "candidates_searched": len(searched),
        "candidates_rejected": sum(1 for r in searched if r.rejected),
    }
    print(f"[bench_mlp] autotune: winner={out['winner']} "
          f"{out['baseline_ms']}ms -> {out['winner_ms']}ms "
          f"(x{out['value']}, {out['candidates_searched']} candidates, "
          f"{out['candidates_rejected']} rejected, trials={trials})",
          file=sys.stderr)
    if on_result is not None:
        on_result(out)
    return out


def measure_prefetch(on_result=None):
    """The `--prefetch` mode (ISSUE 5): steps/s of a warm captured step
    fed by (a) the host-prefetch DataLoader baseline and (b) the
    device-resident prefetcher (`DataLoader(prefetch_to_device=...)`) on
    an INPUT-BOUND configuration — per-sample host augmentation makes the
    pipeline, not the tiny MLP step, the bottleneck. Reports the
    starvation count (input-bound vs compute-bound classification) and
    synchronous-H2D per step for both paths; runs over the 'ici' mesh
    when >= 2 devices are visible so the sharded per-step placement is
    what the device path eliminates."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.observability import registry

    on_tpu = jax.default_backend() == "tpu"
    batch = 512 if on_tpu else 256
    n_steps = 30 if on_tpu else 8
    rng = np.random.RandomState(0)
    N = batch * n_steps
    Xh = rng.randn(N, 784).astype(np.float32)
    yh = rng.randint(0, 10, N).astype(np.float32)

    def aug(x, y):
        # host augmentation heavy enough to input-bind the small step
        out = x
        for k in range(3):
            out = np.tanh(out * 1.01) + 0.001 * np.roll(out, k + 1)
        return out.astype(np.float32), y
    ds = ArrayDataset(Xh, yh).transform(aug)

    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(512, activation="relu"),
            gluon.nn.Dense(256, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net(nd.array(Xh[:batch]))

    on_mesh = len(jax.devices()) >= 2
    if on_mesh:
        from mxnet_tpu.parallel.mesh import make_mesh
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9},
                           kvstore="ici")
        tr._kvstore.set_mesh(make_mesh({"dp": 2}))
        target = tr._kvstore
    else:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        target = True
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(nd.array(Xh[:batch]), nd.array(yh[:batch]))      # compile

    sync = registry().counter("prefetch_h2d_sync")
    starved = registry().counter("prefetch_starved")

    def run(loader):
        sync0, starved0, n = sync.value, starved.value, 0
        t0 = time.monotonic()
        for xb, yb in loader:
            L = step(xb, yb)
            n += 1
        float(L.asnumpy())
        dt = time.monotonic() - t0
        return (n / dt, (sync.value - sync0) / max(n, 1),
                starved.value - starved0, n)

    mk = dict(batch_size=batch, last_batch="discard", prefetch=4)
    host_steps_s, host_sync, _, n_host = run(DataLoader(ds, **mk))
    dev_steps_s, dev_sync, starved_steps, n_dev = run(
        DataLoader(ds, prefetch_to_device=target, **mk))
    input_bound = starved_steps >= n_dev / 2

    # the global batch shards over the dp=2 mesh, so per-chip samples/s
    # is the global rate over the participating devices
    n_chips = 2 if on_mesh else 1
    res = {
        "metric": "prefetch_input_pipeline",
        "value": round(dev_steps_s * batch / n_chips, 1),
        "unit": "samples/sec/chip",
        "devices": n_chips,
        "host_steps_s": round(host_steps_s, 3),
        "device_steps_s": round(dev_steps_s, 3),
        "device_vs_host": round(dev_steps_s / host_steps_s, 3),
        "sync_h2d_per_step_host": round(host_sync, 2),
        "sync_h2d_per_step_device": round(dev_sync, 2),
        "starved_steps": int(starved_steps),
        "steps": int(n_dev),
        "input_bound": bool(input_bound),
        "mesh": bool(on_mesh),
    }
    print(f"[bench_mlp] prefetch: host {host_steps_s:.2f} steps/s "
          f"({host_sync:.1f} sync H2D/step) -> device "
          f"{dev_steps_s:.2f} steps/s ({dev_sync:.1f} sync H2D/step, "
          f"{res['device_vs_host']}x); {starved_steps}/{n_dev} steps "
          f"starved -> {'INPUT' if input_bound else 'COMPUTE'}-bound",
          file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def measure_shard(on_result=None, axes="dp,tp"):
    """The `--shard dp,tp` arm (ISSUE 8): steps/s and per-device
    parameter bytes of the rule-sharded captured step (2-D ('dp','tp')
    mesh, `shard.DEFAULT_RULES`-style layout) against the replicated
    captured step on the same MLP and global batch. Needs >= 4 devices
    (a (2,2) mesh); reports ``value: None`` below that so bench.py
    contract fields stay honest on a 1-chip run."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, shard
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 4:
        res = {"metric": "shard_step_throughput", "value": None,
               "unit": "samples/sec/chip", "skipped": "needs >= 4 devices"}
        print("[bench_mlp] shard: skipped (needs >= 4 devices)",
              file=sys.stderr)
        if on_result is not None:
            on_result(res)
        return res

    batch, steps, X, y, lossf, build = _setup()
    steps = max(5, steps)
    # the zoo MLP: 512/256 hidden divide dp=2; the 10-way head weight is
    # (10, 256) — 10 % 2 == 0, so even the head row-shards
    rules = ((r"_bias$", None),
             (r"dense2_weight$", P("tp", None)),
             (r"_weight$", P("dp", None)),
             (r".*", None))

    def run(shard_axes):
        """shard_axes=None: the REPLICATED baseline — the plain 1-D
        'dp' mesh captured step (params whole on every device)."""
        mx.random.seed(0)
        net = build()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9},
                           kvstore="ici")
        plan = None
        if shard_axes is not None:
            plan = tr.shard(mesh=shard_axes, rules=rules)
        else:
            from mxnet_tpu.parallel.mesh import make_mesh
            tr._kvstore.set_mesh(make_mesh({"dp": n_chips}))
        step = tr.capture(lambda a, b: lossf(net(a), b).mean())
        for _ in range(2):
            step(X, y)                       # compile + warm
        fallback = step.last_fallback_reason
        t0 = time.monotonic()
        for _ in range(steps):
            L = step(X, y)
        float(L.asnumpy())
        dt = time.monotonic() - t0
        params = {p.name: p.data()._data
                  for p in net.collect_params().values()}
        total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in params.values())
        per_dev = total if plan is None else \
            plan.param_bytes_per_device(params)[0]
        return steps / dt, per_dev, total, fallback

    # `axes` names the mesh axes IN ORDER (first = the data axis);
    # BENCH_SHARD_MESH gives their sizes — "--shard tp,dp" genuinely
    # runs a tp-major mesh, not just a different label
    axis_names = [a.strip() for a in axes.split(",")]
    sizes = [int(s) for s in os.environ.get("BENCH_SHARD_MESH",
                                            "2,2").split(",")]
    if len(axis_names) != len(sizes):
        # a silent zip-truncation here would run a fully-replicated mesh
        # while the JSON claims a sharded one
        raise ValueError(
            f"--shard names {len(axis_names)} axes ({axes!r}) but "
            f"BENCH_SHARD_MESH gives {len(sizes)} sizes ({sizes})")
    mesh_axes = dict(zip(axis_names, sizes))
    n_chips = 1
    for s in mesh_axes.values():
        n_chips *= s
    shard_steps_s, per_dev, total, fb = run(mesh_axes)
    repl_steps_s, repl_per_dev, _, repl_fb = run(None)
    if repl_fb is not None:
        # a baseline that silently fell back measured the IMPERATIVE
        # loop — the ratio would compare against the wrong thing
        print(f"[bench_mlp] WARNING: replicated baseline fell back "
              f"({repl_fb}); shard_vs_replicated compares against the "
              f"imperative path", file=sys.stderr)
    res = {
        "metric": "shard_step_throughput",
        "value": round(shard_steps_s * batch / n_chips, 1),
        "unit": "samples/sec/chip",
        "axes": axes,
        "mesh": mesh_axes,
        "shard_steps_s": round(shard_steps_s, 3),
        "replicated_steps_s": round(repl_steps_s, 3),
        "shard_vs_replicated": round(shard_steps_s / repl_steps_s, 3),
        "shard_param_bytes_per_dev": int(per_dev),
        "replicated_param_bytes_per_dev": int(repl_per_dev),
        "param_bytes_total": int(total),
        "fallback": fb,
        "replicated_fallback": repl_fb,
    }
    print(f"[bench_mlp] shard ({axes}): {shard_steps_s:.2f} steps/s "
          f"sharded vs {repl_steps_s:.2f} replicated "
          f"({res['shard_vs_replicated']}x); param bytes/dev "
          f"{per_dev} vs {repl_per_dev} replicated "
          f"({per_dev / total:.2f}x of total)", file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def measure_fleet(on_result=None):
    """The elastic grow-back episode (ISSUE 18): the wall-clock cost of
    a shrink -> grow-back resharding round trip on the bench MLP's
    (2,2) mesh — the headline is the GROW direction (device returns,
    supervisor reverses the shrink through collective redistribution) —
    plus the fleet counters a supervised shrink/regrow episode produces
    (``fleet_regrows``; ``fleet_restarts`` stays 0 in-process — the
    launcher increments it, and a faked value here would lie). Needs
    >= 4 devices; reports ``value: None`` below that so bench.py
    contract fields stay honest on a 1-chip run."""
    import tempfile

    import jax
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 4:
        res = {"metric": "fleet_regrow_ms", "value": None,
               "unit": "ms", "skipped": "needs >= 4 devices"}
        print("[bench_mlp] fleet: skipped (needs >= 4 devices)",
              file=sys.stderr)
        if on_result is not None:
            on_result(res)
        return res

    import mxnet_tpu as mx
    from mxnet_tpu import fault, gluon
    from mxnet_tpu.observability import registry

    batch, steps, X, y, lossf, build = _setup()
    rules = ((r"_bias$", None),
             (r"dense2_weight$", P("tp", None)),
             (r"_weight$", P("dp", None)),
             (r".*", None))
    mx.random.seed(0)
    net = build()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore="ici")
    plan = tr.shard(mesh={"dp": 2, "tp": 2}, rules=rules)
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    for _ in range(2):
        step(X, y)                          # compile + warm

    # timed resize round trips; best-of so a one-off GC pause doesn't
    # become the number. The second lap regrows onto the ORIGINAL plan
    # fingerprint, so it also exercises the executable-cache reuse path.
    shrink_ms, grow_ms = [], []
    for _ in range(3):
        t0 = time.monotonic()
        tr.resize_mesh({"dp": 1, "tp": 2})
        shrink_ms.append((time.monotonic() - t0) * 1e3)
        t0 = time.monotonic()
        tr.resize_mesh({"dp": 2, "tp": 2})
        grow_ms.append((time.monotonic() - t0) * 1e3)
        step(X, y)

    # one supervised shrink -> regrow episode for the counters
    regrows0 = registry().counter("fault_regrows").value
    restarts0 = registry().counter("fleet_restarts").value
    ids = [d.id for d in tr.shard_plan.mesh.devices.flatten()]
    data = [(X, y)] * 4
    count = {"n": 0}

    def sup_step(b):
        count["n"] += 1
        if count["n"] >= 4 and fault.lost_devices():
            fault.clear("device.lost")
        return step(b[0], b[1])

    with tempfile.TemporaryDirectory(prefix="bench_fleet_") as ck:
        try:
            fault.inject("device.lost", at=[2], device=ids[-1])
            rep, _sup = fault.run_supervised(
                tr, sup_step, lambda: iter(data), 10,
                checkpoint_dir=ck, checkpoint_every=4,
                backoff_base=0.0, emergency_save=False,
                regrow_cooldown=1, regrow_hysteresis=1)
        finally:
            fault.clear()
    res = {
        "metric": "fleet_regrow_ms",
        "value": round(min(grow_ms), 2),
        "unit": "ms",
        "shrink_ms": round(min(shrink_ms), 2),
        "fleet_regrows": int(registry().counter("fault_regrows").value
                             - regrows0),
        "fleet_restarts": int(registry().counter("fleet_restarts").value
                              - restarts0),
        "supervised_outcome": rep["outcome"],
    }
    print(f"[bench_mlp] fleet: regrow {res['value']:.2f} ms / shrink "
          f"{res['shrink_ms']:.2f} ms; supervised episode regrows="
          f"{res['fleet_regrows']} ({rep['outcome']})", file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def main():
    args = sys.argv[1:]
    # --prefetch wants >= 2 devices so the mesh placement path is what's
    # measured; on a CPU-only run fork the host platform BEFORE any jax
    # import (no-op if something already imported jax)
    if "--prefetch" in args and "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu" \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=2")
    # --shard / --fleet want >= 4 (a (2,2) mesh) — same dance
    if ("--shard" in args or "--fleet" in args) \
            and "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu" \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    trace = None
    if "--captured" in args:
        print(json.dumps(measure_captured()))
        return
    if "--autotune" in args:
        print(json.dumps(measure_autotune()))
        return
    if "--prefetch" in args:
        print(json.dumps(measure_prefetch()))
        return
    if "--shard" in args:
        i = args.index("--shard")
        axes = (args[i + 1] if len(args) > i + 1
                and not args[i + 1].startswith("-") else "dp,tp")
        print(json.dumps(measure_shard(axes=axes)))
        return
    if "--fleet" in args:
        print(json.dumps(measure_fleet()))
        return
    if "--trace" in args:
        i = args.index("--trace")
        trace = (args[i + 1] if len(args) > i + 1
                 and not args[i + 1].startswith("-")
                 else "/tmp/mxtpu_profile/bench_mlp_trace.json")
    print(json.dumps(measure(trace=trace)))


if __name__ == "__main__":
    main()
