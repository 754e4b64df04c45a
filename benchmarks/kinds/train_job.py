"""Kind `train_job`: a pretraining job through `gluon.Trainer` +
`Trainer.capture`, on one chip or under `tr.shard()` on several.

Set-up: model and weights from the seed, the optimizer of the
configuration, `host_batches` seeded host batches, `warm_steps` steps
(the first compiles or loads the step). Window: the captured step on the
host batches in turn, each handed over as fresh device arrays; the loss
is read every `log_every` steps and the window closes on the first such
read at or after `--seconds`. After the window: every loss read back,
and the program's predict-mode loss on batch 0 against the plain
reference's on the same weights.
"""
from __future__ import annotations

import importlib
import math
import time

from ..lib import harness, models
from ..lib.tracing import TraceSlice


def build(cfg, traffic, seed, devices):
    """(model, captured step, host batches)."""
    import mxnet_tpu as mx
    builder = cfg["builder"]
    model = getattr(models, f"build_{builder}")(cfg, seed, traffic["seq"])
    opt = dict(cfg["optimizer"])
    name = opt.pop("name")
    chips = traffic["chips"]
    if chips > 1:
        from mxnet_tpu.shard import as_mesh
        tr = mx.gluon.Trainer(model.collect_params(), name, opt,
                              kvstore="ici")
        tr.shard(mesh=as_mesh(traffic["mesh"], devices=devices[:chips]))
    else:
        tr = mx.gluon.Trainer(model.collect_params(), name, opt)
    batches = getattr(models, f"{builder}_batches")(
        cfg, seed, traffic["host_batches"], traffic["batch"],
        traffic["seq"], traffic["masked"], traffic["valid_length"])
    loss_fn = getattr(models, f"{builder}_loss_fn")(model, cfg)
    return model, tr.capture(loss_fn), batches


def run_steps(step, batches, start, n):
    """`n` steps from host batch `start` on; returns the loss handles."""
    from mxnet_tpu import nd
    out = []
    for i in range(start, start + n):
        out.append(step(*[nd.array(a) for a in batches[i % len(batches)]]))
    return out


def window(step, batches, seconds, log_every, trace_steps=0):
    """The measured loop. Returns (loss handles, seconds, trace slice or
    None). With `trace_steps`, the groups after the second are traced."""
    handles, ts = [], None
    t0 = time.perf_counter()
    while True:
        if trace_steps and ts is None and len(handles) >= 2 * log_every:
            with TraceSlice() as ts:
                for _ in range(max(trace_steps // log_every, 1)):
                    handles += run_steps(step, batches, len(handles),
                                         log_every)
                    handles[-1].wait_to_read()
            continue
        handles += run_steps(step, batches, len(handles), log_every)
        float(handles[-1].asnumpy())          # the logging read
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return handles, elapsed, ts


def predict_loss(model, cfg, batch):
    """The program's forward in predict mode (no dropout) as one jitted
    call on the live weights, and the plain reference on the same
    weights cast to float32. Both on the first device: weights that a
    shard plan spread over several are brought together first."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.block import extract_pure_fn
    builder = cfg["builder"]
    ref = importlib.import_module(
        f"benchmarks.reference.{cfg['name']}")
    dev = jax.devices()[0]
    arrays = [jax.device_put(jnp.asarray(a), dev) for a in batch]
    fn, params = extract_pure_fn(model, *[nd.array(a) for a in batch[:4]])
    got = jax.jit(lambda p, *xs: getattr(models, f"{builder}_loss_of")(
        fn(p, *xs[:4]), *xs[4:], cfg))(
        [jax.device_put(p, dev) for p in params], *arrays)
    weights = jax.device_put(
        getattr(models, f"{builder}_reference_weights")(model), dev)
    want = jax.jit(ref.loss, static_argnums=(1, 2))(
        weights, cfg["num_attention_heads"], cfg["layer_norm_eps"],
        *arrays)
    return float(got), float(want)


def run(ctx):
    cfg, traffic, say = ctx["config"], ctx["traffic"], ctx["say"]
    chips = traffic["chips"]
    compiles = harness.CompileWatch()
    model, step, batches = build(cfg, traffic, ctx["seed"],
                                 ctx["devices"])
    t = time.perf_counter()
    warm = run_steps(step, batches, 0, traffic["warm_steps"])
    warm[-1].wait_to_read()
    say(f"{traffic['warm_steps']} warm steps (compile or cache load) "
        f"{time.perf_counter() - t:.2f} s")
    problems = []
    if step.last_fallback_reason is not None:
        problems.append(f"captured step fell back: "
                        f"{step.last_fallback_reason}")
    setup = compiles.since()

    setup_s = time.perf_counter() - ctx["t_start"]
    compiles.mark()
    handles, elapsed, ts = window(
        step, batches, ctx["seconds"], traffic["log_every"],
        traffic["trace_steps"] if ctx["trace"] else 0)
    in_window = compiles.since()

    steps = len(handles)
    tokens = traffic["batch"] * traffic["seq"] * steps
    rate = tokens / elapsed / chips
    losses = [float(h.asnumpy()) for h in handles]
    k = traffic["log_every"]
    first, last = sum(losses[:k]) / k, sum(losses[-k:]) / k
    say(f"{steps} steps in {elapsed:.3f} s: {1e3 * elapsed / steps:.2f} ms "
        f"a step, {rate:.1f} tokens/s a chip on {chips} chip(s)")
    say(f"loss: first {k} steps {first:.4f}, last {k} {last:.4f}")
    if not all(math.isfinite(l) for l in losses):
        problems.append("non-finite loss in the window")
    if not last < first:
        problems.append(f"loss did not fall: {first} -> {last}")
    if harness.compiled(in_window) or step.cache_size != 1:
        problems.append(f"compilation inside the window: {in_window}, "
                        f"step cache {step.cache_size}")

    share = traffic["batch"] // chips        # one chip's share of batch 0
    got, want = predict_loss(model, cfg, [a[:share] for a in batches[0]])
    tol = traffic["loss_tolerance"]
    say(f"predict-mode loss on batch 0: program {got:.5f}, float32 "
        f"reference {want:.5f} (relative difference "
        f"{abs(got - want) / abs(want):.2e}, limit {tol})")
    if not abs(got - want) <= tol * abs(want):
        problems.append(f"loss {got} off the reference's {want}")

    from ..lib import flops
    per_token = getattr(flops, f"{cfg['builder']}_train_flops_per_token")(
        cfg, traffic["seq"], traffic["masked"])
    peak = flops.peaks(ctx["device"]["kind"])
    say(f"model FLOP/s utilization {100 * rate * per_token / peak['bf16_flops_per_s']:.2f}% "
        f"of bf16 peak at {per_token / 1e9:.4f} GFLOP a token (a "
        f"rescaling of train_tokens_per_s, not a metric)")
    return {
        "problems": problems, "attempted": steps, "failed": 0,
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": rate},
        "counters": {"setup": setup, "window": in_window, "steps": steps,
                     "window_s": elapsed},
        "trace": ts,
    }
