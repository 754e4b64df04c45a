"""Shared protocol of the benchmark scripts.

- `tpu_or_smoke`: a measurement needs a TPU backend; anything else is
  refused unless `--smoke` asks for the CPU control-flow check.
- `sweep`: the budget-gated, failure-tolerant candidate sweep bench.py
  (ResNet batch sizes) and bench_bert.py (BERT batch sizes) run —
  candidates after the first only START inside `budget_s`; a failing
  candidate (e.g. OOM at the larger batch) is skipped, never fatal, as
  long as at least one lands; `on_best(value)` fires whenever the
  best-so-far improves, letting the caller checkpoint its JSON line (the
  last parseable stdout line is the result, so a failing later candidate
  can't lose a completed measurement).
"""
from __future__ import annotations

import json
import sys
import time


def device_record():
    """The device as jax reports it; goes into every result line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def tpu_or_smoke(tag):
    """(device record, smoke flag) for a bench entry point. Without
    `--smoke` in argv a non-TPU backend is refused: one `"ok": false`
    JSON line and exit code 1 — a rate measured on the CPU is nobody's
    metric."""
    device = device_record()
    smoke = "--smoke" in sys.argv
    if device["platform"] != "tpu" and not smoke:
        print(f"[{tag}] refusing: a measurement needs a TPU backend, jax "
              f"reports {device} (--smoke checks the control flow on the "
              f"CPU)", file=sys.stderr)
        print(json.dumps({"ok": False, "reason": "no TPU backend",
                          "device": device}))
        sys.exit(1)
    return device, smoke


def smoke_line(device, steps, loss):
    """The one JSON line of a `--smoke` run: says what it is, no rate."""
    import math
    return json.dumps({
        "smoke": "CPU control-flow check, not a measurement",
        "device": device, "steps": steps,
        "loss_finite": math.isfinite(float(loss))})


def timed_measure(step, params, mom, data, steps, items_per_dispatch,
                  tag="bench"):
    """The shared measurement protocol: 2 warmup dispatches (compile +
    stabilise), a host fetch of the loss as the sync, then `steps` timed
    dispatches ended by another host fetch. Returns
    items_per_dispatch * steps / elapsed."""
    params, mom, loss = step(params, mom, *data)
    params, mom, loss = step(params, mom, *data)
    float(loss)
    t0 = time.monotonic()
    for _ in range(steps):
        params, mom, loss = step(params, mom, *data)
    final_loss = float(loss)
    dt = time.monotonic() - t0
    rate = items_per_dispatch * steps / dt
    print(f"[{tag}] loss={final_loss:.4f} dt={dt:.3f}s "
          f"-> {rate:.1f} items/s", file=sys.stderr)
    return rate


def make_sgd_step(loss_fn, aux_idx, lr, mu, unroll=1):
    """The jitted SGD-momentum train step every bench worker uses:
    value_and_grad(loss_fn) -> per-tensor momentum update -> aux (BN
    running stats) spliced back into the param list, optionally unrolled
    k steps per dispatch (the BENCH_UNROLL lever). Donation caveat lives
    with the callers: donate COPIES of params, the originals die."""
    unroll = max(1, int(unroll))  # 0/negative would zero the numerator
    import jax

    def step_1(p, mom, *data):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, *data)
        new_mom = [mu * m + gg.astype(m.dtype) for m, gg in zip(mom, g)]
        new_p = [pp - lr * m for pp, m in zip(p, new_mom)]
        for i, v in zip(aux_idx, aux):
            new_p[i] = v
        return new_p, new_mom, loss

    def step_k(p, mom, *data):
        loss = None
        for _ in range(unroll):
            p, mom, loss = step_1(p, mom, *data)
        return p, mom, loss

    return jax.jit(step_k if unroll > 1 else step_1,
                   donate_argnums=(0, 1))


def sweep(candidates, budget_s, run_one, on_best=None, tag="bench"):
    """Run `run_one(candidate) -> float` over candidates; return
    (best_value, best_candidate). Raises RuntimeError if none land."""
    best, best_cand = 0.0, None
    t_start = time.monotonic()
    for i, cand in enumerate(candidates):
        if i > 0 and time.monotonic() - t_start > budget_s:
            print(f"[{tag}] sweep budget spent; skipping {cand}",
                  file=sys.stderr)
            continue
        try:
            value = run_one(cand)
        except Exception as e:  # e.g. OOM at the larger candidate
            print(f"[{tag}] candidate {cand} failed: {e!r}",
                  file=sys.stderr)
            continue
        if value > best:
            best, best_cand = value, cand
            if on_best is not None:
                on_best(best)
    if best_cand is None:
        raise RuntimeError(f"[{tag}] no sweep candidate completed")
    return best, best_cand


class BackgroundEngineLoad:
    """Sustained background dependency-engine flood (ISSUE 7): a producer
    thread keeps `target` short sleep tasks live in one cancellable
    TaskGroup at PRIORITY_BACKGROUND — the stand-in for a co-tenant
    training loop's host-side work (prefetch staging, async checkpoint
    IO). One implementation shared by `bench_serve.py
    --background-train` and the `tools/check_qos.py` tier-1 gate so the
    bench and the gate measure the same contention."""

    def __init__(self, target, task_s=0.02):
        import threading
        from mxnet_tpu import engine
        self._engine = engine
        self.group = engine.TaskGroup("background_load")
        self.target = int(target)
        self.task_s = float(task_s)
        self._stop = threading.Event()
        self.error = None     # a dead flood thread makes any "no
                              # starvation under load" assertion vacuous:
                              # consumers must check this after the run
        self._thread = threading.Thread(target=self._produce, daemon=True)

    def _produce(self):
        while not self._stop.is_set():
            short = self.target - self.group.live()
            try:
                for _ in range(max(0, short)):
                    self.group.push(
                        lambda: time.sleep(self.task_s),
                        priority=self._engine.PRIORITY_BACKGROUND)
            except self._engine.EngineQueueFull:
                pass          # bounded background class: back off, keep
                              # flooding — the load stays sustained
            except BaseException as exc:  # noqa: BLE001
                self.error = exc
                return
            time.sleep(0.005)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)
        self.group.cancel()
        self.group.drain(timeout=60)
        if self.error is not None and not any(exc):
            # surface a dead producer thread: a run "under load" whose
            # flood silently stopped would pass its contention
            # assertions vacuously
            raise RuntimeError(
                f"background flood thread died: {self.error!r}")
        return False
