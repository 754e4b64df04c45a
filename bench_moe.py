"""Expert-parallelism bench: `ShardedMoE` token routing vs the
equal-parameter dense FFN it sparsifies (ISSUE 16; docs/PERFORMANCE.md
"Expert parallelism").

Two arms on the same captured-step protocol, stem + feed-forward block:

  * moe — a `ShardedMoE(units, hidden, E, k)` layer, expert banks
    row-sharded over 'tp' on the (2,2) ('dp','tp') DEFAULT_RULES mesh:
    the captured step lowers dispatch/combine to exactly 2 all-to-alls
    per layer per traversal (`moe_step`), each device computing E/tp
    expert FFNs over its routed token slots;
  * dense — the same stem with one dense FFN of hidden = E * hidden:
    the SAME parameter count (the quality budget), but every token
    pays the full E*hidden FLOPs instead of k*hidden. This is the
    layer MoE sparsifies (Switch arXiv:2101.03961).

The headline is `moe_step_throughput` with the `moe_vs_dense_ffn`
ratio; `moe_drop_frac` reports the capacity-overflow fraction the run
actually suffered (the loud-accounting contract: at
capacity_factor=1.25 it should sit well under 0.05 — a warning prints
if it doesn't) and `moe_a2a_bytes_per_step` prices the routing wire
traffic from the `kv_collective_bytes{op=moe_all_to_all}` counter.

Needs >= 4 devices (the (2,2) mesh); below that `value: None` so the
bench.py JSON fields are omitted honestly rather than faked —
the BENCH_SHARD=0 pattern.

Standalone: `python bench_moe.py` prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

# per-chip samples/s denominator for vs_baseline: a routing step this
# size is all-to-all/latency-bound on the CPU mesh, not compute-bound;
# same spirit as bench_rec's denominator
BASELINE_SAMPLES_S = 100_000.0

UNITS, HIDDEN, EXPERTS, TOP_K, CAP_FACTOR = 32, 64, 8, 2, 1.25


def _setup():
    """(batch, steps, input batches, labels). Batch divisible by the
    (2,2) mesh's 4 token shards."""
    import jax
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    batch = 256 if on_tpu else 32
    steps = 30 if on_tpu else 4

    rng = np.random.RandomState(0)
    Xb = rng.randn(8, batch, UNITS).astype(np.float32)
    yb = rng.randn(8, batch, UNITS).astype(np.float32)
    return batch, steps, Xb, yb


def _build(moe):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    class _Net(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.stem = gluon.nn.Dense(UNITS, in_units=UNITS)
                if moe:
                    self.ffn = gluon.nn.ShardedMoE(
                        UNITS, HIDDEN, num_experts=EXPERTS, k=TOP_K,
                        capacity_factor=CAP_FACTOR)
                else:
                    # equal-parameter dense twin: E experts of `hidden`
                    # collapse into ONE (units -> E*hidden -> units) FFN
                    self.up = gluon.nn.Dense(EXPERTS * HIDDEN,
                                             activation="relu",
                                             in_units=UNITS)
                    self.down = gluon.nn.Dense(UNITS,
                                               in_units=EXPERTS * HIDDEN)

        def hybrid_forward(self, F_, x):
            h = self.stem(x)
            if moe:
                return self.ffn(h)
            return x + self.down(self.up(h))     # residual, like the MoE

    mx.random.seed(0)
    net = _Net()
    net.initialize(mx.init.Xavier())
    return net


def measure(on_result=None):
    """The bench.py arm: sharded-MoE vs equal-parameter dense-FFN
    captured steps. Returns the `moe_*` contract fields; `value: None`
    below 4 devices."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.observability import registry

    if len(jax.devices()) < 4:
        res = {"metric": "moe_step_throughput", "value": None,
               "unit": "samples/sec/chip",
               "skipped": "needs >= 4 devices"}
        print("[bench_moe] skipped (needs >= 4 devices)",
              file=sys.stderr)
        if on_result is not None:
            on_result(res)
        return res

    batch, steps, Xb, yb = _setup()
    lossf = gluon.loss.L2Loss()
    a2a = registry().counter("kv_collective_bytes", op="moe_all_to_all")

    def run(moe):
        net = _build(moe)
        net(nd.array(Xb[0]))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="ici")
        tr.shard(mesh={"dp": 2, "tp": 2})
        step = tr.capture(lambda x, y: lossf(net(x), y).mean())

        for k in range(2):
            step(nd.array(Xb[k]), nd.array(yb[k]))   # compile + warm
        fallback = step.last_fallback_reason
        t0 = time.monotonic()
        for k in range(steps):
            L = step(nd.array(Xb[k % 8]), nd.array(yb[k % 8]))
        float(L.asnumpy())
        dt = time.monotonic() - t0

        drop_frac = None
        if moe:
            stats = net.ffn.publish_metrics()
            drop_frac = float(stats["overflow_frac"])
        return steps / dt, drop_frac, fallback

    a2a0 = a2a.value
    moe_steps_s, drop_frac, moe_fb = run(True)
    a2a_bytes = a2a.value - a2a0
    dense_steps_s, _, dense_fb = run(False)
    if moe_fb is not None:
        print(f"[bench_moe] WARNING: moe arm fell back ({moe_fb}); "
              f"the ratio measures the imperative path", file=sys.stderr)
    if drop_frac is not None and drop_frac >= 0.05:
        print(f"[bench_moe] WARNING: overflow fraction {drop_frac:.4f} "
              f">= 0.05 at capacity_factor={CAP_FACTOR} — routing is "
              f"dropping too many tokens for this gate/data",
              file=sys.stderr)

    res = {
        "metric": "moe_step_throughput",
        "value": round(moe_steps_s * batch / 4, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(moe_steps_s * batch / 4
                             / BASELINE_SAMPLES_S, 4),
        "mesh": {"dp": 2, "tp": 2},
        "moe_config": {"units": UNITS, "hidden": HIDDEN,
                       "experts": EXPERTS, "k": TOP_K,
                       "capacity_factor": CAP_FACTOR},
        "moe_steps_s": round(moe_steps_s, 3),
        "dense_ffn_steps_s": round(dense_steps_s, 3),
        "moe_vs_dense_ffn": round(moe_steps_s / dense_steps_s, 3),
        "moe_drop_frac": (None if drop_frac is None
                          else round(drop_frac, 4)),
        "moe_a2a_bytes_per_step": (None if a2a_bytes == 0
                                   else int(a2a_bytes // (steps + 2))),
        "fallback": moe_fb,
        "dense_fallback": dense_fb,
    }
    print(f"[bench_moe] moe {moe_steps_s:.2f} steps/s vs "
          f"{dense_steps_s:.2f} dense FFN "
          f"({res['moe_vs_dense_ffn']}x); drop frac "
          f"{res['moe_drop_frac']}; "
          f"{res['moe_a2a_bytes_per_step']} all-to-all B/step",
          file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def main():
    # fork CPU devices BEFORE jax imports so the (2,2) mesh exists on a
    # laptop/CI run (no-op when jax is already in, e.g. under bench.py)
    if "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu" \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=4")
    res = measure()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
