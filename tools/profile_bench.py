"""Capture a device profile of the ResNet-50 bench train step and print a
per-op time breakdown.

Usage:  python tools/profile_bench.py [--batch N] [--steps N]

Writes the raw trace under /tmp/mxtpu_prof and prints the top-K HLO ops by
total device time (aggregated over the steps inside the trace), which is the
evidence base for bench tuning.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_step(batch):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    net = resnet50_v1(layout="NHWC", stem_s2d=True)
    net.initialize()
    net.cast("bfloat16")
    x = mx.nd.random.uniform(shape=(batch, 224, 224, 3), dtype="bfloat16")
    net(x)
    fwd, params = extract_pure_fn(net, x, training=True)
    aux_idx = list(fwd.aux_indices)

    key = jax.random.PRNGKey(0)
    labels = jax.random.randint(key, (batch,), 0, 1000)

    def loss_fn(p, xb, yb):
        logits, aux = fwd(p, xb)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1)), aux

    lr, mu = 0.1, 0.9

    def train_step(p, mom, xb, yb):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, xb, yb)
        new_mom = [mu * m + gg.astype(m.dtype) for m, gg in zip(mom, g)]
        new_p = [pp - lr * m for pp, m in zip(p, new_mom)]
        for i, v in zip(aux_idx, aux):
            new_p[i] = v
        return new_p, new_mom, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    mom = [jnp.zeros_like(p) for p in params]
    return step, params, mom, x._data, labels


def parse_xspace(logdir, min_pct=0.3):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise SystemExit(f"no xplane.pb under {logdir}")
    space = xplane_pb2.XSpace()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if "TPU" not in plane.name and "/device" not in plane.name.lower():
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        agg = defaultdict(float)
        total = 0.0
        for line in plane.lines:
            # XLA Ops line has the per-HLO breakdown; "Steps"/"XLA Modules"
            # lines would double-count the same wall time.
            if line.name not in ("XLA Ops",):
                continue
            for ev in line.events:
                dur = ev.duration_ps / 1e12
                agg[ev_meta.get(ev.metadata_id, "?")] += dur
                total += dur
        if not agg:
            continue
        print(f"\n== plane: {plane.name}  total XLA-op time {total*1e3:.1f} ms")
        shown = 0.0
        for name, t in sorted(agg.items(), key=lambda kv: -kv[1]):
            pct = 100 * t / total
            if pct < min_pct:
                break
            shown += pct
            print(f"{t*1e3:9.3f} ms {pct:5.1f}%  {name[:110]}")
        print(f"(shown {shown:.0f}% of device op time)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--logdir", default="/tmp/mxtpu_prof")
    args = ap.parse_args()

    import jax
    step, params, mom, images, labels = build_step(args.batch)
    params, mom, loss = step(params, mom, images, labels)
    params, mom, loss = step(params, mom, images, labels)
    float(loss)  # sync

    jax.profiler.start_trace(args.logdir)
    for _ in range(args.steps):
        params, mom, loss = step(params, mom, images, labels)
    float(loss)
    jax.profiler.stop_trace()
    parse_xspace(args.logdir)


if __name__ == "__main__":
    main()
