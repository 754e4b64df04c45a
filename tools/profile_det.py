"""Device profile of the detection bench train steps (give
SSD/Faster-RCNN the ResNet profile treatment).

Usage:  python tools/profile_det.py [--model ssd|rcnn] [--batch N]
                                    [--steps N] [--input N]

Reuses bench_det's exact step builders (so the profile measures the
benched program, not a lookalike) and profile_bench's xplane parser for
the per-HLO table (a finding for the root PERF.md).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("ssd", "rcnn"), default="ssd")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--input", type=int, default=None)
    ap.add_argument("--logdir", default=None)
    ap.add_argument("--min-pct", type=float, default=0.3)
    args = ap.parse_args()

    import jax
    on_tpu = jax.default_backend() == "tpu"
    input_size = args.input or (512 if on_tpu else 128)
    import bench_det
    if args.model == "ssd":
        batch = args.batch or (16 if on_tpu else 2)
        step, params, mom, data, _ = bench_det.build_step(
            batch, input_size)
    else:
        batch = args.batch or (8 if on_tpu else 2)
        step, params, mom, data = bench_det.build_rcnn_step(
            batch, input_size)
    logdir = args.logdir or f"/tmp/mxtpu_prof_{args.model}"

    params, mom, loss = step(params, mom, *data)
    params, mom, loss = step(params, mom, *data)
    print(f"[profile_det] {args.model} b{batch}@{input_size} "
          f"loss={float(loss):.4f}", file=sys.stderr)

    jax.profiler.start_trace(logdir)
    for _ in range(args.steps):
        params, mom, loss = step(params, mom, *data)
    float(loss)
    jax.profiler.stop_trace()

    from profile_bench import parse_xspace
    parse_xspace(logdir, min_pct=args.min_pct)


if __name__ == "__main__":
    main()
