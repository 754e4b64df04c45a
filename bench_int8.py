"""INT8 inference A/B: quantize_net'd ResNet-50 vs the bf16 original.

The reference's quantization story is an INFERENCE-speed story
(contrib.quantization + calibration -> int8 conv/FC kernels). This
bench proves (or honestly refutes) the same claim on TPU: zoo
resnet50_v1 at batch 128, bf16 forward vs the calibrated int8 forward
(MXU int8xint8->int32 dots), hybridized, images/sec each, plus the
ratio. No baseline denominator — the deliverable is the measured
speedup itself, reported in the JSON line.

ISSUE 14: the speed ratio never ships without an accuracy number —
`logit_mse` (mean squared logit error vs the fp forward on a held
batch) and `greedy_match` (top-1 / greedy-prediction agreement rate)
ride the same JSON line, the quality-column contract the serving
low-precision path also follows (bench_serve --int8-kv).

Off by default; BENCH_INT8=1 adds it to bench.py's extra_metrics.
Standalone: `python bench_int8.py` prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time


def measure(on_result=None):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1, resnet18_v1

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        ctor, mname, batch, size, steps = resnet50_v1, "resnet50", 128, 224, 20
    else:  # CPU smoke uses a smaller model — the metric name says which
        ctor, mname, batch, size, steps = resnet18_v1, "resnet18", 2, 64, 2

    net = ctor(layout="NHWC")
    net.initialize(mx.init.Xavier())
    if on_tpu:
        net.cast("bfloat16")
    dtype = "bfloat16" if on_tpu else "float32"
    x = nd.random.uniform(shape=(batch, size, size, 3), dtype=dtype)
    net(x)  # materialise

    def run(fn, n):
        fn(x)  # warmup/compile
        float(fn(x).asnumpy().sum())  # host-fetch sync
        t0 = time.monotonic()
        for _ in range(n):
            out = fn(x)
        float(out.asnumpy().sum())
        return batch * n / (time.monotonic() - t0)

    net.hybridize()
    fp_s = run(net, steps)
    print(f"[bench_int8] {dtype}: {fp_s:.1f} img/s", file=sys.stderr)

    qnet = quantize_net(net, quantized_dtype="int8",
                        calib_data=[x], calib_mode="naive")
    int8_s = run(qnet, steps)
    print(f"[bench_int8] int8: {int8_s:.1f} img/s "
          f"({int8_s / fp_s:.2f}x)", file=sys.stderr)

    # quality columns (ISSUE 14): logit MSE + greedy-prediction match on
    # a held batch, so the ratio above never ships alone
    ref_logits = np.asarray(net(x).asnumpy(), np.float64)
    q_logits = np.asarray(qnet(x).asnumpy(), np.float64)
    logit_mse = float(np.mean((ref_logits - q_logits) ** 2))
    greedy_match = float(np.mean(
        ref_logits.argmax(axis=-1) == q_logits.argmax(axis=-1)))
    print(f"[bench_int8] logit MSE {logit_mse:.3e}, greedy match "
          f"{greedy_match:.4f}", file=sys.stderr)

    res = {
        "metric": f"{mname}_int8_inference_throughput",
        "value": round(int8_s, 1),
        "unit": "images/sec/chip",
        # NOT vs_baseline: every other bench reserves that key for the
        # external A100-class denominator; this bench's deliverable is
        # the speedup over the SAME chip's fp path
        "speedup_vs_fp": round(int8_s / fp_s, 4),
        "fp_samples_s": round(fp_s, 1),
        "logit_mse": logit_mse,
        "greedy_match": round(greedy_match, 4),
    }
    if on_result is not None:
        on_result(res)
    return res


def main():
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(
        os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps(measure()))


if __name__ == "__main__":
    main()
