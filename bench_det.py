"""SSD-512 (ResNet-50 backbone) training throughput, images/sec/chip
(BASELINE.json config 5: "SSD-512 + Faster-RCNN object detection").

One jitted bf16 NHWC train step: SSD-512-resnet50 forward, MultiBox
target matching against the static anchor grid (precomputed once — the
anchors are model constants, matching GluonCV's generate-once design),
softmax classification + Huber localisation loss, SGD-momentum, donated
buffers.

Baseline denominator (BASELINE_IMG_S = 420), defended two ways:

1. FLOP scaling of the SURVEY §6 ResNet-50 anchor (2500 img/s at
   ~12.3 GFLOP/img-train): SSD-512's backbone runs at 512^2 = 5.2x the
   224^2 pixel count (~21 GFLOP fwd) plus extras and 3x3 heads
   (~3.5 GFLOP), so one train step is ~73 GFLOP/img; a pipeline that
   KEPT ResNet-class MXU efficiency would sustain 2500 * 12.3/73 ~= 420
   images/sec/chip. This is an upper bound on the reference: it assumes
   zero efficiency loss from the multi-scale heads, target matching,
   and the uneven feature-map shapes.
2. Published-ratio check: GluonCV's training speed tables put
   classification ResNet-50 and SSD-512-resnet50 on the same 8xV100
   hardware at a per-GPU throughput ratio of roughly 6-6.5:1 (their
   SSD-512 logs train at ~1/6.3 the img/s of their ResNet-50 runs).
   Applying that empirical pipeline-efficiency ratio to the 2500
   anchor gives 2500/6.3 ~= 395 img/s A100-class.

We keep the HIGHER (more conservative, harder-to-beat) 420 as the
vs_baseline denominator; the ratio-derived ~395 brackets it from
below, so a measured >=1.0x here clears the reference under either
derivation.

Off by default in bench.py's driver line; enable with BENCH_DET=1.
Standalone: `python bench_det.py` prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_IMG_S = 420.0


def build_step(batch, input_size=512):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.models.ssd import SSD
    from mxnet_tpu.ops import detection_ops as D

    backbone = 50 if input_size >= 256 else 18
    net = SSD(num_classes=20, backbone_layers=backbone,
              input_size=input_size)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")

    x = mx.nd.random.uniform(shape=(batch, input_size, input_size, 3),
                             dtype="bfloat16")
    net(x)  # materialise params
    fwd, params = extract_pure_fn(net, x, training=True)
    aux_idx = list(fwd.aux_indices)

    # fixed synthetic scene: 8 boxes/img; targets precomputed OUTSIDE the
    # step (anchor matching depends on labels, not weights — doing it per
    # step would bench the target generator, not the network)
    rng = np.random.RandomState(0)
    M = 8
    wh = rng.uniform(0.1, 0.4, (batch, M, 2))
    xy = rng.uniform(0.0, 0.6, (batch, M, 2))
    # classes in [0, num_classes): multibox_target emits cls+1 (0=bg), so
    # a 1-based label here would index one past the (C+1)-wide logits —
    # an OOB gather that is garbage (NaN loss) on TPU, silently clamped
    # on CPU (found by the first on-chip run of this bench)
    cls = rng.randint(0, 20, (batch, M, 1))
    labels = jnp.asarray(np.concatenate(
        [cls, xy, xy + wh], axis=-1), jnp.float32)
    anchors = jnp.asarray(net.anchors)
    cls_t, loc_t, loc_m = D.multibox_target(anchors, labels, 0.5)
    # OOB class targets are garbage on TPU but CLAMPED on CPU — assert
    # here so a smoke run catches what only the chip would reveal
    assert int(cls_t.max()) <= net.num_classes, int(cls_t.max())

    def loss_fn(p, xb, ct, lt, lm):
        (cls_p, loc_p), aux = fwd(p, xb)
        cls_p = cls_p.astype(jnp.float32)
        loc_p = loc_p.astype(jnp.float32).reshape(ct.shape[0], -1, 4)
        lp = jax.nn.log_softmax(cls_p, axis=-1)
        l_cls = -jnp.mean(jnp.take_along_axis(
            lp, ct.astype(jnp.int32)[..., None], -1))
        d = (loc_p - lt) * lm
        l_loc = jnp.mean(jnp.where(jnp.abs(d) < 1.0, 0.5 * d * d,
                                   jnp.abs(d) - 0.5))
        return l_cls + l_loc, aux

    from bench_util import make_sgd_step
    unroll = max(1, int(os.environ.get("BENCH_DET_UNROLL", "1")))
    step = make_sgd_step(loss_fn, aux_idx, lr=0.01, mu=0.9, unroll=unroll)
    mom = [jnp.zeros_like(p) for p in params]
    data = (x._data, cls_t, loc_t, loc_m)
    return step, params, mom, data, unroll


BASELINE_RCNN_IMG_S = 270.0


def build_rcnn_step(batch, input_size=512, return_parts=False,
                    unroll=1):
    """Full two-stage train step in ONE jitted program: backbone+RPN,
    proposal generation (static-k top-k + NMS), target sampling, RoIAlign
    head, RPN + RCNN losses. The reference runs this as a Python training
    loop around imperative ops; here the whole pipeline compiles into a
    single XLA executable (proposals/NMS are static-shape, so nothing
    falls back to the host between stages). With return_parts=True also
    returns (net, fwd) so callers (tools/det_convergence.py) can run
    held-out eval with the trained params."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock, extract_pure_fn
    from mxnet_tpu.ndarray.ndarray import _apply
    from mxnet_tpu.models.faster_rcnn import FasterRCNN, rcnn_targets
    from mxnet_tpu.ops import detection_ops as D

    backbone = 50 if input_size >= 256 else 18
    post_nms = 128 if input_size >= 256 else 32
    n_samples = 64 if input_size >= 256 else 16
    net = FasterRCNN(num_classes=20, backbone_layers=backbone,
                     input_size=input_size, post_nms=post_nms)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")  # same fp16-class basis as every sibling bench

    class _Train(HybridBlock):
        def __init__(self, inner, **kw):
            super().__init__(**kw)
            self.inner = inner

        def hybrid_forward(self, F, x, gt):
            obj, deltas, feat = self.inner(x)
            props, _ = self.inner.rpn_proposals(obj, deltas, pre_nms=512)
            # proposals/targets are detached constants in the reference's
            # training loop — without stop_gradient the box loss would
            # backprop through NMS/top_k/encode AND chase its own moving
            # targets (box_t depends on deltas)
            rois, cls_t, box_t, box_m = _apply(
                lambda p, g: jax.vmap(lambda pp, gg: rcnn_targets(
                    jax.lax.stop_gradient(pp), gg,
                    num_samples=n_samples))(p, g),
                [props, gt], n_out=4)
            cls, box = self.inner.roi_head(feat, rois)
            return obj, deltas, cls, box, cls_t, box_t, box_m

    wrap = _Train(net)
    x = mx.nd.random.uniform(shape=(batch, input_size, input_size, 3),
                             dtype="bfloat16")
    rng = np.random.RandomState(0)
    M = 8
    wh = rng.uniform(0.1, 0.3, (batch, M, 2)) * input_size
    xy = rng.uniform(0.0, 0.6, (batch, M, 2)) * input_size
    cls_lab = rng.randint(0, 20, (batch, M, 1)).astype(np.float32)
    gt = mx.nd.array(np.concatenate([cls_lab, xy, xy + wh], -1)
                     .astype(np.float32))
    wrap(x, gt)  # materialise params
    fwd, params = extract_pure_fn(wrap, x, gt, training=True)
    aux_idx = list(fwd.aux_indices)

    # RPN targets vs the static anchor grid, precomputed (label-only work)
    anchors_n = jnp.asarray(net.anchors, jnp.float32) / input_size
    gt_n = jnp.asarray(gt._data)
    gt_n = gt_n.at[:, :, 1:].set(gt_n[:, :, 1:] / input_size)
    # variances (1,1,1,1): generate_proposals decodes RPN deltas unscaled,
    # so the supervision must use the same encoding (r4 review finding)
    rpn_cls_t, rpn_box_t, rpn_box_m = D.multibox_target(
        anchors_n, gt_n, 0.5, variances=(1, 1, 1, 1))

    def loss_fn(p, xb, gtb, rct, rbt, rbm):
        (obj, deltas, cls, box, cls_t, box_t, box_m), aux = fwd(p, xb, gtb)
        obj = obj.astype(jnp.float32)
        rpn_obj_l = jnp.mean(
            jax.nn.log_sigmoid(jnp.where(rct > 0, obj, -obj)) * -1.0)
        d = (deltas.astype(jnp.float32) - rbt) * rbm
        rpn_box_l = jnp.mean(jnp.where(jnp.abs(d) < 1.0, 0.5 * d * d,
                                       jnp.abs(d) - 0.5))
        lp = jax.nn.log_softmax(cls.astype(jnp.float32), -1)
        rcnn_cls_l = -jnp.mean(jnp.take_along_axis(
            lp, cls_t.astype(jnp.int32)[..., None], -1))
        bsel = jnp.take_along_axis(
            box.astype(jnp.float32),
            cls_t.astype(jnp.int32)[..., None, None]
            .repeat(4, -1), -2)[..., 0, :]
        d2 = (bsel - box_t) * box_m
        rcnn_box_l = jnp.mean(jnp.where(jnp.abs(d2) < 1.0, 0.5 * d2 * d2,
                                        jnp.abs(d2) - 0.5))
        return rpn_obj_l + rpn_box_l + rcnn_cls_l + rcnn_box_l, aux

    from bench_util import make_sgd_step
    # lr 1e-3: the two-stage loss sees a SHIFTING proposal distribution
    # every step (rois follow the RPN), so the SSD bench's 0.01 oscillates
    step = make_sgd_step(loss_fn, aux_idx, lr=1e-3, mu=0.9,
                         unroll=unroll)
    mom = [jnp.zeros_like(p) for p in params]
    data = (x._data, gt._data, rpn_cls_t, rpn_box_t, rpn_box_m)
    if return_parts:
        return step, params, mom, data, (net, fwd)
    return step, params, mom, data


def _measure_rcnn(batch, steps, input_size):
    # perf lever (BENCH_DET_RCNN_UNROLL=k): k steps per dispatch, the
    # SSD/ResNet amortisation. Resolved HERE only — the convergence and
    # profile tools reuse build_rcnn_step and must keep 1 step = 1 step.
    unroll = max(1, int(os.environ.get("BENCH_DET_RCNN_UNROLL", "1")))
    step, params, mom, data = build_rcnn_step(batch, input_size,
                                              unroll=unroll)
    from bench_util import timed_measure
    return timed_measure(step, params, mom, data, steps, batch * unroll,
                         tag=f"bench_rcnn b{batch}")


def measure_rcnn(batch=None, steps=None, on_result=None):
    """Faster-RCNN-resnet50 train img/s (BASELINE config 5's second half).

    Denominator (BASELINE_RCNN_IMG_S = 270), defended: the backbone cost
    matches SSD's (~75 GFLOP/img train at 512^2) but the two-stage extra
    (proposal top-k/NMS, per-image target sampling, RoIAlign, the
    per-roi head) is gather/sort-bound, not MXU-bound. GluonCV's
    training-speed tables put SSD-512 and Faster-RCNN-resnet50 (1x,
    ~600-800px) at a per-GPU throughput ratio around 1.6-2:1 on the
    same V100 hardware. Dividing the (itself conservative) SSD
    denominator by the FAVOURABLE end of that ratio gives 420/1.6 ~=
    270; the 2:1 end would give 210. As with SSD we keep the higher
    number, so >=1.0x here clears the reference under either reading."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    candidates = ([8, 16] if on_tpu else [2]) if batch is None else (
        list(batch) if isinstance(batch, (list, tuple)) else [batch])
    if steps is None:
        steps = 10 if on_tpu else 2
    input_size = 512 if on_tpu else 128
    print(f"[bench_rcnn] backend={jax.default_backend()} "
          f"candidates={candidates} input={input_size} steps={steps}",
          file=sys.stderr)
    from bench_util import sweep

    def _res(v):
        return {"metric": "faster_rcnn_train_throughput",
                "value": round(v, 1), "unit": "images/sec/chip",
                "vs_baseline": round(v / BASELINE_RCNN_IMG_S, 4)}

    best, _ = sweep(candidates, 200,
                    lambda b: _measure_rcnn(b, steps, input_size),
                    on_best=None if on_result is None
                    else (lambda v: on_result(_res(v))),
                    tag="bench_rcnn")
    return _res(best)


def _measure_one(batch, steps, input_size):
    step, params, mom, data, unroll = build_step(batch, input_size)
    from bench_util import timed_measure
    return timed_measure(step, params, mom, data, steps, batch * unroll,
                         tag=f"bench_det b{batch}")


def measure(batch=None, steps=None, on_result=None):
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if batch is None:
        candidates = [16, 32] if on_tpu else [2]
    else:
        candidates = list(batch) if isinstance(batch, (list, tuple)) \
            else [batch]
    if steps is None:
        steps = 10 if on_tpu else 2
    input_size = 512 if on_tpu else 128
    print(f"[bench_det] backend={jax.default_backend()} "
          f"candidates={candidates} input={input_size} steps={steps}",
          file=sys.stderr)

    from bench_util import sweep
    SWEEP_BUDGET_S = 200

    best, _ = sweep(candidates, SWEEP_BUDGET_S,
                    lambda b: _measure_one(b, steps, input_size),
                    on_best=None if on_result is None
                    else (lambda v: on_result(_result(v))),
                    tag="bench_det")
    return _result(best)


def _result(img_s):
    return {
        "metric": "ssd512_train_throughput",
        "value": round(img_s, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
    }


def main():
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(
        os.path.dirname(os.path.abspath(__file__)))
    batch = os.environ.get("BENCH_DET_BATCH")
    steps = os.environ.get("BENCH_DET_STEPS")
    # standalone: BENCH_DET_RCNN=1 SELECTS the Faster-RCNN metric (one
    # JSON line per invocation); the bench.py driver's BENCH_DET=1 runs
    # both detectors and merges them as extra_metrics
    if os.environ.get("BENCH_DET_RCNN") == "1":
        res = measure_rcnn(
            [int(b) for b in batch.split(",")] if batch else None,
            int(steps) if steps else None)
    else:
        res = measure([int(b) for b in batch.split(",")] if batch else None,
                      int(steps) if steps else None)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
