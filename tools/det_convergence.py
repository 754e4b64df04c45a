"""Detection convergence evidence: train each
detector for a few hundred steps on a LEARNABLE synthetic dataset
(rendered colored rectangles — class == color), record the loss curve,
and sanity-check decoded predictions on held-out scenes.

Usage:  python tools/det_convergence.py [--model ssd|rcnn]
            [--steps N] [--batch N] [--input N] [--report PATH]

The loss curve + eval stats print as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# 4 high-contrast fill colors == 4 classes
_COLORS = np.array([[0.9, 0.1, 0.1], [0.1, 0.9, 0.1],
                    [0.15, 0.15, 0.95], [0.9, 0.9, 0.1]], np.float32)
NUM_CLASSES = len(_COLORS)

# FRCNN scene geometry, shared by train AND held-out eval: boxes sized
# to overlap the model's stride-16 RPN anchors at small inputs
RCNN_SCENE_KW = {"m_boxes": 2, "box_range": (0.4, 0.75)}


def make_scenes(n, size, m_boxes=3, seed=0, box_range=(0.25, 0.5)):
    """Render n scenes of m colored rectangles on noise background.
    Returns images (n, size, size, 3) f32 and labels (n, m, 5)
    [cls, x1, y1, x2, y2] normalized, -1-padded. box_range scales the
    rectangles — the FRCNN run uses larger boxes so the planted objects
    overlap the model's stride-16 RPN anchor sizes at small inputs."""
    rs = np.random.RandomState(seed)
    imgs = rs.uniform(0.3, 0.5, (n, size, size, 3)).astype(np.float32)
    labels = np.full((n, m_boxes, 5), -1.0, np.float32)
    for i in range(n):
        placed = []
        for j in range(m_boxes):
            # rejection-sample placements so later rectangles cannot
            # paint over earlier ones (an occluded gt box would count
            # as a miss in the recall denominator regardless of model
            # quality); scenes that can't fit another box keep the -1
            # pad row, which every consumer already skips
            for _ in range(20):
                w, h = rs.uniform(box_range[0], box_range[1], 2)
                x1 = rs.uniform(0.05, 0.95 - w)
                y1 = rs.uniform(0.05, 0.95 - h)
                cand = (x1, y1, x1 + w, y1 + h)
                if all(_iou(cand, p) < 0.1 for p in placed):
                    break
            else:
                continue
            placed.append(cand)
            c = rs.randint(NUM_CLASSES)
            px1, py1 = int(x1 * size), int(y1 * size)
            px2, py2 = int((x1 + w) * size), int((y1 + h) * size)
            imgs[i, py1:py2, px1:px2] = _COLORS[c] \
                + rs.uniform(-0.05, 0.05, 3).astype(np.float32)
            labels[i, j] = [c, x1, y1, x1 + w, y1 + h]
    return imgs, labels


def _iou(a, b):
    ix1, iy1 = max(a[0], b[0]), max(a[1], b[1])
    ix2, iy2 = min(a[2], b[2]), min(a[3], b[3])
    iw, ih = max(0.0, ix2 - ix1), max(0.0, iy2 - iy1)
    inter = iw * ih
    ua = (a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1]) \
        - inter
    return inter / max(ua, 1e-9)


def run_ssd(args):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.models.ssd import SSD, ssd_decode
    from mxnet_tpu.ops import detection_ops as D
    from bench_util import make_sgd_step

    size, batch = args.input, args.batch
    net = SSD(num_classes=NUM_CLASSES,
              backbone_layers=18 if size < 256 else 50, input_size=size)
    net.initialize(mx.init.Xavier())
    warm = mx.nd.array(np.zeros((batch, size, size, 3), np.float32))
    net(warm)
    fwd, params = extract_pure_fn(net, warm, training=True)
    aux_idx = list(fwd.aux_indices)
    anchors = jnp.asarray(net.anchors)

    n_train = args.batch * 24
    imgs, labels = make_scenes(n_train, size, seed=0)
    t_cls, t_loc, t_msk = [], [], []
    for s in range(0, n_train, batch):
        ct, lt, lm = D.multibox_target(
            anchors, jnp.asarray(labels[s:s + batch]), 0.5)
        t_cls.append(ct); t_loc.append(lt); t_msk.append(lm)

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

    step = make_sgd_step(loss_fn, aux_idx, lr=args.lr, mu=0.9)
    mom = [jnp.zeros_like(p) for p in params]
    curve = []
    n_b = len(t_cls)
    t0 = time.time()
    for it in range(args.steps):
        b = it % n_b
        xb = jnp.asarray(imgs[b * batch:(b + 1) * batch])
        params, mom, loss = step(params, mom, xb, t_cls[b], t_loc[b],
                                 t_msk[b])
        if it % 20 == 0 or it == args.steps - 1:
            curve.append([it, round(float(loss), 4)])
            print(f"[ssd] step {it} loss {float(loss):.4f} "
                  f"({time.time()-t0:.0f}s)", file=sys.stderr)

    # held-out eval through the real decode (softmax -> MultiBoxDetection
    # NMS) — predictions must be finite, in-bounds, and hit the planted
    # boxes with the right class. Reuses the training fwd with the
    # TRAINED param list (same extract, same ordering); batch-stat BN is
    # fine for this sanity check.
    ev_imgs, ev_labels = make_scenes(batch, size, seed=99)
    (cls_p, loc_p), _ = fwd(params, jnp.asarray(ev_imgs))
    det = ssd_decode(mx.nd.NDArray(cls_p.astype(jnp.float32)),
                     mx.nd.NDArray(loc_p.astype(jnp.float32)),
                     net.anchors).asnumpy()
    hits = total = 0
    finite = bool(np.isfinite(det).all())
    for i in range(batch):
        keep = det[i][det[i][:, 0] >= 0]
        keep = keep[keep[:, 1] > 0.3][:8]
        for (c, x1, y1, x2, y2) in ev_labels[i]:
            if c < 0:
                continue
            total += 1
            for row in keep:
                if int(row[0]) == int(c) and \
                        _iou(row[2:6], (x1, y1, x2, y2)) > 0.3:
                    hits += 1
                    break
    return {"model": "ssd", "input": size, "batch": batch,
            "steps": args.steps, "loss_curve": curve,
            "final_loss": curve[-1][1], "detections_finite": finite,
            "holdout_recall@iou0.3": round(hits / max(total, 1), 3)}


def run_rcnn(args):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    import bench_det
    from mxnet_tpu.ops import detection_ops as D
    from bench_util import make_sgd_step
    from mxnet_tpu.gluon.block import extract_pure_fn

    size, batch = args.input, args.batch
    # reuse the benched two-stage step builder wholesale, then retrain it
    # on varying rendered scenes (build_step bakes one batch; the jitted
    # step accepts any same-shape data)
    step, params, mom, data0, (net, fwd) = bench_det.build_rcnn_step(
        batch, size, return_parts=True)
    from mxnet_tpu.models.faster_rcnn import FasterRCNN  # for anchors

    n_train = batch * 24
    imgs, labels = make_scenes(n_train, size, seed=0, **RCNN_SCENE_KW)
    # bench_det's step takes (x, gt_pixels, rpn_cls_t, rpn_box_t,
    # rpn_box_m); regenerate those per chunk
    net_like = FasterRCNN(num_classes=20,
                          backbone_layers=18 if size < 256 else 50,
                          input_size=size)
    anchors_n = jnp.asarray(net_like.anchors, jnp.float32) / size
    batches = []
    for s in range(0, n_train, batch):
        lab = labels[s:s + batch].copy()
        gt_px = lab.copy()
        gt_px[..., 1:] *= size
        gt_px[gt_px[..., 0] < 0] = -1
        gt_n = jnp.asarray(lab, jnp.float32)
        rct, rbt, rbm = D.multibox_target(anchors_n, gt_n, 0.5,
                                          variances=(1, 1, 1, 1))
        batches.append((jnp.asarray(imgs[s:s + batch], jnp.bfloat16),
                        jnp.asarray(gt_px, jnp.float32), rct, rbt, rbm))

    curve = []
    t0 = time.time()
    for it in range(args.steps):
        b = batches[it % len(batches)]
        params, mom, loss = step(params, mom, *b)
        if it % 20 == 0 or it == args.steps - 1:
            curve.append([it, round(float(loss), 4)])
            print(f"[rcnn] step {it} loss {float(loss):.4f} "
                  f"({time.time()-t0:.0f}s)", file=sys.stderr)
    # held-out sanity: after training, the RPN's decoded+NMS'd proposals
    # must cover the planted boxes (recall@IoU0.5) and be finite
    ev_imgs, ev_labels = make_scenes(batch, size, seed=99,
                                     **RCNN_SCENE_KW)
    ev_gt_px = ev_labels.copy()
    ev_gt_px[..., 1:] *= size
    ev_gt_px[ev_labels[..., 0] < 0] = -1
    (obj, deltas, *_rest), _ = fwd(
        params, jnp.asarray(ev_imgs, jnp.bfloat16),
        jnp.asarray(ev_gt_px, jnp.float32))
    props, _scores = net.rpn_proposals(
        mx.nd.NDArray(obj), mx.nd.NDArray(deltas), pre_nms=512)
    props = props.asnumpy()
    finite = bool(np.isfinite(props).all())
    hits = total = 0
    for i in range(batch):
        for (c, x1, y1, x2, y2) in ev_gt_px[i]:
            if c < 0:
                continue
            total += 1
            if any(_iou(p, (x1, y1, x2, y2)) > 0.5 for p in props[i]):
                hits += 1
    return {"model": "rcnn", "input": size, "batch": batch,
            "steps": args.steps, "loss_curve": curve,
            "final_loss": curve[-1][1],
            "proposals_finite": finite,
            "proposal_recall@iou0.5": round(hits / max(total, 1), 3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("ssd", "rcnn"), default="ssd")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--input", type=int, default=None)
    ap.add_argument("--lr", type=float, default=0.02)
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if args.input is None:
        args.input = 256 if on_tpu else 128
    if args.batch is None:
        args.batch = 16 if on_tpu else 4
    res = (run_ssd if args.model == "ssd" else run_rcnn)(args)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
