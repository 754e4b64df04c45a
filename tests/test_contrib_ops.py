"""Reference `contrib` op namespace parity (upstream:
src/operator/contrib/*.cc). Every op is exercised from nd AND sym, with
parity pinned against closed forms (lax conv, numpy FFT, hand-computed
sketches) rather than against our own kernels."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


# --------------------------------------------------------------- fft / ifft
def test_fft_matches_numpy_interleaved():
    x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
    out = nd.contrib.fft(nd.array(x)).asnumpy()
    assert out.shape == (3, 16)
    ref = np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(out[:, 0::2], ref.real, atol=1e-4)
    np.testing.assert_allclose(out[:, 1::2], ref.imag, atol=1e-4)


def test_ifft_unnormalised_roundtrip():
    """Upstream contrib.ifft does NOT divide by d: ifft(fft(x)) == d*x."""
    x = np.random.RandomState(1).randn(2, 16).astype(np.float32)
    back = nd.contrib.ifft(nd.contrib.fft(nd.array(x))).asnumpy()
    np.testing.assert_allclose(back, 16 * x, rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------- count_sketch
def test_count_sketch_closed_form():
    d, out_dim = 6, 4
    rs = np.random.RandomState(2)
    x = rs.randn(3, d).astype(np.float32)
    h = rs.randint(0, out_dim, size=d)
    s = rs.choice([-1.0, 1.0], size=d).astype(np.float32)
    out = nd.contrib.count_sketch(nd.array(x), nd.array(h),
                                  nd.array(s), out_dim).asnumpy()
    ref = np.zeros((3, out_dim), np.float32)
    for j in range(d):
        ref[:, h[j]] += s[j] * x[:, j]
    np.testing.assert_allclose(out, ref, atol=1e-5)


# ---------------------------------------------------- DeformableConvolution
def _ref_conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(pad[0], pad[0]),
                                              (pad[1], pad[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def test_deformable_conv_zero_offset_is_conv():
    """Zero offsets reduce deformable conv to a standard convolution —
    the upstream-documented identity, pinned against lax.conv."""
    rs = np.random.RandomState(3)
    x = rs.randn(2, 4, 9, 9).astype(np.float32)
    w = rs.randn(5, 4, 3, 3).astype(np.float32)
    off = np.zeros((2, 2 * 3 * 3, 9, 9), np.float32)
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), kernel=(3, 3),
        pad=(1, 1)).asnumpy()
    ref = np.asarray(_ref_conv(jnp.asarray(x), jnp.asarray(w), (1, 1),
                               (1, 1)))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


def test_deformable_conv_integer_offset_shifts_sampling():
    """A constant integer offset (dy=0, dx=1) must equal convolving the
    x-shifted image (checks the [dy, dx] channel layout)."""
    rs = np.random.RandomState(4)
    x = rs.randn(1, 2, 8, 8).astype(np.float32)
    w = rs.randn(3, 2, 3, 3).astype(np.float32)
    off = np.zeros((1, 2 * 9, 8, 8), np.float32)
    off[:, 1::2] = 1.0          # dx = +1 for every tap
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), kernel=(3, 3),
        pad=(1, 1)).asnumpy()
    x_shift = np.zeros_like(x)
    x_shift[..., :-1] = x[..., 1:]       # sample at x+1 == image shifted left
    ref = np.asarray(_ref_conv(jnp.asarray(x_shift), jnp.asarray(w), (1, 1),
                               (1, 1)))
    # interior only: the zero-padding border differs (shifted-image pad
    # column vs out-of-image samples) — same sampling everywhere else
    np.testing.assert_allclose(out[..., 1:-1, 1:-1], ref[..., 1:-1, 1:-1],
                               rtol=1e-3, atol=1e-3)


def test_deformable_conv_groups_and_stride():
    rs = np.random.RandomState(5)
    x = rs.randn(1, 4, 8, 8).astype(np.float32)
    w = rs.randn(4, 2, 3, 3).astype(np.float32)     # num_group=2
    off = np.zeros((1, 2 * 2 * 9, 3, 3), np.float32)  # dg=2, OH=OW=3
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), kernel=(3, 3),
        stride=(2, 2), num_group=2, num_deformable_group=2).asnumpy()
    ref = np.asarray(lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), window_strides=(2, 2),
        padding="VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=2))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------- ROIAlign
def test_roi_align_batch_indexing_and_identity():
    """A stride-1 unit-scale ROI over an aligned grid reproduces bilinear
    averages; batch_idx selects the right image; idx<0 zeros the output."""
    rs = np.random.RandomState(6)
    feats = rs.randn(2, 3, 10, 10).astype(np.float32)
    rois = np.array([[0, 2.0, 2.0, 6.0, 6.0],
                     [1, 0.0, 0.0, 4.0, 4.0],
                     [-1, 0.0, 0.0, 4.0, 4.0]], np.float32)
    out = nd.contrib.ROIAlign(nd.array(feats), nd.array(rois),
                              pooled_size=(2, 2), spatial_scale=1.0,
                              sample_ratio=2).asnumpy()
    assert out.shape == (3, 3, 2, 2)
    assert np.all(out[2] == 0.0)                     # invalid batch idx
    assert not np.allclose(out[0], out[1])           # different images
    # parity vs the single-image kernel on image 1
    from mxnet_tpu.ops.detection_ops import roi_align
    ref = np.asarray(roi_align(jnp.asarray(feats[1]),
                               jnp.asarray(rois[1:2, 1:]),
                               out_size=(2, 2), spatial_scale=1.0,
                               sampling_ratio=2))[0]
    np.testing.assert_allclose(out[1], ref, rtol=1e-5)


# ------------------------------------------------------------------ box ops
def test_box_nms_suppression_and_layout():
    # rows: [id, score, x0, y0, x1, y1]
    data = np.array([
        [0, 0.9, 0.0, 0.0, 1.0, 1.0],
        [0, 0.8, 0.05, 0.05, 1.05, 1.05],   # IoU ~0.82 with row 0 -> dead
        [0, 0.7, 2.0, 2.0, 3.0, 3.0],       # disjoint -> survives
        [1, 0.6, 0.0, 0.0, 1.0, 1.0],       # other class -> survives
    ], np.float32)
    out = nd.contrib.box_nms(nd.array(data), overlap_thresh=0.5,
                             id_index=0).asnumpy()
    assert out.shape == data.shape
    kept_scores = sorted(out[out[:, 1] > 0][:, 1].tolist(), reverse=True)
    assert kept_scores == pytest.approx([0.9, 0.7, 0.6])
    assert np.all(out[-1] == -1.0)          # suppressed row is all -1
    # force_suppress ignores the class id -> row 3 dies too
    out_f = nd.contrib.box_nms(nd.array(data), overlap_thresh=0.5,
                               id_index=0, force_suppress=True).asnumpy()
    assert sorted(out_f[out_f[:, 1] > 0][:, 1].tolist(),
                  reverse=True) == pytest.approx([0.9, 0.7])


def test_box_iou_formats_and_batching():
    a = np.array([[0.0, 0.0, 2.0, 2.0]], np.float32)
    b = np.array([[1.0, 1.0, 3.0, 3.0]], np.float32)
    iou = nd.contrib.box_iou(nd.array(a), nd.array(b)).asnumpy()
    np.testing.assert_allclose(iou, [[1.0 / 7.0]], rtol=1e-5)
    # center format: same boxes expressed as (cx, cy, w, h)
    ac = np.array([[1.0, 1.0, 2.0, 2.0]], np.float32)
    bc = np.array([[2.0, 2.0, 2.0, 2.0]], np.float32)
    iou_c = nd.contrib.box_iou(nd.array(ac), nd.array(bc),
                               format="center").asnumpy()
    np.testing.assert_allclose(iou_c, iou, rtol=1e-5)
    # batched
    iou_b = nd.contrib.box_iou(nd.array(np.stack([a, a])),
                               nd.array(np.stack([b, b]))).asnumpy()
    assert iou_b.shape == (2, 1, 1)


# ------------------------------------------------------------ MultiBox trio
def test_multibox_reference_layouts():
    B, C, Hf, Wf = 2, 8, 4, 4
    feat = nd.random.uniform(shape=(B, C, Hf, Wf))
    anchors = nd.contrib.MultiBoxPrior(feat, sizes=(0.4, 0.8),
                                       ratios=(1.0, 2.0), clip=True)
    A = Hf * Wf * 3          # K = |sizes| + |ratios| - 1
    assert anchors.shape == (1, A, 4)
    an = anchors.asnumpy()
    assert an.min() >= 0.0 and an.max() <= 1.0

    labels = np.full((B, 2, 5), -1.0, np.float32)
    labels[0, 0] = [1, 0.1, 0.1, 0.4, 0.4]
    labels[1, 0] = [0, 0.5, 0.5, 0.9, 0.9]
    cls_pred = nd.random.uniform(shape=(B, 3, A))
    loc_t, loc_mask, cls_t = nd.contrib.MultiBoxTarget(
        anchors, nd.array(labels), cls_pred)
    assert loc_t.shape == (B, A * 4)
    assert loc_mask.shape == (B, A * 4)
    assert cls_t.shape == (B, A)
    ct = cls_t.asnumpy()
    assert (ct[0] == 2).any() and not (ct[0] == 1).any()  # cls+1 encoding
    assert (ct[1] == 1).any()

    probs = np.zeros((B, 3, A), np.float32)
    probs[:, 0] = 1.0
    probs[0, 0, 5], probs[0, 1, 5] = 0.1, 0.9   # one confident class-0 det
    dets = nd.contrib.MultiBoxDetection(
        nd.array(probs), nd.zeros((B, A * 4)), anchors, max_det=10)
    assert dets.shape == (B, 10, 6)
    d0 = dets.asnumpy()[0]
    assert d0[0, 0] == 0 and d0[0, 1] == pytest.approx(0.9, rel=1e-3)
    assert np.all(dets.asnumpy()[1][:, 0] == -1)  # nothing above threshold


# ---------------------------------------------------------------- proposals
def test_multi_proposal_basics():
    B, A, Hf, Wf = 2, 2, 5, 5    # A = |scales| * |ratios| = 2*1
    rs = np.random.RandomState(7)
    cls_prob = rs.rand(B, 2 * A, Hf, Wf).astype(np.float32)
    bbox_pred = (rs.randn(B, 4 * A, Hf, Wf) * 0.1).astype(np.float32)
    im_info = np.array([[80.0, 80.0, 1.0]] * B, np.float32)
    rois = nd.contrib.MultiProposal(
        nd.array(cls_prob), nd.array(bbox_pred), nd.array(im_info),
        rpn_pre_nms_top_n=40, rpn_post_nms_top_n=8, feature_stride=16,
        scales=(2, 4), ratios=(1.0,), threshold=0.7,
        rpn_min_size=4).asnumpy()
    assert rois.shape == (B * 8, 5)
    # batch indices blocked [0]*8 then [1]*8
    np.testing.assert_array_equal(rois[:8, 0], 0)
    np.testing.assert_array_equal(rois[8:, 0], 1)
    # proposals clipped to the image
    assert rois[:, 1:].min() >= 0.0
    assert rois[:, [1, 3]].max() <= 79.0 and rois[:, [2, 4]].max() <= 79.0
    # scores come back too when asked
    r2, scores = nd.contrib.MultiProposal(
        nd.array(cls_prob), nd.array(bbox_pred), nd.array(im_info),
        rpn_pre_nms_top_n=40, rpn_post_nms_top_n=8, feature_stride=16,
        scales=(2, 4), ratios=(1.0,), rpn_min_size=4, output_score=True)
    s = scores.asnumpy().reshape(B, 8)
    assert np.all(np.diff(s, axis=1) <= 1e-6)       # sorted descending


def test_proposal_rejects_batched_input():
    with pytest.raises(mx.base.MXNetError):
        nd.contrib.Proposal(nd.zeros((2, 6, 4, 4)), nd.zeros((2, 12, 4, 4)),
                            nd.zeros((2, 3)))


# ------------------------------------------------------------ symbol parity
def test_sym_contrib_json_roundtrip_and_parity():
    """Every new contrib op must build symbolically, round-trip through
    tojson/load_json, and evaluate to the nd result."""
    rs = np.random.RandomState(8)
    feats = rs.randn(1, 2, 6, 6).astype(np.float32)
    rois = np.array([[0, 1.0, 1.0, 4.0, 4.0]], np.float32)

    d = sym.Variable("d")
    r = sym.Variable("r")
    out = sym.contrib.ROIAlign(d, r, pooled_size=(2, 2), spatial_scale=1.0,
                               sample_ratio=2)
    loaded = mx.sym.load_json(out.tojson())
    got = loaded.eval_with({"d": nd.array(feats), "r": nd.array(rois)})
    want = nd.contrib.ROIAlign(nd.array(feats), nd.array(rois),
                               pooled_size=(2, 2))
    np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-5)

    x = rs.randn(2, 8).astype(np.float32)
    v = sym.Variable("x")
    f = sym.contrib.ifft(sym.contrib.fft(v))
    f2 = mx.sym.load_json(f.tojson())
    got = f2.eval_with({"x": nd.array(x)})
    np.testing.assert_allclose(got.asnumpy(), 8 * x, rtol=1e-4, atol=1e-3)

    # one JSON round-trip building every remaining op (graph validity)
    a = sym.Variable("a")
    b = sym.Variable("b")
    graph = sym.Group([
        sym.contrib.box_nms(a),
        sym.contrib.box_iou(a, b),
        sym.contrib.MultiBoxPrior(a, sizes=(0.5,)),
        sym.contrib.fft(a),
        sym.contrib.count_sketch(a, b, b, out_dim=4),
    ]) if hasattr(sym, "Group") else None
    if graph is not None:
        js = graph.tojson()
        assert mx.sym.load_json(js).tojson() == js


def test_sym_deformable_conv_matches_nd():
    rs = np.random.RandomState(9)
    x = rs.randn(1, 2, 6, 6).astype(np.float32)
    w = rs.randn(3, 2, 3, 3).astype(np.float32)
    off = (rs.randn(1, 18, 6, 6) * 0.3).astype(np.float32)
    dv, ov, wv = sym.Variable("x"), sym.Variable("o"), sym.Variable("w")
    out = sym.contrib.DeformableConvolution(dv, ov, wv, kernel=(3, 3),
                                            pad=(1, 1))
    out = mx.sym.load_json(out.tojson())
    got = out.eval_with({"x": nd.array(x), "o": nd.array(off),
                         "w": nd.array(w)})
    want = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), kernel=(3, 3), pad=(1, 1))
    np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-4,
                               atol=1e-5)


def test_sym_multibox_target_three_outputs():
    anchors = nd.contrib.MultiBoxPrior(nd.zeros((1, 1, 3, 3)), sizes=(0.5,))
    A = anchors.shape[1]
    labels = np.full((1, 1, 5), -1.0, np.float32)
    labels[0, 0] = [0, 0.2, 0.2, 0.7, 0.7]
    av, lv, cv = (sym.Variable(n) for n in "alc")
    outs = sym.contrib.MultiBoxTarget(av, lv, cv)
    grp = mx.sym.Group(outs) if isinstance(outs, list) else outs
    js = mx.sym.load_json(grp.tojson())
    got = js.eval_with({"a": anchors, "l": nd.array(labels),
                        "c": nd.zeros((1, 2, A))})
    got = got if isinstance(got, (list, tuple)) else [got]
    assert [tuple(g.shape) for g in got] == [(1, A * 4), (1, A * 4), (1, A)]


def test_box_encode_mean_std_order():
    """Targets are (raw - mean)/std — upstream order, not raw/std - mean."""
    anchors = np.array([[[0.0, 0.0, 2.0, 2.0]]], np.float32)
    refs = np.array([[[0.5, 0.5, 2.5, 2.5]]], np.float32)   # shifted gt
    samples = np.ones((1, 1), np.float32)
    matches = np.zeros((1, 1), np.float32)
    means, stds = (0.1, 0.1, 0.1, 0.1), (0.2, 0.2, 0.3, 0.3)
    t, mask = nd.contrib.box_encode(
        nd.array(samples), nd.array(matches), nd.array(anchors),
        nd.array(refs), means=means, stds=stds)
    # closed form: center offsets dx=dy=0.5/2=0.25, dw=dh=log(1)=0
    raw = np.array([0.25, 0.25, 0.0, 0.0], np.float32)
    want = (raw - np.asarray(means)) / np.asarray(stds)
    np.testing.assert_allclose(t.asnumpy()[0, 0], want, rtol=1e-5)
    assert mask.asnumpy().min() == 1.0


def test_multibox_prior_steps_override():
    """Explicit steps move the anchor grid (SSD presets rely on this)."""
    feat = nd.zeros((1, 1, 4, 4))
    default = nd.contrib.MultiBoxPrior(feat, sizes=(0.2,)).asnumpy()
    stepped = nd.contrib.MultiBoxPrior(
        feat, sizes=(0.2,), steps=(0.5, 0.5)).asnumpy()
    assert not np.allclose(default, stepped)
    # first anchor center with steps=(0.5, 0.5): (0.25, 0.25)
    c0 = (stepped[0, 0, :2] + stepped[0, 0, 2:]) / 2.0
    np.testing.assert_allclose(c0, [0.25, 0.25], atol=1e-6)
    # default spacing is 1/feat: first center (0.125, 0.125)
    c0d = (default[0, 0, :2] + default[0, 0, 2:]) / 2.0
    np.testing.assert_allclose(c0d, [0.125, 0.125], atol=1e-6)


# ------------------------------------------- adaptive pool / bilinear alias
def test_adaptive_avg_pooling2d_matches_torch():
    """Region rule parity (upstream adaptive_avg_pooling-inl.h uses the
    same floor/ceil regions torch does)."""
    torch = pytest.importorskip("torch")
    x = np.random.RandomState(1).rand(2, 3, 13, 17).astype(np.float32)
    out = nd.contrib.AdaptiveAvgPooling2D(
        nd.array(x), output_size=(5, 6)).asnumpy()
    ref = torch.nn.functional.adaptive_avg_pool2d(
        torch.from_numpy(x), (5, 6)).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-5)
    # int output_size means square, and dividing sizes reduce to plain
    # average pooling
    sq = nd.contrib.AdaptiveAvgPooling2D(nd.array(x[:, :, :12, :16]),
                                         output_size=4).asnumpy()
    ref_sq = x[:, :, :12, :16].reshape(2, 3, 4, 3, 4, 4).mean((3, 5))
    np.testing.assert_allclose(sq, ref_sq, atol=1e-5)


def test_adaptive_avg_pooling2d_sym_json_roundtrip():
    x = np.random.RandomState(2).rand(1, 2, 9, 9).astype(np.float32)
    s = sym.contrib.AdaptiveAvgPooling2D(sym.Variable("data"),
                                         output_size=(3, 3))
    s2 = mx.sym.load_json(s.tojson())
    out = s2.bind(mx.cpu(), {"data": nd.array(x)}).forward()[0].asnumpy()
    ref = x.reshape(1, 2, 3, 3, 3, 3).mean((3, 5))
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_bilinear_resize2d_contrib_alias():
    """upstream documents BilinearResize2D under contrib; both nd.contrib
    and sym.contrib must carry the alias."""
    x = np.random.RandomState(3).rand(1, 2, 5, 5).astype(np.float32)
    top = mx.nd.BilinearResize2D(nd.array(x), height=10, width=10).asnumpy()
    via_contrib = nd.contrib.BilinearResize2D(
        nd.array(x), height=10, width=10).asnumpy()
    np.testing.assert_allclose(top, via_contrib, atol=1e-6)
    s = sym.contrib.BilinearResize2D(sym.Variable("data"),
                                     height=10, width=10)
    s2 = mx.sym.load_json(s.tojson())
    out = s2.bind(mx.cpu(), {"data": nd.array(x)}).forward()[0].asnumpy()
    np.testing.assert_allclose(out, via_contrib, atol=1e-6)


def test_log_validation_metrics_callback(caplog):
    import logging
    from mxnet_tpu.callback import (BatchEndParam,
                                    LogValidationMetricsCallback)
    from mxnet_tpu.metric import Accuracy
    m = Accuracy()
    m.update([nd.array([0, 1])], [nd.array([[0.9, 0.1], [0.2, 0.8]])])
    cb = LogValidationMetricsCallback()
    with caplog.at_level(logging.INFO):
        cb(BatchEndParam(epoch=3, nbatch=0, eval_metric=m, locals=None))
    assert any("Validation-accuracy" in r.message for r in caplog.records)


def test_bilinear_resize2d_scale_mode_and_errors():
    x = np.random.RandomState(4).rand(1, 2, 6, 8).astype(np.float32)
    y = nd.contrib.BilinearResize2D(nd.array(x), scale_height=2.0,
                                    scale_width=0.5)
    assert y.shape == (1, 2, 12, 4)
    s = sym.contrib.BilinearResize2D(sym.Variable("d"), scale_height=2.0,
                                     scale_width=0.5)
    out = mx.sym.load_json(s.tojson()).bind(
        mx.cpu(), {"d": nd.array(x)}).forward()[0]
    np.testing.assert_allclose(out.asnumpy(), y.asnumpy(), atol=1e-6)
    with pytest.raises(mx.base.MXNetError):
        nd.contrib.BilinearResize2D(nd.array(x), height=10)  # no width
    with pytest.raises(mx.base.MXNetError):
        sym.contrib.BilinearResize2D(sym.Variable("d"), width=4)
