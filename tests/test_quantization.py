"""INT8 quantization tests (SURVEY.md §2 #49; reference:
tests/python/quantization/test_quantization.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.contrib import quantization as q
from mxnet_tpu.gluon import nn


def test_quantize_dequantize_roundtrip():
    x = nd.array(np.linspace(-2.0, 2.0, 64).astype(np.float32))
    xq, mn, mx_ = q.quantize(x)
    assert "int8" in str(xq.dtype)
    back = q.dequantize(xq, mn, mx_)
    np.testing.assert_allclose(back.asnumpy(), x.asnumpy(), atol=2.0 / 127)


def test_quantized_dense_matches_fp():
    mx.random.seed(0)
    dense = nn.Dense(16, in_units=32)
    dense.initialize()
    qd = q.QuantizedDense(dense)
    assert str(qd.wq.dtype) == "int8"
    x = nd.random.uniform(-1, 1, shape=(4, 32))
    y_fp = dense(x).asnumpy()
    y_q = qd(x).asnumpy()
    # int8 symmetric: ~1% of dynamic range
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.05, err


def test_quantized_conv_matches_fp():
    mx.random.seed(1)
    conv = nn.Conv2D(8, kernel_size=3, padding=1, in_channels=4)
    conv.initialize()
    x = nd.random.uniform(-1, 1, shape=(2, 4, 8, 8))
    y_fp = conv(x).asnumpy()
    qc = q.QuantizedConv2D(conv)
    y_q = qc(x).asnumpy()
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.05, err


def test_quantize_net_end_to_end():
    mx.random.seed(2)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16),
            nn.Dense(10, in_units=32))
    net.initialize()
    x = nd.random.uniform(-1, 1, shape=(8, 16))
    y_fp = net(x).asnumpy()
    qnet = q.quantize_net(net)
    assert len(qnet.quantized_layers) == 2
    y_q = qnet(x).asnumpy()
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.1, err
    # argmax (classification decision) should essentially agree
    agree = (y_fp.argmax(1) == y_q.argmax(1)).mean()
    assert agree >= 0.75


def test_quantize_net_calibration_freezes_scales():
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4))
    net.initialize()
    calib = [nd.random.uniform(-1, 1, shape=(4, 4)) for _ in range(3)]
    qnet = q.quantize_net(net, calib_data=calib, num_calib_batches=3)
    (layer,) = qnet.quantized_layers
    assert layer._act_scale is not None and layer._act_scale > 0
    x = nd.random.uniform(-1, 1, shape=(4, 4))
    err = np.abs(net(x).asnumpy() - qnet(x).asnumpy()).max()
    assert err < 0.1


def test_quantize_net_exclude_layers():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize()
    qnet = q.quantize_net(net, exclude_layers=["1"])
    assert len(qnet.quantized_layers) == 1


def test_quantize_net_no_quantizable_raises():
    net = nn.HybridSequential()
    net.add(nn.Dropout(0.5))
    with pytest.raises(Exception):
        q.quantize_net(net)


def test_quantize_net_nested_sequential():
    """Nested Sequential containers are rewired too (not silently fp)."""
    mx.random.seed(4)
    inner = nn.HybridSequential()
    inner.add(nn.Dense(16, activation="relu", in_units=8))
    net = nn.HybridSequential()
    net.add(inner, nn.Dense(4, in_units=16))
    net.initialize()
    x = nd.random.uniform(-1, 1, shape=(4, 8))
    y_fp = net(x).asnumpy()
    qnet = q.quantize_net(net)
    assert len(qnet.quantized_layers) == 2
    y_q = qnet(x).asnumpy()
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.1, err


def test_quantize_net_custom_block_supported():
    """Quantizable layers inside CUSTOM blocks are rewired too (r3 weak 3:
    the old implementation refused anything but Sequential trees)."""
    mx.random.seed(6)

    class Custom(nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.fc = nn.Dense(4, in_units=4)

        def hybrid_forward(self, F, x):
            return self.fc(x) + x          # residual: not a plain chain

    net = nn.HybridSequential()
    net.add(Custom())
    net.initialize()
    x = nd.random.uniform(-1, 1, shape=(2, 4))
    y_fp = net(x).asnumpy()
    qnet = q.quantize_net(net)
    assert len(qnet.quantized_layers) == 1
    y_q = qnet(x).asnumpy()
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.1, err
    # the ORIGINAL net still runs fp32 when called directly
    np.testing.assert_allclose(net(x).asnumpy(), y_fp, rtol=1e-6)


def test_quantize_net_zoo_resnet18():
    """The obvious int8 target works end to end: quantize_net over a zoo
    resnet18 (custom residual HybridBlocks), classification decisions
    within 1% of fp32 on synthetic data."""
    mx.random.seed(7)
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    net = resnet18_v1(classes=10)
    net.initialize()
    x = nd.random.uniform(0, 1, shape=(8, 3, 32, 32))
    y_fp = net(x).asnumpy()
    qnet = q.quantize_net(net, calib_data=[x], calib_mode="naive")
    assert len(qnet.quantized_layers) >= 18   # convs + fc
    y_q = qnet(x).asnumpy()
    agree = (y_fp.argmax(1) == y_q.argmax(1)).mean()
    assert agree >= 0.99, agree
    rel = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert rel < 0.15, rel


def test_entropy_calibration_beats_naive_on_skewed_activations():
    """A heavy-tailed input (one huge outlier) wrecks max-abs scaling;
    the KL threshold clips the tail and must reconstruct the bulk better."""
    mx.random.seed(8)
    rs = np.random.RandomState(0)
    bulk = rs.uniform(-1, 1, size=(256, 32)).astype(np.float32)
    bulk[0, 0] = 80.0           # lone outlier -> naive scale 80/127
    dense = nn.Dense(16, in_units=32)
    dense.initialize()

    def quantize_with(mode):
        net = nn.HybridSequential()
        net.add(dense)
        qnet = q.quantize_net(net, calib_data=[nd.array(bulk)],
                              calib_mode=mode)
        (layer,) = qnet.quantized_layers
        return qnet, layer

    _, naive_layer = quantize_with("naive")
    q_ent, ent_layer = quantize_with("entropy")
    assert ent_layer._act_scale < naive_layer._act_scale * 0.5, \
        (ent_layer._act_scale, naive_layer._act_scale)
    # reconstruction of the BULK is tighter under the entropy scale
    x_eval = nd.array(rs.uniform(-1, 1, size=(64, 32)).astype(np.float32))
    y_fp = dense(x_eval).asnumpy()
    err_ent = np.abs(q_ent(x_eval).asnumpy() - y_fp).mean()
    s_naive = float(naive_layer._act_scale)
    # naive error floor ~ uniform quantization noise at scale 80/127
    assert err_ent < s_naive, (err_ent, s_naive)


def test_kl_threshold_closed_form():
    """Decaying bulk + lone outlier -> threshold well below amax (coarse
    128-level merges can't reconstruct a non-uniform bulk, clipping can)."""
    hist = np.zeros(2048)
    hist[:128] = np.linspace(1000.0, 10.0, 128)   # decaying bulk
    hist[-1] = 1.0                                 # outlier at amax
    t = q.kl_optimal_threshold(hist, amax=80.0)
    assert t < 20.0, t
    # uniform histogram -> keep (close to) the full range
    t_full = q.kl_optimal_threshold(np.ones(2048), amax=1.0)
    assert t_full > 0.9


def test_uint8_activations_zero_point_decomposition():
    """quantized_dtype='uint8' on non-negative activations: the int8
    MXU path + 128-correction must match fp32 within uint8 resolution,
    and beat int8 resolution on the same data."""
    mx.random.seed(9)
    dense = nn.Dense(16, in_units=32)
    dense.initialize()
    x = nd.random.uniform(0, 1, shape=(64, 32))    # post-relu-like
    net = nn.HybridSequential()
    net.add(dense)
    y_fp = dense(x).asnumpy()

    q_u8 = q.quantize_net(net, quantized_dtype="uint8", calib_data=[x])
    (l_u8,) = q_u8.quantized_layers
    assert l_u8._act_unsigned
    err_u8 = np.abs(q_u8(x).asnumpy() - y_fp).mean()

    q_s8 = q.quantize_net(net, quantized_dtype="int8", calib_data=[x])
    err_s8 = np.abs(q_s8(x).asnumpy() - y_fp).mean()
    assert err_u8 < err_s8, (err_u8, err_s8)

    # 'auto' picks uint8 for non-negative ranges
    q_auto = q.quantize_net(net, quantized_dtype="auto", calib_data=[x])
    (l_auto,) = q_auto.quantized_layers
    assert l_auto._act_unsigned


def test_uint8_conv_border_correction():
    """The zero-point correction map is border-aware under zero padding:
    a padded uint8 conv must still match fp32 at the edges."""
    mx.random.seed(10)
    conv = nn.Conv2D(4, kernel_size=3, padding=1, in_channels=2)
    conv.initialize()
    x = nd.random.uniform(0, 1, shape=(2, 2, 6, 6))
    net = nn.HybridSequential()
    net.add(conv)
    y_fp = conv(x).asnumpy()
    qnet = q.quantize_net(net, quantized_dtype="uint8", calib_data=[x])
    y_q = qnet(x).asnumpy()
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.05, err


def test_quantize_net_inside_hybridize_trace():
    """A hybridized parent jit-traces THROUGH the routers: int8 math in
    the compiled executable, and mode-private caches keep fp32/int8
    executables separate."""
    mx.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4),
            nn.Dense(2, in_units=8))
    net.initialize()
    x = nd.random.uniform(-1, 1, shape=(4, 4))
    y_fp_pre = net(x).asnumpy()
    net.hybridize()
    net(x)                       # build the fp32 compiled cache
    qnet = q.quantize_net(net)
    y_q = qnet(x).asnumpy()
    y_fp_post = net(x).asnumpy()       # original net: still fp32 math
    np.testing.assert_allclose(y_fp_post, y_fp_pre, rtol=1e-5, atol=1e-6)
    assert np.abs(y_q - y_fp_pre).max() > 0  # actually quantized
    err = np.abs(y_q - y_fp_pre).max() / (np.abs(y_fp_pre).max() + 1e-6)
    assert err < 0.1, err


def test_quantized_conv_dilation_and_groups():
    mx.random.seed(5)
    conv = nn.Conv2D(8, kernel_size=3, padding=2, dilation=2, groups=2,
                     in_channels=4)
    conv.initialize()
    x = nd.random.uniform(-1, 1, shape=(2, 4, 8, 8))
    y_fp = conv(x).asnumpy()
    qc = q.QuantizedConv2D(conv)
    y_q = qc(x).asnumpy()
    assert y_q.shape == y_fp.shape
    err = np.abs(y_fp - y_q).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.05, err


def test_quantized_dense_sigmoid_activation():
    dense = nn.Dense(4, activation="sigmoid", in_units=4)
    dense.initialize()
    x = nd.random.uniform(-1, 1, shape=(2, 4))
    y_fp = dense(x).asnumpy()
    y_q = q.QuantizedDense(dense)(x).asnumpy()
    np.testing.assert_allclose(y_fp, y_q, atol=0.02)


def test_calibration_on_hybridized_net():
    """Calibration must not run inside a jit trace (observe() reads
    concrete values): a pre-hybridized, pre-compiled net calibrates fine
    and then runs int8 through the compiled path."""
    mx.random.seed(12)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4))
    net.initialize()
    x = nd.random.uniform(-1, 1, shape=(128, 4))
    net.hybridize()
    net(x)                        # compiled fp32 cache exists
    qnet = q.quantize_net(net, calib_data=[x], calib_mode="entropy")
    (layer,) = qnet.quantized_layers
    assert layer._act_scale is not None
    y_q = qnet(x).asnumpy()
    y_fp = net(x).asnumpy()
    err = np.abs(y_q - y_fp).max() / (np.abs(y_fp).max() + 1e-6)
    assert err < 0.1, err
    # hybridization flags restored after calibration
    assert net._active


def test_uint8_conv_no_tracer_leak_across_jit_boundary():
    """The +128 correction map computed inside a jit trace must not be
    cached and served to a later EAGER call of the same shape."""
    mx.random.seed(13)
    conv = nn.Conv2D(4, kernel_size=3, padding=1, in_channels=2)
    conv.initialize()
    net = nn.HybridSequential()
    net.add(conv)
    x = nd.random.uniform(0, 1, shape=(1, 2, 5, 5))
    qnet = q.quantize_net(net, quantized_dtype="uint8", calib_data=[x])
    net.hybridize()
    y_jit = qnet(x).asnumpy()       # populates nothing tracer-shaped...
    net.hybridize(False)
    y_eager = qnet(x).asnumpy()     # ...or this raises UnexpectedTracer
    np.testing.assert_allclose(y_jit, y_eager, rtol=1e-5, atol=1e-6)


def test_uint8_requires_calibrating_mode():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=4))
    net.initialize()
    x = nd.random.uniform(0, 1, shape=(2, 4))
    with pytest.raises(Exception, match="calib_mode"):
        q.quantize_net(net, quantized_dtype="uint8", calib_data=[x],
                       calib_mode=None)


def test_quantize_net_multi_input_bert():
    """Multi-input nets quantize too (reference upstream only feeds
    batch[0]; calib_inputs=k feeds the first k tuple elements): BERT-mini
    int8 inference stays within 1% of fp32 on the pooled output, with
    every Dense in the encoder (qkv/proj/ffn/pooler) rewired."""
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.models.bert import BERTModel
    net = BERTModel(vocab_size=60, units=32, hidden_size=64, num_layers=2,
                    num_heads=4, max_length=16, dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(0)
    tok = nd.array(rng.randint(0, 60, (2, 12)).astype(np.float32))
    seg = nd.array(np.zeros((2, 12), np.float32))
    _, ref_pool = net(tok, seg)
    q = quantize_net(net, quantized_dtype="int8",
                     calib_data=[(tok, seg)], calib_mode="naive",
                     calib_inputs=2)
    assert len(q.quantized_layers) >= 2 * 4 + 2  # per-layer qkv/proj/ffn1/2
    _, qp = q(tok, seg)
    rel = float(np.abs(qp.asnumpy() - ref_pool.asnumpy()).max()) / \
        float(np.abs(ref_pool.asnumpy()).max())
    assert rel < 0.01, rel
    # fp32 behaviour of the source net is untouched
    _, again = net(tok, seg)
    np.testing.assert_allclose(again.asnumpy(), ref_pool.asnumpy())


# ---- op-level quantization surface (upstream:
# src/operator/quantization/*.cc) ---------------------------------------
def test_nd_contrib_quantize_int8_closed_form():
    rs = np.random.RandomState(0)
    x = rs.randn(5, 7).astype(np.float32) * 3
    q, mn, mx = nd.contrib.quantize(nd.array(x), nd.array([-4.0]),
                                    nd.array([4.0]), out_type="int8")
    assert q.dtype == np.int8
    want = np.clip(np.round(x * 127.0 / 4.0), -127, 127)
    np.testing.assert_allclose(q.asnumpy(), want)
    assert float(mn.asnumpy()) == -4.0 and float(mx.asnumpy()) == 4.0


def test_nd_contrib_quantize_uint8_affine():
    rs = np.random.RandomState(1)
    x = rs.rand(4, 6).astype(np.float32)  # [0, 1)
    q, mn, mx = nd.contrib.quantize(nd.array(x), nd.array([0.0]),
                                    nd.array([1.0]), out_type="uint8")
    assert q.dtype == np.uint8
    np.testing.assert_allclose(q.asnumpy(),
                               np.clip(np.round(x * 255.0), 0, 255))
    back = nd.contrib.dequantize(q, mn, mx).asnumpy()
    np.testing.assert_allclose(back, x, atol=1.0 / 255.0)


def test_quantize_v2_dynamic_and_calibrated():
    rs = np.random.RandomState(2)
    x = rs.randn(8, 8).astype(np.float32)
    # dynamic: range from data
    q, mn, mx = nd.contrib.quantize_v2(nd.array(x), out_type="int8")
    amax = np.abs(x).max()
    np.testing.assert_allclose(float(mx.asnumpy()), amax, rtol=1e-6)
    np.testing.assert_allclose(
        q.asnumpy(), np.clip(np.round(x * 127.0 / amax), -127, 127))
    # calibrated: attr range wins
    q2, mn2, mx2 = nd.contrib.quantize_v2(
        nd.array(x), out_type="int8", min_calib_range=-2.0,
        max_calib_range=2.0)
    np.testing.assert_allclose(
        q2.asnumpy(), np.clip(np.round(x * 127.0 / 2.0), -127, 127))


def test_quantize_v2_dequantize_matches_quantize_net_math():
    """The op pair reproduces the graph-level quantize_net layer math
    (contrib/quantization.py _scale_of: symmetric absmax/127)."""
    from mxnet_tpu.contrib import quantization as qz
    rs = np.random.RandomState(3)
    x = rs.randn(6, 6).astype(np.float32)
    q, mn, mx = nd.contrib.quantize_v2(nd.array(x), out_type="int8")
    ops_back = nd.contrib.dequantize(q, mn, mx).asnumpy()
    gq, gmn, gmx = qz.quantize(nd.array(x))
    graph_back = qz.dequantize(gq, gmn, gmx).asnumpy()
    np.testing.assert_allclose(ops_back, graph_back, atol=1e-6)


def test_requantize_int32_to_int8():
    """int32 accumulator -> int8: matches dequantize-then-requantize
    closed form, calibrated and dynamic."""
    rs = np.random.RandomState(4)
    f = np.clip(rs.randn(5, 5) * 30, -79, 79).astype(np.float32)
    amax32 = 80.0
    q32 = np.round(f.astype(np.float64) * (2**31 - 1) / amax32) \
        .astype(np.int64).astype(np.int32)
    q8, mn, mx = nd.contrib.requantize(
        nd.array(q32), nd.array([-amax32]), nd.array([amax32]))
    fb = q32.astype(np.float64) * amax32 / (2**31 - 1)
    want = np.clip(np.round(fb * 127.0 / np.abs(fb).max()), -127, 127)
    np.testing.assert_allclose(q8.asnumpy(), want)
    q8c, mnc, mxc = nd.contrib.requantize(
        nd.array(q32), nd.array([-amax32]), nd.array([amax32]),
        min_calib_range=-60.0, max_calib_range=60.0)
    wantc = np.clip(np.round(fb * 127.0 / 60.0), -127, 127)
    np.testing.assert_allclose(q8c.asnumpy(), wantc)
    assert float(mxc.asnumpy()) == 60.0


def test_sym_contrib_quantize_json_roundtrip():
    """The full sym chain quantize_v2 -> dequantize survives JSON and
    matches the nd path."""
    rs = np.random.RandomState(5)
    x = rs.randn(4, 4).astype(np.float32)
    d = sym.Variable("data")
    qsym = sym.contrib.quantize_v2(d, out_type="int8",
                                   min_calib_range=-3.0,
                                   max_calib_range=3.0)
    deq = sym.contrib.dequantize(qsym[0], qsym[1], qsym[2])
    loaded = mx.sym.load_json(deq.tojson())
    out = loaded.bind(mx.cpu(), {"data": nd.array(x)}).forward()[0]
    q, mn, mx_ = nd.contrib.quantize_v2(nd.array(x), out_type="int8",
                                        min_calib_range=-3.0,
                                        max_calib_range=3.0)
    want = nd.contrib.dequantize(q, mn, mx_).asnumpy()
    np.testing.assert_allclose(out.asnumpy(), want, atol=1e-6)
    # quantize with tensor ranges round-trips too
    qs = sym.contrib.quantize(sym.Variable("data"), sym.Variable("mn"),
                              sym.Variable("mx"), out_type="uint8")
    loaded2 = mx.sym.load_json(qs.tojson())
    outs = loaded2.bind(mx.cpu(), {"data": nd.array(np.abs(x)),
                                   "mn": nd.array([0.0]),
                                   "mx": nd.array([4.0])}).forward()
    ref_q, _, _ = nd.contrib.quantize(nd.array(np.abs(x)),
                                      nd.array([0.0]), nd.array([4.0]),
                                      out_type="uint8")
    np.testing.assert_allclose(outs[0].asnumpy(), ref_q.asnumpy())


def test_quantized_fully_connected_end_to_end():
    """quantize_v2 -> quantized_fully_connected -> dequantize ~= float FC
    within quantization error (upstream quantized_fully_connected.cc)."""
    rs = np.random.RandomState(6)
    x = rs.randn(8, 32).astype(np.float32)
    w = (rs.randn(16, 32) * 0.2).astype(np.float32)
    b = rs.randn(16).astype(np.float32)
    xq, xmn, xmx = nd.contrib.quantize_v2(nd.array(x), out_type="int8")
    wq, wmn, wmx = nd.contrib.quantize_v2(nd.array(w), out_type="int8")
    acc, omn, omx = nd.contrib.quantized_fully_connected(
        xq, wq, nd.array(b), xmn, xmx, wmn, wmx, num_hidden=16)
    assert acc.asnumpy().dtype == np.int32
    out = nd.contrib.dequantize(acc, omn, omx).asnumpy()
    ref = x @ w.T + b
    # error bound: K * (sx*|w| + sw*|x|) rounding terms; loose 2% rel
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.02
    # int8 deploy chain continues: requantize to int8 with the observed
    # float range, dequantize, same answer within int8 resolution
    amax = float(np.abs(ref).max()) * 1.05
    q8, qmn, qmx = nd.contrib.requantize(acc, omn, omx,
                                         min_calib_range=-amax,
                                         max_calib_range=amax)
    out8 = nd.contrib.dequantize(q8, qmn, qmx).asnumpy()
    assert np.abs(out8 - ref).max() <= amax / 127 * 0.51 + 0.02 * np.abs(ref).max()


def test_quantized_conv_matches_float():
    rs = np.random.RandomState(7)
    x = rs.randn(2, 3, 10, 10).astype(np.float32)
    w = (rs.randn(8, 3, 3, 3) * 0.2).astype(np.float32)
    xq, xmn, xmx = nd.contrib.quantize_v2(nd.array(x), out_type="int8")
    wq, wmn, wmx = nd.contrib.quantize_v2(nd.array(w), out_type="int8")
    acc, omn, omx = nd.contrib.quantized_conv(
        xq, wq, None, xmn, xmx, wmn, wmx, kernel=(3, 3), pad=(1, 1),
        no_bias=True)
    out = nd.contrib.dequantize(acc, omn, omx).asnumpy()
    import jax.numpy as jnp
    from jax import lax
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    ref = np.asarray(lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=dn))
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.03


def test_quantized_pooling_and_flatten():
    rs = np.random.RandomState(8)
    x = rs.randn(2, 4, 8, 8).astype(np.float32)
    xq, lo, hi = nd.contrib.quantize_v2(nd.array(x), out_type="int8")
    # max-pool commutes with the monotone quantize map exactly
    pq, pmn, pmx = nd.contrib.quantized_pooling(xq, lo, hi,
                                                kernel=(2, 2),
                                                pool_type="max")
    dq = nd.contrib.dequantize(pq, pmn, pmx).asnumpy()
    ref = x.reshape(2, 4, 4, 2, 4, 2).max((3, 5))
    amax = np.abs(x).max()
    assert np.abs(dq - ref).max() <= amax / 127 * 0.51 + 1e-6
    fq, fmn, fmx = nd.contrib.quantized_flatten(pq, pmn, pmx)
    assert fq.shape == (2, 4 * 4 * 4)
    # sym chain survives JSON
    s = sym.contrib.quantized_pooling(sym.Variable("q"),
                                      sym.Variable("a"),
                                      sym.Variable("b"), kernel=(2, 2),
                                      pool_type="avg")
    g = mx.sym.load_json(s.tojson())
    outs = g.bind(mx.cpu(), {"q": xq, "a": lo, "b": hi}).forward()
    assert outs[0].asnumpy().dtype == np.int8


def test_quantized_pooling_uint8_and_int_attrs():
    """uint8 pooling (identity 0, clip 0..255) and int stride/pad attrs
    through sym (review findings r5)."""
    rs = np.random.RandomState(9)
    x = rs.rand(1, 2, 8, 8).astype(np.float32)
    xq, lo, hi = nd.contrib.quantize(nd.array(x), nd.array([0.0]),
                                     nd.array([1.0]), out_type="uint8")
    pq, pa, pb = nd.contrib.quantized_pooling(xq, lo, hi, kernel=2,
                                              pool_type="max", stride=2)
    assert pq.asnumpy().dtype == np.uint8
    ref = x.reshape(1, 2, 4, 2, 4, 2).max((3, 5))
    back = nd.contrib.dequantize(pq, pa, pb).asnumpy()
    assert np.abs(back - ref).max() <= 1.0 / 255 + 1e-6
    # avg keeps the full uint8 range (no int8 clip)
    aq, _, _ = nd.contrib.quantized_pooling(xq, lo, hi, kernel=2,
                                            pool_type="avg", stride=2)
    assert aq.asnumpy().max() > 127  # would be impossible under int8 clip
    # sym accepts plain ints for kernel/stride/pad
    s = sym.contrib.quantized_pooling(sym.Variable("q"), sym.Variable("a"),
                                      sym.Variable("b"), kernel=2,
                                      pool_type="max", stride=2)
    outs = mx.sym.load_json(s.tojson()).bind(
        mx.cpu(), {"q": xq, "a": lo, "b": hi}).forward()
    np.testing.assert_allclose(outs[0].asnumpy(), pq.asnumpy())
    s2 = sym.contrib.quantized_conv(
        sym.Variable("d"), sym.Variable("w"), None, sym.Variable("a1"),
        sym.Variable("b1"), sym.Variable("a2"), sym.Variable("b2"),
        stride=1, pad=1, no_bias=True)
    assert "_contrib_quantized_conv" in s2.tojson()


def test_quantize_channelwise_per_channel_scales():
    """ISSUE 14: per-channel symmetric int8 — one independent scale per
    index of `axis`, reconstruction error bounded by half a quantisation
    step per channel, zero channels exact."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    w = rng.randn(5, 16).astype(np.float32)
    w[2] *= 100.0          # a hot channel must not coarsen the others
    w[4] = 0.0             # all-zero channel
    wq, scale = q.quantize_channelwise(jnp.asarray(w), axis=0)
    assert wq.dtype == jnp.int8 and scale.shape == (5,)
    rec = np.asarray(wq, np.float32) * np.asarray(scale)[:, None]
    amax = np.abs(w).max(axis=1)
    for c in range(5):
        step = max(amax[c], 1e-12) / 127.0
        assert np.max(np.abs(rec[c] - w[c])) <= step / 2 + 1e-7
    assert np.all(rec[4] == 0.0)
    # per-channel independence: the hot row's scale is ~100x the rest
    s = np.asarray(scale)
    assert s[2] > 20 * s[0]
    # axis=1 variant quantises per input channel
    wq1, scale1 = q.quantize_channelwise(jnp.asarray(w), axis=1)
    assert scale1.shape == (16,)
    rec1 = np.asarray(wq1, np.float32) * np.asarray(scale1)[None, :]
    step1 = np.abs(w).max(axis=0) / 127.0
    assert np.all(np.abs(rec1 - w) <= step1[None, :] / 2 + 1e-7)
