"""Classic op-name surface (SURVEY.md §2 rows 3/7/24 adjuncts; reference:
elemwise_binary_op_basic.cc, regression_output-inl.h, optimizer_op.cc,
nn/im2col.cc). Numerics vs numpy/torch closed forms."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


def test_aliases_and_small_math():
    a = nd.array([[1.0, -2.0], [3.0, 4.0]])
    onp.testing.assert_allclose(nd.elemwise_add(a, a).asnumpy(),
                                2 * a.asnumpy())
    onp.testing.assert_allclose(nd.elemwise_div(a, a).asnumpy(),
                                onp.ones((2, 2)))
    onp.testing.assert_allclose(nd.identity(a).asnumpy(), a.asnumpy())
    onp.testing.assert_allclose(
        nd.softsign(a).asnumpy(),
        a.asnumpy() / (1 + onp.abs(a.asnumpy())), rtol=1e-6)
    onp.testing.assert_allclose(nd.degrees(nd.array([onp.pi])).asnumpy(),
                                [180.0], rtol=1e-5)
    assert nd.isnan(nd.array([onp.nan, 1.0])).asnumpy().tolist() == [1, 0]
    onp.testing.assert_allclose(nd.trace(a).asnumpy(), 5.0)
    onp.testing.assert_allclose(nd.tril(a).asnumpy(), onp.tril(a.asnumpy()))
    onp.testing.assert_allclose(
        nd.logical_and(nd.array([1, 0]), nd.array([1, 1])).asnumpy(),
        [1, 0])
    onp.testing.assert_allclose(
        nd.SwapAxis(nd.ones((2, 3)), 0, 1).shape, (3, 2))
    onp.testing.assert_allclose(
        nd.broadcast_axes(nd.ones((1, 3)), axis=0, size=4).shape, (4, 3))
    # crop is the deprecated alias of slice, not the Crop op
    onp.testing.assert_allclose(
        nd.crop(a, begin=(0, 1), end=(2, 2)).asnumpy(),
        a.asnumpy()[0:2, 1:2])
    x = nd.array([2.0, -1.5, 0.2])
    onp.testing.assert_allclose(nd.argmax_channel(
        nd.array([[1, 3, 2], [9, 0, 1]])).asnumpy(), [1, 0])
    counts, edges = nd.histogram(x, bins=3, range=(-2, 2))
    assert int(counts.asnumpy().sum()) == 3 and edges.shape == (4,)
    bc = nd.bincount(nd.array([0, 1, 1, 3], dtype="int32"))
    assert bc.asnumpy().tolist() == [1, 2, 0, 1]


def test_softmax_activation():
    x = onp.random.RandomState(0).randn(2, 4).astype(onp.float32)
    out = nd.SoftmaxActivation(nd.array(x))
    onp.testing.assert_allclose(out.asnumpy().sum(-1), onp.ones(2),
                                rtol=1e-5)
    xc = onp.random.RandomState(1).randn(2, 3, 4).astype(onp.float32)
    outc = nd.SoftmaxActivation(nd.array(xc), mode="channel")
    onp.testing.assert_allclose(outc.asnumpy().sum(1), onp.ones((2, 4)),
                                rtol=1e-5)


def test_regression_heads_forward_and_grad():
    x = nd.array([[1.0, 2.0], [3.0, 4.0]])
    y = nd.array([[0.0, 0.0], [0.0, 0.0]])
    x.attach_grad()
    with autograd.record():
        out = nd.LinearRegressionOutput(x, y)
    out.backward()
    # grad = (pred - label) / num_output, reference scaling
    onp.testing.assert_allclose(x.grad.asnumpy(), x.asnumpy() / 2,
                                rtol=1e-6)
    onp.testing.assert_allclose(out.asnumpy(), x.asnumpy())

    x2 = nd.array([[0.0], [2.0]])
    x2.attach_grad()
    with autograd.record():
        o2 = nd.LogisticRegressionOutput(x2, nd.array([[1.0], [0.0]]))
    o2.backward()
    sig = 1 / (1 + onp.exp(-x2.asnumpy()))
    onp.testing.assert_allclose(o2.asnumpy(), sig, rtol=1e-5)
    onp.testing.assert_allclose(x2.grad.asnumpy(),
                                sig - [[1.0], [0.0]], rtol=1e-5)

    x3 = nd.array([[1.0, -1.0]])
    x3.attach_grad()
    with autograd.record():
        o3 = nd.MAERegressionOutput(x3, nd.array([[0.0, 0.0]]))
    o3.backward()
    onp.testing.assert_allclose(x3.grad.asnumpy(), [[0.5, -0.5]])


def test_svm_output_grad_zero_when_margin_satisfied():
    # true class already beyond margin for every class pair -> zero grad
    x = nd.array([[5.0, -5.0]])
    x.attach_grad()
    with autograd.record():
        out = nd.SVMOutput(x, nd.array([0.0]), margin=1.0)
    out.backward()
    onp.testing.assert_allclose(out.asnumpy(), x.asnumpy())
    onp.testing.assert_allclose(x.grad.asnumpy(), [[0.0, 0.0]])
    # violated margin -> pushes true class up, off class down
    x2 = nd.array([[0.0, 0.0]])
    x2.attach_grad()
    with autograd.record():
        o2 = nd.SVMOutput(x2, nd.array([0.0]), margin=1.0, use_linear=True)
    o2.backward()
    g = x2.grad.asnumpy()
    assert g[0, 0] < 0 < g[0, 1]


def test_im2col_col2im_roundtrip():
    torch = pytest.importorskip("torch")
    x = onp.random.RandomState(2).randn(2, 3, 8, 8).astype(onp.float32)
    cols = nd.im2col(nd.array(x), kernel=(3, 3), stride=(2, 2), pad=(1, 1))
    ref = torch.nn.functional.unfold(torch.from_numpy(x), (3, 3),
                                     padding=1, stride=2).numpy()
    onp.testing.assert_allclose(cols.asnumpy(), ref, rtol=1e-5, atol=1e-5)
    back = nd.col2im(cols, output_size=(8, 8), kernel=(3, 3),
                     stride=(2, 2), pad=(1, 1))
    fold = torch.nn.functional.fold(torch.from_numpy(ref), (8, 8), (3, 3),
                                    padding=1, stride=2).numpy()
    onp.testing.assert_allclose(back.asnumpy(), fold, rtol=1e-5, atol=1e-5)


def test_nd_rnn_matches_gluon_layer():
    from mxnet_tpu.gluon import rnn as grnn
    layer = grnn.LSTM(5, num_layers=1)
    layer.initialize()
    x = nd.random.uniform(shape=(7, 2, 4))   # TNC
    out = layer(x)
    params = layer.collect_params()
    pnames, pvals = [], []
    for name, p in params.items():
        # the layer's own prefix: "lstm<n>_", n counting the LSTMs this
        # process has built before
        pnames.append(name[len(layer.prefix):]
                      if name.startswith(layer.prefix) else name)
        pvals.append(p.data())
    # imperative fused op with the same weights
    res = nd.RNN(x, *pvals, mode="lstm", num_layers=1, num_dir=1,
                 hidden_size=5, pnames=tuple(pnames))
    onp.testing.assert_allclose(res.asnumpy(), out.asnumpy(), rtol=1e-5,
                                atol=1e-5)


# ----------------------------------------------------- optimizer update ops
def test_sgd_update_matches_formula():
    w = nd.array([1.0, 2.0])
    g = nd.array([0.5, -0.5])
    out = nd.sgd_update(w, g, lr=0.1, wd=0.0)
    onp.testing.assert_allclose(out.asnumpy(), [0.95, 2.05], rtol=1e-6)
    assert out is w                       # in-place contract


def test_sgd_mom_update_state_carries():
    w, g = nd.array([1.0]), nd.array([1.0])
    m = nd.zeros((1,))
    nd.sgd_mom_update(w, g, m, lr=0.1, momentum=0.9)
    onp.testing.assert_allclose(m.asnumpy(), [-0.1], rtol=1e-6)
    onp.testing.assert_allclose(w.asnumpy(), [0.9], rtol=1e-6)
    nd.sgd_mom_update(w, g, m, lr=0.1, momentum=0.9)
    onp.testing.assert_allclose(m.asnumpy(), [-0.19], rtol=1e-5)


def test_adam_update_matches_torch():
    torch = pytest.importorskip("torch")
    w0 = onp.array([1.0, -2.0, 3.0], onp.float32)
    g0 = onp.array([0.1, 0.2, -0.3], onp.float32)
    w, g = nd.array(w0), nd.array(g0)
    mean, var = nd.zeros((3,)), nd.zeros((3,))
    tw = torch.tensor(w0, requires_grad=True)
    opt = torch.optim.Adam([tw], lr=0.01, betas=(0.9, 0.999), eps=1e-8)
    for step in range(3):
        nd.adam_update(w, g, mean, var, lr=0.01)
        tw.grad = torch.tensor(g0)
        opt.step()
    # mx adam_update applies NO bias correction (reference semantics);
    # torch does — compare against the uncorrected closed form instead
    m = onp.zeros(3)
    v = onp.zeros(3)
    wref = w0.copy()
    for _ in range(3):
        m = 0.9 * m + 0.1 * g0
        v = 0.999 * v + 0.001 * g0 * g0
        wref -= 0.01 * m / (onp.sqrt(v) + 1e-8)
    onp.testing.assert_allclose(w.asnumpy(), wref, rtol=1e-5)


def test_signsgd_rmsprop_ftrl_nag_smoke():
    w, g = nd.array([1.0, -1.0]), nd.array([0.3, -0.3])
    nd.signsgd_update(w, g, lr=0.1)
    onp.testing.assert_allclose(w.asnumpy(), [0.9, -0.9], rtol=1e-6)

    w2, n2 = nd.array([1.0]), nd.zeros((1,))
    nd.rmsprop_update(w2, nd.array([1.0]), n2, lr=0.1, gamma1=0.9)
    assert float(n2.asnumpy()[0]) == pytest.approx(0.1, rel=1e-5)

    w3, z3, n3 = nd.array([1.0]), nd.zeros((1,)), nd.zeros((1,))
    nd.ftrl_update(w3, nd.array([1.0]), z3, n3, lr=0.1, lamda1=0.01)
    assert float(n3.asnumpy()[0]) == pytest.approx(1.0)
    assert float(w3.asnumpy()[0]) != 1.0

    w4, m4 = nd.array([1.0]), nd.zeros((1,))
    nd.nag_mom_update(w4, nd.array([1.0]), m4, lr=0.1, momentum=0.9)
    onp.testing.assert_allclose(m4.asnumpy(), [1.0], rtol=1e-6)


def test_mp_sgd_update_keeps_master_precision():
    w16 = nd.array([1.0, 2.0]).astype("bfloat16")
    w32 = nd.array([1.0, 2.0])
    g16 = nd.array([1e-3, 1e-3]).astype("bfloat16")
    for _ in range(10):
        nd.mp_sgd_update(w16, g16, w32, lr=0.1)
    # fp32 master accumulated 10 tiny steps bf16 alone would lose
    onp.testing.assert_allclose(w32.asnumpy(), [0.999, 1.999], rtol=1e-4)
    assert w16.dtype == onp.dtype("bfloat16") or str(w16.dtype) == "bfloat16"


def test_multi_sum_sq_and_lamb():
    arrs = [nd.array([3.0, 4.0]), nd.array([1.0])]
    ss = nd.multi_sum_sq(*arrs)
    onp.testing.assert_allclose(ss.asnumpy(), [25.0, 1.0])

    w = nd.array([0.5, 0.5])
    g = nd.array([0.1, -0.1])
    mean, var = nd.zeros((2,)), nd.zeros((2,))
    gp = nd.lamb_update_phase1(w, g, mean, var, t=1, wd=0.01)
    assert gp.shape == (2,)
    r1 = nd.norm(w)
    r2 = nd.norm(gp)
    new_w = nd.lamb_update_phase2(w, gp, r1, r2, lr=0.01)
    assert new_w is w and not onp.allclose(w.asnumpy(), [0.5, 0.5])


def test_random_op_aliases():
    assert nd.random_uniform(shape=(3,)).shape == (3,)
    assert nd.sample_poisson(lam=2.0, shape=(4,)).shape == (4,)
    assert nd.random_gamma(shape=(2,)).shape == (2,)


def test_sym_slice_and_fromjson():
    from mxnet_tpu import sym
    data = sym.Variable("data")
    s = sym.slice(data, begin=(0, 1), end=(2, 3))
    e = s.bind(mx.cpu(), {"data": nd.array(onp.arange(12.).reshape(3, 4))})
    out = e.forward()[0]
    onp.testing.assert_allclose(out.asnumpy(),
                                onp.arange(12.).reshape(3, 4)[0:2, 1:3])
    sa = sym.slice_axis(data, axis=1, begin=1, end=3)
    e2 = sa.bind(mx.cpu(), {"data": nd.array(onp.arange(12.).reshape(3, 4))})
    onp.testing.assert_allclose(e2.forward()[0].asnumpy(),
                                onp.arange(12.).reshape(3, 4)[:, 1:3])
    # JSON round-trip through the registered kernels
    s2 = mx.sym.fromjson(s.tojson())
    e3 = s2.bind(mx.cpu(), {"data": nd.array(onp.arange(12.).reshape(3, 4))})
    onp.testing.assert_allclose(e3.forward()[0].asnumpy(),
                                out.asnumpy())
