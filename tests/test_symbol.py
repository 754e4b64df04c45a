"""Symbol API tests (SURVEY.md §2 #12)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


def _mlp():
    data = sym.Variable("data")
    w1 = sym.Variable("w1")
    b1 = sym.Variable("b1")
    h = sym.Activation(sym.FullyConnected(data, w1, b1, num_hidden=8),
                       act_type="relu")
    w2 = sym.Variable("w2")
    b2 = sym.Variable("b2")
    return sym.FullyConnected(h, w2, b2, num_hidden=3)


def test_variable_and_arguments():
    out = _mlp()
    args = out.list_arguments()
    assert args == ["data", "w1", "b1", "w2", "b2"]
    assert len(out.list_outputs()) == 1


def test_infer_shape():
    out = _mlp()
    arg_shapes, out_shapes, _ = out.infer_shape(
        data=(2, 4), w1=(8, 4), b1=(8,), w2=(3, 8), b2=(3,))
    assert out_shapes == [(2, 3)]


def test_executor_forward_backward():
    out = _mlp()
    rng = np.random.RandomState(0)
    args = {"data": nd.array(rng.rand(2, 4)),
            "w1": nd.array(rng.rand(8, 4)), "b1": nd.zeros((8,)),
            "w2": nd.array(rng.rand(3, 8)), "b2": nd.zeros((3,))}
    grads = {k: nd.zeros(v.shape) for k, v in args.items()}
    ex = out.bind(None, args, grads)
    y = ex.forward(is_train=True)
    y0 = y[0] if isinstance(y, (list, tuple)) else y
    assert y0.shape == (2, 3)
    ex.backward(nd.ones((2, 3)))
    assert np.abs(grads["w1"].asnumpy()).sum() > 0
    assert np.abs(grads["data"].asnumpy()).sum() > 0


def test_simple_bind():
    out = _mlp()
    ex = out.simple_bind(data=(2, 4), w1=(8, 4), b1=(8,), w2=(3, 8), b2=(3,))
    y = ex.forward()
    y0 = y[0] if isinstance(y, (list, tuple)) else y
    assert y0.shape == (2, 3)


def test_symbol_arithmetic_and_eval():
    a = sym.Variable("a")
    b = sym.Variable("b")
    c = (a + b * 2.0) / 2.0
    out = c.eval_with({"a": nd.array([2.0]), "b": nd.array([4.0])})
    np.testing.assert_allclose(out.asnumpy(), [5.0])


def test_tojson_load_roundtrip():
    out = _mlp()
    js = out.tojson()
    loaded = mx.sym.load_json(js)
    assert loaded.list_arguments() == out.list_arguments()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "net-symbol.json")
        out.save(path)
        again = mx.sym.load(path)
        assert again.list_arguments() == out.list_arguments()


def test_get_internals_and_group():
    out = _mlp()
    internals = out.get_internals()
    names = internals.list_outputs()
    assert any("fullyconnected" in n.lower() or "FullyConnected" in n
               for n in names) or len(names) > 3


def test_symbolblock_from_symbol():
    from mxnet_tpu.gluon import SymbolBlock, nn
    data = sym.Variable("data")
    w = sym.Variable("w")
    out = sym.FullyConnected(data, w, None, num_hidden=4, no_bias=True)
    from mxnet_tpu.gluon.parameter import Parameter
    p = Parameter("w", shape=(4, 3))
    p.initialize()
    blk = SymbolBlock(out, [data], params={"w": p})
    y = blk(nd.ones((2, 3)))
    assert y.shape == (2, 4)


def test_hybridblock_symbolic_trace():
    """Calling a HybridBlock on a Symbol yields a Symbol graph."""
    from mxnet_tpu.gluon import nn
    net = nn.Dense(5, in_units=3)
    net.initialize()
    data = sym.Variable("data")
    out = net(data)
    assert hasattr(out, "list_arguments")
    assert "data" in out.list_arguments()


def test_group_infer_shape():
    """Group-headed symbols infer member shapes (module.py binds Groups)."""
    data = sym.Variable("data")
    w1 = sym.Variable("w1")
    b1 = sym.Variable("b1")
    h = sym.FullyConnected(data, w1, b1, num_hidden=8)
    out2 = sym.Activation(h, act_type="relu")
    g = sym.Group([h, out2])
    arg_shapes, out_shapes, _ = g.infer_shape(data=(2, 4))
    assert out_shapes == [(2, 8), (2, 8)]
    assert (8, 4) in arg_shapes and (8,) in arg_shapes
    nested = sym.Group([sym.Group([h]), out2])
    _, out_shapes, _ = nested.infer_shape(data=(2, 4))
    assert out_shapes == [(2, 8), (2, 8)]


def test_indexed_group_output():
    """g[i] (indexed Group output) infers shapes and evaluates."""
    data = sym.Variable("data")
    w1 = sym.Variable("w1")
    b1 = sym.Variable("b1")
    h = sym.FullyConnected(data, w1, b1, num_hidden=8)
    r = sym.Activation(h, act_type="relu")
    g = sym.Group([h, r])
    one = g[1]
    _, out_shapes, _ = one.infer_shape(data=(2, 4))
    assert out_shapes == [(2, 8)]
    vals = {"data": np.zeros((2, 4), np.float32) - 1.0,
            "w1": np.ones((8, 4), np.float32),
            "b1": np.zeros((8,), np.float32)}
    out = one._eval_with_values({k: mx.nd.array(v)._data
                                 for k, v in vals.items()})
    assert np.allclose(np.asarray(out), 0.0)  # relu(-4) == 0


def test_s2d_stem_symbolic_trace():
    """S2DStemConv traces symbolically (F=sym) like the Conv2D it replaces."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import S2DStemConv
    blk = S2DStemConv(16)
    blk.initialize()
    x = nd.random.uniform(shape=(1, 8, 8, 3))
    blk(x)  # materialise deferred weight
    out = blk(sym.Variable("data"))
    assert "data" in out.list_arguments()
    _, out_shapes, _ = out.infer_shape(data=(2, 8, 8, 3))
    assert out_shapes == [(2, 4, 4, 16)]


def test_batchnorm_aux_states():
    """BN moving stats are auxiliary states, not trainable arguments
    (reference: nnvm mutable inputs excluded from gradients)."""
    data = sym.Variable("data")
    net = sym.BatchNorm(sym.FullyConnected(data, num_hidden=4,
                                           name="fc"), name="bn")
    args = net.list_arguments()
    aux = net.list_auxiliary_states()
    assert "bn_moving_mean" in aux and "bn_moving_var" in aux
    assert not any("moving" in a for a in args)
    arg_shapes, _, aux_shapes = net.infer_shape(data=(2, 3))
    assert len(arg_shapes) == len(args)
    assert aux_shapes == [(4,), (4,)]


def test_batchnorm_train_updates_moving_stats():
    """Executor.forward(is_train=True) uses batch stats and writes the
    moving-average update back to aux_dict; inference uses moving stats."""
    rs = np.random.RandomState(0)
    x_np = (rs.randn(64, 4).astype(np.float32) * 3.0 + 7.0)
    data = sym.Variable("data")
    net = sym.BatchNorm(data, name="bn", momentum=0.5)
    ex = net.simple_bind(grad_req="null", data=(64, 4),
                         bn_gamma=(4,), bn_beta=(4,))
    ex.arg_dict["bn_gamma"]._assign_value(mx.nd.ones((4,))._data)
    mm0 = ex.aux_dict["bn_moving_mean"].asnumpy().copy()
    out_t = ex.forward(is_train=True, data=mx.nd.array(x_np))[0]
    # training output is batch-normalised: ~zero mean, unit var
    o = out_t.asnumpy()
    assert abs(o.mean()) < 1e-2 and abs(o.var() - 1.0) < 0.1
    mm1 = ex.aux_dict["bn_moving_mean"].asnumpy()
    assert not np.allclose(mm0, mm1)  # moving stats moved
    expect = 0.5 * mm0 + 0.5 * x_np.mean(axis=0)
    np.testing.assert_allclose(mm1, expect, rtol=1e-4, atol=1e-4)
    # inference normalises with the (updated) moving stats
    out_i = ex.forward(is_train=False, data=mx.nd.array(x_np))[0].asnumpy()
    assert abs(out_i.mean()) > 0.1  # not batch-normalised to zero


def test_module_excludes_aux_from_optimizer():
    """Module training must not apply optimizer updates to BN moving stats
    (round-2 review finding)."""
    from mxnet_tpu.module import Module
    from mxnet_tpu.io import NDArrayIter
    rs = np.random.RandomState(1)
    x = rs.randn(32, 6).astype(np.float32)
    y = rs.randint(0, 2, (32,)).astype(np.float32)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.BatchNorm(sym.FullyConnected(data, num_hidden=8, name="fc"),
                      name="bn")
    out = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=2, name="out"),
                            label, name="softmax")
    mod = Module(out, data_names=["data"], label_names=["softmax_label"])
    it = NDArrayIter(x, y, batch_size=16)
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1})
    arg_params, aux_params = mod.get_params()
    assert "bn_moving_mean" in aux_params
    assert "bn_moving_mean" not in arg_params
    assert not any(n.endswith("moving_mean") or n.endswith("moving_var")
                   for n in mod._param_names)
    # moving stats were updated by forward passes (train mode), not frozen
    assert not np.allclose(aux_params["bn_moving_mean"].asnumpy(), 0.0)


def test_name_manager_scoped_counters():
    """mx.name.NameManager gives deterministic auto-names regardless of
    prior construction; Prefix prepends (reference: python/mxnet/name.py)."""
    d = sym.Variable("d")
    _ = sym.FullyConnected(d, num_hidden=2)   # bump the global counter
    with mx.name.NameManager():
        s = sym.FullyConnected(d, num_hidden=2)
        assert "fullyconnected0_weight" in s.list_arguments()
    with mx.name.Prefix("enc_"):
        s = sym.FullyConnected(d, num_hidden=2)
        assert "enc_fullyconnected0_weight" in s.list_arguments()


def test_attr_scope():
    """mx.AttrScope attaches attrs to symbols created inside the scope and
    they round-trip through tojson (reference: python/mxnet/attribute.py)."""
    with mx.AttrScope(ctx_group="dev1", stage="encoder"):
        a = sym.Variable("a", attr={"grp": "x"})
        with mx.AttrScope(stage="decoder"):
            b = sym.FullyConnected(a, num_hidden=4, name="fcattr")
    assert a.attr("ctx_group") == "dev1" and a.attr("grp") == "x"
    assert b.attr("stage") == "decoder" and b.attr("ctx_group") == "dev1"
    outside = sym.Variable("c")
    assert outside.attr("ctx_group") is None
    loaded = mx.sym.load_json(b.tojson())
    assert loaded.attr("stage") == "decoder"
    assert "num_hidden" in b.list_attr()  # op attrs still visible
    import pytest
    with pytest.raises(mx.base.MXNetError):
        mx.AttrScope(bad=3)  # non-string values rejected


def test_symbolic_dropout_train_vs_inference():
    """Dropout is identity in inference and drops+rescales in training
    (round-2 review finding: the train variant must not be a no-op)."""
    data = sym.Variable("data")
    net = sym.Dropout(data, p=0.5)
    x = np.ones((64, 64), np.float32)
    ex = net.bind(None, {"data": mx.nd.array(x)},
                  {"data": mx.nd.zeros((64, 64))})
    out_inf = ex.forward(is_train=False)[0].asnumpy()
    np.testing.assert_array_equal(out_inf, x)  # identity
    out_tr = ex.forward(is_train=True)[0].asnumpy()
    zeros = (out_tr == 0).mean()
    assert 0.3 < zeros < 0.7           # ~half dropped
    kept = out_tr[out_tr != 0]
    np.testing.assert_allclose(kept, 2.0, rtol=1e-6)  # inverted scaling
    out_tr2 = ex.forward(is_train=True)[0].asnumpy()
    assert not np.array_equal(out_tr, out_tr2)  # fresh key per step


def test_softmax_output_use_ignore():
    """SoftmaxOutput(use_ignore=True) zeroes gradients at ignore_label
    positions (reference: softmax_output-inl.h). Without it, padded
    positions emit grad=p and silently corrupt training (found by the
    bucketed-LM end-to-end drive)."""
    x = sym.Variable("x")
    y = sym.Variable("y")
    out = sym.SoftmaxOutput(x, y, use_ignore=True, ignore_label=-1)
    xv = nd.array(np.random.RandomState(0).randn(4, 3).astype(np.float32))
    yv = nd.array(np.array([0, 2, -1, -1], np.float32))
    grads = {"x": nd.zeros((4, 3)), "y": nd.zeros((4,))}
    ex = out.bind(None, {"x": xv, "y": yv}, grads)
    ex.forward(is_train=True)
    ex.backward()
    g = grads["x"].asnumpy()
    assert np.abs(g[:2]).sum() > 0        # real rows got p - onehot
    np.testing.assert_allclose(g[2:], 0.0)  # ignored rows zeroed
    # default (no ignore): padded rows DO get gradients — reference parity
    out2 = sym.SoftmaxOutput(x, y)
    ex2 = out2.bind(None, {"x": xv, "y": yv},
                    {"x": nd.zeros((4, 3)), "y": nd.zeros((4,))})
    ex2.forward(is_train=True)
    ex2.backward()
    assert np.abs(ex2.grad_dict["x"].asnumpy()[2:]).sum() > 0


def test_deconvolution_symbol_and_transpose_layer_trace():
    """sym.Deconvolution matches the nd kernel, and Conv2DTranspose layers
    trace symbolically (export path for decoder/GAN nets)."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ops import nn_ops as K
    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, 5, 5).astype(np.float32)
    w = rs.randn(3, 4, 3, 3).astype(np.float32)
    out = sym.Deconvolution(sym.Variable("x"), sym.Variable("w"),
                            kernel=3, stride=2, num_filter=4, no_bias=True)
    ex = out.bind(None, {"x": nd.array(x), "w": nd.array(w)})
    got = ex.forward()[0].asnumpy()
    expect = np.asarray(K.deconvolution(x, w, None, 2, 0, 0, None))
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)
    _, out_shapes, _ = out.infer_shape(x=(2, 3, 5, 5))
    assert out_shapes == [got.shape]

    blk = nn.Conv2DTranspose(6, 3, strides=2)
    blk.initialize()
    blk(nd.array(x))
    traced = blk(sym.Variable("data"))
    _, shapes, _ = traced.infer_shape(data=(2, 3, 5, 5))
    assert shapes[0][1] == 6  # channels out


def test_auto_names_deterministic_and_collision_free():
    """Auto-names come from NameManager monotonic counters at creation:
    the same build sequence under a fresh manager yields byte-identical
    tojson(), and long chains never collide (regression for the old
    id()%10000 scheme)."""
    def build():
        x = sym.Variable("x")
        h = sym.FullyConnected(x, num_hidden=4)
        h = sym.Activation(h, act_type="relu")
        h = sym.FullyConnected(h, num_hidden=3)
        return h + sym.Variable("bias_extra")

    with mx.name.NameManager():
        j1 = build().tojson()
    with mx.name.NameManager():
        j2 = build().tojson()
    assert j1 == j2  # byte-identical across two constructions

    # 5000-node chain: every auto name unique (the old scheme collided
    # with high probability past ~120 nodes)
    s = sym.Variable("x")
    for _ in range(5000):
        s = sym.Activation(s, act_type="relu")
    names = [n.name for n in s._topo()]
    assert len(names) == len(set(names))


def test_auto_names_assigned_at_creation_order():
    """Names track construction order, not first-access order."""
    with mx.name.NameManager():
        x = sym.Variable("x")
        a = sym.Activation(x, act_type="relu")
        b = sym.Activation(x, act_type="tanh")
        # access b's name first: must still be activation1 (creation order)
        assert b.name == "activation1"
        assert a.name == "activation0"


def test_softmax_use_length_json_roundtrip():
    """Length-masked softmax (reference: softmax(use_length=True)) is a
    2-input node that must survive tojson -> load_json -> bind with the
    mask still biting."""
    d = mx.sym.Variable("scores")
    ln = mx.sym.Variable("ln")
    out = mx.sym.softmax(d, length=ln, axis=-1)
    loaded = mx.sym.load_json(out.tojson())
    scores = mx.nd.random.uniform(shape=(2, 3, 5))
    lens = mx.nd.array(np.array([5, 2], np.float32))
    got = loaded.bind(None, {"scores": scores, "ln": lens}).forward()[0]
    a = got.asnumpy()
    assert np.allclose(a.sum(-1), 1.0, atol=1e-5)
    assert np.allclose(a[1, :, 2:], 0.0, atol=1e-6)
    ref = mx.nd.softmax(scores, length=lens).asnumpy()
    assert np.allclose(a, ref, atol=1e-6)


def test_load_json_malformed_raises_cleanly():
    """Corrupt symbol JSON raises MXNetError at LOAD time for every
    failure class — non-JSON, foreign structure, truncation, and unknown
    op names (validated up front like the reference's nnvm loader, not
    deferred to the first bind)."""
    g = mx.sym.FullyConnected(mx.sym.Variable("d"), num_hidden=4,
                              name="fc")
    js = g.tojson()
    for bad in ("{{{", '{"hello": 1}', js[: len(js) // 2],
                js.replace("FullyConnected", "NoSuchOp")):
        with pytest.raises(mx.base.MXNetError):
            mx.sym.load_json(bad)
    assert mx.sym.load_json(js).list_arguments() == \
        ["d", "fc_weight", "fc_bias"]
