"""Gluon core tests (reference model: tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn


def test_dense_shapes_and_forward():
    layer = nn.Dense(4, in_units=3)
    layer.initialize()
    x = nd.ones((2, 3))
    y = layer(x)
    assert y.shape == (2, 4)


def test_dense_deferred_init():
    layer = nn.Dense(5)
    layer.initialize()
    y = layer(nd.ones((2, 7)))
    assert y.shape == (2, 5)
    assert layer.weight.shape == (5, 7)


def test_sequential_and_params():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    y = net(nd.ones((4, 6)))
    assert y.shape == (4, 3)
    params = net.collect_params()
    assert len(params) == 4  # 2 weights + 2 biases
    names = list(params.keys())
    assert any("weight" in n for n in names)


def test_hybridize_parity():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = nd.array(np.random.rand(5, 8).astype(np.float32))
    y_eager = net(x).asnumpy()
    net.hybridize()
    y_hybrid = net(x).asnumpy()
    assert np.allclose(y_eager, y_hybrid, atol=1e-5)
    # second call uses the cached executable
    y2 = net(x).asnumpy()
    assert np.allclose(y_hybrid, y2)


def test_hybridize_backward():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    net.hybridize()
    x = nd.array([[1.0, 2.0]])
    with autograd.record():
        y = net(x)
    y.backward()
    assert np.allclose(net.weight.grad().asnumpy(), [[1.0, 2.0]])


def test_batchnorm_running_stats():
    bn = nn.BatchNorm(in_channels=3)
    bn.initialize()
    x = nd.array(np.random.rand(8, 3, 4, 4).astype(np.float32) * 5 + 2)
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    assert not np.allclose(rm, 0)  # stats updated
    # inference mode uses running stats
    y = bn(x)
    assert y.shape == x.shape


def test_batchnorm_hybrid_stats():
    bn = nn.BatchNorm(in_channels=2)
    bn.initialize()
    bn.hybridize()
    x = nd.array(np.random.rand(4, 2, 3, 3).astype(np.float32) + 10)
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    assert rm.mean() > 0.5  # moved toward ~10 batch mean


def test_conv2d():
    conv = nn.Conv2D(8, kernel_size=3, padding=1, in_channels=3)
    conv.initialize()
    x = nd.ones((2, 3, 16, 16))
    y = conv(x)
    assert y.shape == (2, 8, 16, 16)
    conv_s = nn.Conv2D(4, kernel_size=3, strides=2)
    conv_s.initialize()
    y2 = conv_s(nd.ones((1, 3, 8, 8)))
    assert y2.shape == (1, 4, 3, 3)


def test_conv2d_nhwc():
    conv = nn.Conv2D(8, kernel_size=3, padding=1, layout="NHWC")
    conv.initialize()
    y = conv(nd.ones((2, 16, 16, 3)))
    assert y.shape == (2, 16, 16, 8)


def test_pooling():
    x = nd.ones((1, 2, 8, 8))
    assert nn.MaxPool2D(2, 2)(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(2, 2)(x).shape == (1, 2, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)


def test_embedding_dropout_layernorm():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    y = emb(nd.array([1, 2, 3]))
    assert y.shape == (3, 4)
    ln = nn.LayerNorm(in_channels=4)
    ln.initialize()
    z = ln(y)
    assert np.allclose(z.asnumpy().mean(-1), 0, atol=1e-5)
    do = nn.Dropout(0.5)
    with autograd.record():
        d = do(y)
    assert d.shape == y.shape


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    f = str(tmp_path / "net.params.npz")
    net.save_parameters(f)
    w_before = net[0].weight.data().asnumpy()

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.initialize()
    net2.load_parameters(f)
    # prefixes differ but structural (strip-prefix) names must map — load by
    # matching relative names requires same architecture
    assert np.allclose(net2[0].weight.data().asnumpy(), w_before)


def test_trainer_step_sgd():
    net = nn.Dense(1, in_units=1, use_bias=False)
    net.initialize(mx.init.Constant(2.0))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array([[1.0]])
    with autograd.record():
        y = net(x)          # y = 2x
        loss = (y * y).sum()  # dL/dw = 2*y*x = 4
    loss.backward()
    trainer.step(1)
    assert np.allclose(net.weight.data().asnumpy(), [[2.0 - 0.4]])


def test_mlp_convergence():
    """End-to-end: MLP learns a separable toy problem (SURVEY.md §4)."""
    np.random.seed(0)
    n = 256
    x = np.random.randn(n, 10).astype(np.float32)
    w_true = np.random.randn(10, 3).astype(np.float32)
    labels = np.argmax(x @ w_true, axis=1).astype(np.float32)

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    xs, ys = nd.array(x), nd.array(labels)
    for _ in range(60):
        with autograd.record():
            out = net(xs)
            loss = loss_fn(out, ys)
        loss.backward()
        trainer.step(n)
    preds = net(xs).asnumpy().argmax(1)
    acc = (preds == labels).mean()
    assert acc > 0.9, f"accuracy {acc}"


def test_block_repr_and_summary():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3))
    net.initialize()
    assert "Dense" in repr(net)
    out = net.summary()
    assert "Total params" in out


def test_clip_global_norm():
    a = nd.ones((2,)) * 3
    b = nd.ones((2,)) * 4
    total = gluon.utils.clip_global_norm([a, b], 1.0)
    assert abs(total - np.sqrt(9 * 2 + 16 * 2)) < 1e-4
    new_norm = np.sqrt((a.asnumpy() ** 2).sum() + (b.asnumpy() ** 2).sum())
    assert new_norm <= 1.0 + 1e-5


def test_split_and_load():
    data = nd.arange(0, 12).reshape((6, 2))
    parts = gluon.utils.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(parts) == 2 and parts[0].shape == (3, 2)


def test_extract_pure_fn_training_aux():
    """extract_pure_fn(training=True) returns BN running-stat updates so an
    exported train step can carry them."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import extract_pure_fn

    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Dense(4))
    net.initialize()
    x = mx.nd.random.uniform(shape=(16, 5))
    net(x)
    fn, params = extract_pure_fn(net, x, training=True)
    assert len(fn.aux_indices) == 2  # running_mean, running_var
    out, aux = jax.jit(fn)(params, x._data)
    assert out.shape == (16, 4) and len(aux) == 2
    # updated stats differ from the init values (mean 0 / var 1)
    before = [params[i] for i in fn.aux_indices]
    changed = [not jnp.allclose(b, a) for b, a in zip(before, aux)]
    assert all(changed)
    # eval path keeps the old contract: bare outputs
    fn_eval, params = extract_pure_fn(net, x)
    y = fn_eval(params, x._data)
    assert y.shape == (16, 4)


def test_export_imports_roundtrip(tmp_path):
    """HybridBlock.export writes a real symbol.json + checkpoint-style
    params that SymbolBlock.imports reloads to identical outputs
    (reference: the export/imports deployment pair)."""
    from mxnet_tpu.gluon import SymbolBlock
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = nd.random.uniform(shape=(2, 8))
    expect = net(x).asnumpy()

    path = str(tmp_path / "model")
    net.export(path, epoch=3)
    import os
    assert os.path.exists(path + "-symbol.json")
    assert os.path.exists(path + "-0003.params.npz")

    loaded = SymbolBlock.imports(path + "-symbol.json", ["data"],
                                 path + "-0003.params.npz")
    got = loaded(x).asnumpy()
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_imports_fallback_and_no_params(tmp_path):
    """Non-symbolic exports warn and are rejected by imports with a clear
    error; imports without a params file yields uninitialized Parameters
    (round-2 review findings)."""
    import warnings
    from mxnet_tpu.gluon import SymbolBlock
    # a Lambda over raw NDArray ops has no symbolic trace -> fallback
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Lambda(lambda x: x * x.sigmoid()))
    net.initialize()
    net(nd.ones((2, 3)))
    path = str(tmp_path / "bnnet")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        net.export(path)
    assert any("no symbolic trace" in str(x.message) for x in w)
    with pytest.raises(mx.base.MXNetError):
        SymbolBlock.imports(path + "-symbol.json", ["data"])

    # symbolic net, no params file: uninitialized Parameters exist
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4))
    net2.initialize()
    net2(nd.ones((2, 3)))
    p2 = str(tmp_path / "ok")
    net2.export(p2)
    blk = SymbolBlock.imports(p2 + "-symbol.json", ["data"])
    assert len(blk.collect_params()) == 2  # weight+bias, no data


def test_export_imports_resnet(tmp_path):
    """Model-zoo nets (conv/BN/pool) export to a real symbol.json with aux
    states and reload to identical outputs — the full deployment path."""
    from mxnet_tpu.gluon import SymbolBlock
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    net = resnet18_v1()
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 64, 64))
    expect = net(x).asnumpy()

    path = str(tmp_path / "resnet18")
    net.export(path)
    loaded = SymbolBlock.imports(path + "-symbol.json", ["data"],
                                 path + "-0000.params.npz")
    got = loaded(x).asnumpy()
    np.testing.assert_allclose(got, expect, rtol=2e-3, atol=2e-4)
    # aux states (BN running stats) rode the aux: prefix
    import numpy as _np
    with _np.load(path + "-0000.params.npz") as f:
        keys = list(f.keys())
    assert any(k.startswith("aux:") for k in keys)
    assert any(k.startswith("arg:") for k in keys)


def test_transformer_export_symbolblock_roundtrip(tmp_path):
    """HybridBlock.export with input_shapes ships the transformer's
    sinusoid tables (collect_constants) in the params file, so
    SymbolBlock.imports reloads and reproduces the trained logits —
    the reference deployment pair for seq2seq."""
    import numpy as np
    from mxnet_tpu.models.transformer import TransformerNMT
    from mxnet_tpu.gluon.block import SymbolBlock
    net = TransformerNMT(vocab_size=25, units=16, hidden=32, num_layers=1,
                         num_heads=4, max_length=10, dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(2)
    B, S = 2, 6
    src = nd.array(rng.randint(0, 25, (B, S)).astype(np.float32))
    tgt = nd.array(rng.randint(0, 25, (B, S)).astype(np.float32))
    ref = net(src, tgt).asnumpy()
    path = str(tmp_path / "nmt")
    net.export(path, num_inputs=2, input_shapes=[(B, S), (B, S)])
    loaded = SymbolBlock.imports(f"{path}-symbol.json", ["data", "data1"],
                                 f"{path}-0000.params.npz")
    got = loaded(src, tgt).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_exported_constants_frozen_on_reimport(tmp_path):
    """Shipped constants (const: prefix) reload grad_req='null' — a
    Trainer on the re-imported transformer must NOT update the sinusoid
    tables (r4 review finding: they came back as trainable args)."""
    import numpy as np
    from mxnet_tpu.models.transformer import TransformerNMT
    from mxnet_tpu.gluon.block import SymbolBlock
    net = TransformerNMT(vocab_size=20, units=16, hidden=32, num_layers=1,
                         num_heads=4, max_length=10, dropout=0.0)
    net.initialize()
    path = str(tmp_path / "nmtf")
    net.export(path, num_inputs=2, input_shapes=[(2, 5), (2, 5)])
    loaded = SymbolBlock.imports(f"{path}-symbol.json", ["data", "data1"],
                                 f"{path}-0000.params.npz")
    consts = {k: p for k, p in loaded.collect_params().items()
              if k.endswith("pos_table")}
    assert consts and all(p.grad_req == "null" for p in consts.values())
    rng = np.random.RandomState(0)
    src = nd.array(rng.randint(0, 20, (2, 5)).astype(np.float32))
    tgt = nd.array(rng.randint(0, 20, (2, 5)).astype(np.float32))
    lab = nd.array(rng.randint(0, 20, (2, 5)).astype(np.float32))
    before = {k: p.data().asnumpy().copy() for k, p in consts.items()}
    tr = gluon.Trainer(loaded.collect_params(), "sgd",
                       {"learning_rate": 0.5})
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        out = loaded(src, tgt)
        L = lossf(out.reshape((-1, 20)), lab.reshape((-1,))).mean()
    L.backward()
    tr.step(2)
    for k, p in consts.items():
        np.testing.assert_array_equal(p.data().asnumpy(), before[k])


def test_bert_export_symbolblock_roundtrip(tmp_path):
    """BERT deploys through the reference export/imports pair too: the
    symbolic trace (decomposed flash attention) exports with shape
    hints and reloads as one Executor, ragged valid_length included."""
    import numpy as np
    from mxnet_tpu.models.bert import BERTModel
    from mxnet_tpu.gluon.block import SymbolBlock
    net = BERTModel(vocab_size=40, units=32, hidden_size=64, num_layers=2,
                    num_heads=4, max_length=12, dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(6)
    B, S = 2, 9
    tok = nd.array(rng.randint(0, 40, (B, S)).astype(np.float32))
    seg = nd.array(np.zeros((B, S), np.float32))
    vl = nd.array(np.array([9, 4], np.float32))
    ref_seq, ref_pool = net(tok, seg, vl)
    path = str(tmp_path / "bert")
    net.export(path, num_inputs=3, input_shapes=[(B, S), (B, S), (B,)])
    loaded = SymbolBlock.imports(f"{path}-symbol.json",
                                 ["data", "data1", "data2"],
                                 f"{path}-0000.params.npz")
    got_seq, got_pool = loaded(tok, seg, vl)
    np.testing.assert_allclose(got_pool.asnumpy(), ref_pool.asnumpy(),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_seq.asnumpy(), ref_seq.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_bert_classifier_export_symbolblock_roundtrip(tmp_path):
    """The finetune deployment path: BERTClassifier (bert + pooled-output
    head) exports symbolically and reloads through SymbolBlock."""
    import numpy as np
    from mxnet_tpu.models.bert import BERTModel, BERTClassifier
    from mxnet_tpu.gluon.block import SymbolBlock
    bert = BERTModel(vocab_size=30, units=32, hidden_size=64, num_layers=1,
                     num_heads=4, max_length=10, dropout=0.0)
    clf = BERTClassifier(bert, num_classes=3, dropout=0.0)
    clf.initialize()
    rng = np.random.RandomState(8)
    B, S = 2, 7
    tok = nd.array(rng.randint(0, 30, (B, S)).astype(np.float32))
    seg = nd.array(np.zeros((B, S), np.float32))
    vl = nd.array(np.array([7, 3], np.float32))
    ref = clf(tok, seg, vl).asnumpy()
    path = str(tmp_path / "bclf")
    clf.export(path, num_inputs=3, input_shapes=[(B, S), (B, S), (B,)])
    loaded = SymbolBlock.imports(f"{path}-symbol.json",
                                 ["data", "data1", "data2"],
                                 f"{path}-0000.params.npz")
    got = loaded(tok, seg, vl).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
