"""ONNX export: structure validated node-by-node via the wire-format
decoder, numerics validated by executing the decoded graph with a
torch-backed mini-interpreter (an implementation independent of the
framework's own compute path)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.contrib.onnx import export_model, proto


def _mlp():
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=16, name="fc1")
    h = sym.Activation(h, act_type="relu", name="relu1")
    h = sym.FullyConnected(h, num_hidden=10, name="fc2")
    out = sym.softmax(h, name="sm")
    shapes = out.infer_shape(data=(2, 8))[0]
    args = {n: nd.random.uniform(shape=s)
            for n, s in zip(out.list_arguments(), shapes)}
    params = {k: v for k, v in args.items() if k != "data"}
    return out, args, params


def test_mlp_structure_node_by_node(tmp_path):
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "mlp.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    assert m["opset"] == [("", 11)]
    g = m["graph"]
    assert g["inputs"] == [("data", (2, 8))]
    assert [o[0] for o in g["outputs"]] == ["sm"]
    got = [(n["op_type"], n["inputs"], n["outputs"]) for n in g["nodes"]]
    assert got == [
        ("Flatten", ["data"], ["fc1_flat__1"]),
        ("Gemm", ["fc1_flat__1", "fc1_weight", "fc1_bias"], ["fc1"]),
        ("Relu", ["fc1"], ["relu1"]),
        ("Flatten", ["relu1"], ["fc2_flat__2"]),
        ("Gemm", ["fc2_flat__2", "fc2_weight", "fc2_bias"], ["fc2"]),
        ("Softmax", ["fc2"], ["sm"]),
    ]
    gemm = g["nodes"][1]["attrs"]
    assert gemm == {"alpha": 1.0, "beta": 1.0, "transA": 0, "transB": 1}
    assert set(g["initializers"]) == set(params)
    for k, v in params.items():
        dims, dtype, raw = g["initializers"][k]
        assert dims == v.shape and dtype == proto.FLOAT
        assert np.allclose(np.frombuffer(raw, np.float32).reshape(dims),
                           v.asnumpy())


# ---------------------------------------------------------------- runtime
def _run_onnx(model, feeds):
    """Execute a decoded ONNX graph with torch ops — independent of the
    framework's jax compute path."""
    import torch
    import torch.nn.functional as F
    g = model["graph"]
    dt_of = {proto.FLOAT: np.float32, proto.INT64: np.int64,
             proto.INT32: np.int32, proto.FLOAT16: np.float16}
    env = {k: torch.from_numpy(np.frombuffer(raw, dt_of.get(_dt, np.float32))
                               .reshape([int(d) for d in dims]).copy())
           for k, (dims, _dt, raw) in g["initializers"].items()}
    for k, v in feeds.items():
        env[k] = torch.from_numpy(np.asarray(v, np.float32))

    for n in g["nodes"]:
        op, a = n["op_type"], n["attrs"]
        x = [env[i] for i in n["inputs"]]
        if op == "Conv":
            y = F.conv2d(x[0], x[1], x[2] if len(x) > 2 else None,
                         stride=list(a["strides"]),
                         padding=list(a["pads"][:2]),
                         dilation=list(a["dilations"]),
                         groups=a["group"])
        elif op == "BatchNormalization":
            y = F.batch_norm(x[0], x[3], x[4], x[1], x[2],
                             training=False, eps=a["epsilon"])
        elif op == "Relu":
            y = F.relu(x[0])
        elif op == "MaxPool":
            y = F.max_pool2d(x[0], list(a["kernel_shape"]),
                             stride=list(a["strides"]),
                             padding=list(a["pads"][:2]))
        elif op == "AveragePool":
            y = F.avg_pool2d(x[0], list(a["kernel_shape"]),
                             stride=list(a["strides"]),
                             padding=list(a["pads"][:2]),
                             count_include_pad=bool(
                                 a.get("count_include_pad", 1)))
        elif op == "GlobalAveragePool":
            y = x[0].mean(dim=(2, 3), keepdim=True)
        elif op == "GlobalMaxPool":
            y = x[0].amax(dim=(2, 3), keepdim=True)
        elif op == "Gemm":
            y = x[0] @ (x[1].t() if a["transB"] else x[1])
            if len(x) > 2:
                y = y + x[2]
        elif op == "Flatten":
            y = x[0].reshape(x[0].shape[0], -1)
        elif op == "Add":
            y = x[0] + x[1]
        elif op == "Sub":
            y = x[0] - x[1]
        elif op == "Mul":
            y = x[0] * x[1]
        elif op == "Div":
            y = x[0] / x[1]
        elif op == "Sqrt":
            y = x[0].sqrt()
        elif op == "Exp":
            y = x[0].exp()
        elif op == "Log":
            y = x[0].log()
        elif op == "ReduceMean":
            y = x[0].mean(dim=list(a["axes"]),
                          keepdim=bool(a.get("keepdims", 1)))
        elif op == "ReduceMax":
            y = x[0].amax(dim=list(a["axes"]),
                          keepdim=bool(a.get("keepdims", 1)))
        elif op == "ReduceSum":
            y = x[0].sum(dim=list(a["axes"]),
                         keepdim=bool(a.get("keepdims", 1)))
        elif op == "Softmax":
            y = F.softmax(x[0], dim=a.get("axis", -1))
        elif op == "Concat":
            y = __import__("torch").cat(x, dim=a["axis"])
        elif op == "Dropout":
            y = x[0]  # inference
        elif op == "Reshape":
            tgt = [int(d) for d in x[1].tolist()]
            shp = list(x[0].shape)
            tgt = [shp[i] if d == 0 else d for i, d in enumerate(tgt)]
            y = x[0].reshape(tgt)
        elif op == "Shape":
            y = __import__("torch").tensor(list(x[0].shape),
                                           dtype=__import__("torch").int64)
        elif op == "MatMul":
            y = x[0] @ x[1]
        elif op == "Transpose":
            y = x[0].permute(list(a["perm"])) if "perm" in a \
                else x[0].t()
        elif op == "Slice":
            starts, ends = x[1].tolist(), x[2].tolist()
            axes = x[3].tolist() if len(x) > 3 else list(range(len(starts)))
            steps = x[4].tolist() if len(x) > 4 else [1] * len(starts)
            slc = [slice(None)] * x[0].dim()
            for s, e, ax, st in zip(starts, ends, axes, steps):
                slc[ax] = slice(s, e, st)
            y = x[0][tuple(slc)]
        elif op == "Cast":
            tm = __import__("torch")
            to = {proto.FLOAT: tm.float32, proto.INT64: tm.int64,
                  proto.INT32: tm.int32, proto.FLOAT16: tm.float16}
            y = x[0].to(to[a["to"]])
        elif op == "Gather":
            got = np.take(x[0].numpy(), x[1].numpy().astype(np.int64),
                          axis=a.get("axis", 0))
            y = __import__("torch").from_numpy(np.asarray(got))
        elif op == "Range":
            y = __import__("torch").arange(
                int(x[0]), int(x[1]), int(x[2]))
        elif op == "Less":
            y = x[0] < x[1]
        elif op == "And":
            y = x[0] & x[1]
        elif op == "Where":
            y = __import__("torch").where(x[0], x[1], x[2])
        elif op == "Tanh":
            y = x[0].tanh()
        elif op == "Unsqueeze":
            y = x[0]
            for ax in sorted(a["axes"]):
                y = y.unsqueeze(ax)
        elif op == "Squeeze":
            y = x[0]
            if "axes" in a:
                for ax in sorted(a["axes"], reverse=True):
                    y = y.squeeze(ax)
            else:
                y = y.squeeze()
        elif op == "ConvTranspose":
            y = F.conv_transpose2d(
                x[0], x[1], x[2] if len(x) > 2 else None,
                stride=list(a["strides"]), padding=list(a["pads"][:2]),
                output_padding=list(a.get("output_padding", (0, 0))),
                groups=a.get("group", 1))
        elif op == "InstanceNormalization":
            y = F.instance_norm(x[0], weight=x[1], bias=x[2],
                                eps=a["epsilon"])
        elif op == "PRelu":
            # honest ONNX semantics: right-aligned unidirectional
            # broadcast of the slope AS SHIPPED (no flatten rescue —
            # a wrong slope shape must fail here like in onnxruntime)
            torch_mod = __import__("torch")
            y = torch_mod.where(x[0] >= 0, x[0], x[0] * x[1])
        else:
            raise AssertionError(f"mini-runtime: unimplemented op {op}")
        env[n["outputs"][0]] = y
    return [env[name].numpy() for name, _ in g["outputs"]]


def test_mlp_numerics_vs_torch_runtime(tmp_path):
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "mlp.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    ref = out.bind(None, args).forward()[0].asnumpy()
    got = _run_onnx(m, {"data": args["data"].asnumpy()})[0]
    assert np.allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("name", ["resnet18_v1", "alexnet",
                                  "squeezenet1.0", "densenet121"])
def test_zoo_cnn_exports_and_runs(name, tmp_path):
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model(name, classes=10)
    net.initialize()
    shape = (1, 3, 64, 64)
    x = nd.random.uniform(shape=shape)
    ref = net(x).asnumpy()
    graph = net(sym.Variable("data"))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(graph, params, {"data": shape},
                        onnx_file_path=str(tmp_path / f"{name}.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    g = m["graph"]
    assert len(g["nodes"]) > 5
    # every non-data graph input is materialised as an initializer
    assert set(g["initializers"]) == set(graph.list_arguments() +
                                         graph.list_auxiliary_states()) - \
        {"data"}
    got = _run_onnx(m, {"data": x.asnumpy()})[0]
    assert np.allclose(got, ref, atol=1e-3), \
        f"{name}: onnx runtime diverges (max err " \
        f"{np.abs(got - ref).max():.2e})"


def test_unsupported_op_raises(tmp_path):
    g = sym.SequenceReverse(sym.Variable("d"))
    with pytest.raises(mx.base.MXNetError, match="no converter"):
        export_model(g, {}, {"d": (3, 2)},
                     onnx_file_path=str(tmp_path / "x.onnx"))


def test_fix_gamma_pins_ones(tmp_path):
    """sym.BatchNorm defaults fix_gamma=True (gamma pinned to ones in
    compute); the exporter must pin the serialized scale too."""
    x = sym.Variable("data")
    out = sym.BatchNorm(x, name="bn")  # fix_gamma=True default
    shapes = dict(zip(out.list_arguments() + out.list_auxiliary_states(),
                      list(out.infer_shape(data=(2, 3, 4, 4))[0]) +
                      list(out.infer_shape(data=(2, 3, 4, 4))[2])))
    params = {n: nd.random.uniform(1.5, 2.5, shape=s)
              for n, s in shapes.items() if n != "data"}
    path = export_model(out, params, {"data": (2, 3, 4, 4)},
                        onnx_file_path=str(tmp_path / "bn.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    bn = [n for n in m["graph"]["nodes"]
          if n["op_type"] == "BatchNormalization"][0]
    scale_name = bn["inputs"][1]
    assert scale_name != "bn_gamma", "raw gamma serialized despite fix_gamma"
    dims, _dt, raw = m["graph"]["initializers"][scale_name]
    assert np.allclose(np.frombuffer(raw, np.float32), 1.0)
    # numerics agree with the framework's fix_gamma compute (aux states
    # must go through aux_states=, not args — Executor defaults them
    # otherwise)
    aux_names = set(out.list_auxiliary_states())
    data = nd.random.uniform(shape=(2, 3, 4, 4))
    args = {"data": data,
            **{k: v for k, v in params.items() if k not in aux_names}}
    aux = {k: v for k, v in params.items() if k in aux_names}
    ref = out.bind(None, args, aux_states=aux).forward()[0].asnumpy()
    got = _run_onnx(m, {"data": data.asnumpy()})[0]
    assert np.allclose(got, ref, atol=1e-4)


def test_softmax_nonlast_axis_decomposed(tmp_path):
    """opset-11 Softmax coerces to 2D, so axis != -1 must be decomposed
    into max-shifted Exp/ReduceSum/Div to keep MXNet's per-axis meaning."""
    x = sym.Variable("data")
    out = sym.softmax(x, axis=1, name="sm")
    path = export_model(out, {}, {"data": (2, 3, 5)},
                        onnx_file_path=str(tmp_path / "sm.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    ops = [n["op_type"] for n in m["graph"]["nodes"]]
    assert "Softmax" not in ops and "Div" in ops and "ReduceMax" in ops
    d = nd.random.uniform(shape=(2, 3, 5))
    ref = out.bind(None, {"data": d}).forward()[0].asnumpy()
    got = _run_onnx(m, {"data": d.asnumpy()})[0]
    assert np.allclose(got, ref, atol=1e-5)


def test_unknown_output_shape_omits_shape_field(tmp_path):
    """Unknown shapes must omit TensorShapeProto (present-but-empty means
    rank 0 to ONNX consumers)."""
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "m.onnx"))
    raw = open(path, "rb").read()
    g = proto.decode(proto.decode(raw)[7][0])
    (out_vi,) = g[12]
    v = proto.decode(out_vi)
    tensor = proto.decode(proto.decode(v[2][0])[1][0])
    assert 2 not in tensor, "shape field present for unknown output shape"


def test_stem_s2d_rejected(tmp_path):
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    net = resnet18_v1(layout="NHWC", stem_s2d=True)
    net.initialize()
    x = nd.random.uniform(shape=(1, 32, 32, 3))
    net(x)
    graph = net(sym.Variable("data"))
    with pytest.raises(mx.base.MXNetError, match="stem_s2d|NCHW|NHWC"):
        export_model(graph,
                     {k: v.data() for k, v in net.collect_params().items()},
                     {"data": (1, 32, 32, 3)},
                     onnx_file_path=str(tmp_path / "s.onnx"))


# -------------------------------------------------- import (onnx2mx)
def test_import_mlp_roundtrip(tmp_path):
    """export -> import -> bind reproduces the original network exactly
    (reference: onnx2mx import_model return convention)."""
    from mxnet_tpu.contrib.onnx import import_model
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "m.onnx"))
    ref = out.bind(None, args).forward()[0].asnumpy()
    sym2, arg_p, aux_p = import_model(path)
    assert set(arg_p) == set(params) and not aux_p
    ex = sym2.bind(None, {"data": args["data"], **arg_p})
    np.testing.assert_allclose(ex.forward()[0].asnumpy(), ref, atol=1e-5)


@pytest.mark.parametrize("name", ["resnet18_v1", "squeezenet1.0"])
def test_import_zoo_cnn_roundtrip(name, tmp_path):
    """CNN with BatchNorm/pools/concat: import must classify running stats
    as aux and reproduce logits."""
    from mxnet_tpu.contrib.onnx import import_model
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model(name, classes=10)
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 64, 64))
    ref = net(x).asnumpy()
    graph = net(sym.Variable("data"))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(graph, params, {"data": (1, 3, 64, 64)},
                        onnx_file_path=str(tmp_path / "z.onnx"))
    sym2, arg_p, aux_p = import_model(path)
    if "resnet" in name:
        assert aux_p, "BN running stats should import as aux"
        assert all("running" in k for k in aux_p)
    ex = sym2.bind(None, {"data": x, **arg_p}, aux_states=aux_p)
    np.testing.assert_allclose(ex.forward()[0].asnumpy(), ref, atol=1e-4)


def test_import_to_gluon_runs(tmp_path):
    from mxnet_tpu.contrib.onnx import import_to_gluon
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "g.onnx"))
    ref = out.bind(None, args).forward()[0].asnumpy()
    block = import_to_gluon(path)
    got = block(args["data"]).asnumpy()
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_import_unknown_op_raises(tmp_path):
    from mxnet_tpu.contrib.onnx import proto as P2, import_model
    node = P2.message(P2.f_bytes(1, "x"), P2.f_bytes(2, "y"),
                      P2.f_bytes(3, "n0"), P2.f_bytes(4, "NotAnOp"))
    vi = P2.message(P2.f_bytes(1, "x"))
    graph = P2.message(P2.f_bytes(1, node), P2.f_bytes(2, "g"),
                       P2.f_bytes(11, vi),
                       P2.f_bytes(12, P2.message(P2.f_bytes(1, "y"))))
    model = P2.message(P2.f_varint(1, 6), P2.f_bytes(7, graph))
    p = tmp_path / "bad.onnx"
    p.write_bytes(model)
    with pytest.raises(mx.base.MXNetError, match="no importer"):
        import_model(str(p))


def test_proto_decodes_packed_repeated_fields():
    """External ONNX writers pack repeated ints (proto3); the decoder must
    read packed and unpacked forms identically."""
    from mxnet_tpu.contrib.onnx import proto as P2
    # TensorProto with PACKED dims [2, 3] (field 1, wire type 2)
    packed_dims = P2._varint(2) + P2._varint(3)
    t = P2.message(P2.f_bytes(1, packed_dims),
                   P2.f_varint(2, P2.FLOAT),
                   P2.f_bytes(8, "w"),
                   P2.f_bytes(9, np.arange(6, np.float32).tobytes()
                              if False else
                              np.arange(6, dtype=np.float32).tobytes()))
    # AttributeProto with PACKED ints (field 8)
    at = P2.message(P2.f_bytes(1, "kernel_shape"),
                    P2.f_varint(20, P2.ATTR_INTS),
                    P2.f_bytes(8, P2._varint(3) + P2._varint(3)))
    node = P2.message(P2.f_bytes(1, "x"), P2.f_bytes(2, "y"),
                      P2.f_bytes(3, "n"), P2.f_bytes(4, "MaxPool"),
                      P2.f_bytes(5, at))
    graph = P2.message(P2.f_bytes(1, node), P2.f_bytes(2, "g"),
                       P2.f_bytes(5, t),
                       P2.f_bytes(12, P2.message(P2.f_bytes(1, "y"))))
    model = P2.message(P2.f_varint(1, 6), P2.f_bytes(7, graph))
    m = P2.decode_model(model)
    assert m["graph"]["initializers"]["w"][0] == (2, 3)
    assert m["graph"]["nodes"][0]["attrs"]["kernel_shape"] == (3, 3)


def test_import_reshape_net_no_orphan_params(tmp_path):
    """Reshape shape tensors are attrs after import, never params."""
    from mxnet_tpu.contrib.onnx import import_model
    x = sym.Variable("data")
    g = sym.reshape(sym.FullyConnected(x, num_hidden=12, name="fc"),
                    shape=(2, 3, 4))
    shapes = g.infer_shape(data=(2, 6))[0]
    args = {n: nd.random.uniform(shape=s)
            for n, s in zip(g.list_arguments(), shapes)}
    params = {k: v for k, v in args.items() if k != "data"}
    path = export_model(g, params, {"data": (2, 6)},
                        onnx_file_path=str(tmp_path / "r.onnx"))
    sym2, arg_p, aux_p = import_model(path)
    assert set(arg_p) == set(params), arg_p.keys()  # no shape-tensor leak
    ref = g.bind(None, args).forward()[0].asnumpy()
    got = sym2.bind(None, {"data": args["data"], **arg_p}).forward()[0]
    np.testing.assert_allclose(got.asnumpy(), ref, atol=1e-6)


def test_import_squeeze_multi_axis_roundtrip(tmp_path):
    from mxnet_tpu.contrib.onnx import import_model
    g = sym.squeeze(sym.Variable("data"), axis=(1, 3))
    path = export_model(g, {}, {"data": (2, 1, 3, 1)},
                        onnx_file_path=str(tmp_path / "sq.onnx"))
    sym2, _, _ = import_model(path)
    d = nd.random.uniform(shape=(2, 1, 3, 1))
    out = sym2.bind(None, {"data": d}).forward()[0]
    assert out.shape == (2, 3)


def test_import_pool_spec_defaults(tmp_path):
    """A spec-minimal external MaxPool (no strides attr) means stride 1."""
    from mxnet_tpu.contrib.onnx import proto as P2, import_model
    at = P2.message(P2.f_bytes(1, "kernel_shape"),
                    P2.f_varint(20, P2.ATTR_INTS),
                    P2.f_varint(8, 2), P2.f_varint(8, 2))
    node = P2.message(P2.f_bytes(1, "data"), P2.f_bytes(2, "y"),
                      P2.f_bytes(3, "p0"), P2.f_bytes(4, "MaxPool"),
                      P2.f_bytes(5, at))
    vi = P2.message(P2.f_bytes(1, "data"))
    graph = P2.message(P2.f_bytes(1, node), P2.f_bytes(2, "g"),
                       P2.f_bytes(11, vi),
                       P2.f_bytes(12, P2.message(P2.f_bytes(1, "y"))))
    model = P2.message(P2.f_varint(1, 6), P2.f_bytes(7, graph))
    p = tmp_path / "pool.onnx"
    p.write_bytes(model)
    sym2, _, _ = import_model(str(p))
    d = nd.array(np.arange(2 * 1 * 4 * 4, dtype=np.float32)
                 .reshape(2, 1, 4, 4))
    out = sym2.bind(None, {"data": d}).forward()[0]
    assert out.shape == (2, 1, 3, 3), out.shape  # stride 1, valid pads


def test_deconv_norm_prelu_export_runs(tmp_path):
    """Conv2DTranspose + InstanceNorm + GroupNorm + PReLU export and
    reproduce framework numerics under the torch runtime (the conv
    autoencoder deployment path)."""
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1),
            nn.GroupNorm(num_groups=2),
            nn.PReLU(),
            nn.Conv2DTranspose(4, 4, strides=2, padding=1),
            nn.InstanceNorm(),
            nn.Activation("relu"))
    net.initialize()
    x = nd.random.uniform(shape=(2, 3, 8, 8))
    ref = net(x).asnumpy()
    graph = net(sym.Variable("data"))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(graph, params, {"data": (2, 3, 8, 8)},
                        onnx_file_path=str(tmp_path / "dn.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    ops = [n["op_type"] for n in m["graph"]["nodes"]]
    assert "ConvTranspose" in ops and "InstanceNormalization" in ops
    assert "PRelu" in ops and "Shape" in ops
    got = _run_onnx(m, {"data": x.asnumpy()})[0]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def _bert_mini():
    from mxnet_tpu.models.bert import BERTModel
    net = BERTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                    num_heads=4, max_length=16, dropout=0.0)
    net.initialize()
    return net


def test_bert_encoder_export_matches_torch_runtime(tmp_path):
    """BERT-mini: the symbolic encoder trace —
    fused-QKV attention decomposed to slice/batch_dot/length-masked
    softmax — exports to opset 11 and reproduces the framework's eager
    (flash-attention-path) logits under the independent torch runtime,
    including a ragged valid_length batch."""
    net = _bert_mini()
    B, S = 2, 12
    rng = np.random.RandomState(7)
    tok = rng.randint(0, 50, (B, S)).astype(np.float32)
    seg = rng.randint(0, 2, (B, S)).astype(np.float32)
    vl = np.array([12, 7], np.float32)
    ref_seq, ref_pool = net(nd.array(tok), nd.array(seg), nd.array(vl))
    g = sym.Group(list(net(sym.Variable("token_ids", shape=(B, S)),
                           sym.Variable("segment_ids", shape=(B, S)),
                           sym.Variable("valid_length", shape=(B,)))))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(g, params,
                        {"token_ids": (B, S), "segment_ids": (B, S),
                         "valid_length": (B,)},
                        onnx_file_path=str(tmp_path / "bert.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    ops = [n["op_type"] for n in m["graph"]["nodes"]]
    # attention mask ops present and dynamic (no baked-in mask constant)
    for required in ("Range", "Less", "Where", "MatMul", "Tanh"):
        assert required in ops, f"missing {required} in exported graph"
    got = _run_onnx(m, {"token_ids": tok, "segment_ids": seg,
                        "valid_length": vl})
    np.testing.assert_allclose(got[0], ref_seq.asnumpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[1], ref_pool.asnumpy(),
                               rtol=1e-4, atol=1e-4)
    # the mask must actually bite: full-length ref on the padded row
    # diverges from the ragged run
    ref_full, _ = net(nd.array(tok), nd.array(seg))
    assert not np.allclose(got[0][1], ref_full.asnumpy()[1], atol=1e-4)


def test_bert_export_no_valid_length(tmp_path):
    net = _bert_mini()
    B, S = 2, 8
    rng = np.random.RandomState(3)
    tok = rng.randint(0, 50, (B, S)).astype(np.float32)
    seg = np.zeros((B, S), np.float32)
    ref_seq, ref_pool = net(nd.array(tok), nd.array(seg))
    g = sym.Group(list(net(sym.Variable("token_ids", shape=(B, S)),
                           sym.Variable("segment_ids", shape=(B, S)))))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(g, params,
                        {"token_ids": (B, S), "segment_ids": (B, S)},
                        onnx_file_path=str(tmp_path / "bert_nm.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    got = _run_onnx(m, {"token_ids": tok, "segment_ids": seg})
    np.testing.assert_allclose(got[1], ref_pool.asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_bert_import_roundtrip(tmp_path):
    """Export bert-mini, import it back, bind, and match the framework's
    eager logits — the dynamic attention-mask idiom (Shape/Range/Less/
    Where) must rebuild and execute through the importer."""
    from mxnet_tpu.contrib.onnx import import_model
    net = _bert_mini()
    B, S = 2, 10
    rng = np.random.RandomState(11)
    tok = rng.randint(0, 50, (B, S)).astype(np.float32)
    seg = rng.randint(0, 2, (B, S)).astype(np.float32)
    vl = np.array([10, 4], np.float32)
    ref_seq, ref_pool = net(nd.array(tok), nd.array(seg), nd.array(vl))
    g = sym.Group(list(net(sym.Variable("token_ids", shape=(B, S)),
                           sym.Variable("segment_ids", shape=(B, S)),
                           sym.Variable("valid_length", shape=(B,)))))
    params = {k: v.data() for k, v in net.collect_params().items()}
    path = export_model(g, params,
                        {"token_ids": (B, S), "segment_ids": (B, S),
                         "valid_length": (B,)},
                        onnx_file_path=str(tmp_path / "bert_i.onnx"))
    s2, args, aux = import_model(path)
    feed = dict(args)
    feed.update(token_ids=nd.array(tok), segment_ids=nd.array(seg),
                valid_length=nd.array(vl))
    outs = s2.bind(None, feed, aux_states=aux).forward()
    np.testing.assert_allclose(outs[0].asnumpy(), ref_seq.asnumpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs[1].asnumpy(), ref_pool.asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_transformer_nmt_export_matches_torch_runtime(tmp_path):
    """Transformer NMT (encoder + CAUSAL decoder + tied projection)
    exports to opset 11 and reproduces eager teacher-forcing logits
    under the torch runtime. The causal mask exports dynamically
    (Range x2 + Less + And), the sinusoid tables ride
    collect_constants() as initializers, and the tied embedding exports
    once (reused by embed and the output MatMul)."""
    from mxnet_tpu.models.transformer import TransformerNMT
    net = TransformerNMT(vocab_size=40, units=16, hidden=32, num_layers=2,
                         num_heads=4, max_length=16, dropout=0.0)
    net.initialize()
    B, S = 2, 9
    rng = np.random.RandomState(5)
    src = rng.randint(0, 40, (B, S)).astype(np.float32)
    tgt = rng.randint(0, 40, (B, S)).astype(np.float32)
    vl = np.array([9, 5], np.float32)
    ref = net(nd.array(src), nd.array(tgt), nd.array(vl)).asnumpy()
    g = net(sym.Variable("src", shape=(B, S)),
            sym.Variable("tgt", shape=(B, S)),
            sym.Variable("src_valid_length", shape=(B,)))
    params = {k: v.data() for k, v in net.collect_params().items()}
    params.update(net.collect_constants())
    path = export_model(g, params,
                        {"src": (B, S), "tgt": (B, S),
                         "src_valid_length": (B,)},
                        onnx_file_path=str(tmp_path / "nmt.onnx"))
    m = proto.decode_model(open(path, "rb").read())
    ops = [n["op_type"] for n in m["graph"]["nodes"]]
    # both mask kinds export: length (encoder/cross) and causal rows
    # (decoder self) — at least two Range-based masks in the graph
    assert ops.count("Range") >= 2 and ops.count("Less") >= 2
    got = _run_onnx(m, {"src": src, "tgt": tgt, "src_valid_length": vl})[0]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    # causality must bite: changing a LATER tgt token can't affect
    # earlier positions' logits
    tgt2 = tgt.copy()
    tgt2[:, -1] = (tgt2[:, -1] + 7) % 40
    got2 = _run_onnx(m, {"src": src, "tgt": tgt2,
                         "src_valid_length": vl})[0]
    np.testing.assert_allclose(got2[:, :-1], got[:, :-1],
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(got2[:, -1], got[:, -1], atol=1e-5)


def test_masked_softmax_causal_plus_length_export():
    """causal AND length masks compose (the And path): exported graph
    matches the framework kernel on a ragged causal attention map."""
    import tempfile, os
    d = sym.Variable("scores")
    ln = sym.Variable("ln")
    out = sym.softmax(d, length=ln, axis=-1, causal=True)
    scores = nd.random.uniform(shape=(2, 2, 5, 5))
    lens = nd.array(np.array([5, 3], np.float32))
    ref = mx.nd.softmax(scores, lens, causal=True).asnumpy()
    with tempfile.TemporaryDirectory() as td:
        path = export_model(out, {}, {"scores": (2, 2, 5, 5), "ln": (2,)},
                            onnx_file_path=os.path.join(td, "ms.onnx"))
        m = proto.decode_model(open(path, "rb").read())
    assert "And" in [n["op_type"] for n in m["graph"]["nodes"]]
    got = _run_onnx(m, {"scores": scores.asnumpy(), "ln": lens.asnumpy()})[0]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # row 0 attends only to col 0; batch 1 cols >= 3 are dead
    assert np.allclose(got[:, :, 0, 1:], 0, atol=1e-7)
    assert np.allclose(got[1, :, :, 3:], 0, atol=1e-7)


def test_transformer_nmt_import_roundtrip(tmp_path):
    """Export the NMT model, import it back, bind, and match eager
    logits — the dynamic causal idiom (two Range chains + Less/And)
    must rebuild and execute through the importer too."""
    from mxnet_tpu.contrib.onnx import import_model
    from mxnet_tpu.models.transformer import TransformerNMT
    net = TransformerNMT(vocab_size=35, units=16, hidden=32, num_layers=1,
                         num_heads=4, max_length=12, dropout=0.0)
    net.initialize()
    B, S = 2, 8
    rng = np.random.RandomState(9)
    src = rng.randint(0, 35, (B, S)).astype(np.float32)
    tgt = rng.randint(0, 35, (B, S)).astype(np.float32)
    vl = np.array([8, 5], np.float32)
    ref = net(nd.array(src), nd.array(tgt), nd.array(vl)).asnumpy()
    g = net(sym.Variable("src", shape=(B, S)),
            sym.Variable("tgt", shape=(B, S)),
            sym.Variable("src_valid_length", shape=(B,)))
    params = {k: v.data() for k, v in net.collect_params().items()}
    params.update(net.collect_constants())
    path = export_model(g, params,
                        {"src": (B, S), "tgt": (B, S),
                         "src_valid_length": (B,)},
                        onnx_file_path=str(tmp_path / "nmt_i.onnx"))
    s2, args, aux = import_model(path)
    feed = dict(args)
    feed.update(src=nd.array(src), tgt=nd.array(tgt),
                src_valid_length=nd.array(vl))
    outs = s2.bind(None, feed, aux_states=aux).forward()
    np.testing.assert_allclose(outs[0].asnumpy(), ref,
                               rtol=2e-4, atol=2e-4)


def test_decode_model_malformed_raises_cleanly(tmp_path):
    """Truncated or garbage bytes must raise MXNetError('malformed...')
    — never hang (the wire walk only advances) and never leak a bare
    IndexError. Truncations that happen to land on a field boundary may
    decode leniently to a partial dict; both outcomes are acceptable,
    a hang or foreign exception is not."""
    out, args, params = _mlp()
    path = export_model(out, params, {"data": (2, 8)},
                        onnx_file_path=str(tmp_path / "m.onnx"))
    raw = open(path, "rb").read()
    for cut in (1, 7, len(raw) // 3, len(raw) // 2, len(raw) - 2):
        try:
            m = proto.decode_model(raw[:cut])
            assert isinstance(m, dict)          # lenient partial decode
        except mx.base.MXNetError as e:
            assert "malformed ONNX file" in str(e)
    # each of these drives a DIFFERENT underlying failure: bad wire type
    # (ValueError), scalar-where-submessage (TypeError), varint
    # truncation (IndexError) — all must surface as the one contract
    for garbage in (b"\xff" * 64, b"\x0b", b"\x38\x01"):
        with pytest.raises(mx.base.MXNetError, match="malformed ONNX"):
            proto.decode_model(garbage)


def test_decode_model_crafted_attr_garbage():
    """Value-level garbage the wire walk can't type-check also surfaces
    as MXNetError: a packed-floats blob of non-multiple-of-4 length
    (struct.error underneath) and an ATTR_INT whose payload arrives as
    bytes (TypeError underneath)."""
    name = proto.f_bytes(1, b"a")
    # AttributeProto type=FLOATS(6) with a 3-byte packed field-7 blob
    bad_floats = proto.message(name, proto.f_varint(20, 6),
                               proto.f_bytes(7, b"\x00\x01\x02"))
    # AttributeProto type=INT(2) with field 3 as length-delimited bytes
    bad_int = proto.message(name, proto.f_varint(20, 2),
                            proto.f_bytes(3, b"xy"))
    for attr in (bad_floats, bad_int):
        node = proto.message(proto.f_bytes(4, b"Relu"),
                             proto.f_bytes(5, attr))
        graph = proto.message(proto.f_bytes(1, node))
        model = proto.message(proto.f_bytes(7, graph))
        with pytest.raises(mx.base.MXNetError, match="malformed ONNX"):
            proto.decode_model(bytes(model))
