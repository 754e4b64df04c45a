"""ONNX → Symbol importer (reference: python/mxnet/contrib/onnx/onnx2mx/
import_model.py + import_onnx.py + _op_translations.py).

Reads an ONNX file through the wire-format decoder in `proto.py` (no
`onnx` package) and rebuilds a Symbol graph + parameter dicts:

    sym, arg_params, aux_params = import_model("model.onnx")

mirroring the reference's return convention, so the result binds/executes
exactly like a loaded symbol.json checkpoint. The op table covers the
surface `export.py` emits (CNN/MLP graphs: Conv, BatchNormalization,
pooling, Gemm, activations, elemwise, Concat, Reshape, Transpose, Gather,
reductions, softmax family) — the same coverage direction the reference's
onnx2mx table took.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError
from . import proto as P

__all__ = ["import_model", "import_to_gluon"]

_IMPORTERS = {}


def register_importer(op_type):
    def deco(fn):
        _IMPORTERS[op_type] = fn
        return fn
    return deco


_ONNX_TO_NP = {P.FLOAT: np.float32, P.DOUBLE: np.float64,
               P.FLOAT16: np.float16, P.UINT8: np.uint8, P.INT8: np.int8,
               P.INT32: np.int32, P.INT64: np.int64, P.BOOL: np.bool_}
try:
    import ml_dtypes as _mld
    _ONNX_TO_NP[P.BFLOAT16] = _mld.bfloat16
except ImportError:  # pragma: no cover
    pass


def _np_dtype(onnx_flag):
    if onnx_flag not in _ONNX_TO_NP:
        raise MXNetError(f"ONNX import: unsupported tensor dtype flag "
                         f"{onnx_flag}")
    return np.dtype(_ONNX_TO_NP[onnx_flag])


class _Ctx:
    def __init__(self, sym_mod, initializers):
        self.sym = sym_mod
        self.env = {}            # tensor name -> Symbol
        self.initializers = initializers  # name -> np array (consts too)

    def get(self, name):
        if name not in self.env:
            raise MXNetError(f"ONNX import: tensor {name!r} undefined")
        return self.env[name]

    def const_array(self, name):
        """The raw array behind an initializer input (Reshape shapes,
        axes-as-inputs...)."""
        if name not in self.initializers:
            raise MXNetError(f"ONNX import: {name!r} must be an "
                             "initializer (dynamic value not supported)")
        return self.initializers[name]


def _pads_to_pad(pads):
    if pads is None:
        return (0, 0)
    pads = tuple(pads)
    half = len(pads) // 2
    begin, end = pads[:half], pads[half:]
    if begin != end:
        raise MXNetError(f"ONNX import: asymmetric pads {pads} not "
                         "supported (symmetric only, like the reference)")
    return begin


@register_importer("Conv")
def _conv(node, ctx, S):
    a = node["attrs"]
    ins = node["inputs"]
    w = ctx.const_array(ins[1])
    return S.Convolution(
        ctx.get(ins[0]), ctx.get(ins[1]),
        ctx.get(ins[2]) if len(ins) > 2 else None,
        kernel=tuple(a.get("kernel_shape", w.shape[2:])),
        stride=tuple(a.get("strides", (1, 1))),
        pad=_pads_to_pad(a.get("pads")),
        dilate=tuple(a.get("dilations", (1, 1))),
        num_filter=int(w.shape[0]),
        num_group=int(a.get("group", 1)),
        no_bias=len(ins) <= 2, name=node["name"] or None)


@register_importer("BatchNormalization")
def _bn(node, ctx, S):
    a = node["attrs"]
    ins = [ctx.get(i) for i in node["inputs"]]
    return S.BatchNorm(*ins, eps=a.get("epsilon", 1e-5),
                       momentum=a.get("momentum", 0.9), fix_gamma=False,
                       name=node["name"] or None)


_ACT = {"Relu": "relu", "Sigmoid": "sigmoid", "Tanh": "tanh",
        "Softplus": "softrelu", "Softsign": "softsign"}


def _make_act(onnx_op):
    def imp(node, ctx, S):
        return S.Activation(ctx.get(node["inputs"][0]),
                            act_type=_ACT[onnx_op],
                            name=node["name"] or None)
    return imp


for _o in _ACT:
    _IMPORTERS[_o] = _make_act(_o)


@register_importer("MaxPool")
def _maxpool(node, ctx, S):
    a = node["attrs"]
    k = tuple(a["kernel_shape"])
    # ONNX spec defaults: strides 1 per axis, count_include_pad 0
    return S.Pooling(ctx.get(node["inputs"][0]), pool_type="max",
                     kernel=k,
                     stride=tuple(a.get("strides") or (1,) * len(k)),
                     pad=_pads_to_pad(a.get("pads")),
                     name=node["name"] or None)


@register_importer("AveragePool")
def _avgpool(node, ctx, S):
    a = node["attrs"]
    k = tuple(a["kernel_shape"])
    return S.Pooling(ctx.get(node["inputs"][0]), pool_type="avg",
                     kernel=k,
                     stride=tuple(a.get("strides") or (1,) * len(k)),
                     pad=_pads_to_pad(a.get("pads")),
                     count_include_pad=bool(a.get("count_include_pad", 0)),
                     name=node["name"] or None)


@register_importer("GlobalAveragePool")
def _gavg(node, ctx, S):
    return S.Pooling(ctx.get(node["inputs"][0]), pool_type="avg",
                     global_pool=True, name=node["name"] or None)


@register_importer("GlobalMaxPool")
def _gmax(node, ctx, S):
    return S.Pooling(ctx.get(node["inputs"][0]), pool_type="max",
                     global_pool=True, name=node["name"] or None)


@register_importer("Gemm")
def _gemm(node, ctx, S):
    a = node["attrs"]
    if a.get("alpha", 1.0) != 1.0 or a.get("beta", 1.0) != 1.0 or \
            a.get("transA", 0):
        raise MXNetError("ONNX import: Gemm with alpha/beta != 1 or "
                         "transA not supported")
    ins = node["inputs"]
    w = ctx.const_array(ins[1]) if ins[1] in ctx.initializers else None
    wsym = ctx.get(ins[1])
    if not a.get("transB", 0):
        # FullyConnected wants (out, in): transpose the weight symbolically
        wsym = S.transpose(wsym, axes=(1, 0))
        num_hidden = int(w.shape[1]) if w is not None else None
    else:
        num_hidden = int(w.shape[0]) if w is not None else None
    return S.FullyConnected(
        ctx.get(ins[0]), wsym,
        ctx.get(ins[2]) if len(ins) > 2 else None,
        num_hidden=num_hidden, no_bias=len(ins) <= 2, flatten=False,
        name=node["name"] or None)


@register_importer("MatMul")
def _matmul(node, ctx, S):
    # ONNX MatMul has numpy-matmul semantics at every rank (batched at
    # rank>2) — that's batch_dot (jnp.matmul), NOT dot (jnp.dot, which
    # outer-products the batch dims of rank>2 operands)
    return S.batch_dot(ctx.get(node["inputs"][0]),
                       ctx.get(node["inputs"][1]),
                       name=node["name"] or None)


@register_importer("Flatten")
def _flatten(node, ctx, S):
    if node["attrs"].get("axis", 1) != 1:
        raise MXNetError("ONNX import: Flatten axis != 1 unsupported")
    return S.flatten(ctx.get(node["inputs"][0]), name=node["name"] or None)


@register_importer("Softmax")
def _softmax(node, ctx, S):
    # opset-11 default axis is 1 with coerce-to-2D semantics; per-axis
    # softmax at axis=1 matches it exactly for rank-2 tensors (the common
    # classifier head). Higher-rank axis-less Softmax differs — rare, and
    # flagged here rather than silently mis-imported.
    axis = node["attrs"].get("axis", 1)
    return S.softmax(ctx.get(node["inputs"][0]), axis=axis,
                     name=node["name"] or None)


@register_importer("LogSoftmax")
def _log_softmax(node, ctx, S):
    axis = node["attrs"].get("axis", 1)
    return S.log_softmax(ctx.get(node["inputs"][0]), axis=axis,
                         name=node["name"] or None)


@register_importer("Dropout")
def _dropout(node, ctx, S):
    return S.Dropout(ctx.get(node["inputs"][0]),
                     p=node["attrs"].get("ratio", 0.5),
                     name=node["name"] or None)


@register_importer("Concat")
def _concat(node, ctx, S):
    return S.concat(*[ctx.get(i) for i in node["inputs"]],
                    dim=node["attrs"]["axis"], name=node["name"] or None)


@register_importer("Reshape")
def _reshape(node, ctx, S):
    shape = tuple(int(d) for d in ctx.const_array(node["inputs"][1]))
    return S.reshape(ctx.get(node["inputs"][0]), shape=shape,
                     name=node["name"] or None)


@register_importer("Transpose")
def _transpose(node, ctx, S):
    return S.transpose(ctx.get(node["inputs"][0]),
                       axes=tuple(node["attrs"].get("perm", ())) or None,
                       name=node["name"] or None)


@register_importer("Unsqueeze")
def _unsqueeze(node, ctx, S):
    out = ctx.get(node["inputs"][0])
    for axis in sorted(int(a) for a in node["attrs"]["axes"]):
        out = S.expand_dims(out, axis=axis)
    return out


@register_importer("Squeeze")
def _squeeze(node, ctx, S):
    axes = node["attrs"].get("axes")
    if axes is None:
        axis = None
    else:
        axis = tuple(int(a) for a in axes)
        if len(axis) == 1:
            axis = axis[0]
    return S.squeeze(ctx.get(node["inputs"][0]), axis=axis,
                     name=node["name"] or None)


@register_importer("Cast")
def _cast(node, ctx, S):
    return S.cast(ctx.get(node["inputs"][0]),
                  dtype=str(_np_dtype(node["attrs"]["to"])),
                  name=node["name"] or None)


@register_importer("Gather")
def _gather(node, ctx, S):
    axis = node["attrs"].get("axis", 0)
    idx_name = node["inputs"][1]
    try:
        idx = ctx.const_array(idx_name)
    except (KeyError, MXNetError):
        idx = None
    if idx is not None and idx.size <= 16:
        # inline small constant indices as an attr: keeps the gather
        # concrete at trace time (Shape->Gather->Range mask chains)
        from ...symbol.symbol import _make
        val = int(idx) if idx.ndim == 0 else tuple(int(i) for i in idx)
        return _make("take", [ctx.get(node["inputs"][0])],
                     {"axis": axis, "indices": val},
                     name=node["name"] or None)
    return S.take(ctx.get(node["inputs"][0]), ctx.get(idx_name),
                  axis=axis, name=node["name"] or None)


@register_importer("Shape")
def _shape(node, ctx, S):
    return S.shape_array(ctx.get(node["inputs"][0]),
                         name=node["name"] or None)


@register_importer("Range")
def _range(node, ctx, S):
    # limit may be a graph tensor (the exporter's dynamic attention mask:
    # Shape -> Gather -> Range — concrete at trace time since shapes are
    # static under jit); start/delta must be constants, inlined as attrs
    # so only the limit rides the graph
    start = ctx.const_array(node["inputs"][0])
    delta = ctx.const_array(node["inputs"][2])
    # .reshape(()).item(): int() on an ndim>0 size-1 array is a NumPy
    # deprecation
    return S._dynamic_arange(ctx.get(node["inputs"][1]),
                             start=int(np.asarray(start).reshape(()).item()),
                             delta=int(np.asarray(delta).reshape(()).item()),
                             name=node["name"] or None)


@register_importer("Less")
def _less(node, ctx, S):
    return S.broadcast_lesser(ctx.get(node["inputs"][0]),
                              ctx.get(node["inputs"][1]))


@register_importer("And")
def _and(node, ctx, S):
    # comparison importers yield float 0/1 masks (the reference
    # broadcast_lesser convention), so logical-and is their product
    return S.broadcast_mul(ctx.get(node["inputs"][0]),
                           ctx.get(node["inputs"][1]))


@register_importer("Where")
def _where(node, ctx, S):
    return S.where(ctx.get(node["inputs"][0]), ctx.get(node["inputs"][1]),
                   ctx.get(node["inputs"][2]), name=node["name"] or None)


@register_importer("Slice")
def _slice(node, ctx, S):
    starts = ctx.const_array(node["inputs"][1]).tolist()
    ends = ctx.const_array(node["inputs"][2]).tolist()
    if len(node["inputs"]) > 3:
        axes = ctx.const_array(node["inputs"][3]).tolist()
    else:
        axes = list(range(len(starts)))
    if len(node["inputs"]) > 4:
        steps = ctx.const_array(node["inputs"][4]).tolist()
        if any(s != 1 for s in steps):
            raise MXNetError("ONNX import: Slice steps != 1 unsupported")
    out = ctx.get(node["inputs"][0])
    for s, e, ax in zip(starts, ends, axes):
        out = S.slice_axis(out, axis=int(ax), begin=int(s),
                           end=None if e >= 2**31 else int(e))
    return out


def _binary(op_method):
    def imp(node, ctx, S):
        fn = getattr(S, op_method)
        return fn(ctx.get(node["inputs"][0]), ctx.get(node["inputs"][1]),
                  name=node["name"] or None)
    return imp


def _elemwise(opname):
    def imp(node, ctx, S):
        from ...symbol.symbol import _make
        return _make(opname, [ctx.get(i) for i in node["inputs"]], {},
                     name=node["name"] or None)
    return imp


for _o, _mx in [("Add", "elemwise_add"), ("Sub", "elemwise_sub"),
                ("Mul", "elemwise_mul"), ("Div", "elemwise_div")]:
    _IMPORTERS[_o] = _elemwise(_mx)


def _unary(opname):
    def imp(node, ctx, S):
        from ...symbol.symbol import _make
        return _make(opname, [ctx.get(node["inputs"][0])], {},
                     name=node["name"] or None)
    return imp


for _o, _mx in [("Sqrt", "sqrt"), ("Exp", "exp"), ("Log", "log"),
                ("Neg", "negative"), ("Abs", "abs"), ("Relu6", None)]:
    if _mx:
        _IMPORTERS[_o] = _unary(_mx)


def _reduce(opname):
    def imp(node, ctx, S):
        from ...symbol.symbol import _make
        a = node["attrs"]
        axes = a.get("axes")
        axis = tuple(int(x) for x in axes) if axes else None
        if axis is not None and len(axis) == 1:
            axis = axis[0]
        return _make(opname, [ctx.get(node["inputs"][0])],
                     {"axis": axis, "keepdims": bool(a.get("keepdims", 1))},
                     name=node["name"] or None)
    return imp


for _o, _mx in [("ReduceMean", "mean"), ("ReduceSum", "sum"),
                ("ReduceMax", "max"), ("ReduceMin", "min")]:
    _IMPORTERS[_o] = _reduce(_mx)


# ------------------------------------------------------------- entry points
def import_model(onnx_file):
    """ONNX file → (sym, arg_params, aux_params), the reference onnx2mx
    return convention. BatchNorm running stats land in aux_params (they
    feed aux input slots of the rebuilt graph); everything else is an
    arg."""
    from ... import symbol as S
    from ...ndarray.ndarray import NDArray
    import jax.numpy as jnp

    with open(onnx_file, "rb") as f:
        model = P.decode_model(f.read())
    g = model["graph"]

    inits = {}
    for name, (dims, dtype, raw) in g["initializers"].items():
        inits[name] = np.frombuffer(raw, _np_dtype(dtype)).reshape(
            [int(d) for d in dims]).copy()

    ctx = _Ctx(S, inits)
    for name, _shape in g["inputs"]:
        ctx.env[name] = S.Variable(name)
    for name in inits:
        ctx.env[name] = S.Variable(name)

    for node in g["nodes"]:
        imp = _IMPORTERS.get(node["op_type"])
        if imp is None:
            raise MXNetError(
                f"ONNX import: no importer for {node['op_type']!r} "
                f"(node {node['name']!r}); supported: "
                f"{sorted(_IMPORTERS)}")
        out_sym = imp(node, ctx, S)
        outs = node["outputs"]
        if len(outs) == 1:
            ctx.env[outs[0]] = out_sym
        else:
            for i, o in enumerate(outs):
                ctx.env[o] = out_sym[i]

    heads = [ctx.get(name) for name, _ in g["outputs"]]
    sym = heads[0] if len(heads) == 1 else S.Group(heads)

    # only initializers the rebuilt graph actually consumes as inputs
    # become parameters — Reshape shape tensors (folded into attrs) and
    # gamma tensors orphaned by the exporter's fix_gamma substitution must
    # not leak into arg_params as trainable constants
    arg_names = set(sym.list_arguments())
    aux_names = set(sym.list_auxiliary_states())
    arg_params, aux_params = {}, {}
    for name, arr in inits.items():
        if name in aux_names:
            aux_params[name] = NDArray(jnp.asarray(arr))
        elif name in arg_names:
            arg_params[name] = NDArray(jnp.asarray(arr))
    return sym, arg_params, aux_params


def import_to_gluon(onnx_file, ctx=None):
    """ONNX file → a ready-to-run gluon SymbolBlock (reference:
    onnx2mx import_to_gluon)."""
    from ... import symbol as S
    from ...gluon.block import SymbolBlock
    from ...gluon.parameter import Parameter
    sym, arg_params, aux_params = import_model(onnx_file)
    inputs = [v for v in sym.list_arguments() if v not in arg_params]
    params = {}
    for k, v in arg_params.items():
        p = Parameter(k, shape=v.shape)
        p.set_data(v)
        params[k] = p
    for k, v in aux_params.items():
        p = Parameter(k, shape=v.shape, grad_req="null")
        p.set_data(v)
        params[k] = p
    return SymbolBlock(sym, [S.Variable(v) for v in inputs], params=params)
