"""Symbolic operator namespace (reference: mxnet.symbol ops).

Registers pure kernels (shared with ops/nn_ops.py) under stable names so
graphs serialise, and exposes the reference's symbol-level API
(sym.FullyConnected, sym.Activation, ...).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as _np

from ..base import MXNetError, _np_dtype
from ..ops import nn_ops as K
from .symbol import (Symbol, _make, register_aux_slots, register_op,
                     register_shape_rule, register_train_op)

__all__ = ["FullyConnected", "Convolution", "StemConvS2D", "Activation",
           "BatchNorm", "Deconvolution", "InstanceNorm", "GroupNorm", "PReLU",
           "LayerNorm", "Pooling", "Dropout", "Embedding", "softmax",
           "log_softmax", "SoftmaxOutput", "LinearRegressionOutput",
           "MAERegressionOutput", "LogisticRegressionOutput",
           "flatten", "Flatten", "reshape", "Custom", "RNN",
           "slice", "slice_axis",
           "SequenceMask", "SequenceLast", "SequenceReverse",
           "smooth_l1", "softmin", "hard_sigmoid",
           "cast", "Cast", "take",
           "LRN", "L2Normalization", "UpSampling", "BlockGrad",
           "stop_gradient", "MakeLoss", "SliceChannel", "split",
           "transpose", "concat", "Concat", "dot", "batch_dot", "sum", "mean",
           "max", "min", "relu", "sigmoid", "tanh", "exp", "log", "sqrt",
           "square", "negative", "zeros", "ones", "broadcast_add",
           "broadcast_mul", "elemwise_add", "expand_dims", "squeeze",
           "where", "shape_array", "_dynamic_arange", "broadcast_lesser",
           "broadcast_lesser_equal", "broadcast_greater",
           "broadcast_greater_equal"]

# -- elemwise registry -------------------------------------------------------
register_op("elemwise_add", jnp.add)
register_op("elemwise_sub", jnp.subtract)
register_op("elemwise_mul", jnp.multiply)
register_op("elemwise_div", jnp.divide)
register_op("elemwise_pow", jnp.power)
register_op("elemwise_add_scalar", lambda a, scalar: a + scalar)
register_op("elemwise_sub_scalar", lambda a, scalar: a - scalar)
register_op("elemwise_mul_scalar", lambda a, scalar: a * scalar)
register_op("elemwise_div_scalar", lambda a, scalar: a / scalar)
register_op("elemwise_pow_scalar", lambda a, scalar: a ** scalar)
register_op("rsub_scalar", lambda a, scalar: scalar - a)
register_op("rdiv_scalar", lambda a, scalar: scalar / a)
# comparisons return float 0/1 arrays (reference: broadcast_lesser etc.)
register_op("broadcast_lesser",
            lambda a, b: (a < b).astype(jnp.float32))
register_op("broadcast_lesser_equal",
            lambda a, b: (a <= b).astype(jnp.float32))
register_op("broadcast_greater",
            lambda a, b: (a > b).astype(jnp.float32))
register_op("broadcast_greater_equal",
            lambda a, b: (a >= b).astype(jnp.float32))
register_op("broadcast_lesser_scalar",
            lambda a, scalar: (a < scalar).astype(jnp.float32))
register_op("broadcast_lesser_equal_scalar",
            lambda a, scalar: (a <= scalar).astype(jnp.float32))
register_op("broadcast_greater_scalar",
            lambda a, scalar: (a > scalar).astype(jnp.float32))
register_op("broadcast_greater_equal_scalar",
            lambda a, scalar: (a >= scalar).astype(jnp.float32))
register_op("negative", jnp.negative)
register_op("relu", jax.nn.relu)
register_op("sigmoid", jax.nn.sigmoid)
register_op("tanh", jnp.tanh)
register_op("exp", jnp.exp)
register_op("log", jnp.log)
register_op("sqrt", jnp.sqrt)
register_op("square", jnp.square)
def _softmax_kernel(a, *length, axis=-1, use_length=False, causal=False):
    """Softmax with optional masking of the softmax axis (reference:
    softmax(..., use_length=True), src/operator/nn/softmax.cc; the causal
    flag is the attention-export extension). `length` has shape (B,) =
    data's leading dim; positions >= length along the (last) softmax axis
    are excluded. causal=True additionally masks positions past the query
    row (axis -2). -1e9 (not -inf) keeps fully-masked rows finite and
    matches the ONNX export decomposition bit-for-bit."""
    if not length and not causal:
        return jax.nn.softmax(a, axis=axis)
    if axis % a.ndim != a.ndim - 1:
        raise MXNetError("softmax: masking supports the last axis only")
    keep = jnp.ones((), bool)
    idx = jnp.arange(a.shape[-1])
    if length:
        (ln,) = length
        lb = ln.astype(jnp.int32).reshape(
            (ln.shape[0],) + (1,) * (a.ndim - 1))
        keep = keep & (idx < lb)
    if causal:
        rows = jnp.arange(a.shape[-2])[:, None]
        keep = keep & (idx[None, :] <= rows)
    return jax.nn.softmax(jnp.where(keep, a, -1e9), axis=-1)


register_op("softmax", _softmax_kernel)
register_op("log_softmax", lambda a, axis=-1: jax.nn.log_softmax(a, axis=axis))
register_op("sum", lambda a, axis=None, keepdims=False:
            jnp.sum(a, axis=axis, keepdims=keepdims))
register_op("mean", lambda a, axis=None, keepdims=False:
            jnp.mean(a, axis=axis, keepdims=keepdims))
register_op("max", lambda a, axis=None, keepdims=False:
            jnp.max(a, axis=axis, keepdims=keepdims))
register_op("min", lambda a, axis=None, keepdims=False:
            jnp.min(a, axis=axis, keepdims=keepdims))
# reference reshape magic codes (0 = copy input dim) resolved against the
# concrete input shape at execution; -1 passes through to jnp
register_op("reshape", lambda a, shape: a.reshape(
    tuple(a.shape[i] if s == 0 else s for i, s in enumerate(shape))))
register_op("flatten", lambda a: a.reshape(a.shape[0], -1))
register_op("transpose", lambda a, axes=None: jnp.transpose(a, axes))
register_op("expand_dims", lambda a, axis: jnp.expand_dims(a, axis))
register_op("squeeze", lambda a, axis=None: jnp.squeeze(a, axis))
register_op("concat", lambda *xs, dim=1: jnp.concatenate(xs, axis=dim))
register_op("dot", jnp.dot)
register_op("batch_dot", jnp.matmul)
register_op("FullyConnected",
            lambda x, w, *b, no_bias=False, num_hidden=None, flatten=True:
            K.fully_connected(x, w, b[0] if b else None, flatten))
register_op("Convolution",
            lambda x, w, *b, kernel=None, stride=1, pad=0, dilate=1,
            num_filter=None, num_group=1, no_bias=False, layout=None:
            K.convolution(x, w, b[0] if b else None, stride, pad, dilate,
                          num_group, layout))
register_op("Deconvolution",
            lambda x, w, *b, kernel=None, stride=1, pad=0, adj=0,
            num_filter=None, no_bias=False, layout=None:
            K.deconvolution(x, w, b[0] if b else None, stride, pad, adj,
                            layout))
register_op("StemConvS2D",
            lambda x, w, num_filter=None: K.stem_conv_s2d(x, w))
register_op("Activation", lambda x, act_type="relu": K.activation(x, act_type))
def _bn_infer(x, g, b, mm, mv, eps=1e-5, momentum=0.9, axis=1,
              fix_gamma=False, use_global_stats=False):
    if fix_gamma:
        g = jnp.ones_like(g)
    return K.batch_norm(x, g, b, mm, mv, eps, momentum, False, axis)[0]


register_op("BatchNorm", _bn_infer)


def _bn_train_variant(x, g, b, mm, mv, eps=1e-5, momentum=0.9, axis=1,
                      fix_gamma=False, use_global_stats=False, _rng=None):
    """Training BatchNorm: batch stats normalise, moving stats update
    (reference: BN's mutable aux inputs written during the forward).
    use_global_stats freezes the moving stats (fine-tune mode)."""
    if fix_gamma:
        g = jnp.ones_like(g)
    if use_global_stats:
        return K.batch_norm(x, g, b, mm, mv, eps, momentum, False, axis)[0], {}
    y, new_mm, new_mv = K.batch_norm(x, g, b, mm, mv, eps, momentum, True,
                                     axis)
    return y, {3: new_mm, 4: new_mv}


register_train_op("BatchNorm", _bn_train_variant)
register_aux_slots("BatchNorm", {3: "zeros", 4: "ones"})  # mean, var
register_op("LayerNorm", lambda x, g, b, axis=-1, eps=1e-5:
            K.layer_norm(x, g, b, axis, eps))
register_op("InstanceNorm", lambda x, g, b, eps=1e-5:
            K.instance_norm(x, g, b, eps))
register_op("GroupNorm", lambda x, g, b, num_groups=1, eps=1e-5:
            K.group_norm(x, g, b, num_groups, eps))
register_op("PReLU", K.prelu)
register_op("Pooling",
            lambda x, kernel=None, pool_type="max", stride=None, pad=0,
            global_pool=False, layout=None, count_include_pad=True:
            K.global_pooling(x, pool_type, layout or "NCHW") if global_pool
            else K.pooling(x, kernel, pool_type, stride, pad, layout,
                           count_include_pad))
register_op("Dropout", lambda x, p=0.5: x)  # inference: identity


def _dropout_train(x, p=0.5, _rng=None):
    """Inverted dropout for Executor.forward(is_train=True); the key is a
    per-node fold of the step key the Executor draws each forward."""
    if not p or _rng is None:
        return x, {}
    return K.dropout(x, _rng, p), {}


register_train_op("Dropout", _dropout_train)
register_op("Embedding", lambda i, w, input_dim=None, output_dim=None:
            K.embedding(i, w))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _softmax_output_op(x, label, use_ignore, ignore_label, normalization,
                       grad_scale):
    return jax.nn.softmax(x, axis=-1)


def _so_fwd(x, label, use_ignore, ignore_label, normalization, grad_scale):
    p = jax.nn.softmax(x, axis=-1)
    return p, (p, label)


def _so_bwd(use_ignore, ignore_label, normalization, grad_scale, res, g):
    """Loss-head backward (reference: src/operator/softmax_output-inl.h):
    the cotangent is ignored; grad = (p - onehot(label)) * grad_scale,
    with ignore_label rows zeroed when use_ignore (padding positions —
    essential for bucketed LM training), 'valid' dividing by the
    non-ignored label count and 'batch' by the leading dim."""
    p, label = res
    ilab = label.astype(jnp.int32)
    oh = jax.nn.one_hot(ilab, p.shape[-1], dtype=p.dtype)
    grad = (p - oh) * grad_scale
    if use_ignore:
        keep = (ilab != int(ignore_label)).astype(p.dtype)
        grad = grad * keep[..., None]
        valid_cnt = jnp.maximum(keep.sum(), 1.0)
    else:
        valid_cnt = float(int(_np.prod(label.shape)))
    if normalization == "valid":
        grad = grad / valid_cnt
    elif normalization == "batch":
        grad = grad / p.shape[0]
    return (grad, jnp.zeros(label.shape, label.dtype))


_softmax_output_op.defvjp(_so_fwd, _so_bwd)


def _softmax_output_eval(x, *l, use_ignore=False, ignore_label=-1,
                         normalization="null", grad_scale=1.0):
    if not l:
        return jax.nn.softmax(x, axis=-1)
    return _softmax_output_op(x, l[0], bool(use_ignore), int(ignore_label),
                              normalization, float(grad_scale))


register_op("SoftmaxOutput", _softmax_output_eval)


def _regression_output(link, grad_fn):
    """Loss-head factory (reference: src/operator/regression_output-inl.h):
    forward applies the link; backward ignores the incoming cotangent and
    emits grad_fn(pred, label) * grad_scale / num_output, where num_output
    is the per-sample element count — the reference's exact scaling."""

    import functools

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def op(x, label, grad_scale):
        return link(x)

    def fwd(x, label, grad_scale):
        p = link(x)
        return p, (p, label)

    def bwd(grad_scale, res, g):
        p, label = res
        lab = label.reshape(p.shape).astype(p.dtype)
        # NB: plain `max` here would resolve to the symbol-level reduce op
        # this module exports — use the product directly (empty shape -> 1)
        num_output = int(_np.prod(p.shape[1:])) or 1
        return (grad_fn(p, lab) * (grad_scale / num_output),
                jnp.zeros(label.shape, label.dtype))

    op.defvjp(fwd, bwd)
    return lambda x, *l, grad_scale=1.0: (
        op(x, l[0], float(grad_scale)) if l else link(x))


register_op("LinearRegressionOutput",
            _regression_output(lambda x: x, lambda p, y: p - y))
register_op("MAERegressionOutput",
            _regression_output(lambda x: x, lambda p, y: jnp.sign(p - y)))
register_op("LogisticRegressionOutput",
            _regression_output(jax.nn.sigmoid, lambda p, y: p - y))
register_op("zeros", lambda shape=(), dtype=None: jnp.zeros(shape, dtype))
register_op("ones", lambda shape=(), dtype=None: jnp.ones(shape, dtype))


# -- parameter shape-inference rules (reference: per-op nnvm InferShape) ----
def _fc_shapes(ins, attrs):
    data = ins[0]
    if data is None:
        return ins
    nh = attrs.get("num_hidden")
    in_f = int(_np.prod(data[1:])) if attrs.get("flatten", True) else data[-1]
    out = [data, (nh, in_f)]
    if len(ins) == 3:
        out.append((nh,))
    return out


def _convlike_shapes(ins, attrs, weight_shape):
    """Shared data->weight/bias fill for conv-family ops;
    weight_shape(num_filter, in_c, groups, kernel, channel_first)."""
    data = ins[0]
    if data is None:
        return ins
    layout = attrs.get("layout") or {3: "NCW", 4: "NCHW",
                                     5: "NCDHW"}[len(data)]
    c = data[layout.index("C")]
    k = attrs.get("kernel")
    k = (k,) * (len(data) - 2) if isinstance(k, int) else tuple(k)
    nf, g = attrs.get("num_filter"), attrs.get("num_group", 1)
    out = [data, weight_shape(nf, c, g, k, layout.index("C") == 1)]
    if len(ins) == 3:
        out.append((nf,))
    return out


def _conv_shapes(ins, attrs):
    return _convlike_shapes(
        ins, attrs,
        lambda nf, c, g, k, cf: (nf, c // g) + k if cf
        else (nf,) + k + (c // g,))


def _norm_shapes(ins, attrs):
    data = ins[0]
    if data is None:
        return ins
    c = data[attrs.get("axis", 1) if len(data) > 1 else 0]
    return [data] + [(c,)] * (len(ins) - 1)


def _ln_shapes(ins, attrs):
    data = ins[0]
    if data is None:
        return ins
    return [data] + [(data[attrs.get("axis", -1)],)] * (len(ins) - 1)


def _embed_shapes(ins, attrs):
    return [ins[0], (attrs.get("input_dim"), attrs.get("output_dim"))]


register_shape_rule("FullyConnected", _fc_shapes)
def _deconv_shapes(ins, attrs):
    # transposed conv weight is (I, O/g, *k) in every layout (the rhs
    # spec is "IO"+spatial — see K.deconvolution)
    return _convlike_shapes(
        ins, attrs, lambda nf, c, g, k, cf: (c, nf // g) + k)


register_shape_rule("Convolution", _conv_shapes)
register_shape_rule("Deconvolution", _deconv_shapes)
register_shape_rule("StemConvS2D",
                    lambda ins, attrs: ins if ins[0] is None
                    else [ins[0], (attrs["num_filter"], 7, 7, ins[0][3])])
register_shape_rule("BatchNorm", _norm_shapes)
register_shape_rule("LayerNorm", _ln_shapes)


def _chan1_shapes(ins, attrs):
    data = ins[0]
    if data is None:
        return ins
    c = data[1] if len(data) > 1 else data[0]
    return [data] + [(c,)] * (len(ins) - 1)


register_shape_rule("InstanceNorm", _chan1_shapes)
register_shape_rule("GroupNorm", _chan1_shapes)
register_shape_rule("PReLU", _chan1_shapes)
register_shape_rule("Embedding", _embed_shapes)


# -- symbol-level API --------------------------------------------------------
def FullyConnected(data, weight=None, bias=None, num_hidden=None,
                   no_bias=False, flatten=True, name=None, **kwargs):
    ins = [data, weight] + ([] if no_bias else [bias])
    return _make("FullyConnected", ins,
                 {"no_bias": no_bias, "num_hidden": num_hidden,
                  "flatten": flatten}, name=name,
                 input_names=["data", "weight", "bias"])


def StemConvS2D(data, weight=None, num_filter=None, name=None, **kwargs):
    return _make("StemConvS2D", [data, weight], {"num_filter": num_filter},
                 name=name, input_names=["data", "weight"])


def Deconvolution(data, weight=None, bias=None, kernel=None, stride=1,
                  pad=0, adj=0, num_filter=None, no_bias=False, layout=None,
                  name=None, **kwargs):
    ins = [data, weight] + ([] if no_bias else [bias])
    return _make("Deconvolution", ins,
                 {"kernel": kernel, "stride": stride, "pad": pad,
                  "adj": adj, "num_filter": num_filter, "no_bias": no_bias,
                  "layout": layout}, name=name,
                 input_names=["data", "weight", "bias"])


def Convolution(data, weight=None, bias=None, kernel=None, stride=1, pad=0,
                dilate=1, num_filter=None, num_group=1, no_bias=False,
                layout=None, name=None, **kwargs):
    ins = [data, weight] + ([] if no_bias else [bias])
    return _make("Convolution", ins,
                 {"kernel": kernel, "stride": stride, "pad": pad,
                  "dilate": dilate, "num_filter": num_filter,
                  "num_group": num_group, "no_bias": no_bias,
                  "layout": layout}, name=name,
                 input_names=["data", "weight", "bias"])


def Activation(data, act_type="relu", name=None, **kwargs):
    return _make("Activation", [data], {"act_type": act_type}, name=name)


def BatchNorm(data, gamma=None, beta=None, moving_mean=None, moving_var=None,
              eps=1e-5, momentum=0.9, axis=1, fix_gamma=True,
              use_global_stats=False, name=None, **kwargs):
    """fix_gamma defaults True, matching the reference op (gamma pinned to
    1 unless explicitly released); gluon.nn.BatchNorm trains gamma via
    scale=True, also matching the reference Gluon layer."""
    return _make("BatchNorm", [data, gamma, beta, moving_mean, moving_var],
                 {"eps": eps, "momentum": momentum, "axis": axis,
                  "fix_gamma": fix_gamma,
                  "use_global_stats": use_global_stats}, name=name,
                 input_names=["data", "gamma", "beta", "moving_mean",
                              "moving_var"])


def LayerNorm(data, gamma=None, beta=None, axis=-1, eps=1e-5, name=None,
              **kwargs):
    return _make("LayerNorm", [data, gamma, beta],
                 {"axis": axis, "eps": eps}, name=name,
                 input_names=["data", "gamma", "beta"])


def InstanceNorm(data, gamma=None, beta=None, eps=1e-5, name=None, **kwargs):
    return _make("InstanceNorm", [data, gamma, beta], {"eps": eps},
                 name=name, input_names=["data", "gamma", "beta"])


def GroupNorm(data, gamma=None, beta=None, num_groups=1, eps=1e-5,
              name=None, **kwargs):
    return _make("GroupNorm", [data, gamma, beta],
                 {"num_groups": num_groups, "eps": eps}, name=name,
                 input_names=["data", "gamma", "beta"])


def PReLU(data, alpha=None, name=None, **kwargs):
    return _make("PReLU", [data, alpha], {}, name=name,
                 input_names=["data", "alpha"])


def Pooling(data, kernel=None, pool_type="max", stride=None, pad=0,
            global_pool=False, layout=None, count_include_pad=True,
            name=None, **kwargs):
    return _make("Pooling", [data],
                 {"kernel": kernel, "pool_type": pool_type, "stride": stride,
                  "pad": pad, "global_pool": global_pool, "layout": layout,
                  "count_include_pad": count_include_pad},
                 name=name)


def Dropout(data, p=0.5, name=None, **kwargs):
    return _make("Dropout", [data], {"p": p}, name=name)


def Embedding(data, weight=None, input_dim=None, output_dim=None, name=None,
              **kwargs):
    return _make("Embedding", [data, weight],
                 {"input_dim": input_dim, "output_dim": output_dim},
                 name=name, input_names=["data", "weight"])


def SoftmaxOutput(data, label=None, use_ignore=False, ignore_label=-1,
                  normalization="null", grad_scale=1.0, name=None,
                  **kwargs):
    if normalization not in ("null", "valid", "batch"):
        raise MXNetError(f"SoftmaxOutput normalization must be "
                         f"null/valid/batch, got {normalization!r}")
    ins = [data] if label is None else [data, label]
    return _make("SoftmaxOutput", ins,
                 {"use_ignore": use_ignore, "ignore_label": ignore_label,
                  "normalization": normalization,
                  "grad_scale": grad_scale}, name=name)


def LinearRegressionOutput(data, label=None, grad_scale=1.0, name=None,
                           **kwargs):
    ins = [data] if label is None else [data, label]
    return _make("LinearRegressionOutput", ins,
                 {"grad_scale": grad_scale}, name=name)


def MAERegressionOutput(data, label=None, grad_scale=1.0, name=None,
                        **kwargs):
    ins = [data] if label is None else [data, label]
    return _make("MAERegressionOutput", ins,
                 {"grad_scale": grad_scale}, name=name)


def LogisticRegressionOutput(data, label=None, grad_scale=1.0, name=None,
                             **kwargs):
    ins = [data] if label is None else [data, label]
    return _make("LogisticRegressionOutput", ins,
                 {"grad_scale": grad_scale}, name=name)


def softmax(data, length=None, axis=-1, use_length=False, causal=False,
            name=None):
    if length is not None or use_length:
        if length is None:
            raise MXNetError("softmax: use_length=True needs a length input")
        return _make("softmax", [data, length],
                     {"axis": axis, "use_length": True, "causal": causal},
                     name=name)
    if causal:
        return _make("softmax", [data], {"axis": axis, "causal": True},
                     name=name)
    return _make("softmax", [data], {"axis": axis}, name=name)


def log_softmax(data, axis=-1, name=None):
    return _make("log_softmax", [data], {"axis": axis}, name=name)


def flatten(data, name=None, **kwargs):
    return _make("flatten", [data], {}, name=name)


Flatten = flatten


def reshape(data, shape, name=None, **kwargs):
    return _make("reshape", [data], {"shape": tuple(shape)}, name=name)


def transpose(data, axes=None, name=None):
    return _make("transpose", [data], {"axes": axes}, name=name)


def concat(*data, dim=1, name=None, **kwargs):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return _make("concat", list(data), {"dim": dim}, name=name)


Concat = concat


def dot(lhs, rhs, name=None, **kwargs):
    return _make("dot", [lhs, rhs], {}, name=name)


def batch_dot(lhs, rhs, name=None, **kwargs):
    return _make("batch_dot", [lhs, rhs], {}, name=name)


def sum(data, axis=None, keepdims=False, name=None):
    return _make("sum", [data], {"axis": axis, "keepdims": keepdims}, name=name)


def mean(data, axis=None, keepdims=False, name=None):
    return _make("mean", [data], {"axis": axis, "keepdims": keepdims},
                 name=name)


def max(data, axis=None, keepdims=False, name=None):
    return _make("max", [data], {"axis": axis, "keepdims": keepdims}, name=name)


def min(data, axis=None, keepdims=False, name=None):
    return _make("min", [data], {"axis": axis, "keepdims": keepdims}, name=name)


def _slice_kernel(a, begin=(), end=(), step=None):
    import builtins
    step = step or [None] * len(begin)
    # builtins.slice: the symbolic `slice` op shadows the name below
    idx = tuple(builtins.slice(b, e, s)
                for b, e, s in zip(begin, end, step))
    return a[idx]


register_op("slice", _slice_kernel)
register_op("slice_axis",
            lambda a, axis=0, begin=0, end=None:
            jax.lax.slice_in_dim(a, begin, a.shape[axis] if end is None
                                 else (end if end >= 0
                                       else a.shape[axis] + end),
                                 axis=axis))


def slice(data, begin, end, step=None, name=None):  # noqa: A001
    return _make("slice", [data],
                 {"begin": tuple(begin), "end": tuple(end),
                  "step": tuple(step) if step else None}, name=name)


def slice_axis(data, axis, begin, end, name=None):
    return _make("slice_axis", [data],
                 {"axis": axis, "begin": begin, "end": end}, name=name)


def expand_dims(data, axis, name=None):
    return _make("expand_dims", [data], {"axis": axis}, name=name)


def squeeze(data, axis=None, name=None):
    return _make("squeeze", [data], {"axis": axis}, name=name)


def broadcast_add(lhs, rhs, name=None):
    return _make("elemwise_add", [lhs, rhs], {}, name=name)


def broadcast_mul(lhs, rhs, name=None):
    return _make("elemwise_mul", [lhs, rhs], {}, name=name)


def _broadcast_cmp(opname):
    def f(lhs, rhs, name=None):
        return _make(opname, [lhs, rhs], {}, name=name)
    f.__name__ = opname
    return f


broadcast_lesser = _broadcast_cmp("broadcast_lesser")
broadcast_lesser_equal = _broadcast_cmp("broadcast_lesser_equal")
broadcast_greater = _broadcast_cmp("broadcast_greater")
broadcast_greater_equal = _broadcast_cmp("broadcast_greater_equal")


elemwise_add = broadcast_add


def _unary(opname):
    def f(data, name=None, **kwargs):
        return _make(opname, [data], {}, name=name)
    f.__name__ = opname
    return f


relu = _unary("relu")
sigmoid = _unary("sigmoid")
tanh = _unary("tanh")
exp = _unary("exp")
log = _unary("log")
sqrt = _unary("sqrt")
square = _unary("square")
negative = _unary("negative")


def zeros(shape, dtype=None, name=None, **kwargs):
    return _make("zeros", [], {"shape": tuple(shape), "dtype": dtype},
                 name=name)


def ones(shape, dtype=None, name=None, **kwargs):
    return _make("ones", [], {"shape": tuple(shape), "dtype": dtype},
                 name=name)


# -- custom ops in symbol graphs (reference: mx.sym.Custom / custom.cc) -----
def _custom_eval(*args, _train=False, op_type=None, **prop_kwargs):
    from ..operator import _build_custom_fn
    in_shapes = [tuple(a.shape) for a in args]
    fn, _, _ = _build_custom_fn(op_type, prop_kwargs, in_shapes,
                                train=_train)
    return fn(*args)


register_op("_custom", _custom_eval)
register_train_op(
    "_custom",
    lambda *args, _rng=None, **kw: (_custom_eval(*args, _train=True, **kw),
                                    {}))


def _custom_shapes(ins, attrs):
    """Let CustomOpProp.infer_shape fill unknown input shapes (reference:
    custom-op shape inference completes weight shapes). The prop receives
    the partially-known list (None for unknowns) and returns the
    completed input shapes as its first element. An unregistered op_type
    propagates (loading a graph requires re-registering its custom ops);
    only a prop that cannot handle partial shapes falls back."""
    from ..operator import get as _get_custom
    kw = {k: v for k, v in attrs.items() if k != "op_type"}
    prop = _get_custom(attrs["op_type"])(**kw)  # raises if unregistered
    try:
        return list(prop.infer_shape(list(ins))[0])
    except (TypeError, ValueError, AttributeError, IndexError):
        return ins  # prop cannot handle partial shapes: leave unknown


register_shape_rule("_custom", _custom_shapes)


def Custom(*inputs, op_type=None, name=None, **prop_kwargs):
    """Place a registered CustomOp in a symbol graph (reference:
    mx.sym.Custom). Shapes/arity come from the registered CustomOpProp;
    attrs are plain JSON values, so the graph round-trips through
    symbol.json (the op must be registered again at load time, like the
    reference)."""
    from ..operator import _prop_for
    prop = _prop_for(op_type, prop_kwargs, len(inputs))
    return _make("_custom", list(inputs),
                 {"op_type": op_type, **prop_kwargs}, name=name,
                 n_out=len(prop.list_outputs()))


# -- classic extra ops (reference: lrn.cc, l2_normalization.cc, ...) --------
from ..ops import extra_ops as _extra

register_op("LRN", lambda x, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5:
            _extra.lrn_k(x, alpha, beta, knorm, nsize))
register_op("L2Normalization", lambda x, eps=1e-10, mode="instance":
            _extra.l2_normalization_k(x, eps, mode))
register_op("UpSampling", lambda x, scale=2, sample_type="nearest",
            num_filter=0: _extra.upsampling_k(x, scale, sample_type))
register_op("BlockGrad", jax.lax.stop_gradient)
register_op("MakeLoss", lambda x, grad_scale=1.0:
            _extra.make_loss_k(x, grad_scale))
register_op("SliceChannel",
            lambda x, num_outputs=1, axis=1, squeeze_axis=False:
            tuple(jnp.squeeze(p, axis=axis) if squeeze_axis else p
                  for p in jnp.split(x, num_outputs, axis=axis)))


def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, name=None):
    return _make("LRN", [data], {"alpha": alpha, "beta": beta,
                                 "knorm": knorm, "nsize": nsize}, name=name)


def L2Normalization(data, eps=1e-10, mode="instance", name=None):
    return _make("L2Normalization", [data], {"eps": eps, "mode": mode},
                 name=name)


def UpSampling(data, scale=2, sample_type="nearest", num_filter=0,
               name=None, **kwargs):
    return _make("UpSampling", [data],
                 {"scale": scale, "sample_type": sample_type}, name=name)


def BlockGrad(data, name=None):
    return _make("BlockGrad", [data], {}, name=name)


stop_gradient = BlockGrad


def MakeLoss(data, grad_scale=1.0, name=None, **kwargs):
    return _make("MakeLoss", [data], {"grad_scale": grad_scale}, name=name)


def SliceChannel(data, num_outputs=1, axis=1, squeeze_axis=False,
                 name=None):
    return _make("SliceChannel", [data],
                 {"num_outputs": num_outputs, "axis": axis,
                  "squeeze_axis": squeeze_axis}, name=name,
                 n_out=num_outputs)


split = SliceChannel


# -- cast / indexing (reference: tensor cast + take ops) --------------------
register_op("cast", lambda x, dtype="float32": x.astype(dtype))
def _take_kernel(a, *maybe_idx, axis=0, mode="clip", indices=None):
    # `indices` as an ATTR (no second input) keeps the gather concrete
    # when `a` is itself concrete (numpy) under jit tracing — the ONNX
    # importer inlines constant indices this way so Shape->Gather->Range
    # mask chains fold at trace time instead of failing on a traced arange
    m = {"clip": "clip", "wrap": "wrap"}.get(mode, "clip")
    if not maybe_idx and isinstance(a, _np.ndarray):
        return _np.take(a, _np.asarray(indices), axis=axis, mode=m)
    idx = maybe_idx[0] if maybe_idx else jnp.asarray(indices)
    if hasattr(idx, "astype"):
        idx = idx.astype(jnp.int32)
    return jnp.take(a, idx, axis=axis, mode=m)


register_op("take", _take_kernel)
register_op("abs", jnp.abs)


def cast(data, dtype="float32", name=None):
    return _make("cast", [data], {"dtype": dtype}, name=name)


Cast = cast


def take(a, indices, axis=0, mode="clip", name=None):
    return _make("take", [a, indices], {"axis": axis, "mode": mode},
                 name=name)


# -- sequence ops (reference: src/operator/sequence_*.cc) -------------------
from ..ops import seq_ops as _seq

register_op("SequenceMask",
            lambda *ins, use_sequence_length=False, value=0.0, axis=0:
            _seq.sequence_mask_k(ins[0],
                                 ins[1] if use_sequence_length else None,
                                 value=value, axis=axis))
register_op("SequenceLast",
            lambda *ins, use_sequence_length=False, axis=0:
            _seq.sequence_last_k(ins[0],
                                 ins[1] if use_sequence_length else None,
                                 axis=axis))
register_op("SequenceReverse",
            lambda *ins, use_sequence_length=False, axis=0:
            _seq.sequence_reverse_k(ins[0],
                                    ins[1] if use_sequence_length else None,
                                    axis=axis))
register_op("smooth_l1",
            lambda x, scalar=1.0: _seq.smooth_l1_k(x, scalar=scalar))
register_op("softmin", lambda x, axis=-1: _seq.softmin_k(x, axis=axis))
register_op("hard_sigmoid",
            lambda x, alpha=0.2, beta=0.5:
            _seq.hard_sigmoid_k(x, alpha=alpha, beta=beta))


def _seq_inputs(data, sequence_length, use_sequence_length):
    try:
        return _seq._seq_args(data, sequence_length, use_sequence_length)
    except ValueError as e:
        raise MXNetError(str(e)) from None


def SequenceMask(data, sequence_length=None, use_sequence_length=False,
                 value=0.0, axis=0, name=None):
    return _make("SequenceMask",
                 _seq_inputs(data, sequence_length, use_sequence_length),
                 {"use_sequence_length": use_sequence_length,
                  "value": value, "axis": axis}, name=name)


def SequenceLast(data, sequence_length=None, use_sequence_length=False,
                 axis=0, name=None):
    return _make("SequenceLast",
                 _seq_inputs(data, sequence_length, use_sequence_length),
                 {"use_sequence_length": use_sequence_length, "axis": axis},
                 name=name)


def SequenceReverse(data, sequence_length=None, use_sequence_length=False,
                    axis=0, name=None):
    return _make("SequenceReverse",
                 _seq_inputs(data, sequence_length, use_sequence_length),
                 {"use_sequence_length": use_sequence_length, "axis": axis},
                 name=name)


def smooth_l1(data, scalar=1.0, name=None):
    return _make("smooth_l1", [data], {"scalar": scalar}, name=name)


def softmin(data, axis=-1, name=None):
    return _make("softmin", [data], {"axis": axis}, name=name)


def hard_sigmoid(data, alpha=0.2, beta=0.5, name=None):
    return _make("hard_sigmoid", [data], {"alpha": alpha, "beta": beta},
                 name=name)


# -- fused RNN layers as one symbol node (reference: sym.RNN / rnn-inl.h) ---
def _rnn_eval(x, *rest, mode="lstm", num_layers=1, num_dir=1,
              hidden_size=0, layout_ntc=False, pnames=(),
              state_outputs=False, use_sequence_length=False, dropout=0.0,
              _rng=None):
    from ..gluon.rnn.rnn_layer import rnn_forward
    ns = 2 if mode == "lstm" else 1
    seq_len = None
    if use_sequence_length:
        seq_len, rest = rest[0], rest[1:]
    if state_outputs:
        svals, pvals = rest[:ns], rest[ns:]
    else:
        batch = x.shape[0] if layout_ntc else x.shape[1]
        zero = jnp.zeros((num_layers * num_dir, batch, hidden_size),
                         x.dtype)
        svals, pvals = (zero,) * ns, rest
    return rnn_forward(mode, num_layers, num_dir, layout_ntc, pnames,
                       x, svals, pvals, dropout=dropout, rng=_rng,
                       seq_len=seq_len)


register_op("RNN", _rnn_eval)
# training: inter-layer dropout keyed off the Executor's step rng
register_train_op("RNN", lambda *a, _rng=None, **kw:
                  (_rnn_eval(*a, _rng=_rng, **kw), {}))


def _rnn_shapes(ins, attrs):
    data = ins[0]
    if data is None:
        return ins
    mode = attrs.get("mode", "lstm")
    L, D = attrs.get("num_layers", 1), attrs.get("num_dir", 1)
    H = attrs.get("hidden_size")
    g = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]
    ns = (2 if mode == "lstm" else 1) if attrs.get("state_outputs") else 0
    batch = data[0] if attrs.get("layout_ntc") else data[1]
    in_size = data[-1]
    out = [data] + \
        ([(batch,)] if attrs.get("use_sequence_length") else []) + \
        [(L * D, batch, H)] * ns
    for name in attrs.get("pnames", ()):
        layer = int(name.split("_")[0][1:])
        if name.endswith("i2h_weight"):
            out.append((g * H, in_size if layer == 0 else H * D))
        elif name.endswith("h2h_weight"):
            out.append((g * H, H))
        else:
            out.append((g * H,))
    return out


register_shape_rule("RNN", _rnn_shapes)


def RNN(data, *state_and_params, mode="lstm", num_layers=1, num_dir=1,
        hidden_size=0, layout_ntc=False, pnames=(), state_outputs=False,
        use_sequence_length=False, dropout=0.0, name=None):
    """Fused multi-layer (bi)RNN node (reference: mx.sym.RNN): one lax.scan
    stack per layer/direction compiled inside the Executor's program. With
    use_sequence_length=True the first extra input (after data) is the (N,)
    sequence_length vector (reference rnn-inl.h variable-length path)."""
    ns = (2 if mode == "lstm" else 1)
    return _make("RNN", [data] + list(state_and_params),
                 {"mode": mode, "num_layers": num_layers,
                  "num_dir": num_dir, "hidden_size": hidden_size,
                  "layout_ntc": layout_ntc, "pnames": tuple(pnames),
                  "state_outputs": state_outputs,
                  "use_sequence_length": use_sequence_length,
                  "dropout": dropout},
                 name=name, n_out=1 + ns)


# --------------------------------------------------------------------------
# dynamic-shape helpers (reference: mx.sym.shape_array, mx.sym.where —
# src/operator/tensor/elemwise_unary_op_basic.cc, control_flow_op.cc).
# These also let the ONNX importer rebuild the exporter's dynamic
# attention-mask idiom (Shape -> Range -> Less -> Where) eagerly.
# NUMPY output on purpose: a shape is static under jit, and keeping the
# value out of jnp (which lifts constants into tracers at trace time)
# lets Shape->Gather->Range chains fold to Python ints — the ONNX
# importer's dynamic attention mask relies on this
# zero initial RNN state derived from a graph tensor: 0 in `shape`
# marks the batch dim, filled from the like-input's leading axis at
# trace time (the legacy rnn_cell.begin_state path — upstream uses
# sym.zeros with shape=(0, H) and nnvm back-infers the 0; our executor
# traces concrete shapes, so the batch rides the graph instead)
register_op("_rnn_zero_state",
            lambda x, shape=(), batch_axis=0: jnp.zeros(
                tuple(x.shape[batch_axis] if s == 0 else s for s in shape),
                x.dtype))
register_op("_rnn_ones_like", jnp.ones_like)

register_op("shape_array", lambda a: _np.asarray(a.shape, _np.int32))
register_op("where", lambda c, a, b: jnp.where(c != 0, a, b))
# arange whose limit arrives as a (scalar) graph INPUT, not an attr.
# Executable when the limit is concrete: eagerly, or under jit when it
# folds from static shapes (shape_array output is concrete at trace
# time); a genuinely data-dependent limit is a dynamic shape and raises.
register_op("_dynamic_arange",
            lambda l, start=0, delta=1:
            jnp.arange(int(start), int(_np.asarray(l).reshape(-1)[0]),
                       int(delta)))


def shape_array(data, name=None):
    return _make("shape_array", [data], {}, name=name)


def where(condition, x, y, name=None):
    return _make("where", [condition, x, y], {}, name=name)


def _dynamic_arange(limit, start=0, delta=1, name=None):
    return _make("_dynamic_arange", [limit],
                 {"start": start, "delta": delta}, name=name)


# -- indexing/selection mirrors of the nd surface (probe
# gaps): one_hot, topk, pick, gather_nd, slice_like,
# broadcast_axis, masked_softmax, SVMOutput -------------------------------
def _one_hot_eval(idx, depth=0, on_value=1.0, off_value=0.0,
                  dtype=None):
    oh = jax.nn.one_hot(idx.astype(jnp.int32), int(depth))
    out = oh * (on_value - off_value) + off_value
    return out.astype(_np_dtype(dtype) if dtype else jnp.float32)


register_op("one_hot", _one_hot_eval)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype=None,
            name=None):
    return _make("one_hot", [indices],
                 {"depth": int(depth), "on_value": on_value,
                  "off_value": off_value, "dtype": dtype}, name=name)


def _topk_eval(x, k=1, axis=-1, ret_typ="indices", is_ascend=False):
    if ret_typ not in ("indices", "value", "both", "mask"):
        raise MXNetError(f"topk: unknown ret_typ {ret_typ!r}")
    v = -x if not is_ascend else x
    vals, idx = jax.lax.top_k(jnp.moveaxis(-v, axis, -1), int(k))
    # lax.top_k takes the LARGEST of (-v) = smallest of v when ascending
    if ret_typ == "mask":
        # same-shape 0/1 mask of the selected entries (reference mode)
        moved = jnp.moveaxis(x, axis, -1)
        mask = jnp.zeros_like(moved).at[
            (*jnp.indices(idx.shape[:-1], sparse=True), idx)].set(1.0)
        return jnp.moveaxis(mask, -1, axis)
    vals = jnp.moveaxis(vals if not is_ascend else -vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx.astype(jnp.float32)
    return idx.astype(jnp.float32)  # reference returns float indices


register_op("topk", _topk_eval)


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False,
         name=None):
    return _make("topk", [data],
                 {"k": int(k), "axis": axis, "ret_typ": ret_typ,
                  "is_ascend": bool(is_ascend)}, name=name,
                 n_out=2 if ret_typ == "both" else 1)


register_op("pick",
            lambda x, i, axis=-1, keepdims=False:
            (jnp.take_along_axis(x, i.astype(jnp.int32)[..., None]
                                 if i.ndim == x.ndim - 1 else
                                 i.astype(jnp.int32), axis)
             if keepdims else
             jnp.squeeze(jnp.take_along_axis(
                 x, i.astype(jnp.int32)[..., None]
                 if i.ndim == x.ndim - 1 else i.astype(jnp.int32),
                 axis), axis)))


def pick(data, index, axis=-1, keepdims=False, name=None):
    return _make("pick", [data, index],
                 {"axis": axis, "keepdims": bool(keepdims)}, name=name)


register_op("gather_nd",
            lambda a, i: a[tuple(i.astype(jnp.int32))])


def gather_nd(data, indices, name=None):
    return _make("gather_nd", [data, indices], {}, name=name)


def _slice_like_eval(a, b, axes=None):
    import builtins
    axes_ = axes if axes else tuple(range(b.ndim))
    idx = [builtins.slice(None)] * a.ndim
    for ax in axes_:
        idx[ax] = builtins.slice(0, b.shape[ax])
    return a[tuple(idx)]


register_op("slice_like", _slice_like_eval)


def slice_like(data, shape_like, axes=None, name=None):
    return _make("slice_like", [data, shape_like],
                 {"axes": tuple(axes) if axes else None}, name=name)


def _broadcast_axis_eval(a, axis=0, size=1):
    axes = axis if isinstance(axis, (list, tuple)) else [axis]
    sizes = size if isinstance(size, (list, tuple)) else [size]
    shape = list(a.shape)
    for ax, s in zip(axes, sizes):
        shape[ax] = s
    return jnp.broadcast_to(a, tuple(shape))


register_op("broadcast_axis", _broadcast_axis_eval)


def broadcast_axis(data, axis=0, size=1, name=None):
    return _make("broadcast_axis", [data],
                 {"axis": axis, "size": size}, name=name)


from ..ops.tensor_ops import masked_softmax_k as _masked_softmax_k

register_op("masked_softmax", _masked_softmax_k)


def masked_softmax(data, mask, axis=-1, temperature=1.0, name=None):
    """reference: masked_softmax (softmax.cc) — masked-off positions get
    exactly 0 probability."""
    return _make("masked_softmax", [data, mask],
                 {"axis": axis, "temperature": temperature}, name=name)


from ..ops.compat_ops import svm_output_k as _svm_k

register_op("SVMOutput", lambda x, y=None, margin=1.0,
            regularization_coefficient=1.0, use_linear=False:
            x if y is None else _svm_k(
                x, y, margin, regularization_coefficient, use_linear))


def SVMOutput(data, label=None, margin=1.0,
              regularization_coefficient=1.0, use_linear=False,
              name=None, **kw):
    """reference: svm_output.cc — identity forward, hinge-loss backward."""
    ins = [data] if label is None else [data, label]
    return _make("SVMOutput", ins,
                 {"margin": margin,
                  "regularization_coefficient": regularization_coefficient,
                  "use_linear": use_linear}, name=name)


__all__ += ["one_hot", "topk", "pick", "gather_nd", "slice_like",
            "broadcast_axis", "masked_softmax", "SVMOutput"]


# -- classic spatial extra ops, sym side (wave 4: upstream registers
# these under both namespaces; nd side lives in ops/extra_ops.py) ---------
from ..ops import extra_ops as _xtra

from ..ops.tensor_ops import functools_reduce as _fold_add

register_op("add_n", lambda *xs: _fold_add(xs))   # one n-ary-add impl
register_op("Crop",
            lambda x, *like, h_w=None, offset=(0, 0), center_crop=False:
            _xtra.crop_k(x, like_shape=like[0].shape, offset=offset,
                         center_crop=center_crop) if like else
            _xtra.crop_k(x, h_w=h_w, offset=offset,
                         center_crop=center_crop))
register_op("ROIPooling",
            lambda x, rois, pooled_size=(7, 7), spatial_scale=1.0:
            _xtra.roi_pooling_k(x, rois, tuple(pooled_size),
                                spatial_scale))
register_op("GridGenerator",
            lambda a, target_shape=None:
            _xtra.grid_generator_k(a, tuple(target_shape)))
register_op("BilinearSampler", _xtra.bilinear_sampler_k)
register_op("SpatialTransformer",
            lambda x, a, target_shape=None:
            _xtra.spatial_transformer_k(x, a, tuple(target_shape)))
register_op("Correlation",
            lambda a, b, kernel_size=1, max_displacement=4, stride1=1,
            stride2=1, is_multiply=True:
            _xtra.correlation_k(a, b, kernel_size=kernel_size,
                                max_displacement=max_displacement,
                                stride1=stride1, stride2=stride2,
                                is_multiply=is_multiply))

from ..ops.compat_ops import _im2col_fn as _im2col_k
from ..ops.compat_ops import _norm2 as _normN


def _im2col_eval(x, kernel=None, stride=1, dilate=1, pad=0):
    nsp = x.ndim - 2          # spatial dims from the DATA, like nd side
    return _im2col_k(x, _normN(kernel, nsp), _normN(stride, nsp),
                     _normN(dilate, nsp), _normN(pad, nsp))


register_op("im2col", _im2col_eval)


def add_n(*args, name=None):
    return _make("add_n", list(args), {}, name=name)


def Crop(data, crop_like=None, h_w=None, offset=(0, 0),
         center_crop=False, name=None, **kw):
    if crop_like is None and h_w is None:
        raise MXNetError("Crop: need crop_like or h_w")
    ins = [data] + ([crop_like] if crop_like is not None else [])
    return _make("Crop", ins,
                 {"h_w": h_w, "offset": tuple(offset),
                  "center_crop": center_crop}, name=name)


def ROIPooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
               name=None, **kw):
    return _make("ROIPooling", [data, rois],
                 {"pooled_size": tuple(pooled_size),
                  "spatial_scale": spatial_scale}, name=name)


def GridGenerator(data, transform_type="affine", target_shape=None,
                  name=None, **kw):
    if transform_type != "affine":
        raise MXNetError("GridGenerator: only affine mode")
    if target_shape is None:
        raise MXNetError("GridGenerator: target_shape is required")
    return _make("GridGenerator", [data],
                 {"target_shape": tuple(target_shape)}, name=name)


def BilinearSampler(data, grid, name=None, **kw):
    return _make("BilinearSampler", [data, grid], {}, name=name)


def SpatialTransformer(data, loc, target_shape=None,
                       transform_type="affine",
                       sampler_type="bilinear", name=None, **kw):
    if transform_type != "affine" or sampler_type != "bilinear":
        raise MXNetError("SpatialTransformer: affine+bilinear only")
    if target_shape is None:
        raise MXNetError("SpatialTransformer: target_shape is required")
    return _make("SpatialTransformer", [data, loc],
                 {"target_shape": tuple(target_shape)}, name=name)


def Correlation(data1, data2, kernel_size=1, max_displacement=4,
                stride1=1, stride2=1, is_multiply=True, name=None, **kw):
    return _make("Correlation", [data1, data2],
                 {"kernel_size": kernel_size,
                  "max_displacement": max_displacement, "stride1": stride1,
                  "stride2": stride2, "is_multiply": is_multiply},
                 name=name)


def im2col(data, kernel, stride=1, dilate=1, pad=0, name=None, **kw):
    return _make("im2col", [data],
                 {"kernel": kernel if isinstance(kernel, int)
                  else tuple(kernel), "stride": stride,
                  "dilate": dilate, "pad": pad}, name=name)


__all__ += ["add_n", "Crop", "ROIPooling", "GridGenerator",
            "BilinearSampler", "SpatialTransformer", "Correlation",
            "im2col"]


register_op("ones_like", jnp.ones_like)
register_op("zeros_like", jnp.zeros_like)
register_op("full", lambda shape=(), val=0.0, dtype=None:
            jnp.full(tuple(shape), val,
                     _np_dtype(dtype) if dtype else jnp.float32))


def ones_like(data, name=None):
    return _make("ones_like", [data], {}, name=name)


def zeros_like(data, name=None):
    return _make("zeros_like", [data], {}, name=name)


def full(shape, val, dtype=None, name=None, **kw):
    return _make("full", [], {"shape": tuple(shape), "val": val,
                              "dtype": dtype}, name=name)


__all__ += ["ones_like", "zeros_like", "full"]
