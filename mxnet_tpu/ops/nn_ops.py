"""Neural-network ops (reference: src/operator/nn/*).

Two layers:
  * pure kernels over `jax.Array` (suffix-free lowercase functions) — these
    are what Gluon layers call inside `hybrid_forward`, so a hybridized net
    traces into one XLA executable. Convs ride `lax.conv_general_dilated`
    (MXU), layouts are configurable (reference default NCHW accepted; NHWC is
    the TPU-preferred fast path used by the model zoo's `layout` option).
  * imperative NDArray wrappers with the reference's legacy op names
    (FullyConnected, Convolution, BatchNorm, Pooling, Activation, Dropout,
    SoftmaxOutput, ...) dispatched through `_apply` so autograd records them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, _apply, _lift

__all__ = [
    "fully_connected", "convolution", "deconvolution", "stem_conv_s2d",
    "StemConvS2D", "batch_norm",
    "layer_norm", "group_norm", "instance_norm", "pooling", "global_pooling",
    "activation", "leaky_relu", "dropout", "embedding", "softmax",
    "log_softmax", "softmax_cross_entropy", "rnn_step",
    "FullyConnected", "Convolution", "Deconvolution", "BatchNorm", "LayerNorm",
    "InstanceNorm", "GroupNorm", "PReLU",
    "Pooling", "Activation", "LeakyReLU", "Dropout", "Embedding",
    "SoftmaxOutput",
    "softmax_nd", "log_softmax_nd", "relu", "sigmoid", "gelu", "silu",
    "Pooling_v1", "Convolution_v1",
]


# ---------------------------------------------------------------------------
# pure kernels (jax.Array -> jax.Array)
# ---------------------------------------------------------------------------
def _amp_cast(x, weight):
    """Op-level AMP autocast (amp.init()): fp32 matmul/conv operands run on
    the MXU in the AMP target dtype. EITHER side being fp32 is downcast —
    a bf16 activation meeting an fp32 master weight must not promote the
    dot back to fp32. Applied at trace time; no-op when AMP is off."""
    from ..amp import autocast_dtype
    dt = autocast_dtype()
    if dt is None:
        return x, weight
    if x.dtype == jnp.float32:
        x = x.astype(dt)
    if weight.dtype == jnp.float32:
        weight = weight.astype(dt)
    return x, weight


def fully_connected(x, weight, bias=None, flatten=True):
    """y = x @ W^T + b. weight: (num_hidden, in_units) — reference convention
    (src/operator/nn/fully_connected.cc)."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    x, weight = _amp_cast(x, weight)
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def _conv_dn(ndim, layout):
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]
    spatial = layout.replace("N", "").replace("C", "")
    rhs = "OI" + spatial  # weight layout (out_ch, in_ch, *kernel)
    return layout, lax.conv_dimension_numbers(
        (1,) * (ndim + 2), (1,) * (ndim + 2), (layout, rhs, layout))


def convolution(x, weight, bias=None, stride=1, pad=0, dilate=1,
                num_group=1, layout=None):
    """N-d convolution on the MXU. weight layout (O, I/g, *k) for NC* layouts
    or (O, *k, I/g) for N*C layouts (reference: conv layout semantics)."""
    ndim = x.ndim - 2
    if isinstance(stride, int):
        stride = (stride,) * ndim
    if isinstance(pad, int):
        pad = (pad,) * ndim
    if isinstance(dilate, int):
        dilate = (dilate,) * ndim
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]
    spatial = layout.replace("N", "").replace("C", "")
    rhs = ("OI" + spatial) if layout.index("C") == 1 else ("O" + spatial + "I")
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, (layout, rhs, layout))
    x, weight = _amp_cast(x, weight)
    # bf16 in / bf16 out: the TPU MXU accumulates in fp32 internally, and a
    # preferred_element_type upcast would poison the conv transpose (the AD
    # rule requires cotangent dtype == primal dtype). fp32 master weights
    # compute in the activation dtype; the astype transpose returns the
    # weight cotangent in fp32 (the multi-precision optimizer pattern).
    if weight.dtype != x.dtype:
        weight = weight.astype(x.dtype)
    y = lax.conv_general_dilated(
        x, weight, window_strides=tuple(stride),
        padding=tuple((p, p) for p in pad),
        rhs_dilation=tuple(dilate), dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None:
        c_axis = layout.index("C")
        shape = [1] * y.ndim
        shape[c_axis] = -1
        y = y + bias.reshape(shape).astype(y.dtype)
    return y


def stem_conv_s2d(x, weight):
    """7x7/stride-2/pad-3 NHWC convolution computed via space-to-depth.

    Mathematically identical to `convolution(x, weight, stride=2, pad=3,
    layout="NHWC")` for a (O, 7, 7, C) weight, but the conv runs on the
    (H/2, W/2, 4C) space-to-depth input with a (O, 4, 4, 4C) repacked kernel,
    stride 1, asymmetric pad (2, 1). A 3-channel stride-2 conv tiles terribly
    onto the MXU (its weight gradient ran at <5% efficiency in profiles);
    4x the input channels and stride 1 fix the tiling. This is the standard
    TPU ResNet stem optimisation (MLPerf space-to-depth trick).
    """
    n, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(
            f"stem_conv_s2d needs even spatial dims, got {(h, w)}; use "
            "convolution(..., stride=2, pad=3) for odd sizes")
    o = weight.shape[0]
    xs = x.reshape(n, h // 2, 2, w // 2, 2, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
    # repack: w2[o, ka, kb, (p*2+q)*C + c] = w[o, u, v, c] with
    # u = 2*ka + p - 4 + 3, i.e. grid index u+1 in an 8-wide padded kernel
    wp = jnp.pad(weight, ((0, 0), (1, 0), (1, 0), (0, 0)))       # (O,8,8,C)
    w2 = wp.reshape(o, 4, 2, 4, 2, c).transpose(0, 1, 3, 2, 4, 5)
    w2 = w2.reshape(o, 4, 4, 4 * c)
    dn = lax.conv_dimension_numbers(xs.shape, w2.shape,
                                    ("NHWC", "OHWI", "NHWC"))
    return lax.conv_general_dilated(
        xs, w2.astype(xs.dtype), window_strides=(1, 1),
        padding=((2, 1), (2, 1)), dimension_numbers=dn)


def deconvolution(x, weight, bias=None, stride=1, pad=0, adj=0, layout=None):
    """Transposed convolution (reference: deconvolution.cc). weight (I, O, *k)."""
    ndim = x.ndim - 2
    if isinstance(stride, int):
        stride = (stride,) * ndim
    if isinstance(pad, int):
        pad = (pad,) * ndim
    if isinstance(adj, int):
        adj = (adj,) * ndim
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]
    spatial = layout.replace("N", "").replace("C", "")
    rhs = "IO" + spatial
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, (layout, rhs, layout))
    k = weight.shape[2:]
    padding = tuple((d - 1 - p, d - 1 - p + a) for d, p, a in
                    zip(k, pad, adj))
    # gradient formulation of transposed conv: dilate the input by `stride`
    # and convolve with the spatially-flipped kernel (out = (in-1)*s - 2p +
    # k + adj, reference deconvolution.cc semantics)
    flipped = lax.rev(weight, tuple(range(2, weight.ndim)))
    y = lax.conv_general_dilated(
        x, flipped, window_strides=(1,) * ndim, padding=padding,
        lhs_dilation=tuple(stride), dimension_numbers=dn)
    if bias is not None:
        c_axis = layout.index("C")
        shape = [1] * y.ndim
        shape[c_axis] = -1
        y = y + bias.reshape(shape).astype(y.dtype)
    return y


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_train(x, gamma, beta, shift, axis, eps):
    """Training-mode BN core with a hand-fused backward.

    Forward is two memory passes: one fused multi-output reduction computing
    E[x] and E[x^2] in fp32 (single read of x), one elementwise apply.
    Backward is two more: one fused reduction for (dbeta, dgamma), one
    elementwise pass for dx — the minimum for BN training. Autodiff of the
    naive two-stage mean/var formulation costs ~2x more passes, which
    profiling showed dominating the ResNet-50 step (BN reduce fusions were
    44% of device time). The stat outputs (batch mean/var, fp32) feed the
    moving-average update only and are treated as stop_gradient, matching
    the reference where running stats are non-differentiable aux states
    (src/operator/nn/batch_norm.cc).
    """
    y, mean, var, _inv = _bn_train_fwd_impl(x, gamma, beta, shift, axis, eps)
    return y, mean, var


def _bn_train_fwd_impl(x, gamma, beta, shift, axis, eps):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = -1
    xf = x.astype(jnp.float32)
    # shifted one-pass moments: E[x^2]-E[x]^2 on raw values loses all fp32
    # precision when |mean| >> std (training diverged within steps once
    # activations drifted). Shifting by the running mean — an independent
    # input, so both reduces still fuse into ONE pass over x — keeps the
    # cancellation at O(eps * (std^2 + lag^2)) where lag = |E[x] - shift|,
    # benign since the running mean tracks the batch mean.
    sf = lax.stop_gradient(shift.astype(jnp.float32)).reshape(shape)
    xc = xf - sf
    m1 = jnp.mean(xc, axis=axes)
    var = jnp.maximum(jnp.mean(xc * xc, axis=axes) - m1 * m1, 0.0)
    mean = m1 + sf.reshape(-1)
    inv = lax.rsqrt(var + eps)
    gf = gamma.astype(jnp.float32).reshape(shape)
    bf = beta.astype(jnp.float32).reshape(shape)
    y = ((xf - mean.reshape(shape)) * inv.reshape(shape) * gf + bf)
    return y.astype(x.dtype), mean, var, inv


def _bn_train_vjp_fwd(x, gamma, beta, shift, axis, eps):
    y, mean, var, inv = _bn_train_fwd_impl(x, gamma, beta, shift, axis, eps)
    return (y, mean, var), (x, gamma, mean, inv, shift)


def _bn_train_vjp_bwd(axis, eps, res, cots):
    dy, _dmean, _dvar = cots   # stat outputs: aux tracking only, no grad
    x, gamma, mean, inv, shift = res
    axes = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = -1
    n = 1
    for i in axes:
        n *= x.shape[i]
    dyf = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    dbeta = jnp.sum(dyf, axis=axes)                  # fused with dgamma:
    dgamma = jnp.sum(dyf * xhat, axis=axes)          # one pass over (x, dy)
    k = (gamma.astype(jnp.float32) * inv / n).reshape(shape)
    dx = k * (n * dyf - dbeta.reshape(shape) - xhat * dgamma.reshape(shape))
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype), jnp.zeros_like(shift))


_bn_train.defvjp(_bn_train_vjp_fwd, _bn_train_vjp_bwd)


def batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, training=True, axis=1):
    """BatchNorm. Returns (y, new_moving_mean, new_moving_var)."""
    if training:
        y, mean, var = _bn_train(x, gamma, beta, moving_mean, axis,
                                 float(eps))
        new_mean = (momentum * moving_mean.astype(jnp.float32)
                    + (1 - momentum) * mean).astype(moving_mean.dtype)
        new_var = (momentum * moving_var.astype(jnp.float32)
                   + (1 - momentum) * var).astype(moving_var.dtype)
        return y, new_mean, new_var
    shape = [1] * x.ndim
    shape[axis] = -1
    inv = lax.rsqrt(moving_var.astype(jnp.float32) + eps)
    scale = (gamma.astype(jnp.float32) * inv).reshape(shape)
    shift = (beta.astype(jnp.float32)
             - gamma.astype(jnp.float32) * moving_mean.astype(jnp.float32)
             * inv).reshape(shape)
    y = (x.astype(jnp.float32) * scale + shift).astype(x.dtype)
    return y, moving_mean, moving_var


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = -1
    return y * gamma.reshape(shape) + beta.reshape(shape)


def group_norm(x, gamma, beta, num_groups, eps=1e-5):
    """GroupNorm over channel-first (N, C, ...) layout."""
    n, c = x.shape[0], x.shape[1]
    orig = x.shape
    xg = x.reshape(n, num_groups, c // num_groups, -1)
    mean = jnp.mean(xg, axis=(2, 3), keepdims=True)
    var = jnp.var(xg, axis=(2, 3), keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    y = xg.reshape(orig)
    shape = [1] * x.ndim
    shape[1] = -1
    return y * gamma.reshape(shape) + beta.reshape(shape)


def instance_norm(x, gamma, beta, eps=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[1] = -1
    return y * gamma.reshape(shape) + beta.reshape(shape)


def pooling(x, kernel, pool_type="max", stride=None, pad=0, layout=None,
            count_include_pad=True):
    """Max/avg/sum pooling via lax.reduce_window."""
    ndim = x.ndim - 2
    if isinstance(kernel, int):
        kernel = (kernel,) * ndim
    stride = stride or kernel
    if isinstance(stride, int):
        stride = (stride,) * ndim
    if isinstance(pad, int):
        pad = (pad,) * ndim
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]
    c_axis = layout.index("C")
    window = [1] * x.ndim
    strides = [1] * x.ndim
    paddings = [(0, 0)] * x.ndim
    sp = [i for i in range(x.ndim) if i not in (0, c_axis)]
    for i, ax in enumerate(sp):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        paddings[ax] = (pad[i], pad[i])
    if pool_type == "max":
        # init must be a python scalar: an array-valued init defeats XLA's
        # monoid recognition and kills the reduce_window VJP on TPU
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = -jnp.inf
        else:
            init = int(jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max,
                                 tuple(window), tuple(strides), tuple(paddings))
    zero = 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0
    s = lax.reduce_window(x, zero, lax.add,
                          tuple(window), tuple(strides), tuple(paddings))
    if pool_type == "sum":
        return s
    if count_include_pad:
        denom = 1
        for k in kernel:
            denom *= k
        return s / denom
    ones = jnp.ones_like(x)
    cnt = lax.reduce_window(ones, zero, lax.add,
                            tuple(window), tuple(strides), tuple(paddings))
    return s / cnt


def global_pooling(x, pool_type="avg", layout="NCHW", keepdims=True):
    c_axis = layout.index("C")
    axes = tuple(i for i in range(x.ndim) if i not in (0, c_axis))
    if pool_type == "max":
        return jnp.max(x, axis=axes, keepdims=keepdims)
    return jnp.mean(x, axis=axes, keepdims=keepdims)


_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": jax.nn.gelu,
    "erf_gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "swish": jax.nn.silu,
    "silu": jax.nn.silu,
    "mish": jax.nn.mish,
    "relu6": lambda x: jnp.clip(x, 0, 6),
    # MXNet semantics: clip(0.2*x + 0.5, 0, 1) — NOT jax.nn.hard_sigmoid's
    # 1/6 slope; must match nd.hard_sigmoid (ops/seq_ops.py)
    "hard_sigmoid": lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0),
    "hard_swish": jax.nn.hard_swish,
    "exp": jnp.exp,
    "identity": lambda x: x,
}


def activation(x, act_type="relu"):
    return _ACTS[act_type](x)


def leaky_relu(x, act_type="leaky", slope=0.25, alpha=None):
    if act_type in ("leaky", "prelu"):
        a = slope if alpha is None else alpha
        return jnp.where(x >= 0, x, a * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        return jax.nn.selu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown leaky_relu act_type {act_type}")


def dropout(x, key, p=0.5, training=True, axes=()):
    """Inverted dropout; along `axes` one draw is shared (the mask has
    extent 1 there)."""
    if not training or p <= 0:
        return x
    shape = list(x.shape)
    for ax in axes:
        shape[ax] = 1
    return mx_dropout(x, key, 1.0 - p, tuple(shape))


@partial(jax.jit, static_argnames=("keep", "shape"))
def mx_dropout(x, key, keep, shape):
    """The bits and the mask multiply. A named jitted function, so that
    forward and backward carry `mx_dropout` in their ops' `op_name`
    (`jvp(jit(mx_dropout))`, `transpose(jvp(jit(mx_dropout)))`) and in the
    compile cache's key; XLA inlines the call. compilex `op_scopes` says
    why a `jax.named_scope` would not do, and maps the device's ops to
    it for the benchmark's `dropout_share_pct`.

    The bits are XLA's `RngBitGenerator`, not threefry: on the TPU one
    instruction that the compiler neither fuses into the mask's consumers
    nor repeats, so a site's bits are made once and the backward reads the
    stored one-byte mask. `key` is the site's two-word threefry key,
    widened to the generator's four words; the same key gives the same
    mask. The keep probability is exact to 2**-32 (an integer compare)."""
    state = jnp.concatenate([key, key ^ jnp.uint32(0x9E3779B9)])
    _, bits = jax.lax.rng_bit_generator(state, shape, dtype=jnp.uint32)
    mask = bits < jnp.uint32(min(int(keep * 2 ** 32), 2 ** 32 - 1))
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


def embedding(indices, weight):
    # integer index batches pass through UNTOUCHED (int32/int64): the old
    # unconditional astype(int32) round-tripped nothing through float,
    # but ISSUE 15 pins the contract — only non-integer indices (the
    # MXNet float-default compat path) are cast, and that cast is lossy
    # above 2**24 rows (recommender scale wants a ShardedEmbedding with
    # integer inputs, which refuses floats outright)
    if not jnp.issubdtype(indices.dtype, jnp.integer):
        indices = indices.astype(jnp.int32)
    return jnp.take(weight, indices, axis=0)


def softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def softmax_cross_entropy(logits, labels, sparse=True, axis=-1):
    logp = jax.nn.log_softmax(logits, axis=axis)
    if sparse:
        lab = labels.astype(jnp.int32)
        return -jnp.take_along_axis(logp, lab[..., None], axis=axis)[..., 0]
    return -jnp.sum(labels * logp, axis=axis)


def rnn_step(x, h, wx, wh, b, mode="rnn_tanh"):
    g = jnp.matmul(x, wx.T) + jnp.matmul(h, wh.T) + b
    if mode == "rnn_tanh":
        return jnp.tanh(g)
    if mode == "rnn_relu":
        return jax.nn.relu(g)
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# imperative NDArray wrappers (reference legacy op names)
# ---------------------------------------------------------------------------
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True, **kwargs):
    ins = [data, weight] + ([] if no_bias or bias is None else [bias])
    if no_bias or bias is None:
        return _apply(lambda x, w, _f=flatten: fully_connected(x, w, None, _f), ins)
    return _apply(lambda x, w, b, _f=flatten: fully_connected(x, w, b, _f), ins)


def Convolution(data, weight, bias=None, kernel=None, stride=1, pad=0,
                dilate=1, num_filter=None, num_group=1, no_bias=False,
                layout=None, **kwargs):
    if no_bias or bias is None:
        return _apply(lambda x, w, _s=stride, _p=pad, _d=dilate, _g=num_group,
                      _l=layout: convolution(x, w, None, _s, _p, _d, _g, _l),
                      [data, weight])
    return _apply(lambda x, w, b, _s=stride, _p=pad, _d=dilate, _g=num_group,
                  _l=layout: convolution(x, w, b, _s, _p, _d, _g, _l),
                  [data, weight, bias])


def StemConvS2D(data, weight, **kwargs):
    """NDArray wrapper for `stem_conv_s2d` (7x7/s2/p3 NHWC stem conv)."""
    return _apply(stem_conv_s2d, [data, weight])


def Deconvolution(data, weight, bias=None, kernel=None, stride=1, pad=0,
                  adj=0, num_filter=None, no_bias=False, layout=None, **kwargs):
    if no_bias or bias is None:
        return _apply(lambda x, w, _s=stride, _p=pad, _a=adj, _l=layout:
                      deconvolution(x, w, None, _s, _p, _a, _l), [data, weight])
    return _apply(lambda x, w, b, _s=stride, _p=pad, _a=adj, _l=layout:
                  deconvolution(x, w, b, _s, _p, _a, _l), [data, weight, bias])


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
              momentum=0.9, fix_gamma=False, use_global_stats=False,
              axis=1, **kwargs):
    from .. import autograd
    training = autograd.is_training() and not use_global_stats
    out, new_mean, new_var = _apply(
        lambda x, g, b, mm, mv, _e=eps, _m=momentum, _t=training, _ax=axis:
        batch_norm(x, jnp.ones_like(g) if fix_gamma else g, b, mm, mv,
                   _e, _m, _t, _ax),
        [data, gamma, beta, moving_mean, moving_var], n_out=3)
    if training:
        # reference semantics: aux states are mutated in place during training
        moving_mean._assign_value(new_mean._data)
        moving_var._assign_value(new_var._data)
    return out


def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, **kwargs):
    return _apply(lambda x, g, b, _ax=axis, _e=eps: layer_norm(x, g, b, _ax, _e),
                  [data, gamma, beta])


def prelu(x, alpha):
    """PReLU with shared or per-channel alpha (reference: leaky_relu-inl.h
    act_type='prelu')."""
    if x.ndim > 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x >= 0, x, alpha * x)


def InstanceNorm(data, gamma, beta, eps=1e-5, **kwargs):
    return _apply(lambda x, g, b, _e=eps: instance_norm(x, g, b, _e),
                  [data, gamma, beta])


def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5, **kwargs):
    return _apply(lambda x, g, b, _n=num_groups, _e=eps:
                  group_norm(x, g, b, _n, _e), [data, gamma, beta])


def PReLU(data, alpha, **kwargs):
    return _apply(prelu, [data, alpha])


def Pooling(data, kernel=None, pool_type="max", stride=None, pad=0,
            global_pool=False, layout=None, **kwargs):
    if global_pool:
        return _apply(lambda x, _pt=pool_type, _l=layout or "NCHW":
                      global_pooling(x, _pt, _l), [data])
    return _apply(lambda x, _k=kernel, _pt=pool_type, _s=stride, _p=pad,
                  _l=layout: pooling(x, _k, _pt, _s, _p, _l), [data])


def Activation(data, act_type="relu", **kwargs):
    return _apply(lambda x, _a=act_type: activation(x, _a), [data])


def LeakyReLU(data, act_type="leaky", slope=0.25, **kwargs):
    return _apply(lambda x, _a=act_type, _s=slope: leaky_relu(x, _a, _s), [data])


def Dropout(data, p=0.5, mode="training", **kwargs):
    from .. import autograd
    from ..random import _next_key
    if not autograd.is_training() and mode != "always":
        return data
    key = _next_key()
    return _apply(lambda x, _k=key, _p=p: dropout(x, _k, _p, True), [data])


def Embedding(data, weight, input_dim=None, output_dim=None, **kwargs):
    return _apply(lambda i, w: embedding(i, w), [data, weight])


def SoftmaxOutput(data, label=None, **kwargs):
    return _apply(lambda x: jax.nn.softmax(x, axis=-1), [data])


def softmax_nd(data, length=None, axis=-1, temperature=None,
               use_length=False, causal=False):
    # positional order matches the reference AND the symbol-side softmax:
    # (data, length, axis, ...) — python/mxnet/ndarray/gen_op softmax
    # reference: softmax(..., use_length=True) masks positions >= the
    # per-batch length along the (last) softmax axis (src/operator/nn/
    # softmax.cc); `causal` (attention-export extension) masks positions
    # past the query row. Same kernel the symbol op and ONNX export pin.
    if length is not None or use_length or causal:
        if use_length and length is None:
            raise MXNetError("softmax: use_length=True needs a length input")

        def masked(x, *maybe_ln, _ax=axis, _t=temperature):
            if _t is not None and _t != 1.0:
                x = x / _t
            if _ax % x.ndim != x.ndim - 1:
                raise MXNetError(
                    "softmax: masking supports the last axis only")
            keep = jnp.ones((), bool)
            idx = jnp.arange(x.shape[-1])
            if maybe_ln:
                lb = maybe_ln[0].astype(jnp.int32).reshape(
                    (maybe_ln[0].shape[0],) + (1,) * (x.ndim - 1))
                keep = keep & (idx < lb)
            if causal:
                keep = keep & (idx[None, :] <= jnp.arange(
                    x.shape[-2])[:, None])
            return jax.nn.softmax(jnp.where(keep, x, -1e9), axis=-1)

        ins = [data] + ([length] if length is not None else [])
        return _apply(masked, ins)
    return _apply(lambda x, _ax=axis, _t=temperature: softmax(x, _ax, _t), [data])


def log_softmax_nd(data, axis=-1):
    return _apply(lambda x, _ax=axis: log_softmax(x, _ax), [data])


def relu(data):
    return _apply(jax.nn.relu, [data])


def sigmoid(data):
    return _apply(jax.nn.sigmoid, [data])


def gelu(data):
    return _apply(jax.nn.gelu, [data])


def silu(data):
    return _apply(jax.nn.silu, [data])


# legacy _v1 spellings (reference: pooling_v1.cc, convolution_v1.cc —
# identical semantics; upstream kept both op names registered)
Pooling_v1 = Pooling
Convolution_v1 = Convolution
