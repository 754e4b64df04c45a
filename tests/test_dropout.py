"""Dropout's mask (ISSUE 27): `ops.nn_ops.mx_dropout` draws its bits from
XLA's bit generator, keyed from the site's key. What every caller counts
on, whatever the generator: a Bernoulli(1 - p) mask that is a function of
the key, survivors scaled by 1 / keep, the dtype kept, a mask of its own
for every site, one draw shared along `axes`, the backward under the
forward's mask, and the input untouched outside training."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import nn_ops as K

N = 1_000_000


@pytest.mark.parametrize("p", [0.1, 0.5])
def test_keep_fraction_within_four_standard_errors(p):
    x = jnp.ones((1000, 1000), jnp.float32)
    y = np.asarray(K.dropout(x, jax.random.PRNGKey(7), p))
    kept = (y != 0).mean()
    assert abs(kept - (1 - p)) < 4 * np.sqrt(p * (1 - p) / N)
    # inverted scaling: what survives is x / keep, the rest exactly 0
    assert set(np.unique(y)) == {0.0, np.float32(1.0) / np.float32(1 - p)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_survivors_are_x_over_keep_in_the_input_dtype(dtype):
    x = jnp.asarray(np.random.RandomState(0).randn(64, 128), dtype)
    y = K.dropout(x, jax.random.PRNGKey(1), 0.1)
    assert y.dtype == x.dtype and y.shape == x.shape
    kept = np.asarray(y != 0)
    want = np.asarray((x / 0.9).astype(dtype), np.float32)
    got = np.asarray(y, np.float32)
    # to an ulp: compiled, a division by a constant may be a multiplication
    np.testing.assert_allclose(got[kept], want[kept],
                               rtol=2 * float(jnp.finfo(dtype).eps))
    assert 0.8 < kept.mean() < 0.97


def test_mask_is_a_function_of_the_key():
    x = jnp.ones((256, 256), jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    a, b = K.dropout(x, k1, 0.5), K.dropout(x, k1, 0.5)
    assert (np.asarray(a) == np.asarray(b)).all()
    c = np.asarray(K.dropout(x, k2, 0.5))
    # two independent fair masks agree on about half the elements
    assert 0.45 < (np.asarray(a) == c).mean() < 0.55


@pytest.mark.parametrize("mode", ["eager", "hybridized", "captured"])
def test_two_dropout_blocks_draw_different_masks(mode):
    class Two(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.a, self.b = nn.Dropout(0.5), nn.Dropout(0.5)

        def hybrid_forward(self, F, x):
            return self.a(x) - self.b(x)

    net = Two()
    x = nd.ones((64, 64))
    if mode == "captured":
        # capture needs a parameter to train: a scale the masks pass through
        scale = gluon.nn.Dense(64, in_units=64, use_bias=False)
        scale.initialize(mx.init.One())
        tr = gluon.Trainer(scale.collect_params(), "sgd",
                           {"learning_rate": 0.0})
        step = tr.capture(lambda a: (net(a) * scale(a)).abs().mean())
        # equal masks would cancel to a loss of exactly 0
        assert float(step(x).asnumpy()) > 0
        assert float(step(x).asnumpy()) > 0 and step.cache_size == 1
        return
    if mode == "hybridized":
        net.hybridize()
    with autograd.record(train_mode=True):
        d = net(x).asnumpy()
    # the difference of two fair masks is 0 on half, +-2 on a quarter each
    assert 0.4 < (d == 0).mean() < 0.6 and (d > 0).any() and (d < 0).any()
    with autograd.record(train_mode=True):
        again = net(x).asnumpy()
    assert (again != d).any()                    # and fresh every call


def test_axes_share_one_draw_along_the_named_axes():
    x = jnp.ones((32, 16, 8), jnp.float32)
    y = np.asarray(K.dropout(x, jax.random.PRNGKey(5), 0.5, axes=(1,)))
    assert (y == y[:, :1, :]).all()              # one draw along axis 1
    assert len(np.unique(y[:, 0, :])) == 2       # and a real mask across it
    net = nn.Dropout(0.5, axes=(0,))
    with autograd.record(train_mode=True):
        z = net(nd.ones((8, 64))).asnumpy()
    assert (z == z[:1]).all() and len(np.unique(z)) == 2


@pytest.mark.parametrize("hybridize", [False, True])
def test_gradient_is_nonzero_exactly_where_the_output_is(hybridize):
    # the tape replays the op with its captured key: the backward's mask
    # has to be the forward's
    net = nn.Dropout(0.3)
    if hybridize:
        net.hybridize()
    x = nd.array(np.random.RandomState(2).rand(128, 64).astype(np.float32)
                 + 0.5)
    x.attach_grad()
    with autograd.record(train_mode=True):
        y = net(x)
        L = (y * y).sum()
    L.backward()
    yn, g = y.asnumpy(), x.grad.asnumpy()
    assert ((g != 0) == (yn != 0)).all() and 0.6 < (yn != 0).mean() < 0.8
    np.testing.assert_allclose(g, 2 * yn / 0.7, rtol=1e-5)


@pytest.mark.parametrize("case", ["predict", "p0", "nd_predict", "npx_off"])
def test_input_untouched_outside_training_and_at_p0(case):
    x = nd.array(np.random.RandomState(4).randn(16, 16).astype(np.float32))
    if case == "predict":
        y = nn.Dropout(0.5)(x)
    elif case == "p0":
        with autograd.record(train_mode=True):
            y = nn.Dropout(0.0)(x)
    elif case == "nd_predict":
        y = nd.Dropout(x, p=0.5)
    else:
        y = mx.npx.dropout(x, p=0.5, training=False)
    assert (y.asnumpy() == x.asnumpy()).all()
    k = jax.random.PRNGKey(0)
    assert K.dropout(x._data, k, 0.5, training=False) is x._data
    assert K.dropout(x._data, k, 0.0) is x._data
