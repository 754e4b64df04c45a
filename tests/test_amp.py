"""AMP tests (SURVEY.md §2 #32)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, nd, autograd, gluon
from mxnet_tpu.gluon import nn


def test_convert_block_casts_matmul_keeps_norms():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(axis=1, in_channels=8),
            nn.Dense(2, in_units=8))
    net.initialize()
    amp.convert_block(net, "bfloat16")
    dense_w = net[0].weight.data()
    bn_gamma = net[1].gamma.data()
    assert "bfloat16" in str(dense_w.dtype)
    assert "float32" in str(bn_gamma.dtype)


def test_bf16_forward_backward():
    net = nn.Dense(4, in_units=4)
    net.initialize()
    net.cast("bfloat16")
    x = nd.random.uniform(shape=(2, 4), dtype="bfloat16")
    with autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    g = net.weight.grad()
    assert "bfloat16" in str(g.dtype)
    assert np.isfinite(g.asnumpy().astype(np.float32)).all()


def test_dynamic_loss_scaler_down_on_overflow():
    s = amp.DynamicLossScaler(init_scale=1024.0, scale_factor=2.0,
                              scale_window=2)
    s.update_scale(True)
    assert s.loss_scale == 512.0
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 1024.0  # window hit -> scale back up


def test_dynamic_loss_scaler_floor_at_one():
    """Satellite (ISSUE 3): repeated overflows halve the scale but never
    push it below 1.0 (the floor that keeps grads representable)."""
    s = amp.DynamicLossScaler(init_scale=4.0, scale_factor=2.0,
                              scale_window=100)
    for _ in range(10):
        s.update_scale(True)
    assert s.loss_scale == 1.0
    s.update_scale(True)
    assert s.loss_scale == 1.0      # clamped, not 0.5


def test_dynamic_loss_scaler_window_resets_on_overflow():
    """An overflow inside the growth window resets the unskipped streak:
    growth needs a FULL clean window afterwards."""
    s = amp.DynamicLossScaler(init_scale=1024.0, scale_factor=2.0,
                              scale_window=3)
    s.update_scale(False)
    s.update_scale(False)
    s.update_scale(True)            # overflow 1 step before growth
    assert s.loss_scale == 512.0
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 512.0    # streak restarted: no growth yet
    s.update_scale(False)
    assert s.loss_scale == 1024.0   # full clean window -> doubles
    # and the window counter resets after growth too
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 1024.0


def test_scale_loss_and_unscale_roundtrip():
    amp.init(target_dtype="float16")
    try:
        net = nn.Dense(2, in_units=2)
        net.initialize()
        x = nd.ones((1, 2))
        with autograd.record():
            y = net(x).sum()
            scaled = amp.scale_loss(y)
        scaled.backward()
        scale = amp._state["scaler"].loss_scale
        g_scaled = net.weight.grad().asnumpy().copy()
        amp.unscale([p for p in net.collect_params().values()])
        g = net.weight.grad().asnumpy()
        np.testing.assert_allclose(g * scale, g_scaled, rtol=1e-3)
    finally:
        amp._state["scaler"] = None
        amp._state["initialized"] = False


def test_overflow_detection():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    x = nd.ones((1, 2))
    with autograd.record():
        y = net(x).sum() * float("inf")
    y.backward()
    s = amp.DynamicLossScaler()
    assert s.has_overflow(list(net.collect_params().values()))


@pytest.fixture
def _amp_off():
    yield
    amp.reset()


def test_init_autocasts_dense_compute(_amp_off):
    """amp.init() must actually change op compute dtype: fp32 in, bf16 out."""
    net = nn.Dense(4, in_units=4)
    net.initialize()
    x = nd.ones((2, 4))
    assert "float32" in str(net(x).dtype)
    amp.init("bfloat16")
    y = net(x)
    assert "bfloat16" in str(y.dtype)
    # params stay fp32 masters
    assert "float32" in str(net.weight.data().dtype)


def test_convert_block_fixes_blanket_cast(_amp_off):
    """_KEEP_FP32 is live: convert_block after net.cast('bfloat16') restores
    the norm layers to fp32."""
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(axis=1, in_channels=8))
    net.initialize()
    net.cast("bfloat16")
    assert "bfloat16" in str(net[1].gamma.data().dtype)
    amp.convert_block(net, "bfloat16")
    assert "float32" in str(net[1].gamma.data().dtype)
    assert "bfloat16" in str(net[0].weight.data().dtype)


def test_trainer_skips_update_on_overflow_and_halves_scale(_amp_off):
    """Force an overflow, assert the update is
    skipped and the loss scale halves."""
    amp.init("float16")
    scaler = amp._state["scaler"]
    scaler.loss_scale = 1024.0
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    w0 = net.weight.data().asnumpy().copy()
    x = nd.ones((1, 2))
    with autograd.record():
        loss = amp.scale_loss(net(x).sum() * float("inf"))
    loss.backward()
    trainer.step(1)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0)  # skipped
    assert scaler.loss_scale == 512.0                            # halved
    # a clean step afterwards must update
    with autograd.record():
        loss = amp.scale_loss(net(x).sum())
    loss.backward()
    trainer.step(1)
    assert not np.allclose(net.weight.data().asnumpy(), w0)


def test_init_busts_hybridize_cache(_amp_off):
    """amp.init() after a hybridized net compiled must still take effect
    (the jit cache is keyed on the autocast dtype)."""
    net = nn.Dense(4, in_units=4)
    net.initialize()
    net.hybridize()
    x = nd.ones((2, 4))
    assert "float32" in str(net(x).dtype)   # compiled pre-AMP
    amp.init("bfloat16")
    assert "bfloat16" in str(net(x).dtype)  # fresh trace post-AMP
    amp.reset()
    assert "float32" in str(net(x).dtype)   # and back


def test_trainer_update_also_guarded(_amp_off):
    """The allreduce_grads()+update() flow must hit the same AMP
    unscale/overflow guard as step()."""
    amp.init("float16")
    scaler = amp._state["scaler"]
    scaler.loss_scale = 1024.0
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    w0 = net.weight.data().asnumpy().copy()
    x = nd.ones((1, 2))
    with autograd.record():
        loss = amp.scale_loss(net(x).sum() * float("inf"))
    loss.backward()
    trainer.allreduce_grads()
    trainer.update(1)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0)
    assert scaler.loss_scale == 512.0
    # clean grads: update() must unscale before applying
    with autograd.record():
        loss = amp.scale_loss(net(x).sum())
    loss.backward()
    trainer.allreduce_grads()
    trainer.update(1)
    w1 = net.weight.data().asnumpy()
    assert not np.allclose(w1, w0)
    # grad of sum(xW^T+b) wrt W is x=1; unscaled update = lr*1 = 0.1
    np.testing.assert_allclose(w0 - w1, np.full_like(w0, 0.1), rtol=1e-3)


def test_trainer_skip_nonfinite(_amp_off):
    """skip_nonfinite guards non-AMP training too (§5 failure detection)."""
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, skip_nonfinite=True)
    w0 = net.weight.data().asnumpy().copy()
    x = nd.ones((1, 2))
    with autograd.record():
        loss = net(x).sum() * float("nan")
    loss.backward()
    trainer.step(1)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(1)
    assert not np.allclose(net.weight.data().asnumpy(), w0)
