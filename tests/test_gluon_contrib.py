"""gluon.contrib layers: SyncBatchNorm (cross-replica stats on the virtual
mesh), pixel shuffle, ConvLSTM/LSTMP/VariationalDropout cells."""
import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import contrib
from mxnet_tpu.gluon.contrib.nn import sync_batch_norm
from mxnet_tpu.parallel.mesh import make_mesh


def test_sync_batch_norm_cross_replica_stats():
    """Inside a dp shard_map, SyncBatchNorm stats are GLOBAL-batch: the
    sharded output must match plain BN run on the full batch — and differ
    from per-shard BN when shard means differ."""
    rs = np.random.RandomState(0)
    # per-shard distributions differ wildly so local != global stats
    x = np.concatenate([rs.randn(2, 4, 3, 3) * (i + 1) + 2 * i
                        for i in range(8)]).astype(np.float32)
    g = np.abs(rs.randn(4).astype(np.float32)) + 0.5
    b = rs.randn(4).astype(np.float32)
    mm = np.zeros(4, np.float32)
    mv = np.ones(4, np.float32)

    y_full, nm_full, nv_full = sync_batch_norm(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b), jnp.asarray(mm),
        jnp.asarray(mv), training=True, axis_name=None)

    mesh = make_mesh({"dp": 8})
    y_sh, nm_sh, nv_sh = shard_map(
        lambda xs, gs, bs, mms, mvs: sync_batch_norm(
            xs, gs, bs, mms, mvs, training=True, axis_name="dp"),
        mesh=mesh,
        in_specs=(P("dp"), P(None), P(None), P(None), P(None)),
        out_specs=(P("dp"), P(None), P(None)))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
        jnp.asarray(mm), jnp.asarray(mv))
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_full),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nv_sh), np.asarray(nv_full),
                               rtol=2e-4, atol=2e-4)
    # and per-shard (unsynced) stats give a DIFFERENT result
    y_local = shard_map(
        lambda xs, gs, bs, mms, mvs: sync_batch_norm(
            xs, gs, bs, mms, mvs, training=True, axis_name=None)[0],
        mesh=mesh,
        in_specs=(P("dp"), P(None), P(None), P(None), P(None)),
        out_specs=P("dp"))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
        jnp.asarray(mm), jnp.asarray(mv))
    assert np.abs(np.asarray(y_local) - np.asarray(y_full)).max() > 0.1


def test_sync_batch_norm_layer_eager_matches_batchnorm():
    """Outside any mesh the layer degrades to plain BatchNorm."""
    from mxnet_tpu.gluon.nn import BatchNorm
    rs = np.random.RandomState(1)
    x = nd.array(rs.randn(8, 4, 5, 5).astype(np.float32))
    sbn = contrib.nn.SyncBatchNorm(in_channels=4)
    bn = BatchNorm(in_channels=4)
    sbn.initialize()
    bn.initialize()
    with autograd.record():
        y1 = sbn(x)
    with autograd.record():
        y2 = bn(x)
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=1e-4,
                               atol=1e-4)
    # running stats updated identically
    np.testing.assert_allclose(sbn.running_var.data().asnumpy(),
                               bn.running_var.data().asnumpy(), rtol=1e-4,
                               atol=1e-5)


def test_pixel_shuffle_2d():
    ps = contrib.nn.PixelShuffle2D(2)
    x = nd.array(np.arange(1 * 8 * 2 * 2, dtype=np.float32)
                 .reshape(1, 8, 2, 2))
    y = ps(x)
    assert y.shape == (1, 2, 4, 4)
    # matches the torch.pixel_shuffle layout contract
    import torch
    expect = torch.pixel_shuffle(torch.from_numpy(x.asnumpy()), 2).numpy()
    np.testing.assert_allclose(y.asnumpy(), expect)


def test_conv2d_lstm_cell():
    cell = contrib.rnn.Conv2DLSTMCell(input_shape=(3, 8, 8),
                                      hidden_channels=6, i2h_kernel=3,
                                      h2h_kernel=3)
    cell.initialize()
    x = nd.random.uniform(shape=(2, 3, 8, 8))
    states = cell.begin_state(batch_size=2)
    assert states[0].shape == (2, 6, 8, 8)
    out, new_states = cell(x, states)
    assert out.shape == (2, 6, 8, 8)
    assert len(new_states) == 2
    # unroll over a short sequence
    seq = nd.random.uniform(shape=(2, 4, 3, 8, 8))  # NTC...
    outs, final = cell.unroll(4, seq, layout="NTC", merge_outputs=True)
    assert outs.shape == (2, 4, 6, 8, 8)


def test_conv_lstm_gradients_flow():
    cell = contrib.rnn.Conv1DLSTMCell(input_shape=(2, 10),
                                      hidden_channels=4)
    cell.initialize()
    x = nd.random.uniform(shape=(3, 2, 10))
    states = cell.begin_state(batch_size=3)
    with autograd.record():
        out, _ = cell(x, states)
        loss = (out ** 2).sum()
    loss.backward()
    g = cell.i2h_weight.grad().asnumpy()
    assert np.abs(g).sum() > 0


def test_lstmp_cell():
    cell = contrib.rnn.LSTMPCell(hidden_size=16, projection_size=5)
    cell.initialize()
    x = nd.random.uniform(shape=(4, 7))
    states = cell.begin_state(batch_size=4)
    assert states[0].shape == (4, 5) and states[1].shape == (4, 16)
    out, new_states = cell(x, states)
    assert out.shape == (4, 5)
    with autograd.record():
        out, _ = cell(x, cell.begin_state(batch_size=4))
        out.sum().backward()
    assert np.abs(cell.h2r_weight.grad().asnumpy()).sum() > 0


def test_variational_dropout_cell_mask_reuse():
    from mxnet_tpu.gluon.rnn import LSTMCell
    base = contrib.rnn.VariationalDropoutCell(LSTMCell(8), drop_inputs=0.5)
    base.initialize()
    x = nd.ones((2, 8))
    states = base.base_cell.begin_state(batch_size=2)
    with autograd.record():
        y1, _ = base(x, states)
        y2, _ = base(x, states)  # same mask -> identical outputs
    np.testing.assert_array_equal(y1.asnumpy(), y2.asnumpy())
    k1 = np.asarray(base._base_key)
    base.reset()
    assert not np.array_equal(k1, np.asarray(base._base_key))
    # inference: no dropout
    y, _ = base(x, states)
    assert y.shape == (2, 8)


def test_variational_dropout_cell_trace_then_eager():
    """Masks must not leak tracers: a traced call followed by an eager call
    without reset() must work (round-2 review finding)."""
    import jax
    from mxnet_tpu.gluon.rnn import LSTMCell
    cell = contrib.rnn.VariationalDropoutCell(LSTMCell(4), drop_inputs=0.5)
    cell.initialize()
    x = nd.ones((2, 4))
    states = cell.base_cell.begin_state(batch_size=2)
    cell(x, states)  # materialise deferred params eagerly before tracing
    with autograd.record():
        @jax.jit
        def traced(xv):
            out, _ = cell(nd.NDArray(xv), states)
            return out._data
        traced(x._data)
        out, _ = cell(x, states)  # eager reuse: same key, fresh mask
    assert out.shape == (2, 4)


def test_sparse_embedding_divergence():
    import pytest
    with pytest.raises(mx.base.MXNetError):
        contrib.nn.SparseEmbedding(10, 4)
