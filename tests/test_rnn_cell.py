"""Legacy mx.rnn cell API (reference:
python/mxnet/rnn/rnn_cell.py): cells build Symbol graphs, unroll,
bind through Module/BucketingModule, and the fused sym.RNN node
computes the same numbers as the unfused per-step chain."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.module import BucketingModule

N, T, I, H = 4, 5, 3, 8


def _bind_with_random(out, rs, data, extra=None):
    shapes, _, _ = out.infer_shape(data=data.shape)
    vals = {"data": data}
    for n, s in zip(out.list_arguments(), shapes):
        if n != "data":
            vals[n] = nd.array(rs.randn(*s).astype(np.float32) * 0.1)
    if extra:
        vals.update(extra)
    return out.bind(mx.cpu(), vals), vals


def test_lstm_cell_unroll_shapes_and_params():
    cell = mx.rnn.LSTMCell(num_hidden=H, prefix="lstm_")
    out, states = cell.unroll(T, inputs=sym.Variable("data"),
                              merge_outputs=True)
    # weights are SHARED across timesteps: exactly one i2h/h2h pair
    assert sorted(out.list_arguments()) == [
        "data", "lstm_h2h_bias", "lstm_h2h_weight",
        "lstm_i2h_bias", "lstm_i2h_weight"]
    shapes, _, _ = out.infer_shape(data=(N, T, I))
    d = dict(zip(out.list_arguments(), shapes))
    assert d["lstm_i2h_weight"] == (4 * H, I)
    assert d["lstm_h2h_weight"] == (4 * H, H)
    assert len(states) == 2
    assert cell.state_shape == [(0, H), (0, H)]


def test_lstm_cell_matches_fused_rnn():
    """The unfused per-step chain and the single sym.RNN node (one
    lax.scan) agree — same gate order, same weights."""
    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    cell = mx.rnn.LSTMCell(num_hidden=H, prefix="l0_")
    out, _ = cell.unroll(T, inputs=sym.Variable("data"),
                         merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y_unfused = ex.forward()[0].asnumpy()

    fused = mx.rnn.FusedRNNCell(num_hidden=H, num_layers=1, mode="lstm",
                                prefix="", get_next_state=True)
    fout, fstates = fused.unroll(T, inputs=sym.Variable("data"),
                                 merge_outputs=True)
    assert len(fstates) == 2
    y_fused = fout.bind(mx.cpu(), vals).forward()[0].asnumpy()
    np.testing.assert_allclose(y_unfused, y_fused, atol=2e-5)


def test_gru_cell_matches_fused_rnn():
    rs = np.random.RandomState(1)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    cell = mx.rnn.GRUCell(num_hidden=H, prefix="l0_")
    out, _ = cell.unroll(T, inputs=sym.Variable("data"),
                         merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()
    f = mx.rnn.FusedRNNCell(num_hidden=H, num_layers=1, mode="gru",
                            prefix="")
    fout, _ = f.unroll(T, inputs=sym.Variable("data"), merge_outputs=True)
    y_f = fout.bind(mx.cpu(), vals).forward()[0].asnumpy()
    np.testing.assert_allclose(y, y_f, atol=2e-5)


def test_rnn_cell_tanh_relu_closed_form():
    rs = np.random.RandomState(2)
    x = nd.array(rs.randn(N, 1, I).astype(np.float32))
    for act, fn in [("tanh", np.tanh),
                    ("relu", lambda v: np.maximum(v, 0))]:
        cell = mx.rnn.RNNCell(num_hidden=H, activation=act, prefix="r_")
        out, _ = cell.unroll(1, inputs=sym.Variable("data"),
                             merge_outputs=True)
        ex, vals = _bind_with_random(out, rs, x)
        y = ex.forward()[0].asnumpy()
        xv = x.asnumpy()[:, 0]
        want = fn(xv @ vals["r_i2h_weight"].asnumpy().T
                  + vals["r_i2h_bias"].asnumpy()
                  + np.zeros((N, H), np.float32)
                  @ vals["r_h2h_weight"].asnumpy().T
                  + vals["r_h2h_bias"].asnumpy())
        np.testing.assert_allclose(y[:, 0], want, atol=1e-5)


def test_unfuse_same_numbers_same_params():
    rs = np.random.RandomState(3)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    fused = mx.rnn.FusedRNNCell(num_hidden=H, num_layers=2, mode="lstm",
                                prefix="base_")
    fout, _ = fused.unroll(T, inputs=sym.Variable("data"),
                           merge_outputs=True)
    ex, vals = _bind_with_random(fout, rs, x)
    y_fused = ex.forward()[0].asnumpy()
    stack = fused.unfuse()
    uout, _ = stack.unroll(T, inputs=sym.Variable("data"),
                           merge_outputs=True)
    assert sorted(uout.list_arguments()) == sorted(fout.list_arguments())
    y_unfused = uout.bind(mx.cpu(), vals).forward()[0].asnumpy()
    np.testing.assert_allclose(y_fused, y_unfused, atol=2e-5)


def test_sequential_residual_dropout_stack():
    rs = np.random.RandomState(4)
    x = nd.array(rs.randn(N, T, H).astype(np.float32))  # input dim == H
    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(num_hidden=H, prefix="s0_"))
    stack.add(mx.rnn.DropoutCell(0.3, prefix="drop_"))
    stack.add(mx.rnn.ResidualCell(mx.rnn.GRUCell(num_hidden=H,
                                                 prefix="s1_")))
    out, states = stack.unroll(T, inputs=sym.Variable("data"),
                               merge_outputs=True)
    assert len(states) == len(stack.state_info) == 3  # h,c + gru h
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()          # inference: dropout identity
    assert y.shape == (N, T, H) and np.isfinite(y).all()
    # residual contribution: zeroing the gru's weights leaves identity
    z = dict(vals)
    for k in list(z):
        if k.startswith("s1_"):
            z[k] = nd.array(np.zeros(z[k].shape, np.float32))
    y_zero = out.bind(mx.cpu(), z).forward()[0].asnumpy()
    lstm_only, _ = mx.rnn.LSTMCell(num_hidden=H, prefix="s0_").unroll(
        T, inputs=sym.Variable("data"), merge_outputs=True)
    y_lstm = lstm_only.bind(
        mx.cpu(), {k: v for k, v in vals.items()
                   if k == "data" or k.startswith("s0_")}
    ).forward()[0].asnumpy()
    np.testing.assert_allclose(y_zero, y_lstm, atol=1e-5)


def test_bidirectional_cell():
    rs = np.random.RandomState(5)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    bi = mx.rnn.BidirectionalCell(
        mx.rnn.LSTMCell(num_hidden=H, prefix="fwd_"),
        mx.rnn.LSTMCell(num_hidden=H, prefix="bwd_"))
    out, states = bi.unroll(T, inputs=sym.Variable("data"),
                            merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()
    assert y.shape == (N, T, 2 * H)
    # forward half equals the plain forward cell
    fwd_out, _ = mx.rnn.LSTMCell(num_hidden=H, prefix="fwd_").unroll(
        T, inputs=sym.Variable("data"), merge_outputs=True)
    y_fwd = fwd_out.bind(
        mx.cpu(), {k: v for k, v in vals.items()
                   if k == "data" or k.startswith("fwd_")}
    ).forward()[0].asnumpy()
    np.testing.assert_allclose(y[:, :, :H], y_fwd, atol=1e-5)
    with pytest.raises(mx.base.MXNetError):
        bi(sym.Variable("d"), states)


def test_zoneout_cell_inference_blend():
    """At inference Dropout is identity, so zoneout blends
    (1-z)*new + z*prev deterministically."""
    rs = np.random.RandomState(6)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    base = mx.rnn.LSTMCell(num_hidden=H, prefix="z_")
    cell = mx.rnn.ZoneoutCell(base, zoneout_outputs=0.25,
                              zoneout_states=0.25)
    out, _ = cell.unroll(T, inputs=sym.Variable("data"),
                         merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()
    assert y.shape == (N, T, H) and np.isfinite(y).all()
    with pytest.raises(mx.base.MXNetError):
        mx.rnn.ZoneoutCell(mx.rnn.FusedRNNCell(num_hidden=H))


def test_begin_state_contract():
    cell = mx.rnn.LSTMCell(num_hidden=H, prefix="b_")
    # explicit batch: concrete zeros
    states = cell.begin_state(batch_size=3)
    for s in states:
        v = s.bind(mx.cpu(), {}).forward()[0].asnumpy()
        assert v.shape == (3, H) and (v == 0).all()
    # no batch info: a clear error, not silent empties
    cell.reset()
    with pytest.raises(mx.base.MXNetError):
        cell.begin_state()
    with pytest.raises(mx.base.MXNetError):
        mx.rnn.FusedRNNCell(num_hidden=H)(sym.Variable("d"), [])


def test_unrolled_cell_json_roundtrip():
    rs = np.random.RandomState(7)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    for make in (lambda: mx.rnn.LSTMCell(num_hidden=H, prefix="j_"),
                 lambda: mx.rnn.FusedRNNCell(num_hidden=H, prefix="j_",
                                             mode="gru")):
        out, _ = make().unroll(T, inputs=sym.Variable("data"),
                               merge_outputs=True)
        ex, vals = _bind_with_random(out, rs, x)
        y = ex.forward()[0].asnumpy()
        out2 = mx.sym.load_json(out.tojson())
        y2 = out2.bind(mx.cpu(), vals).forward()[0].asnumpy()
        np.testing.assert_allclose(y, y2, atol=1e-6)


def _sentences(n=300, seed=0, V=16):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ln = rs.choice([4, 6, 8])
        start = rs.randint(0, V)
        out.append([(start + t) % V for t in range(ln)])
    return out


def test_word_lm_bucketing_with_cells():
    """The classic upstream LSTM word-LM shape: shared cell stack,
    sym_gen unrolling per bucket, BucketingModule.fit (reference:
    example/rnn/bucketing/lstm_bucketing.py)."""
    V, E, HH = 16, 12, 24
    stack = mx.rnn.SequentialRNNCell()
    for i in range(2):
        stack.add(mx.rnn.LSTMCell(num_hidden=HH, prefix=f"lstm_l{i}_"))

    def sym_gen(seq_len):
        with mx.name.NameManager():
            data = sym.Variable("data")
            label = sym.Variable("softmax_label")
            embed = sym.Embedding(data, input_dim=V, output_dim=E,
                                  name="embed")
            stack.reset()
            outputs, _ = stack.unroll(seq_len, inputs=embed,
                                      merge_outputs=True)
            pred = sym.reshape(outputs, (-1, HH))
            pred = sym.FullyConnected(pred, num_hidden=V, name="pred")
            label_f = sym.reshape(label, (-1,))
            out = sym.SoftmaxOutput(pred, label_f, use_ignore=True,
                                    ignore_label=-1, name="softmax")
        return out, ["data"], ["softmax_label"]

    it = mx.rnn.BucketSentenceIter(_sentences(400), batch_size=16,
                                   buckets=[4, 6, 8])
    mod = BucketingModule(sym_gen, default_bucket_key=8)
    mod.fit(it, num_epoch=5, optimizer="adam",
            optimizer_params={"learning_rate": 0.02},
            eval_metric=mx.metric.Perplexity(ignore_label=-1))
    m = mx.metric.create("acc")
    it.reset()
    for batch in it:
        mod.forward(batch, is_train=False)
        mod.update_metric(m, [nd.array(
            batch.label[0].asnumpy().reshape(-1))])
    # next token is deterministic ((w+1) % V): a trained LM crushes
    # 1/16 chance; padding rows cap the ceiling
    assert m.get()[1] > 0.5, m.get()


def test_fused_cell_tnc_layout():
    """TNC layout: the zero-state batch dim must come from axis 1 of the
    merged (T, N, C) sequence (regression: it used axis 0 = T)."""
    rs = np.random.RandomState(8)
    x = nd.array(rs.randn(T, N, I).astype(np.float32))   # time-major
    f = mx.rnn.FusedRNNCell(num_hidden=H, num_layers=1, mode="lstm",
                            prefix="tnc_")
    out, _ = f.unroll(T, inputs=sym.Variable("data"), layout="TNC",
                      merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()
    assert y.shape == (T, N, H)
    # same weights, NTC layout, transposed input -> same numbers
    out2, _ = f.unroll(T, inputs=sym.Variable("data"), layout="NTC",
                       merge_outputs=True)
    v2 = dict(vals); v2["data"] = nd.array(x.asnumpy().transpose(1, 0, 2))
    y2 = out2.bind(mx.cpu(), v2).forward()[0].asnumpy()
    np.testing.assert_allclose(y, y2.transpose(1, 0, 2), atol=1e-5)


def test_zoneout_inference_expectation():
    """Inference zoneout output is exactly (1-z)*new + z*prev: with the
    base cell's weights all zero the LSTM emits 0 every step, so the
    zoneout chain stays 0; with zoneout_outputs=1.0 the first step's
    prev is 0 too. Check the blend arithmetic directly on step 2."""
    rs = np.random.RandomState(9)
    x = nd.array(rs.randn(N, 2, I).astype(np.float32))
    z = 0.25
    base = mx.rnn.LSTMCell(num_hidden=H, prefix="zz_")
    cell = mx.rnn.ZoneoutCell(base, zoneout_outputs=z)
    out, _ = cell.unroll(2, inputs=sym.Variable("data"),
                         merge_outputs=True)
    ex, vals = _bind_with_random(out, rs, x)
    y = ex.forward()[0].asnumpy()
    # plain cell outputs
    base2 = mx.rnn.LSTMCell(num_hidden=H, prefix="zz_")
    pout, _ = base2.unroll(2, inputs=sym.Variable("data"),
                           merge_outputs=True)
    yp = pout.bind(mx.cpu(), vals).forward()[0].asnumpy()
    # step 1: prev=0 -> (1-z)*h1 ; step 2: prev=step1 output
    np.testing.assert_allclose(y[:, 0], (1 - z) * yp[:, 0], atol=1e-5)
    np.testing.assert_allclose(
        y[:, 1], (1 - z) * yp[:, 1] + z * y[:, 0], atol=1e-5)


def test_rnn_checkpoint_helpers(tmp_path):
    """save/load_rnn_checkpoint + do_rnn_checkpoint (reference:
    rnn/rnn.py) round-trip the cell weights."""
    rs = np.random.RandomState(10)
    x = nd.array(rs.randn(N, T, I).astype(np.float32))
    cell = mx.rnn.LSTMCell(num_hidden=H, prefix="ck_")
    out, _ = cell.unroll(T, inputs=sym.Variable("data"),
                         merge_outputs=True)
    shapes, _, _ = out.infer_shape(data=(N, T, I))
    args = {n: nd.array(rs.randn(*s).astype(np.float32) * 0.1)
            for n, s in zip(out.list_arguments(), shapes) if n != "data"}
    prefix = str(tmp_path / "lm")
    mx.rnn.save_rnn_checkpoint(cell, prefix, 3, out, args, {})
    s2, args2, aux2 = mx.rnn.load_rnn_checkpoint(cell, prefix, 3)
    assert sorted(args2) == sorted(args)
    for k in args:
        np.testing.assert_allclose(args2[k].asnumpy(), args[k].asnumpy())
    # the callback form saves on the period
    cb = mx.rnn.do_rnn_checkpoint(cell, prefix, period=2)
    cb(1, out, args, {})          # epoch 1 -> (1+1) % 2 == 0 -> saves
    s3, args3, _ = mx.rnn.load_rnn_checkpoint(cell, prefix, 2)
    assert sorted(args3) == sorted(args)


def test_begin_state_func_contract():
    """func=sym.zeros works with batch_size; the upstream 0-batch idiom
    raises a helpful error instead of silently building empty states."""
    cell = mx.rnn.GRUCell(num_hidden=H, prefix="f_")
    states = cell.begin_state(func=sym.zeros, batch_size=3)
    v = states[0].bind(mx.cpu(), {}).forward()[0].asnumpy()
    assert v.shape == (3, H) and (v == 0).all()
    cell.reset()
    with pytest.raises(mx.base.MXNetError):
        cell.begin_state(func=sym.zeros)
