"""KVStore tests (SURVEY.md §2 #28)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, kvstore


def test_create_kinds():
    assert kvstore.create("local").type == "local"
    assert kvstore.create("device").type == "device"
    assert kvstore.create("nccl").type == "device"
    assert kvstore.create("dist_sync").type == "ici"
    with pytest.raises(Exception):
        kvstore.create("bogus")


def test_init_push_pull_aggregation():
    kv = kvstore.create("local")
    kv.init("w", nd.zeros((4,)))
    kv.push("w", [nd.ones((4,)), nd.ones((4,)) * 2])  # device grads sum
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(4, 3.0))


def test_pushpull_and_multiple_keys():
    kv = kvstore.create("device")
    kv.init(["a", "b"], [nd.zeros((2,)), nd.zeros((2,))])
    kv.push(["a", "b"], [[nd.ones((2,))], [nd.ones((2,)) * 5]])
    outs = kv.pull(["a", "b"])
    np.testing.assert_allclose(outs[0].asnumpy(), [1, 1])
    np.testing.assert_allclose(outs[1].asnumpy(), [5, 5])


def test_optimizer_offload():
    """set_optimizer makes push apply the update instead of overwriting."""
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5))
    w0 = nd.ones((3,))
    kv.init(0, w0)
    kv.push(0, [nd.ones((3,))])           # grad = 1 -> w = 1 - 0.5
    out = nd.zeros((3,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(3, 0.5))


def test_rank_and_workers_single_process():
    kv = kvstore.create("ici")
    assert kv.rank == 0
    assert kv.num_workers == 1


def test_row_sparse_raises():
    kv = kvstore.create("local")
    with pytest.raises(Exception):
        kv.row_sparse_pull("x")


def test_ici_mesh_allreduce():
    """ici kvstore push over an 8-device mesh = psum of per-device shards."""
    import jax
    from mxnet_tpu.parallel.mesh import make_mesh
    kv = kvstore.create("ici").set_mesh(make_mesh({"dp": 8}))
    kv.init("g", nd.zeros((8, 2)))
    vals = [nd.array(np.full((8, 2), float(i))) for i in range(2)]
    kv.push("g", vals)
    out = nd.zeros((8, 2))
    kv.pull("g", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((8, 2), 1.0))


def _dp_mesh():
    from mxnet_tpu.parallel.mesh import make_mesh
    return make_mesh({"dp": 8})


def test_ici_allreduce_stacked_layout():
    """A (R, *shape) stack sharded over the dp axis reduces to (*shape):
    8 replicas each contribute their row, result is the row-sum."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    stacked = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    a = jax.device_put(stacked, NamedSharding(mesh, P("dp")))
    # auto-detects stacked from the sharding
    got = kv.allreduce_([a])
    np.testing.assert_allclose(np.asarray(got), stacked.sum(0))
    # explicit layout gives the same
    got2 = kv.allreduce_([a], layout="stacked")
    np.testing.assert_allclose(np.asarray(got2), stacked.sum(0))


def test_ici_allreduce_replicated_layout():
    """A replicated gradient (XLA already psum'd it inside the step) must NOT
    be multiplied by the axis size."""
    import jax
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    a = np.full((8, 2), 3.0, np.float32)  # host array: replicated semantics
    got = kv.allreduce_([jax.numpy.asarray(a)])
    np.testing.assert_allclose(np.asarray(got), a)
    got2 = kv.allreduce_([jax.numpy.asarray(a)], layout="replicated")
    np.testing.assert_allclose(np.asarray(got2), a)


def test_ici_allreduce_stacked_bad_shape_raises():
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    with pytest.raises(Exception):
        kv.allreduce_([nd.ones((3, 2))._data], layout="stacked")


def test_optimizer_states_roundtrip(tmp_path):
    """save/load_optimizer_states must actually restore momentum buffers."""
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    kv.init("w", nd.ones((3,)))
    kv.push("w", [nd.ones((3,))])     # builds momentum state
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)
    w_after_1 = nd.array(kv.pull("w").asnumpy())  # copy: store mutates

    kv2 = kvstore.create("local")
    kv2.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                          momentum=0.9))
    kv2.init("w", w_after_1)          # weights come from the param ckpt
    kv2.load_optimizer_states(fname)  # momentum comes from the state file
    # one more push on both must produce identical weights (momentum carried)
    kv.push("w", [nd.ones((3,))])
    kv2.push("w", [nd.ones((3,))])
    np.testing.assert_allclose(kv.pull("w").asnumpy(),
                               kv2.pull("w").asnumpy())


def test_optimizer_states_resume_num_update(tmp_path):
    """lr schedules must resume at the saved step on the kvstore path:
    save/load_optimizer_states round-trips optimizer.num_update (a silent
    reset would re-serve the warmup/undecayed learning rate)."""
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
    kv.init("w", nd.ones((3,)))
    for _ in range(5):
        kv.push("w", [nd.ones((3,))])
    assert kv._optimizer.num_update == 5
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)

    kv2 = kvstore.create("local")
    kv2.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
    kv2.init("w", nd.ones((3,)))
    kv2.load_optimizer_states(fname)
    assert kv2._optimizer.num_update == 5
    # counting must CONTINUE from the restored per-key counts, not
    # stagnate at max(5, fresh-count) until post-resume pushes catch up
    for _ in range(2):
        kv2.push("w", [nd.ones((3,))])
    assert kv2._optimizer.num_update == 7


def test_load_optimizer_states_requires_optimizer(tmp_path):
    kv = kvstore.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd"))
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)
    kv2 = kvstore.create("local")
    with pytest.raises(Exception):
        kv2.load_optimizer_states(fname)


def test_init_distributed_single_host_noop():
    """No cluster env, no args: init_distributed stays single-process."""
    kvstore.init_distributed()
    kv = kvstore.create("ici")
    assert kv.num_workers == 1 and kv.rank == 0


# ------------------------------------------------- gradient compression
def _stacked(mesh, arr):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(arr, NamedSharding(mesh, P("dp")))


def test_compression_rejects_unknown_type():
    import pytest
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        kvstore.create("ici").set_gradient_compression({"type": "4bit"})


def test_int8_compression_close_to_exact_and_wire_is_int8():
    """int8 codes with a pmax-shared scale: result within quantization
    error of the exact sum, and the gathered operand really is int8."""
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    kv.set_gradient_compression({"type": "int8"})
    rs = np.random.RandomState(0)
    stacked = rs.randn(8, 64).astype(np.float32)
    a = _stacked(mesh, stacked)
    got = np.asarray(kv.allreduce_([a], layout="stacked", key="w"))
    exact = stacked.sum(0)
    # per-replica quant error <= scale/2; 8 replicas
    scale = np.abs(stacked).max() / 127.0
    assert np.abs(got - exact).max() <= 8 * scale * 0.51 + 1e-6
    st = kv.compression_stats
    assert st["wire_bytes_per_replica"] * 4 == st["raw_bytes_per_replica"]
    # the all_gather moves int8, not f32: check the jaxpr
    jaxpr = str(jax.make_jaxpr(kv.compression_wire_fn(a))(
        jnp.zeros((8, 64), jnp.float32), jnp.zeros((8, 64), jnp.float32)))
    import re
    m = re.search(r":i8\[[^\]]*\]\s*=\s*all_gather", jaxpr)
    assert m, jaxpr[:2000]


def test_2bit_compression_wire_is_16x_smaller():
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    stacked = np.full((8, 64), 0.6, np.float32)
    a = _stacked(mesh, stacked)
    got = np.asarray(kv.allreduce_([a], layout="stacked", key="w"))
    # every element >= threshold: each replica contributes +0.5
    np.testing.assert_allclose(got, np.full(64, 8 * 0.5), rtol=1e-6)
    st = kv.compression_stats
    assert st["wire_bytes_per_replica"] * 16 == st["raw_bytes_per_replica"]
    jaxpr = str(jax.make_jaxpr(kv.compression_wire_fn(a))(
        jnp.zeros((8, 64), jnp.float32), jnp.zeros((8, 64), jnp.float32)))
    import re
    m = re.search(r":u8\[[^\]]*\]\s*=\s*all_gather", jaxpr)
    assert m, jaxpr[:2000]


def test_2bit_error_feedback_accumulates():
    """A constant gradient below threshold must still get through over
    steps via the residual (the whole point of error feedback)."""
    mesh = _dp_mesh()
    kv = kvstore.create("ici").set_mesh(mesh)
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    stacked = np.full((8, 16), 0.2, np.float32)  # below threshold
    a = _stacked(mesh, stacked)
    sums = [np.asarray(kv.allreduce_([a], layout="stacked", key="g")).mean()
            for _ in range(10)]
    # step pattern: residual builds 0.2,0.4->fire 0.5,...; over 10 steps
    # the mean transmitted value approaches the true 8*0.2=1.6 per step
    assert abs(np.mean(sums) - 8 * 0.2) < 0.25, sums
    assert max(sums) > 0  # it does fire


def test_compressed_training_matches_uncompressed():
    """MLP trained with int8-compressed ici allreduce converges to the
    same solution as uncompressed (within tolerance) on the 8-device
    mesh — the acceptance test."""
    from mxnet_tpu.parallel.mesh import make_mesh

    def train(compression):
        rs = np.random.RandomState(1)
        w_true = rs.randn(10, 1).astype(np.float32)
        X = rs.randn(256, 10).astype(np.float32)
        y = X @ w_true
        mesh = make_mesh({"dp": 8})
        kv = kvstore.create("ici").set_mesh(mesh)
        if compression:
            kv.set_gradient_compression(compression)
        w = jnp.zeros((10, 1), jnp.float32)
        kv.init("w", mx.nd.array(np.zeros((10, 1), np.float32)))
        grad_fn = jax.jit(jax.grad(
            lambda w, X, y: jnp.mean((X @ w - y) ** 2)))
        lr = 0.05
        for step in range(60):
            # 8 towers, each on its slice of the batch (stacked layout)
            grads = np.stack([np.asarray(grad_fn(
                w, X[i * 32:(i + 1) * 32], y[i * 32:(i + 1) * 32]))
                for i in range(8)])
            g = _stacked(mesh, grads.astype(np.float32))
            total = kv.allreduce_([g], layout="stacked", key="w")
            w = w - lr * jnp.asarray(total) / 8.0
        final = float(jnp.mean((X @ w - y) ** 2))
        return final

    base = train(None)
    comp = train({"type": "int8"})
    assert base < 1e-3, f"uncompressed failed to converge: {base}"
    assert comp < 5e-3, f"int8-compressed failed to converge: {comp}"


def test_trainer_forwards_compression_params():
    """gluon.Trainer(compression_params=...) configures the store
    (previously accepted and silently dropped)."""
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=3)
    net.initialize()
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1}, kvstore="ici",
                          compression_params={"type": "int8"})
    assert tr._kvstore._compression == {"type": "int8", "threshold": 0.5}
    with pytest.raises(mx.base.MXNetError):
        mx.gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1}, kvstore="ici",
                         compression_params={"type": "bogus"})
    with pytest.raises(mx.base.MXNetError):
        mx.gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1}, kvstore=None,
                         compression_params={"type": "int8"})


def test_trainer_update_on_kvstore_matches_local_update():
    """update_on_kvstore=True (previously ignored): the optimizer runs on
    the store (push applies, pull returns) with identical numerics to the
    local-update path, momentum state included."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn, loss as gloss

    def run(on_kv):
        mx.random.seed(5)
        np.random.seed(5)
        net = nn.Dense(4, in_units=6)
        net.initialize()
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9},
                              kvstore="local", update_on_kvstore=on_kv)
        lf = gloss.L2Loss()
        rs = np.random.RandomState(0)
        x = nd.array(rs.randn(8, 6).astype(np.float32))
        y = nd.array(rs.randn(8, 4).astype(np.float32))
        for _ in range(3):
            with autograd.record():
                loss = lf(net(x), y)
            loss.backward()
            tr.step(8)
        return {k: v.data().asnumpy() for k, v in
                net.collect_params().items()}

    a, b = run(False), run(True)
    for (k0, v0), (k1, v1) in zip(a.items(), b.items()):
        np.testing.assert_allclose(v0, v1, rtol=1e-6,
                                   err_msg=f"{k0} vs {k1}")


def test_trainer_update_on_kvstore_requires_store():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(2, in_units=2)
    net.initialize()
    with pytest.raises(mx.base.MXNetError):
        mx.gluon.Trainer(net.collect_params(), "sgd", {},
                         kvstore=None, update_on_kvstore=True)


def test_update_on_kvstore_respects_mults_and_states(tmp_path):
    """lr_mult/wd_mult survive the stringified store keys; trainer
    save/load_states round-trips the STORE's optimizer state; update()
    is rejected (the store owns the optimizer)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn, loss as gloss
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net.bias.lr_mult = 0.0          # frozen via multiplier
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.5, "momentum": 0.9},
                          kvstore="local", update_on_kvstore=True)
    lf = gloss.L2Loss()
    x = nd.array(np.ones((2, 4), np.float32))
    y = nd.array(np.zeros((2, 3), np.float32))
    b0 = net.bias.data().asnumpy().copy()
    w0 = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    tr.step(2)
    assert np.allclose(net.bias.data().asnumpy(), b0), \
        "lr_mult=0 ignored on the kvstore path"
    assert not np.allclose(net.weight.data().asnumpy(), w0)
    f = str(tmp_path / "t.states")
    tr.save_states(f)
    tr.load_states(f)                # momentum restored from the STORE
    with pytest.raises(mx.base.MXNetError, match="update_on_kvstore"):
        tr.update(2)


# ------------------------------------- ISSUE 10: collective deadlines
def test_collective_timeout_fires_and_recovers(monkeypatch):
    """A kv.timeout stall past MXTPU_COLLECTIVE_TIMEOUT_MS raises the
    typed CollectiveTimeout (counted per op); once the schedule is
    exhausted the same store keeps working under the deadline."""
    from mxnet_tpu import fault
    from mxnet_tpu.observability import registry
    monkeypatch.setenv("MXTPU_COLLECTIVE_TIMEOUT_MS", "100")
    kv = kvstore.create("ici")
    a = jnp.ones((4,))
    c0 = registry().counter("kv_collective_timeouts", op="allreduce").value
    fault.inject("kv.timeout", at=[1], action="stall", delay=0.6)
    try:
        with pytest.raises(kvstore.CollectiveTimeout) as ei:
            kv.allreduce_([a], layout="replicated", key="w")
        assert ei.value.op == "allreduce" and ei.value.timeout_ms == 100
        assert registry().counter("kv_collective_timeouts",
                                  op="allreduce").value == c0 + 1
        out = kv.allreduce_([a], layout="replicated", key="w")
        np.testing.assert_array_equal(np.asarray(out), np.ones(4))
    finally:
        fault.clear()


def test_collective_deadline_propagates_inner_errors(monkeypatch):
    """A collective that FAILS (rather than hangs) under the deadline
    re-raises its own error, not a timeout."""
    from mxnet_tpu import fault
    monkeypatch.setenv("MXTPU_COLLECTIVE_TIMEOUT_MS", "500")
    kv = kvstore.create("ici")
    fault.inject("kv.collective", at=[1])
    try:
        with pytest.raises(fault.FaultInjected):
            kv.allreduce_([jnp.ones(2)], layout="replicated")
    finally:
        fault.clear()


def test_collective_timeout_env_malformed_disables(monkeypatch):
    from mxnet_tpu.fault import retry as retry_mod
    monkeypatch.setenv("MXTPU_COLLECTIVE_TIMEOUT_MS", "soon")
    retry_mod._warned_env.discard("MXTPU_COLLECTIVE_TIMEOUT_MS")
    assert kvstore.collective_timeout_ms() == 0.0
    kv = kvstore.create("ici")        # and the fast path still works
    out = kv.allreduce_([jnp.ones(3)], layout="replicated")
    np.testing.assert_array_equal(np.asarray(out), np.ones(3))
