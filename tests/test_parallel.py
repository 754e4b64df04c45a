"""Parallelism tests on the virtual 8-device CPU mesh (SURVEY.md §2 #37-41):
each strategy must match its single-device reference numerically."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.parallel.mesh import make_mesh, shard_batch
from mxnet_tpu.parallel.ring_attention import ring_attention as _ring_attn
from mxnet_tpu.parallel import tensor_parallel as tp
from mxnet_tpu.parallel import pipeline as pp
from mxnet_tpu.parallel import moe as moe_mod
from mxnet_tpu.ops.pallas_kernels import attention_reference


def _layer_order(name):
    """Sort key that reads a layer's number as a number: as text, dense10
    sorts before dense9, and two nets' parameters then pair up wrongly
    whenever the process-wide layer counter crosses a power of ten."""
    return [int(t) if t.isdigit() else t for t in re.split(r"(\d+)", name)]


def test_make_mesh_and_shard_batch():
    mesh = make_mesh({"dp": 4, "tp": 2})
    assert dict(mesh.shape) == {"dp": 4, "tp": 2}
    x = jnp.arange(32.0).reshape(8, 4)
    xs = shard_batch(mesh, x, "dp")
    np.testing.assert_allclose(np.asarray(xs), np.asarray(x))


def test_ring_attention_matches_reference():
    mesh = make_mesh({"sp": 8})
    B, H, S, D = 2, 2, 64, 8
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, H, S, D))
               for kk in jax.random.split(key, 3))
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        ring = shard_map(
            lambda q_, k_, v_: _ring_attn(q_, k_, v_, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None))(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_match_reference():
    """Ring flash is differentiable end to end: grads through the lse
    merge + ppermute ring must equal full-attention grads."""
    mesh = make_mesh({"sp": 4})
    B, H, S, D = 1, 2, 32, 8
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (B, H, S, D))
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(jax.random.PRNGKey(9), (B, H, S, D))
    for causal in (False, True):
        # check_vma=False: matches ring_attention_sharded's own entry
        ring_f = shard_map(
            lambda q_, k_, v_: _ring_attn(q_, k_, v_, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False)

        g1 = jax.grad(lambda *a: (ring_f(*a) * w).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda *a: (attention_reference(*a, causal=causal) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


def test_ring_flash_pallas_interpret(monkeypatch):
    """SURVEY #42's ring FLASH claim: with 128-multiple shards the per-step
    block compute runs the real Pallas kernels (interpret mode on CPU) —
    fwd AND bwd (a kernel failure raises; there is no XLA fallback by
    exception)."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    mesh = make_mesh({"sp": 2})
    B, H, S, D = 1, 1, 256, 64            # 128 per shard -> pallas path
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(kk, (B, H, S, D))
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(jax.random.PRNGKey(5), (B, H, S, D))
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        # check_vma=False: the pallas HLO *interpreter* can't mix vma in
        # dynamic_slice (jax limitation; its error text suggests exactly
        # this flag). Real-TPU lowering works under check_vma=True — the
        # kernels carry vma on their out_shapes (_sds).
        ring_f = shard_map(
            lambda q_, k_, v_: _ring_attn(q_, k_, v_, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False)
        ring = ring_f(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   rtol=2e-3, atol=2e-4)
        # backward through the Pallas ring kernels
        g1 = jax.grad(lambda *a: (ring_f(*a) * w).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda *a: (attention_reference(*a, causal=causal) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)


def test_data_parallel_step_matches_single_device():
    from mxnet_tpu.parallel.data_parallel import make_train_step
    from mxnet_tpu.gluon import nn

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(3, in_units=16))
        net.initialize(mx.init.Xavier())
        return net

    mx.random.seed(3)
    net_a = build()
    # copy weights into net_b
    net_b = build()
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        # deep copy: the dp step donates its input buffers
        pb.set_data(nd.array(pa.data().asnumpy()))

    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    o1 = mx.optimizer.create("sgd", learning_rate=0.1)
    o2 = mx.optimizer.create("sgd", learning_rate=0.1)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 3)

    step_1, init_1 = make_train_step(net_a, loss, o1)
    s1 = init_1()
    s1, l1 = step_1(s1, x, y, 0.1, jax.random.PRNGKey(0))

    mesh = make_mesh({"dp": 8})
    step_8, init_8 = make_train_step(net_b, loss, o2, mesh=mesh)
    s8 = init_8()
    s8, l8 = step_8(s8, shard_batch(mesh, x), shard_batch(mesh, y), 0.1,
                    jax.random.PRNGKey(0))
    assert abs(float(l1) - float(l8)) < 1e-5
    # the two nets carry different auto-prefixes; match params positionally
    for n1, n8 in zip(sorted(s1[0], key=_layer_order),
                      sorted(s8[0], key=_layer_order)):
        np.testing.assert_allclose(np.asarray(s1[0][n1]),
                                   np.asarray(s8[0][n8]), rtol=1e-5,
                                   atol=1e-6, err_msg=f"{n1} vs {n8}")


def test_tensor_parallel_dense_matches_dense():
    mesh = make_mesh({"tp": 8})
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 16))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (16, 32)) * 0.1
    want = jnp.matmul(jax.nn.relu(jnp.matmul(x, w1.T)), w2.T)

    def fn(x_, w1_, w2_):
        h = jax.nn.relu(tp.column_parallel_dense(x_, w1_, mesh=mesh))
        return tp.row_parallel_dense(h, w2_, mesh=mesh)

    with mesh:
        got = jax.jit(fn, in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P("tp", None)),
            NamedSharding(mesh, P(None, "tp"))))(x, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pp": 4})
    key = jax.random.PRNGKey(0)
    ws = [jax.random.normal(k, (8, 8)) * 0.3
          for k in jax.random.split(key, 4)]
    stacked = pp.stack_stage_params([{"w": w} for w in ws])
    x = jax.random.normal(jax.random.PRNGKey(9), (6, 4, 8))  # (micro, mb, D)

    def stage_fn(params, h):
        return jnp.tanh(jnp.matmul(h, params["w"]))

    got = pp.pipeline_apply(stage_fn, stacked, x, mesh)
    want = x
    for w in ws:
        want = jnp.tanh(jnp.matmul(want, w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_moe_sharded_matches_dense():
    mesh = make_mesh({"ep": 4})
    params = moe_mod.init_moe_params(jax.random.PRNGKey(0), 4, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8))
    # capacity >= tokens so nothing drops; sharded == unsharded
    out_ref, aux_ref = moe_mod.moe_ffn(params, x, capacity_factor=4.0)
    specs = moe_mod.moe_param_specs()
    sharded = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)), params, specs)
    with mesh:
        out_sh, aux_sh = jax.jit(
            lambda p, xx: moe_mod.moe_ffn(p, xx, capacity_factor=4.0))(
            sharded, x)
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out_ref),
                               rtol=1e-4, atol=1e-5)


def test_trainer_kvstore_dp_allreduce():
    """gluon.Trainer with kvstore aggregates multi-device grads."""
    from mxnet_tpu.gluon import nn
    net = nn.Dense(2, in_units=2)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 1.0}, kvstore="local")
    w0 = net.weight.data().asnumpy().copy()
    x = nd.ones((4, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    w1 = net.weight.data().asnumpy()
    assert not np.allclose(w0, w1)


def test_data_parallel_remat_matches():
    """make_train_step(remat=True) rematerialises the forward on backward
    — memory trade only, identical math."""
    from mxnet_tpu.parallel.data_parallel import make_train_step
    from mxnet_tpu.gluon import nn
    from mxnet_tpu import gluon

    def build():
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(3, in_units=16))
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 8)))
        return net

    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 3)

    outs = []
    for remat in (False, True):
        net = build()
        step, init_state = make_train_step(net, loss, opt, remat=remat)
        state = init_state()
        state, l = step(state, x, y, 0.1, jax.random.PRNGKey(2))
        outs.append((jax.tree_util.tree_map(np.asarray, state[0]), float(l)))
    (p0, l0), (p1, l1) = outs
    assert np.isclose(l0, l1, rtol=1e-6)
    # the two nets carry different auto-prefixes; compare positionally
    for k0, k1 in zip(sorted(p0, key=_layer_order),
                      sorted(p1, key=_layer_order)):
        np.testing.assert_allclose(p0[k0], p1[k1], rtol=1e-6, atol=1e-7)


def test_ulysses_attention_matches_reference():
    """All-to-all (Ulysses) sequence parallelism: full-attention numerics
    with sequence-sharded inputs, heads divided across the axis."""
    from mxnet_tpu.parallel import ulysses_attention_sharded
    mesh = make_mesh({"sp": 8})
    B, S, H, D = 2, 64, 8, 8
    key = jax.random.PRNGKey(3)
    # (B, S, H, D) layout: sequence axis second, as activations flow
    q, k, v = (jax.random.normal(kk, (B, S, H, D))
               for kk in jax.random.split(key, 3))
    for causal in (False, True):
        ref = attention_reference(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal)   # (B, H, S, D)
        out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(out, 1, 2)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_ulysses_grads_match_reference():
    # H=8 over sp=4: two heads per device, so the head-block ordering of
    # the all_to_all split/concat is actually exercised (H/P=1 would be
    # trivially self-inverse)
    from mxnet_tpu.parallel import ulysses_attention_sharded
    mesh = make_mesh({"sp": 4})
    B, S, H, D = 1, 32, 8, 8
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(kk, (B, S, H, D))
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, D))

    def uly_loss(q_, k_, v_):
        return (ulysses_attention_sharded(q_, k_, v_, mesh,
                                          causal=True) * w).sum()

    def ref_loss(q_, k_, v_):
        out = attention_reference(
            jnp.swapaxes(q_, 1, 2), jnp.swapaxes(k_, 1, 2),
            jnp.swapaxes(v_, 1, 2), causal=True)
        return (jnp.swapaxes(out, 1, 2) * w).sum()

    g1 = jax.grad(uly_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_ulysses_rejects_indivisible_heads():
    from mxnet_tpu.parallel import ulysses_attention_sharded
    mesh = make_mesh({"sp": 8})
    q = jnp.zeros((1, 16, 4, 8))  # 4 heads over 8 devices
    with pytest.raises(Exception, match="divisible"):
        ulysses_attention_sharded(q, q, q, mesh)


def test_zero_sharded_optimizer_state_matches_replicated():
    """zero=True (ZeRO-1 / arXiv:2004.13336): optimizer state shards over
    dp with identical training numerics; momentum leaves really live
    sharded (1/P per device)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    def build():
        net = nn.HybridSequential()
        # explicit prefixes: the functional state rides jit pytrees,
        # whose dict flatten SORTS keys — auto-counter names would make
        # the two builds' sorted orders diverge at 9->10 boundaries
        net.add(nn.Dense(32, in_units=16, activation="relu",
                         prefix="l1_"),
                nn.Dense(8, in_units=32, prefix="l2_"))
        net.initialize()
        return net

    mesh = make_mesh({"dp": 8})
    rs = np.random.RandomState(0)
    X = rs.randn(32, 16).astype(np.float32)
    Y = rs.randn(32, 8).astype(np.float32)

    results = []
    for zero in (False, True):
        mx.random.seed(7)
        np.random.seed(7)
        net = build()
        tr = DataParallelTrainer(net, gloss.L2Loss(),
                                 mx.optimizer.SGD(learning_rate=0.1,
                                                  momentum=0.9),
                                 mesh, zero=zero)
        losses = [float(tr.step(nd.array(X), nd.array(Y)))
                  for _ in range(4)]
        params, opt_state, _ = tr.state
        results.append((losses, {k: np.asarray(v) for k, v in
                                 params.items()}, opt_state))

    (l0, p0, _), (l1, p1, opt1) = results
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    # identical explicit prefixes: compare by NAME (the product also
    # addresses by name — order through jit pytrees is sorted-keys)
    assert sorted(p0) == sorted(p1)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    # the big momentum leaf is genuinely dp-sharded
    from jax.sharding import NamedSharding
    sharded = [leaf for leaf in jax.tree_util.tree_leaves(opt1)
               if hasattr(leaf, "sharding")
               and isinstance(leaf.sharding, NamedSharding)
               and "dp" in str(leaf.sharding.spec)]
    assert sharded, "no optimizer-state leaf is dp-sharded under zero=True"
