"""Expert parallelism (mxnet_tpu/shard/moe.py + gluon.nn.ShardedMoE,
ISSUE 16): top-k routing math vs a per-token reference, the 2-all-to-all
expert-parallel captured step, capacity-overflow drop accounting (loud,
exact, residual pass-through), aux-loss gradient flow, per-param axis
overrides in the rule syntax, and elastic resize keeping the fast path."""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, shard
from mxnet_tpu.base import MXNetError
from mxnet_tpu.observability import registry
from mxnet_tpu.shard import moe as smoe

B, D, H, E = 8, 16, 16, 4
_rng = np.random.RandomState(0)
X = _rng.randn(B, D).astype(np.float32)
Y = _rng.randn(B, D).astype(np.float32)


def _moe_params(rng, e=E, d=D, h=H, scale=0.3):
    return (rng.randn(e, d).astype(np.float32) * scale,       # gate
            rng.randn(e, d, h).astype(np.float32) * 0.1,      # w1
            rng.randn(e, h).astype(np.float32) * 0.01,        # b1
            rng.randn(e, h, d).astype(np.float32) * 0.1,      # w2
            rng.randn(e, d).astype(np.float32) * 0.01)        # b2


def _reference_moe(x, gw, w1, b1, w2, b2, k, cap):
    """Per-token numpy reference with GShard k-major drop priority:
    first choices of every token outrank all second choices; within a
    choice tier, batch order. Returns (y, n_dropped)."""
    N = x.shape[0]
    logits = x @ gw.T
    z = np.exp(logits - logits.max(-1, keepdims=True))
    probs = z / z.sum(-1, keepdims=True)
    top_e = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    top_p = np.take_along_axis(probs, top_e, axis=-1)
    if k > 1:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)
    used = {e_: 0 for e_ in range(E)}
    y = np.zeros_like(x)
    dropped = 0
    for c in range(k):                      # choice-major = k-major
        for n in range(N):
            e_ = int(top_e[n, c])
            if used[e_] >= cap:
                dropped += 1
                continue
            used[e_] += 1
            h_ = np.maximum(x[n] @ w1[e_] + b1[e_], 0.0)
            y[n] += top_p[n, c] * (h_ @ w2[e_] + b2[e_])
    return y, dropped


def _mesh22():
    return shard.make_mesh_2d(dp=2, tp=2)


class _MoENet(gluon.nn.HybridBlock):
    """Dense stem + one ShardedMoE layer (the stem keeps the MoE
    input cotangent live, matching real stacks)."""

    def __init__(self, **kw):
        moe_kw = {k: kw.pop(k) for k in
                  ("k", "capacity_factor", "aux_loss_coef") if k in kw}
        super().__init__(**kw)
        with self.name_scope():
            self.proj = gluon.nn.Dense(D, in_units=D)
            self.moe = gluon.nn.ShardedMoE(D, H, num_experts=E,
                                           **moe_kw)

    def hybrid_forward(self, Fm, x):
        return self.moe(self.proj(x))


def _build(seed=0, **moe_kw):
    mx.random.seed(seed)
    net = _MoENet(**moe_kw)
    net.initialize(mx.init.Xavier())
    net(nd.array(X))
    return net


def _capture(net, sharded=True):
    lossf = gluon.loss.L2Loss()
    if sharded:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore="ici")
        tr.shard(mesh={"dp": 2, "tp": 2})
    else:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
    return tr, tr.capture(lambda a, b: lossf(net(a), b).mean())


# ------------------------------------------------------- routing math
def test_capacity_and_layout_reasons():
    assert smoe.capacity(8, 4, 2, 1.25) == 5    # ceil(1.25*2*8/4)
    assert smoe.capacity(8, 4, 1, 0.25) == 1    # floor of 1
    lay = smoe.routing_layout(B, E, 2, 1.25)
    assert not lay["sharded"] and lay["reason"] == "no_mesh"
    mesh = _mesh22()
    lay = smoe.routing_layout(B, E, 2, 1.25, mesh=mesh, axis="tp",
                              data_axis="dp")
    assert lay["sharded"] and lay["reason"] is None
    assert lay["n_exp_shards"] == 2 and lay["n_tok_shards"] == 4
    assert lay["tokens_local"] == 2 and lay["capacity"] == 2
    # degenerate axis -> local, with the reason recorded
    m1 = shard.make_mesh_2d(dp=4, tp=1)
    lay = smoe.routing_layout(B, E, 2, 1.25, mesh=m1, axis="tp",
                              data_axis="dp")
    assert not lay["sharded"] and lay["reason"] == "axis_size_1"
    lay = smoe.routing_layout(B, 3, 2, 1.25, mesh=mesh, axis="tp",
                              data_axis="dp")
    assert lay["reason"] == "experts_not_divisible"
    lay = smoe.routing_layout(7, E, 2, 1.25, mesh=mesh, axis="tp",
                              data_axis="dp")
    assert lay["reason"] == "tokens_not_divisible"


@pytest.mark.parametrize("k", [1, 2])
def test_local_routing_matches_reference(k):
    """Generous capacity (no drops): the fused dispatch/combine equals
    the per-token loop for top-1 and top-2."""
    gw, w1, b1, w2, b2 = _moe_params(np.random.RandomState(1))
    y, aux, frac, drops = smoe.moe_forward(
        jnp.asarray(X), gw, w1, b1, w2, b2, n_experts=E, k=k,
        capacity_factor=4.0)
    ref, ref_drops = _reference_moe(X, gw, w1, b1, w2, b2, k=k,
                                    cap=smoe.capacity(B, E, k, 4.0))
    assert ref_drops == 0 and float(drops) == 0 and float(frac) == 0
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-6)
    assert float(aux) > 0                # E*sum f_e P_e, Switch §2.2


def test_capacity_overflow_drop_accounting():
    """Tight capacity: the drop count matches the k-major reference
    EXACTLY, dropped (token, choice) pairs contribute exactly zero to
    the combine (the residual pass-through contract), and gradients
    still flow through the kept tokens and the router."""
    gw, w1, b1, w2, b2 = _moe_params(np.random.RandomState(2))
    cap = smoe.capacity(B, E, 1, 0.25)
    assert cap == 1
    y, aux, frac, drops = smoe.moe_forward(
        jnp.asarray(X), gw, w1, b1, w2, b2, n_experts=E, k=1,
        capacity_factor=0.25)
    ref, ref_drops = _reference_moe(X, gw, w1, b1, w2, b2, k=1, cap=cap)
    assert ref_drops > 0
    assert float(drops) == ref_drops
    assert float(frac) == pytest.approx(ref_drops / float(B))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-6)
    # dropped tokens: the reference row is exactly zero -> ours too
    zero_rows = np.where(np.all(ref == 0.0, axis=1))[0]
    assert zero_rows.size > 0
    assert np.all(np.asarray(y)[zero_rows] == 0.0)

    def loss(xv, gwv):
        yv, auxv, _, _ = smoe.moe_forward(
            xv, gwv, w1, b1, w2, b2, n_experts=E, k=1,
            capacity_factor=0.25)
        return jnp.sum(yv * yv) + auxv

    dx, dg = jax.grad(loss, argnums=(0, 1))(jnp.asarray(X), gw)
    assert float(jnp.max(jnp.abs(dx))) > 0
    assert float(jnp.max(jnp.abs(dg))) > 0


def test_local_path_lowers_with_zero_collectives():
    """No mesh, and a mesh whose expert axis has size 1, both lower to
    ZERO collectives — the degenerate-mesh contract."""
    gw, w1, b1, w2, b2 = _moe_params(np.random.RandomState(3))
    from mxnet_tpu.observability.compilex import analyze_jit
    args = (jnp.asarray(X), gw, w1, b1, w2, b2)
    info = analyze_jit(jax.jit(lambda *a: smoe.moe_forward(
        *a, n_experts=E, k=2)), *args)
    assert info["collective_total"] == 0
    m1 = shard.make_mesh_2d(dp=4, tp=1)
    info = analyze_jit(jax.jit(lambda *a: smoe.moe_forward(
        *a, n_experts=E, k=2, mesh=m1, axis="tp", data_axis="dp")),
        *args)
    assert info["collective_total"] == 0


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs a (2,2) mesh")
def test_sharded_matches_local_bitwise():
    """The (dp,tp) token-sharded dispatch is BITWISE the local path:
    same routing decisions, same outputs, real data movement."""
    gw, w1, b1, w2, b2 = _moe_params(np.random.RandomState(4))
    y_l, _, f_l, d_l = smoe.moe_forward(
        jnp.asarray(X), gw, w1, b1, w2, b2, n_experts=E, k=2,
        capacity_factor=4.0)
    mesh = _mesh22()
    y_s, _, f_s, d_s = jax.jit(lambda *a: smoe.moe_forward(
        *a, n_experts=E, k=2, capacity_factor=4.0, mesh=mesh,
        axis="tp", data_axis="dp"))(jnp.asarray(X), gw, w1, b1, w2, b2)
    np.testing.assert_array_equal(np.asarray(y_l), np.asarray(y_s))
    assert float(d_l) == float(d_s) == 0


# ------------------------------------------------- captured fast path
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs a (2,2) mesh")
def test_captured_moe_step_contract(monkeypatch):
    """The headline contract in one warm run: the step publishes as
    `moe_step`, the HLO holds EXACTLY A2A_PER_LAYER * STEP_TRAVERSALS
    all-to-alls for one layer, 1 dispatch + zero sync H2D through the
    device prefetcher, the per-step `moe_all_to_all` byte counter
    matches `a2a_bytes_per_step`, drop accounting accumulates, and
    publish_metrics lands it all in the registry."""
    from mxnet_tpu import profiler
    # inspect THIS compile even if another test file on the same xdist
    # worker compiled a `moe_step` first (default policy: first only)
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    from mxnet_tpu.observability import compilex
    from mxnet_tpu.prefetch import DevicePrefetcher

    net = _build()
    tr, step = _capture(net, sharded=True)
    a2a = registry().counter("kv_collective_bytes", op="moe_all_to_all")
    a0 = a2a.value
    step(nd.array(X), nd.array(Y))
    lay = smoe.routing_layout(B, E, 2, 1.25, mesh=_mesh22(), axis="tp",
                              data_axis="dp")
    per_step = smoe.a2a_bytes_per_step(lay, E, D, 4)
    assert per_step > 0
    assert a2a.value - a0 == per_step
    assert step.last_fallback_reason is None

    sync = registry().counter("prefetch_h2d_sync")
    pf = DevicePrefetcher(((X, Y) for _ in range(3)),
                          capture_spec=tr._kvstore)
    before = sync.value
    for xb, yb in pf:
        profiler.reset_dispatches()
        step(xb, yb)
        assert profiler.dispatch_count() <= 2
        assert step.last_fallback_reason is None
    pf.close()
    assert sync.value == before
    assert step.cache_size == 1

    info = step.hlo_info()
    assert info["collectives"].get("all-to-all") == \
        smoe.A2A_PER_LAYER * smoe.STEP_TRAVERSALS
    assert "moe_step" in compilex.instrumented()
    assert a2a.value - a0 == 4 * per_step      # every step priced

    # loud accounting: aux params updated in-step, registry on publish
    frac = float(net.moe.overflow_frac.data().asnumpy()[0])
    assert 0.0 <= frac <= 1.0
    stats = net.moe.publish_metrics()
    assert stats["aux_loss"] > 0
    g = registry().gauge("moe_overflow_frac", layer=net.moe.name)
    assert g.value == pytest.approx(frac)
    if stats["dropped"] > 0:
        c = registry().counter("moe_tokens_dropped", layer=net.moe.name)
        assert c.value >= stats["dropped"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs a (2,2) mesh")
def test_sharded_vs_replicated_captured_parity():
    """Same net, same data: the (2,2) expert-parallel captured step and
    the replicated captured step produce matching losses and final
    expert banks. aux_loss_coef=0 keeps the per-slice aux averaging
    difference out of the loss head, and capacity_factor=4 keeps BOTH
    paths drop-free — capacity is per source device, so a tight factor
    legitimately drops different tokens locally vs sharded."""
    net_s = _build(seed=7, aux_loss_coef=0.0, capacity_factor=4.0)
    _, step_s = _capture(net_s, sharded=True)
    net_r = _build(seed=7, aux_loss_coef=0.0, capacity_factor=4.0)
    _, step_r = _capture(net_r, sharded=False)
    for _ in range(3):
        ls = float(step_s(nd.array(X), nd.array(Y)).asnumpy())
        lr = float(step_r(nd.array(X), nd.array(Y)).asnumpy())
        np.testing.assert_allclose(ls, lr, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        net_s.moe.expert_ffn1_weight.data().asnumpy(),
        net_r.moe.expert_ffn1_weight.data().asnumpy(),
        rtol=1e-3, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs a (2,2) mesh")
def test_aux_loss_flows_into_captured_loss_and_gradients():
    """The captured loss head includes coef * aux exactly on the first
    step (same init), the aux param records the unscaled aux, and a
    nonzero coefficient changes the router update."""
    coef = 0.5
    net_0 = _build(seed=9, aux_loss_coef=0.0)
    _, step_0 = _capture(net_0, sharded=True)
    net_c = _build(seed=9, aux_loss_coef=coef)
    _, step_c = _capture(net_c, sharded=True)
    l0 = float(step_0(nd.array(X), nd.array(Y)).asnumpy())
    lc = float(step_c(nd.array(X), nd.array(Y)).asnumpy())
    aux = float(net_c.moe.aux_loss.data().asnumpy()[0])
    assert aux > 0
    np.testing.assert_allclose(lc - l0, coef * aux, rtol=1e-4,
                               atol=1e-6)
    # the aux gradient reached the router: gate updates differ
    g0 = net_0.moe.gate_weight.data().asnumpy()
    gc = net_c.moe.gate_weight.data().asnumpy()
    assert not np.allclose(g0, gc)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs a (2,2) mesh")
def test_resize_mesh_keeps_fast_path():
    """(2,2) -> (1,2): the expert banks redistribute, training
    continues without fallback, and the routing all-to-alls stay live
    (tp is still 2) — the byte counter keeps incrementing."""
    net = _build()
    tr, step = _capture(net, sharded=True)
    step(nd.array(X), nd.array(Y))
    w = net.moe.expert_ffn1_weight.data().asnumpy().copy()
    tr.resize_mesh({"dp": 1, "tp": 2})
    np.testing.assert_array_equal(
        net.moe.expert_ffn1_weight.data().asnumpy(), w)
    a2a = registry().counter("kv_collective_bytes", op="moe_all_to_all")
    a0 = a2a.value
    step(nd.array(X), nd.array(Y))
    assert step.last_fallback_reason is None
    assert a2a.value > a0
    assert not np.allclose(
        net.moe.expert_ffn1_weight.data().asnumpy(), w)


# ------------------------------------------------- rules & validation
def test_default_rules_route_expert_banks_to_tp():
    plan = shard.plan({"dp": 2, "tp": 2})
    assert tuple(plan.spec_for("shardedmoe0_expert_ffn1_weight",
                               (E, D, H))) == ("tp",)
    assert tuple(plan.spec_for("shardedmoe0_expert_ffn2_bias",
                               (E, D))) == ("tp",)
    # the router stays replicated (every device gates its own tokens)
    assert tuple(plan.spec_for("shardedmoe0_gate_weight",
                               (E, D))) == ()


def test_rule_axis_string_override_and_validation():
    """A bare axis-name string is row-shard-dim-0 shorthand, validated
    HARD against the mesh (unlike a P-spec, which downgrades)."""
    rules = ((r"(?:^|_)expert[^/]*_weight$", "dp"),) + \
        shard.DEFAULT_RULES
    plan = shard.plan({"dp": 2, "tp": 2}, rules=rules)
    assert tuple(plan.spec_for("x_expert_ffn1_weight",
                               (E, D, H))) == ("dp",)
    with pytest.raises(MXNetError, match="ep"):
        shard.plan({"dp": 2, "tp": 2},
                   rules=((r"expert", "ep"),))
    # P-spec with an unknown axis still downgrades (unchanged contract)
    plan = shard.plan({"dp": 2, "tp": 2},
                      rules=((r"expert", P("ep")),))
    assert tuple(plan.spec_for("x_expert_ffn1_weight",
                               (E, D, H))) == ()


def test_rules_json_round_trip():
    rules = ((r"(?:^|_)expert[^/]*_(?:weight|bias)$", "tp"),
             (r"dense\d+_weight$", P(None, "tp")),
             (r".*_bias$", None))
    data = shard.rules_to_json(rules)
    back = shard.rules_from_json(data)
    assert len(back) == len(rules)
    assert back[0] == rules[0]            # string stays a string
    assert back[2] == rules[2]
    assert tuple(back[1][1]) == tuple(rules[1][1])
    # and the codec output is plain-JSON serialisable
    import json
    json.loads(json.dumps(data))


def test_large_replicated_expert_bank_warns(monkeypatch):
    """A big expert bank that no rule shards warns LOUDLY and names the
    kind — same contract as the embedding tables."""
    monkeypatch.setenv("MXTPU_SHARD_WARN_BYTES", "1024")
    plan = shard.plan({"dp": 2, "tp": 2},
                      rules=((r"never_matches_zzz", None),))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spec = plan.spec_for("big_expert_ffn1_weight", (8, 64, 64))
    assert tuple(spec) == ()
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)]
    assert any("expert bank" in m for m in msgs)


# ------------------------------------------------------- block basics
def test_sharded_moe_block_validation():
    with pytest.raises(MXNetError, match="k="):
        gluon.nn.ShardedMoE(D, H, num_experts=4, k=5)
    with pytest.raises(MXNetError, match="capacity_factor"):
        gluon.nn.ShardedMoE(D, H, num_experts=4, capacity_factor=0)
    with pytest.raises(MXNetError, match="activation"):
        gluon.nn.ShardedMoE(D, H, num_experts=4, activation="zelu")
    net = _build()
    with pytest.raises(MXNetError, match="feature dim"):
        net.moe(nd.array(np.zeros((4, D + 1), np.float32)))


def test_eager_loop_owns_aux_loss():
    """Hand-written eager training: the block stashes the scaled aux on
    `last_aux_loss` for the caller (no capture to collect it), and the
    aux params update under autograd.record."""
    from mxnet_tpu import autograd
    net = _build(aux_loss_coef=0.1)
    with autograd.record():
        y = net(nd.array(X))
        assert net.moe.last_aux_loss is not None
        L = (y * y).mean() + net.moe.last_aux_loss
    L.backward()
    assert float(net.moe.aux_loss.data().asnumpy()[0]) > 0
    g = net.moe.gate_weight.grad()
    assert float(np.max(np.abs(g.asnumpy()))) > 0
