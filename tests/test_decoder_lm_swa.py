"""Sliding-window and full attention mixed in `models.decoder_lm` (the
"swa" mixer beside "gqa", RoPE and YaRN on both, a softmax router without
a shared expert) and the ring a slot in `serve.lm_runtime`, at a small
size on the CPU: hidden 64, the pattern S S S F, 8 query heads over 2 KV
heads of 16, a window of 32 over pages of 16 (a ring of 3), 8 experts
top-2 of 32 wide. The plain reference is the benchmark's
(`benchmarks/reference/mellum2_12b_l8.py`), fed the model's own arrays."""
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import decoder_lm as dlm
from mxnet_tpu.ndarray.ndarray import NDArray

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import harness, lm as blm, lm_swa  # noqa: E402
from benchmarks.reference import mellum2_12b_l8 as ref  # noqa: E402

VOCAB = 50
PATTERN = ("swa", "swa", "swa", "gqa")
YARN = (16.0, 64, 32.0, 1.0, 1.2772588722239782)


def spec_of(held=(0, 8), **kw):
    args = dict(hidden=64, heads=8, kv_heads=2, head_dim=16, kda_heads=0,
                kda_head_dim=0, conv_kernel=0, num_experts=8, top_k=2,
                expert_width=32, held_lo=held[0], held_n=held[1],
                scaling=1.0, eps=1e-6, pattern=PATTERN, attn_gate=False,
                router_bias=False, window=32, attn_rope=True,
                rope_theta=500000.0, rope_yarn=YARN,
                router_score="softmax", shared_expert=False)
    args.update(kw)
    return dlm.LMSpec(**args)


def seeded(block, seed, std=0.3):
    """Weights large enough that every term of every layer shows."""
    rng = np.random.default_rng(seed)
    for p in block.collect_params().values():
        v = std * rng.normal(size=p.shape).astype(np.float32)
        p.set_data(NDArray(jnp.asarray(1 + v if p.name.endswith("gamma")
                                       else v)))
    return block


@pytest.fixture(scope="module")
def model():
    return seeded(dlm.DecoderLM(VOCAB, spec_of()), 0)


@pytest.fixture(scope="module")
def reference(model):
    weights, dims = lm_swa.reference_weights(model), blm.dims(model.spec)
    fwd = jax.jit(ref.forward, static_argnums=(1,),
                  static_argnames=("low", "leave_out"))
    return lambda tokens, **how: {
        k: np.asarray(v) if not isinstance(v, list) else
        [np.asarray(a) for a in v]
        for k, v in fwd(weights, dims, jnp.asarray(tokens, jnp.int32),
                        **how).items()}


# ------------------------------------------------------ the positional term
def test_the_yarn_table_at_the_published_sizes():
    """`low`, `high`, the first and the last frequency and m, against the
    formulas of the configuration's `assumed.yarn`."""
    cfg = harness.load_json(harness.ROOT,
                            "benchmarks/configs/mellum2_12b_l8.json")
    spec = lm_swa.spec_of(cfg)
    assert dlm.yarn_range(spec) == (18, 35)

    def c(n):
        return 128 * math.log(8192 / (2 * math.pi * n)) \
            / (2 * math.log(500000))

    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    inv, m = dlm.attn_rope_table(spec, "gqa")
    plain, one = dlm.attn_rope_table(spec, "swa")
    inv, plain = np.asarray(inv), np.asarray(plain)
    assert m == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    assert one == 1.0 and inv.shape == plain.shape == (64,)
    i = np.arange(64)
    np.testing.assert_allclose(plain, 500000.0 ** (-i / 64), rtol=1e-5)
    assert plain[0] == 1.0 and plain[-1] == pytest.approx(
        500000 ** (-63 / 64), rel=1e-5)
    # under `low` a pair keeps its frequency, over `high` it turns 16
    # times slower, a linear ramp between
    np.testing.assert_array_equal(inv[:19], plain[:19])
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    ramp = (i[19:35] - 18) / 17
    np.testing.assert_allclose(
        inv[19:35], plain[19:35] * (1 - ramp) + plain[19:35] / 16 * ramp,
        rtol=1e-5)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        500000 ** (-63 / 64) / 16, rel=1e-5)
    # the reference builds the same table from the same keys, by itself
    f, scale = ref.rope_table(dict(blm.dims(spec)), "gqa",
                              ref._How(None, None))
    np.testing.assert_allclose(np.asarray(f), inv, rtol=1e-6)
    assert scale == m
    # a spec without `rope_yarn` rotates its full layers by the plain one
    bare, m_bare = dlm.attn_rope_table(spec._replace(rope_yarn=()), "gqa")
    np.testing.assert_array_equal(np.asarray(bare), plain)
    assert m_bare == 1.0


@pytest.mark.parametrize("kind", ["swa", "gqa"])
def test_rotation_of_a_sequence_is_rotation_a_position_at_a_time(kind):
    """`gqa_project` over positions 0..T-1 at once (the prefill) and one
    position at a time at any slot's length (the decode turn)."""
    spec = spec_of()
    rng = np.random.default_rng(2)
    w = {"qkv_weight": jnp.asarray(rng.normal(size=(12 * 16, 64)),
                                   jnp.float32)}
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    q, gate, k, v = dlm.gqa_project(w, spec, x, jnp.arange(40), kind)
    assert gate is None and q.shape == (40, 8, 16) and k.shape == (40, 2, 16)
    for t in (0, 1, 17, 39):
        q1, _, k1, v1 = dlm.gqa_project(w, spec, x[t:t + 1],
                                        jnp.asarray([t]), kind)
        np.testing.assert_allclose(np.asarray(q1[0]), np.asarray(q[t]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(k1[0]), np.asarray(k[t]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(v1[0]), np.asarray(v[t]),
                                   atol=1e-5)
    # position 0 is not turned, but scaled by m on a full layer; v never
    bare = dlm.gqa_project(w, spec._replace(attn_rope=False), x)
    m = YARN[4] if kind == "gqa" else 1.0
    np.testing.assert_allclose(np.asarray(q[0]), m * np.asarray(bare[0][0]),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(bare[3]))
    assert np.abs(np.asarray(k[5]) - m * np.asarray(bare[2][5])).max() > 0.1
    # the plain table is `rope`'s
    plain, _ = dlm.attn_rope_table(spec, "swa")
    np.testing.assert_array_equal(
        np.asarray(dlm.rotate(x[:, :16], jnp.arange(40), plain)),
        np.asarray(dlm.rope(x[:, :16], jnp.arange(40), 500000.0)))


# ---------------------------------------------------------- the expert layer
def _moe_weights(rng, spec):
    d, wd, n = spec.hidden, spec.expert_width, spec.held_n
    return {"router_weight": jnp.asarray(rng.normal(size=(spec.num_experts,
                                                         d)), jnp.float32),
            "experts_gate_up": jnp.asarray(
                0.3 * rng.normal(size=(n, d, 2 * wd)), jnp.float32),
            "experts_down": jnp.asarray(
                0.3 * rng.normal(size=(n, wd, d)), jnp.float32)}


def _uncut_layer(w, x, k):
    """softmax over all experts, top-k renormalised, every expert's
    SwiGLU, no shared expert: plain numpy."""
    logits = x @ np.asarray(w["router_weight"]).T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    idx = np.argsort(-p, -1)[:, :k]
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = p[t, idx[t]]
        for e, wt in zip(idx[t], chosen / chosen.sum()):
            gu = x[t] @ np.asarray(w["experts_gate_up"][e])
            g, u = np.split(gu, 2)
            y[t] += wt * ((g / (1 + np.exp(-g)) * u)
                          @ np.asarray(w["experts_down"][e]))
    return y, idx


def test_softmax_routing_without_a_shared_expert():
    spec = spec_of()
    rng = np.random.default_rng(4)
    w = _moe_weights(rng, spec)
    x = rng.normal(size=(11, 64)).astype(np.float32)
    idx, wts = dlm.moe_route(w, spec, jnp.asarray(x))
    want, want_idx = _uncut_layer(w, x, 2)
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-6)
    y, counts, chose = dlm.moe_forward(w, spec, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)
    assert int(np.asarray(counts).sum()) == 11 * 2
    # the sigmoid router gives other weights to the same experts
    _, sig = dlm.moe_route(w, spec._replace(router_score="sigmoid"),
                           jnp.asarray(x))
    assert np.abs(np.asarray(sig) - np.asarray(wts)).max() > 1e-2
    # the block has no shared expert's parameters, and says so
    block = dlm.MoELayer(spec)
    assert sorted(block.collect_params()._params) == sorted(
        block.prefix + n for n in ("router_weight", "experts_gate_up",
                                   "experts_down"))
    with pytest.raises(MXNetError, match="router_score"):
        dlm.MoELayer(spec._replace(router_score="tanh"))


@pytest.mark.parametrize("shares", [1, 2])
def test_the_shares_of_softmax_experts_add_up_to_the_uncut_layer(shares):
    """All 8 experts held is the uncut layer; two halves (0-3, 4-7), each
    routing over all 8 and adding its own experts' terms, add up to it:
    no shared expert to count once."""
    rng = np.random.default_rng(6)
    full = spec_of()
    w = _moe_weights(rng, full)
    x = rng.normal(size=(13, 64)).astype(np.float32)
    want, _ = _uncut_layer(w, x, 2)
    total, rows = np.zeros_like(x), 0
    each = 8 // shares
    for s in range(shares):
        spec = spec_of(held=(s * each, each))
        mine = dict(w, experts_gate_up=w["experts_gate_up"][s * each:
                                                            (s + 1) * each],
                    experts_down=w["experts_down"][s * each:(s + 1) * each])
        y, counts, _ = dlm.moe_forward(mine, spec, jnp.asarray(x))
        total += np.asarray(y)
        rows += int(np.asarray(counts).sum())
    np.testing.assert_allclose(total, want, atol=1e-4)
    assert rows == 13 * 2


# ------------------------------------------------------------- the model
def test_gluon_forward_agrees_with_the_plain_reference(model, reference):
    """70 positions: past two windows, so a window layer's mask shows."""
    toks = np.random.default_rng(1).integers(0, VOCAB, (2, 70))
    out = model(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert out.shape == (2, 70, VOCAB)
    for b in range(2):
        want = reference(toks[b])
        np.testing.assert_allclose(out[b], want["logits"], atol=2e-4)
        assert (want["routing"] >= 0).all() and len(want["keys"]) == 4
        assert want["keys"][0].shape == (70, 32)


def test_parameters_of_window_and_full_layers(model):
    names = list(model.collect_params().keys())

    def block(i):
        return [n.split(f"decoderblock{i}_")[1] for n in names
                if f"decoderblock{i}_" in n]

    for i in range(4):              # a window layer holds what a full one
        assert block(i) == ["norm1_gamma", "mixer_qkv_weight",
                            "mixer_o_weight", "norm2_gamma",
                            "moe_router_weight", "moe_experts_gate_up",
                            "moe_experts_down"]
    kinds = [type(b.mixer).__name__ for b in model.layers]
    assert kinds == ["WindowAttention"] * 3 + ["GatedAttention"]
    with pytest.raises(MXNetError, match="window"):
        dlm.DecoderLM(VOCAB, spec_of(window=0))
    with pytest.raises(MXNetError, match="'swa'"):
        dlm.DecoderLM(VOCAB, spec_of(pattern=("swa", "local")))


@pytest.mark.parametrize("control", ["rotation", "yarn", "attn_factor",
                                     "window", "sigmoid", "renorm"])
def test_every_control_of_the_reference_differs(reference, control):
    toks = np.random.default_rng(3).integers(0, VOCAB, (70,))
    full = reference(toks)["logits"]
    scale = np.abs(full).max()
    other = reference(toks, leave_out=control)["logits"]
    assert np.abs(other - full).max() / scale > 1e-2, control
    if control == "window":         # under the window nothing changes
        assert np.abs(other[:32] - full[:32]).max() / scale < 1e-5


# ------------------------------------------------------------ the server
def _server(model, **kw):
    args = dict(slots=2, page_size=16, max_prompt_len=100, max_new_tokens=12,
                eos_id=-1, prefix_cache=False)
    args.update(kw)
    return mx.serve.Server(model, **args)


def _greedy(reference, prompt, n, width=128):
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((width,), np.int64)
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(reference(padded)["logits"][len(seq) - 1])))
    return seq[len(prompt):]


def test_server_generates_the_references_greedy_tokens(model, reference):
    """Five requests over two slots (slots, rings and pages reused) on
    the engine loop: ONE prefill and ONE decode executable, a ring a slot
    beside the paged KV; prompts under the window, past it, and past two
    laps of the ring (48 rows)."""
    from mxnet_tpu.observability import registry
    rng = np.random.default_rng(5)
    srv = _server(model, engine_driven=True)
    prompts = [rng.integers(0, VOCAB, n) for n in (1, 20, 47, 100, 66)]
    hs = [srv.submit(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]
    got = [h.result(timeout=300) for h in hs]
    for p, g in zip(prompts, got):
        assert g == _greedy(reference, p, len(g))
    rt = srv.runtime
    assert srv.wait(timeout=60) and srv.pool.in_use() == 0
    assert rt.decode_traces == 1 and rt.prefill_traces == 1
    pages = srv.pool.num_pages
    assert pages == 2 * 7 + 1 and rt.ring == 3
    assert [k.shape for k, _ in rt.kv_pages] == [(pages, 16, 32)]
    assert [(k.shape, v.shape) for k, v in rt.ring_pages] \
        == [((2 * 3, 16, 32),) * 2] * 3
    assert rt.kda_state == rt.ssm_state == rt.latent_pages == []
    # the pool's budget is the full layer's; the rings have their gauge
    assert rt.kv_bytes_per_page() == 2 * 2 * 16 * 16 * 4
    assert rt.ring_cache_bytes() == 3 * 2 * (2 * 3 * 16 * 32) * 4
    assert registry().gauge("serve_ring_cache_bytes").value \
        == rt.ring_cache_bytes()
    assert rt.slot_state_bytes() == 0
    win = rt.window_counters()
    assert win["turns"] >= srv.scheduler.decode_turns > 0
    # each request's turns read min(len + 1, 32) keys a window layer
    want = sum(min(len(p) - 1 + t + 1, 32) for p, g in zip(prompts, got)
               for t in range(len(g)))
    assert want <= win["ring_tokens"] <= want + 32 * 5
    moe = rt.moe_counters()
    assert moe["rows"].shape == (4, 8) and moe["rows"].sum(1).all()
    srv.close()


@pytest.mark.parametrize("kw,word", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative_k": 1}, "speculative_k"),
], ids=["prefix_cache", "speculative_k"])
def test_page_reuse_is_refused_beside_a_ring(model, kw, word):
    with pytest.raises(MXNetError, match=word + r".*sliding-window.*ring"):
        _server(model, **kw)
