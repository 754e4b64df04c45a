"""The decoder-only language model (`models.decoder_lm`), its ops and
its serving runtime, at a small size on the CPU: hidden 64, four layers
in the published pattern (GQA, KDA, KDA, KDA), 16 experts top-4 of which 4
are held, 2 KV heads under 8 query heads. The plain reference is the
benchmark's (`benchmarks/reference/solar_open2_ep8.py`), fed the model's
own arrays."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.fault import injection as finj
from mxnet_tpu.models import decoder_lm as dlm
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import grouped_matmul as gmm
from mxnet_tpu.ops import kda
from mxnet_tpu.ops import nn_ops
from mxnet_tpu.ops import pallas_kernels as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import lm as blm  # noqa: E402
from benchmarks.reference import solar_open2_ep8 as ref  # noqa: E402

VOCAB = 50


def spec_of(held=(0, 4), pattern=("gqa", "kda", "kda", "kda")):
    return dlm.LMSpec(hidden=64, heads=8, kv_heads=2, head_dim=16,
                      kda_heads=4, kda_head_dim=16, conv_kernel=4,
                      num_experts=16, top_k=4, expert_width=32,
                      held_lo=held[0], held_n=held[1], scaling=1.0,
                      eps=1e-5, pattern=pattern)


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
    weights, dims = blm.reference_weights(model), blm.dims(model.spec)
    fwd = jax.jit(ref.logits, static_argnums=(1, 3))
    return lambda tokens, low=None: np.asarray(
        fwd(weights, dims, jnp.asarray(tokens, jnp.int32), low))


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


# ------------------------------------------------------------- the model
def test_gluon_forward_agrees_with_the_plain_reference(model, reference):
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, (2, 21)).astype(np.int32)
    out = model(mx.nd.array(toks)).asnumpy()
    assert out.shape == (2, 21, VOCAB)
    for b in range(2):
        np.testing.assert_allclose(out[b], reference(toks[b]), atol=1e-4)


def test_the_reference_below_the_configurations_precision_differs(reference):
    toks = np.random.default_rng(2).integers(0, VOCAB, 40)
    want = reference(toks)
    scale = np.abs(want).max()
    state = np.abs(reference(toks, "state") - want).max() / scale
    low = np.abs(reference(toks, "all") - want).max() / scale
    assert 1e-5 < state < low and low > 1e-2


# ------------------------------------------------------------ the server
def _server(model, **kw):
    args = dict(slots=2, page_size=8, max_prompt_len=24, max_new_tokens=12,
                eos_id=-1, prefix_cache=False, engine_driven=False)
    args.update(kw)
    return mx.serve.Server(model, **args)


def _teacher_forced(srv, prompt, forced):
    """Logits the runtime gives for `forced` fed one a turn after
    `prompt`'s prefill, through slot 0, the page pool and the slot state."""
    rt, pool = srv.runtime, srv.pool
    pages = pool.alloc(pool.pages_for(len(prompt) + len(forced)))
    tables = np.zeros((rt.slots, rt.max_pages_per_slot), np.int32)
    tables[0, :len(pages)] = pages
    rt.prefill(0, prompt, pages)
    active = np.zeros((rt.slots,), np.int32)
    active[0] = 1
    lens = np.zeros((rt.slots,), np.int32)
    cur = np.zeros((rt.slots,), np.int32)
    seq = list(prompt) + list(forced)
    out = []
    for t in range(len(forced) + 1):
        lens[0] = len(prompt) - 1 + t
        cur[0] = seq[len(prompt) - 1 + t]
        _, lg = rt.decode(tables, lens, cur, active)
        out.append(np.asarray(lg[0]))
    pool.free(pages)
    return np.stack(out)


@pytest.mark.parametrize("n_prompt", [1, 7, 24])
def test_prefill_then_decode_gives_the_reference_logits(model, reference,
                                                        n_prompt):
    rng = np.random.default_rng(n_prompt)
    srv = _server(model)
    prompt = rng.integers(0, VOCAB, n_prompt)
    forced = rng.integers(0, VOCAB, 9)
    got = _teacher_forced(srv, prompt, forced)
    want = reference(np.concatenate([prompt, forced]))[n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4)
    # a second request through the same slot: prefill overwrote the state
    prompt2 = rng.integers(0, VOCAB, 5)
    got = _teacher_forced(srv, prompt2, forced[:3])
    want = reference(np.concatenate([prompt2, forced[:3]]))[4:]
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert srv.pool.in_use() == 0
    srv.close()


def _greedy(reference, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(reference(np.asarray(seq))[-1])))
    return seq[len(prompt):]


def test_server_generates_the_references_greedy_tokens(model, reference):
    """Five requests over two slots (slots reused), a decode fault in the
    middle (every running request requeued and prefilled again), and
    `prompt_tokens` forced one a turn after the prompt."""
    rng = np.random.default_rng(5)
    srv = _server(model, max_retries=2)
    prompts = [rng.integers(0, VOCAB, n) for n in (1, 5, 17, 24, 9)]
    finj.inject("serve.decode", at=[4])
    try:
        hs = [srv.submit(p, max_new_tokens=4 + i)
              for i, p in enumerate(prompts)]
        extra = rng.integers(0, VOCAB, 3)
        forced = srv.submit(prompts[1], max_new_tokens=3,
                            prompt_tokens=extra)
        got = [h.result(timeout=300) for h in hs]
        got_forced = forced.result(timeout=300)
    finally:
        finj.clear("serve.decode")
    assert sum(h.retries for h in hs) >= 1
    for p, g in zip(prompts, got):
        assert g == _greedy(reference, p, len(g))
    assert got_forced == _greedy(
        reference, np.concatenate([prompts[1], extra]), 3)
    rt = srv.runtime
    assert rt.decode_traces == 1 and rt.prefill_traces == 1
    assert srv.pool.in_use() == 0
    moe = rt.moe_counters()
    assert moe["rows"].shape == (4, 4) and moe["rows"].sum() > 0
    # every dispatch (a decode turn or a prefill) touched 0..4 experts a
    # layer; a prefill runs every layer's experts but the last's
    turns = srv.scheduler.decode_turns
    assert (moe["dispatches"][:-1] >= turns + 6).all()
    assert turns <= moe["dispatches"][-1] <= moe["dispatches"][0] - 6
    assert (moe["touched"] <= 4 * moe["dispatches"]).all()
    assert (np.asarray(rt.routing["prefill"])[-1] == -1).all()
    assert (np.asarray(rt.routing["prefill"])[:-1] >= 0).all()
    assert np.asarray(rt.routing["decode"]).shape == (4, rt.slots, 4)
    assert (moe["touched"] > 0).all()
    assert all(s.dtype == jnp.float32 for s in rt.kda_state)
    assert rt.slot_state_bytes() == 3 * 2 * (4 * 16 * 16 * 4
                                             + 3 * 3 * 4 * 16 * 4)
    srv.close()


@pytest.mark.parametrize("ends", ["by_length", "on_eos"])
def test_a_backlog_looks_ahead_and_keeps_every_token(model, reference, ends):
    """ISSUE 35 over `LMRuntime`: 3 x slots requests of mixed lengths
    queued at once run with a turn in flight (n+1 dispatched before n is
    read, the token fed back on the device, the slot's recurrent state
    written in dispatch order); the same requests a wave at a time never
    look ahead. Both give the reference's greedy tokens through ONE
    decode executable, a direct `decode` call included, and every launch
    was read: the last layer's experts ran once a committed turn."""
    from mxnet_tpu.observability import registry
    rng = np.random.default_rng(35)
    prompts = [rng.integers(0, VOCAB, n) for n in (3, 24, 1, 9, 17, 6)]
    budgets = [9, 4, 12, 7, 3, 10]
    want = [_greedy(reference, p, n) for p, n in zip(prompts, budgets)]
    eos_id = -1
    if ends == "on_eos":
        eos_id = want[0][3]
        want = [t[:t.index(eos_id) + 1] if eos_id in t else t for t in want]
        assert any(len(t) < n for t, n in zip(want, budgets))
    srv = _server(model, eos_id=eos_id)
    sched, rt = srv.scheduler, srv.runtime
    hs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    sched.run_until_idle()
    assert [h.result() for h in hs] == want
    ahead = sched.lookahead_turns
    assert 0 < ahead < sched.decode_turns
    assert rt.moe_counters()["dispatches"][-1] == sched.decode_turns
    assert srv.pool.in_use() == 0
    waves = []
    for i in range(0, 6, 2):
        hs = [srv.submit(p, max_new_tokens=n)
              for p, n in zip(prompts[i:i + 2], budgets[i:i + 2])]
        sched.run_until_idle()
        waves += [h.result() for h in hs]
    assert waves == want and sched.lookahead_turns == ahead
    compiles = registry().counter("compiles", executable="serve_lm_decode")
    before = compiles.value
    s_n = rt.slots
    rt.decode(np.zeros((s_n, rt.max_pages_per_slot), np.int32),
              np.zeros((s_n,), np.int32), np.zeros((s_n,), np.int32),
              np.zeros((s_n,), np.int32))
    assert rt.decode_traces == 1 and rt.prefill_traces == 1
    assert compiles.value == before
    assert rt.moe_counters()["dispatches"][-1] == sched.decode_turns + 1
    srv.close()


def test_the_previous_steps_tokens_feed_the_next_on_the_device(model):
    """`LMRuntime.decode_launch`'s `active` 2 / 1 / 0, as
    `DecodeRuntime`'s: the logits and the slots' recurrent state are
    those of feeding the chosen tokens from the host."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 9)]

    def run(on_device):
        srv = _server(model)
        rt, pool = srv.runtime, srv.pool
        tables = np.zeros((2, rt.max_pages_per_slot), np.int32)
        lens = np.zeros((2,), np.int32)
        cur = np.zeros((2,), np.int32)
        for s, p in enumerate(prompts):
            pages = pool.alloc(pool.pages_for(len(p) + 3))
            tables[s, :len(pages)] = pages
            rt.prefill(s, p, pages)
            lens[s], cur[s] = len(p) - 1, p[-1]
        ones = np.ones((2,), np.int32)
        out = []
        if on_device:
            reads = [rt.decode_launch(tables, lens, cur, ones)]
            # the host's tokens are wrong on purpose: they must not count
            reads += [rt.decode_launch(tables, lens + t, cur + 1, 2 * ones)
                      for t in (1, 2)]
            out = [np.asarray(r()[1]) for r in reads]
        else:
            for t in range(3):
                cur, lg = rt.decode(tables, lens + t, cur, ones)
                out.append(np.asarray(lg))
        state = [np.asarray(a) for a in rt.kda_state]
        assert rt.decode_traces == 1
        srv.close()
        return out, state

    (fed, fed_state), (host, host_state) = run(True), run(False)
    for a, b in zip(fed + fed_state, host + host_state):
        assert np.array_equal(a, b)


def test_a_turns_admissions_are_one_dispatch_a_prompt_in_queue_order(model):
    """Over `LMRuntime` the scheduler's gathered admissions stay n
    dispatches for n prompts, in the order they were queued (a prompt
    reads every weight: the device bounds this prefill)."""
    from mxnet_tpu.observability import registry
    srv = _server(model, slots=4, max_new_tokens=2)
    rt = srv.runtime
    seen, orig = [], rt.prefill
    rt.prefill = lambda slot, prompt, pages: (
        seen.append((slot, len(prompt), len(pages))),
        orig(slot, prompt, pages))[1]
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, n) for n in (3, 17, 9)]
    rows = registry().counter("serve_prefill_rows")
    d0, rows0 = mx.profiler.dispatch_count("serve_prefill"), rows.value
    hs = [srv.submit(p) for p in prompts]
    assert srv.scheduler.step().admitted == 3
    assert mx.profiler.dispatch_count("serve_prefill") - d0 == 3
    assert rows.value - rows0 == 3 and rt.prefill_traces == 1
    assert seen == [(0, 3, 1), (1, 17, 3), (2, 9, 2)]
    assert [h._slot for h in hs] == [0, 1, 2]
    assert all(len(h.result(timeout=300)) == 2 for h in hs)
    assert srv.pool.in_use() == 0
    srv.close()


def test_server_runs_on_the_engine_loop(model, reference):
    srv = _server(model, engine_driven=True)
    prompt = np.arange(1, 8)
    assert srv.submit(prompt, max_new_tokens=5).result(timeout=300) \
        == _greedy(reference, prompt, 5)
    assert srv.wait(timeout=60) and srv.pool.in_use() == 0
    srv.close()


@pytest.mark.parametrize("kw,word", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative_k": 1}, "speculative_k"),
], ids=["prefix_cache", "speculative_k"])
def test_page_reuse_is_refused_for_recurrent_state(model, kw, word):
    with pytest.raises(MXNetError, match=word + ".*recurrent"):
        _server(model, **kw)


def test_page_reuse_is_refused_without_recurrent_layers_too():
    plain = seeded(dlm.DecoderLM(VOCAB, spec_of(pattern=("gqa", "gqa"))), 3)
    with pytest.raises(MXNetError, match="prefix_cache.*one dispatch"):
        _server(plain, prefix_cache=True)
    srv = _server(plain)
    assert len(srv.submit([3, 4, 5], max_new_tokens=4).result(timeout=300)) \
        == 4
    srv.close()


def test_low_precision_options_and_bad_prompts_are_refused(model):
    with pytest.raises(MXNetError, match="not offered"):
        _server(model, kv_dtype="int8")
    srv = _server(model)
    with pytest.raises(MXNetError, match="max_src_len"):
        srv.submit(np.arange(25))
    with pytest.raises(MXNetError, match="page budget"):
        srv.submit(np.arange(24), max_new_tokens=12,
                   prompt_tokens=np.arange(6))
    srv.close()


# ------------------------------------------------------------------- KDA
def _kda_inputs(rng, h, t, dk, dv):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(h, t, dk))).astype(np.float32)
    k = unit(rng.normal(size=(h, t, dk))).astype(np.float32)
    v = rng.normal(size=(h, t, dv)).astype(np.float32)
    g = -2 * np.abs(rng.normal(size=(h, t, dk))).astype(np.float32)
    beta = rng.uniform(0, 2, (h, t)).astype(np.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_scan_equals_the_one_token_update(chunk):
    rng = np.random.default_rng(chunk)
    q, k, v, g, beta = _kda_inputs(rng, 3, 64, 8, 16)
    s0 = rng.normal(size=(3, 8, 16)).astype(np.float32)
    o, s_end = kda.kda_chunked(q, k, v, g, beta, s0, chunk=chunk)
    s, outs = jnp.asarray(s0), []
    for t in range(64):
        o_t, s = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                              beta[:, t], s)
        outs.append(o_t)
    np.testing.assert_allclose(o, np.stack(outs, 1), atol=2e-5)
    np.testing.assert_allclose(s_end, s, atol=2e-5)


def test_kda_padding_leaves_the_state_alone():
    rng = np.random.default_rng(7)
    q, k, v, g, beta = _kda_inputs(rng, 2, 32, 8, 8)
    s0 = rng.normal(size=(2, 8, 8)).astype(np.float32)
    g[:, 20:], beta[:, 20:] = 0.0, 0.0
    o_all, padded = kda.kda_chunked(q, k, v, g, beta, s0, chunk=16)
    # only the chunks that hold the first `length` positions are run
    o_cut, cut = kda.kda_chunked(q, k, v, g, beta, s0, chunk=16,
                                 length=jnp.int32(9))
    np.testing.assert_allclose(o_cut[:, :16], o_all[:, :16], atol=2e-5)
    assert not np.asarray(o_cut[:, 16:]).any()
    o_cut, cut = kda.kda_chunked(q, k, v, g, beta, s0, chunk=16,
                                 length=jnp.int32(20))
    np.testing.assert_allclose(cut, padded, atol=2e-5)
    s = jnp.asarray(s0)
    for t in range(20):
        _, s = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            s)
    np.testing.assert_allclose(padded, s, atol=2e-5)


def test_kda_step_kernel_equals_the_plain_step(interpret, monkeypatch):
    rng = np.random.default_rng(8)
    q, k, v, g, beta = _kda_inputs(rng, 3, 4, 128, 16)   # "h" = slots here
    st = rng.normal(size=(3, 4, 16, 128)).astype(np.float32)
    o1, s1 = kda.kda_step_slots(q, k, v, g, beta, jnp.asarray(st))
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0")
    o0, s0 = kda.kda_step_slots(q, k, v, g, beta, jnp.asarray(st))
    np.testing.assert_allclose(o1, o0, atol=1e-4)
    np.testing.assert_allclose(s1, s0, atol=1e-5)


def test_causal_conv_step_continues_the_sequence():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(10, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    whole = kda.causal_conv(jnp.asarray(x), jnp.asarray(w))
    tail = jnp.zeros((1, 3, 6))
    for t in range(10):
        out, tail = kda.causal_conv_step(tail, jnp.asarray(x[t:t + 1]),
                                         jnp.asarray(w))
        np.testing.assert_allclose(out[0], whole[t], atol=1e-5)


# ---------------------------------------------------------- expert layer
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that the four shares of the 16
    experts give, the shared expert counted once, add up to what the
    layer that holds all 16 gives."""
    rng = np.random.default_rng(10)
    whole = seeded(dlm.MoELayer(spec_of(held=(0, 16))), 11)
    w = whole.weights()
    x = jnp.asarray(rng.normal(size=(37, 64)).astype(np.float32))
    full, counts, _ = dlm.moe_forward(w, spec_of(held=(0, 16)), x)
    assert int(counts.sum()) == 37 * 4              # dropless
    shared = nn_ops.swiglu(x, w["shared_gate_up"], w["shared_down"])
    total = shared
    for lo in range(0, 16, 4):
        part = dict(w, experts_gate_up=w["experts_gate_up"][lo:lo + 4],
                    experts_down=w["experts_down"][lo:lo + 4])
        y, c, _ = dlm.moe_forward(part, spec_of(held=(lo, 4)), x)
        np.testing.assert_array_equal(c, counts[lo:lo + 4])
        total = total + (y - shared)
    np.testing.assert_allclose(total, full, atol=1e-4)
    # and the share the plain reference computes is the program's share
    part = dict(w, experts_gate_up=w["experts_gate_up"][4:8],
                experts_down=w["experts_down"][4:8])
    mine, _, ids = dlm.moe_forward(part, spec_of(held=(4, 4)), x)
    names = {"router_weight": "router", "experts_gate_up": "gate_up",
             "experts_down": "down"}
    p = {names.get(k, k): v for k, v in part.items()}
    theirs, their_ids, slack = ref._experts(
        ref._How(None, None), p, dict(blm.dims(spec_of(held=(4, 4)))), x,
        None)
    np.testing.assert_allclose(mine, theirs, atol=1e-4)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(their_ids, -1))
    assert float(jnp.abs(slack).max()) == 0.0
    # forced onto other ids the reference computes those, and says what
    # the forcing cost: the lowest used score under its own k-th largest
    forced = jnp.asarray(their_ids).at[:, 0].set(
        (jnp.max(their_ids, -1) + 1) % 16)
    other, used, slack = ref._experts(
        ref._How(None, None), p, dict(blm.dims(spec_of(held=(4, 4)))), x,
        forced)
    np.testing.assert_array_equal(used, forced)
    assert float(slack.min()) >= 0.0 and float(slack.max()) > 0.0
    assert float(jnp.abs(other - theirs).max()) > 1e-3


def test_no_token_is_dropped_however_uneven_the_routing():
    spec = spec_of(held=(0, 4))
    layer = seeded(dlm.MoELayer(spec), 12)
    w = dict(layer.weights())
    bias = np.zeros(16, np.float32)
    bias[2] = 10.0                          # every token chooses expert 2
    w["router_bias"] = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(13).normal(size=(40, 64)),
                    jnp.float32)
    valid = jnp.arange(40) < 33
    y, counts, _ = dlm.moe_forward(w, spec, x, valid)
    assert int(counts[2]) == 33
    theirs, _, _ = ref._experts(ref._How(None, None), {
        "router": w["router_weight"], "router_bias": w["router_bias"],
        "gate_up": w["experts_gate_up"], "down": w["experts_down"],
        "shared_gate_up": w["shared_gate_up"],
        "shared_down": w["shared_down"]}, dict(blm.dims(spec)), x, None)
    np.testing.assert_allclose(y[:33], theirs[:33], atol=1e-4)


def test_grouped_matmul_kernel_equals_the_row_by_row_product(interpret):
    rng = np.random.default_rng(14)
    groups, k, n, tile, rows = 5, 128, 256, 8, 64
    group = rng.integers(0, groups + 1, rows).astype(np.int32)
    group[:20] = 2
    x = rng.normal(size=(rows, k)).astype(np.float32)
    w = rng.normal(size=(groups, k, n)).astype(np.float32)
    dest, tile_group, used, counts, src, _ = gmm.layout(jnp.asarray(group),
                                                        groups, tile)
    cap = gmm.rows_capacity(rows, groups, tile)
    laid = jnp.asarray(x)[src]
    out = np.asarray(gmm.grouped_matmul(laid, jnp.asarray(w), tile_group,
                                        used, tile))
    np.testing.assert_array_equal(
        counts, np.bincount(group, minlength=groups + 1)[:groups])
    for i in range(rows):
        if group[i] < groups:
            np.testing.assert_allclose(out[int(dest[i])], x[i] @ w[group[i]],
                                       atol=1e-3)
        else:
            assert int(dest[i]) == cap


def _layout_case(name):
    """(group (A,), groups, tile): `groups` in `group` is a row not taken."""
    rng = np.random.default_rng(15)
    groups, tile, rows = 5, 8, 64
    if name == "an_empty_group":
        group = rng.integers(0, groups + 1, rows)
        group[group == 3] = 1
    elif name == "whole_tiles_exactly":
        group = np.repeat([0, 2, groups, 4], 16)        # two tiles each
        rng.shuffle(group)
    elif name == "no_row_taken":
        group = np.full(rows, groups)
    elif name == "one_group_takes_every_row":
        group = np.full(rows, 2)
    elif name == "valid_rows_left_out":                  # as the layer does
        idx = rng.integers(0, 16, (16, 4))              # 16 tokens, top-4
        mine = (idx >= 4) & (idx < 4 + groups) & (np.arange(16) < 11)[:, None]
        group = np.where(mine, idx - 4, groups).reshape(-1)
    else:
        group = rng.integers(0, groups + 1, rows)
    return np.asarray(group, np.int32), groups, tile


@pytest.mark.parametrize("case", [
    "random", "an_empty_group", "whole_tiles_exactly", "no_row_taken",
    "one_group_takes_every_row", "valid_rows_left_out"])
def test_the_layouts_inverse_map_names_the_row_that_lies_in_each_row(case):
    group, groups, tile = _layout_case(case)
    dest, tile_group, used, counts, src, live = map(
        np.asarray, gmm.layout(jnp.asarray(group), groups, tile))
    cap = gmm.rows_capacity(len(group), groups, tile)
    assert src.shape == live.shape == (cap,) and src.dtype == np.int32
    at = np.flatnonzero(live)
    # a live row holds the row of `group` whose place it is, of the tile's
    # group, in a used tile; rows of a group keep the order they came in
    np.testing.assert_array_equal(dest[src[at]], at)
    np.testing.assert_array_equal(group[src[at]], tile_group[at // tile])
    assert (at // tile < used).all()
    # and every row taken is some live row's: the two maps are inverses
    np.testing.assert_array_equal(np.sort(src[at]),
                                  np.flatnonzero(dest < cap))
    assert len(at) == int(counts.sum()) == int((group < groups).sum())
    # a row where none lies names SOME row, so that a gather stays in range
    assert ((src >= 0) & (src < len(group))).all()
    for g in range(groups):
        mine = src[at][group[src[at]] == g]
        np.testing.assert_array_equal(mine, np.sort(mine))


def _scatters(jaxpr):
    """Every scatter of a jaxpr and of the jaxprs its equations hold:
    (primitive name, the shape of the updates)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append((eqn.primitive.name, eqn.invars[2].aval.shape))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _scatters(sub)
    return found


def test_the_expert_layer_scatters_no_row_of_its_input():
    """The tiles' rows are GATHERED through the layout's inverse map: the
    only scatters left move int32 (the counts, a row's rank, the inverse
    map itself)."""
    spec = spec_of(held=(4, 4))
    w = seeded(dlm.MoELayer(spec), 16).weights()
    x = jnp.zeros((37, 64), jnp.float32)
    valid = jnp.arange(37) < 30
    found = _scatters(jax.make_jaxpr(
        lambda w, x, valid: dlm.moe_forward(w, spec, x, valid))(
            w, x, valid).jaxpr)
    assert found, "the walk sees layout's int32 scatters"
    assert all(shape in ((), (37 * 4,)) for _, shape in found), found
    # and the walk would have seen the scatter of rows
    rows = jax.make_jaxpr(lambda x, dest: jax.jit(
        lambda x, dest: jnp.zeros((200, 64)).at[dest].set(x, mode="drop"))(
            x, dest))(x, jnp.zeros((37,), jnp.int32)).jaxpr
    assert _scatters(rows) == [("scatter", (37, 64))]


# ------------------------------------------- grouped-KV paged attention
def _paged_case(rng, dtype, dh=128):
    """Pools page by page, (P, psize, H, dh): what `_dense_attention`
    reads; `_flat` and `_head_major` give the two forms a pool is kept in."""
    s, hq, h, psize, npg, pool = 4, 8, 2, 16, 11, 60
    q = jnp.asarray(rng.normal(size=(s, hq, dh)), dtype)
    kp = jnp.asarray(rng.normal(size=(pool, psize, h, dh)), dtype)
    vp = jnp.asarray(rng.normal(size=(pool, psize, h, dh)), dtype)
    lens = np.asarray([1, 17, 170, 30], np.int32)
    perm, c = rng.permutation(np.arange(1, pool)), 0
    tables = np.zeros((s, npg), np.int32)
    for i in range(s):
        n = -(-int(lens[i]) // psize)
        tables[i, :n] = perm[c:c + n]
        c += n
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lens)


def _flat(*pools):
    return [p.reshape(*p.shape[:2], -1) for p in pools]


def _head_major(*pools):
    return [p.transpose(2, 0, 1, 3) for p in pools]


def _dense_attention(q, kp, vp, tables, lens):
    out = []
    for i in range(q.shape[0]):
        n = int(lens[i])
        k = np.asarray(kp)[np.asarray(tables[i])].reshape(
            -1, *kp.shape[2:])[:n]
        v = np.asarray(vp)[np.asarray(tables[i])].reshape(
            -1, *vp.shape[2:])[:n]
        rep = q.shape[1] // kp.shape[2]
        kk, vv = (jnp.asarray(np.repeat(a, rep, 1).transpose(1, 0, 2)[None])
                  for a in (k, v))
        out.append(np.asarray(pk.attention_reference(
            q[i][None, :, None, :], kk, vv))[0, :, 0])
    return np.stack(out)


@pytest.mark.parametrize("form", ["lax", "head-major-pool",
                                  "kernel-flat-pool"])
def test_paged_attention_with_grouped_kv_heads(form, monkeypatch):
    """Off the chip the plain path; on it (interpret mode here) the
    flat-pool kernel takes the groups as they are, and head-major pools
    take the plain path over repeated KV heads."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0" if form == "lax"
                       else "1")
    q, kp, vp, tables, lens = _paged_case(np.random.default_rng(15),
                                          jnp.float32)
    want = _dense_attention(q, kp, vp, tables, lens)
    pools = (_flat if form == "kernel-flat-pool" else _head_major)(kp, vp)
    got = pk.ragged_paged_attention(q, *pools, tables, lens)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flat_pool_kernel_takes_a_window_and_bfloat16(interpret,
                                                      monkeypatch):
    rng = np.random.default_rng(16)
    q, kp, vp, tables, lens = _paged_case(rng, jnp.bfloat16)
    q4 = jnp.asarray(rng.normal(size=(4, 3, 8, 128)), jnp.bfloat16)
    got = pk.ragged_paged_attention(q4, *_flat(kp, vp), tables, lens)
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0")
    want = pk.ragged_paged_attention(q4, *_head_major(kp, vp), tables, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.05)


# -------------------------------------------------------------- gluon.nn
def test_rmsnorm_and_swiglu_blocks():
    rng = np.random.default_rng(17)
    x = rng.normal(size=(3, 5, 8)).astype(np.float32)
    norm = seeded(mx.gluon.nn.RMSNorm(8, epsilon=1e-5), 18)
    g = norm.gamma.data().asnumpy()
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(norm(mx.nd.array(x)).asnumpy(), want,
                               atol=1e-5)
    ffn = seeded(mx.gluon.nn.SwiGLU(8, 12), 19)
    gu, down = (p.data().asnumpy() for p in (ffn.gate_up_weight,
                                             ffn.down_weight))
    gate, up = x @ gu[:12].T, x @ gu[12:].T
    want = (gate / (1 + np.exp(-gate)) * up) @ down.T
    np.testing.assert_allclose(ffn(mx.nd.array(x)).asnumpy(), want,
                               atol=1e-5)
