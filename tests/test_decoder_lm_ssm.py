"""Layers of ONE sub-layer in `models.decoder_lm` (Mamba-2 state-space
layers, ungated relu^2 experts with a wider shared expert, softmax
attention without a gate at 16 query heads a KV head) and the second
recurrent state in `serve.lm_runtime`, at a small size on the CPU: hidden
64, the pattern M E M * E M E, 4 Mamba-2 heads of 8 over a state of 16 in
2 groups, 32 query heads over 2 KV heads of 16, 16 experts top-4 of 32
wide (4 held) and a shared expert of 48. The plain reference is the
benchmark's (`benchmarks/reference/nemotron3_nano_ep2.py`), fed the
model's own arrays."""
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
from benchmarks.lib import lm as blm, lm_ssm  # noqa: E402
from benchmarks.reference import nemotron3_nano_ep2 as ref  # noqa: E402

VOCAB = 50
PATTERN = ("mamba", "moe", "mamba", "gqa", "moe", "mamba", "moe")


def spec_of(held=(0, 4), experts=16, **kw):
    args = dict(hidden=64, heads=32, kv_heads=2, head_dim=16, kda_heads=0,
                kda_head_dim=0, conv_kernel=4, num_experts=experts, top_k=4,
                expert_width=32, held_lo=held[0], held_n=held[1],
                scaling=2.5, eps=1e-5, pattern=PATTERN, paired=False,
                attn_gate=False, expert_act="relu2", shared_width=48,
                ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
                ssm_chunk=8)
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
    weights, dims = lm_ssm.reference_weights(model), blm.dims(model.spec)
    fwd = jax.jit(ref.forward, static_argnums=(1,),
                  static_argnames=("low", "leave_out"))
    return lambda tokens, **how: {
        k: np.asarray(v) if not isinstance(v, list) else
        [np.asarray(a) for a in v]
        for k, v in fwd(weights, dims, jnp.asarray(tokens, jnp.int32),
                        **how).items()}


# ------------------------------------------------------------- the model
def test_gluon_forward_agrees_with_the_plain_reference(model, reference):
    """Lengths that are not whole chunks of 8: the block pads the scan."""
    toks = np.random.default_rng(1).integers(0, VOCAB, (2, 29))
    out = model(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert out.shape == (2, 29, VOCAB)
    for b in range(2):
        want = reference(toks[b])
        np.testing.assert_allclose(out[b], want["logits"], atol=1e-4)
        assert (want["routing"][[0, 2, 3, 5]] == -1).all()
        assert (want["routing"][[1, 4, 6]] >= 0).all()
        assert len(want["state"]) == len(want["tails"]) == 3


def test_parameters_of_layers_of_one_sub_layer(model):
    names = list(model.collect_params().keys())

    def block(i):
        return [n.split(f"decoderblock{i}_")[1] for n in names
                if f"decoderblock{i}_" in n]

    assert block(0) == ["norm1_gamma", "mixer_in_weight", "mixer_conv_weight",
                        "mixer_conv_bias", "mixer_dt_bias", "mixer_a_log",
                        "mixer_d_skip", "mixer_norm_gamma", "mixer_o_weight"]
    assert block(1) == ["norm2_gamma", "moe_router_weight", "moe_router_bias",
                        "moe_experts_up", "moe_experts_down", "moe_shared_up",
                        "moe_shared_down"]
    assert block(3) == ["norm1_gamma", "mixer_qkv_weight", "mixer_o_weight"]
    shapes = {n.split("_layers_")[-1]: p.shape
              for n, p in model.collect_params().items()}
    inner, conv = model.spec.ssm_dims()
    assert (inner, conv) == (32, 32 + 2 * 2 * 16)
    assert shapes["decoderblock0_mixer_in_weight"] == (inner + conv + 4, 64)
    assert shapes["decoderblock0_mixer_conv_weight"] == (4, conv)
    # no gate: the first expert matrix is (width, d), not (d, 2 width); the
    # shared expert has its own width; no gate's rows in attention
    assert shapes["decoderblock1_moe_experts_up"] == (4, 32, 64)
    assert shapes["decoderblock1_moe_experts_down"] == (4, 32, 64)
    assert shapes["decoderblock1_moe_shared_up"] == (48, 64)
    assert shapes["decoderblock1_moe_shared_down"] == (64, 48)
    assert shapes["decoderblock3_mixer_qkv_weight"] == ((32 + 4) * 16, 64)
    w = dlm.lm_weights(model)["layers"]
    assert sorted(w[0]) == ["mixer", "norm1_gamma"]
    assert sorted(w[1]) == ["moe", "norm2_gamma"]
    assert model.spec.sublayers()[:4] == (("mamba", None), (None, "moe"),
                                          ("mamba", None), ("gqa", None))


def test_bad_patterns_are_refused_by_kind():
    with pytest.raises(MXNetError, match="'mamba'.*'moe'.*one sub-layer"):
        dlm.DecoderLM(VOCAB, spec_of(pattern=("mamba", "ssm")))
    with pytest.raises(MXNetError, match="pair's mixer"):
        dlm.DecoderLM(VOCAB, spec_of(pattern=("mamba", "moe"), paired=True))
    with pytest.raises(MXNetError, match="ffn is a pair's"):
        dlm.DecoderLM(VOCAB, spec_of(ffn=("moe",) * 7))
    with pytest.raises(MXNetError, match="ssm_heads"):
        dlm.DecoderLM(VOCAB, spec_of(ssm_groups=3))
    with pytest.raises(MXNetError, match="expert_act"):
        dlm.DecoderLM(VOCAB, spec_of(expert_act="gelu"))
    # a pair may hold a Mamba-2 mixer too, and an ungated attention
    pair = dlm.DecoderLM(VOCAB, spec_of(pattern=("mamba", "gqa"),
                                        paired=True))
    assert pair.spec.sublayers() == (("mamba", "moe"), ("gqa", "moe"))


@pytest.mark.parametrize("control", [
    {"low": "all"}, {"leave_out": "d_skip"}, {"leave_out": "gate"},
    {"leave_out": "conv_bias"}, {"leave_out": "dt_bias"},
    {"leave_out": "relu"}, {"leave_out": "shared"},
    {"leave_out": "scaling"}, {"leave_out": "one_norm"}],
    ids=["low_all", "no_d_skip", "no_gate", "no_conv_bias", "no_dt_bias",
         "relu_for_relu2", "no_shared", "scaling_1", "one_norm"])
def test_every_control_of_the_reference_differs(reference, control):
    toks = np.random.default_rng(2).integers(0, VOCAB, 40)
    full, low = reference(toks), reference(toks, **control)
    off = np.abs(full["logits"] - low["logits"]).max() \
        / np.abs(full["logits"]).max()
    assert off > 1e-2, off


def test_the_reference_in_bfloat16_state_rounds_the_state_only(reference):
    """`low="state"`: every value the state holds is a bfloat16, and the
    logits move by the rounding alone (1.6e-2 of the largest seen)."""
    toks = np.random.default_rng(2).integers(0, VOCAB, 40)
    full, low = reference(toks), reference(toks, low="state")
    off = np.abs(full["logits"] - low["logits"]).max() \
        / np.abs(full["logits"]).max()
    assert 0 < off < 5e-2, off
    for exact, rounded in zip(full["state"], low["state"]):
        bits = np.asarray(rounded, np.float32).view(np.uint32)
        assert (bits & 0xFFFF == 0).all()
        assert (np.asarray(exact).view(np.uint32) & 0xFFFF != 0).any()


# ------------------------------------------------------------ the server
def _server(model, **kw):
    args = dict(slots=2, page_size=8, max_prompt_len=24, max_new_tokens=12,
                eos_id=-1, prefix_cache=False, engine_driven=False)
    args.update(kw)
    return mx.serve.Server(model, **args)


def _teacher_forced(srv, prompt, forced, slot=0):
    """(logits the runtime gives for `forced` fed one a turn after
    `prompt`'s prefill, the slot's state and tails a Mamba-2 layer
    then), through one slot, the page pool and the slot state."""
    rt, pool = srv.runtime, srv.pool
    seq = list(prompt) + list(forced)
    pages = pool.alloc(pool.pages_for(len(seq)))
    tables = np.zeros((rt.slots, rt.max_pages_per_slot), np.int32)
    tables[slot, :len(pages)] = pages
    rt.prefill(slot, prompt, pages)
    active = np.zeros((rt.slots,), np.int32)
    active[slot] = 1
    lens = np.zeros((rt.slots,), np.int32)
    cur = np.zeros((rt.slots,), np.int32)
    out = []
    for t in range(len(forced) + 1):
        lens[slot] = len(prompt) - 1 + t
        cur[slot] = seq[len(prompt) - 1 + t]
        _, lg = rt.decode(tables, lens, cur, active)
        out.append(np.asarray(lg[slot]))
    pool.free(pages)
    return (np.stack(out), [np.asarray(s[slot]) for s in rt.ssm_state],
            [np.asarray(c[slot]) for c in rt.conv_tails])


@pytest.mark.parametrize("n_prompt,kernel", [(1, False), (7, False),
                                             (24, False), (13, True)],
                         ids=["1", "7", "24", "13-kernel"])
def test_prefill_then_decode_gives_the_reference_logits_and_state(
        model, reference, n_prompt, kernel, monkeypatch):
    """Prefill (the chunked scan, stopped at the prompt's end), then
    decode turns through the slot's state and pages, against the
    reference's full forward: logits, the state and the convolution's
    tails the slot is left with."""
    if kernel:
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(n_prompt)
    srv = _server(model)
    assert srv.runtime._plen == 32                 # whole chunks and pages
    prompt = rng.integers(0, VOCAB, n_prompt)
    forced = rng.integers(0, VOCAB, 9)
    got, state, tails = _teacher_forced(srv, prompt, forced)
    seq = np.concatenate([prompt, forced])
    want = reference(seq)
    np.testing.assert_allclose(got, want["logits"][n_prompt - 1:], atol=1e-4)
    assert len(state) == 3 and state[0].shape == (4, 8, 16)
    for mine, theirs in zip(state, want["state"]):
        assert mine.dtype == np.float32
        np.testing.assert_allclose(mine, theirs, atol=1e-4)
    for mine, theirs in zip(tails, want["tails"]):
        np.testing.assert_allclose(mine, theirs, atol=1e-4)
    # a second request through the other slot; then the first slot again:
    # prefill overwrote its state
    for slot, n in ((1, 5), (0, 3)):
        prompt2 = rng.integers(0, VOCAB, n)
        got, _, _ = _teacher_forced(srv, prompt2, forced[:3], slot=slot)
        want = reference(np.concatenate([prompt2, forced[:3]]))
        np.testing.assert_allclose(got, want["logits"][n - 1:], atol=1e-4)
    assert srv.pool.in_use() == 0
    srv.close()


def _greedy(reference, prompt, n, width=40):
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((width,), np.int64)
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(reference(padded)["logits"][len(seq) - 1])))
    return seq[len(prompt):]


def test_server_generates_the_references_greedy_tokens(model, reference):
    """Five requests over two slots (slots and pages reused) on the
    engine loop: ONE prefill and ONE decode executable, two kinds of
    state side by side."""
    from mxnet_tpu.observability import registry
    rng = np.random.default_rng(5)
    srv = _server(model, engine_driven=True)
    prompts = [rng.integers(0, VOCAB, n) for n in (1, 5, 17, 24, 9)]
    hs = [srv.submit(p, max_new_tokens=4 + i) for i, p in enumerate(prompts)]
    got = [h.result(timeout=300) for h in hs]
    for p, g in zip(prompts, got):
        assert g == _greedy(reference, p, len(g))
    rt = srv.runtime
    assert srv.wait(timeout=60) and srv.pool.in_use() == 0
    assert rt.decode_traces == 1 and rt.prefill_traces == 1
    moe = rt.moe_counters()
    turns = srv.scheduler.decode_turns
    experts, others = [1, 4, 6], [0, 2, 3, 5]
    # layers without experts count nothing; a prefill stops after the
    # last Mamba-2 layer, so the last expert layer sees decode turns only
    assert moe["rows"].shape == (7, 4) and not moe["rows"][others].any()
    assert not moe["dispatches"][others].any()
    assert not moe["touched"][others].any()
    assert list(moe["dispatches"][experts]) == [turns + 5, turns + 5, turns]
    assert np.asarray(rt.routing["decode"]).shape == (7, rt.slots, 4)
    assert (np.asarray(rt.routing["decode"])[others] == -1).all()
    assert (np.asarray(rt.routing["prefill"])[others + [6]] == -1).all()
    assert (np.asarray(rt.routing["prefill"])[[1, 4], :3] >= 0).all()
    pages = srv.pool.num_pages
    assert rt.kda_state == [] and rt.latent_pages == []
    assert [s.shape for s in rt.ssm_state] == [(2, 4, 8, 16)] * 3
    assert all(s.dtype == jnp.float32 for s in rt.ssm_state)
    assert [c.shape for c in rt.conv_tails] == [(2, 3, 96)] * 3
    assert rt.slot_state_bytes() == 3 * 2 * (4 * 8 * 16 + 3 * 96) * 4
    assert registry().gauge("serve_slot_state_bytes").value \
        == rt.slot_state_bytes()
    assert rt.kv_bytes_per_page() == 2 * 2 * 16 * 8 * 4    # one "gqa" layer
    assert [p.shape for p in rt._state["k"]] == [(pages, 8, 32)]
    srv.close()


@pytest.mark.parametrize("kw,word", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"speculative_k": 1}, "speculative_k"),
], ids=["prefix_cache", "speculative_k"])
def test_page_reuse_is_refused_beside_state_space_layers(model, kw, word):
    with pytest.raises(MXNetError,
                       match=word + r".*recurrent \(KDA or state-space\)"):
        _server(model, **kw)


def test_a_failed_state_is_made_anew_with_both_kinds(model):
    srv = _server(model)
    rt = srv.runtime
    pages = srv.pool.alloc(2)
    rt.prefill(1, np.arange(1, 12), pages)
    assert all(np.asarray(s[1]).any() for s in rt.ssm_state)
    assert all(np.asarray(c[1]).any() for c in rt.conv_tails)
    assert not np.asarray(rt.ssm_state[0][0]).any()       # the other slot
    srv.pool.free(pages)
    rt.reset_pages()
    assert all(not np.asarray(s).any() for s in rt.ssm_state)
    assert all(not np.asarray(c).any() for c in rt.conv_tails)
    srv.close()


# ---------------------------------------------------------- expert layer
def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_relu2_experts_add_up_to_the_uncut_layer(shares):
    """The guide's share test for THIS expert layer (ungated relu^2
    experts, a shared expert of its own width, scaling 2.5): the parts
    that the shares of the 16 experts give (two: experts 0-7 and 8-15, as
    the deployment's 0-63 and 64-127), the shared expert counted once,
    add up to what the layer that holds all 16 gives, and to the uncut
    reference's layer."""
    rng = np.random.default_rng(10)
    full_spec = spec_of(held=(0, 16))
    whole = seeded(dlm.MoELayer(full_spec), 11)
    w = whole.weights()
    assert "experts_gate_up" not in w and "shared_gate_up" not in w
    x = jnp.asarray(rng.normal(size=(37, 64)).astype(np.float32))
    full, counts, ids = dlm.moe_forward(w, full_spec, x)
    assert int(counts.sum()) == 37 * 4              # dropless
    shared = _relu2(x @ w["shared_up"].T) @ w["shared_down"].T
    total, n = shared, 16 // shares
    for lo in range(0, 16, n):
        part = dict(w, experts_up=w["experts_up"][lo:lo + n],
                    experts_down=w["experts_down"][lo:lo + n])
        y, c, _ = dlm.moe_forward(part, spec_of(held=(lo, n)), x)
        np.testing.assert_array_equal(c, counts[lo:lo + n])
        total = total + (y - shared)
    np.testing.assert_allclose(total, full, atol=1e-4)
    names = {"router_weight": "router", "experts_up": "up",
             "experts_down": "down"}
    p = {names.get(k, k): v for k, v in w.items()}
    theirs, their_ids, slack = ref._experts(
        ref._How(None, None), p, dict(blm.dims(full_spec)), x, None)
    np.testing.assert_allclose(total, theirs, atol=1e-4)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(their_ids, -1))
    assert float(jnp.abs(slack).max()) == 0.0
    _, wts = dlm.moe_route(w, full_spec, x)
    np.testing.assert_allclose(wts.sum(-1), 2.5, atol=1e-5)


def test_grouped_matmul_takes_a_bank_kept_out_by_in(monkeypatch):
    """`nt`: each group's matrix (out, in), a width that is not whole
    lane tiles taken as one block; the kernel (interpret mode) and the
    einsum give the row-by-row product."""
    from mxnet_tpu.ops import grouped_matmul as gmm
    rng = np.random.default_rng(12)
    groups, k, n, tile = 3, 128, 144, 8
    group = jnp.asarray(rng.integers(0, groups + 1, 40), jnp.int32)
    dest, tile_group, used, counts, src, _ = gmm.layout(group, groups, tile)
    x = jnp.asarray(rng.normal(size=(40, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(groups, n, k)).astype(np.float32))
    rows = x[src]
    want = np.einsum("tk,tnk->tn", x, np.asarray(w)[np.minimum(group, 2)])
    for interpret in ("0", "1"):
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", interpret)
        text = str(jax.make_jaxpr(lambda *a: gmm.grouped_matmul(
            *a, tile, nt=True))(rows, w, tile_group, used))
        assert ("mxtpu_gmm" in text) == (interpret == "1")
        out = gmm.grouped_matmul(rows, w, tile_group, used, tile, nt=True)
        held = np.asarray(group) < groups
        np.testing.assert_allclose(
            np.asarray(out)[np.asarray(dest)[held]], want[held], rtol=1e-4,
            atol=1e-4)


# ---------------------------------------------------------------- attention
def test_ungated_attention_at_sixteen_query_heads_a_kv_head(monkeypatch):
    """32 query heads over 2 KV heads, no gate: the sequence form against
    the reference's masked softmax, and one decode position through the
    flat pools against the sequence's last row (lax and kernel)."""
    spec = spec_of(head_dim=128)
    layer = seeded(dlm.GatedAttention(spec), 13, std=0.1)
    w = layer.weights()
    assert list(w) == ["o_weight", "qkv_weight"] or set(w) == {
        "qkv_weight", "o_weight"}
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(21, 64)).astype(np.float32))
    y, k, v = dlm.gqa_sequence(w, spec, x)
    want = ref._attention(ref._How(None, None),
                          {"qkv": w["qkv_weight"], "o": w["o_weight"]},
                          dict(blm.dims(spec)), x)
    np.testing.assert_allclose(y, want, atol=2e-4)
    # the last position as a decode turn: its K/V written, 20 cached
    psize, pages = 8, [3, 1, 2]
    for interpret in ("0", "1"):
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", interpret)
        pools = [jnp.zeros((5, psize, 2 * 128), jnp.float32)
                 .at[jnp.asarray(pages)].set(jnp.pad(
                     a[:20].reshape(20, -1), ((0, 4), (0, 0)))
                     .reshape(3, psize, -1)) for a in (k, v)]
        tables = jnp.asarray([pages + [0]], jnp.int32)
        out, _, _ = dlm.mx_gqa(w, x[20:], *pools, tables,
                               jnp.asarray([20], jnp.int32),
                               jnp.asarray([2], jnp.int32),
                               jnp.asarray([4], jnp.int32), spec=spec)
        np.testing.assert_allclose(out[0], want[20], atol=2e-4)
