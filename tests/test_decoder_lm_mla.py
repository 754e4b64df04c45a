"""Multi-head latent attention in `models.decoder_lm`, its decode kernel
and its latent pages in `serve.lm_runtime`, at a small size on the CPU:
hidden 64, 4 heads of 16 + 8 (q, k) and 16 (v), ranks 32 / 24, one dense
layer and two expert layers (8 experts top-2 of which 4 are held) in
sandwich norms. The plain reference is the benchmark's
(`benchmarks/reference/pangu_ultra_ep16.py`, the EXPANDED form only), fed
the model's own arrays."""
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
from mxnet_tpu.ops import nn_ops
from mxnet_tpu.ops import pallas_kernels as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import lm as blm, lm_mla  # noqa: E402
from benchmarks.reference import pangu_ultra_ep16 as ref  # noqa: E402

VOCAB = 50


def spec_of(held=(0, 4), experts=8, **kw):
    args = dict(hidden=64, heads=4, kv_heads=0, head_dim=0, kda_heads=0,
                kda_head_dim=0, conv_kernel=0, num_experts=experts, top_k=2,
                expert_width=32, held_lo=held[0], held_n=held[1],
                scaling=2.5, eps=1e-5, pattern=("mla",) * 3, q_rank=32,
                kv_rank=24, nope_dim=16, rope_dim=8, v_dim=16,
                rope_theta=25600000.0, ffn=("dense", "moe", "moe"),
                dense_width=96, sandwich=True, router_bias=False)
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
    weights, dims = lm_mla.reference_weights(model), blm.dims(model.spec)
    fwd = jax.jit(ref.forward, static_argnums=(1,),
                  static_argnames=("low", "leave_out"))
    return lambda tokens, **how: {
        k: np.asarray(v) if not isinstance(v, list) else
        [np.asarray(a) for a in v]
        for k, v in fwd(weights, dims, jnp.asarray(tokens, jnp.int32),
                        **how).items()}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


# ------------------------------------------------------------- the model
def test_gluon_forward_agrees_with_the_plain_reference(model, reference):
    toks = np.random.default_rng(1).integers(0, VOCAB, (2, 21))
    out = model(mx.nd.array(toks.astype(np.int32))).asnumpy()
    assert out.shape == (2, 21, VOCAB)
    for b in range(2):
        np.testing.assert_allclose(out[b], reference(toks[b])["logits"],
                                   atol=1e-4)


def test_parameters_of_a_sandwich_block_and_of_the_older_pattern(model):
    names = sorted(model.collect_params().keys())
    block0 = [n.split("decoderblock0_")[1] for n in names
              if "decoderblock0_" in n]
    assert set(block0) == {
        "norm1_gamma", "norm1_post_gamma", "norm2_gamma", "norm2_post_gamma",
        "mixer_qa_weight", "mixer_qa_norm_gamma", "mixer_qb_weight",
        "mixer_kva_weight", "mixer_kv_norm_gamma", "mixer_kb_weight",
        "mixer_vb_weight", "mixer_o_weight", "ffn_gate_up_weight",
        "ffn_down_weight"}
    assert not any("router_bias" in n for n in names)
    # a spec that says nothing of the new fields builds what it built
    old = dlm.LMSpec(hidden=64, heads=8, kv_heads=2, head_dim=16,
                     kda_heads=4, kda_head_dim=16, conv_kernel=4,
                     num_experts=16, top_k=4, expert_width=32, held_lo=0,
                     held_n=4, scaling=1.0, eps=1e-5, pattern=("gqa", "kda"))
    assert (old.ffn_kinds(), old.sandwich, old.router_bias) \
        == (("moe", "moe"), False, True)
    block = [n.split("decoderblock0_")[1] for n in
             dlm.DecoderLM(VOCAB, old).collect_params().keys()
             if "decoderblock0_" in n]
    assert block == ["norm1_gamma", "mixer_qgkv_weight", "mixer_o_weight",
                     "norm2_gamma", "moe_router_weight", "moe_router_bias",
                     "moe_experts_gate_up", "moe_experts_down",
                     "moe_shared_gate_up", "moe_shared_down"]
    with pytest.raises(MXNetError, match="ffn"):
        dlm.DecoderLM(VOCAB, spec_of(ffn=("dense", "moe")))
    with pytest.raises(MXNetError, match="pattern"):
        dlm.DecoderLM(VOCAB, spec_of(pattern=("mla", "mha", "mla")))
    with pytest.raises(MXNetError, match="rope_dim"):
        dlm.DecoderLM(VOCAB, spec_of(rope_dim=7))


@pytest.mark.parametrize("control", [
    {"low": "all"}, {"low": "cache"}, {"leave_out": "rope"},
    {"leave_out": "post_norms"}, {"leave_out": "kv_norm"},
    {"leave_out": "shared"}, {"leave_out": "scaling"}],
    ids=["low_all", "low_cache", "no_rope", "no_post_norms", "no_kv_norm",
         "no_shared", "scaling_1"])
def test_every_control_of_the_reference_differs(reference, control):
    toks = np.random.default_rng(2).integers(0, VOCAB, 40)
    want = reference(toks)["logits"]
    off = np.abs(reference(toks, **control)["logits"] - want).max() \
        / np.abs(want).max()
    assert off > 1e-2


def test_rope_is_a_complex_rotation_by_position():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(9, 5, 8)).astype(np.float32)
    pos = np.asarray([0, 1, 2, 3, 50, 700, 1023, 2047, 131071])
    theta = 25600000.0
    ang = pos[:, None] * theta ** (-np.arange(4) / 4.0)
    z = (x[..., :4] + 1j * x[..., 4:]) * np.exp(1j * ang)[:, None]
    want = np.concatenate([z.real, z.imag], -1)
    got = dlm.rope(jnp.asarray(x), jnp.asarray(pos), theta)
    np.testing.assert_allclose(got, want, atol=2e-3)    # float32 angles
    np.testing.assert_allclose(got[:5], want[:5], atol=1e-5)
    np.testing.assert_array_equal(got[0], x[0])         # position 0
    # one key for all heads: (T, r) turns as (T, 1, r) does
    np.testing.assert_allclose(
        dlm.rope(jnp.asarray(x[:, 0]), jnp.asarray(pos), theta), got[:, 0])
    # and the reference's rotation is the same one
    np.testing.assert_allclose(ref._rope(jnp.asarray(x), jnp.asarray(pos),
                                         theta), got, atol=1e-6)


def test_the_absorbed_position_equals_the_expanded_sequence():
    """Decode's form (q through W_UK against the latent rows, P c_kv
    through W_UV) against prefill's (k_nope and v of every head made
    from c_kv), position by position."""
    spec = spec_of()
    layer = seeded(dlm.LatentAttention(spec), 4)
    w = layer.weights()
    t = 19
    x = jnp.asarray(np.random.default_rng(5).normal(size=(t, 64)),
                    jnp.float32)
    pos = jnp.arange(t)
    y_seq, rows = dlm.mla_sequence(w, spec, x, pos)
    assert rows.shape == (t, 32)
    # the rows as two pages of a pool whose rows are wider than they are
    pool = jnp.zeros((4, 16, 40), jnp.float32).at[jnp.asarray([2, 1])].set(
        jnp.pad(rows, ((0, 32 - t), (0, 8))).reshape(2, 16, 40))
    tables = jnp.asarray([[2, 1]] * t, jnp.int32)
    q_nope, q_rope, again = dlm.mla_project(w, spec, x, pos)
    np.testing.assert_array_equal(again, rows)
    o_lat = pk.latent_paged_attention(
        dlm.mla_absorb(w, spec, q_nope, q_rope), pool, tables, pos + 1,
        spec.kv_rank)
    np.testing.assert_allclose(dlm.mla_expand(w, spec, o_lat), y_seq,
                               atol=1e-4)


# ------------------------------------------------------------ the kernel
def _latent_case(rng, dtype, heads=4, kv_rank=24, rope=8, lanes=40,
                 psize=8, npg=11):
    s, pool = 5, 70
    q = jnp.asarray(rng.normal(size=(s, heads, kv_rank + rope)), dtype)
    pages = np.zeros((pool, psize, lanes), np.float32)
    pages[..., :kv_rank + rope] = rng.normal(
        size=(pool, psize, kv_rank + rope))
    lens = np.asarray([1, 17, psize * npg, 30, 0], np.int32)
    perm, c = rng.permutation(np.arange(1, pool)), 0
    tables = np.zeros((s, npg), np.int32)
    for i in range(s):
        n = -(-int(lens[i]) // psize)
        tables[i, :n] = perm[c:c + n]
        c += n
    return (q, jnp.asarray(pages, dtype), jnp.asarray(tables),
            jnp.asarray(lens))


def _dense_latent_attention(q, pages, tables, lens, kv_rank):
    out = np.zeros((q.shape[0], q.shape[1], kv_rank), np.float32)
    for i in range(q.shape[0]):
        n = int(lens[i])
        if not n:
            continue
        rows = np.asarray(pages, np.float32)[np.asarray(tables[i])] \
            .reshape(-1, pages.shape[-1])[:n]
        s = np.asarray(q[i], np.float32) @ rows[:, :q.shape[-1]].T
        p = np.exp(s - s.max(-1, keepdims=True))
        out[i] = (p / p.sum(-1, keepdims=True)) @ rows[:, :kv_rank]
    return out


@pytest.mark.parametrize("form,step_keys", [
    ("lax", 512), ("kernel", 512), ("kernel", 32), ("kernel", 24)],
    ids=["lax", "kernel-one-step", "kernel-three-steps",
         "kernel-ragged-last-step"])
def test_latent_paged_attention_on_ragged_lengths(form, step_keys,
                                                  monkeypatch):
    """Off the chip the gather; on it (interpret mode here) the kernel,
    a slot's pages in one grid step or in several, the last of which may
    reach past the table's width."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if form == "kernel" else "0")
    monkeypatch.setattr(pk, "_MLA_STEP_KEYS", step_keys)
    q, pages, tables, lens = _latent_case(np.random.default_rng(6),
                                          jnp.float32)
    want = _dense_latent_attention(q, pages, tables, lens, 24)
    got = np.asarray(pk.latent_paged_attention(q, pages, tables, lens, 24))
    assert got.shape == (5, 4, 24)
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-5)


def test_latent_kernel_takes_bfloat16_at_lane_tiles(interpret, monkeypatch):
    """The chip's shapes in small: rows of 128 + 64 values in 256 lanes,
    16 heads (one bfloat16 sublane tile), 16-row pages."""
    rng = np.random.default_rng(7)
    q, pages, tables, lens = _latent_case(rng, jnp.bfloat16, heads=16,
                                          kv_rank=128, rope=64, lanes=256,
                                          psize=16, npg=6)
    got = pk.latent_paged_attention(q, pages, tables, lens, 128)
    assert got.dtype == jnp.bfloat16
    want = _dense_latent_attention(q, pages, tables, lens, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:4], want[:4],
                               atol=0.05)
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "0")
    lax = pk.latent_paged_attention(q, pages, tables, lens, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:4],
                               np.asarray(lax, np.float32)[:4], atol=0.05)


# ------------------------------------------------------------ the server
def _server(model, **kw):
    args = dict(slots=2, page_size=8, max_prompt_len=24, max_new_tokens=12,
                eos_id=-1, prefix_cache=False, engine_driven=False)
    args.update(kw)
    return mx.serve.Server(model, **args)


def _teacher_forced(srv, prompt, forced, slot=0):
    """(logits the runtime gives for `forced` fed one a turn after
    `prompt`'s prefill, the rows each layer's pages then hold), through
    one slot and the page pool."""
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
    rows = [np.asarray(p)[np.asarray(pages)].reshape(-1, p.shape[-1])
            [:len(seq)] for p in rt.latent_pages]
    pool.free(pages)
    return np.stack(out), rows


@pytest.mark.parametrize("n_prompt,kernel", [(1, False), (7, False),
                                             (24, False), (13, True)],
                         ids=["1", "7", "24", "13-kernel"])
def test_prefill_then_decode_gives_the_reference_logits_and_rows(
        model, reference, n_prompt, kernel, monkeypatch):
    if kernel:
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(n_prompt)
    srv = _server(model)
    prompt = rng.integers(0, VOCAB, n_prompt)
    forced = rng.integers(0, VOCAB, 9)
    got, rows = _teacher_forced(srv, prompt, forced)
    want = reference(np.concatenate([prompt, forced]))
    np.testing.assert_allclose(got, want["logits"][n_prompt - 1:], atol=1e-4)
    assert len(rows) == 3
    for mine, theirs in zip(rows, want["latent"]):
        np.testing.assert_allclose(mine[:, :32], theirs, atol=1e-4)
    # a second request through the other slot and pages freed and reused
    prompt2 = rng.integers(0, VOCAB, 5)
    got, _ = _teacher_forced(srv, prompt2, forced[:3], slot=1)
    want = reference(np.concatenate([prompt2, forced[:3]]))["logits"][4:]
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert srv.pool.in_use() == 0
    srv.close()


def _greedy(reference, prompt, n, width=40):
    """The reference's argmax chain; the sequence rides in a fixed width
    (one compile): a causal forward's position does not see what follows."""
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((width,), np.int64)
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(reference(padded)["logits"][len(seq) - 1])))
    return seq[len(prompt):]


def test_server_generates_the_references_greedy_tokens(model, reference):
    """Five requests over two slots (slots and pages reused) on the
    engine loop; the device state is latent pages and nothing else."""
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
    # the dense layer counts nothing; a prefill runs no last-layer experts
    assert moe["rows"].shape == (3, 4) and not moe["rows"][0].any()
    assert moe["dispatches"][0] == 0 and moe["touched"][0] == 0
    assert moe["dispatches"][1] == turns + 5
    assert moe["dispatches"][2] == turns
    assert np.asarray(rt.routing["decode"]).shape == (3, rt.slots, 2)
    assert (np.asarray(rt.routing["decode"])[0] == -1).all()
    assert (np.asarray(rt.routing["prefill"])[[0, 2]] == -1).all()
    assert rt.kda_state == [] and rt.conv_tails == []
    assert rt.slot_state_bytes() == 0
    pages = srv.pool.num_pages
    assert [p.shape for p in rt.latent_pages] == [(pages, 8, 32)] * 3
    assert rt.latent_cache_bytes() == 3 * pages * 8 * 32 * 4
    assert rt.kv_bytes_per_page() == 3 * 8 * 32 * 4
    assert registry().gauge("serve_latent_cache_bytes").value \
        == rt.latent_cache_bytes()
    srv.close()


def test_page_reuse_is_refused_and_pages_are_remapped(model):
    with pytest.raises(MXNetError, match="prefix_cache.*one dispatch"):
        _server(model, prefix_cache=True)
    with pytest.raises(MXNetError, match="speculative_k"):
        _server(model, speculative_k=1)
    srv = _server(model)
    rt = srv.runtime
    prompt = np.arange(1, 12)
    pages = srv.pool.alloc(2)
    rt.prefill(0, prompt, pages)
    before = [np.asarray(p) for p in rt.latent_pages]
    free = [p for p in range(1, srv.pool.num_pages) if p not in pages][:2]
    rt.remap_pages(dict(zip(pages, free)))
    for was, now in zip(before, rt.latent_pages):
        assert np.abs(was[pages]).sum() > 0
        np.testing.assert_array_equal(np.asarray(now)[free], was[pages])
    srv.pool.free(pages)
    rt.reset_pages()
    assert all(not np.asarray(p).any() for p in rt.latent_pages)
    srv.close()


# ---------------------------------------------------------- expert layer
@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_of_this_router_add_up_to_the_uncut_layer(shares):
    """The guide's share test for THIS router (sigmoid scores, no bias,
    no groups, scaling 2.5): the parts that all the shares of the 16
    experts give, the shared expert counted once, add up to what the
    layer that holds all 16 gives, and to the uncut reference's layer."""
    rng = np.random.default_rng(10)
    full_spec = spec_of(held=(0, 16), experts=16)
    whole = seeded(dlm.MoELayer(full_spec), 11)
    w = whole.weights()
    assert "router_bias" not in w
    x = jnp.asarray(rng.normal(size=(37, 64)).astype(np.float32))
    full, counts, ids = dlm.moe_forward(w, full_spec, x)
    assert int(counts.sum()) == 37 * 2              # dropless
    shared = nn_ops.swiglu(x, w["shared_gate_up"], w["shared_down"])
    total, n = shared, 16 // shares
    for lo in range(0, 16, n):
        part = dict(w, experts_gate_up=w["experts_gate_up"][lo:lo + n],
                    experts_down=w["experts_down"][lo:lo + n])
        y, c, _ = dlm.moe_forward(part, spec_of(held=(lo, n), experts=16), x)
        np.testing.assert_array_equal(c, counts[lo:lo + n])
        total = total + (y - shared)
    np.testing.assert_allclose(total, full, atol=1e-4)
    names = {"router_weight": "router", "experts_gate_up": "gate_up",
             "experts_down": "down"}
    p = {names.get(k, k): v for k, v in w.items()}
    theirs, their_ids, slack = ref._experts(
        ref._How(None, None), p, dict(blm.dims(full_spec)), x, None)
    np.testing.assert_allclose(total, theirs, atol=1e-4)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(their_ids, -1))
    assert float(jnp.abs(slack).max()) == 0.0
    # the weights of a token's two experts sum to the scaling factor
    _, wts = dlm.moe_route(w, full_spec, x)
    np.testing.assert_allclose(wts.sum(-1), 2.5, atol=1e-5)
