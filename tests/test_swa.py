"""Sliding-window attention in `ops.pallas_kernels`: the windowed flash
kernel (the server's prefill) and the ring decode kernel
`mxtpu_rpa_ring` with its lax form, in interpret mode on the CPU, and the
ring a window layer's prefill and decode steps leave behind
(`models.decoder_lm.mx_swa_seq` / `mx_swa`)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import decoder_lm as dlm
from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


def _masked_softmax(q, k, v, window):
    """q, k, v (H, T, d): query t over the keys t - window < j <= t."""
    t = np.arange(q.shape[1])
    back = t[:, None] - t[None, :]
    seen = (back >= 0) & (back < window)
    s = np.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)


# ------------------------------------------------------ the flash kernel
@pytest.mark.parametrize("window", [40, 128, 200, 384, 385, 1000],
                         ids=lambda w: f"w{w}")
def test_windowed_flash_equals_a_masked_plain_softmax(interpret, monkeypatch,
                                                      window):
    """384 positions in blocks of 128: windows under a block, of a block,
    across blocks, of the whole length and over it; the blocks wholly
    behind a window are skipped, not computed."""
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", "128")
    rng = np.random.default_rng(window)
    q, k, v = (rng.normal(size=(2, 384, 128)).astype(np.float32)
               for _ in range(3))
    got = pk.flash_attention(*(jnp.asarray(a)[None] for a in (q, k, v)),
                             causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got[0]),
                               _masked_softmax(q, k, v, window), atol=2e-6)
    # and off the kernel (a length that does not tile): the XLA path
    got = pk.flash_attention(*(jnp.asarray(a)[None, :, :100]
                               for a in (q, k, v)), causal=True,
                             window=window)
    np.testing.assert_allclose(
        np.asarray(got[0]),
        _masked_softmax(q[:, :100], k[:, :100], v[:, :100], window),
        atol=2e-6)


def test_flash_without_a_window_is_what_it_was(interpret):
    """`window=None` traces the kernel the call without it traces (cell
    1's forward), and a window needs a causal call without lengths."""
    x = jnp.zeros((1, 2, 256, 128), jnp.float32)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(
            lambda q, k, v: pk.flash_attention(q, k, v, causal=True,
                                               **kw))(x, x, x))

    plain = jaxpr()
    assert jaxpr(window=None) == plain
    windowed = jaxpr(window=64)
    assert "mxtpu_flash_fwd" in plain and "mxtpu_flash_fwd" in windowed
    # the window's mask is the windowed call's alone
    assert windowed.count(" sub ") > plain.count(" sub ")
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(x, x, x, window=8)
    with pytest.raises(ValueError, match="kv_lengths"):
        pk.flash_attention(x, x, x, causal=True, window=8,
                           kv_lengths=jnp.ones((1,), jnp.int32))


def test_windowed_flash_fetches_only_the_blocks_it_computes(interpret,
                                                           monkeypatch):
    """The K and V index maps name, at a skipped step, the nearest live
    block of the q block, so the pipeline does not fetch what the kernel
    skips: poison behind the window changes nothing."""
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", "128")
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 512, 128)), jnp.float32)
               for _ in range(3))
    clean = pk.flash_attention(q, k, v, causal=True, window=100)
    # the last q block (384..511) sees keys from 285 on: blocks 0 and 1
    # (keys 0..255) are wholly behind it
    bad_k = k.at[:, :, :256].set(jnp.nan)
    bad_v = v.at[:, :, :256].set(jnp.nan)
    got = pk.flash_attention(q, bad_k, bad_v, causal=True, window=100)
    np.testing.assert_array_equal(np.asarray(got[0, 0, 384:]),
                                  np.asarray(clean[0, 0, 384:]))


# ------------------------------------------------------- the ring kernel
def _ring_case(seed, lengths, dh=128, window=32, psize=8, heads=(8, 2)):
    rng = np.random.default_rng(seed)
    s, (hq, h) = len(lengths), heads
    ring = window // psize + 1
    q = jnp.asarray(rng.normal(size=(s, hq, dh)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(s * ring, psize, h * dh)),
                        jnp.float32) for _ in range(2))
    return q, k, v, jnp.asarray(lengths, jnp.int32), window


@pytest.mark.parametrize("lengths", [(1, 5, 32), (33, 40, 41), (79, 80, 81),
                                     (97, 1000, 5120)], ids=str)
def test_ring_kernel_equals_its_lax_form(interpret, lengths):
    q, k, v, ln, window = _ring_case(sum(lengths), lengths)
    got = pk.ring_paged_attention(q, k, v, ln, window)
    n = k.shape[0] // len(lengths) * k.shape[1]
    want = pk._ring_attention_lax(q, k.reshape(len(lengths), n, -1),
                                  v.reshape(len(lengths), n, -1), ln,
                                  window, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("lengths", [(1, 5, 24, 25), (72, 73, 96, 97),
                                     (200, 500, 1000, 3)], ids=str)
def test_ring_kernel_in_blocks_equals_its_lax_form(interpret, monkeypatch,
                                                   lengths):
    """A ring too large for the kernel's budget whole (the budget cut so
    that 3 pages of 8 rows of 2 x 128 float32 lanes fill it): 10 pages
    rounded up to 12 in 4 blocks, slots under a block, at and past the
    window, and several laps of the ring; online softmax over the
    blocks, only the live ones read."""
    monkeypatch.setattr(pk, "_RPA_VMEM_BUDGET", 4 * 3 * 8 * 256 * 4)
    window, psize = 72, 8
    ring = pk.ring_pages_for(window, psize, 256, 4)
    assert ring == 12 and pk.ring_block_pages(ring, psize, 256, 4) == 3
    rng = np.random.default_rng(sum(lengths))
    s = len(lengths)
    q = jnp.asarray(rng.normal(size=(s, 8, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(s * ring, psize, 256)),
                        jnp.float32) for _ in range(2))
    ln = jnp.asarray(lengths, jnp.int32)
    got = pk.ring_paged_attention(q, k, v, ln, window)
    n = ring * psize
    want = pk._ring_attention_lax(q, k.reshape(s, n, -1),
                                  v.reshape(s, n, -1), ln, window,
                                  128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    with pytest.raises(ValueError, match="not whole blocks"):
        pk.ring_paged_attention(q, k[:s * 10], v[:s * 10], ln, window)


def test_ring_kernel_reads_no_block_past_a_slots_length(interpret,
                                                        monkeypatch):
    """A slot that has not filled its ring neither fetches nor computes
    on the blocks past its length (the grid steps there name its last
    live block again, and are skipped): NaN in every row past the second
    block of 24 leaves slots of 1, 24, 25 and 48 positions as they
    were."""
    monkeypatch.setattr(pk, "_RPA_VMEM_BUDGET", 4 * 3 * 8 * 256 * 4)
    rng = np.random.default_rng(48)
    q = jnp.asarray(rng.normal(size=(4, 8, 128)), jnp.float32)
    k, v = (rng.normal(size=(4, 96, 256)).astype(np.float32)
            for _ in range(2))
    ln = jnp.asarray([1, 24, 25, 48], jnp.int32)

    def attend(k, v):
        return np.asarray(pk.ring_paged_attention(
            q, jnp.asarray(k).reshape(48, 8, 256),
            jnp.asarray(v).reshape(48, 8, 256), ln, 72))

    clean = attend(k, v)
    k[:, 48:], v[:, 48:] = np.nan, np.nan
    np.testing.assert_array_equal(attend(k, v), clean)
    assert np.isfinite(clean).all()


def test_ring_kernel_is_one_block_at_the_window_cells_shape(interpret):
    """The window configuration's ring (65 pages of 16 rows, 4 KV heads
    of 128 in bf16) fits the budget whole: one block a slot, as before
    the kernel took blocks."""
    assert pk.ring_block_pages(65, 16, 512, 2) == 65
    rng = np.random.default_rng(65)
    lengths = (700, 3000)
    q = jnp.asarray(rng.normal(size=(2, 32, 128)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(2 * 65, 16, 512)), jnp.bfloat16)
            for _ in range(2))
    ln = jnp.asarray(lengths, jnp.int32)
    got = pk.ring_paged_attention(q, k, v, ln, 1024)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = pk._ring_attention_lax(f32[0], f32[1].reshape(2, 1040, -1),
                                  f32[2].reshape(2, 1040, -1), ln, 1024,
                                  128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-2)


def test_ring_rows_hold_the_newest_position_congruent_to_them():
    back = np.asarray(pk.ring_rows_back(jnp.asarray([1, 41, 100]), 40))
    assert back.shape == (3, 40)
    # one position held, at row 0: every other row reads further back
    # than the slot's length
    assert back[0, 0] == 0 and (back[0, 1:] >= 1).all()
    # position 40 at row 0 again, position 39 at row 39, 1 at row 1
    assert back[1, 0] == 0 and back[1, 39] == 1 and back[1, 1] == 39
    pos = 99 - back[2]
    assert sorted(pos) == list(range(60, 100))
    assert (pos % 40 == np.arange(40)).all()
    with pytest.raises(ValueError, match="cannot hold a window"):
        q, k, v, ln, _ = _ring_case(0, (3,))
        pk.ring_paged_attention(q, k, v, ln, 48)


@pytest.mark.parametrize("form", ["ring_lax", "ring_kernel", "flash"])
def test_the_windows_edge(interpret, monkeypatch, form):
    """Position t reads key t - (window - 1) and not key t - window: the
    output equals a softmax over exactly `window` keys within 1e-6 and
    differs from one over window + 1 keys by far more than 1e-4."""
    window, t, dh = 32, 100, 128
    rng = np.random.default_rng(9)
    k_all, v_all = (rng.normal(size=(t + 1, dh)).astype(np.float32)
                    for _ in range(2))
    q = rng.normal(size=(dh,)).astype(np.float32)

    def over(first):
        s = k_all[first:] @ q / np.sqrt(dh)
        p = np.exp(s - s.max())
        return (p / p.sum()) @ v_all[first:]

    want, one_more = over(t - window + 1), over(t - window)
    assert np.abs(want - one_more).max() > 5e-3
    if form == "flash":
        pad = 128 - (t + 1)
        qs = np.zeros((128, dh), np.float32)
        qs[t] = q
        ks, vs = (np.pad(a, ((0, pad), (0, 0))) for a in (k_all, v_all))
        got = pk.flash_attention(*(jnp.asarray(a)[None, None]
                                   for a in (qs, ks, vs)), causal=True,
                                 window=window)[0, 0, t]
    else:
        if form == "ring_lax":
            monkeypatch.delenv("MXTPU_PALLAS_INTERPRET")
        psize, ring = 8, window // 8 + 1
        n = ring * psize
        # the ring as t + 1 one-position writes leave it
        rk, rv = np.zeros((n, dh), np.float32), np.zeros((n, dh), np.float32)
        for p in range(t + 1):
            rk[p % n], rv[p % n] = k_all[p], v_all[p]
        got = pk.ring_paged_attention(
            jnp.asarray(q)[None, None], jnp.asarray(rk).reshape(ring, psize,
                                                               dh),
            jnp.asarray(rv).reshape(ring, psize, dh),
            jnp.asarray([t + 1], jnp.int32), window)[0, 0]
    assert np.abs(np.asarray(got) - want).max() < 1e-6
    assert np.abs(np.asarray(got) - one_more).max() > 1e-4


# ------------------------------------- what a layer's steps leave in a ring
SPEC = dlm.LMSpec(hidden=32, heads=4, kv_heads=2, head_dim=8, kda_heads=0,
                  kda_head_dim=0, conv_kernel=0, num_experts=4, top_k=2,
                  expert_width=16, held_lo=0, held_n=4, scaling=1.0,
                  eps=1e-6, pattern=("swa",), attn_gate=False,
                  router_bias=False, window=32, attn_rope=True,
                  rope_theta=500000.0)


@pytest.mark.parametrize("n,m", [(20, 5), (48, 0), (48, 7), (70, 30),
                                 (100, 12), (1, 60)],
                         ids=["under_the_ring", "a_whole_lap", "a_lap_and_7",
                              "over_two_laps", "prefill_over_two_laps",
                              "one_token_then_steps"])
def test_a_ring_holds_the_last_positions_a_query_may_read(n, m):
    """A prefill of n positions into slot 1 of 3, then m one-position
    steps: ring row p % 48 holds the rotated key and the value of every
    position a query at the last position may read; the neighbours'
    rings are untouched; an empty slot's step writes nowhere."""
    psize, slots = 8, 3
    ring = pk.ring_pages_for(SPEC.window, psize,
                             SPEC.kv_heads * SPEC.head_dim, 4)
    assert ring == 5
    rows = ring * psize
    rng = np.random.default_rng(n * 100 + m)
    w = {"qkv_weight": jnp.asarray(0.2 * rng.normal(size=(64, 32)),
                                   jnp.float32),
         "o_weight": jnp.asarray(0.2 * rng.normal(size=(32, 32)),
                                 jnp.float32)}
    total = n + m
    plen = -(-max(total, 1) // psize) * psize
    x = jnp.asarray(rng.normal(size=(plen, 32)), jnp.float32)
    _, k_all, v_all = dlm.gqa_sequence(w, SPEC, x, "swa")
    k_all, v_all = (np.asarray(a).reshape(plen, -1) for a in (k_all, v_all))
    k_ring = jnp.full((slots * ring, psize, 16), 7.0, jnp.float32)
    v_ring = jnp.full((slots * ring, psize, 16), 7.0, jnp.float32)
    y, k_ring, v_ring = dlm.mx_swa_seq(w, x, k_ring, v_ring, jnp.int32(1),
                                       jnp.int32(n), spec=SPEC)
    y_full = np.asarray(y)
    for t in range(n, total):
        lens = jnp.asarray([3, t, 0], jnp.int32)
        valid = jnp.asarray([False, True, False])
        y, k_ring, v_ring = dlm.mx_swa(
            w, jnp.stack([x[0], x[t], x[1]]), k_ring, v_ring, lens, valid,
            spec=SPEC)
        # the step's output is the sequence form's at that position
        np.testing.assert_allclose(np.asarray(y[1]), y_full[t], atol=1e-4)
    held = np.asarray(k_ring).reshape(slots, rows, 16)
    held_v = np.asarray(v_ring).reshape(slots, rows, 16)
    assert (held[[0, 2]] == 7.0).all() and (held_v[[0, 2]] == 7.0).all()
    last = total - 1
    for p in range(max(0, last - SPEC.window + 1), total):
        np.testing.assert_allclose(held[1, p % rows], k_all[p], atol=1e-6)
        np.testing.assert_allclose(held_v[1, p % rows], v_all[p], atol=1e-6)
