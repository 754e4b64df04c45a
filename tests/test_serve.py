"""Serving engine (ISSUE 6): paged KV cache, ragged paged attention,
continuous batching, request API, fault/chaos behaviour.

The load-bearing guarantees pinned here:

  * the paged decode path is BITWISE-identical to the dense-cache
    `decode_step` on equal context width (shared decode core);
  * the KV page pool NEVER leaks: `in_use` returns to 0 after every
    request completes — including chaos (decode faults, exhausted
    retries) and page-exhaustion preemption;
  * the decode executable compiles once and never retraces across slot
    occupancy / page-table changes (also gated in check_dispatch).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.fault import injection as finj
from mxnet_tpu.observability import registry
from mxnet_tpu.serve import (PageAllocError, PagePool, ServeError,
                             ServeOverloaded)
from mxnet_tpu.serve.kv_pages import NULL_PAGE


def _tiny_model(vocab=50, units=32, layers=2, heads=4, max_length=32,
                seed=11):
    from mxnet_tpu.models.transformer import TransformerNMT
    mx.random.seed(seed)
    m = TransformerNMT(vocab, units=units, hidden=2 * units,
                       num_layers=layers, num_heads=heads,
                       max_length=max_length, dropout=0.0)
    m.initialize()
    return m


def _server(model=None, **kw):
    model = model if model is not None else _tiny_model()
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_src_len", 16)
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("engine_driven", False)
    return mx.serve.Server(model, **kw)


@pytest.fixture(autouse=True)
def _clear_faults():
    finj.clear()
    yield
    finj.clear()


# ---------------------------------------------------------------- pool
def test_page_pool_alloc_free_accounting():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.capacity == 7 and pool.available() == 7
    a = pool.alloc(3)
    assert len(a) == 3 and NULL_PAGE not in a
    assert pool.in_use() == 3 and pool.available() == 4
    b = pool.alloc(4)
    assert pool.available() == 0
    pool.free(a)
    assert pool.in_use() == 4 and pool.available() == 3
    pool.free(b)
    assert pool.in_use() == 0 and pool.available() == 7
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2 and pool.pages_for(0) == 1


def test_page_pool_exhaustion_is_atomic_and_counted():
    reg = registry()
    fail0 = reg.counter("kv_page_alloc_failures").value
    pool = PagePool(num_pages=4, page_size=2)
    pool.alloc(2)
    with pytest.raises(PageAllocError):
        pool.alloc(2)       # only 1 free: all-or-nothing
    assert pool.available() == 1    # nothing was granted
    assert reg.counter("kv_page_alloc_failures").value == fail0 + 1


def test_page_pool_free_errors():
    pool = PagePool(num_pages=4, page_size=2)
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(MXNetError):
        pool.free(pages)            # double free
    with pytest.raises(MXNetError):
        pool.free([NULL_PAGE])      # reserved null page


def test_page_pool_defrag_mapping():
    pool = PagePool(num_pages=8, page_size=2)
    a = pool.alloc(5)               # pages 1..5
    pool.free([a[0], a[2]])         # live: {2, 4, 5} (alloc order 1..5)
    live = sorted({1, 2, 3, 4, 5} - {a[0], a[2]})
    mapping = pool.defrag()
    # live pages renumbered to 1..3; only movers appear in the mapping
    assert set(mapping.keys()) <= set(live)
    assert sorted(mapping.values()) == sorted(
        n for n, o in zip(range(1, 4), live) if n != o)
    assert pool.in_use() == 3
    assert pool.available() == 4
    # post-defrag allocations hand out ids above the compacted range
    assert all(p > 3 for p in pool.alloc(2))


# ----------------------------------------------- ragged paged attention
def _paged_fixture(seed=0, S=3, H=2, dh=8, P=9, psize=8, npages=2,
                   lanes=None):
    """Head-major pools (H, P, psize, lanes): the one 4-D pool contract.
    `lanes` past the head size hold what a reader must never see."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(S, H, dh).astype(np.float32))
    kp = jnp.asarray(rng.randn(H, P, psize, dh).astype(np.float32))
    vp = jnp.asarray(rng.randn(H, P, psize, dh).astype(np.float32))
    if lanes:
        kp, vp = (jnp.pad(p, [(0, 0)] * 3 + [(0, lanes - dh)],
                          constant_values=7.0) for p in (kp, vp))
    pt = jnp.asarray(np.array([[1, 2], [3, 0], [4, 5]], np.int32))
    lens = jnp.asarray(np.array([12, 5, 16], np.int32))
    return q, kp, vp, pt, lens


# the chunks a slot's pages are fetched in: 11 pages a slot, an
# empty slot beside one that fills every page, five heads, the table in
# one chunk (`_RAGGED`) or in chunks of 3 pages that cut slots and do not
# divide the table (`_CHUNKS`); a slot that fills 12 pages in whole
# chunks of 4 between empty slots (`_FULL`); two slots that share a
# prefix page (`_SHARED`); a chunk of 2 pages over a table of 7 that one
# slot fills (`_UNEVEN`); and a fast-memory budget in which a chunk is
# half a page (`_SPLIT`)
_RAGGED = dict(H=5, npages=11, lens=(0, 88, 37, 1))
_CHUNKS = dict(_RAGGED, pages_a_chunk=3)
_FULL = dict(H=3, npages=12, lens=(1, 96, 0, 1), pages_a_chunk=4)
_SHARED = dict(H=2, npages=4, lens=(30, 21, 5), pages_a_chunk=1, share=True)
_UNEVEN = dict(H=4, npages=7, lens=(56, 17, 0), pages_a_chunk=2)
_SPLIT = dict(H=6, npages=3, lens=(40, 0, 9), psize=16, split=8)


def _ragged_case(monkeypatch, H, npages, lens, psize=8, W=None, int8=False,
                 split=None, pages_a_chunk=None, share=False, dh=8, seed=5):
    """(q, kp, vp, pt, lens, scales): a slot's live pages in table order,
    the null page behind them as the scheduler leaves it; `split` shrinks
    the kernel's budget so that a buffer holds that many keys, a part of a
    page; `pages_a_chunk` sets the bytes a chunk fetches to that many
    pages; `share` gives slot 1 slot 0's first page, as a cached prefix
    does."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    item = 1 if int8 else 4
    if split:
        monkeypatch.setattr(pk, "_RPA_VMEM_BUDGET",
                            2 * pk._RPA_BUFFERS * split * H * dh * item)
        # an int8 page of 16 rows is less than its 32-row tile: whole
        assert pk._rpa_chunk(H, npages, psize, dh, item) == (
            psize if int8 else split)
    if pages_a_chunk:
        tile = 8 * max(1, 4 // item)
        page = -(-psize // tile) * tile * H * dh * item
        monkeypatch.setattr(pk, "_RPA_STEP_BYTES", pages_a_chunk * page)
        assert pk._rpa_chunk(H, npages, psize, dh, item) == \
            pages_a_chunk * psize
    rng = np.random.RandomState(seed)
    S = len(lens)
    live = [-(-n // psize) for n in lens]
    assert max(live) == npages          # one slot fills every page
    pt = np.zeros((S, npages), np.int32)
    ids = iter(rng.permutation(sum(live)) + 1)
    for s, n in enumerate(live):
        pt[s, :n] = [next(ids) for _ in range(n)]
    if share:
        pt[1, 0] = pt[0, 0]
    shape = (H, sum(live) + 1, psize, dh)
    if int8:
        kp, vp = (rng.randint(-127, 128, shape).astype(np.int8)
                  for _ in range(2))
        scales = tuple(jnp.asarray((rng.rand(*shape[:2]) * 0.05 + 1e-3)
                                   .astype(np.float32)) for _ in range(2))
    else:
        kp, vp = (rng.randn(*shape).astype(np.float32) for _ in range(2))
        scales = (None, None)
    q = rng.randn(*((S, H, dh) if W is None else (S, W, H, dh)))
    return (jnp.asarray(q.astype(np.float32)), jnp.asarray(kp),
            jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(np.array(lens, np.int32)), scales)


def _assert_seen_rows_close(out, ref, lens):
    """Every query row that sees a key at all: an empty slot's first row
    is garbage nobody reads, in the kernel and the fallback alike."""
    out, ref = np.asarray(out), np.asarray(ref)
    if out.ndim == 3:
        out, ref = out[:, None], ref[:, None]
    seen = (np.asarray(lens)[:, None] + np.arange(out.shape[1])) > 0
    assert seen.sum() >= seen.size - 1
    np.testing.assert_allclose(out[seen], ref[seen], rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("lanes", [None, 128], ids=["lanes=dh", "lanes=128"])
def test_paged_attention_lax_matches_shared_math(lanes):
    """The gather fallback must be EXACTLY the shared single-query math
    over the gathered context (that is what buys decode-path parity)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import (
        _paged_attention_lax, single_query_cached_attention)
    q, kp, vp, pt, lens = _paged_fixture(lanes=lanes)
    out = _paged_attention_lax(q, kp, vp, pt, lens)
    S, H, dh = q.shape
    L = pt.shape[1] * kp.shape[2]
    # a slot's context, position by position: page pt[s, j]'s rows
    kc = jnp.stack([kp[:, pt[s], :, :dh].reshape(H, L, dh)
                    for s in range(S)])
    vc = jnp.stack([vp[:, pt[s], :, :dh].reshape(H, L, dh)
                    for s in range(S)])
    mask = (jnp.arange(L)[None, :] < lens[:, None])[:, None, None, :]
    ref = single_query_cached_attention(q[:, :, None, :], kc, vc,
                                        mask)[:, :, 0]
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("cfg", [
    {}, {"psize": 16, "buffers": 3}, {"lanes": 128}, {"ragged": _RAGGED},
    {"ragged": dict(_RAGGED, psize=16, lens=(0, 176, 37, 1)),
     "buffers": 2}, {"ragged": _SPLIT}, {"ragged": _CHUNKS},
    {"ragged": _FULL}, {"ragged": _SHARED}, {"ragged": _UNEVEN},
    {"ragged": _CHUNKS, "buffers": 3}],
    ids=["default", "buffers=3", "lanes=128", "ragged", "ragged-buffers=2",
         "split", "chunks", "full", "shared", "uneven", "chunks-buffers=3"])
def test_paged_attention_kernel_interpret(monkeypatch, cfg):
    """The Pallas ragged-paged kernel numerics, pinned on CPU via
    interpret mode (same harness as the flash-kernel tests), over pools
    whose rows are whole lane tiles, as the server keeps them (the lanes
    past the head hold sevens), and with the page walker's ring two and
    three buffers deep as well as `_RPA_BUFFERS`: every form must
    reproduce the lax fallback. So must the chunks a slot's pages are
    fetched in: chunk edges inside a slot and past the table's width, a
    full slot between empty ones, a shared prefix page, and a chunk of
    half a page (`_RAGGED` and its siblings, `_SPLIT`)."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    cfg = dict(cfg)
    if "buffers" in cfg:
        monkeypatch.setattr(pk, "_RPA_BUFFERS", cfg.pop("buffers"))
    if "ragged" in cfg:
        q, kp, vp, pt, lens, _ = _ragged_case(monkeypatch,
                                              **cfg.pop("ragged"))
    else:
        q, kp, vp, pt, lens = _paged_fixture(psize=cfg.get("psize", 8),
                                             lanes=cfg.get("lanes", 0))
        if "psize" in cfg:
            lens = lens * 2          # reach into the second page
    out_k = pk.ragged_paged_attention(q, kp, vp, pt, lens)
    ref = pk._paged_attention_lax(q, kp, vp, pt, lens)
    _assert_seen_rows_close(out_k, ref, lens)


def test_decode_runtime_paged_counters_count_the_live_pages():
    """`DecodeRuntime.paged_counters()` from the `lens` each launch holds:
    a running slot reads ceil((len + 1) / page_size) pages in a decode
    turn and ceil((len + qlen) / page_size) in a verify launch, of a
    table `max_pages_per_slot` wide; an empty slot reads none."""
    from mxnet_tpu.models.transformer import decoder_weights, encoder_weights
    from mxnet_tpu.serve.decode import DecodeRuntime
    model = _tiny_model()
    rt = DecodeRuntime(decoder_weights(model), encoder_weights(model),
                       slots=3, num_pages=13, page_size=4,
                       max_pages_per_slot=4, max_src_len=8, width=3)
    assert rt.paged_counters() == {"turns": 0, "live_pages": 0,
                                   "table_pages": 0}
    tables = np.zeros((3, 4), np.int32)
    tok = np.zeros((3,), np.int32)
    for lens, active in (([0, 3, 9], [1, 1, 0]), ([1, 4, 15], [1, 2, 1])):
        rt.decode(tables, np.asarray(lens, np.int32), tok,
                  np.asarray(active, np.int32))
    # ceil((len + 1) / 4) of the five running slots: 1, 1, 1, 2, 4
    assert rt.paged_counters() == {"turns": 2, "live_pages": 9,
                                   "table_pages": 5 * 4}
    rt.decode_multi(tables, np.array([2, 6, 0], np.int32),
                    np.zeros((3, 3), np.int32), np.array([3, 1, 1], np.int32),
                    np.array([1, 1, 0], np.int32))
    # ceil((len + qlen) / 4) of the two running slots: 2, 2
    assert rt.paged_counters() == {"turns": 3, "live_pages": 13,
                                   "table_pages": 7 * 4}


# --------------------------------------------------- decode-path parity
def test_paged_decode_bitwise_parity():
    """The serve paged decode and the dense-cache `decode_step` (the
    beam-search path) share one decode core + KV layout: on identical
    memory and equal context width, executing both cores op-by-op (the
    shared functions themselves, outside jit) produces BITWISE-equal
    logits at every step. The jitted production path is additionally
    checked to pick identical tokens (whole-program XLA fusion is allowed
    its ~1-ULP reassociation, but never a different argmax here)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer import (decode_step, decoder_weights,
                                              encoder_weights)
    from mxnet_tpu.serve.decode import DecodeRuntime

    model = _tiny_model()
    w = decoder_weights(model)
    ew = encoder_weights(model)
    rng = np.random.RandomState(3)
    src = rng.randint(4, 50, (9,)).astype(np.int32)

    psize, npages = 4, 4            # paged context width = dense Lmax
    lmax = psize * npages
    rt = DecodeRuntime(w, ew, slots=2, num_pages=2 * npages + 1,
                       page_size=psize, max_pages_per_slot=npages,
                       max_src_len=12)
    rt.prefill(0, src)

    # dense twin fed the EXACT memory the prefill executable wrote
    n_layers = len(w["layers"])
    h = w["num_heads"]
    dh = w["embed"].shape[1] // h
    mem_kv = [(rt.mem_k[li, 0:1], rt.mem_v[li, 0:1])
              for li in range(n_layers)]
    mem_vl = rt.mem_vl[0:1]
    caches = (jnp.zeros((n_layers, 1, h, lmax, dh), w["embed"].dtype),) * 2

    page_tables = np.full((2, npages), NULL_PAGE, np.int32)
    page_tables[0] = [1, 2, 3, 4]   # slot 0 owns 4 pages
    pt_dev = jnp.asarray(page_tables)
    active = jnp.asarray(np.array([1, 0], np.int32))
    lens = np.zeros((2,), np.int32)
    tok = np.array([2, 0], np.int32)        # BOS
    # the eager core keeps its own copy of the page state (the jitted
    # runtime call donates rt.k_pages/v_pages)
    kp, vp = ([jnp.array(p) for p in pools]
              for pools in (rt.k_pages, rt.v_pages))

    for t in range(8):
        logits_d, caches = decode_step(
            w, caches, mem_kv, mem_vl, jnp.asarray(tok[:1]), t)
        # the shared core, executed eagerly: bitwise (its named scopes,
        # jitted functions of their own, run op by op too)
        with jax.disable_jit():
            (kp, vp, _, _), (_, logits_e) = rt._decode_program(
                (kp, vp, None, None),
                (pt_dev, jnp.asarray(lens), jnp.asarray(tok), active,
                 jnp.zeros((2,), jnp.int32), rt.mem_k, rt.mem_v,
                 rt.mem_vl))
        assert np.array_equal(np.asarray(logits_e)[0],
                              np.asarray(logits_d)[0]), f"step {t}"
        # the jitted production path: same token choice, logits ~1 ULP
        next_paged, logits_p = rt.decode(page_tables, lens, tok, active)
        np.testing.assert_allclose(np.asarray(logits_p)[0],
                                   np.asarray(logits_d)[0],
                                   rtol=2e-6, atol=2e-6)
        nxt = int(np.argmax(np.asarray(logits_d)[0]))
        assert int(next_paged[0]) == nxt
        tok = np.array([nxt, 0], np.int32)
        lens[0] += 1


def test_serve_greedy_matches_beam1_cached():
    """End to end: the server's greedy decode equals `beam_search_cached`
    with beam_size=1 (same shared decode core, full pipeline)."""
    from mxnet_tpu.models.transformer import beam_search_cached
    model = _tiny_model()
    rng = np.random.RandomState(0)
    src = rng.randint(4, 50, (8,)).astype(np.int32)
    srv = _server(model, max_new_tokens=11)
    try:
        got = srv.submit(src).result()
    finally:
        srv.close()
    tokens, _ = beam_search_cached(model, mx.nd.array(src.reshape(1, -1)),
                                   beam_size=1, max_length=12)
    beam = tokens.asnumpy()[0, 0].tolist()   # [BOS, tok, tok, ...]
    want = beam[1:1 + len(got)]
    eos_cut = want.index(3) + 1 if 3 in want else len(want)
    assert got == want[:eos_cut] or got == want


# ----------------------------------------------- continuous batching
def test_continuous_batching_admits_midflight_and_frees_pages():
    srv = _server(max_new_tokens=12)
    sched = srv.scheduler
    rng = np.random.RandomState(1)
    long1 = srv.submit(rng.randint(4, 50, (6,)), max_new_tokens=10)
    short = srv.submit(rng.randint(4, 50, (5,)), max_new_tokens=2)
    late = srv.submit(rng.randint(4, 50, (7,)), max_new_tokens=3)
    r = sched.step()
    assert r.admitted == 2          # both slots fill, `late` queues
    assert sched.active_count() == 2
    saw_midflight = False
    for _ in range(40):
        if not sched.pending_work():
            break
        sched.step()
        states = (long1.state, late.state)
        if states == ("running", "running"):
            saw_midflight = True    # late admitted while long1 in flight
    assert saw_midflight, "continuous batching never backfilled"
    assert len(short.result()) == 2
    assert len(long1.result()) == 10
    assert len(late.result()) == 3
    assert srv.pool.in_use() == 0
    srv.close()


def test_continuous_batching_admits_into_running_batch():
    """A mixed-length queue on 2 slots: a `step()` admits into a batch
    that is part full, and the queue drains in fewer turns than running
    it one whole batch after another would need. `eos_id=-1`: every
    request runs its budget, one token a turn, so both bounds follow
    from the budgets alone."""
    budgets = (12, 2, 6, 3)
    srv = _server(_tiny_model(seed=13), slots=2, max_new_tokens=12,
                  eos_id=-1)
    sched = srv.scheduler
    rng = np.random.RandomState(5)
    handles = [srv.submit(rng.randint(4, 50, (6,)), max_new_tokens=b)
               for b in budgets]
    steps, admitted_midflight = 0, 0
    while sched.pending_work():
        running_before = sched.active_count()
        r = sched.step()
        steps += 1
        assert steps < 200
        if running_before and r.admitted:
            admitted_midflight += r.admitted
    assert [len(h.result()) for h in handles] == list(budgets)
    # the first turn fills both slots; the other two requests can only
    # have gone into a batch that was still running
    assert admitted_midflight == 2
    # batch after batch, each pair of slots waits for its longer request
    batch_after_batch = max(budgets[:2]) + max(budgets[2:])
    assert max(budgets) <= steps < batch_after_batch, steps
    assert srv.pool.in_use() == 0
    srv.close()


def test_close_fails_pending_requests_instead_of_stranding():
    srv = _server()
    rng = np.random.RandomState(22)
    h = srv.submit(rng.randint(4, 50, (5,)))    # queued, never stepped
    srv.close()
    assert h.state == "failed" and h.done()
    with pytest.raises(ServeError):
        h.result(timeout=1)
    assert srv.pool.in_use() == 0


def test_backpressure_bounded_queue():
    reg = registry()
    rej0 = reg.counter("serve_requests", result="rejected").value
    srv = _server(max_queue=2)
    rng = np.random.RandomState(2)
    srv.submit(rng.randint(4, 50, (4,)))
    srv.submit(rng.randint(4, 50, (4,)))
    with pytest.raises(ServeOverloaded):
        srv.submit(rng.randint(4, 50, (4,)))
    assert reg.counter("serve_requests", result="rejected").value \
        == rej0 + 1
    srv.scheduler.run_until_idle()
    assert srv.pool.in_use() == 0
    srv.close()


def test_submit_validates_source_tokens():
    srv = _server()
    with pytest.raises(MXNetError):
        srv.submit([], max_new_tokens=4)            # empty source
    with pytest.raises(MXNetError):
        srv.submit(np.arange(4, 40, dtype=np.int32))  # > max_src_len
    srv.close()


def test_submit_rejects_request_pool_can_never_serve():
    """A token budget needing more pages than the WHOLE pool holds is
    rejected at submit time (it would deterministically exhaust the pool
    mid-decode and burn retries)."""
    model = _tiny_model(seed=27)
    srv = _server(model, slots=2, page_size=2, num_pages=3,  # 2 usable
                  max_new_tokens=6)
    with pytest.raises(MXNetError):
        srv.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=6)
    h = srv.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=4)
    assert len(h.result(timeout=30)) >= 1
    assert srv.pool.in_use() == 0
    srv.close()


def test_throughput_is_per_server():
    """serve_tokens is process-global; throughput() must count per
    scheduler instance (regression: a second — even concurrent — server
    double-counted the first one's tokens)."""
    model = _tiny_model(seed=28)
    a = _server(model, max_new_tokens=4)
    b = _server(model, max_new_tokens=4)    # concurrently alive
    a.submit(np.arange(4, 10, dtype=np.int32)).result(timeout=30)
    assert b.throughput() == 0.0            # a's tokens don't leak into b
    assert a.throughput() > 0
    b.submit(np.arange(4, 10, dtype=np.int32)).result(timeout=30)
    assert b.scheduler.tokens_generated == 4
    assert a.scheduler.tokens_generated == 4
    a.close()
    b.close()


def test_construction_validates_encoder_pos_table():
    """max_src_len beyond the ENCODER position table fails at
    construction, not with an opaque shape error on every prefill."""
    model = _tiny_model(seed=29, max_length=8)
    with pytest.raises(MXNetError):
        _server(model, max_src_len=16)


def test_streaming_yields_incrementally():
    srv = _server(max_new_tokens=6)
    rng = np.random.RandomState(4)
    toks = list(srv.stream(rng.randint(4, 50, (5,)), timeout=30))
    assert 1 <= len(toks) <= 6
    assert all(isinstance(t, int) for t in toks)
    assert srv.pool.in_use() == 0
    srv.close()


def test_engine_driven_server():
    """The decode loop as dependency-engine tasks: submits from the user
    thread, decoding on engine workers, clean drain + close."""
    from mxnet_tpu import engine
    srv = _server(engine_driven=True, max_new_tokens=6)
    rng = np.random.RandomState(6)
    hs = [srv.submit(rng.randint(4, 50, (n,))) for n in (5, 8, 3)]
    res = [h.result(timeout=60) for h in hs]
    assert all(1 <= len(r) <= 6 for r in res)
    assert srv.wait(timeout=30)
    assert srv.pool.in_use() == 0
    srv.close()
    assert not any("serve" in f["site"] for f in engine.failures())


def test_page_exhaustion_preempts_not_deadlocks():
    """Two long requests on a pool that cannot hold both: the loser is
    preempted (pages freed, requeued) instead of wedging the batch, and
    everything still completes with zero leaked pages."""
    reg = registry()
    pre0 = reg.counter("serve_page_preemptions").value
    model = _tiny_model(seed=17)
    srv = _server(model, slots=2, page_size=2, num_pages=4,  # 3 usable
                  max_new_tokens=6, max_retries=5)
    rng = np.random.RandomState(7)
    h1 = srv.submit(rng.randint(4, 50, (5,)), max_new_tokens=6)
    h2 = srv.submit(rng.randint(4, 50, (6,)), max_new_tokens=6)
    srv.scheduler.run_until_idle(max_steps=500)
    assert len(h1.result()) >= 1 and len(h2.result()) >= 1
    assert reg.counter("serve_page_preemptions").value > pre0
    # preemption is queueing, not a fault: the retry budget is untouched
    assert h1.preemptions + h2.preemptions >= 1
    assert h1.retries == 0 and h2.retries == 0
    assert srv.pool.in_use() == 0
    srv.close()


def test_defrag_midflight_keeps_decoding_correctly():
    """Pool compaction between steps (device remap + table remap) must
    not change what a request generates."""
    def run(with_defrag):
        model = _tiny_model(seed=19)
        srv = _server(model, slots=2, page_size=2, max_new_tokens=8)
        rng = np.random.RandomState(8)
        h1 = srv.submit(rng.randint(4, 50, (6,)), max_new_tokens=8)
        h2 = srv.submit(rng.randint(4, 50, (4,)), max_new_tokens=2)
        sched = srv.scheduler
        for i in range(40):
            if not sched.pending_work():
                break
            sched.step()
            if with_defrag and i == 3:
                # h2 finished -> holes in the pool -> compaction moves
                # h1's live pages mid-request
                sched.defrag()
        out = (h1.result(), h2.result())
        assert srv.pool.in_use() == 0
        srv.close()
        return out

    assert run(True) == run(False)


# ------------------------------------------------------------- chaos
def test_chaos_decode_fault_retries_without_leaking():
    """A fault mid-decode kills the in-flight batch: requests are retried
    from scratch and complete; page accounting returns to baseline."""
    reg = registry()
    ret0 = reg.counter("serve_decode_retries").value
    srv = _server(max_new_tokens=6, max_retries=2)
    rng = np.random.RandomState(9)
    finj.inject("serve.decode", at=[2])      # second decode turn dies
    h1 = srv.submit(rng.randint(4, 50, (5,)))
    h2 = srv.submit(rng.randint(4, 50, (7,)))
    srv.scheduler.run_until_idle(max_steps=500)
    assert finj.fires("serve.decode") == 1
    assert len(h1.result()) >= 1 and len(h2.result()) >= 1
    assert h1.retries + h2.retries >= 1
    assert reg.counter("serve_decode_retries").value == ret0 + 1
    assert srv.pool.in_use() == 0
    # the stream restarted with the retry: no pre-fault token prefix
    # duplicated ahead of the regenerated sequence
    assert list(h1.stream(timeout=1)) == h1.result()
    assert list(h2.stream(timeout=1)) == h2.result()
    srv.close()


def test_requeue_rearms_stream_and_ttft():
    """A retried request restarts its stream (undelivered chunks of the
    aborted attempt dropped) and re-arms TTFT measurement."""
    srv = _server(max_new_tokens=6, max_retries=2)
    rng = np.random.RandomState(24)
    finj.inject("serve.decode", at=[2])      # die after one emitted token
    h = srv.submit(rng.randint(4, 50, (5,)))
    sched = srv.scheduler
    sched.step()                             # admit + first token
    assert len(h.tokens) == 1 and h.t_first_token is not None
    sched.step()                             # fault -> requeue
    assert h.state == "queued" and h.retries == 1
    assert h.t_first_token is None           # TTFT re-arms
    assert not h._chunks                     # aborted chunks dropped
    sched.run_until_idle(max_steps=200)
    assert list(h.stream(timeout=1)) == h.result()
    assert h.ttft is not None and h.ttft <= h.latency
    assert srv.pool.in_use() == 0
    srv.close()


def test_chaos_decode_fault_exhausted_retries_fails_cleanly():
    srv = _server(max_new_tokens=6, max_retries=1)
    rng = np.random.RandomState(10)
    finj.inject("serve.decode", prob=1.0)    # every decode turn dies
    h = srv.submit(rng.randint(4, 50, (5,)))
    srv.scheduler.run_until_idle(max_steps=100)
    assert h.state == "failed"
    with pytest.raises(ServeError):
        h.result(timeout=1)
    assert srv.pool.in_use() == 0            # failed != leaked
    srv.close()


def test_prefill_failure_fails_only_that_request():
    """An ordinary prefill error (donated buffers still alive — the CPU
    case) fails the admitted request only; in-flight traffic continues."""
    srv = _server(max_new_tokens=4)
    rng = np.random.RandomState(25)
    ok1 = srv.submit(rng.randint(4, 50, (5,)))
    srv.scheduler.step()                     # ok1 admitted + decoding
    orig = srv.runtime._prefill_fn
    calls = {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient prefill failure")
        return orig(*args)

    srv.runtime._prefill_fn = flaky
    bad = srv.submit(rng.randint(4, 50, (4,)))
    ok2 = srv.submit(rng.randint(4, 50, (6,)))
    srv.scheduler.run_until_idle(max_steps=200)
    assert bad.state == "failed"
    assert len(ok1.result()) >= 1 and len(ok2.result()) >= 1
    assert srv.pool.in_use() == 0
    srv.close()


def test_prefill_memory_loss_restarts_inflight_requests():
    """A prefill failure that consumed the donated memory buffers
    (`MemoryStateLost`) restarts EVERY in-flight request — re-admission
    re-prefills each slot — with zero leaked pages."""
    srv = _server(max_new_tokens=4, max_retries=2)
    rng = np.random.RandomState(26)
    inflight = srv.submit(rng.randint(4, 50, (5,)))
    srv.scheduler.step()                     # admitted + one token
    assert inflight.state == "running"
    orig = srv.runtime._prefill_fn
    calls = {"n": 0}

    def lossy(mem_k, *args):
        calls["n"] += 1
        if calls["n"] == 1:
            mem_k.delete()                   # what a consumed donation is
            raise RuntimeError("prefill consumed donated buffers")
        return orig(mem_k, *args)

    srv.runtime._prefill_fn = lossy
    bad = srv.submit(rng.randint(4, 50, (4,)))
    srv.scheduler.run_until_idle(max_steps=200)
    assert bad.state == "failed"
    # the in-flight request was restarted from scratch and completed
    assert inflight.retries >= 1
    assert len(inflight.result()) >= 1
    assert srv.pool.in_use() == 0
    srv.close()


def test_chaos_admit_fault_rejects_one_request():
    srv = _server()
    rng = np.random.RandomState(12)
    finj.inject("serve.admit", at=[1])
    with pytest.raises(ServeError):
        srv.submit(rng.randint(4, 50, (4,)))
    h = srv.submit(rng.randint(4, 50, (4,)))  # next one sails through
    assert len(h.result()) >= 1
    assert srv.pool.in_use() == 0
    srv.close()


# ------------------------------------------------------------ metrics
def test_serve_metrics_and_percentiles():
    reg = registry()
    ttft = reg.histogram("serve_ttft_seconds")
    lat = reg.histogram("serve_request_seconds")
    t0, l0 = ttft.count, lat.count
    srv = _server(max_new_tokens=4)
    rng = np.random.RandomState(14)
    hs = [srv.submit(rng.randint(4, 50, (5,))) for _ in range(3)]
    for h in hs:
        h.result()
    srv.close()
    assert ttft.count == t0 + 3 and lat.count == l0 + 3
    snap = lat.snapshot()
    # the quantile-snapshot satellite: p50/p95/p99 all present + ordered
    assert snap["count"] >= 3
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    qs = lat.quantiles((0.5, 0.95, 0.99))
    assert qs[0.5] == snap["p50"] and qs[0.99] == snap["p99"]
    tps = srv.throughput()
    assert tps > 0
    assert reg.gauge("serve_tokens_per_s").snapshot() == tps


def test_warm_server_zero_recompiles_against_compile_counters():
    """ISSUE 11 satellite: the existing retrace pin (decode compiles
    once, ever) restated against the compile observatory — a WARM server
    performs ZERO recompiles of either executable across varying slot
    occupancy, measured on `compiles{executable=serve_decode|serve_prefill}`,
    and both executables land compile telemetry in the metrics snapshot."""
    reg = registry()
    dec_c = reg.counter("compiles", executable="serve_decode")
    pre_c = reg.counter("compiles", executable="serve_prefill")
    srv = _server(slots=3, max_new_tokens=8)
    rng = np.random.RandomState(21)
    # warm: the first request compiles prefill + decode exactly once
    srv.submit(rng.randint(4, 50, (5,)), max_new_tokens=3).result()
    base_d, base_p = dec_c.value, pre_c.value
    assert srv.runtime.decode_traces == 1
    # mixed-length traffic so occupancy and page tables vary mid-flight
    hs = [srv.submit(rng.randint(4, 50, (n,)), max_new_tokens=t)
          for n, t in ((3, 8), (7, 2), (6, 5), (8, 4), (4, 7))]
    for h in hs:
        h.result()
    assert dec_c.value == base_d, "warm decode recompiled"
    assert pre_c.value == base_p, "warm prefill recompiled"
    assert srv.runtime.decode_traces == 1
    srv.close()
    # per-executable compile telemetry (prefill vs decode) is in the
    # snapshot next to the serve_* series
    snap = reg.snapshot()
    execs = {dict(s["labels"]).get("executable")
             for s in snap.get("compile_seconds", [])}
    assert {"serve_decode", "serve_prefill"} <= execs


def test_encode_memory_matches_eager_encoder_bitwise():
    """The prefill executable's pure encoder is bitwise-equal to the
    eager `model.encode` path (they share flash_attention and the
    layer math)."""
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer import encode_memory, encoder_weights
    model = _tiny_model()
    rng = np.random.RandomState(15)
    src = rng.randint(4, 50, (2, 12)).astype(np.int32)
    svl = np.array([8, 12], np.int32)
    eager, _ = model.encode(mx.nd.array(src), mx.nd.array(svl))
    pure = encode_memory(encoder_weights(model), jnp.asarray(src),
                         jnp.asarray(svl))
    assert np.array_equal(eager.asnumpy(), np.asarray(pure))


# ------------------------------------------- per-request deadlines (ISSUE 7)
def test_deadline_expired_in_queue_evicted_cleanly():
    """A queued request whose deadline elapses before admission fails
    with ServeDeadlineExceeded — not a generic ServeError — pages stay
    at baseline and serve_deadline_expired counts it."""
    from mxnet_tpu.serve import ServeDeadlineExceeded
    reg = registry()
    base = reg.counter("serve_deadline_expired").value
    srv = _server(slots=1, max_new_tokens=8)
    rng = np.random.RandomState(21)
    # a long request occupies the only slot...
    long_h = srv.submit(rng.randint(4, 50, (5,)), max_new_tokens=8)
    srv.scheduler.step()                 # admit it
    # ...so this one waits in queue past its deadline
    doomed = srv.submit(rng.randint(4, 50, (4,)), max_new_tokens=4,
                        deadline_ms=1)
    import time
    time.sleep(0.02)
    srv.scheduler.step()                 # sweep fires
    assert doomed.done()
    with pytest.raises(ServeDeadlineExceeded):
        doomed.result()
    assert reg.counter("serve_deadline_expired").value == base + 1
    srv.scheduler.run_until_idle()
    assert len(long_h.result()) >= 1     # the slot holder is unaffected
    assert srv.pool.in_use() == 0
    srv.close()


def test_deadline_expired_mid_decode_frees_pages():
    """A RUNNING request past its deadline is evicted mid-decode: pages
    return to the pool, the stream ends with ServeDeadlineExceeded, and
    other in-flight requests keep decoding."""
    from mxnet_tpu.serve import ServeDeadlineExceeded
    srv = _server(slots=2, max_new_tokens=12)
    rng = np.random.RandomState(22)
    doomed = srv.submit(rng.randint(4, 50, (5,)), max_new_tokens=12,
                        deadline_ms=30)
    other = srv.submit(rng.randint(4, 50, (4,)), max_new_tokens=3)
    sched = srv.scheduler
    sched.step()                          # admit both, decode one token
    import time
    time.sleep(0.05)                      # doomed's deadline elapses
    sched.run_until_idle(max_steps=200)
    with pytest.raises(ServeDeadlineExceeded):
        doomed.result()
    assert doomed.state == "failed"
    assert len(other.result()) >= 1       # neighbour finished normally
    assert srv.pool.in_use() == 0         # evicted pages freed
    srv.close()


def test_no_deadline_requests_unaffected():
    """deadline_ms=None (default) keeps the old behaviour bit-for-bit."""
    srv = _server(max_new_tokens=4)
    rng = np.random.RandomState(23)
    h = srv.submit(rng.randint(4, 50, (5,)))
    assert len(h.result(timeout=60)) >= 1
    assert srv.pool.in_use() == 0
    srv.close()


def test_engine_loop_survives_injected_task_fault():
    """QoS hardening (ISSUE 7): an injected engine.task fault that kills
    a serve loop task must not wedge the server — the loop re-arms on a
    fresh var (serve_loop_restarts counts it) and every request still
    completes with zero leaked pages."""
    from mxnet_tpu import engine
    reg = registry()
    base_restarts = reg.counter("serve_loop_restarts").value
    srv = _server(engine_driven=True, max_new_tokens=6)
    rng = np.random.RandomState(24)
    # warm one request through so the executables are compiled and the
    # fault hits a steady-state loop task
    srv.submit(rng.randint(4, 50, (4,))).result(timeout=120)
    # drain BEFORE arming: the warm-up loop task may still be in flight
    # (result() returns on the last token, the task disarms later) and a
    # straggler task from an earlier test could otherwise absorb the
    # at=[1] fault — it must hit the loop task the next submit kicks
    engine.wait_for_all()
    finj.inject("engine.task", at=[1])    # the NEXT engine task dies
    hs = [srv.submit(rng.randint(4, 50, (n,))) for n in (5, 6, 3)]
    res = [h.result(timeout=120) for h in hs]
    finj.clear("engine.task")
    assert all(1 <= len(r) <= 6 for r in res)
    assert srv.wait(timeout=60)
    assert srv.pool.in_use() == 0
    srv.close()
    assert reg.counter("serve_loop_restarts").value > base_restarts
    # the fault is VISIBLE (sticky failure report), not swallowed
    assert any("FaultInjected" in f["error"] for f in engine.failures())
    engine.clear_failures()


def test_engine_loop_survives_high_class_queue_limits():
    """QoS hardening (ISSUE 7): a bounded HIGH-class queue that sheds or
    rejects a serve loop task must not leave the loop armed-but-taskless
    — shed tasks re-push, rejected kicks disarm so the next kick
    retries."""
    import threading
    import time
    from mxnet_tpu import engine
    from mxnet_tpu.serve.engine_bridge import EngineLoop

    class FakeSched:
        def __init__(self, work):
            self.work = work

        def step(self):
            if self.work:
                self.work -= 1
                return True
            return False

        def pending_work(self):
            return self.work > 0

    # shed: wedge every worker, queue the loop task, shed it with a
    # second high push — the loop must re-push itself and still drain
    sched = FakeSched(3)
    loop = EngineLoop(sched)
    gate = threading.Event()
    for _ in range(engine.num_workers()):
        engine.push(gate.wait)
    time.sleep(0.05)
    prev = engine.set_queue_limit(engine.PRIORITY_HIGH, 1, "shed_oldest")
    try:
        loop.kick()                              # queued loop task
        engine.push(lambda: None, priority=engine.PRIORITY_HIGH)  # sheds it
        gate.set()
        deadline = time.monotonic() + 10
        while sched.pending_work() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not sched.pending_work()          # shed loop task re-pushed
    finally:
        engine.set_queue_limit(engine.PRIORITY_HIGH, *prev)
        gate.set()
    loop.close()
    engine.wait_for_all()

    # reject: a kick into a full high queue disarms instead of wedging;
    # once the limit lifts, the next kick decodes again
    sched2 = FakeSched(2)
    loop2 = EngineLoop(sched2)
    gate2 = threading.Event()
    blocker = engine.push(gate2.wait, priority=engine.PRIORITY_HIGH)
    time.sleep(0.05)
    prev = engine.set_queue_limit(engine.PRIORITY_HIGH, 1, "reject")
    try:
        wedge = threading.Event()
        for _ in range(engine.num_workers()):
            engine.push(wedge.wait)
        time.sleep(0.05)
        # blocker running, workers wedged: one queued high task fills the
        # limit, so the loop's kick is rejected -> must disarm cleanly
        engine.push(lambda: None, priority=engine.PRIORITY_HIGH)
        loop2.kick()
        assert sched2.pending_work()             # nothing ran yet
        wedge.set()
        gate2.set()
    finally:
        engine.set_queue_limit(engine.PRIORITY_HIGH, *prev)
        gate2.set()
        wedge.set()
    engine.wait_for_all()
    loop2.kick()                                 # retried kick proceeds
    deadline = time.monotonic() + 10
    while sched2.pending_work() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not sched2.pending_work()
    loop2.close()
    assert blocker.done()


# ------------------------------------------- serving fast path (ISSUE 12)
def test_page_pool_free_is_atomic_regression():
    """A double-free mid-list must leave the pool UNTOUCHED: before the
    fix, the earlier pages of the list were already freed and counted
    when the error fired, corrupting the leak accounting the tier-1
    gates assert on."""
    reg = registry()
    pool = PagePool(num_pages=8, page_size=4)
    a = pool.alloc(3)
    pool.free([a[0]])
    frees0 = reg.counter("kv_page_frees").value
    with pytest.raises(MXNetError):
        pool.free([a[1], a[0], a[2]])    # a[0] already free, mid-list
    # NOTHING moved: a[1]/a[2] still live, free counter flat
    assert pool.in_use() == 2
    assert pool.ref_count(a[1]) == 1 and pool.ref_count(a[2]) == 1
    assert reg.counter("kv_page_frees").value == frees0
    # over-release via duplicates within ONE list is caught up front too
    with pytest.raises(MXNetError):
        pool.free([a[1], a[1]])
    assert pool.in_use() == 2
    pool.free([a[1], a[2]])
    assert pool.in_use() == 0


def test_page_pool_refcount_sharing():
    reg = registry()
    pool = PagePool(num_pages=8, page_size=4)
    pages = pool.alloc(2)
    pool.share(pages)                    # second owner
    assert pool.ref_count(pages[0]) == 2
    assert pool.total_refs() == 4
    assert reg.gauge("kv_page_refs").value == 4
    pool.free(pages)                     # first owner releases
    assert pool.in_use() == 2            # still live (one owner left)
    assert pool.available() == 5
    # duplicate releases within one list are legal up to the refcount
    pool.share([pages[0]])
    pool.free([pages[0], pages[0]])
    assert pool.ref_count(pages[0]) == 0
    pool.free([pages[1]])
    assert pool.in_use() == 0 and pool.total_refs() == 0
    with pytest.raises(MXNetError):
        pool.share([pages[0]])           # free page: nothing to share


def test_prefix_cache_radix_unit():
    from mxnet_tpu.serve.prefix_cache import PrefixCache, content_key
    pool = PagePool(num_pages=16, page_size=4)
    cache = PrefixCache(pool)
    k1 = content_key([7, 8, 9])
    k2 = content_key([7, 8])             # different source: no sharing
    seq = [2, 10, 11, 12, 13, 14, 15, 16, 17]    # [BOS] + 8 prompt
    pages = pool.alloc(2)
    assert cache.insert(k1, seq, pages) == 2
    assert pool.ref_count(pages[0]) == 2         # cache's own reference
    # full match, partial match, foreign-source and diverging lookups
    assert cache.lookup(k1, seq, 2) == pages
    assert cache.lookup(k1, seq, 1) == [pages[0]]
    assert cache.lookup(k2, seq, 2) == []
    div = list(seq)
    div[6] = 99                                   # diverges in chunk 2
    assert cache.lookup(k1, div, 2) == [pages[0]]
    # owner releases; cache keeps the pages alive at refcount 1
    pool.free(pages)
    assert pool.in_use() == 2
    # LRU eviction: only LEAF nodes with no in-flight adopters go, least
    # recently used first — and an adopted page is skipped
    pool.share([pages[1]])                        # simulate an adopter
    assert cache.evict(2) == 0                    # leaf pinned, parent has
    pool.free([pages[1]])                         # a child: nothing to do
    assert cache.evict(1) == 1                    # leaf (chunk 2) goes
    assert cache.lookup(k1, seq, 2) == [pages[0]]
    assert cache.evict(1) == 1                    # now the root chunk
    assert cache.pages_held() == 0 and pool.in_use() == 0
    # remap keeps the index coherent with a real defrag
    anchors = pool.alloc(2)                       # occupy the low ids
    p2 = pool.alloc(1)
    cache.insert(k1, seq, p2)
    pool.free(p2)                                 # cache is the only owner
    pool.free(anchors)                            # low ids free: p2 moves
    mapping = pool.defrag()
    cache.remap(mapping)
    new_id = mapping[p2[0]]
    assert cache.lookup(k1, seq, 1) == [new_id]
    assert cache.clear() == 1
    assert pool.in_use() == 0


def _drain(srv, *submits, max_steps=500):
    handles = [srv.submit(s, max_new_tokens=m, prompt_tokens=p)
               for s, m, p in submits]
    srv.scheduler.run_until_idle(max_steps=max_steps)
    return [h.result(timeout=60) for h in handles]


def test_prompted_greedy_bitwise_contract():
    """THE fast-path contract: for one (source, prompt, budget) request
    the committed token sequence is IDENTICAL across every serving
    configuration — prefix cache cold, warm, disabled; speculative k=2
    and k=3 — and page refcounts return to the cache-held baseline
    after every request, to zero after close()."""
    from mxnet_tpu import profiler
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(5)
    src = rng.randint(4, 50, (7,)).astype(np.int32)
    prompt = rng.randint(4, 50, (9,)).astype(np.int32)

    def run(srv):
        t0 = srv.scheduler.decode_turns
        out = _drain(srv, (src, 8, prompt))[0]
        return out, srv.scheduler.decode_turns - t0

    srv = _server(model, max_new_tokens=8, max_prompt_len=12,
                  num_pages=16)
    cold, cold_turns = run(srv)
    warm, warm_turns = run(srv)
    assert warm == cold
    assert warm_turns < cold_turns          # adopted pages skip prefill
    cache = srv.prefix_cache
    assert cache.hits == 1 and cache.misses == 1
    assert cache.tokens_saved == 8          # two full 4-token pages
    # drained: only the cache holds pages, each at refcount exactly 1
    assert srv.pool.in_use() == cache.pages_held() == 2
    assert srv.pool.total_refs() == 2
    srv.close()
    assert srv.pool.in_use() == 0 and srv.pool.total_refs() == 0

    srv = _server(model, max_new_tokens=8, max_prompt_len=12,
                  num_pages=16, prefix_cache=False)
    nocache, _ = run(srv)
    assert srv.prefix_cache is None
    srv.close()
    assert nocache == cold

    for k in (2, 3):
        srv = _server(model, max_new_tokens=8, max_prompt_len=12,
                      num_pages=16, speculative_k=k)
        spec, _ = run(srv)
        assert spec == cold, f"speculative k={k} changed greedy output"
        assert srv.runtime.verify_traces == 1
        srv.close()
        assert srv.pool.in_use() == 0


def test_speculative_accepts_and_reduces_turns():
    """On self-repetitive greedy output the n-gram proposer earns its
    keep: drafted tokens get accepted, a solo request finishes in fewer
    decode turns than tokens, and the acceptance histogram records the
    distribution profiler.dumps() surfaces."""
    reg = registry()
    hist0 = reg.histogram("serve_spec_accepted_tokens").count
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(5)
    src = rng.randint(4, 50, (7,)).astype(np.int32)
    srv = _server(model, max_new_tokens=12, max_prompt_len=12,
                  num_pages=16, speculative_k=3)
    out = _drain(srv, (src, 12, None))[0]
    sched = srv.scheduler
    assert sched.spec_accepted > 0
    assert sched.decode_turns < len(out)    # strictly fewer turns/token
    assert reg.histogram("serve_spec_accepted_tokens").count > hist0
    srv.close()


def test_prefix_eviction_under_pressure():
    """When the pool is dry, admission evicts LRU cache-only pages
    instead of failing or preempting — cached prefixes only cost
    capacity while it is spare."""
    reg = registry()
    ev0 = reg.counter("serve_prefix_evictions").value
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(6)
    src = rng.randint(4, 50, (5,)).astype(np.int32)
    pa = rng.randint(4, 50, (9,)).astype(np.int32)
    pb = rng.randint(4, 50, (9,)).astype(np.int32)
    # capacity 5: a request's working set is 4 pages (prompt 9 + 6 new),
    # so after A leaves its 2 cached pages behind, B's growth hits a dry
    # pool and must reclaim from the cache
    srv = _server(model, slots=1, max_new_tokens=6, max_prompt_len=12,
                  num_pages=6)
    a = _drain(srv, (src, 6, pa))[0]
    assert srv.prefix_cache.pages_held() == 2
    b = _drain(srv, (src, 6, pb))[0]
    assert len(a) >= 1 and len(b) >= 1
    assert reg.counter("serve_prefix_evictions").value > ev0
    # evicted pages left the cache index too — nothing dangling
    assert srv.pool.in_use() == srv.prefix_cache.pages_held()
    srv.close()
    assert srv.pool.in_use() == 0


def test_chaos_prefix_and_speculate_faults_degrade_identically():
    """Injected cache-lookup/insert and draft faults DEGRADE (cold path /
    unspeculated turn) with bitwise-identical request output, zero
    leaked pages and zero stuck refcounts."""
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(8)
    reqs = [(rng.randint(4, 50, (6,)).astype(np.int32),
             5, rng.randint(4, 50, (9,)).astype(np.int32))
            for _ in range(3)]
    reqs.append(reqs[0])                    # a warm repeat in the mix

    def run(faulty):
        srv = _server(model, slots=2, max_new_tokens=6, max_prompt_len=12,
                      num_pages=24, speculative_k=2)
        fired = 0
        if faulty:
            finj.inject("serve.prefix", prob=0.5, seed=13)
            finj.inject("serve.speculate", prob=0.5, seed=14)
        try:
            outs = _drain(srv, *reqs)
            if faulty:
                fired = (finj.fires("serve.prefix")
                         + finj.fires("serve.speculate"))
        finally:
            finj.clear()
        held = srv.prefix_cache.pages_held()
        assert srv.pool.in_use() == held    # requests fully released
        assert srv.pool.total_refs() == held
        srv.close()
        assert srv.pool.in_use() == 0
        return outs, fired

    clean, _ = run(faulty=False)
    chaos, fired = run(faulty=True)
    assert fired > 0
    assert chaos == clean


def test_spec_preemption_with_prompt_no_leak():
    """Page-pressure preemption under speculation + prompts: requests
    restart (re-adopting any cached prefix), complete, and the pool
    returns to the cache-held baseline."""
    reg = registry()
    pre0 = reg.counter("serve_page_preemptions").value
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(11)
    src = rng.randint(4, 50, (5,)).astype(np.int32)
    prompts = [rng.randint(4, 50, (6,)).astype(np.int32)
               for _ in range(2)]
    srv = _server(model, slots=2, max_new_tokens=8, max_prompt_len=8,
                  num_pages=7, speculative_k=2)   # capacity 6: contended
    outs = _drain(srv, (src, 8, prompts[0]), (src, 8, prompts[1]),
                  max_steps=2000)
    assert all(len(o) >= 1 for o in outs)
    assert reg.counter("serve_page_preemptions").value > pre0
    assert srv.pool.in_use() == srv.prefix_cache.pages_held()
    srv.close()
    assert srv.pool.in_use() == 0


def test_submit_prompt_validation():
    srv = _server(max_new_tokens=8, max_prompt_len=8)
    with pytest.raises(MXNetError):
        # prompt + max_new over the per-slot page budget
        srv.submit([5, 6, 7], max_new_tokens=8,
                   prompt_tokens=list(range(4, 20)))
    srv.close()


def test_paged_attention_multi_rowwise_matches_single():
    """The widened lax fallback runs the SAME shared math per query row:
    row i equals the single-query path over `lengths + i` visible keys
    to reduction-order tolerance (XLA batches the W-row contraction; the
    TOKEN-level identity the speculative commits rely on is pinned end
    to end by test_prompted_greedy_bitwise_contract)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import (_paged_attention_lax,
                                              _paged_attention_lax_multi)
    q1, kp, vp, pt, lens = _paged_fixture()
    S, H, dh = q1.shape
    W = 3
    rng = np.random.RandomState(21)
    q = jnp.asarray(rng.randn(S, W, H, dh).astype(np.float32))
    out = _paged_attention_lax_multi(q, kp, vp, pt, lens)
    for i in range(W):
        ref = _paged_attention_lax(q[:, i], kp, vp, pt, lens + i)
        np.testing.assert_allclose(np.asarray(out[:, i]),
                                   np.asarray(ref),
                                   rtol=2e-6, atol=2e-6, err_msg=str(i))


@pytest.mark.parametrize("cfg", [
    {}, {"buffers": 2}, {"psize": 16, "buffers": 3},
    {"ragged": dict(_RAGGED, W=3)}, {"ragged": dict(_RAGGED, W=1)},
    {"ragged": dict(_SPLIT, W=3)}, {"ragged": dict(_CHUNKS, W=4)},
    {"ragged": dict(_UNEVEN, W=4)}],
    ids=["default", "buffers=2", "buffers=3", "ragged-W=3", "ragged-W=1",
         "split-W=3", "chunks-W=4", "uneven-W=4"])
def test_paged_attention_multi_kernel_interpret(monkeypatch, cfg):
    """The widened Pallas kernel numerics, pinned on CPU via interpret
    mode against the lax fallback (same harness as the 1-wide test), with
    the page walker's ring `_RPA_BUFFERS`, two and three buffers deep."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    cfg = dict(cfg)
    if "buffers" in cfg:
        monkeypatch.setattr(pk, "_RPA_BUFFERS", cfg.pop("buffers"))
    if "ragged" in cfg:
        q, kp, vp, pt, lens, _ = _ragged_case(monkeypatch,
                                              **cfg.pop("ragged"))
    else:
        q1, kp, vp, pt, lens = _paged_fixture(psize=cfg.get("psize", 8))
        S, H, dh = q1.shape
        rng = np.random.RandomState(22)
        q = jnp.asarray(rng.randn(S, 4, H, dh).astype(np.float32))
    out_k = pk.ragged_paged_attention(q, kp, vp, pt, lens)
    ref = pk._paged_attention_lax_multi(q, kp, vp, pt, lens)
    _assert_seen_rows_close(out_k, ref, lens)


def test_cache_aware_admission_prefers_warm_prefix_under_pressure():
    """When pages are tight, admission reorders the queue toward the
    request with the longest warm cached prefix (smaller fresh-page
    cost) instead of blind FIFO — counted by
    `serve_prefix_admit_preferred`."""
    reg = registry()
    pref0 = reg.counter("serve_prefix_admit_preferred").value
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(14)
    src = rng.randint(4, 50, (5,)).astype(np.int32)
    pa = rng.randint(4, 50, (9,)).astype(np.int32)
    pc = rng.randint(4, 50, (9,)).astype(np.int32)
    srv = _server(model, slots=1, max_new_tokens=6, max_prompt_len=12,
                  num_pages=6)                    # capacity 5: tight
    _drain(srv, (src, 6, pa))                     # cache pa's 2 pages
    blocker = srv.submit(src, max_new_tokens=6)   # occupies the slot
    srv.scheduler.step()
    assert blocker.state == "running"
    cold = srv.submit(src, max_new_tokens=6, prompt_tokens=pc)
    warm = srv.submit(src, max_new_tokens=6, prompt_tokens=pa)
    srv.scheduler.run_until_idle(max_steps=1000)
    assert len(cold.result()) >= 1 and len(warm.result()) >= 1
    assert reg.counter("serve_prefix_admit_preferred").value > pref0
    assert warm.prompt_cached_tokens == 8         # adopted, not rebuilt
    assert warm.t_done < cold.t_done              # warm jumped the queue
    srv.close()
    assert srv.pool.in_use() == 0


def test_warm_preference_cannot_starve_cold_head():
    """The warm-prefix admission preference is BOUNDED: a cold queue
    head bypassed `MAX_ADMIT_BYPASS` times is admitted regardless, so
    sustained warm traffic cannot starve it."""
    from mxnet_tpu.serve.scheduler import Scheduler
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(15)
    src = rng.randint(4, 50, (5,)).astype(np.int32)
    pa = rng.randint(4, 50, (9,)).astype(np.int32)
    pc = rng.randint(4, 50, (9,)).astype(np.int32)
    srv = _server(model, slots=1, max_new_tokens=6, max_prompt_len=12,
                  num_pages=6, max_queue=16)     # capacity 5: tight
    _drain(srv, (src, 6, pa))                    # warm pa's prefix
    blocker = srv.submit(src, max_new_tokens=6)
    srv.scheduler.step()
    cold = srv.submit(src, max_new_tokens=6, prompt_tokens=pc)
    warms = [srv.submit(src, max_new_tokens=6, prompt_tokens=pa)
             for _ in range(Scheduler.MAX_ADMIT_BYPASS + 2)]
    srv.scheduler.run_until_idle(max_steps=4000)
    assert len(cold.result()) >= 1
    # the bound bit: cold was bypassed at most MAX_ADMIT_BYPASS times,
    # so it finished before the LAST warm request
    assert cold._admit_bypassed <= Scheduler.MAX_ADMIT_BYPASS
    assert cold.t_done < warms[-1].t_done
    assert len(blocker.result()) >= 1
    srv.close()
    assert srv.pool.in_use() == 0


# ------------------------------------------- low precision (ISSUE 14)
def _int8_model():
    # smaller than _tiny_model: the low-precision suite compiles several
    # extra executables, and the tier-1 window is tight
    return _tiny_model(vocab=40, units=16, layers=1, heads=2,
                       max_length=48, seed=13)


def _match_rate(ref, out):
    matched = sum(sum(1 for x, y in zip(a, b) if x == y)
                  for a, b in zip(ref, out))
    total = sum(max(len(a), len(b)) for a, b in zip(ref, out))
    return matched / max(total, 1)


def _lp_requests(n=5, seed=3):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        src = rng.randint(4, 40, (int(rng.randint(3, 10)),)).astype(
            np.int32)
        prompt = rng.randint(4, 40, (8,)).astype(np.int32) if i % 2 \
            else None
        reqs.append((src, int(rng.choice([4, 6, 8])), prompt))
    # repeat a prompted request so the prefix-warm path runs too
    return reqs + [r for r in reqs if r[2] is not None][:1]


def test_int8_kv_token_match_cold_warm_and_speculative():
    """The accuracy contract: int8-KV greedy output matches fp32 at
    >= 0.99 token-match rate across prefix-cache cold/warm traffic and
    speculative k in {2, 3} — and the pool accounting stays exact (no
    stuck references beyond the cache, zero after close)."""
    model = _int8_model()
    reqs = _lp_requests()
    fp = _server(model, max_prompt_len=8)
    ref = _drain(fp, *reqs)
    fp.close()
    for k in (0, 2, 3):
        srv = _server(model, max_prompt_len=8, kv_dtype="int8",
                      speculative_k=k)
        out = _drain(srv, *reqs)
        assert srv.pool.in_use() == srv.prefix_cache.pages_held()
        rate = _match_rate(ref, out)
        srv.close()
        assert srv.pool.in_use() == 0
        assert rate >= 0.99, (k, rate)


def test_int8_pages_carry_scales_through_radix_cache():
    """Shared int8 pages carry their scales: scales are indexed by page
    id in the pool-parallel scale arrays, so a warm request adopting
    cached prompt pages sees the cold request's exact quantised content
    AND grid — cold vs warm output is BITWISE identical."""
    model = _int8_model()
    rng = np.random.RandomState(7)
    src = rng.randint(4, 40, (6,)).astype(np.int32)
    prompt = rng.randint(4, 40, (8,)).astype(np.int32)
    srv = _server(model, max_prompt_len=8, kv_dtype="int8")
    cold = _drain(srv, (src, 8, prompt))[0]
    cache = srv.prefix_cache
    pages = [n.page for n in cache._nodes]
    assert pages, "prompt pages were not cached"
    ks = np.asarray(srv.runtime.k_scales)      # (L, H, P)
    vs = np.asarray(srv.runtime.v_scales)
    assert np.all(ks[:, :, pages] > 0) and np.all(vs[:, :, pages] > 0)
    hits0 = cache.hits
    warm = _drain(srv, (src, 8, prompt))[0]
    assert cache.hits == hits0 + 1
    assert warm == cold            # adopted pages + scales, bit for bit
    traces = srv.runtime.decode_traces
    srv.close()
    assert traces == 1 and srv.pool.in_use() == 0


def test_int8_kv_fixed_budget_capacity():
    """The capacity pin: a fixed HBM byte budget holds >= 1.9x the
    TOKENS of the fp32 pool (scale arrays included in the arithmetic),
    and `Server(kv_hbm_bytes=)` sizes its pool to exactly that
    accounting."""
    from mxnet_tpu.serve.quant import kv_page_bytes, token_capacity
    geo = dict(n_layers=1, page_size=4, num_heads=2, head_dim=8)
    budget = 32 * kv_page_bytes(kv_dtype="float32", **geo)
    cap_fp = token_capacity(budget, kv_dtype="float32", **geo)
    cap_q = token_capacity(budget, kv_dtype="int8", **geo)
    assert cap_q / cap_fp >= 1.9
    model = _int8_model()
    srv = _server(model, kv_dtype="int8", kv_hbm_bytes=budget,
                  max_new_tokens=8)
    assert srv.pool.capacity * srv.pool.page_size == cap_q
    assert srv.runtime.kv_bytes_per_page() == kv_page_bytes(
        kv_dtype="int8", **geo)
    srv.close()
    with pytest.raises(MXNetError):
        _server(model, kv_dtype="int8", kv_hbm_bytes=budget, num_pages=8)


def test_chaos_quant_fault_degrades_to_full_precision():
    """serve.quant chaos (the PR 12 fault-discipline mold): an injected
    quantization fault degrades THAT request to the full-precision path
    with output identical to an fp32 server's, zero leaked pages and
    zero stuck refcounts; the next request runs the quantized path
    normally."""
    from mxnet_tpu.observability import registry as _registry
    model = _int8_model()
    rng = np.random.RandomState(9)
    src = rng.randint(4, 40, (6,)).astype(np.int32)
    prompt = rng.randint(4, 40, (8,)).astype(np.int32)
    fp = _server(model, max_prompt_len=8)
    ref = _drain(fp, (src, 8, prompt))[0]
    fp.close()
    srv = _server(model, max_prompt_len=8, kv_dtype="int8",
                  weight_dtype="int8")
    deg0 = _registry().counter("serve_quant_degraded").value
    finj.inject("serve.quant", times=1)
    degraded = _drain(srv, (src, 8, prompt))[0]
    assert degraded == ref
    assert _registry().counter("serve_quant_degraded").value == deg0 + 1
    # the degraded request never touched the quantized pool: nothing
    # held beyond (possibly) cache pages, and no refcount above 1
    assert srv.pool.in_use() == srv.prefix_cache.pages_held()
    # fault exhausted: the next request runs quantized (counter flat,
    # decode executable actually dispatched)
    out2 = _drain(srv, (src, 8, prompt))[0]
    assert len(out2) == len(ref)
    assert _registry().counter("serve_quant_degraded").value == deg0 + 1
    assert srv.runtime.decode_traces == 1
    bad = [p for p in range(1, srv.pool.num_pages)
           if srv.pool.ref_count(p) > 1]
    assert not bad
    srv.close()
    assert srv.pool.in_use() == 0


def test_weight_int8_serve_matches_fp32():
    """Per-channel int8 weights: the serve snapshot quantises (decoder
    Dense leaves become (int8, bias, per-output-channel scale); the
    embed carries per-row scales), the MODEL's master weights stay full
    precision, and greedy output matches fp32 at >= 0.99."""
    import jax.numpy as jnp
    model = _int8_model()
    reqs = _lp_requests(n=4, seed=5)
    fp = _server(model, max_prompt_len=8)
    ref = _drain(fp, *reqs)
    fp.close()
    srv = _server(model, max_prompt_len=8, weight_dtype="int8")
    w = srv.runtime._w
    assert w["embed"].dtype == jnp.int8 and "embed_scale" in w
    wq, b, s = w["layers"][0]["qkv"]
    assert wq.dtype == jnp.int8 and s.shape == (wq.shape[0],)
    # master weights untouched
    assert model.embed.weight.data()._data.dtype == jnp.float32
    out = _drain(srv, *reqs)
    rate = _match_rate(ref, out)
    srv.close()
    assert rate >= 0.99, rate
    assert srv.pool.in_use() == 0


@pytest.mark.parametrize("tables", [dict(H=2, npages=2, lens=(12, 5, 16)),
                                    _RAGGED, _SPLIT, _CHUNKS, _SHARED],
                         ids=["fixture", "ragged", "split", "chunks",
                              "shared"])
@pytest.mark.parametrize("W", [None, 1, 3], ids=["single", "W=1", "W=3"])
def test_paged_attention_quant_kernel_interpret(monkeypatch, tables, W):
    """The quantised Pallas kernels' numerics (scales via scalar
    prefetch, one a head and page inside a step of several heads and
    pages, dequant in VMEM), pinned on CPU via interpret mode against
    the lax gathered-dequant fallback — 1-wide and widened."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops.pallas_kernels import (
        _paged_attention_lax, _paged_attention_lax_multi,
        ragged_paged_attention)
    q, kp, vp, pt, lens, (ks, vs) = _ragged_case(monkeypatch, W=W, int8=True,
                                                 **tables)
    out = ragged_paged_attention(q, kp, vp, pt, lens,
                                 k_scales=ks, v_scales=vs)
    lax_fn = _paged_attention_lax if W is None else _paged_attention_lax_multi
    _assert_seen_rows_close(
        out, lax_fn(q, kp, vp, pt, lens, k_scales=ks, v_scales=vs), lens)


def test_quant_degrade_honours_deadline():
    """A deadline_ms request hit by a serve.quant fault gets no deadline
    amnesty: the remaining budget rides into the full-precision
    fallback, and an already/soon-expired request surfaces the same
    `ServeDeadlineExceeded` the normal path raises (counted into
    `serve_deadline_expired`), with nothing leaked."""
    from mxnet_tpu.observability import registry as _registry
    from mxnet_tpu.serve.scheduler import ServeDeadlineExceeded
    model = _int8_model()
    rng = np.random.RandomState(4)
    src = rng.randint(4, 40, (6,)).astype(np.int32)
    srv = _server(model, kv_dtype="int8")
    exp0 = _registry().counter("serve_deadline_expired").value
    finj.inject("serve.quant", times=1)
    h = srv.submit(src, max_new_tokens=8, deadline_ms=0.5)
    with pytest.raises(ServeDeadlineExceeded):
        h.result(timeout=60)
    assert _registry().counter("serve_deadline_expired").value > exp0
    # fault exhausted + no deadline: the quantized path serves normally
    out = _drain(srv, (src, 4, None))[0]
    assert len(out) >= 1
    srv.close()
    assert srv.pool.in_use() == 0


# ------------------------------- the pools' layout is invisible (PR 29)
# what the (L, P, psize, H, dh) pools of PR 28 generated on this journey
_JOURNEY_TOKENS = [[30, 33, 33, 33, 30, 30, 30, 33, 33, 33], [16, 16],
                   [33, 33, 30, 30, 30, 30],
                   [30, 33, 33, 33, 30, 30, 30, 33, 33, 33]]


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp32", "int8"])
@pytest.mark.parametrize("k", [0, 2], ids=["width1", "widened"])
@pytest.mark.parametrize("lanes", [None, 128], ids=["lanes=dh", "lanes=128"])
def test_pool_layout_keeps_the_greedy_tokens(monkeypatch, lanes, kv_dtype,
                                             k):
    """A server driven through admissions into both slots, a queued
    request, `defrag` moving live pages mid-flight (`remap_pages`) and
    a prefix-cache hit generates the tokens pinned from the parent's
    pool layout: where a page's rows live on the device shows nowhere,
    be they as wide as the head (off the TPU) or whole lane tiles (what
    `pool_lanes` gives on it)."""
    if lanes:
        from mxnet_tpu.serve import decode
        monkeypatch.setattr(decode, "pool_lanes", lambda dh: lanes)
    model = _tiny_model(max_length=48, seed=4)
    # louder layers under a quieter embedding, so that the argmax follows
    # the attended context and not the token fed back
    for name, p in model.collect_params().items():
        if p.data().ndim >= 2:
            p.set_data(p.data() * (0.3 if "embed" in name else 6.0))
    srv = _server(model, page_size=2, max_new_tokens=10, max_prompt_len=12,
                  num_pages=40, kv_dtype=kv_dtype, speculative_k=k)
    rng = np.random.RandomState(3)
    src = [rng.randint(4, 50, (n,)).astype(np.int32) for n in (7, 4, 6)]
    prompt = rng.randint(4, 50, (9,)).astype(np.int32)
    handles = [srv.submit(src[0], max_new_tokens=10, prompt_tokens=prompt),
               srv.submit(src[1], max_new_tokens=2),
               srv.submit(src[2], max_new_tokens=6)]
    sched, moved = srv.scheduler, 0
    for _ in range(200):
        if not sched.pending_work():
            break
        sched.step()
        moved += sched.defrag()
    out = [h.result(timeout=60) for h in handles]
    hits0 = srv.prefix_cache.hits
    out += _drain(srv, (src[0], 10, prompt))
    assert moved > 0 and srv.prefix_cache.hits == hits0 + 1
    assert srv.pool.in_use() == srv.prefix_cache.pages_held()
    rt = srv.runtime
    assert rt.k_pages[0].shape[-1] == (lanes or 8)
    assert (rt.verify_traces if k else rt.decode_traces) == 1
    srv.close()
    assert srv.pool.in_use() == 0
    assert out == _JOURNEY_TOKENS


# -------------------------- one prefill dispatch a turn (PR 33)
def _wide_server(model, **kw):
    """Room for 2R + 3 admissions in one turn."""
    kw.setdefault("slots", 68)
    kw.setdefault("max_new_tokens", 3)
    kw.setdefault("max_queue", 80)
    return _server(model, **kw)


@pytest.mark.parametrize("n_of", [lambda r: 1, lambda r: 2, lambda r: r,
                                  lambda r: r + 1, lambda r: 2 * r + 3],
                         ids=["1", "2", "R", "R+1", "2R+3"])
def test_a_turns_admissions_share_prefill_dispatches(n_of):
    """n admissions in ONE turn ride in ceil(n / R) dispatches of the
    one prefill executable (a device loop over the dispatch's rows);
    every slot's encoder memory is what n one-row `prefill` calls
    write, and the tokens are those of a server that admits one request
    a turn."""
    model = _tiny_model()
    srv, ref = _wide_server(model), _wide_server(model)
    rt = srv.runtime
    r_n = rt.prefill_rows
    assert r_n == 32                # min(slots, 32), set in the code
    n = n_of(r_n)
    rng = np.random.RandomState(40 + n)
    srcs = [rng.randint(4, 50, (int(k),)).astype(np.int32)
            for k in rng.randint(1, 17, (n,))]
    rows = registry().counter("serve_prefill_rows")
    d0, rows0 = mx.profiler.dispatch_count("serve_prefill"), rows.value
    hs = [srv.submit(s) for s in srcs]
    res = srv.scheduler.step()
    assert res.admitted == n
    assert mx.profiler.dispatch_count("serve_prefill") - d0 == -(-n // r_n)
    assert rows.value - rows0 == n and rt.prefill_traces == 1
    assert sorted(h._slot for h in hs) == list(range(n))
    # the same sources, one row a dispatch, into the same slots
    for h, s in zip(hs, srcs):
        ref.runtime.prefill(h._slot, s)
    assert mx.profiler.dispatch_count("serve_prefill") - d0 \
        == -(-n // r_n) + n
    for got, want in ((rt.mem_k, ref.runtime.mem_k),
                      (rt.mem_v, ref.runtime.mem_v)):
        np.testing.assert_allclose(np.asarray(got)[:, :n],
                                   np.asarray(want)[:, :n],
                                   rtol=0, atol=1e-6)
    assert np.array_equal(np.asarray(rt.mem_vl)[:n],
                          [s.size for s in srcs])
    assert np.array_equal(np.asarray(rt.mem_vl), np.asarray(ref.runtime.mem_vl))
    srv.scheduler.run_until_idle(max_steps=200)
    one_at_a_time = []
    for s in srcs:
        one_at_a_time.append(ref.submit(s).result(timeout=60))
    assert [h.result(timeout=60) for h in hs] == one_at_a_time
    assert srv.pool.in_use() == 0 and ref.runtime.prefill_traces == 1
    srv.close()
    ref.close()


def test_pool_dry_mid_gather_admits_the_gathered_and_requeues_the_rest():
    """The third request finds no first page: the two gathered before it
    are prefilled (one dispatch) and seated, it goes back to the head of
    the queue with the fourth still behind it."""
    srv = _wide_server(_tiny_model(), num_pages=3, max_new_tokens=4,
                       prefix_cache=False)
    rng = np.random.RandomState(41)
    hs = [srv.submit(rng.randint(4, 50, (5,))) for _ in range(4)]
    d0 = mx.profiler.dispatch_count("serve_prefill")
    res = srv.scheduler.step()
    assert res.admitted == 2
    assert mx.profiler.dispatch_count("serve_prefill") - d0 == 1
    assert [h.state for h in hs] == ["running", "running", "queued",
                                     "queued"]
    assert list(srv.scheduler._queue) == hs[2:]
    assert all(len(h.result(timeout=60)) == 4 for h in hs)
    assert srv.pool.in_use() == 0
    srv.close()


def test_a_failed_dispatch_fails_its_own_requests_only():
    """R + 2 admissions in one turn, the FIRST dispatch made to raise
    with the donated buffers alive: its R requests fail (each counted),
    their pages are freed and their slots stay free; the second
    dispatch's two requests are seated in the same turn, and the next
    turn admits into the slots the failed ones never took."""
    srv = _wide_server(_tiny_model())
    rt = srv.runtime
    r_n = rt.prefill_rows
    rng = np.random.RandomState(42)
    orig, calls = rt._prefill_fn, {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient prefill failure")
        return orig(*args)

    rt._prefill_fn = flaky
    failed = registry().counter("serve_requests", result="failed")
    f0 = failed.value
    hs = [srv.submit(rng.randint(4, 50, (5,))) for _ in range(r_n + 2)]
    res = srv.scheduler.step()
    assert res.admitted == 2 and calls["n"] == 2
    assert [h.state for h in hs[:r_n]] == ["failed"] * r_n
    assert failed.value - f0 == r_n
    assert "transient prefill failure" in hs[0].error
    assert srv.scheduler.active_count() == 2
    assert srv.pool.in_use() == 2            # the survivors' first pages
    assert sorted(h._slot for h in hs[r_n:]) == [r_n, r_n + 1]
    late = srv.submit(rng.randint(4, 50, (6,)))
    assert srv.scheduler.step().admitted == 1 and late._slot == 0
    srv.scheduler.run_until_idle(max_steps=200)
    assert all(len(h.result(timeout=60)) >= 1 for h in hs[r_n:] + [late])
    assert srv.pool.in_use() == 0
    srv.close()


def test_memory_loss_in_a_batch_restarts_every_inflight_request():
    """`MemoryStateLost` from a batched dispatch: the dispatch's own
    requests fail, every request already in a slot restarts from
    scratch, nothing leaks."""
    srv = _wide_server(_tiny_model(), max_new_tokens=4, max_retries=2)
    rt = srv.runtime
    rng = np.random.RandomState(43)
    inflight = [srv.submit(rng.randint(4, 50, (5,))) for _ in range(3)]
    srv.scheduler.step()
    assert [h.state for h in inflight] == ["running"] * 3
    orig, calls = rt._prefill_fn, {"n": 0}

    def lossy(mem_k, *args):
        calls["n"] += 1
        if calls["n"] == 1:
            mem_k.delete()
            raise RuntimeError("prefill consumed donated buffers")
        return orig(mem_k, *args)

    rt._prefill_fn = lossy
    bad = [srv.submit(rng.randint(4, 50, (4,))) for _ in range(2)]
    srv.scheduler.run_until_idle(max_steps=200)
    assert [h.state for h in bad] == ["failed"] * 2
    assert "MemoryStateLost" in bad[0].error
    assert all(h.retries >= 1 and len(h.result()) == 4 for h in inflight)
    assert srv.pool.in_use() == 0
    srv.close()


# ------------------------------------- one turn in flight (ISSUE 35)
def _mixed_requests(n, seed=35, lo=2, hi=12):
    rng = np.random.RandomState(seed)
    return [(rng.randint(4, 50, (int(k),)).astype(np.int32), int(m))
            for k, m in zip(rng.randint(3, 12, n), rng.randint(lo, hi, n))]


def _beam1(model, src, n, eos_id):
    """The plain greedy reference: `beam_search_cached` at one beam, cut
    where the server would stop."""
    from mxnet_tpu.models.transformer import beam_search_cached
    tokens, _ = beam_search_cached(model, mx.nd.array(src.reshape(1, -1)),
                                   beam_size=1, max_length=n + 1)
    want = tokens.asnumpy()[0, 0].tolist()[1:n + 1]
    return want[:want.index(eos_id) + 1] if eos_id in want else want


def _until_in_flight(sched, max_steps=50):
    for _ in range(max_steps):
        sched.step()
        if sched._inflight is not None:
            return
    raise AssertionError("no turn was ever left in flight")


def _drains(why):
    return registry().counter("serve_lookahead_drains", why=why).value


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_a_backlog_looks_ahead_and_keeps_every_token(kv_dtype):
    """3 x slots requests of mixed lengths, queued at once: turns are
    dispatched before the previous turn's read, and every request's
    tokens are the plain greedy reference's and those of the same
    requests sent a wave at a time (no backlog, so no lookahead), through
    ONE decode executable, a direct `decode` call included."""
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(9)
    srv = _server(model, slots=3, eos_id=-1, kv_dtype=kv_dtype)
    sched, rt = srv.scheduler, srv.runtime
    ahead0 = registry().counter("serve_lookahead_turns").value
    hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs]
    sched.run_until_idle()
    backlog = [h.result() for h in hs]
    ahead = sched.lookahead_turns
    assert 0 < ahead < sched.decode_turns
    assert registry().counter("serve_lookahead_turns").value \
        == ahead0 + ahead
    assert srv.pool.in_use() == srv.prefix_cache.pages_held()
    waves = []
    for i in range(0, 9, 3):
        hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs[i:i + 3]]
        sched.run_until_idle()
        waves += [h.result() for h in hs]
    assert sched.lookahead_turns == ahead       # a wave leaves no queue
    assert waves == backlog
    if kv_dtype is None:
        assert backlog == [_beam1(model, s, m, -1) for s, m in reqs]
    assert [len(t) for t in backlog] == [m for _, m in reqs]
    compiles = registry().counter(
        "compiles", executable="serve_decode" + ("_int8" if kv_dtype
                                                 else "")).value
    s_n = rt.slots
    rt.decode(np.zeros((s_n, rt.max_pages_per_slot), np.int32),
              np.zeros((s_n,), np.int32), np.full((s_n,), 2, np.int32),
              np.zeros((s_n,), np.int32))
    assert rt.decode_traces == 1
    assert registry().counter(
        "compiles", executable="serve_decode" + ("_int8" if kv_dtype
                                                 else "")).value == compiles
    srv.close()
    assert srv.pool.in_use() == 0


def test_speculation_never_leaves_a_turn_in_flight():
    model = _tiny_model(max_length=48)
    reqs = _mixed_requests(6)
    srv = _server(model, slots=2, eos_id=-1, speculative_k=2,
                  max_prompt_len=8)
    hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs]
    sched = srv.scheduler
    while sched.pending_work():
        sched.step()
        assert sched._inflight is None
    assert sched.lookahead_turns == 0
    assert [h.result() for h in hs] \
        == [_beam1(model, s, m, -1) for s, m in reqs]
    srv.close()


def test_prompt_tokens_are_forced_from_the_host_under_a_turn_in_flight():
    """A slot that still has known tokens to feed takes them from the
    host, turn in flight or not; only the generation frontier reads the
    previous turn's choice on the device."""
    model = _tiny_model(max_length=48)
    rng = np.random.RandomState(3)
    reqs = [(s, m, rng.randint(4, 50, (int(k),)))
            for (s, m), k in zip(_mixed_requests(6, seed=4), (5, 0, 9, 3, 7,
                                                               1))]
    def run(backlog):
        srv = _server(model, slots=2, eos_id=-1, max_prompt_len=12,
                      prefix_cache=False)
        out = []
        for i in range(0, 6, 6 if backlog else 2):
            hs = [srv.submit(s, max_new_tokens=m, prompt_tokens=p)
                  for s, m, p in reqs[i:i + (6 if backlog else 2)]]
            srv.scheduler.run_until_idle()
            out += [h.result() for h in hs]
        assert (srv.scheduler.lookahead_turns > 0) == backlog
        assert srv.pool.in_use() == 0
        srv.close()
        return out

    assert run(True) == run(False)


@pytest.mark.parametrize("event", ["eos", "deadline", "pool_dry", "defrag",
                                   "shutdown"])
def test_what_is_rare_meets_a_turn_in_flight(event):
    """An `eos_id` hit, a deadline's expiry, a dry pool's preemption,
    `defrag()` and `shutdown()`, each with a turn in flight: the pool
    ends empty and every handle has the right tokens or the right
    error."""
    import time
    from mxnet_tpu.serve import ServeDeadlineExceeded
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(8, seed=8, lo=6)
    plain = [_beam1(model, s, m, -1) for s, m in reqs]
    eos_id = -1
    if event == "eos":
        # a token the first request chooses mid-way ends it (and cuts
        # whoever else chooses it) when the next turn is already out
        eos_id = next(t for t in plain[0] if t != plain[0][0])
    want = [t[:t.index(eos_id) + 1] if eos_id in t else t for t in plain]
    kw = dict(slots=2, eos_id=eos_id, prefix_cache=False)
    if event == "pool_dry":
        kw.update(page_size=2, num_pages=10, max_retries=0)  # 9 usable
    srv = _server(model, **kw)
    sched = srv.scheduler
    why = {"eos": "queue_empty", "deadline": "queue_empty"}.get(event,
                                                               event)
    drains0 = _drains(why)
    hs = [srv.submit(s, max_new_tokens=m,
                     deadline_ms=6e4 if event == "deadline" and i == 1
                     else None)
          for i, (s, m) in enumerate(reqs)]
    _until_in_flight(sched)
    sched.step()
    assert sched._inflight is not None and sched.lookahead_turns == 1
    if event == "deadline":
        assert hs[1].state == "running"
        hs[1].deadline = time.monotonic()       # it passes now
    elif event == "defrag":
        sched.defrag()
        assert sched._inflight is None
    elif event == "shutdown":
        done = [h for h in hs if h.done()]
        srv.close()
        assert sched._inflight is None
        for h, t in zip(hs, want):
            if h in done or h.state == "done":
                assert h.result() == t
            else:
                with pytest.raises(ServeError):
                    h.result(timeout=1)
        assert any(h.state == "failed" for h in hs)
    sched.run_until_idle(max_steps=2000)
    assert _drains(why) > drains0
    assert sched.lookahead_turns > 0
    if event == "deadline":
        with pytest.raises(ServeDeadlineExceeded):
            hs[1].result()
    if event == "pool_dry":
        assert sum(h.preemptions for h in hs) > 0
        assert all(h.retries == 0 for h in hs)
    if event == "eos":
        assert 1 < len(want[0]) < reqs[0][1]
    if event != "shutdown":
        for i, (h, t) in enumerate(zip(hs, want)):
            if not (event == "deadline" and i == 1):
                assert h.result() == t, i
    assert srv.pool.in_use() == 0
    assert srv.runtime.decode_traces == 1
    srv.close()


def test_a_decode_fault_under_a_turn_in_flight_fails_the_running_only():
    """An injected `serve.decode` fault with a turn in flight: the
    requests in the slots (the turn in flight's and the next one's) fail,
    the turn in flight is committed first, and the queued ones run to
    the right tokens."""
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(6, seed=8, lo=8)
    srv = _server(model, slots=2, eos_id=-1, max_retries=0,
                  prefix_cache=False)
    sched = srv.scheduler
    hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs]
    _until_in_flight(sched)
    sched.step()
    running = [r for r in sched._slots if r is not None]
    had = [len(r.tokens) for r in running]
    drains0 = _drains("error")
    finj.inject("serve.decode", times=1)
    sched.step()
    assert finj.fires("serve.decode") == 1
    assert sched._inflight is None and _drains("error") == drains0 + 1
    assert [r.state for r in running] == ["failed", "failed"]
    assert all(h.state == "queued" for h in hs if h not in running)
    sched.run_until_idle()
    for h, (s, m) in zip(hs, reqs):
        if h in running:
            with pytest.raises(ServeError):
                h.result(timeout=1)
        else:
            assert h.result() == _beam1(model, s, m, -1)
    assert srv.pool.in_use() == 0
    srv.close()


def test_a_failed_read_takes_the_turn_dispatched_after_it_along():
    """A decode error surfaces at the READ of the turn in flight: its
    slots and those of the turn dispatched after it are retried, the
    pools are reset, and everything still ends with the right tokens."""
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(6, seed=8, lo=8)
    srv = _server(model, slots=2, eos_id=-1, max_retries=1,
                  prefix_cache=False)
    sched, rt = srv.scheduler, srv.runtime
    hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs]
    _until_in_flight(sched)
    sched.step()
    running = [r for r in sched._slots if r is not None]

    def boom():
        raise RuntimeError("device lost the step")

    sched._inflight.read = boom
    res = sched.step()
    assert sched._inflight is None and res.retried == 2
    assert all(r.state == "queued" and r.retries == 1 for r in running)
    sched.run_until_idle()
    assert [h.result() for h in hs] \
        == [_beam1(model, s, m, -1) for s, m in reqs]
    assert srv.pool.in_use() == 0 and rt.decode_traces == 1
    srv.close()


def test_the_previous_steps_tokens_feed_the_next_on_the_device():
    """`decode_launch` with `active` 2 takes a slot's input token from the
    previous launch's choice where it lies on the device, 1 takes the
    host's, 0 leaves the slot out: the logits are those of feeding the
    same tokens from the host, the launch returns before anything is
    read, and it is still the ONE executable."""
    srv = _server(_tiny_model(seed=19), slots=3, eos_id=-1)
    rt, pool = srv.runtime, srv.pool
    rng = np.random.RandomState(2)
    tables = np.zeros((3, rt.max_pages_per_slot), np.int32)
    for s in range(3):
        pages = pool.alloc(2)
        tables[s, :2] = pages
        rt.prefill(s, rng.randint(4, 50, (6,)))
    lens = np.zeros((3,), np.int32)
    first = rt.decode_launch(tables, lens, np.array([2, 7, 9], np.int32),
                             np.array([1, 1, 1], np.int32))
    # slot 0 from the device, slot 1 from the host, slot 2 sits out: the
    # host's entries for slots 0 and 2 must not matter
    second = rt.decode_launch(tables, lens + 1,
                              np.array([44, 11, 45], np.int32),
                              np.array([2, 1, 0], np.int32))
    chose, _ = first()
    _, fed = second()
    rt.reset_pages()
    rt.decode(tables, lens, np.array([2, 7, 9], np.int32),
              np.array([1, 1, 1], np.int32))
    _, host = rt.decode(tables, lens + 1,
                        np.array([chose[0], 11, 0], np.int32),
                        np.array([1, 1, 0], np.int32))
    assert np.array_equal(np.asarray(fed)[:2], np.asarray(host)[:2])
    _, other = rt.decode(tables, lens + 1,
                         np.array([chose[0] + 1, 11, 0], np.int32),
                         np.array([1, 1, 0], np.int32))
    assert not np.array_equal(np.asarray(other)[0], np.asarray(host)[0])
    assert rt.decode_traces == 1
    srv.close()


def test_a_turn_dispatched_ahead_writes_only_pages_its_slots_own():
    """Page safety of the lookahead: at every dispatch, turn in flight or
    not, each running slot's row goes to a page that slot's request holds
    then, and to no other slot's. A request that ends on `eos_id` while
    the next turn is out frees its pages at commit; their next owner is
    dispatched after that stale row, and reads the right tokens."""
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(8, seed=8, lo=6)
    plain = [_beam1(model, s, m, -1) for s, m in reqs]
    eos_id = next(t for t in plain[0] if t != plain[0][0])
    srv = _server(model, slots=2, eos_id=eos_id, prefix_cache=False)
    sched, rt = srv.scheduler, srv.runtime
    launch, stale = rt.decode_launch, []

    def checked(tables, lens, tok, active):
        writes = {}
        for s, r in enumerate(sched._slots):
            if active[s]:
                page = int(tables[s, lens[s] // rt.page_size])
                assert page != NULL_PAGE and page in r._pages
                writes[s] = page
        assert len(set(writes.values())) == len(writes)
        if sched._inflight is not None:
            stale.append(sum(1 for s, r in sched._inflight.rows.items()
                             if active[s] == 2))
        return launch(tables, lens, tok, active)

    rt.decode_launch = checked
    hs = [srv.submit(s, max_new_tokens=m) for s, m in reqs]
    sched.run_until_idle()
    assert sum(stale) > 0                   # rows were fed on the device
    assert [h.result() for h in hs] == [
        t[:t.index(eos_id) + 1] if eos_id in t else t for t in plain]
    assert any(len(h.tokens) < m for h, (_, m) in zip(hs, reqs))
    assert srv.pool.in_use() == 0
    srv.close()


def test_an_engine_driven_backlog_looks_ahead_under_concurrent_submits():
    """The engine's loop cranks turns with one in flight while four
    threads submit (more requests than slots, and a queue that flickers
    empty as they race the loop): every request gets the plain greedy
    tokens, the pool ends empty, nothing is left in flight."""
    import sys
    import threading
    from mxnet_tpu import engine
    model = _tiny_model(seed=19)
    reqs = _mixed_requests(24, seed=5)
    want = [_beam1(model, s, m, -1) for s, m in reqs]
    srv = _server(model, slots=3, eos_id=-1, engine_driven=True,
                  prefix_cache=False)
    handles = [None] * len(reqs)

    def feed(k):
        for i in range(k, len(reqs), 4):
            handles[i] = srv.submit(*reqs[i][:1], max_new_tokens=reqs[i][1])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=feed, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        got = [h.result(timeout=120) for h in handles]
    finally:
        sys.setswitchinterval(old)
    assert got == want
    assert srv.wait(timeout=60)
    sched = srv.scheduler
    assert sched.lookahead_turns > 0 and sched._inflight is None
    assert srv.pool.in_use() == 0 and srv.runtime.decode_traces == 1
    srv.close()
    assert not any("serve" in f["site"] for f in engine.failures())
