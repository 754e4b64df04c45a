"""Serving decode runtime: ONE cached decode executable + ONE cached
prefill executable over device-resident paged KV state (ISSUE 6).

The decode step is compiled exactly once per server: every shape in the
program is static — `(slots, num_pages, page_size)` for the self-attention
page pools, `(slots, max_src_len)` for the per-slot encoder memory — and
everything that changes between steps (slot occupancy, page tables,
per-slot lengths, current tokens) rides as ARGUMENTS, so ragged batch
composition never retraces (`decode_traces` stays 1; enforced by
tools/check_dispatch.py's serve phase in tier-1). The K/V page pools are
DONATED to the executable, so the per-step page writes are in-place
scatters into the same device buffers — the paged cache never doubles in
HBM.

The prefill executable is compiled once too, for ONE static number of
sources R (`prefill_rows`): the scheduler hands `prefill_many` a turn's
whole list of admissions, and a dispatch takes up to R of them, encoded
one a trip of a device loop into their slots of the donated memory
buffers. The host pays one dispatch a turn (a dispatch a request cost it
1.5 ms each on the v5e, for 0.13 ms of device work) and the device works
for the filled rows only; `prefill(slot, src)` is the batch of one.

The pools are ONE array a layer, kept head-major `(H, P, psize, lanes)`:
the shape `mxtpu_rpa`'s block specs read (a page's block is its
`(H, 1, psize, lanes)`: a slot's heads and eight of its pages make one
grid step), so the kernel takes a pool where it lies
and a decode or verify program holds no operation whose result has a
pool's size (tests/test_tpu_compile.py pins that on the chip's own
compiler). Two things keep it so. A row is `pool_lanes(dh)` wide, the
head's values and zeros up to whole 128-lane tiles: the device then
lays the array out row-major, as the kernel needs it (a minor dimension
under 128 it lays out of the lanes, and the program copies every pool
into the kernel's layout and back, every turn); the price is HBM, twice
the logical bytes for 64-wide heads. And a page write scatters one
head's row at a time (`_rows`), because XLA's scatter wants its window
minor-most and would otherwise copy the pool page-major and back around
each write. A page id indexes axis 1 of every pool and of the int8
scales; `serve.lm_runtime.LMRuntime` keeps its pools on the same
principle, in the flat shape its kernel reads.

Slot conventions (shared with serve.scheduler):

  * inactive slots route their scatter writes to the pool's reserved null
    page 0 and their outputs are garbage the scheduler never reads — no
    branches on occupancy inside the program;
  * `lens[s]` is the number of cached positions BEFORE this step — also
    the position index of the token being decoded (BOS decodes at 0);
  * page tables are padded with the null page, so unused entries gather
    valid memory.

The per-layer math is `models.transformer`'s factored decode core
(`decode_embed` / `decoder_layer_*`), and the self-attention is
`ops.pallas_kernels.ragged_paged_attention` — the Pallas kernel on TPU,
the shared-math lax gather on the CPU mesh — so a paged decode is
bitwise-identical to the dense-cache `decode_step` on equal context
width (tests/test_serve.py pins this).

Int8 KV cache (ISSUE 14, ``kv_dtype="int8"``): the page pools store
int8 with PER-PAGE / PER-HEAD f32 scales in parallel ``(H, P)``
arrays, one a layer, so a fixed HBM page budget holds ~4x the tokens
of fp32 pages (~2x bf16) — directly more concurrent requests per chip
on the bandwidth-bound decode loop (on the TPU an int8 tile holds 32
rows, so pages under 32 tokens reach 2x, not 4x). Writes keep a
RUNNING-MAX scale per page:
a token whose |K| exceeds the page's current range grows the scale and
requantises the page's existing rows in the same fused scatter (exact
no-op when the scale doesn't move — ratio 1.0 round-trips int8
losslessly); a write at page offset 0 RESETS the page (a freed page's
stale scale must not leak into its next owner). Scales are indexed by
page id, so prefix-cache page sharing and `defrag` carry them for free,
and all four pool arrays are donated — the executables stay 1 dispatch
/ 0 retraces (check_dispatch's quantized-serve phase gates this).
Dequantisation happens inside `ragged_paged_attention` (in-kernel on
TPU, gathered-context-only in the lax fallback).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler
from ..base import MXNetError
from ..models.transformer import (decode_embed, decode_project,
                                  decoder_layer_qkv, decoder_layer_self_post,
                                  decoder_layer_cross,
                                  decoder_layer_cross_multi,
                                  decoder_layer_ffn,
                                  encode_memory, precompute_memory_kv)
from ..observability import registry as _obs_registry
from ..observability import tracer as _tracer
from ..observability import compilex as _compilex
from ..ops.pallas_kernels import pool_lanes, ragged_paged_attention
from .kv_pages import NULL_PAGE

__all__ = ["DecodeRuntime", "MemoryStateLost"]


def _rows(pages, page, off=None):
    """The index of every head's rows at `page` (and `off`) of one
    head-major pool, the head a scatter index of its own: the update
    window is then ONE head's (psize, lanes) block or (lanes,) row,
    minor-most where it lies. (`pages.at[:, page, off]` names the same
    elements with a window across the heads, which XLA's TPU scatter
    serves by copying the pool page-major and back.)"""
    heads = jnp.arange(pages.shape[0]).reshape((-1,) + (1,) * page.ndim)
    return (heads, page[None]) if off is None else (heads, page[None],
                                                     off[None])


def _quant_page_write(pages, scales, page, off, vals):
    """Quantised paged K/V write with running-max per-page/per-head
    scales (ISSUE 14). pages: one layer's (H, P, psize, lanes) int8
    pool; scales: its (H, P) f32; page/off: (...,) int32 target page
    ids/offsets (inactive rows routed to the null page by the caller);
    vals: (H, ..., lanes) fp token projections. The dims between are
    (S,) for the 1-wide decode program and (S, W) for the widened verify
    program — duplicate page ids within a window are safe because every
    duplicate computes identical update values (scatter-max for scales,
    identical requantised blocks for content). Returns (pages, scales)."""
    f32 = scales.dtype
    amax = jnp.max(jnp.abs(vals.astype(f32)), axis=-1)       # (H, ...)
    # a write at offset 0 starts the page's life: zero the stale content
    # AND scale a previous owner left behind (scales only ever grow
    # within a life, so without the reset a hot former tenant would
    # permanently coarsen the page's quantisation grid)
    fresh_page = jnp.zeros((pages.shape[1],), bool).at[
        jnp.where(off == 0, page, NULL_PAGE)].set(True)
    at_page = _rows(pages, page)
    sc0 = jnp.where(fresh_page, jnp.float32(0), scales)      # (H, P)
    new_sc = sc0.at[at_page].max(amax / 127.0)
    old_g = scales[at_page]                                  # (H, ...)
    new_g = new_sc[at_page]
    safe = jnp.maximum(new_g, 1e-30)
    ratio = jnp.where(new_g > 0, old_g / safe, jnp.float32(1))
    blk = pages[at_page].astype(f32)              # (H, ..., psize, lanes)
    blk = jnp.round(blk * ratio[..., None, None])
    blk = jnp.where(fresh_page[page][..., None, None], jnp.float32(0), blk)
    tok = jnp.clip(jnp.round(vals.astype(f32) / safe[..., None]),
                   -127, 127)
    pages = pages.at[at_page].set(blk.astype(jnp.int8))
    pages = pages.at[_rows(pages, page, off)].set(tok.astype(jnp.int8))
    return pages, new_sc


def _cached_attention(pools, page, off, qh, kh, vh, page_tables, lens):
    """One layer's share of a turn: the tokens' K/V rows (..., H, dh)
    into its pools (k_pages, v_pages, k_scales, v_scales; the scales None
    without int8 KV) at (page, off), then the shared attention launch
    over those pools where they lie. Returns (attention, the pools)."""
    k_pages, v_pages, k_scales, v_scales = pools
    pad = [(0, 0)] * kh.ndim
    pad[-1] = (0, k_pages.shape[-1] - kh.shape[-1])
    written = []
    for pages, scales, vals in ((k_pages, k_scales, kh),
                                (v_pages, v_scales, vh)):
        vals = jnp.pad(jnp.moveaxis(vals, -2, 0), pad)      # (H, ..., lanes)
        if scales is None:
            pages = pages.at[_rows(pages, page, off)].set(vals)
        else:
            pages, scales = _quant_page_write(pages, scales, page, off, vals)
        written.append((pages, scales))
    (k_pages, k_scales), (v_pages, v_scales) = written
    a = ragged_paged_attention(qh, k_pages, v_pages, page_tables, lens + 1,
                               k_scales=k_scales, v_scales=v_scales)
    return a, (k_pages, v_pages, k_scales, v_scales)


# ------------------------------------------ the named device-time scopes
# The parts of a decode turn (`mx_embed`, `mx_self_attn`, `mx_cross_attn`,
# `mx_ffn`, `mx_head`; the verify program shares them where it shares the
# code) and of a trip of the prefill program's loop (`mx_encoder`,
# `mx_memory_kv`). In a program each runs as `_scope(name, fn,
# weights...)(arrays...)`: a jitted function that XLA inlines and whose
# name its ops carry (`models.decoder_lm` says why not `jax.named_scope`,
# and how a scope is named). The weights are BOUND, not passed: these
# programs close over them, so inside a function they are what they are
# in the program, concrete arrays whose own arithmetic (`w.T`, a cast)
# runs once while the function is traced and leaves a constant; as
# operands they would be traced, and the compiler would lay a weight out
# otherwise (read in the described-chip compile: the QKV weights
# transposed, the 36,548-wide projection read in float32). A layer's
# pools ride through `mx_self_attn`, the memory buffers through
# `mx_memory_kv`, and the writes stay in place in the donated buffers.
def _scope(name, fn, *bound):
    def scoped(*arrays):
        return fn(*bound, *arrays)
    scoped.__name__ = scoped.__qualname__ = name
    return jax.jit(scoped)


def _self_attn(L, h, x, pools, page, off, page_tables, lens):
    """QKV, the page write, the paged attention, the output projection
    with its residual and norm. x: (S, U) or a window's (S, W, U)."""
    q, k, v = (a.reshape(x.shape[:-1] + (h, -1))
               for a in decoder_layer_qkv(L, x))
    a, pools = _cached_attention(pools, page, off, q, k, v, page_tables,
                                 lens)
    return decoder_layer_self_post(L, x, a.reshape(x.shape)), pools


def _head(w, x):
    logits = decode_project(w, x)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


def _memory_kv(w, memory, vl, mem, slot):
    """A source's cross-attention K/V of every layer and their writes,
    with the source's length, into the slot's rows of the memory
    buffers `mem`."""
    mem_k, mem_v, mem_vl = mem
    kv = precompute_memory_kv(w, memory)
    mk = jnp.stack([k for k, _ in kv])      # (n_layers, 1, H, Ssrc, dh)
    mv = jnp.stack([v for _, v in kv])
    at = (0, slot, 0, 0, 0)
    return (lax.dynamic_update_slice(mem_k, mk, at),
            lax.dynamic_update_slice(mem_v, mv, at),
            lax.dynamic_update_slice(mem_vl, vl, (slot,)))


def _raised(call, *args):
    """The exception `call(*args)` raised, or None: what a runtime's
    `prefill_many` yields of each dispatch."""
    try:
        call(*args)
    except Exception as e:
        return e
    return None


class MemoryStateLost(MXNetError):
    """A prefill dispatch failed AFTER consuming its donated encoder-
    memory buffers: every slot's cross-attention state is gone, not just
    the request being admitted. The runtime has already rebuilt zeroed
    buffers; the scheduler must restart ALL in-flight requests (their
    re-admission re-prefills each slot)."""


class DecodeRuntime:
    """Device state + the two cached executables of one serving engine.

    weights / enc_weights: `models.transformer.decoder_weights` /
    `encoder_weights` snapshots. All device state (K/V page pools, per-slot
    encoder memory) lives on this object; the scheduler only ever hands it
    host-side int arrays."""

    def __init__(self, weights, enc_weights, slots, num_pages, page_size,
                 max_pages_per_slot, max_src_len, width=1, kv_dtype=None):
        u = weights["embed"].shape[1]
        h = weights["num_heads"]
        if u % h:
            raise MXNetError("units not divisible by heads")
        self._w = weights
        self._ew = enc_weights
        self.slots = int(slots)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.max_src_len = int(max_src_len)
        self._h = h
        self._dh = u // h
        self._n_layers = len(weights["layers"])
        max_pos = weights["pos"].shape[0]
        if self.max_pages_per_slot * self.page_size > max_pos:
            raise MXNetError(
                f"page budget covers {self.max_pages_per_slot * page_size} "
                f"positions but the decoder pos table has only {max_pos}")
        enc_pos = enc_weights["pos"].shape[0]
        if self.max_src_len > enc_pos:
            raise MXNetError(
                f"max_src_len {self.max_src_len} exceeds the encoder pos "
                f"table ({enc_pos}) — every prefill would fail")
        if kv_dtype not in (None, "float32", "int8"):
            raise MXNetError(f"kv_dtype must be None/'float32'/'int8', "
                             f"got {kv_dtype!r}")
        self.kv_quant = kv_dtype == "int8"
        # compute dtype from the (always-fp) pos table, NOT the embed —
        # an int8-quantised weight snapshot keeps its embed in int8
        self._dtype = weights["pos"].dtype
        self.reset_pages()
        self.reset_mem()
        self.width = int(width)
        if self.width < 1:
            raise MXNetError("decode width must be >= 1")
        # R, the ONE static number of sources a prefill dispatch takes
        # (the rows of its input arrays; its device loop runs over those
        # that are filled): a turn's n admissions ride in ceil(n / R)
        # dispatches (`prefill_many`)
        self.prefill_rows = min(self.slots, 32)
        self._m_rows = _obs_registry().counter("serve_prefill_rows")
        # retrace telemetry: the python bodies run ONLY while jax traces,
        # so these counters are exactly the number of compilations — the
        # check_dispatch serve gate asserts they stay at 1 across every
        # slot-occupancy / page-table variation (and, for the widened
        # verify executable, across every draft-acceptance variation)
        self.decode_traces = 0
        self.prefill_traces = 0
        self.verify_traces = 0
        # compile observatory: prefill vs decode publish as separate
        # executables (`compiles{executable=serve_decode}` == number of
        # decode compilations, the same invariant decode_traces counts —
        # check_fusion budgets the decode HLO, test_serve pins zero warm
        # recompiles against these counters). int8-KV runtimes publish
        # under their own *_int8 names so the quantized-serve budgets
        # (check_fusion) and the fp budgets never shadow each other.
        int8 = "_int8" if self.kv_quant else ""
        self._decode_fn = _compilex.instrument(
            jax.jit(self._decode_program, donate_argnums=(0,)),
            "serve_decode" + int8)
        self._prefill_fn = _compilex.instrument(
            jax.jit(self._prefill_program, donate_argnums=(0, 1, 2)),
            "serve_prefill")
        # every pool and every scale array has its pages on axis 1 (the
        # full-precision runtime's scales are None: no leaves)
        self._remap_fn = _compilex.instrument(
            jax.jit(lambda pools, perm: jax.tree_util.tree_map(
                lambda p: p[:, perm], pools), donate_argnums=(0,)),
            "serve_page_remap")
        # the WIDENED verify executable (ISSUE 12): width > 1 servers run
        # every decode turn through one (slots, width) program — drafted
        # tokens verified by a single batched target pass, chunked prompt
        # prefill teacher-forced width tokens at a time. Static shapes;
        # per-slot ragged window lengths ride as arguments, so varying
        # draft acceptance never retraces (verify_traces stays 1).
        self._verify_fn = None
        if self.width > 1:
            self._verify_fn = _compilex.instrument(
                jax.jit(self._verify_program, donate_argnums=(0,)),
                "serve_verify" + int8)
        # autotune (ISSUE 20): greedy decode is bitwise-contracted — a
        # compile-space candidate that moves ONE logit bit is rejected
        # by the search guard regardless of speed; these executables are
        # unsharded (plan None is the note_plan default, nothing to note)
        from .. import tune as _tune
        for _exe in ("serve_decode", "serve_decode_int8", "serve_prefill",
                     "serve_verify", "serve_verify_int8",
                     "serve_page_remap"):
            _tune.register_contract(_exe, "bitwise")

    # --------------------------------------------- the scheduler's seam
    # what a scheduler asks of any runtime about a request's start:
    # `page_reuse_refusal`, `begin`, `prefill_many`
    # (`serve.lm_runtime.LMRuntime` answers each differently)
    page_reuse_refusal = None   # pages alone share and rewind this state

    def begin(self, src, bos_id):
        """(tokens known before generation, how many of them prefill
        caches): the source goes to the encoder, the decoder starts from
        BOS with nothing cached."""
        return [bos_id], 0

    # ------------------------------------------------------- programs
    # The decode and verify executables are `program(pools, inputs) ->
    # (pools, outputs)` with pools = (k_pages, v_pages, k_scales,
    # v_scales), donated so that page writes are in place, the scales
    # None without int8 KV (`k_scales is None` selects the
    # write/attention form at TRACE time — the fp programs hold nothing
    # of the int8 ones, and a decode-loop fix can never reach one
    # precision and miss the other).
    def _pools(self):
        return self.k_pages, self.v_pages, self.k_scales, self.v_scales

    def _layer_self_attn(self, pools, li, L, x, page, off, page_tables,
                         lens):
        """The `mx_self_attn` scope over layer `li`'s pools, which it
        leaves in their places in the lists of `pools`."""
        x, mine = _scope("mx_self_attn", _self_attn, L, self._h)(
            x, tuple(None if a is None else a[li] for a in pools), page,
            off, page_tables, lens)
        for a, new in zip(pools, mine):
            if a is not None:
                a[li] = new
        return x

    def _decode_program(self, pools, inputs):
        """One token a slot. inputs: (page_tables, lens, tok, active,
        prev_tok, mem_k, mem_v, mem_vl); outputs: (next_tok, logits). A
        slot's input token is the host's `tok` where `active` is 1 and
        the previous step's `next_tok` where it is 2: `prev_tok` is that
        output, still on the device and NOT donated (its own step's
        reader fetches it after this dispatch)."""
        self.decode_traces += 1
        (page_tables, lens, tok, active, prev_tok, mem_k, mem_v,
         mem_vl) = inputs
        tok = jnp.where(active == 2, prev_tok, tok)
        w, h, psize = self._w, self._h, self.page_size
        pools = [None if a is None else list(a) for a in pools]
        x = _scope("mx_embed", decode_embed, w)(tok, lens)   # (S, U)
        rows = jnp.arange(tok.shape[0])
        page = page_tables[rows, lens // psize]
        page = jnp.where(active > 0, page, NULL_PAGE)
        off = lens % psize
        for li, L in enumerate(w["layers"]):
            x = self._layer_self_attn(pools, li, L, x, page, off,
                                      page_tables, lens)
            x = _scope("mx_cross_attn", decoder_layer_cross, L, h)(
                x, mem_k[li], mem_v[li], mem_vl)
            x = _scope("mx_ffn", decoder_layer_ffn, L)(x)
        return tuple(pools), _scope("mx_head", _head, w)(x)

    def _verify_program(self, pools, inputs):
        """The widened decode step. inputs: (page_tables, lens, toks,
        qlens, active, mem_k, mem_v, mem_vl): toks (S, W) window tokens
        per slot at positions lens..lens+W-1, qlens (S,) valid window
        lengths (ragged — rows past qlen scatter to the null page and
        their outputs are garbage the scheduler never commits). outputs:
        (next_tok, logits) for EVERY window position, so one dispatch
        verifies a whole drafted run.
        int8 mode: window writes that share a page combine through the
        quantised write helper's scatter-max scales."""
        self.verify_traces += 1
        (page_tables, lens, toks, qlens, active, mem_k, mem_v,
         mem_vl) = inputs
        w, h, psize = self._w, self._h, self.page_size
        pools = [None if a is None else list(a) for a in pools]
        s_n, width = toks.shape
        npages = page_tables.shape[1]
        rows = jnp.arange(s_n)
        pos = lens[:, None] + jnp.arange(width, dtype=lens.dtype)[None, :]
        x = _scope("mx_embed", decode_embed, w)(toks, pos)   # (S, W, U)
        slot_page = jnp.minimum(pos // psize, npages - 1)
        page = page_tables[rows[:, None], slot_page]     # (S, W)
        valid = (jnp.arange(width)[None, :] < qlens[:, None]) \
            & (active[:, None] > 0)
        page = jnp.where(valid, page, NULL_PAGE)
        off = pos % psize
        for li, L in enumerate(w["layers"]):
            # query i sees positions 0..lens+i (its own included): the
            # ragged-query-length form of the shared paged attention
            x = self._layer_self_attn(pools, li, L, x, page, off,
                                      page_tables, lens)
            x = decoder_layer_cross_multi(L, h, x, mem_k[li], mem_v[li],
                                          mem_vl)
            x = _scope("mx_ffn", decoder_layer_ffn, L)(x)
        return tuple(pools), _scope("mx_head", _head, w)(x)  # (S, W[, V])

    def _prefill_program(self, mem_k, mem_v, mem_vl, rows):
        """rows (R, max_src_len + 2) int32, a request a row: its source,
        padded, then the source's length and the request's slot; the
        rows are filled from the first and an unused row's length is 0.
        The filled rows are encoded one a trip of a device loop, each
        written to its slot of the donated memory buffers: one dispatch
        does the device work of n one-request prefills and no more. A
        trip's `dynamic_update_slice` is in place in the loop's carried
        buffers (a scatter along the slot axis is not window-minor-most,
        and XLA's TPU scatter would copy both 400 MB buffers around it).
        ONE integer argument, because each costs the host a transfer of
        its own (0.15 ms on the v5e, of a 0.3 ms dispatch)."""
        self.prefill_traces += 1
        s_n = self.max_src_len
        src, src_len, slots = rows[:, :s_n], rows[:, s_n], rows[:, s_n + 1]

        def encode_row(i, mem):
            row = lax.dynamic_slice_in_dim(src, i, 1)        # (1, Ssrc)
            vl = lax.dynamic_slice_in_dim(src_len, i, 1).astype(jnp.int32)
            memory = _scope("mx_encoder", encode_memory, self._ew)(
                row, vl)                                     # (1, Ssrc, U)
            return _scope("mx_memory_kv", _memory_kv, self._w)(
                memory, vl, mem, slots[i])

        return lax.fori_loop(0, jnp.sum(src_len > 0), encode_row,
                             (mem_k, mem_v, mem_vl))

    # ---------------------------------------------------------- calls
    def prefill(self, slot, src_tokens, src_len=None):
        """Encode one request's source into decode slot `slot`: the
        batch of one of `prefill_many`'s dispatch (the same executable,
        one valid row)."""
        self._prefill_rows([(slot, src_tokens, src_len)])

    def prefill_many(self, entries):
        """Encode a turn's admissions, `entries` = [(slot, source, pages)]
        (the pages are the scheduler's: an encoder writes none), in
        dispatches of up to `prefill_rows` requests. A generator: after
        each dispatch it yields (how many entries it held, the exception
        it raised or None), so the scheduler stamps or fails exactly
        that dispatch's requests before the next one goes out."""
        for i in range(0, len(entries), self.prefill_rows):
            group = entries[i:i + self.prefill_rows]
            yield len(group), _raised(
                self._prefill_rows,
                [(slot, src, None) for slot, src, _pages in group])

    def _prefill_rows(self, rows):
        """ONE dispatch of the cached prefill executable (encoder +
        cross-attention K/V projection + slot writes) against the donated
        memory buffers: `rows` = [(slot, source, valid length or None)],
        at most `prefill_rows` of them, packed into the first rows of the
        program's one static integer argument."""
        n, s_n = len(rows), self.max_src_len
        if not 1 <= n <= self.prefill_rows:
            raise MXNetError(f"a prefill dispatch takes 1.."
                             f"{self.prefill_rows} requests, got {n}")
        packed = np.zeros((self.prefill_rows, s_n + 2), np.int32)
        for i, (slot, src_tokens, src_len) in enumerate(rows):
            src = np.asarray(src_tokens, np.int32).reshape(-1)
            if not 1 <= src.size <= s_n:
                raise MXNetError(f"source length {src.size}: this server "
                                 f"takes 1..{s_n} (max_src_len)")
            packed[i, :src.size] = src
            packed[i, s_n] = src.size if src_len is None else src_len
            packed[i, s_n + 1] = slot
        profiler.record_dispatch("serve_prefill")
        self._m_rows.inc(n)
        old = (self.mem_k, self.mem_v, self.mem_vl)

        def launch():
            self.mem_k, self.mem_v, self.mem_vl = self._prefill_fn(
                self.mem_k, self.mem_v, self.mem_vl, packed)

        try:
            if _tracer.ACTIVE:
                with _tracer.span("serve.prefill", cat="serve",
                                  args={"rows": n, "src_len": int(
                                      packed[:n, s_n].max())}):
                    launch()
            else:
                launch()
        except Exception as e:
            # donation hazard (same rule as cachedop): a failure that
            # consumed the donated memory buffers loses EVERY slot's
            # encoder state, not just this dispatch's — rebuild zeroed
            # buffers and tell the scheduler to restart the in-flight
            # requests. A failure that left the buffers alive (trace/
            # compile-stage, CPU no-op donation) stays with the
            # dispatch's own requests.
            if any(getattr(a, "is_deleted", lambda: False)()
                   for a in old):
                self.reset_mem()
                raise MemoryStateLost(
                    f"prefill failed after consuming donated memory "
                    f"buffers: {type(e).__name__}: {e}") from e
            raise

    def decode_launch(self, page_tables, lens, tok, active):
        """Dispatch one decode step for every slot (ONE dispatch) and
        return its `read`: the call that waits for the step and gives
        what `decode` returns. Each active slot's K/V goes into its
        current page in place, then the shared ragged-paged-attention
        launch runs. `active[s]` is 0 (empty), 1 (input token `tok[s]`)
        or 2 (the token the PREVIOUS launch chose for the slot, which
        never left the device): a scheduler that launches a step before
        it has read the one before feeds the tokens back that way."""
        profiler.record_dispatch("serve_decode")
        inputs = (jnp.asarray(page_tables, jnp.int32),
                  jnp.asarray(lens, jnp.int32), jnp.asarray(tok, jnp.int32),
                  jnp.asarray(active, jnp.int32), self._last_tok,
                  self.mem_k, self.mem_v, self.mem_vl)
        next_tok, logits = self._run(self._decode_fn, inputs)
        self._last_tok = next_tok
        return lambda: (np.asarray(next_tok), logits)

    def decode(self, page_tables, lens, tok, active):
        """One decode step for every slot, launched and read: returns
        (next_tok (S,) host int32, logits (S, V) device array)."""
        return self.decode_launch(page_tables, lens, tok, active)()

    def _run(self, fn, inputs):
        (self.k_pages, self.v_pages, self.k_scales,
         self.v_scales), outputs = fn(self._pools(), inputs)
        return outputs

    def decode_multi(self, page_tables, lens, toks, qlens, active):
        """One WIDENED decode turn for every slot (still ONE dispatch):
        writes each active slot's window K/V into its pages in place,
        runs the shared ragged-paged-attention launch with per-slot
        ragged query lengths, returns (next_tok (S, W) host int32,
        logits (S, W, V) device array). Greedy commits derived from
        these outputs are identical to `decode` run token-by-token —
        the bitwise-greedy contract tests/test_serve.py pins."""
        if self._verify_fn is None:
            raise MXNetError("decode_multi needs width > 1 (construct "
                             "DecodeRuntime(width=k+1))")
        profiler.record_dispatch("serve_decode")
        inputs = (jnp.asarray(page_tables, jnp.int32),
                  jnp.asarray(lens, jnp.int32),
                  jnp.asarray(toks, jnp.int32),
                  jnp.asarray(qlens, jnp.int32),
                  jnp.asarray(active, jnp.int32),
                  self.mem_k, self.mem_v, self.mem_vl)
        next_tok, logits = self._run(self._verify_fn, inputs)
        return np.asarray(next_tok), logits

    def remap_pages(self, mapping):
        """Apply a `PagePool.defrag()` renumbering to the device pools
        (and, int8 mode, the parallel scale arrays — scales travel with
        their page ids): one gather-permutation dispatch (donated,
        in-place)."""
        if not mapping:
            return
        perm = np.arange(self.num_pages)
        for old, new in mapping.items():
            perm[new] = old
        profiler.record_dispatch("serve_page_remap")
        (self.k_pages, self.v_pages, self.k_scales,
         self.v_scales) = self._remap_fn(self._pools(), jnp.asarray(perm))

    def reset_pages(self):
        """Drop ALL cached KV state, scales included (construction, and
        the scheduler's catastrophic failure path after an executable
        error, when page contents can no longer be trusted)."""
        def per_layer(shape, dtype):
            return [jnp.zeros(shape, dtype) for _ in range(self._n_layers)]

        pool = (self._h, self.num_pages, self.page_size,
                pool_lanes(self._dh))
        dtype = jnp.int8 if self.kv_quant else self._dtype
        self.k_pages, self.v_pages = (per_layer(pool, dtype)
                                      for _ in range(2))
        self.k_scales = self.v_scales = None
        # the last decode step's tokens, the next one's `prev_tok`
        self._last_tok = jnp.zeros((self.slots,), jnp.int32)
        if self.kv_quant:
            self.k_scales, self.v_scales = (
                per_layer(pool[:2], jnp.float32) for _ in range(2))
            _obs_registry().gauge("kv_page_scale_bytes").set(
                2 * self._n_layers * self._h * self.num_pages * 4)

    def kv_bytes_per_page(self):
        """Bytes of the values one page holds (K + V across layers; int8
        mode includes the per-page scale rows): what a page costs in a
        pool of `head_dim`-wide rows. The device pools keep a row at
        whole 128-lane tiles (`pool_lanes`), so heads under 128 wide
        cost HBM in proportion."""
        from .quant import kv_page_bytes
        return kv_page_bytes(
            self._n_layers, self.page_size, self._h, self._dh,
            "int8" if self.kv_quant else str(self._dtype))

    def reset_mem(self):
        """Rebuild zeroed per-slot encoder memory (after a prefill
        failure consumed the donated buffers)."""
        shape = (self._n_layers, self.slots, self._h, self.max_src_len,
                 self._dh)
        self.mem_k = jnp.zeros(shape, self._dtype)
        self.mem_v = jnp.zeros(shape, self._dtype)
        self.mem_vl = jnp.zeros((self.slots,), jnp.int32)
