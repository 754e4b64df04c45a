"""Serving runtime for decoder-only language models (`models.decoder_lm`):
ONE cached prefill executable and ONE cached decode executable behind the
same `Server`, `Scheduler`, `PagePool` and `EngineLoop` that serve
`TransformerNMT` through `serve.decode.DecodeRuntime`.

Five kinds of device state live side by side, each only where the
model's pattern has its layers, all donated to both executables so every
write is in place:

  * paged KV for the softmax-attention ("gqa") layers: pools of `(P,
    psize, Hkv * dh)` a layer (the shape the decode kernel reads in
    place), reached through the scheduler's page tables;
  * a RING a slot for the sliding-window ("swa") layers, which no page
    table reaches and the page pool does not count: K and V pools of
    `(slots * R, psize, Hkv * dh)` a layer, R = window / psize + 1
    pages, slot s owning pages s * R .. s * R + R - 1 for its life and
    position p kept at ring page (p // psize) % R, row p % psize. What a
    row holds from a lap before is further back than the window, and a
    query masks every row by the position it holds
    (`ops.pallas_kernels.ring_paged_attention`): the cache of a window
    layer is bounded by the window, whatever the context;
  * latent pages for the latent-attention ("mla") layers, through the
    SAME page tables: ONE pool `(P, psize, lanes)` a layer, a row a token
    holding `c_kv | k_rope` (kv_rank + rope_dim values, zeros up to whole
    128-lane tiles on the chip), which the decode kernel reads once as
    keys and values;
  * fixed per-slot arrays that no page table reaches, for the KDA layers:
    the recurrent state `(slots, H, dv, dk)` float32 (value-major, as the
    decode kernel walks it) and the short
    convolution's last `K - 1` inputs `(slots, K - 1, 3 H dk)`;
  * the same two for the Mamba-2 state-space ("mamba") layers, a second
    recurrent form beside KDA's: the state `(slots, H, P, N)` float32 and
    the convolution's last inputs `(slots, K - 1, H P + 2 G N)`.

A layer is a (mixer, feed-forward) pair or, where the spec is not
`paired`, one of the two: both programs walk `LMSpec.sublayers()`.

A request's first argument is its prompt. Prefill runs the whole prompt
but its last token in one dispatch (padded to the static prompt length):
it writes the prompt's K/V or latent rows into the pages the scheduler
granted and OVERWRITES the slot's recurrent state, so a slot needs no
clearing when a request leaves it, and a requeued request is simply
prefilled again. The last prompt token is the first decode turn's input, so every generated
token, the first included, comes out of the decode executable.

Recurrent state cannot be shared by page or rewound by dropping pages,
and a slot's ring is no page of the pool and has overwritten its oldest
rows by the time a draft is rejected: `page_reuse_refusal` says which
holds and the scheduler refuses the radix prefix cache and speculative
decoding for this runtime until state snapshots exist (ROADMAP Queue 2
B); without recurrent or window layers it still refuses both, because
prefill cannot yet start after adopted pages.

The weights are an ARGUMENT of both executables, not constants: at
several GB they cannot be baked into a program.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .. import profiler
from ..base import MXNetError
from ..models import decoder_lm as lm
from ..ops.nn_ops import rms_norm
from ..ops.pallas_kernels import pool_lanes
from ..observability import registry as _obs_registry
from ..observability import tracer as _tracer
from ..observability import compilex as _compilex
from .decode import MemoryStateLost, _raised
from .kv_pages import NULL_PAGE

__all__ = ["LMRuntime"]

_CHUNK = 32        # positions a step of the chunked KDA scan
_MIXERS = ("gqa", "swa", "kda", "mla", "mamba")
# a recurrent mixer's per-slot state and convolution tails in `_state`,
# and its sequence form
_RECURRENT = {"kda": ("kda", "conv", lm.mx_kda_seq),
              "mamba": ("ssm", "ssm_conv", lm.mx_mamba_seq)}


# ---------------------------------- the programs' own device-time scopes
# Named jitted functions that XLA inlines, as `models.decoder_lm`'s
# (which says why, and how a scope is named): what both programs do
# around the mixers and the expert layers. A pool or a slot array rides
# through `mx_cache_write` as `k_pages` rides through `lm.mx_gqa`: the
# write stays in place in the donated buffer.
@jax.jit
def mx_embed(table, tok):
    """The embedding gather."""
    return table[tok]


@partial(jax.jit, static_argnames=("eps",))
def mx_norm(x, gamma, eps):
    """A sub-layer's RMSNorm on the way in."""
    return rms_norm(x, gamma, eps)


@partial(jax.jit, static_argnames=("eps",))
def mx_join(x, y, gamma, eps):
    """The residual after a sub-layer: its output normed first where the
    block is a sandwich (`gamma` not None)."""
    if gamma is not None:
        y = rms_norm(y, gamma, eps)
    return x + y


@partial(jax.jit, static_argnames=("eps",))
def mx_head(x, gamma, head, eps):
    """Final norm, the vocabulary projection, the argmax: (the token
    chosen (S,) int32, logits (S, V) float32)."""
    logits = jax.lax.dot_general(
        rms_norm(x, gamma, eps), head, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return jnp.argmax(logits, -1).astype(jnp.int32), logits


@partial(jax.jit, static_argnames=("psize",))
def mx_cache_write(pool, at, values, psize=None):
    """A prefill's write into a donated array. With `psize`: `values`
    (T, ...) are the rows of T / psize whole pages, padded with zeros to
    the pool's lanes, `at` their page ids. Without: `values` is one
    slot's row, `at` the slot."""
    if psize is not None:
        values = values.reshape(values.shape[0], -1)
        lanes = pool.shape[-1] - values.shape[-1]
        if lanes:
            values = jnp.pad(values, ((0, 0), (0, lanes)))
        values = values.reshape(-1, psize, pool.shape[-1])
    return pool.at[at].set(values)


class LMRuntime:
    """Device state + the two cached executables of one decoder-only
    serving engine. The scheduler hands it host-side int arrays only."""

    kv_quant = False

    def __init__(self, model, slots, num_pages, page_size,
                 max_pages_per_slot, max_prompt_len, width=1):
        self.width = int(width)     # the scheduler refuses any but 1
        self.spec = spec = model.spec
        self._w = lm.lm_weights(model)
        self._dtype = self._w["embed"].dtype
        self.slots = int(slots)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.max_src_len = int(max_prompt_len)      # the scheduler's name
        if self.max_src_len < 1:
            raise MXNetError("a decoder-only server needs max_prompt_len "
                             ">= 1: the prompt is the request")
        # layers of each kind: each kind keeps its own device state
        self._n = {k: spec.pattern.count(k) for k in _MIXERS}
        # the static prefill length: whole chunks of the recurrent layers'
        # scans and whole pages
        step = math.lcm(_CHUNK, self.page_size,
                        *([spec.ssm_chunk] if self._n["mamba"] else []))
        self._plen = -(-self.max_src_len // step) * step
        subs = spec.sublayers()
        # which layers have experts (another layer counts no dispatch)
        self._is_moe = np.array([f == "moe" for _, f in subs], np.int64)
        # prefill gives no logits: it stops after the last mixer, and
        # whatever follows (the last pair's feed-forward, trailing layers
        # of one feed-forward each) feeds nothing
        self._last_mixer = max(i for i, (m, _) in enumerate(subs) if m)
        self._in_prefill = self._is_moe * (np.arange(len(subs))
                                           < self._last_mixer)
        # pages a slot's ring has in each sliding-window layer
        self.ring = lm.ring_pages_for(spec.window, self.page_size) \
            if self._n["swa"] else 0
        self.page_reuse_refusal = (
            "the model has recurrent (KDA or state-space) layers: a "
            "slot's state after a prefix is one array a layer that pages "
            "neither share nor rewind, so prefix-cache adoption and "
            "rejected speculative drafts would leave it wrong; both wait "
            "for state snapshots"
            if self._n["kda"] or self._n["mamba"] else
            "the model has sliding-window layers: their keys and values "
            "lie in a ring a slot that the page pool does not hold, so "
            "adopted pages would leave the ring empty, and a rejected "
            "draft has already overwritten the ring's oldest rows"
            if self._n["swa"] else
            "the decoder-only runtime prefills a whole prompt in one "
            "dispatch and decodes one token a turn: it cannot start after "
            "adopted pages and has no widened verify executable yet")
        self.decode_traces = 0
        self.prefill_traces = 0
        self._m_rows = _obs_registry().counter("serve_prefill_rows")
        self.reset_pages()
        self._decode_fn = _compilex.instrument(
            jax.jit(self._decode_program, donate_argnums=(0,)),
            "serve_lm_decode")
        self._prefill_fn = _compilex.instrument(
            jax.jit(self._prefill_program, donate_argnums=(0,)),
            "serve_lm_prefill")
        self._remap_fn = _compilex.instrument(
            jax.jit(lambda pools, perm: [p[perm] for p in pools],
                    donate_argnums=(0,)), "serve_page_remap")
        from .. import tune as _tune
        for exe in ("serve_lm_decode", "serve_lm_prefill"):
            _tune.register_contract(exe, "bitwise")

    # --------------------------------------------- the scheduler's seam
    def begin(self, src, bos_id):
        """(tokens known before generation, how many of them prefill
        caches): the prompt itself, all but its last token prefilled."""
        toks = [int(t) for t in src]
        return toks, len(toks) - 1

    # ---------------------------------------------------------- state
    def reset_pages(self):
        """Zeroed device state of every kind (construction, and after a
        failed dispatch consumed the donated buffers)."""
        s, sp = self.slots, self.spec
        ssm_conv = sp.ssm_dims()[1]
        pool = (self.num_pages, self.page_size, sp.kv_heads * sp.head_dim)
        ring = (s * self.ring, self.page_size, sp.kv_heads * sp.head_dim)
        latent = (self.num_pages, self.page_size,
                  pool_lanes(sp.kv_rank + sp.rope_dim))
        c = 3 * sp.kda_heads * sp.kda_head_dim
        self._state = {
            "k": [jnp.zeros(pool, self._dtype) for _ in range(self._n["gqa"])],
            "v": [jnp.zeros(pool, self._dtype) for _ in range(self._n["gqa"])],
            "ring_k": [jnp.zeros(ring, self._dtype)
                       for _ in range(self._n["swa"])],
            "ring_v": [jnp.zeros(ring, self._dtype)
                       for _ in range(self._n["swa"])],
            "lat": [jnp.zeros(latent, self._dtype)
                    for _ in range(self._n["mla"])],
            "kda": [jnp.zeros((s, sp.kda_heads, sp.kda_head_dim,
                               sp.kda_head_dim), jnp.float32)
                    for _ in range(self._n["kda"])],
            "conv": [jnp.zeros((s, sp.conv_kernel - 1, c), self._dtype)
                     for _ in range(self._n["kda"])],
            "ssm": [jnp.zeros((s, sp.ssm_heads, sp.ssm_head_dim,
                               sp.ssm_state), jnp.float32)
                    for _ in range(self._n["mamba"])],
            "ssm_conv": [jnp.zeros((s, sp.conv_kernel - 1, ssm_conv),
                                   self._dtype)
                         for _ in range(self._n["mamba"])],
        }
        # always-on counters of the expert layers, by layer, kept on the
        # host so that a reader never touches a donated buffer: a decode
        # turn's counts come back with its tokens, a prefill's wait in
        # `_pending` until the next turn's read has passed them
        layers = len(sp.pattern)
        self._moe = {"rows": np.zeros((layers, sp.held_n), np.int64),
                     "touched": np.zeros((layers,), np.int64),
                     "dispatches": np.zeros((layers,), np.int64)}
        self._pending = []
        # always-on counts of the sliding-window layers' decode turns
        self._win = {"turns": 0, "ring_tokens": 0}
        # the last decode step's tokens, the next one's `prev_tok`
        self._last_tok = jnp.zeros((s,), jnp.int32)
        # the expert ids every row chose in the last prefill (layers,
        # prompt rows, k) and the last decode turn (layers, slots, k):
        # device arrays that no turn fetches, for whoever audits the
        # routing against a reference (rows past a prompt's end and empty
        # slots hold ids that nothing used; -1 where a layer has no
        # experts or prefill does not run them)
        self.routing = {"prefill": None, "decode": None}
        _obs_registry().gauge("serve_slot_state_bytes").set(
            self.slot_state_bytes())
        _obs_registry().gauge("serve_latent_cache_bytes").set(
            self.latent_cache_bytes())
        _obs_registry().gauge("serve_ring_cache_bytes").set(
            self.ring_cache_bytes())

    def slot_state_bytes(self):
        """Device bytes of the per-slot arrays (both recurrent forms'
        state and convolution tails) that the page pool does not count."""
        return sum(a.size * a.dtype.itemsize
                   for k in ("kda", "conv", "ssm", "ssm_conv")
                   for a in self._state[k])

    def latent_cache_bytes(self):
        """Device bytes of the latent pools as they are kept (the rows'
        padding to whole lane tiles included)."""
        return sum(a.size * a.dtype.itemsize for a in self._state["lat"])

    def ring_cache_bytes(self):
        """Device bytes of the sliding-window layers' rings, every slot's
        (the page pool's budget does not count them)."""
        return sum(a.size * a.dtype.itemsize
                   for k in ("ring_k", "ring_v") for a in self._state[k])

    def kv_bytes_per_page(self):
        """What one page of the POOL holds over all layers: K and V of
        the "gqa" layers, kv_rank + rope_dim values a token of the "mla"
        layers (values, not the tiles they are kept in). A "swa" layer's
        ring is no page of the pool: `ring_cache_bytes` counts it."""
        sp = self.spec
        per_token = (2 * self._n["gqa"] * sp.kv_heads * sp.head_dim
                     + self._n["mla"] * (sp.kv_rank + sp.rope_dim))
        return (per_token * self.page_size
                * jnp.dtype(self._dtype).itemsize)

    def moe_counters(self):
        """The expert layers' always-on counts since the state was made,
        decode turns and prefills together. `rows` (layers, held
        experts): the rows each held expert computed; `touched`
        (layers,): how many (dispatch, held expert) pairs had a row at
        all, so how many experts' weights the dispatches had to read;
        `dispatches` (layers,): the decode turns and prefills that ran
        the layer's experts. A prefill runs the experts of the layers
        before the last mixer only: what follows it would feed nothing
        (prefill gives no logits); it is counted at the decode turn that
        follows it."""
        return {k: v.copy() for k, v in self._moe.items()}

    def window_counters(self):
        """The sliding-window layers' always-on counts since the state
        was made: `turns`, the decode turns launched, and `ring_tokens`,
        the sum over those turns and their running slots of
        min(len + 1, window): the keys (and values) ONE window layer's
        decode attention had to read, the current position among them.
        Counted from the `lens` a launch holds on the host."""
        return dict(self._win)

    def _count(self, counts, prefill=False):
        self._moe["rows"] += counts
        self._moe["touched"] += (counts > 0).sum(1)
        self._moe["dispatches"] += self._in_prefill if prefill \
            else self._is_moe

    @property
    def kda_state(self):
        """The recurrent state arrays, one a KDA layer (read-only use)."""
        return list(self._state["kda"])

    @property
    def ssm_state(self):
        """The state-space layers' state arrays, one a "mamba" layer
        (read-only use)."""
        return list(self._state["ssm"])

    @property
    def kv_pages(self):
        """The paged K and V pools, one (K, V) pair a "gqa" layer
        (read-only use)."""
        return list(zip(self._state["k"], self._state["v"]))

    @property
    def ring_pages(self):
        """The rings, one (K, V) pair of `(slots * ring, psize, Hkv * dh)`
        pools a "swa" layer (read-only use)."""
        return list(zip(self._state["ring_k"], self._state["ring_v"]))

    @property
    def latent_pages(self):
        """The latent pools, one an "mla" layer (read-only use)."""
        return list(self._state["lat"])

    @property
    def conv_tails(self):
        """The convolution's last K - 1 inputs of every slot, one array a
        recurrent layer: the KDA layers', then the state-space layers'
        (read-only use)."""
        return self._state["conv"] + self._state["ssm_conv"]

    def remap_pages(self, mapping):
        if not mapping:
            return
        perm = np.arange(self.num_pages)
        for old, new in mapping.items():
            perm[new] = old
        profiler.record_dispatch("serve_page_remap")
        st = self._state
        n = self._n["gqa"]
        pools = self._remap_fn(st["k"] + st["v"] + st["lat"],
                               jnp.asarray(perm))
        st["k"], st["v"], st["lat"] = pools[:n], pools[n:2 * n], pools[2 * n:]

    # ------------------------------------------------------- programs
    def _layers(self, weights):
        """(mixer's kind or None, layer weights, index among the layers
        of that kind)."""
        seen = dict.fromkeys(self._n, 0)
        for (kind, _), L in zip(self.spec.sublayers(), weights["layers"]):
            yield kind, L, seen.get(kind)
            if kind:
                seen[kind] += 1

    def _join(self, x, y, L, which):
        """The residual after a sub-layer: its output normed first where
        the block is a sandwich."""
        return mx_join(x, y, L.get(which + "_post_gamma"),
                       eps=self.spec.eps)

    def _no_experts(self, rows):
        """What a layer without experts counts: no rows, ids of -1."""
        return (jnp.zeros((self.spec.held_n,), jnp.int32),
                jnp.full((rows, self.spec.top_k), -1, jnp.int32))

    def _ffn(self, x, L, valid):
        """The residual after a layer's feed-forward, where it has one:
        (x, rows each held expert took, expert ids chosen); only an
        expert layer has the last two."""
        if "moe" not in L and "ffn" not in L:
            return (x, *self._no_experts(x.shape[0]))
        h = mx_norm(x, L["norm2_gamma"], eps=self.spec.eps)
        if "moe" in L:
            y, n, idx = lm.mx_moe(L["moe"], h, valid, spec=self.spec)
        else:
            y = lm.mx_ffn(L["ffn"], h)
            n, idx = self._no_experts(x.shape[0])
        return self._join(x, y, L, "norm2"), n, idx

    def _decode_program(self, state, weights, page_tables, lens, tok,
                        active, prev_tok):
        """`active`, `prev_tok`: as `DecodeRuntime._decode_program`'s (a
        slot's input token is `tok` at 1, the previous step's choice,
        which stayed on the device and is not donated, at 2)."""
        self.decode_traces += 1
        spec, psize = self.spec, self.page_size
        valid = active > 0
        tok = jnp.where(active == 2, prev_tok, tok)
        x = mx_embed(weights["embed"], tok)                  # (S, d)
        page = page_tables[jnp.arange(tok.shape[0]), lens // psize]
        page = jnp.where(valid, page, NULL_PAGE)
        off = lens % psize
        counts, chose = [], []
        for kind, L, j in self._layers(weights):
            if kind:
                h = mx_norm(x, L["norm1_gamma"], eps=spec.eps)
            if kind == "gqa":
                y, state["k"][j], state["v"][j] = lm.mx_gqa(
                    L["mixer"], h, state["k"][j], state["v"][j],
                    page_tables, lens, page, off, spec=spec)
            elif kind == "swa":
                y, state["ring_k"][j], state["ring_v"][j] = lm.mx_swa(
                    L["mixer"], h, state["ring_k"][j], state["ring_v"][j],
                    lens, valid, spec=spec)
            elif kind == "mla":
                y, state["lat"][j] = lm.mx_mla(
                    L["mixer"], h, state["lat"][j], page_tables, lens,
                    page, off, spec=spec)
            elif kind == "kda":
                y, state["kda"][j], state["conv"][j] = lm.mx_kda(
                    L["mixer"], h, state["kda"][j], state["conv"][j],
                    spec=spec)
            elif kind == "mamba":
                y, state["ssm"][j], state["ssm_conv"][j] = lm.mx_mamba(
                    L["mixer"], h, state["ssm"][j], state["ssm_conv"][j],
                    spec=spec)
            if kind:
                x = self._join(x, y, L, "norm1")
            x, n, idx = self._ffn(x, L, valid)
            counts.append(n)
            chose.append(idx)
        next_tok, logits = mx_head(x, weights["final_norm_gamma"],
                                   weights["head"], eps=spec.eps)
        return (state, next_tok, logits, jnp.stack(counts),
                jnp.stack(chose))

    def _prefill_program(self, state, weights, tokens, n, slot, page_row):
        """tokens: (plen,) the prompt without its last token, padded; n:
        () how many are real; page_row: the slot's page-table row."""
        self.prefill_traces += 1
        spec, psize = self.spec, self.page_size
        pos = jnp.arange(self._plen)
        valid = pos < n
        x = mx_embed(weights["embed"], tokens)
        n_pg = self._plen // psize
        row = jnp.pad(page_row, (0, max(0, n_pg - page_row.shape[0])),
                      constant_values=NULL_PAGE)[:n_pg]
        # pages past the prompt's end take the writes of the padding
        pages = jnp.where(jnp.arange(n_pg) * psize < n, row, NULL_PAGE)
        tail_at = n - (spec.conv_kernel - 1) + jnp.arange(
            spec.conv_kernel - 1)
        counts, chose = [], []
        for i, (kind, L, j) in enumerate(self._layers(weights)):
            if kind:
                h = mx_norm(x, L["norm1_gamma"], eps=spec.eps)
            if kind == "gqa":
                y, k, v = lm.mx_gqa_seq(L["mixer"], h, spec=spec)
                for name, a in (("k", k), ("v", v)):
                    state[name][j] = mx_cache_write(state[name][j], pages,
                                                    a, psize=psize)
            elif kind == "swa":
                y, state["ring_k"][j], state["ring_v"][j] = lm.mx_swa_seq(
                    L["mixer"], h, state["ring_k"][j], state["ring_v"][j],
                    slot, n, spec=spec)
            elif kind == "mla":
                y, rows = lm.mx_mla_seq(L["mixer"], h, pos, spec=spec)
                state["lat"][j] = mx_cache_write(state["lat"][j], pages,
                                                 rows, psize=psize)
            elif kind in _RECURRENT:
                which, conv, sequence = _RECURRENT[kind]
                y, s_end, pre = sequence(L["mixer"], h, valid, spec=spec)
                if kind == "kda":       # a slot's state is value-major
                    s_end = s_end.swapaxes(-1, -2)
                state[which][j] = mx_cache_write(state[which][j], slot,
                                                 s_end)
                tail = jnp.where((tail_at >= 0)[:, None],
                                 pre[jnp.maximum(tail_at, 0)], 0)
                state[conv][j] = mx_cache_write(state[conv][j], slot, tail)
            if i == self._last_mixer:
                # what follows the last mixer would feed nothing: prefill
                # gives no logits, the next position needs only the state
                for _ in range(i, len(spec.pattern)):
                    n, idx = self._no_experts(self._plen)
                    counts.append(n)
                    chose.append(idx)
                break
            if kind:
                x = self._join(x, y, L, "norm1")
            x, c, idx = self._ffn(x, L, valid)
            counts.append(c)
            chose.append(idx)
        return state, jnp.stack(counts), jnp.stack(chose)

    # ---------------------------------------------------------- calls
    def prefill(self, slot, prompt, pages):
        """Run all but the last token of `prompt` into decode slot `slot`
        (ONE dispatch): K/V or latent rows into `pages` (the slot's
        granted pages, in order), a sliding-window layer's newest pages
        into the slot's ring, the recurrent state and convolution tails
        into the slot's rows, whatever a previous request left there
        overwritten."""
        toks = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= toks.size <= self.max_src_len:
            raise MXNetError(f"prompt of {toks.size} tokens: this server "
                             f"takes 1..{self.max_src_len} "
                             f"(max_prompt_len)")
        n = toks.size - 1
        if len(pages) * self.page_size < n:
            raise MXNetError(f"{len(pages)} pages cannot hold a prompt of "
                             f"{toks.size} tokens")
        padded = np.zeros((self._plen,), np.int32)
        padded[:n] = toks[:n]
        row = np.full((self.max_pages_per_slot,), NULL_PAGE, np.int32)
        row[:len(pages)] = pages
        profiler.record_dispatch("serve_prefill")
        self._m_rows.inc()
        old = jax.tree_util.tree_leaves(self._state)

        def launch():
            self._state, counts, self.routing["prefill"] = self._prefill_fn(
                self._state, self._w, jnp.asarray(padded), jnp.int32(n),
                jnp.int32(slot), jnp.asarray(row))
            self._pending.append(counts)

        try:
            if _tracer.ACTIVE:
                with _tracer.span("serve.prefill", cat="serve",
                                  args={"slot": int(slot),
                                        "prompt_len": int(toks.size)}):
                    launch()
            else:
                launch()
        except Exception as e:
            # the donation hazard DecodeRuntime.prefill describes: every
            # slot's state went with the consumed buffers
            if any(a.is_deleted() for a in old):
                self.reset_pages()
                raise MemoryStateLost(
                    f"prefill failed after consuming the donated state: "
                    f"{type(e).__name__}: {e}") from e
            raise

    def prefill_many(self, entries):
        """A turn's admissions, `entries` = [(slot, prompt, pages)], one
        `prefill` dispatch each in the order given (a prompt reads every
        weight: the device bounds this prefill, not the dispatches).
        Yields (1, the exception or None) after each, as
        `DecodeRuntime.prefill_many` does a dispatch."""
        for entry in entries:
            yield 1, _raised(self.prefill, *entry)

    def decode_launch(self, page_tables, lens, tok, active):
        """Dispatch one decode step for every slot (ONE dispatch) and
        return its `read`, as `DecodeRuntime.decode_launch` does
        (`active` 0 / 1 / 2 the same). The read also brings the step's
        expert counts, and those of the prefills dispatched before it,
        into `moe_counters()`: read every launch, once, in order."""
        profiler.record_dispatch("serve_decode")
        if self._n["swa"]:
            run = np.asarray(active) > 0
            self._win["turns"] += 1
            self._win["ring_tokens"] += int(np.minimum(
                np.asarray(lens)[run] + 1, self.spec.window).sum())
        (self._state, next_tok, logits, counts,
         self.routing["decode"]) = self._decode_fn(
            self._state, self._w, jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(tok, jnp.int32),
            jnp.asarray(active, jnp.int32), self._last_tok)
        self._last_tok = next_tok
        pending, self._pending = self._pending, []

        def read():
            host_tok, step, prefills = jax.device_get(
                (next_tok, counts, pending))
            for c in prefills:
                self._count(c, prefill=True)
            self._count(step)
            return host_tok, logits

        return read

    def decode(self, page_tables, lens, tok, active):
        """One decode step for every slot, launched and read. Returns
        (next_tok (S,) host int32, logits (S, V) float32 device array)."""
        return self.decode_launch(page_tables, lens, tok, active)()
