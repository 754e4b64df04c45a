"""Request-level serving API (ISSUE 6): `serve.Server`.

    model = transformer_base(vocab_size=...);  # trained TransformerNMT
    srv = mx.serve.Server(model, slots=8, page_size=16, num_pages=128)
    h = srv.submit([5, 9, 11], max_new_tokens=32)   # source token ids
    print(h.result())                               # generated ids
    for tok in srv.stream([5, 9, 11]):              # or stream them
        ...
    srv.close()

One `Server` owns: the weight snapshots (`decoder_weights` /
`encoder_weights`), the device-resident paged KV state + the two cached
executables (`serve.decode.DecodeRuntime`), the page allocator
(`serve.kv_pages.PagePool`), the continuous-batching scheduler, and an
engine-driven decode loop (`serve.engine_bridge.EngineLoop`). Submissions
from any thread kick the loop; decoding happens on engine workers.
`engine_driven=False` runs the crank inline in `result()`/`stream()`
instead — deterministic single-threaded mode for tests and tools.

Observability: per-request TTFT/latency histograms with p50/p95/p99
(`serve_ttft_seconds`, `serve_request_seconds`), `serve_tokens` and
tokens/s (`serve_tokens_per_s` gauge via `throughput()`), queue/slot
gauges, KV-page accounting from the pool, and `serve.*` trace spans when
the tracer is active (docs/SERVING.md + docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import threading
import time

from ..base import MXNetError
from ..models.decoder_lm import DecoderLM
from ..models.transformer import decoder_weights, encoder_weights
from ..observability import registry as _obs_registry
from .decode import DecodeRuntime
from .engine_bridge import EngineLoop
from .kv_pages import PagePool
from .lm_runtime import LMRuntime
from .scheduler import Scheduler

__all__ = ["Server"]


class Server:
    """Continuous-batching inference server for a `TransformerNMT` or a
    decoder-only `models.decoder_lm.DecoderLM`; which runtime serves the
    model follows from what the model is. For a `DecoderLM` `submit`'s
    first argument is the prompt (1..max_prompt_len tokens, `max_src_len`
    unused), `prompt_tokens` are forced after it one a turn, the low-
    precision options are not offered, and a model with recurrent layers
    refuses `prefix_cache` and `speculative_k` (docs/SERVING.md).

    slots: max concurrent decoding requests; page_size: tokens per KV
    page; num_pages: device pool size INCLUDING the reserved null page;
    max_src_len: static source padding length; max_new_tokens: per-slot
    generation cap; max_prompt_len: per-slot decoder-prompt cap (page-
    budget denominator is prompt + generation); speculative_k: tokens
    drafted per turn and verified in ONE widened dispatch (0 = classic
    one-token turns); prefix_cache: share full prompt pages across
    requests through the content-hashed radix index.

    Low precision (ISSUE 14): `kv_dtype="int8"` stores K/V pages int8
    with per-page/per-head scales — a fixed HBM budget holds ~4x the
    tokens of fp32 pages (`kv_hbm_bytes=` sizes the pool from a byte
    budget instead of a page count); `weight_dtype="int8"` runs the
    decode/prefill matmuls over per-output-channel int8 weight
    SNAPSHOTS (the model's master weights stay full precision). Every
    quantized server keeps a lazy full-precision twin: a `serve.quant`
    fault degrades that request to it with fp32-identical greedy
    output. See docs/SERVING.md "Low-precision serving" for the
    accuracy contract and knobs."""

    def __init__(self, model, slots=8, page_size=16, num_pages=None,
                 max_src_len=32, max_new_tokens=32, max_prompt_len=0,
                 speculative_k=0, prefix_cache=True, bos_id=2, eos_id=3,
                 max_queue=64, max_retries=1,
                 engine_driven=True, kv_dtype=None, weight_dtype=None,
                 kv_hbm_bytes=None):
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if speculative_k < 0:
            raise MXNetError("speculative_k must be >= 0")
        if weight_dtype not in (None, "float32", "int8"):
            raise MXNetError(f"weight_dtype must be None/'float32'/"
                             f"'int8', got {weight_dtype!r}")
        self.max_new_tokens = int(max_new_tokens)
        self.max_prompt_len = int(max_prompt_len)
        self.speculative_k = int(speculative_k)
        self.kv_dtype = kv_dtype if kv_dtype != "float32" else None
        self.weight_dtype = weight_dtype if weight_dtype != "float32" \
            else None
        budget_tokens = int(max_new_tokens) + self.max_prompt_len
        if isinstance(model, DecoderLM):
            self._init_lm(model, slots, page_size, num_pages, budget_tokens,
                          kv_hbm_bytes)
        else:
            self._init_nmt(model, slots, page_size, num_pages,
                           budget_tokens, max_src_len, kv_hbm_bytes)
        # quantized servers keep the model handle so a serve.quant fault
        # can degrade a request to a lazily-built full-precision twin
        self._model = model if (self.kv_dtype or self.weight_dtype) \
            else None
        self._fp_twin = None
        self._fp_lock = threading.Lock()
        quant_fallback = self._full_precision_decode if \
            self._model is not None else None
        self._sched = Scheduler(self._rt, self._pool, bos_id=bos_id,
                                eos_id=eos_id, max_queue=max_queue,
                                max_retries=max_retries,
                                prefix_cache=prefix_cache,
                                quant_fallback=quant_fallback)
        self._engine_driven = bool(engine_driven)
        self._loop = EngineLoop(self._sched) if self._engine_driven \
            else None
        self._closed = False
        # serialises submit() against close(): a submit that slips past
        # the closed check after shutdown drained the queue would strand
        # its handle forever
        self._close_lock = threading.Lock()
        self._t_start = time.perf_counter()
        self._m_tps = _obs_registry().gauge("serve_tokens_per_s")

    def _init_lm(self, model, slots, page_size, num_pages, budget_tokens,
                 kv_hbm_bytes):
        """Pool and runtime of a decoder-only model: pages for its
        softmax-attention layers only, per-slot arrays for the rest."""
        if self.kv_dtype or self.weight_dtype or kv_hbm_bytes is not None:
            raise MXNetError("kv_dtype, weight_dtype and kv_hbm_bytes are "
                             "not offered for a decoder-only model yet")
        if num_pages is None:
            num_pages = slots * (-(-budget_tokens // int(page_size))) + 1
        pages_per_slot = -(-budget_tokens // int(page_size))
        self._rt = LMRuntime(model, slots=slots, num_pages=num_pages,
                             page_size=page_size,
                             max_pages_per_slot=pages_per_slot,
                             max_prompt_len=self.max_prompt_len,
                             width=self.speculative_k + 1)
        self._pool = PagePool(num_pages, page_size,
                              page_bytes=self._rt.kv_bytes_per_page())

    def _init_nmt(self, model, slots, page_size, num_pages, budget_tokens,
                  max_src_len, kv_hbm_bytes):
        dec_w = decoder_weights(model)
        enc_w = encoder_weights(model)
        if self.weight_dtype == "int8":
            from .quant import (quantize_decoder_weights,
                                quantize_encoder_weights)
            dec_w = quantize_decoder_weights(dec_w)
            enc_w = quantize_encoder_weights(enc_w)
        if num_pages is None:
            if kv_hbm_bytes is not None:
                # pool sized from an HBM byte budget: the int8 cache's
                # capacity story — same bytes, ~4x the fp32 tokens
                from .quant import pages_for_budget
                u = dec_w["embed"].shape[1]
                h = dec_w["num_heads"]
                num_pages = pages_for_budget(
                    kv_hbm_bytes, len(dec_w["layers"]), int(page_size),
                    h, u // h, self.kv_dtype or str(dec_w["pos"].dtype))
            else:
                # every slot can hold a full-length request + null page
                num_pages = slots * \
                    (-(-budget_tokens // int(page_size))) + 1
        elif kv_hbm_bytes is not None:
            raise MXNetError("pass num_pages OR kv_hbm_bytes, not both")
        try:
            from .quant import kv_page_bytes
            u = dec_w["embed"].shape[1]
            h = dec_w["num_heads"]
            pbytes = kv_page_bytes(
                len(dec_w["layers"]), int(page_size), h, u // h,
                self.kv_dtype or str(dec_w["pos"].dtype))
        except MXNetError:
            pbytes = None            # exotic compute dtype: no byte gauge
        self._pool = PagePool(num_pages, page_size, page_bytes=pbytes)
        pages_per_slot = self._pool.pages_for(budget_tokens)
        self._rt = DecodeRuntime(
            dec_w, enc_w, slots=slots,
            num_pages=num_pages, page_size=page_size,
            max_pages_per_slot=pages_per_slot, max_src_len=max_src_len,
            width=self.speculative_k + 1, kv_dtype=self.kv_dtype)

    # ------------------------------------------------------------- API
    @property
    def scheduler(self):
        return self._sched

    @property
    def runtime(self):
        return self._rt

    @property
    def pool(self):
        return self._pool

    @property
    def prefix_cache(self):
        """The radix prefix cache (None when disabled)."""
        return self._sched.prefix_cache

    def submit(self, src_tokens, max_new_tokens=None, prompt_tokens=None,
               deadline_ms=None):
        """Enqueue a request; returns its `Request` handle immediately.
        Raises `ServeOverloaded` under backpressure. The handle's
        `.result(timeout)` / `.stream(timeout)` / `.done()` consume it.

        `prompt_tokens` is a decoder-side prompt (system prompt /
        few-shot template) teacher-forced before generation; its full KV
        pages are shared across requests through the content-hashed
        radix prefix cache, so a matching prefix skips that part of
        prefill (see docs/SERVING.md). `deadline_ms` bounds the request
        END-TO-END (queue wait included): when it elapses the scheduler
        evicts the request — queued or mid-decode — with a clean
        `ServeDeadlineExceeded`, frees its KV pages, and counts it into
        `serve_deadline_expired`."""
        if prompt_tokens is not None \
                and len(prompt_tokens) > self.max_prompt_len:
            raise MXNetError(
                f"prompt of {len(prompt_tokens)} tokens exceeds this "
                f"server's max_prompt_len {self.max_prompt_len} (size "
                f"the server with max_prompt_len= to accept prompts)")
        with self._close_lock:
            if self._closed:
                raise MXNetError("Server is closed")
            req = self._sched.submit(
                src_tokens, max_new_tokens if max_new_tokens is not None
                else self.max_new_tokens, prompt_tokens=prompt_tokens,
                deadline_ms=deadline_ms)
            if self._loop is not None:
                self._loop.kick()
            else:
                req._inline_sched = self._sched
            return req

    def stream(self, src_tokens, max_new_tokens=None, prompt_tokens=None,
               timeout=None, deadline_ms=None):
        """Submit + yield generated token ids as they are produced."""
        req = self.submit(src_tokens, max_new_tokens,
                          prompt_tokens=prompt_tokens,
                          deadline_ms=deadline_ms)
        yield from req.stream(timeout=timeout)

    def wait(self, handles=None, timeout=None):
        """Await completion of `handles` (or ALL traffic when None):
        inline mode cranks the scheduler up to the deadline; engine mode
        waits on the loop / the handles' events. Returns True when
        everything asked for finished (failed counts as finished),
        False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout

        def expired():
            return deadline is not None and time.monotonic() > deadline

        if handles is None:
            if self._loop is not None:
                return self._loop.wait_idle(timeout)
            while self._sched.pending_work():
                if expired():
                    return False
                self._sched.step()
            return True
        for h in handles:
            if self._loop is None:
                while not h.done():
                    if expired():
                        return False
                    self._sched.step()
            else:
                rem = None if deadline is None \
                    else max(0.0, deadline - time.monotonic())
                if not h._done.wait(rem):
                    return False
        return True

    def throughput(self):
        """THIS server's generated tokens/s since construction — counted
        per scheduler instance, so concurrent servers don't pollute each
        other (also sets the `serve_tokens_per_s` gauge, last-writer-
        wins across servers)."""
        dt = max(time.perf_counter() - self._t_start, 1e-9)
        tps = self._sched.tokens_generated / dt
        self._m_tps.set(tps)
        return tps

    def _full_precision_decode(self, src, prompt, max_new,
                               deadline=None):
        """The serve.quant degradation path (ISSUE 14): decode ONE
        request through a lazily-built full-precision twin server (1
        slot, inline, no prefix cache, no speculation) — greedy output
        is identical to an fp32 `Server`'s BY CONSTRUCTION, and the
        request never touches the quantized executables or this
        server's page pool. The twin compiles on the first fault only;
        fault-free quantized serving pays nothing. `deadline` is the
        original request's absolute monotonic deadline: the REMAINING
        budget becomes the twin request's own `deadline_ms`, so expiry
        surfaces as `ServeDeadlineExceeded` exactly as on the normal
        path (a degraded request gets no deadline amnesty)."""
        deadline_ms = None
        if deadline is not None:
            deadline_ms = max(0.0, (deadline - time.monotonic()) * 1e3)
        with self._fp_lock:
            if self._fp_twin is None:
                self._fp_twin = Server(
                    self._model, slots=1,
                    page_size=self._pool.page_size,
                    max_src_len=self._rt.max_src_len,
                    max_new_tokens=self.max_new_tokens,
                    max_prompt_len=self.max_prompt_len,
                    bos_id=self._sched.bos_id, eos_id=self._sched.eos_id,
                    prefix_cache=False, engine_driven=False)
            h = self._fp_twin.submit(
                src, max_new,
                prompt_tokens=prompt if len(prompt) else None,
                deadline_ms=deadline_ms)
            return h.result(timeout=600)

    def close(self):
        """Stop the loop and FAIL any still-pending requests (their
        handles unblock with `ServeError`, their pages return to the
        pool) — close never strands a held `Request`."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        if self._loop is not None:
            self._loop.close()
        self._sched.shutdown()
        if self._rt.kv_quant:
            # the gauge is last-writer-wins across servers (like
            # serve_tokens_per_s); a closed pool's scale bytes are gone
            _obs_registry().gauge("kv_page_scale_bytes").set(0)
        with self._fp_lock:
            if self._fp_twin is not None:
                self._fp_twin.close()
                self._fp_twin = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
