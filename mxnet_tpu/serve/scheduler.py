"""Continuous (inflight) batching scheduler (ISSUE 6).

Every `step()` is one turn of the serving crank:

  1. ADMIT — pop queued requests into free decode slots while pages are
     available (all-or-nothing first-page grant), then hand the runtime
     the turn's whole list: the translation runtime encodes up to
     `prefill_rows` sources in ONE dispatch of its cached prefill
     executable, a decoder-only runtime takes one dispatch a prompt;
  2. DECODE — one shared decode dispatch for ALL active slots (mixed
     lengths share the ragged-paged-attention launch), growing each
     active request by one token and one cache position, allocating a
     fresh page exactly when a request crosses a page boundary;
  3. EVICT — requests that emitted EOS or hit their token budget leave
     their slot and return every page to the pool immediately, so the
     NEXT step can admit into the freed capacity. No drain barriers:
     short requests never wait for long ones.

ONE TURN IN FLIGHT (ISSUE 35). While requests still wait for a SLOT after
a turn's admission (and the turn is one token wide), the crank looks ahead
by one turn: it plans and dispatches turn n+1 from what it knows without
turn n's tokens — each slot of turn n stands one position further, a slot
that reaches `max_new_tokens` in turn n is left out, and a slot at the
generation frontier takes its input token from turn n's output where it
lies on the device — and only THEN reads turn n and commits it. Commit,
eviction, the next call's deadline sweep, admission (its prefill
dispatches queue behind n+1) and planning all run while the device works.
With the queue empty an arrival would join turn n+2 where it joins n+1
today, so the turn in flight is read first and the serial order above
returns (`serve_lookahead_drains{why=queue_empty}`); a widened turn
drafts from committed tokens and never leaves one in flight. Everything
rare reads and commits the turn in flight before it acts (a dry pool's
preemption, `defrag`, `shutdown`, a fault). A request that ended on
`eos_id` in turn n has run one wasted position in n+1: commit drops a row
whose slot no longer holds its request. Page safety: a turn dispatched
ahead writes only rows of pages its slots own at dispatch, and the device
runs dispatches in order, so a page freed at commit n and granted again is
written by its new owner AFTER the stale row (a slot's recurrent state
likewise: the new owner's prefill overwrites it, behind the stale step).

Backpressure: the admission queue is bounded (`max_queue`); a submit into
a full queue raises `ServeOverloaded` (counted) instead of buffering
unboundedly. A request that cannot get its next page mid-decode is
PREEMPTED — pages freed, requeued at the front — rather than deadlocking
the pool (`serve_page_preemptions`).

The serving fast path (ISSUE 12) stacks two optimisations on the same
crank:

  * PREFIX CACHE — requests may carry a decoder-side `prompt_tokens`
    sequence (system prompt / few-shot template) that is teacher-forced
    into the paged KV cache before generation. Full prompt pages are
    indexed in a content-hashed radix tree (`prefix_cache.PrefixCache`);
    a later request with the same source and a matching prompt prefix
    ADOPTS those pages (refcounted sharing, never a copy) and skips that
    part of prefill. Under page pressure admission evicts LRU cache-only
    pages instead of failing (`serve_prefix_evictions`).
  * SPECULATIVE DECODING — with `width > 1` (Server(speculative_k=k)),
    each turn drafts up to k tokens by n-gram prompt lookup over the
    request's own committed history and verifies the whole window with
    ONE pass through the widened decode executable; the accepted run +
    one corrective token commit together. Greedy output is IDENTICAL to
    the 1-wide loop — drafts only change how many turns it takes.

Fault discipline (fault/injection.py points `serve.admit` /
`serve.decode` / `serve.prefix` / `serve.speculate`): an admit-time
fault fails ONLY the request being admitted. A decode-time fault kills
the whole in-flight batch — every active request frees its pages and is
retried from scratch (bounded by `max_retries`) or failed cleanly;
either way `kv_pages_in_use` returns to baseline (the chaos test
asserts this). An error raised by the decode executable itself
additionally resets the page pools AND clears the prefix cache (their
contents are no longer trustworthy after a partial in-place step); with a
turn in flight it surfaces at that turn's read and takes the turn
dispatched after it along. A
`serve.prefix` or `serve.speculate` fault merely DEGRADES — cache
lookup/insert skipped, turn runs unspeculated — with bitwise-identical
request output.
"""
from __future__ import annotations

import collections
import threading
import time

from ..base import MXNetError
from ..fault import injection as _finj
from ..observability import registry as _obs_registry
from ..observability import tracer as _tracer
from .decode import MemoryStateLost
from .kv_pages import NULL_PAGE, PageAllocError
from .prefix_cache import PrefixCache, content_key
from .speculate import propose_ngram

__all__ = ["Request", "Scheduler", "ServeError", "ServeOverloaded",
           "ServeDeadlineExceeded", "StepResult"]

_STREAM_END = object()


class ServeError(MXNetError):
    """A request failed inside the serving engine."""


class ServeOverloaded(ServeError):
    """Admission queue full — backpressure; retry later."""


class ServeDeadlineExceeded(ServeError):
    """The request's `deadline_ms` elapsed before it finished: it was
    evicted (queued or mid-decode), its pages freed, and
    `serve_deadline_expired` counted it."""


class Request:
    """One inference request + its result/stream plumbing. Create via
    `Server.submit`; consume via `.result()` / `.stream()` / `.tokens`."""

    def __init__(self, rid, src, max_new_tokens, prompt=None,
                 deadline_ms=None):
        self.id = rid
        self.src = src
        # decoder-side prompt (ISSUE 12): tokens teacher-forced into the
        # paged KV cache before free-running generation — the shared-
        # system-prompt material the radix prefix cache deduplicates
        self.prompt = [] if prompt is None else [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        # absolute monotonic deadline: survives retries/preemptions (the
        # budget is end-to-end, not per-attempt)
        self.deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        self.state = "queued"       # queued|running|done|failed
        self.tokens = []            # generated ids (EOS included if hit)
        self.error = None
        self._exc = None            # typed failure (ServeDeadlineExceeded)
        self.retries = 0            # fault retries (budget: max_retries)
        self.preemptions = 0        # page-pressure requeues (own budget)
        self.t_submit = time.perf_counter()
        self.t_admit = None         # stamped at each admission into a slot
        self.t_first_token = None
        self.t_done = None
        self._slot = None
        self._pages = []
        self.known = None           # [BOS] + prompt + committed tokens
        self._n_table = 0           # valid page-table entries this attempt
        self._cache_done = False    # prompt pages offered to the cache
        self.prompt_cached_tokens = 0   # adopted prefix length (positions)
        self._content_key = None    # memoized source hash (Scheduler)
        self._admit_bypassed = 0    # warm-preference skips of THIS head
        self._done = threading.Event()
        self._chunks = collections.deque()  # streamed tokens + sentinel
        self._chunk_cv = threading.Condition()
        self._inline_sched = None   # set by Server(engine_driven=False)
        self._on_finish = None      # one-shot scheduler bookkeeping hook

    # ------------------------------------------------------- consumer
    @property
    def ttft(self):
        """Seconds from submit to first generated token (None until)."""
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def latency(self):
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit

    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        """Block until the request finishes; returns the generated token
        list, or raises `ServeError` if it failed. In inline mode
        (Server(engine_driven=False)) this call cranks the scheduler,
        still honouring the deadline."""
        wait_timeout = timeout
        if self._inline_sched is not None:
            deadline = None if timeout is None \
                else time.monotonic() + timeout
            while not self._done.is_set():
                if deadline is not None and time.monotonic() > deadline:
                    break
                self._inline_sched.step()
            if deadline is not None:
                # the crank spent (part of) the budget; only the
                # remainder may be slept away below
                wait_timeout = max(0.0, deadline - time.monotonic())
        if not self._done.wait(wait_timeout):
            raise ServeError(f"request {self.id} timed out after "
                             f"{timeout}s")
        if self.state == "failed":
            if self._exc is not None:
                raise self._exc
            raise ServeError(f"request {self.id} failed: {self.error}")
        return list(self.tokens)

    def stream(self, timeout=None):
        """Yield generated token ids as they are produced; raises
        `ServeError` at the end if the request failed. `timeout` bounds
        the wait for EACH token (inline mode cranks the scheduler up to
        that per-token deadline)."""
        while True:
            with self._chunk_cv:
                item = self._chunks.popleft() if self._chunks else None
            if item is None:
                if self._inline_sched is not None:
                    deadline = None if timeout is None \
                        else time.monotonic() + timeout
                    while True:
                        with self._chunk_cv:
                            if self._chunks:
                                break
                        if deadline is not None and \
                                time.monotonic() > deadline:
                            raise ServeError(
                                f"request {self.id}: no token within "
                                f"{timeout}s")
                        self._inline_sched.step()
                    continue
                with self._chunk_cv:
                    while not self._chunks:
                        if not self._chunk_cv.wait(timeout):
                            raise ServeError(
                                f"request {self.id}: no token within "
                                f"{timeout}s")
                    item = self._chunks.popleft()
            if item is _STREAM_END:
                if self.state == "failed":
                    if self._exc is not None:
                        raise self._exc
                    raise ServeError(
                        f"request {self.id} failed: {self.error}")
                return
            yield item

    # ------------------------------------------------------- producer
    def _emit(self, tok):
        self.tokens.append(tok)
        with self._chunk_cv:
            self._chunks.append(tok)
            self._chunk_cv.notify_all()

    def _finish(self, state, error=None):
        self.state = state
        self.error = error
        self.t_done = time.perf_counter()
        cb, self._on_finish = self._on_finish, None
        if cb is not None:
            cb()
        with self._chunk_cv:
            self._chunks.append(_STREAM_END)
            self._chunk_cv.notify_all()
        self._done.set()


class StepResult:
    """What one scheduler turn did (truthy = progress was made)."""
    __slots__ = ("admitted", "decoded", "completed", "preempted", "retried")

    def __init__(self, admitted=0, decoded=0, completed=0, preempted=0,
                 retried=0):
        self.admitted = admitted
        self.decoded = decoded
        self.completed = completed
        self.preempted = preempted
        self.retried = retried

    def __bool__(self):
        return bool(self.admitted or self.decoded)


class _Turn:
    """A dispatched decode turn: `rows` {slot: request} as it ran them,
    their `plans` (`Scheduler._plan_turn`), `read` (the call that waits
    for the turn and gives its (slots, width) host tokens), and when it
    began."""
    __slots__ = ("rows", "plans", "read", "t0")

    def __init__(self, rows, plans, read, t0):
        self.rows = rows
        self.plans = plans
        self.read = read
        self.t0 = t0


class Scheduler:
    def __init__(self, runtime, pool, bos_id=2, eos_id=3, max_queue=64,
                 max_retries=1, max_preemptions=8, prefix_cache=True,
                 spec_ngram=2, quant_fallback=None):
        import numpy as np
        self._np = np
        self._rt = runtime
        self._pool = pool
        # speculative decoding rides the runtime's widened executable:
        # width = spec_k + 1 (window = current token + k drafts)
        self.width = int(getattr(runtime, "width", 1))
        self.spec_k = self.width - 1
        self.spec_ngram = int(spec_ngram)
        refusal = runtime.page_reuse_refusal
        if refusal and (prefix_cache or self.width > 1):
            raise MXNetError(
                f"{'prefix_cache' if prefix_cache else 'speculative_k'} "
                f"refused: {refusal}")
        if prefix_cache is True:
            self._cache = PrefixCache(pool)
        elif prefix_cache:
            self._cache = prefix_cache      # caller-supplied instance
        else:
            self._cache = None
        self.bos_id = int(bos_id)
        self.eos_id = int(eos_id)
        self.max_queue = int(max_queue)
        self.max_retries = int(max_retries)
        # page-pressure preemptions are legitimate queueing, not faults —
        # they get their own (laxer) restart budget so transient capacity
        # pressure cannot burn a request's fault retries
        self.max_preemptions = int(max_preemptions)
        # low-precision degradation path (ISSUE 14): on a `serve.quant`
        # fault, a quantized server routes THAT request through this
        # full-precision callback instead of the int8 executables —
        # identical greedy output to an fp32 server, no pages touched
        self._quant_fallback = quant_fallback
        s = runtime.slots
        self._slots = [None] * s                       # Request per slot
        self._page_tables = np.full(
            (s, runtime.max_pages_per_slot), NULL_PAGE, np.int32)
        self._lens = np.zeros((s,), np.int32)
        self._queue = collections.deque()
        self._lock = threading.Lock()
        # live admitted requests carrying a deadline — gates the per-turn
        # expiry sweep so deadline-free workloads never pay the O(queue)
        # scan (same idiom as engine._admit's _deadline_queued gate)
        self._deadline_live = 0
        self._deadline_lock = threading.Lock()
        # serialises whole turns: step() (engine loop or inline result()
        # cranks from several threads), defrag()'s device remap, and
        # shutdown() must never interleave mid-turn
        self._step_lock = threading.Lock()
        self._next_id = 0
        self.tokens_generated = 0   # per-instance (the registry counter
                                    # below is process-global)
        reg = _obs_registry()
        self._m_queue = reg.gauge("serve_queue_depth")
        self._m_queue.set(0)
        self._m_active = reg.gauge("serve_active_slots")
        self._m_active.set(0)
        self._m_tokens = reg.counter("serve_tokens")
        self._m_ok = reg.counter("serve_requests", result="ok")
        self._m_failed = reg.counter("serve_requests", result="failed")
        self._m_rejected = reg.counter("serve_requests", result="rejected")
        self._m_retries = reg.counter("serve_decode_retries")
        self._m_preempt = reg.counter("serve_page_preemptions")
        self._m_deadline = reg.counter("serve_deadline_expired")
        self._m_ttft = reg.histogram("serve_ttft_seconds")
        self._m_queue_wait = reg.histogram("serve_queue_wait_seconds")
        self._m_latency = reg.histogram("serve_request_seconds")
        self._m_step = reg.histogram("serve_decode_step_seconds")
        # speculative decoding telemetry (ISSUE 12): the acceptance
        # distribution is the regression signal — profiler.dumps() shows
        # it as a [serve-spec] row
        self._m_spec_hist = reg.histogram("serve_spec_accepted_tokens")
        self._m_spec_drafted = reg.counter("serve_spec_drafted")
        self._m_spec_accepted = reg.counter("serve_spec_accepted")
        self._m_spec_degraded = reg.counter("serve_spec_degraded")
        self._m_prefix_degraded = reg.counter("serve_prefix_degraded")
        self._m_quant_degraded = reg.counter("serve_quant_degraded")
        self._m_warm_pref = reg.counter("serve_prefix_admit_preferred")
        # the lookahead (ISSUE 35): turns dispatched before the previous
        # turn's read, and why a turn in flight was read before the next
        # dispatch (queue_empty, pool_dry, defrag, shutdown, error)
        self._m_ahead = reg.counter("serve_lookahead_turns")
        self._m_drains = {
            why: reg.counter("serve_lookahead_drains", why=why)
            for why in ("queue_empty", "pool_dry", "defrag", "shutdown",
                        "error")}
        # the turn dispatched and not yet read, if any (a `_Turn`)
        self._inflight = None
        # per-instance tallies (registry counters are process-global)
        self.decode_turns = 0       # committed turns
        self.lookahead_turns = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

    # ------------------------------------------------------------ API
    @property
    def prefix_cache(self):
        """The radix prefix cache (None when disabled)."""
        return self._cache

    def submit(self, src_tokens, max_new_tokens, prompt_tokens=None,
               deadline_ms=None):
        """Enqueue a request; returns the `Request` handle. Raises
        `ServeOverloaded` when the bounded admission queue is full and
        `ServeError` when the `serve.admit` fault point fires.
        `prompt_tokens` (ISSUE 12) is a decoder-side prompt teacher-
        forced before generation begins — its full KV pages are shared
        through the radix prefix cache, so a later request with the same
        source and a matching prompt prefix adopts them and skips that
        part of prefill. `deadline_ms` bounds the request END-TO-END
        (queue wait included): once it elapses the request is evicted
        wherever it is — queued or mid-decode — with
        `ServeDeadlineExceeded`, its pages freed and
        `serve_deadline_expired` counting the eviction."""
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        prompt = [] if prompt_tokens is None else [
            int(t) for t in self._np.asarray(prompt_tokens,
                                             self._np.int32).reshape(-1)]
        src = self._np.asarray(src_tokens, self._np.int32).reshape(-1)
        if src.size == 0:
            raise MXNetError("src_tokens must be non-empty (an empty "
                             "source has no cross-attention context)")
        if src.size > self._rt.max_src_len:
            raise MXNetError(f"source length {src.size} exceeds the "
                             f"server's max_src_len "
                             f"{self._rt.max_src_len}")
        # cached positions the request can reach: what the runtime's
        # prefill caches of the source (nothing of an encoder's), the
        # prompt, the generation
        positions = self._rt.begin(src, self.bos_id)[1] + len(prompt) \
            + max_new
        budget = self._rt.max_pages_per_slot * self._rt.page_size
        if positions > budget:
            raise MXNetError(
                f"prompt ({positions - max_new}) + max_new_tokens "
                f"({max_new}) exceeds the per-slot page budget "
                f"({self._rt.max_pages_per_slot} pages x "
                f"{self._rt.page_size})")
        need = self._pool.pages_for(positions)
        if need > self._pool.capacity:
            # doomed even with the pool to itself: reject at submit time
            # instead of burning prefills + retries on guaranteed
            # mid-decode page exhaustion
            raise MXNetError(
                f"prompt + max_new_tokens ({positions - max_new} + "
                f"{max_new}) needs {need} pages but the pool only has "
                f"{self._pool.capacity} total")
        with self._lock:
            rid = self._next_id
            self._next_id += 1
        req = Request(rid, src, max_new, prompt=prompt,
                      deadline_ms=deadline_ms)
        try:
            if _finj.ENABLED:
                _finj.check("serve.admit", context=f"request {rid}")
        except Exception as e:
            self._m_failed.inc()
            req._finish("failed", f"admit fault: {e!r}")
            raise ServeError(f"request {rid} rejected at admission: "
                             f"{e}") from e
        with self._lock:
            if len(self._queue) >= self.max_queue:
                self._m_rejected.inc()
                req._finish("failed", "admission queue full")
                raise ServeOverloaded(
                    f"admission queue full ({self.max_queue}); retry "
                    "later")
            self._queue.append(req)
            self._m_queue.set(len(self._queue))
            if req.deadline is not None:
                with self._deadline_lock:
                    self._deadline_live += 1
                req._on_finish = self._dec_deadline_live
        if _tracer.ACTIVE:
            _tracer.instant("serve.submit", args={"id": rid})
        return req

    def pending_work(self):
        with self._lock:
            return bool(self._queue) or self._inflight is not None \
                or any(r is not None for r in self._slots)

    def active_count(self):
        return sum(1 for r in self._slots if r is not None)

    # ----------------------------------------------------------- step
    def step(self):
        """One serving turn: admit -> decode -> evict. Returns a
        `StepResult` (truthy when any progress was made). Turns are
        serialised on an internal lock (inline handles may crank from
        several threads; `defrag`/`shutdown` take the same lock)."""
        with self._step_lock:
            return self._step_locked()

    def _step_locked(self):
        # the turn's phases are child spans of `serve.turn` (admit, plan,
        # decode_step, commit); what no child covers is the deadline
        # sweep, the active lists and `_decode`'s array building
        if _tracer.ACTIVE:
            with _tracer.span("serve.turn", cat="serve",
                              args={"turn": self.decode_turns,
                                    "queued": len(self._queue)}):
                return self._turn()
        return self._turn()

    def _turn(self):
        res = StepResult()
        self._expire_deadlines()
        with _tracer.span("serve.admit", cat="serve"):
            res.admitted = self._admit(res)
        # look ahead only while requests still wait for a SLOT: a new
        # arrival then loses nothing by a turn in flight. With the queue
        # empty it would join turn n+2 where it joins n+1, and a widened
        # turn drafts from committed tokens: both keep the serial order
        ahead = self.width == 1 and bool(self._queue)
        if self._inflight is not None and not ahead:
            self._drain("queue_empty", res)
            return res
        active = [(s, r) for s, r in enumerate(self._slots)
                  if r is not None]
        if not active and self._inflight is None:
            self._m_active.set(0)
            return res
        t0 = time.perf_counter()
        turn = None
        try:
            if _finj.ENABLED:
                _finj.check("serve.decode",
                            context=f"{len(active)} active")
            with _tracer.span("serve.plan", cat="serve"):
                plans = self._plan_turn(active, res)
            if plans is not None:
                rows = {s: r for s, r in active if s in plans}
                if not rows and self._inflight is None:
                    return res
                turn, next_tok = self._decode(rows, plans, t0, ahead)
                res.decoded = len(rows)
        except _finj.FaultInjected as e:
            self._fail_inflight(res, e, reset_pages=False)
            return res
        except Exception as e:  # executable error: pages untrustworthy
            self._fail_inflight(res, e, reset_pages=True)
            return res
        if plans is None:
            # the pool is dry under a turn in flight: commit it (what it
            # completes frees pages) and let the next call preempt from
            # committed state if it still must
            self._drain("pool_dry", res)
        elif turn is not None:
            self._finish_turn(turn, next_tok, res)
        return res

    def _finish_turn(self, turn, next_tok, res):
        """A turn's tokens are on the host: count it and commit it."""
        self._m_step.observe(time.perf_counter() - turn.t0)
        res.decoded = res.decoded or len(turn.rows)
        self.decode_turns += 1
        with _tracer.span("serve.commit", cat="serve"):
            self._commit(turn.rows, turn.plans, next_tok, res)

    def _drain(self, why, res):
        """Read and commit the turn in flight, if there is one: what
        everything that needs the committed state does first (the serial
        order's return, a dry pool, `defrag`, `shutdown`, a fault). A
        read that fails takes the in-flight requests with it, as a decode
        error does."""
        turn, self._inflight = self._inflight, None
        if turn is None:
            return
        self._m_drains[why].inc()
        try:
            # the read alone: a decode_step without a dispatch, which
            # carries no `cached_tokens` for a reader to count
            with _tracer.span("serve.decode_step", cat="serve",
                              args={"active": 0}):
                next_tok = self._read(turn)
        except Exception as e:
            self._fail_inflight(res, e, reset_pages=True)
            return
        self._finish_turn(turn, next_tok, res)

    def _read(self, turn, lookahead=False):
        """A turn's tokens: the wait for the device and their copy to
        the host. Traced as `serve.decode_read`, a child of the turn's
        `serve.decode_step`; `lookahead` 1 where a later turn was
        already in flight while it waited (the wait is then the host's
        slack, not the device's idleness)."""
        if _tracer.ACTIVE:
            with _tracer.span("serve.decode_read", cat="serve",
                              args={"lookahead": int(lookahead)}):
                return turn.read()
        return turn.read()

    def _commit(self, rows, plans, next_tok, res):
        """The turn's host tail: commit each slot's accepted tokens, emit
        them, offer finished prompt pages to the prefix cache, evict the
        requests that are done."""
        now = time.perf_counter()
        for s, r in rows.items():
            if self._slots[s] is not r:
                # the request left its slot while the turn was in flight
                # (it ended on `eos_id` a turn earlier, or its deadline
                # passed): the row is nobody's. A request that is
                # REQUEUED never has a turn in flight (`_drain` first),
                # so the same request here is the same attempt
                continue
            L, window, f, _ = plans[s]
            q = len(window)
            g = next_tok[s]                    # (width,) host int32
            commits = []
            accepted = 0
            if L + f == len(r.known):
                # the window reaches the generation frontier: g[f-1] is
                # the greedy token after the last known one, and each
                # accepted draft (window[i+1] == g[i]) validates one
                # more greedy commit — EXACTLY the tokens the 1-wide
                # loop would have produced over as many turns
                i = f - 1
                while True:
                    tok = int(g[i])
                    commits.append(tok)
                    if tok == self.eos_id or \
                            len(r.tokens) + len(commits) \
                            >= r.max_new_tokens:
                        break
                    if i + 1 < q and window[i + 1] == tok:
                        i += 1
                        continue
                    break
                accepted = i - (f - 1)
                self._lens[s] = L + f + accepted
            else:
                # pure prompt turn: every window token was forced, every
                # prediction is for a position we already know
                self._lens[s] = L + q
            if q > f:
                drafted = q - f
                self._m_spec_drafted.inc(drafted)
                self.spec_drafted += drafted
                self._m_spec_accepted.inc(accepted)
                self.spec_accepted += accepted
                self._m_spec_hist.observe(accepted)
            self._offer_prompt_pages(s, r)
            if not commits:
                continue
            if r.t_first_token is None:
                r.t_first_token = now
            r.known.extend(commits)
            for tok in commits:
                r._emit(tok)
            if commits[-1] == self.eos_id \
                    or len(r.tokens) >= r.max_new_tokens:
                self._evict(s, r, "done")
                res.completed += 1
        self._m_active.set(self.active_count())

    def defrag(self):
        """Compact the page pool: renumber live pages into the low ids,
        remap the device pools (one gather dispatch) and every active
        slot's page table + request page list. Takes the step lock, so
        it is safe to call from any thread while the engine loop is
        decoding; a no-op when the pool is already compact. Returns the
        number of pages that moved."""
        with self._step_lock:
            return self._defrag_locked()

    def _defrag_locked(self):
        self._drain("defrag", StepResult())
        mapping = self._pool.defrag()
        if not mapping:
            return 0
        self._rt.remap_pages(mapping)
        np = self._np
        remap = np.arange(self._rt.num_pages)
        for old, new in mapping.items():
            remap[old] = new
        self._page_tables = remap[self._page_tables].astype(np.int32)
        for r in self._slots:
            if r is not None:
                r._pages = [mapping.get(p, p) for p in r._pages]
        if self._cache is not None:
            self._cache.remap(mapping)
        return len(mapping)

    def shutdown(self, reason="server closed"):
        """Fail every queued and in-flight request (pages freed, events
        set) — `Server.close()` calls this so held handles can never
        block forever on a stopped loop."""
        with self._step_lock:
            self._shutdown_locked(reason)

    def _shutdown_locked(self, reason):
        self._drain("shutdown", StepResult())
        with self._lock:
            queued = list(self._queue)
            self._queue.clear()
            self._m_queue.set(0)
        for r in queued:
            self._m_failed.inc()
            r._finish("failed", reason)
        for s, r in enumerate(self._slots):
            if r is not None:
                self._release_slot(s, r)
                self._m_failed.inc()
                r._finish("failed", reason)
        if self._cache is not None:
            self._cache.clear()
        self._m_active.set(0)

    def run_until_idle(self, max_steps=100000):
        """Drive `step()` until queue and slots drain (tests, tools)."""
        for _ in range(max_steps):
            if not self.pending_work():
                return
            self.step()
        raise MXNetError("scheduler failed to drain")

    # ------------------------------------------------------- internals
    def _dec_deadline_live(self):
        with self._deadline_lock:
            self._deadline_live -= 1

    def _expire_deadlines(self):
        """Evict every request whose end-to-end deadline has elapsed —
        queued requests leave the admission queue, running ones leave
        their slot with pages freed — finishing each with a clean
        `ServeDeadlineExceeded` (serve_deadline_expired counts them).
        Gated on the live deadline count: a deadline-free workload pays
        one lock acquire per turn, not an O(queue) sweep."""
        with self._deadline_lock:
            if not self._deadline_live:
                return
        now = time.monotonic()
        expired = []
        with self._lock:
            stale = [r for r in self._queue
                     if r.deadline is not None and now > r.deadline]
            if stale:
                stale_ids = {id(r) for r in stale}   # O(n) rebuild, not
                keep = collections.deque(r for r in self._queue  # O(n*k)
                                         if id(r) not in stale_ids)
                self._queue = keep
                self._m_queue.set(len(keep))
                expired.extend(stale)
        for s, r in enumerate(self._slots):
            if r is not None and r.deadline is not None \
                    and now > r.deadline:
                self._release_slot(s, r)
                expired.append(r)
        for r in expired:
            self._m_deadline.inc()
            self._m_failed.inc()
            r._exc = ServeDeadlineExceeded(
                f"request {r.id} exceeded its deadline "
                f"({len(r.tokens)} token(s) generated)")
            r._finish("failed", "deadline exceeded")
            if _tracer.ACTIVE:
                _tracer.instant("serve.deadline_expired",
                                args={"id": r.id})
        if expired:
            self._m_active.set(self.active_count())

    def _admit(self, res=None):
        """A turn's admissions: gather what can start, hand the runtime
        the whole list (`prefill_many`: ONE dispatch for up to
        `prefill_rows` requests of an encoder's sources; a decoder-only
        runtime takes one a prompt), then stamp each dispatch's requests
        into their slots as it returns."""
        gathered = self._gather_admissions()
        if not gathered:
            return 0
        admitted = done = 0
        for n, err in self._rt.prefill_many(
                [(s, req.src, pages) for s, req, pages, *_ in gathered]):
            group = gathered[done:done + n]
            done += n
            if err is None:
                for entry in group:
                    self._seat(*entry)
                admitted += n
                continue
            # the dispatch failed: its requests fail, their pages go
            # back, their slots stay free, and the turn goes on
            for _s, req, pages, *_ in group:
                self._pool.free(pages)
                self._m_failed.inc()
                req._finish("failed", f"prefill error: {err!r}")
            if isinstance(err, MemoryStateLost):
                # the donated memory buffers died: EVERY in-flight slot
                # lost its encoder state (the runtime already rebuilt
                # zeroed buffers) — restart those requests from scratch;
                # re-admission re-prefills each slot
                self._fail_inflight(
                    res if res is not None else StepResult(), err,
                    reset_pages=False)
        if admitted:
            self._m_active.set(self.active_count())
        return admitted

    def _gather_admissions(self):
        """Pop queued requests while a free slot and their first pages
        are there: [(slot, request, pages, known, adopted positions,
        prefilled positions)], nothing dispatched yet. A slot is reserved
        by its place in the list."""
        free = [s for s, r in enumerate(self._slots) if r is None]
        psize = self._pool.page_size
        gathered = []
        while len(gathered) < len(free):
            with self._lock:
                if not self._queue:
                    break
                req = self._pop_next_locked()
                self._m_queue.set(len(self._queue))
            # serve.quant fault (ISSUE 14): degrade THIS request to the
            # full-precision path before it touches pages or slots —
            # leak-freedom is structural (nothing was allocated yet)
            if self._quant_fallback is not None and _finj.ENABLED:
                try:
                    _finj.check("serve.quant",
                                context=f"request {req.id}")
                except _finj.FaultInjected:
                    self._degrade_quant(req)
                    continue
            # what is known before generation, and how much of it the
            # runtime's prefill caches (a decoder-only prompt; nothing
            # of an encoder's source)
            known, prefilled = self._rt.begin(req.src, self.bos_id)
            known = known + req.prompt
            # prefix-cache adoption (ISSUE 12): the longest cached chain
            # of FULL prompt pages under this source's content hash is
            # adopted (shared, never copied) — those positions skip
            # teacher-forced prefill entirely. Capped so the next input
            # token is still a KNOWN one (the page after the adopted run
            # starts with prompt material).
            hit = []
            if self._cache is not None and len(req.prompt) >= psize:
                try:
                    if _finj.ENABLED:
                        _finj.check("serve.prefix",
                                    context=f"lookup request {req.id}")
                    hit = self._cache.lookup(self._src_key(req), known,
                                             len(req.prompt) // psize)
                except _finj.FaultInjected:
                    # degrade to the cold path: same output, no reuse
                    self._m_prefix_degraded.inc()
                    hit = []
                if hit:
                    # the adopter's reference FIRST: pressure eviction
                    # below must never reap the pages just handed out
                    self._pool.share(hit)
            try:
                # the pages prefill writes and the first decode write's
                # beyond the adopted ones (one page, for an encoder's)
                first = self._alloc_pages(self._pool.pages_for(
                    len(hit) * psize + prefilled + 1) - len(hit))
            except PageAllocError:
                # no first page -> push back and stop gathering; decode
                # progress on the current actives will free pages
                if hit:
                    self._pool.free(hit)
                with self._lock:
                    self._queue.appendleft(req)
                    self._m_queue.set(len(self._queue))
                break
            gathered.append((free[len(gathered)], req, hit + first, known,
                             len(hit) * psize, prefilled))
        return gathered

    def _seat(self, s, req, pages, known, adopted, prefilled):
        """A prefilled request takes its slot."""
        req.state = "running"
        req._slot = s
        req._pages = pages
        req.known = known
        req.prompt_cached_tokens = adopted
        req._cache_done = False
        self._slots[s] = req
        self._page_tables[s, :] = NULL_PAGE
        self._page_tables[s, :len(pages)] = pages
        req._n_table = len(pages)
        self._lens[s] = adopted + prefilled
        # queue wait, measured where the request leaves the queue:
        # submit to holding a slot, its own prefill dispatch included
        req.t_admit = time.perf_counter()
        wait = req.t_admit - req.t_submit
        self._m_queue_wait.observe(wait)
        if _tracer.ACTIVE:
            _tracer.instant("serve.admitted", cat="serve", args={
                "id": req.id, "slot": s,
                "queue_wait_ms": wait * 1e3,
                "cached_tokens": adopted})

    # a cold queue head is bypassed by warm-preferred admissions at most
    # this many times before FIFO order reasserts itself — bounds
    # starvation under sustained warm traffic
    MAX_ADMIT_BYPASS = 4

    def _pop_next_locked(self):
        """Cache-aware admission order: FIFO normally, but when pages
        are TIGHT (the head's full cold working set no longer fits the
        free pool) prefer the queued request with the LONGEST warm
        cached prefix — it admits at a smaller fresh-page cost, which
        cuts the mid-decode preemptions page pressure would otherwise
        cause. A head bypassed `MAX_ADMIT_BYPASS` times is admitted
        regardless (no starvation under sustained warm arrivals). Probes
        use `PrefixCache.peek` (no metrics, no LRU touch);
        `serve_prefix_admit_preferred` counts reorders. Caller holds
        `self._lock`."""
        if self._cache is None or len(self._queue) <= 1:
            return self._queue.popleft()
        head = self._queue[0]
        if head._admit_bypassed >= self.MAX_ADMIT_BYPASS \
                or self._pool.available() >= self._pool.pages_for(
                    len(head.prompt) + head.max_new_tokens):
            return self._queue.popleft()
        psize = self._pool.page_size
        best_i, best_warm = 0, -1
        for i, r in enumerate(self._queue):
            warm = 0
            if len(r.prompt) >= psize:
                warm = self._cache.peek(self._src_key(r),
                                        [self.bos_id] + r.prompt,
                                        len(r.prompt) // psize)
            if warm > best_warm:
                best_i, best_warm = i, warm
        if best_i == 0:
            return self._queue.popleft()
        head._admit_bypassed += 1
        req = self._queue[best_i]
        del self._queue[best_i]
        self._m_warm_pref.inc()
        return req

    @staticmethod
    def _src_key(req):
        """Memoized content hash of the request's source (immutable per
        request; the admission hot path probes it repeatedly)."""
        if req._content_key is None:
            req._content_key = content_key(req.src)
        return req._content_key

    def _alloc_pages(self, n):
        """`pool.alloc` with prefix-cache pressure relief: when the pool
        is dry, evict least-recently-used CACHE-ONLY pages (nothing in
        flight adopted them) and retry, so cached prefixes cost capacity
        only while it is spare — admission never fails because of them."""
        try:
            return self._pool.alloc(n)
        except PageAllocError:
            if self._cache is None or not self._cache.evict(n):
                raise
            return self._pool.alloc(n)

    def _plan_turn(self, active, res):
        """Build every active slot's token window for this turn — the
        FORCED tokens first (known-but-uncached prompt / committed
        tokens), then up to `spec_k` n-gram drafts once the window
        reaches the generation frontier — and allocate the pages those
        positions need: {slot: (position, window, forced tokens in it,
        source)}. A slot whose current page is full when the pool
        is dry is preempted (pages freed, requeued) exactly like the
        1-wide path; a slot that can only fit part of its window just
        runs a shorter window (ragged qlens are free — same executable,
        same dispatch).

        Under a turn in flight (n; this plans n+1) a slot of turn n
        stands one position further than the host has committed; one that
        reaches `max_new_tokens` in turn n is left out; one at the
        generation frontier has nothing known to feed, and takes turn n's
        choice where it lies on the device (source 2, the runtime's
        `active` code; 1 is the host's token). If the pool runs dry the
        plan is dropped (None): preemption is for committed state."""
        prev = self._inflight
        psize = self._rt.page_size
        budget = self._rt.max_pages_per_slot * psize
        width = self.width
        draft_ok = self.spec_k > 0
        if draft_ok and _finj.ENABLED:
            try:
                _finj.check("serve.speculate", context="draft window")
            except _finj.FaultInjected:
                # degrade: run the turn unspeculated — committed output
                # is IDENTICAL, only turns/token suffers
                self._m_spec_degraded.inc()
                draft_ok = False
        plans = {}
        for s, r in active:
            L = int(self._lens[s])
            if prev is not None and prev.rows.get(s) is r:
                if L + 1 == len(r.known) \
                        and len(r.tokens) + 1 >= r.max_new_tokens:
                    continue            # turn n is its last, by length
                L += 1
            window = list(r.known[L:L + width])
            f, source = len(window), 1
            if not window:
                window, f, source = [0], 1, 2
            elif draft_ok and f < width:
                window.extend(propose_ngram(r.known, width - f,
                                            self.spec_ngram))
            del window[budget - L:]     # never write past the page budget
            need_idx = (L + len(window) - 1) // psize
            while r._n_table <= need_idx:
                try:
                    page = self._alloc_pages(1)[0]
                except PageAllocError:
                    if prev is not None:
                        return None
                    del window[r._n_table * psize - L:]
                    break
                r._pages.append(page)
                self._page_tables[s, r._n_table] = page
                r._n_table += 1
            if not window:
                self._m_preempt.inc()
                self._requeue(s, r, "page pool exhausted mid-decode",
                              preempted=True)
                res.preempted += 1
                continue
            plans[s] = (L, window, min(f, len(window)), source)
        return plans

    def _decode(self, rows, plans, t0, ahead):
        """Dispatch the planned turn and read the turn that is due: the
        one in flight if there is one (this turn then takes its place),
        else this one, unless it is to stay in flight (`ahead`). Returns
        (the turn read or None, its tokens)."""
        np = self._np
        width = self.width
        mask = np.zeros((self._rt.slots,), np.int32)
        toks = np.zeros((self._rt.slots, width), np.int32)
        qlens = np.ones((self._rt.slots,), np.int32)
        lens = np.zeros((self._rt.slots,), np.int32)
        for s, (L, window, _f, source) in plans.items():
            mask[s] = source
            toks[s, :len(window)] = window
            qlens[s] = len(window)
            lens[s] = L
        # the scheduler's own table changes under a turn in flight (a
        # commit, an admission), and a host array may go to the device
        # after the dispatch returns, or stay the device's own on a CPU
        tables = self._page_tables.copy()

        def dispatch():
            if width == 1:
                read = self._rt.decode_launch(tables, lens, toks[:, 0], mask)
                return _Turn(rows, plans,
                             lambda: read()[0].reshape(-1, 1), t0)
            out, _ = self._rt.decode_multi(tables, lens, toks, qlens, mask)
            return _Turn(rows, plans, lambda: out, t0)

        def launch():
            prev, cur = self._inflight, None
            if rows:
                # the dispatch and the array building that belongs to it
                # (a widened turn's tokens come back with its dispatch)
                if _tracer.ACTIVE:
                    with _tracer.span("serve.decode_launch", cat="serve",
                                      args={"active": len(rows)}):
                        cur = dispatch()
                else:
                    cur = dispatch()
                if prev is not None:
                    self._m_ahead.inc()
                    self.lookahead_turns += 1
            due = prev if prev is not None else (None if ahead else cur)
            # a read that raises leaves NO turn in flight: the error
            # takes the turn dispatched after it along
            self._inflight = None
            next_tok = None if due is None else self._read(
                due, lookahead=cur is not None and cur is not due)
            self._inflight = None if cur is due else cur
            return due, next_tok

        if _tracer.ACTIVE:
            # `cached_tokens` marks a span that dispatched: its readers
            # reckon a decode step's bytes from it
            args = {"active": len(rows)}
            if rows:
                args["cached_tokens"] = int(lens.sum())
            with _tracer.span("serve.decode_step", cat="serve", args=args):
                return launch()
        return launch()

    def _offer_prompt_pages(self, s, r):
        """Once a request's prompt positions are fully cached, index its
        FULL prompt pages in the radix cache (the cache takes its own
        reference; chunks another request already cached keep theirs).
        One-shot per admission attempt; a `serve.prefix` fault degrades
        to not caching — the request itself is unaffected."""
        if self._cache is None or r._cache_done:
            return
        psize = self._rt.page_size
        ncache = (len(r.prompt) + 1) // psize   # [BOS] + prompt chunks
        if ncache == 0:
            r._cache_done = True
            return
        if int(self._lens[s]) < ncache * psize:
            return
        r._cache_done = True
        try:
            if _finj.ENABLED:
                _finj.check("serve.prefix",
                            context=f"insert request {r.id}")
        except _finj.FaultInjected:
            self._m_prefix_degraded.inc()
            return
        pages = [int(p) for p in self._page_tables[s, :ncache]]
        self._cache.insert(self._src_key(r), r.known, pages)

    def _degrade_quant(self, req):
        """Run one request through the full-precision fallback (a
        `serve.quant` fault fired at its admission): greedy output is
        IDENTICAL to an fp32 server's, the quantized executables and the
        page pool are never touched for it, and the handle's stream/
        result plumbing behaves normally (tokens arrive in one burst).
        The request's end-to-end deadline stays in force — the remaining
        budget rides into the fallback, and expiry surfaces as the same
        `ServeDeadlineExceeded` the normal path raises."""
        self._m_quant_degraded.inc()
        req.state = "running"
        try:
            toks = self._quant_fallback(req.src, req.prompt,
                                        req.max_new_tokens,
                                        deadline=req.deadline)
        except ServeDeadlineExceeded:
            self._m_deadline.inc()
            self._m_failed.inc()
            req._exc = ServeDeadlineExceeded(
                f"request {req.id} exceeded its deadline (degraded "
                f"full-precision attempt)")
            req._finish("failed", "deadline exceeded")
            return
        except Exception as e:
            self._m_failed.inc()
            req._finish("failed", f"quant degrade failed: {e!r}")
            return
        now = time.perf_counter()
        if toks and req.t_first_token is None:
            req.t_first_token = now
        for tok in toks:
            req._emit(tok)
        self._m_ok.inc()
        self._m_tokens.inc(len(req.tokens))
        self.tokens_generated += len(req.tokens)
        if req.ttft is not None:
            self._m_ttft.observe(req.ttft)
        self._m_latency.observe(time.perf_counter() - req.t_submit)
        req._finish("done")
        if _tracer.ACTIVE:
            _tracer.instant("serve.quant_degraded",
                            args={"id": req.id, "tokens": len(req.tokens)})

    def _release_slot(self, s, r):
        if r._pages:
            self._pool.free(r._pages)
        r._pages = []
        r._slot = None
        r._n_table = 0
        self._slots[s] = None
        self._page_tables[s, :] = NULL_PAGE
        self._lens[s] = 0

    def _evict(self, s, r, state):
        self._release_slot(s, r)
        self._m_ok.inc()
        # token/TTFT metrics land ONCE, at completion — per-step counting
        # would double-report any request a fault or preemption restarted
        self._m_tokens.inc(len(r.tokens))
        self.tokens_generated += len(r.tokens)
        if r.ttft is not None:
            self._m_ttft.observe(r.ttft)
        self._m_latency.observe(time.perf_counter() - r.t_submit)
        r._finish(state)
        if _tracer.ACTIVE:
            _tracer.instant("serve.request_done", args={
                "id": r.id, "tokens": len(r.tokens),
                "ttft_ms": round((r.ttft or 0) * 1e3, 3)})

    def _requeue(self, s, r, why, preempted=False):
        """Restart a request from scratch (pages freed, queued at the
        front); fail it cleanly when the relevant restart budget is
        spent (fault retries and page preemptions count separately). The
        stream restarts too: undelivered chunks from the aborted attempt
        are dropped and TTFT re-arms, so consumers see one clean token
        sequence (tokens a live streamer already pulled before the fault
        are superseded by the retry — inherent to streaming + retry)."""
        self._release_slot(s, r)
        if preempted:
            r.preemptions += 1
            exhausted = r.preemptions > self.max_preemptions
        else:
            r.retries += 1
            exhausted = r.retries > self.max_retries
        r.tokens = []
        r.known = None              # rebuilt (and re-adopted) at admission
        r._cache_done = False
        r.prompt_cached_tokens = 0
        r.t_admit = None
        r.t_first_token = None
        with r._chunk_cv:
            r._chunks.clear()
        if exhausted:
            self._m_failed.inc()
            r._finish("failed", why)
            return False
        r.state = "queued"
        with self._lock:
            self._queue.appendleft(r)
            self._m_queue.set(len(self._queue))
        return True

    def _fail_inflight(self, res, exc, reset_pages):
        """A decode-time fault killed the whole in-flight batch: every
        active request retries from scratch or fails cleanly; page
        accounting returns to baseline either way. A turn in flight that
        can still be read is committed first: what it completed stays
        completed."""
        self._drain("error", res)
        self._m_retries.inc()
        for s, r in enumerate(self._slots):
            if r is not None and self._requeue(s, r,
                                               f"decode fault: {exc!r}"):
                res.retried += 1
        if reset_pages:
            self._rt.reset_pages()
            if self._cache is not None:
                # page CONTENTS are no longer trustworthy — cached
                # prefixes must not be adopted into fresh requests
                self._cache.clear()
        self._m_active.set(self.active_count())
