"""mx.serve — the inference subsystem (ISSUE 6): continuous/inflight
batching over a paged KV cache, one cached decode executable per server.

Pieces (docs/SERVING.md has the full design):

  * `kv_pages.PagePool` — host-side REFCOUNTED allocator over the fixed
    device page pools (page 0 reserved as the null page);
    alloc/share/free/defrag with leak-proof accounting in the metrics
    registry.
  * `prefix_cache.PrefixCache` — content-hashed radix index of full
    prompt pages (ISSUE 12): matching requests adopt cached pages and
    skip that prefill; LRU eviction under page pressure.
  * `speculate.propose_ngram` — the n-gram/prompt-lookup draft proposer
    behind `Server(speculative_k=)`'s widened verify executable.
  * `decode.DecodeRuntime` — the device state + TWO cached executables:
    prefill (pure encoder + cross-attention K/V into a slot, donated
    buffers) and decode (in-place paged K/V writes + ONE shared
    `ragged_paged_attention` launch for all slots, static
    (slots, page_budget) shapes, zero retraces across occupancy); the
    K/V pools are one head-major (H, P, psize, lanes) array a layer,
    the shape the attention kernel reads in place, so a turn copies
    none.
  * `scheduler.Scheduler` — continuous batching: admit into free slots
    every step, evict finished requests immediately, bounded admission
    queue with `ServeOverloaded` backpressure, page-exhaustion
    preemption, `serve.admit`/`serve.decode` fault points with bounded
    retries.
  * `engine_bridge.EngineLoop` — the crank as dependency-engine tasks.
  * `server.Server` — the request-level API: `submit` / `stream` /
    `wait` / `throughput`.
"""
from __future__ import annotations

from . import kv_pages
from . import prefix_cache
from . import speculate
from . import decode
from . import scheduler
from . import engine_bridge
from . import server
from .kv_pages import PagePool, PageAllocError
from .prefix_cache import PrefixCache
from .scheduler import (Request, Scheduler, ServeDeadlineExceeded,
                        ServeError, ServeOverloaded)
from .server import Server

__all__ = ["Server", "Request", "Scheduler", "PagePool", "PageAllocError",
           "PrefixCache", "ServeError", "ServeOverloaded",
           "ServeDeadlineExceeded", "kv_pages", "prefix_cache",
           "speculate", "decode", "scheduler", "engine_bridge", "server"]
