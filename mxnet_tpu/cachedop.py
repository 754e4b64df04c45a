"""CachedOp-style one-executable training step (reference capability:
src/imperative/cached_op.cc — the engine behind `HybridBlock.hybridize` —
extended here to the WHOLE training step, the paper's "lazy graphs lower
to one jitted XLA executable" claim applied end to end).

`Trainer.capture(loss_fn)` (convenience: `mx.jit_step(trainer, loss_fn)`)
returns a `CachedStep` that compiles one full step into ONE jitted XLA
executable:

  * hybridized forward + loss — `loss_fn(*batch)` is traced functionally
    (parameters become program inputs via the same `_TraceContext`
    mechanism HybridBlock uses, so hybridized blocks inline and BatchNorm
    aux updates become extra outputs);
  * the backward via `jax.vjp` of that trace — no tape, no re-trace;
  * in-graph gradient reduction over the 'ici' mesh (the kvstore's
    `graph_allreduce` / `graph_reduce_scatter` lowering replaces the
    host-driven `allreduce_flat` round-trip, so XLA's latency-hiding
    scheduler overlaps the psum with backward compute, arXiv:2301.13062);
  * the AMP unscale + nonfinite/overflow guard as a `lax.cond` (the skip
    branch passes weights/state through untouched);
  * the multi-tensor optimizer update (the same staged numerics as the
    fused bucketed kernel — `multi_tensor.apply_param_update`).

Parameter and optimizer-state buffers are DONATED to the executable, so
Adam-family steps update in place instead of doubling live HBM.

Executables are cached by (batch avals, parameter signature, optimizer
state signature, scale mode, hyperparameters, mesh); per-step values —
lr/wd schedules, loss scale, rescale, the grad.nan poison, the RNG key —
ride in as weak-typed arguments and never retrace. Unsupported
configurations (custom-update optimizers, `update_on_kvstore`, gradient
compression, multi-process 'ici' without a mesh, host syncs inside
`loss_fn`) fall back TRANSPARENTLY to the imperative record/backward/step
path, with the reason recorded on `cachedop_fallbacks{reason=}`.

`sharded_update=True` (arXiv:2004.13336) additionally reduce-scatters
each eligible gradient, updates only this replica's row-shard of the
weight and optimizer state, and all-gathers the new weights inside the
same program; optimizer state stays row-sharded across steps (each
replica only ever touches its shard). Eligible = elementwise update rule
(`Optimizer.elementwise`) and dim 0 divisible by the mesh axis;
ineligible parameters take the replicated psum+update path in the same
executable.

Input interplay (mxnet_tpu/prefetch.py): a batch staged by the device
prefetcher with this step's exact mesh sharding enters the executable
with NO second placement; host batches pay a counted synchronous
transfer (`prefetch_h2d_sync`), and device-committed batches in a
different layout reshard with `cachedop_fallbacks{reason=resharded_input}`.

Reliability interplay (docs/RELIABILITY.md): captured steps still honor
the step watchdog (`MXTPU_STEP_TIMEOUT_MS`) and the `grad.nan` fault
point — the injection multiplies the in-graph gradients by a NaN poison
argument, so the overflow/nonfinite `lax.cond` reflex is chaos-testable
without leaving the executable.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from . import autograd
from . import kvstore as kvs_mod
from . import profiler
from . import random as _random
from .gluon.block import _TraceContext
from .ndarray.ndarray import NDArray
from .observability import tracer as _tracer
from .observability import registry as _obs_registry
from .observability import compilex as _compilex
from .fault import injection as _finj

__all__ = ["CachedStep", "jit_step"]

_reg = _obs_registry()
_hits = _reg.counter("cachedop_cache_hits")
_miss_counters = {}      # reason -> Counter cachedop_cache_misses{reason=}
_fallback_counters = {}  # reason -> Counter cachedop_fallbacks{reason=}

# cache-key layout; positions feed miss-reason classification
_KEY_FIELDS = ("shape_change", "param_change", "state_change", "scale_mode",
               "hyper_change", "autocast", "mesh", "sharded", "grad_reduce",
               "clip", "plan", "sparse", "tiered")


def _mesh_fingerprint(mesh):
    """Structural identity of a mesh for executable cache keys: axis
    names, axis sizes, and the exact device ids in mesh order. Two
    meshes with the same fingerprint produce equal NamedShardings, so a
    step compiled over one runs over the other — which is what lets an
    elastic shrink → grow-back round trip (fault/supervisor.py) reuse
    the pre-shrink executables instead of recompiling (an `id(mesh)`
    key — the pre-PR-18 scheme — could not, since resize always builds
    a fresh Mesh object)."""
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flatten()))


def _miss(reason):
    c = _miss_counters.get(reason)
    if c is None:
        c = _miss_counters[reason] = _reg.counter("cachedop_cache_misses",
                                                  reason=reason)
    c.inc()


def _fallback(reason):
    c = _fallback_counters.get(reason)
    if c is None:
        c = _fallback_counters[reason] = _reg.counter("cachedop_fallbacks",
                                                      reason=reason)
    c.inc()


_sparse_demotions = _reg.counter("cachedop_sparse_demotions")
_demotion_warned = set()    # param names already warned about


def _warn_sparse_demotion(name):
    """A `ShardedEmbedding` table used OUTSIDE its lookup sites (tied
    output projection, a norm over the raw weights, ...) cannot take
    the sparse fast path — the hoisted-table backward would silently
    drop the non-lookup use's gradient. It trains dense instead:
    correct numerics, O(vocab) gradient, and this one-per-name warning
    so the lost memory headline is visible."""
    _sparse_demotions.inc()
    if name in _demotion_warned:
        return
    _demotion_warned.add(name)
    warnings.warn(
        f"ShardedEmbedding table {name!r} is read outside its lookup "
        f"sites (tied projection / raw-weight use); the sparse "
        f"fast path cannot carry that use's gradient, so the table "
        f"trains through the DENSE path (correct, but materialises an "
        f"O(vocab) gradient). Untie the weight or look it up through "
        f"the block to regain the sparse path.", RuntimeWarning,
        stacklevel=3)


def _note_step_failure(exc):
    """Step-failure surfacing for the recovery supervisor: a captured (or
    fallback-imperative) step that DIES mid-flight records what killed it
    — ``cachedop_step_failures{kind=<exception type>}`` plus a trace
    instant — before the exception propagates, so a crash report written
    seconds later attributes the step death even when the raising layer's
    own telemetry was lost with the wedge. Cold path: the registry's
    (name, labels) memo is the handle cache."""
    kind = type(exc).__name__
    _reg.counter("cachedop_step_failures", kind=kind).inc()
    if _tracer.ACTIVE:
        _tracer.instant("cachedop.step_failure", cat="trainer",
                        args={"kind": kind, "error": str(exc)[:200]})


# executables retained per CachedStep; a full jitted step program is heavy
# (variable-length NLP batches would otherwise accumulate one per shape
# forever), so the cache is a bounded LRU like the backward cache's
_CACHE_MAX = 8


class _CaptureUnsupported(Exception):
    """Internal: this call cannot be captured — take the imperative path."""

    def __init__(self, reason):
        self.reason = reason
        super().__init__(reason)


# one aval-signature format shared with the backward cache, so the two
# cache-key layouts cannot drift apart
from .autograd import _aval_sig as _aval  # noqa: E402


def _dev0_view(a):
    """Zero-copy single-device view of a REPLICATED mesh output: shard 0
    holds the full logical value, and a one-device array keeps every
    eager/hybridized consumer (eval forwards, monitors, checkpoints)
    working without caring that the captured step ran on a mesh."""
    try:
        return a.addressable_shards[0].data
    except Exception:
        return a


def _logical_view(a):
    """The value eager code should see for a mesh output: a replicated
    array collapses to its zero-copy device-0 shard view; a genuinely
    SHARDED array (rule-driven FSDP/TP layout) IS its own logical value —
    it stays mesh-resident so the next step pays no re-placement and
    per-device memory stays at the shard size (.asnumpy()/save still see
    the full logical array)."""
    spec = getattr(getattr(a, "sharding", None), "spec", None)
    if spec is not None and any(e is not None for e in tuple(spec)):
        return a
    return _dev0_view(a)


def jit_step(trainer, loss_fn, **kwargs):
    """Convenience for `trainer.capture(loss_fn, **kwargs)`:

        step = mx.jit_step(trainer, lambda x, y: lossf(net(x), y).mean())
        for x, y in batches:
            loss = step(x, y)
    """
    return CachedStep(trainer, loss_fn, **kwargs)


class CachedStep:
    """One captured training step (see module docstring). Calling it runs
    forward + backward + gradient reduction + guard + optimizer update as
    one dispatch and returns `loss_fn`'s output (loss first) as NDArrays.

    `grad_reduce` ('mean', the default, or 'sum') states how the in-graph
    mesh reduction composes with the loss: a batch-MEAN loss needs the
    per-replica gradients averaged over the axis to match the imperative
    whole-batch semantics; a per-sample-SUM loss needs them summed.
    """

    def __init__(self, trainer, loss_fn, sharded_update=False,
                 grad_reduce="mean"):
        if grad_reduce not in ("mean", "sum"):
            raise MXNetError(f"grad_reduce must be 'mean' or 'sum', "
                             f"got {grad_reduce!r}")
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._sharded = bool(sharded_update)
        self._grad_reduce = grad_reduce
        from collections import OrderedDict
        self._cache = OrderedDict()   # LRU: key -> (jfn, meta)
        self._last_key = None
        self._warned = set()
        # mesh captures: ("d"|"n", idx) -> (device-0 view, mesh-resident
        # array); as long as the param still holds the view, the next
        # step reuses the mesh copy instead of re-broadcasting
        self._mesh_cache = {}
        self.last_fallback_reason = None

    def _mesh_resident(self, kind, idx, cur):
        c = self._mesh_cache.get((kind, idx))
        if c is not None and c[0] is cur:
            return c[1]
        return cur

    def _store(self, key, entry):
        while len(self._cache) >= _CACHE_MAX:
            self._cache.popitem(last=False)
        self._cache[key] = entry

    # ------------------------------------------------------------------
    @property
    def cache_size(self):
        return len(self._cache)

    def hlo_info(self):
        """Optimized-HLO counts of the most recently dispatched
        executable (compilex inspection: fusions, collectives, copies,
        donation aliases, module bytes) — None before the first captured
        call or when inspection was skipped by policy. What
        tools/check_fusion.py budgets."""
        entry = self._cache.get(self._last_key)
        if entry is None or entry[0] == "unsupported":
            return None
        return getattr(entry[0], "last_hlo", None)

    @property
    def last_compile_seconds(self):
        """Wall clock of the most recent executable's compiling dispatch
        (measured by compilex BEFORE any HLO-inspection recompile, so it
        is the cost a training loop actually paid) — None if the current
        entry never compiled in this process."""
        entry = self._cache.get(self._last_key)
        if entry is None or entry[0] == "unsupported":
            return None
        return getattr(entry[0], "last_compile_seconds", None)

    def __call__(self, *batch, batch_size=None):
        try:
            if _tracer.ACTIVE:
                with _tracer.span("Trainer.captured_step", cat="trainer",
                                  args={"params": len(self._trainer._params),
                                        "sharded": self._sharded,
                                        "cache_size": len(self._cache)}):
                    return self._call_impl(batch, batch_size)
            return self._call_impl(batch, batch_size)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            _note_step_failure(e)
            raise

    def _call_impl(self, batch, batch_size):
        from . import prefetch as _prefetch_mod
        batch_nd = []
        for b in batch:
            if isinstance(b, NDArray):
                batch_nd.append(b)
                continue
            arr = jnp.asarray(b)
            if not isinstance(b, jax.Array):
                # a HOST batch converted inside the step dispatch is a
                # synchronous critical-path transfer — the device
                # prefetcher (mxnet_tpu/prefetch.py) exists to make this
                # count zero on warm steps
                _prefetch_mod.record_sync_h2d(
                    int(arr.size) * jnp.dtype(arr.dtype).itemsize)
            batch_nd.append(NDArray(arr))
        if batch_size is None:
            if not batch_nd or batch_nd[0].ndim == 0:
                raise MXNetError("capture: pass batch_size= when the first "
                                 "batch argument has no leading batch dim")
            batch_size = int(batch_nd[0].shape[0])
        self.last_fallback_reason = None
        try:
            return self._captured(batch_nd, batch_size)
        except _CaptureUnsupported as e:
            kv = getattr(self._trainer, "_kvstore", None)
            if kv is not None and getattr(kv, "_shard_plan", None) \
                    is not None:
                # with a shard plan the params/optimizer state live
                # SHARDED between steps — the imperative path would mix
                # mesh-resident and host arrays and train garbage, so
                # the fallback is NOT transparent here (fallback matrix:
                # docs/PERFORMANCE.md "Parameter sharding")
                raise MXNetError(
                    f"captured step with a shard plan cannot fall back "
                    f"to the imperative path (reason: {e.reason}); fix "
                    f"the configuration or detach the plan "
                    f"(kvstore.set_mesh) before training imperatively"
                ) from e
            self.last_fallback_reason = e.reason
            _fallback(e.reason)
            if e.reason not in self._warned:
                self._warned.add(e.reason)
                warnings.warn(f"CachedStep: falling back to the imperative "
                              f"path ({e.reason})", RuntimeWarning,
                              stacklevel=3)
            return self._imperative(batch_nd, batch_size)

    # --------------------------------------------------- imperative twin
    def _imperative(self, batch_nd, batch_size):
        """Reference-semantics fallback: record, backward on the (AMP-
        scaled) loss, `Trainer.step`. Same return value as the captured
        path (the RAW loss, not the scaled one)."""
        from . import amp
        for p in self._trainer._params:
            # the imperative path computes dense grads for everything;
            # drop any sparse pair an earlier captured step left behind
            if getattr(p, "_sparse_grad", None) is not None:
                p._sparse_grad = None
        from .shard import moe as _smoe
        with autograd.record():
            with _smoe.capture_scope(None) as moe_tape:
                out = self._loss_fn(*batch_nd)
            leaves, treedef = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            if not leaves or not isinstance(leaves[0], NDArray):
                raise MXNetError("capture: loss_fn must return an NDArray "
                                 "loss (optionally nested with extra "
                                 "outputs, loss leaf first)")
            # MoE load-balancing aux losses join the head exactly like
            # the captured path does (same loss value either way)
            for aux_l in moe_tape.losses:
                leaves[0] = leaves[0] + aux_l
            if moe_tape.losses:
                out = jax.tree_util.tree_unflatten(treedef, leaves)
            sc = amp.scaler()
            head = leaves[0] * sc.loss_scale if sc is not None else leaves[0]
        head.backward()
        self._trainer.step(batch_size)
        return out

    # ------------------------------------------------------ captured path
    def _captured(self, batch_nd, batch_size):
        # the step's host phases are child spans of Trainer.captured_step:
        # step_key here, step_stage / step_launch / step_writeback in
        # _dispatch (a cache miss's build lies between, under no child)
        with _tracer.span("Trainer.step_key", cat="trainer"):
            (key, diff, state_nds, scaler, scale_mode, spec, plan,
             sparse_info, tiered_ks) = self._step_key(batch_nd)
        entry = self._cache.get(key)
        if entry is None:
            _miss(self._miss_reason(key))
            profiler.record_jit_cache(False)
            self._last_key = key
            try:
                entry = self._build(batch_nd, diff, state_nds, scale_mode,
                                    spec, plan, sparse_info, tiered_ks)
            except _CaptureUnsupported as e:
                # negative-cache the failure: later steps with the same
                # signature skip straight to the imperative path instead
                # of re-running the abstract pre-pass every step
                self._store(key, ("unsupported", e.reason))
                raise
            self._store(key, entry)
        elif entry[0] == "unsupported":
            self._cache.move_to_end(key)
            self._last_key = key
            raise _CaptureUnsupported(entry[1])
        else:
            self._cache.move_to_end(key)
            _hits.inc()
            profiler.record_jit_cache(True)
            self._last_key = key
        jfn, meta = entry
        try:
            return self._dispatch(jfn, meta, batch_nd, diff, state_nds,
                                  batch_size, scaler, scale_mode)
        except _CaptureUnsupported as e:
            # a first-dispatch compile failure is as permanent as a build
            # failure: negative-cache it so later steps skip straight to
            # the imperative path
            self._store(key, ("unsupported", e.reason))
            raise

    def _step_key(self, batch_nd):
        """Eligibility checks, optimizer-state lookup and the cache key
        of this call: everything `_captured` needs before the lookup."""
        tr = self._trainer
        opt = tr._optimizer
        from . import amp
        from .optimizer import multi_tensor
        if tr._update_on_kvstore:
            raise _CaptureUnsupported("update_on_kvstore")
        if not multi_tensor.supports(opt):
            raise _CaptureUnsupported("optimizer")
        kv = tr._kvstore
        spec = None
        plan = None
        if kv is not None and kv.type == "ici":
            if kv._compression is not None:
                raise _CaptureUnsupported("compression")
            plan = kv.shard_plan()
            if plan is None:
                spec = kv.capture_spec()
                if spec is None and jax.process_count() > 1:
                    raise _CaptureUnsupported("multiprocess")
        if self._sharded and plan is not None:
            raise MXNetError(
                "sharded_update=True composes with the 1-D replicated "
                "mesh only; a shard plan already shards weights and "
                "optimizer state per-rule — drop sharded_update")
        if self._sharded and spec is None:
            raise MXNetError(
                "sharded_update=True needs an 'ici' kvstore with a "
                "multi-device mesh attached (kvstore.set_mesh)")
        params = tr._params
        if any(p._deferred_init is not None for p in params):
            raise _CaptureUnsupported("deferred_init")
        diff = [(i, p) for i, p in enumerate(params)
                if p.grad_req != "null" and p._data is not None
                and p._grad is not None]
        if not diff:
            raise _CaptureUnsupported("no_grads")
        if spec is not None:
            _, _, n_rep = spec
            for b in batch_nd:
                if b.ndim == 0 or b.shape[0] % n_rep:
                    raise _CaptureUnsupported("batch_not_divisible")
        if plan is not None and jax.process_count() > 1:
            # multi-controller plan sharding would need host batches
            # placed onto non-addressable devices — refuse cleanly here
            # (the no-fallback rule turns this into an MXNetError)
            # instead of dying inside device_put
            raise _CaptureUnsupported("multiprocess")
        # NB under a plan a batch whose dim 0 the data axis does not
        # divide is NOT an error: every such leaf replicates
        # (per-leaf, in the build's batch_sh) and the global-batch loss
        # math is unchanged — a routine end-of-epoch partial batch must
        # degrade (one extra cache entry, no dp parallelism for that
        # step), never abort a run that has no imperative fallback.

        scaler = amp.scaler()
        scale_mode = ("amp" if scaler is not None
                      else "skip" if tr.skip_nonfinite else "none")

        # sparse-embedding fast-path eligibility (ISSUE 15): marked
        # `ShardedEmbedding` tables, row-sharded by their rule over one
        # mesh axis, elementwise optimizer — shard/embedding.py
        from .shard import embedding as _semb
        sparse_info = _semb.sparse_eligibility(plan, diff, opt)

        # tiered tables (ISSUE 19): a converted parameter's live data IS
        # the hot cache — it can only train through the captured sparse
        # path fed by a RowPrefetcher, never imperatively
        tiered_ks = {k: p._tiered_state for k, (i, p) in enumerate(diff)
                     if getattr(p, "_tiered_state", None) is not None}
        if tiered_ks and plan is None:
            names = sorted(diff[k][1].name for k in tiered_ks)
            raise MXNetError(
                f"tiered embedding tables {names} can only train under "
                f"an active shard plan (the live parameter is the hot "
                f"cache, not the logical table); call Trainer.shard "
                f"and capture the step")

        updater = tr._updater
        state_nds = []
        for i, p in diff:
            if i not in updater.states:
                updater.states[i] = opt.create_state_multi_precision(
                    i, p.data())
            st = updater.states[i]
            st = st if isinstance(st, tuple) else \
                ((st,) if st is not None else ())
            state_nds.append(st)

        key = (
            tuple(_aval(b._data) for b in batch_nd),
            tuple(p._struct_sig() for p in params),
            tuple(tuple(_aval(s._data) for s in sv) for sv in state_nds),
            scale_mode,
            multi_tensor._hyper_sig(opt),
            str(amp.autocast_dtype()),
            None if spec is None else (_mesh_fingerprint(spec[0]),
                                       spec[1], spec[2]),
            self._sharded,
            self._grad_reduce,
            None if opt.clip_gradient is None else float(opt.clip_gradient),
            None if plan is None else plan.signature(),
            tuple(sorted((k, v["axis"]) for k, v in sparse_info.items())),
            tuple(sorted(tiered_ks)),
        )
        return (key, diff, state_nds, scaler, scale_mode, spec, plan,
                sparse_info, tiered_ks)

    def _miss_reason(self, key):
        last = self._last_key
        if last is None:
            return "first"
        for name, a, b in zip(_KEY_FIELDS, key, last):
            if a != b:
                return name
        return "other"

    # ------------------------------------------------------------ build
    def _build(self, batch_nd, diff, state_nds, scale_mode, spec,
               plan=None, sparse_info=None, tiered_ks=None):
        tr = self._trainer
        opt = tr._optimizer
        kv = tr._kvstore
        from .optimizer import multi_tensor as _mt
        from .optimizer.multi_tensor import apply_param_update
        from jax import shard_map
        from .shard import embedding as _semb
        from .shard import moe as _smoe
        from jax.sharding import PartitionSpec as P
        sparse_info = sparse_info or {}
        tiered_ks = tiered_ks or {}

        diff_ids = {id(p) for _, p in diff}
        diff_params = [p for _, p in diff]
        nondiff = [p for p in tr._params
                   if p._data is not None and id(p) not in diff_ids]
        guard = scale_mode != "none"
        unscale = scale_mode == "amp"
        clip = None if opt.clip_gradient is None else float(opt.clip_gradient)
        mp_flags = [bool(opt.multi_precision
                         and p.data()._data.dtype != np.float32)
                    for _, p in diff]
        n_diff = len(diff)
        mean = self._grad_reduce == "mean"
        mesh = axis = None
        n_rep = 1
        if spec is not None:
            mesh, axis, n_rep = spec

        # per-param sharded-update eligibility (arXiv:2004.13336);
        # irrelevant under a shard plan (rules own the layout there)
        shard_ok = []
        for (i, p), sv in zip(diff, state_nds):
            w = p.data()._data
            shard_ok.append(bool(
                self._sharded and type(opt).elementwise and w.ndim >= 1
                and w.shape[0] >= n_rep and w.shape[0] % n_rep == 0
                and all(s._data.shape == w.shape or s._data.ndim == 0
                        for s in sv)))

        # rule-resolved per-parameter specs (the GSPMD-lowered path):
        # grads are pinned to the weight's layout IN-GRAPH so they
        # materialise already reduce-scattered (kvstore.graph_constrain)
        plan_specs = None
        if plan is not None:
            plan_specs = [plan.spec_for(p.name, p.data()._data.shape)
                          for _, p in diff]

        loss_fn = self._loss_fn
        meta = {"treedef": None, "n_out": 0, "aux": [], "nondiff": nondiff}

        def traced(rng, diff_vals, nondiff_vals, batch_vals):
            """Functional run of loss_fn: every trainer parameter reads its
            traced value, layer RNG flows from `rng`, aux updates (BN
            running stats) are captured as outputs."""
            nd_list = meta["nondiff"]
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(True)
            try:
                with _TraceContext(rng) as tctx, \
                        _smoe.capture_scope(plan) as moe_tape:
                    for p, v in zip(diff_params, diff_vals):
                        p._trace_override = NDArray(v)
                    for p, v in zip(nd_list, nondiff_vals):
                        p._trace_override = NDArray(v)
                    out = loss_fn(*[NDArray(v) for v in batch_vals])
                    leaves, treedef = jax.tree_util.tree_flatten(
                        out, is_leaf=lambda x: isinstance(x, NDArray))
                    if not leaves or not all(isinstance(l, NDArray)
                                             for l in leaves):
                        raise MXNetError(
                            "capture: loss_fn must return NDArray(s), "
                            "loss leaf first")
                    # MoE aux losses (load balancing) join the loss
                    # head HERE, inside the trace — so they are part of
                    # the differentiated program and their gradient
                    # drives the router (shard/moe.py)
                    head = leaves[0]
                    for aux_l in moe_tape.losses:
                        head = head + aux_l
                    meta["treedef"] = treedef
                    meta["n_out"] = len(leaves)
                    meta["aux"] = [p for p, _ in tctx.aux_updates]
                    meta["moe_sites"] = list(moe_tape.sites)
                    return ([head._data] +
                            [l._data for l in leaves[1:]],
                            [v._data if isinstance(v, NDArray) else v
                             for _, v in tctx.aux_updates])
            finally:
                for p in diff_params:
                    p._trace_override = None
                for p in nd_list:
                    p._trace_override = None
                autograd.set_recording(prev_rec)
                autograd.set_training(prev_train)

        # abstract pre-pass: (a) surface trace errors (host syncs inside
        # loss_fn) as a clean fallback, (b) discover the aux-update set so
        # aux params NOT already program inputs become ones (else their
        # values would bake in as compile-time constants)
        rng0 = _random._next_key()
        dvals = [p.data()._data for p in diff_params]
        bvals = [b._data for b in batch_nd]

        from .gluon import parameter as _param_mod

        def _prepass():
            # the watch collects Parameters whose CONCRETE data the trace
            # reads (no override installed): non-trainer params a
            # fine-tuning loss_fn touches — left alone they would bake in
            # as compile-time constants and go stale on set_data()
            watch = set()
            prev = _param_mod._capture_watch
            _param_mod._capture_watch = watch
            try:
                nvals0 = [p._data._data for p in meta["nondiff"]]
                jax.eval_shape(traced, rng0, dvals, nvals0, bvals)
            finally:
                _param_mod._capture_watch = prev
            return watch

        try:
            for _ in range(3):   # promotion closes after one extra pass
                watch = _prepass()
                known = set(diff_ids)
                known.update(id(p) for p in meta["nondiff"])
                promote = [p for p in watch
                           if p._data is not None and id(p) not in known]
                promote += [p for p in meta["aux"]
                            if id(p) not in known
                            and all(p is not q for q in promote)]
                if not promote:
                    break
                meta["nondiff"] = meta["nondiff"] + promote
        except MXNetError:
            raise
        except _CaptureUnsupported:
            raise
        except Exception as e:
            raise _CaptureUnsupported(
                f"trace_error:{type(e).__name__}") from e
        if mesh is not None and meta["n_out"] != 1:
            # extra outputs have no canonical cross-replica layout
            raise _CaptureUnsupported("extra_outputs_mesh")
        nondiff = meta["nondiff"]
        pos_of = {id(p): j for j, p in enumerate(nondiff)}
        meta["aux_pos"] = [pos_of.get(id(p)) for p in meta["aux"]]

        # sparse-embedding site discovery (ISSUE 15): one more abstract
        # pass with the RECORD context installed tells us which eligible
        # tables the model actually looks up and with what index shapes
        # — the out_shardings pytree below needs that before tracing.
        # An eligible table with no lookup site trains dense (zero grad).
        # The pass traces to a JAXPR with the diff values as the
        # arguments: record-mode lookups never touch the table value, so
        # a table whose argument is still REFERENCED anywhere has a
        # NON-lookup use (a tied output projection, a norm over the raw
        # weights, ...). Its cotangent could not ride the sparse row
        # block — the fast path would silently drop that use's gradient
        # — so such a table DEMOTES to the dense path (correct numerics,
        # dense O(vocab) gradient), loudly.
        sparse_live = {}
        if sparse_info:
            rec = _semb.SparseLookupContext(
                "record", [id(diff_params[k]) for k in sparse_info])
            try:
                with rec:
                    nvals0 = [p._data._data for p in meta["nondiff"]]
                    closed = jax.make_jaxpr(
                        lambda dv: traced(rng0, dv, nvals0, bvals))(
                        dvals)
            except MXNetError:
                raise
            except Exception as e:
                raise _CaptureUnsupported(
                    f"trace_error:{type(e).__name__}") from e
            # every reference to a top-level arg appears in some eqn's
            # (or the output's) invars — call-style primitives receive
            # outer vars at their call site, so no recursion is needed.
            # A pass-through into a sub-jaxpr counts as a use: that can
            # only demote (dense = always-correct), never miss a use.
            referenced = set()
            for eqn in closed.jaxpr.eqns:
                referenced.update(id(v) for v in eqn.invars)
            referenced.update(id(v) for v in closed.jaxpr.outvars)
            for k, info in sparse_info.items():
                sites = rec.sites.get(id(diff_params[k]))
                if not sites:
                    continue
                if id(closed.jaxpr.invars[k]) in referenced:
                    _warn_sparse_demotion(diff_params[k].name)
                    continue
                shapes = [tuple(int(d) for d in s.shape) for s in sites]
                n_flat = sum(
                    int(np.prod(shp, dtype=np.int64)) if shp else 1
                    for shp in shapes)
                sparse_live[k] = dict(info, site_shapes=shapes,
                                      n_flat=n_flat)
        live_ks = sorted(sparse_live)
        dense_ks = [k for k in range(n_diff) if k not in sparse_live]

        # tiered hot caches (ISSUE 19) are hard-wired to the sparse fast
        # path: a tiered table that fell off it (demoted by a direct
        # table reference, tied weights, or no recorded lookup) cannot
        # train — the dense path would read the cache as if it were the
        # logical table. Loud, no fallback.
        tiered_live = sorted(tiered_ks)
        for k in tiered_live:
            if k not in sparse_live:
                raise MXNetError(
                    f"tiered embedding {diff_params[k].name!r} did not "
                    f"take the sparse fast path this step (demoted by a "
                    f"direct table reference, or the table was never "
                    f"looked up) — a tiered table trains only through "
                    f"the sparse lookup; remove direct uses of the "
                    f"weight from loss_fn")
        if tiered_live:
            meta["tiered"] = [
                (k, int(sparse_live[k]["n_flat"]),
                 2 + sum(bool(b) for b in tiered_ks[k].row_like))
                for k in tiered_live]

        def program(batch_vals, diff_vals, nondiff_vals, state_vals, rng,
                    lrs, wds, rescale, inv_scale, loss_scale, poison,
                    tiered_vals=()):
            if tiered_vals:
                # scatter the prefetcher's staged cold rows into their
                # slots FIRST — the record pass, lookup, and scatter-add
                # update below all see the filled cache. Sentinel slot
                # ids (== n_slots) drop; an all-hit step scatters an
                # all-sentinel block (pure device no-op after fusion).
                diff_vals = list(diff_vals)
                state_vals = [list(sv) for sv in state_vals]
                off = 0
                for k in tiered_live:
                    ts = tiered_ks[k]
                    ax = sparse_live[k]["axis"]
                    inc_slots = tiered_vals[off]
                    inc_rows = tiered_vals[off + 1]
                    off += 2
                    diff_vals[k] = _semb.scatter_rows(
                        diff_vals[k], inc_slots, inc_rows, plan.mesh, ax)
                    for j, rl in enumerate(ts.row_like):
                        if not rl:
                            continue
                        state_vals[k][j] = _semb.scatter_rows(
                            state_vals[k][j], inc_slots,
                            tiered_vals[off], plan.mesh, ax)
                        off += 1
            se = {}
            if sparse_live:
                # discovery pass with CONCRETE tracers: record each
                # lookup site's index value. Only the recorded index
                # extraction survives DCE — the rest of this forward is
                # dead (its outputs are unused).
                rec = _semb.SparseLookupContext(
                    "record", [id(diff_params[k]) for k in live_ks])
                with rec:
                    traced(rng, diff_vals, nondiff_vals, batch_vals)
                for k in live_ks:
                    info = sparse_live[k]
                    sites = rec.sites[id(diff_params[k])]
                    flats = [s.reshape(-1).astype(jnp.int32)
                             for s in sites]
                    flat = jnp.concatenate(flats) if len(flats) > 1 \
                        else flats[0]
                    # dedup: each distinct row crosses the interconnect
                    # once per step; the sentinel (vocab) is out of
                    # range on every shard, so scatters drop pad slots
                    uniq, inv = jnp.unique(
                        flat, size=int(flat.shape[0]),
                        fill_value=info["vocab"], return_inverse=True)
                    inv = inv.reshape(-1).astype(jnp.int32)
                    rows = _semb.gather_rows(diff_vals[k], uniq,
                                             plan.mesh, info["axis"])
                    segs, off = [], 0
                    for shp in info["site_shapes"]:
                        segs.append((off, shp))
                        off += int(np.prod(shp, dtype=np.int64)) \
                            if shp else 1
                    se[k] = [uniq, inv, rows, segs]

            def run_traced(dv_full, consume_rows=None):
                if not sparse_live:
                    return traced(rng, dv_full, nondiff_vals, batch_vals)
                cctx = _semb.SparseLookupContext(
                    "consume", [id(diff_params[k]) for k in live_ks])
                for k, r in zip(live_ks, consume_rows):
                    uniq, inv, _, segs = se[k]
                    cctx.set_rows(diff_params[k], r, inv, segs)
                with cctx:
                    return traced(rng, dv_full, nondiff_vals, batch_vals)

            if sparse_live:
                # the tables are HOISTED OUT of the vjp: the gathered
                # (U, D) row blocks are the differentiable inputs, so
                # the backward materialises a dense-of-touched block +
                # indices, never an O(vocab) gradient
                def fwd(dv_dense, rows_list):
                    full = list(diff_vals)
                    for k, v in zip(dense_ks, dv_dense):
                        full[k] = v
                    leaves, aux = run_traced(full, rows_list)
                    return leaves[0], (leaves[1:], aux)

                head, vjp_fn, (extra, aux_vals) = jax.vjp(
                    fwd, [diff_vals[k] for k in dense_ks],
                    [se[k][2] for k in live_ks], has_aux=True)
                cot = jnp.ones_like(head) * jnp.asarray(loss_scale,
                                                        head.dtype)
                g_dense, g_rows_list = vjp_fn(cot)
                grads = [None] * n_diff
                for k, g in zip(dense_ks, g_dense):
                    grads[k] = g * poison
                g_rows = {k: g * poison
                          for k, g in zip(live_ks, g_rows_list)}
            else:
                def fwd(dv):
                    leaves, aux = traced(rng, dv, nondiff_vals,
                                         batch_vals)
                    return leaves[0], (leaves[1:], aux)

                head, vjp_fn, (extra, aux_vals) = jax.vjp(
                    fwd, diff_vals, has_aux=True)
                cot = jnp.ones_like(head) * jnp.asarray(loss_scale,
                                                        head.dtype)
                grads = list(vjp_fn(cot)[0])
                # grad.nan fault point: poison is 1.0 unless the
                # injection schedule fired this step (then NaN) — same
                # reflex test as the imperative trainer's gradient
                # poisoning, in-graph
                grads = [g * poison for g in grads]
                g_rows = {}

            if plan_specs is not None:
                # rule-driven layout: no explicit psum — the loss is
                # computed over the GLOBAL batch, so the dp reduction is
                # already part of the backward; the constraint makes each
                # gradient land reduce-scattered into its weight's layout
                # (sparse-path tables have no dense gradient to constrain)
                grads = [g if g is None else kv.graph_constrain(g, ps)
                         for g, ps in zip(grads, plan_specs)]

            if mesh is not None:
                grads = [
                    kv.graph_reduce_scatter(g, axis, n_rep, mean=mean)
                    if sh else kv.graph_allreduce(g, axis, n_rep, mean=mean)
                    for g, sh in zip(grads, shard_ok)]
                head = kv.graph_allreduce(head, axis, n_rep, mean=mean)
                aux_vals = [kv.graph_allreduce(v, axis, n_rep, mean=True)
                            for v in aux_vals]

            # local (shard) views of weights; states arrive pre-sharded
            # through their in_specs
            w_locals, sv_locals = [], []
            for k in range(n_diff):
                w = diff_vals[k]
                sv = tuple(state_vals[k])
                if shard_ok[k]:
                    chunk = w.shape[0] // n_rep
                    ridx = jax.lax.axis_index(axis)
                    w = jax.lax.dynamic_slice_in_dim(w, ridx * chunk,
                                                     chunk, 0)
                w_locals.append(w)
                sv_locals.append(sv)

            flag = jnp.zeros((), jnp.int32)
            if guard:
                shard_cnt = sum(
                    (jnp.sum(~jnp.isfinite(g.astype(jnp.float32)),
                             dtype=jnp.int32)
                     for g, sh in zip(grads, shard_ok) if sh),
                    jnp.zeros((), jnp.int32))
                repl_cnt = sum(
                    (jnp.sum(~jnp.isfinite(g.astype(jnp.float32)),
                             dtype=jnp.int32)
                     for g, sh in zip(grads, shard_ok)
                     if not sh and g is not None),
                    jnp.zeros((), jnp.int32))
                # sparse rows count into the same reflex: a nonfinite
                # touched-row gradient skips the whole update
                repl_cnt = repl_cnt + sum(
                    (jnp.sum(~jnp.isfinite(g.astype(jnp.float32)),
                             dtype=jnp.int32) for g in g_rows.values()),
                    jnp.zeros((), jnp.int32))
                if mesh is not None and any(shard_ok):
                    shard_cnt = kv.graph_allreduce(shard_cnt, axis, n_rep)
                flag = ((shard_cnt + repl_cnt) > 0).astype(jnp.int32)

            def _sparse_out_g(k):
                og = g_rows[k] * inv_scale if unscale else g_rows[k]
                return (se[k][0], og)

            def do_update(_):
                nws, nss, ogs = [], [], []
                for k in range(n_diff):
                    if k in sparse_live:
                        # scatter-add arm (ISSUE 15): touched rows are
                        # gathered, staged through the exact multi-
                        # tensor numerics, and written back on the
                        # OWNING shard only — the donated table/state
                        # buffers update in place, untouched rows never
                        # move (lazy/sparse-update semantics)
                        uniq = se[k][0]

                        def stage(w_r, g_r, sv_r, _k=k):
                            nw, ns, _ = _mt.sparse_update_rows(
                                opt, w_r, g_r, sv_r, lrs[_k], wds[_k],
                                mp_flags[_k], clip, rescale,
                                inv_scale if unscale else None)
                            return nw, ns

                        nw, ns = _semb.sparse_row_update(
                            w_locals[k], sv_locals[k], uniq, g_rows[k],
                            plan.mesh, sparse_live[k]["axis"], stage)
                        nws.append(nw)
                        nss.append(ns)
                        ogs.append(_sparse_out_g(k))
                        continue
                    nw, ns, og = apply_param_update(
                        opt, w_locals[k], grads[k], sv_locals[k],
                        lrs[k], wds[k], mp_flags[k], clip, rescale,
                        inv_scale if unscale else None)
                    nws.append(nw)
                    nss.append(ns)
                    ogs.append(og if og is not None else grads[k])
                return tuple(nws), tuple(nss), tuple(ogs)

            def skip_update(_):
                # grads still end unscaled on the skip path (per-param
                # path parity: amp.unscale runs before the skip)
                ogs = tuple(
                    _sparse_out_g(k) if k in sparse_live
                    else (grads[k] * inv_scale if unscale else grads[k])
                    for k in range(n_diff))
                return (tuple(w_locals),
                        tuple(tuple(sv) for sv in sv_locals), ogs)

            # a named jitted function (XLA inlines the call), so that the
            # optimizer's ops carry `jit(mx_update)` in their `op_name`
            # and the name is in the compile cache's key: compilex
            # `op_scopes` says why a `jax.named_scope` would not do, and
            # maps the device's ops to it for `update_share_pct`
            def mx_update():
                if guard:
                    return jax.lax.cond(flag > 0, skip_update, do_update,
                                        None)
                return do_update(None)

            new_ws, new_ss, out_gs = jax.jit(mx_update)()

            if mesh is not None and any(shard_ok):
                # sharded params: all-gather the new weights IN-PROGRAM;
                # states and grads stay row-sharded (out_specs P(axis))
                new_ws = tuple(
                    kv.graph_all_gather(w, axis) if sh else w
                    for w, sh in zip(new_ws, shard_ok))
            return ([head] + list(extra), list(aux_vals), list(new_ws),
                    [tuple(sv) for sv in new_ss], list(out_gs), flag)

        jit_kwargs = {}
        if mesh is None and plan is None:
            fn = program
        elif plan is not None:
            # Rule-driven GSPMD lowering: the program itself contains no
            # explicit collectives — inputs arrive committed to their
            # per-rule NamedShardings (dispatch places them once;
            # thereafter a no-op), out_shardings pin params/state/grads
            # to the SAME layouts so donation reuses the sharded buffers
            # in place, and the partitioner inserts the FSDP
            # gather-before-use / reduce-scatter-after-backward and TP
            # collectives the specs imply.
            from jax.sharding import NamedSharding
            from .ops.pallas_kernels import kernel_mesh
            pmesh = plan.mesh
            # the partitioner cannot split a Mosaic kernel: the Pallas
            # calls inside run per shard (batch over the data axis,
            # heads over the one other axis of a 2-D mesh)
            others = [a for a in pmesh.axis_names if a != plan.data_axis]
            head_axis = others[0] if len(others) == 1 else None

            def fn(*args):
                with kernel_mesh(pmesh, plan.data_axis, head_axis):
                    return program(*args)

            repl = NamedSharding(pmesh, P())
            n_dp = int(pmesh.shape[plan.data_axis])
            bsh = plan.batch_sharding()

            def batch_sh(b):
                if b.ndim >= 1 and b.shape[0] % n_dp == 0:
                    return bsh
                return repl

            diff_sh = [NamedSharding(pmesh, ps) for ps in plan_specs]
            nondiff_sh = [plan.sharding(p.name, p._data._data.shape)
                          for p in nondiff]
            state_sh = []
            for (i, p), sv in zip(diff, state_nds):
                w_shape = p.data()._data.shape
                state_sh.append(tuple(
                    NamedSharding(pmesh, plan.state_spec(
                        p.name, w_shape, s._data.shape)) for s in sv))
            aux_sh = [plan.sharding(p.name, p._data._data.shape)
                      for p in meta["aux"]]
            # grads: dense params land in their weight's layout; a
            # sparse-path table's "gradient" is the (unique_ids, rows)
            # pair — replicated, O(touched), never O(vocab)
            grad_sh = [(repl, repl) if k in sparse_live else diff_sh[k]
                       for k in range(len(diff_sh))]
            jit_kwargs["out_shardings"] = (
                [repl] * meta["n_out"],      # loss leaves: replicated
                aux_sh,
                diff_sh,                     # new weights keep their rule
                state_sh,                    # state stays sharded
                grad_sh,
                repl,                        # guard flag
            )
            meta["shardings"] = (
                [batch_sh(b) for b in batch_nd],
                diff_sh, nondiff_sh, state_sh, repl,
            )
            # per-spec collective accounting: gradient bytes entering the
            # cross-replica reduction, attributed to the layout that rule
            # produced (kv_collective_bytes{op=spmd_grad_reduce,spec=});
            # sparse tables account their all-to-all payloads instead —
            # per step per table: one (shards, U) int32 index exchange
            # plus one (shards, U, D) vector return
            per_spec = {}
            for k, ((i, p), ps) in enumerate(zip(diff, plan_specs)):
                if k in sparse_live:
                    continue
                g = p._grad._data
                nbytes = int(g.size) * jnp.dtype(g.dtype).itemsize
                per_spec[str(ps)] = per_spec.get(str(ps), 0) + nbytes
            meta["coll_specs"] = sorted(per_spec.items())
            embed_bytes = 0
            for k, info in sparse_live.items():
                n_sh = int(pmesh.shape[info["axis"]])
                itemsize = jnp.dtype(
                    diff[k][1].data()._data.dtype).itemsize
                embed_bytes += n_sh * info["n_flat"] * (
                    4 + info["dim"] * itemsize)
            meta["embed_bytes"] = embed_bytes
        else:
            def state_spec(k, sv):
                return tuple(
                    P(axis) if shard_ok[k] and s._data.ndim != 0 else P()
                    for s in sv)

            in_specs = (
                [P(axis)] * len(batch_nd),
                [P()] * n_diff,
                [P()] * len(nondiff),
                [state_spec(k, sv) for k, sv in enumerate(state_nds)],
                P(),
                tuple(P() for _ in range(n_diff)),
                tuple(P() for _ in range(n_diff)),
                P(), P(), P(), P(),
            )
            out_specs = (
                [P()],                                   # head (reduced)
                [P()] * len(meta["aux"]),                # aux (pmean'd)
                [P()] * n_diff,                          # new weights
                [state_spec(k, sv) for k, sv in enumerate(state_nds)],
                [P(axis) if sh else P() for sh in shard_ok],   # grads
                P(),                                     # guard flag
            )
            fn = shard_map(program, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
            # imperative arrays are committed to one device; resharding
            # onto the mesh must be explicit (jit refuses to guess).
            # device_put is a no-op once an array already carries the
            # right sharding — params/state pay it on the first step only.
            from jax.sharding import NamedSharding
            repl = NamedSharding(mesh, P())
            meta["shardings"] = (
                [NamedSharding(mesh, P(axis)) for _ in batch_nd],
                [repl] * n_diff,
                [repl] * len(nondiff),
                [tuple(NamedSharding(mesh, s)
                       for s in state_spec(k, sv))
                 for k, sv in enumerate(state_nds)],
                repl,
            )

        # MoE routing sites the trace reported (shard/moe.py tape): the
        # sharded ones carry their static a2a byte cost; a site that
        # fell back to local dispatch carries bytes=0 plus its reason —
        # loud accounting, the demotion-not-silent discipline
        moe_sites = meta.get("moe_sites") or []
        moe_live = plan is not None and any(s["sharded"]
                                            for s in moe_sites)
        meta["moe_bytes"] = sum(s.get("bytes", 0) for s in moe_sites)

        # compile observatory (observability/compilex.py): the captured
        # step's compiles/HLO structure publish under the executable name
        # check_fusion budgets — "sharded_embed_step" when the sparse
        # embedding fast path is live (its all-to-all count is pinned),
        # "moe_step" when expert-parallel MoE routing is live under a
        # plan (its all-to-all count is pinned too; a model with BOTH
        # sparse tables and MoE keeps the embed name — the sparse path
        # restructures the program, MoE only adds in-graph collectives),
        # "sharded_step" when a rule plan owns the layout,
        # "captured_step" otherwise (single-device or 1-D mesh)
        exe_name = ("sharded_embed_step" if sparse_live
                    else "moe_step" if moe_live
                    else "sharded_step" if plan is not None
                    else "captured_step")
        # autotune (ISSUE 20): the shard-plan signature versions any
        # stored compile-space winner (a winner tuned under one layout
        # is stale under another, tune_stale{reason=plan}), and the
        # training step's numerics contract is the documented fp
        # tolerance — optimisation may re-associate, not drift
        from . import tune as _tune
        _tune.note_plan(exe_name,
                        None if plan is None else str(plan.signature()))
        _tune.register_contract(exe_name, "allclose", rtol=1e-5,
                                atol=1e-7)
        jfn = _compilex.instrument(
            jax.jit(fn, donate_argnums=(1, 3), **jit_kwargs), exe_name)
        meta.update({
            "fresh": True,     # first dispatch compiles: scope the CPU
                               # donation-noop warning to that call only
            "guard": guard,
            "unscale": unscale,
            "shard_ok": shard_ok,
            "mesh": spec,
            "plan": plan is not None,
            "sparse": sorted(sparse_live),
            "coll_bytes": 0 if mesh is None else sum(
                int(p._grad._data.size)
                * jnp.dtype(p._grad._data.dtype).itemsize
                for _, p in diff),
            "coll_op": ("in_graph_reduce_scatter"
                        if any(shard_ok) else "in_graph_psum"),
        })
        return jfn, meta

    # --------------------------------------------------------- dispatch
    def _dispatch(self, jfn, meta, batch_nd, diff, state_nds, batch_size,
                  scaler, scale_mode):
        with _tracer.span("Trainer.step_stage", cat="trainer"):
            args, snapshot = self._stage(meta, batch_nd, diff, state_nds,
                                         batch_size, scaler)
        fresh = meta.pop("fresh", False)
        try:
            with _tracer.span("Trainer.step_launch", cat="trainer"):
                if fresh:
                    # buffer donation is a no-op on CPU test meshes; jax
                    # warns at compile time — suppress it HERE, not
                    # process-wide
                    with warnings.catch_warnings():
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not")
                        out = jfn(*args)
                else:
                    out = jfn(*args)
        except Exception as e:
            # no update ran: un-bump the optimistic update counts so lr
            # schedules stay aligned with what was actually applied
            self._restore_update_counts(snapshot)
            # donation hazard: if the program EXECUTED far enough to
            # consume its donated inputs before failing, the param/state
            # buffers are gone — falling back would read deleted arrays
            # and silently train garbage. Only a failure that left every
            # donated buffer alive (trace/compile-stage errors) may take
            # the transparent imperative fallback.
            donated_dead = any(
                getattr(a, "is_deleted", lambda: False)()
                for group in (args[1], args[3])      # diff, state values
                for leaf in group
                for a in (leaf if isinstance(leaf, tuple) else (leaf,)))
            if donated_dead:
                raise MXNetError(
                    "captured step failed AFTER its donated parameter/"
                    "state buffers were consumed — model state is lost; "
                    "restore from a checkpoint (see docs/PERFORMANCE.md "
                    f"donation rules). Cause: {type(e).__name__}: {e}"
                ) from e
            if fresh and not isinstance(e, _CaptureUnsupported):
                # first call = trace/compile of the backward+update stages
                # (the forward-only prepass cannot see those): treat like
                # any other capture failure — transparent fallback
                raise _CaptureUnsupported(
                    f"compile_error:{type(e).__name__}") from e
            raise
        with _tracer.span("Trainer.step_writeback", cat="trainer"):
            return self._writeback(meta, diff, state_nds, out, snapshot,
                                   scaler, scale_mode)

    def _restore_update_counts(self, snapshot):
        opt = self._trainer._optimizer
        opt.num_update, counts = snapshot
        for i, c in counts.items():
            if c is None:
                opt._index_update_count.pop(i, None)
            else:
                opt._index_update_count[i] = c

    def _stage(self, meta, batch_nd, diff, state_nds, batch_size, scaler):
        """Everything between the cache hit and the launch: update
        counts, the per-step scalars, the rng key, value lists and their
        placement. Returns (the program's arguments, the update-count
        snapshot a skipped or failed step rolls back to)."""
        tr = self._trainer
        opt = tr._optimizer
        tr._optimizer.rescale_grad = tr._scale / batch_size
        # optimistic update-count bump (the skip branch rolls it back, so
        # lr schedules see exactly what the imperative skip leaves behind)
        snapshot = (opt.num_update,
                    {i: opt._index_update_count.get(i) for i, _ in diff})
        for i, _ in diff:
            opt._update_count(i)
        lrs = tuple(float(opt._get_lr(i)) for i, _ in diff)
        wds = tuple(float(opt._get_wd(i)) for i, _ in diff)
        rescale = float(opt.rescale_grad)
        inv_scale = 0.0 if scaler is None else 1.0 / float(scaler.loss_scale)
        loss_scale = 1.0 if scaler is None else float(scaler.loss_scale)
        poison = (float("nan")
                  if _finj.ENABLED and _finj.should_fire("grad.nan")
                  else 1.0)
        rng = _random._next_key()

        profiler.record_dispatch("captured_step")
        if meta["coll_bytes"]:
            kvs_mod._count_collective(meta["coll_op"], meta["coll_bytes"])
        for spec_str, nbytes in meta.get("coll_specs", ()):
            kvs_mod._count_collective("spmd_grad_reduce", nbytes,
                                      spec=spec_str)
        if meta.get("embed_bytes"):
            # the hot-path currency of the sharded-embedding workload:
            # bytes the bucketed index/vector all-to-alls move per step
            kvs_mod._count_collective("embed_all_to_all",
                                      meta["embed_bytes"])
        if meta.get("moe_bytes"):
            # same currency for expert parallelism: bytes the MoE
            # dispatch/combine all-to-alls move per step (forward pair,
            # shard/moe.py a2a_bytes_per_step convention)
            kvs_mod._count_collective("moe_all_to_all",
                                      meta["moe_bytes"])
        batch_vals = [b._data for b in batch_nd]
        diff_vals = [self._mesh_resident("d", i, p.data()._data)
                     for i, p in diff]
        nondiff_vals = [self._mesh_resident("n", j, p._data._data)
                        for j, p in enumerate(meta["nondiff"])]
        state_vals = [tuple(s._data for s in sv) for sv in state_nds]
        sh = meta.get("shardings")
        if sh is not None:
            from . import prefetch as _prefetch_mod
            # Batch placement: a device-prefetched batch already carries
            # the step's exact NamedSharding — use it as-is (zero-copy,
            # no critical-path H2D). Anything else pays a synchronous
            # per-step placement here (counted, so check_dispatch can
            # assert zero with the prefetcher active); a batch that is
            # device-COMMITTED but in a different layout additionally
            # records cachedop_fallbacks{reason=resharded_input} — the
            # producer staged it, just not where this step runs.
            staged = []
            for v, tgt in zip(batch_vals, sh[0]):
                if getattr(v, "sharding", None) == tgt:
                    staged.append(v)
                    continue
                if getattr(v, "committed", False):
                    _fallback("resharded_input")
                _prefetch_mod.record_sync_h2d(
                    int(v.size) * jnp.dtype(v.dtype).itemsize)
                staged.append(jax.device_put(v, tgt))
            batch_vals = staged
            # params/state/rng: no-ops once mesh-resident (first step only)
            diff_vals, nondiff_vals, state_vals, rng = jax.device_put(
                (diff_vals, nondiff_vals, state_vals, rng),
                (sh[1], sh[2], sh[3], sh[4]))
            # frozen nondiff params broadcast onto the mesh ONCE: remember
            # the mesh-resident copy so later steps skip the transfer
            for j, p in enumerate(meta["nondiff"]):
                self._mesh_cache[("n", j)] = (p._data._data,
                                              nondiff_vals[j])
        args = (batch_vals, diff_vals, nondiff_vals, state_vals,
                rng, lrs, wds, rescale, inv_scale, loss_scale, poison)
        if meta.get("tiered"):
            # consume the RowPrefetcher's staged cold-row plan for this
            # step (already committed replicated on the mesh — passing
            # it costs no placement here). The contract is strict
            # depth-1: exactly one planned batch per dispatch.
            tiered_vals = []
            for k, n_flat, n_blocks in meta["tiered"]:
                ts = diff[k][1]._tiered_state
                prod = ts.take_pending()
                if prod is None:
                    raise MXNetError(
                        f"tiered embedding {diff[k][1].name!r}: no "
                        f"staged row plan for this step — feed the "
                        f"training loop through prefetch.RowPrefetcher "
                        f"(raw index batches cannot address the hot "
                        f"cache)")
                if len(prod) != n_blocks or \
                        int(prod[0].shape[0]) != n_flat:
                    raise MXNetError(
                        f"tiered embedding {diff[k][1].name!r}: staged "
                        f"row plan shape ({len(prod)} blocks, "
                        f"{int(prod[0].shape[0])} ids) does not match "
                        f"the captured step ({n_blocks} blocks, "
                        f"{n_flat} ids) — the prefetcher must translate "
                        f"exactly this step's index batch, once")
                tiered_vals.extend(prod)
            args = args + (tuple(tiered_vals),)
        return args, snapshot

    def _writeback(self, meta, diff, state_nds, out, snapshot, scaler,
                   scale_mode):
        """From the launch's return to the step's: rebind every handle
        to its post-step buffer, read the guard flag, settle the update
        counts, wrap the loss."""
        tr = self._trainer
        loss_leaves, aux_vals, new_ws, new_ss, out_gs, flag = out
        sh = meta.get("shardings")
        # Interop rule for mesh captures: anything eager code may consume
        # (params, aux, replicated grads, the loss) is rebound to a ZERO-
        # COPY device-0 shard view of the replicated mesh output, so
        # eval/monitoring/hybridized forwards keep working on one device;
        # the mesh-resident array itself is kept in _mesh_cache so the
        # next captured step pays no re-broadcast. Row-sharded outputs
        # (optimizer state, sharded-update grads) stay mesh-resident —
        # their next-step in_specs match exactly and .asnumpy()/save see
        # the full logical value.
        if sh is not None and meta.get("plan"):
            # rule-sharded layout: params/grads/aux that a rule SHARDS
            # stay mesh-resident (the global array is the logical value
            # and per-device memory stays at the shard size); replicated
            # ones collapse to the device-0 view like the 1-D mesh path
            for (i, p), w in zip(diff, new_ws):
                v = _logical_view(w)
                p.data()._rebind(v)
                self._mesh_cache[("d", i)] = (v, w)
            for (_, p), g in zip(diff, out_gs):
                if isinstance(g, tuple):
                    # sparse fast path: the table's gradient exists ONLY
                    # as (unique_ids, touched_rows) — p.grad() keeps its
                    # previous (stale) buffer; consumers of sparse grads
                    # read this pair (docs/PERFORMANCE.md "Sharded
                    # embeddings")
                    p._sparse_grad = (NDArray(_dev0_view(g[0])),
                                      NDArray(_dev0_view(g[1])))
                    continue
                # a table that trained sparse EARLIER but dense now
                # (demotion, plan/optimizer change) must not leave a
                # stale (ids, rows) pair for consumers to read
                if getattr(p, "_sparse_grad", None) is not None:
                    p._sparse_grad = None
                p._grad._rebind(_logical_view(g))
            for p, v, j in zip(meta["aux"], aux_vals, meta["aux_pos"]):
                view = _logical_view(v)
                p._data._rebind(view)
                if j is not None:
                    self._mesh_cache[("n", j)] = (view, v)
            loss_leaves = [_dev0_view(v) for v in loss_leaves]
        elif sh is not None:
            for (i, p), w in zip(diff, new_ws):
                v = _dev0_view(w)
                p.data()._rebind(v)
                self._mesh_cache[("d", i)] = (v, w)
            for (_, p), g, sok in zip(diff, out_gs, meta["shard_ok"]):
                p._grad._rebind(g if sok else _dev0_view(g))
            for p, v, j in zip(meta["aux"], aux_vals, meta["aux_pos"]):
                view = _dev0_view(v)
                p._data._rebind(view)
                if j is not None:
                    self._mesh_cache[("n", j)] = (view, v)
            loss_leaves = [_dev0_view(v) for v in loss_leaves]
        else:
            for (_, p), w in zip(diff, new_ws):
                p.data()._rebind(w)
            for (_, p), g in zip(diff, out_gs):
                p._grad._rebind(g)
            for p, v in zip(meta["aux"], aux_vals):
                p._data._rebind(v)
        for sv_nd, sv_new in zip(state_nds, new_ss):
            for s_nd, s_val in zip(sv_nd, sv_new):
                s_nd._rebind(s_val)

        # step k is dispatched and every NDArray handle points at its
        # post-step buffer: wake the RowPrefetcher so batch k+1's row
        # plan resolves overlapped with this step's device compute (its
        # writeback np.asarray blocks until the compute lands — the
        # data-flow barrier)
        for k, _n, _b in meta.get("tiered") or ():
            diff[k][1]._tiered_state.notify_step()

        applied = True
        if meta["guard"]:
            overflow = bool(flag)   # ONE host sync — the imperative
            applied = not overflow  # nonfinite guard pays the same
            if scaler is not None:
                scaler.update_scale(overflow)
        if applied:
            tr._note_applied()
        else:
            self._restore_update_counts(snapshot)
            tr._note_skip("AMP overflow" if scale_mode == "amp"
                          else "nonfinite gradients")
        tr._tick_step()

        out_nd = [NDArray(v) for v in loss_leaves]
        return jax.tree_util.tree_unflatten(meta["treedef"], out_nd)
