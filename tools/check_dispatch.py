#!/usr/bin/env python
"""Captured-step dispatch-budget checker (ISSUE 4 acceptance; same tier-1
wiring pattern as chaos_check/check_trace).

Trains a small MLP N steps twice — once through the captured one-
executable step (`Trainer.capture`) and once through the imperative
record/backward/step() loop — asserting that

  * a warm captured step stays within the dispatch budget (<= 2
    trainer-issued device dispatches per step; in practice exactly 1,
    the `captured_step` launch),
  * the captured step never silently falls back to the imperative path,
  * the capture cache compiles ONCE (every warm step is a jit-cache hit),
  * final parameters MATCH the imperative run to tight tolerance.

ISSUE 5 extension — the warm-step budget also covers the INPUT side:
with the device prefetcher (`mxnet_tpu.prefetch.DevicePrefetcher`)
feeding the captured step, a warm step must perform ZERO synchronous
host->device transfers (the `prefetch_h2d_sync` counter stays flat),
while a host-path control batch must trip the same detector (proving
the zero is a measurement, not a dead counter). Runs over the 'ici'
mesh when >= 2 devices are available (the sharded-placement path),
single-device otherwise.

ISSUE 8 extension — the warm-step budget also covers the RULE-SHARDED
captured step: with a (2,2) ('dp','tp') shard plan (mxnet_tpu/shard/)
attached, a warm step must stay within the same dispatch budget, do zero
synchronous H2D when the device prefetcher feeds it, and genuinely
reduce per-device parameter bytes (>= 4 devices; skipped below that).

ISSUE 15 extension — the warm-step budget also covers the SHARDED-
EMBEDDING captured step: a DLRM-style model with a `ShardedEmbedding`
table row-sharded over 'tp' (vocab >> batch) must hold the same <=2
dispatch budget warm, do zero synchronous H2D with the device
prefetcher staging integer index batches, shrink per-device embedding
bytes (`embed_param_bytes_frac` < 1), and its backward must fit under
the bytes of ONE dense (V, D) table gradient — the in-HLO proof that
the sparse fast path never materialises an O(vocab) cotangent
(>= 4 devices; skipped below).

ISSUE 16 extension — the warm-step budget also covers the EXPERT-
PARALLEL MoE captured step: a `ShardedMoE` layer with its expert banks
row-sharded over 'tp' on the (2,2) mesh (the 2-all-to-all token-routing
path live, publishing as `moe_step`) must hold the same <=2 dispatch
budget warm and do zero synchronous H2D with the device prefetcher
(>= 4 devices; skipped below).

ISSUE 19 extension — the warm-step budget also covers the TIERED
embedding captured step: a `ShardedEmbedding(tiered=True, hbm_rows=N)`
table — host-resident cold rows behind a fixed device hot cache, fed
through the engine-prefetched `RowPrefetcher` — must hold the same <=2
dispatch budget on a warm all-hit step with ZERO synchronous H2D (a hot
step touches only slots already on device), and a forced miss step's
asynchronous row staging must stay bounded by the touched-row bytes
(>= 4 devices; skipped below).

ISSUE 6 extension — the warm-step budget also covers the SERVE decode
loop: a warm continuous-batching decode turn must be at most ONE device
dispatch (the shared ragged-paged-attention decode executable), the
decode executable must never RETRACE while slot occupancy and page
tables vary mid-flight (mixed-length admissions/evictions between
steps), and the KV page pool must return to zero pages in use once
every request completes. PR 33: an admission turn of n <= R requests
(`DecodeRuntime.prefill_rows`) pays ONE prefill dispatch beside it, from
ONE prefill executable.

ISSUE 12 extension — the serving FAST PATH: speculative decode holds
the same <=1 dispatch per warm turn with ZERO retraces of the widened
verify executable across varying draft acceptance (and must actually
accept drafts, or the zero would be vacuous); a prefix-cache-warm
request takes STRICTLY fewer prefill (decode-turn) dispatches than the
cold control while a cache-disabled control shows no reduction; page
refcounts return to exactly the cache-held baseline after every
request and to zero after close().

Standalone:

    JAX_PLATFORMS=cpu python tools/check_dispatch.py [--steps N] [--budget B]

exit 0 = within budget + parity, 1 = violation (details on stderr).
Prints one JSON line with the measured numbers on stdout.
"""
from __future__ import annotations

import json
import os
import sys

DEFAULT_STEPS = 5
DISPATCH_BUDGET = 2


def run(steps=DEFAULT_STEPS, budget=DISPATCH_BUDGET):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, profiler

    rng = np.random.RandomState(0)
    X = nd.array(rng.randn(16, 32).astype(np.float32))
    y = nd.array(rng.randint(0, 8, 16).astype(np.float32))
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    def build():
        mx.random.seed(0)
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(8))
        net.initialize(mx.init.Xavier())
        net(X)
        return net

    errors = []

    # -- captured ----------------------------------------------------------
    net_c = build()
    tr_c = gluon.Trainer(net_c.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    step = tr_c.capture(lambda a, b: lossf(net_c(a), b).mean())
    step(X, y)                              # compile
    per_step = []
    for _ in range(steps):
        profiler.reset_dispatches()
        step(X, y)
        per_step.append(profiler.dispatch_count())
        if step.last_fallback_reason is not None:
            errors.append(f"captured step fell back: "
                          f"{step.last_fallback_reason}")
    worst = max(per_step)
    if worst > budget:
        errors.append(f"captured dispatch budget exceeded: {worst}/step "
                      f"(budget {budget}; per-step {per_step})")
    if step.cache_size != 1:
        errors.append(f"capture cache grew to {step.cache_size} entries "
                      f"for a fixed-shape loop (expected 1)")

    # -- imperative twin ---------------------------------------------------
    net_i = build()
    tr_i = gluon.Trainer(net_i.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    with autograd.record():
        L = lossf(net_i(X), y).mean()
    L.backward()
    tr_i.step(16)                           # warm the fused-kernel cache
    imp_per_step = None
    for _ in range(steps):
        with autograd.record():
            L = lossf(net_i(X), y).mean()
        L.backward()
        profiler.reset_dispatches()
        tr_i.step(16)
        imp_per_step = profiler.dispatch_count()

    # both nets have now taken exactly steps+1 updates
    max_dev = 0.0
    for pc, pi in zip(net_c.collect_params().values(),
                      net_i.collect_params().values()):
        a, b = pc.data().asnumpy(), pi.data().asnumpy()
        dev = float(np.max(np.abs(a - b) / (np.abs(b) + 1e-6)))
        max_dev = max(max_dev, dev)
        if not np.allclose(a, b, rtol=1e-4, atol=1e-6):
            errors.append(f"parity violation on {pc.name}: "
                          f"max rel dev {dev:.2e}")
            break

    prefetch_res = _run_prefetch_phase(steps, errors)
    shard_res = _run_shard_phase(steps, errors)
    shard_res.update(_run_embed_phase(errors))
    shard_res.update(_run_moe_phase(errors))
    shard_res.update(_run_tiered_phase(errors))
    serve_res = _run_serve_phase(errors)
    serve_res.update(_run_serve_fastpath_phase(errors))
    serve_res.update(_run_serve_int8_phase(errors))

    res = {
        "steps": steps,
        "captured_dispatches_per_step": worst,
        "captured_per_step": per_step,
        "imperative_dispatches_per_step": imp_per_step,
        "budget": budget,
        "max_rel_dev": max_dev,
    }
    res.update(prefetch_res)
    res.update(shard_res)
    res.update(serve_res)
    res["errors"] = errors
    res["ok"] = not errors
    return res


def _run_prefetch_phase(steps, errors):
    """Zero-synchronous-H2D budget for the device-prefetched input path
    (ISSUE 5): warm captured steps fed by a DevicePrefetcher must leave
    the `prefetch_h2d_sync` counter flat; a host-path batch through the
    same warm step must move it (detector liveness control)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.observability import registry
    from mxnet_tpu.prefetch import DevicePrefetcher

    sync = registry().counter("prefetch_h2d_sync")
    rng = np.random.RandomState(1)
    Xh = rng.randn(16, 32).astype(np.float32)
    yh = rng.randint(0, 8, 16).astype(np.float32)
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(nd.array(Xh))

    on_mesh = len(jax.devices()) >= 2
    if on_mesh:
        from mxnet_tpu.parallel.mesh import make_mesh
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="ici")
        tr._kvstore.set_mesh(make_mesh({"dp": 2}))
    else:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(nd.array(Xh), nd.array(yh))            # compile

    # control: host batches through the WARM step must fire the detector
    # (mesh: per-step device_put sharding; 1-device: raw-numpy convert)
    base = sync.value
    if on_mesh:
        step(nd.array(Xh), nd.array(yh))
    else:
        step(Xh, yh)
    detector_fires = sync.value > base
    if not detector_fires:
        errors.append("sync-H2D detector did not fire on host-path "
                      "batches (the zero below would be vacuous)")

    # device-prefetched loop: every warm step must be transfer-free
    pf = DevicePrefetcher(((Xh, yh) for _ in range(steps)),
                          capture_spec=tr._kvstore if on_mesh else None)
    worst_sync = 0
    try:
        for xb, yb in pf:
            base = sync.value
            step(xb, yb)
            worst_sync = max(worst_sync, sync.value - base)
            if step.last_fallback_reason is not None:
                errors.append(f"prefetched captured step fell back: "
                              f"{step.last_fallback_reason}")
    finally:
        pf.close()
    if worst_sync:
        errors.append(f"device-prefetched warm step performed "
                      f"{worst_sync} synchronous H2D transfer(s) "
                      f"(budget 0)")
    return {
        "prefetch_sync_h2d_per_step": worst_sync,
        "prefetch_sync_h2d_budget": 0,
        "prefetch_detector_fires": detector_fires,
        "prefetch_mesh": on_mesh,
    }


def _run_shard_phase(steps, errors):
    """Rule-sharded captured step budget (ISSUE 8): on a 2-D (2,2) mesh
    with the DEFAULT_RULES shard plan, a warm captured step must stay
    within the same <=2 dispatch budget (in practice 1), do ZERO
    synchronous H2D with the device prefetcher feeding it, and actually
    reduce per-device parameter bytes below the replicated footprint.
    Needs >= 4 devices (the tier-1 conftest forks 8 CPU devices);
    single-device standalone runs report the phase skipped."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, profiler
    from mxnet_tpu.observability import registry
    from mxnet_tpu.prefetch import DevicePrefetcher

    if len(jax.devices()) < 4:
        return {"shard_mesh": False, "shard_dispatches_per_step": None,
                "shard_sync_h2d_per_step": None,
                "shard_param_bytes_frac": None}

    sync = registry().counter("prefetch_h2d_sync")
    rng = np.random.RandomState(2)
    Xh = rng.randn(16, 32).astype(np.float32)
    yh = rng.randint(0, 8, 16).astype(np.float32)
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(nd.array(Xh))

    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore="ici")
    plan = tr.shard(mesh={"dp": 2, "tp": 2})
    params = {p.name: p.data()._data
              for p in net.collect_params().values()}
    per_dev, total = plan.param_bytes_per_device(params)
    frac = per_dev / total
    if frac >= 1.0:
        errors.append(f"shard plan did not reduce per-device parameter "
                      f"bytes ({per_dev}/{total})")

    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(nd.array(Xh), nd.array(yh))            # compile
    worst = 0
    worst_sync = 0
    pf = DevicePrefetcher(((Xh, yh) for _ in range(steps)),
                          capture_spec=tr._kvstore)
    try:
        for xb, yb in pf:
            base = sync.value
            profiler.reset_dispatches()
            step(xb, yb)
            worst = max(worst, profiler.dispatch_count())
            worst_sync = max(worst_sync, sync.value - base)
            if step.last_fallback_reason is not None:
                errors.append(f"sharded captured step fell back: "
                              f"{step.last_fallback_reason}")
    finally:
        pf.close()
    if worst > DISPATCH_BUDGET:
        errors.append(f"sharded captured dispatch budget exceeded: "
                      f"{worst}/step (budget {DISPATCH_BUDGET})")
    if worst_sync:
        errors.append(f"sharded device-prefetched warm step performed "
                      f"{worst_sync} synchronous H2D transfer(s) "
                      f"(budget 0)")
    return {
        "shard_mesh": True,
        "shard_dispatches_per_step": worst,
        "shard_sync_h2d_per_step": worst_sync,
        "shard_param_bytes_frac": round(frac, 4),
    }


def _run_embed_phase(errors):
    """Sharded-embedding budget (ISSUE 15): a warm captured DLRM step —
    `ShardedEmbedding` table row-sharded over 'tp' on the (2,2) mesh,
    vocab >> batch so the bound below bites — must stay within the <=2
    dispatch budget, do ZERO synchronous H2D with the device prefetcher
    staging the INTEGER index batches, genuinely shrink per-device
    embedding bytes (`embed_param_bytes_frac` < 1; ~1/tp), and its
    backward must never materialise an O(vocab) dense gradient: the
    executable's temp allocation is asserted under the bytes ONE dense
    (V, D) table gradient would cost. Needs >= 4 devices; skipped
    cleanly below that. The model is deliberately tiny (one table, a
    1-unit tower, ~10 steps total) to stay inside the tier-1 verify
    window."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, profiler
    from mxnet_tpu.observability import registry
    from mxnet_tpu.prefetch import DevicePrefetcher
    from mxnet_tpu.shard import embedding as semb

    if len(jax.devices()) < 4:
        return {"embed_mesh": False, "embed_dispatches_per_step": None,
                "embed_sync_h2d_per_step": None,
                "embed_param_bytes_frac": None,
                "embed_backward_temp_frac": None}

    V, D, B, F = 4096, 16, 16, 4          # vocab >> B*F touched rows
    rng = np.random.RandomState(3)
    Ih = rng.randint(0, V, (B, F)).astype(np.int32)
    Xh = rng.randn(B, 4).astype(np.float32)
    yh = rng.randn(B).astype(np.float32)

    class _DLRM(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = gluon.nn.ShardedEmbedding(V, D)
                self.top = gluon.nn.Dense(1, in_units=F * D + 4)

        def hybrid_forward(self, F_, idx, xd):
            e = self.embed(idx).reshape((idx.shape[0], -1))
            return self.top(F_.concat(e, xd, dim=1))

    mx.random.seed(0)
    net = _DLRM()
    net.initialize(mx.init.Xavier())
    net(nd.array(Ih, dtype=np.int32), nd.array(Xh))
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="ici")
    plan = tr.shard(mesh={"dp": 2, "tp": 2})

    params = {p.name: p.data()._data
              for p in net.collect_params().values()}
    frac = semb.embed_param_bytes_frac(plan, params)
    if frac is None or frac >= 1.0:
        errors.append(f"shard plan did not reduce per-device embedding "
                      f"bytes (embed_param_bytes_frac={frac})")

    step = tr.capture(lambda a, b, c: lossf(net(a, b), c).mean())
    step(nd.array(Ih, dtype=np.int32), nd.array(Xh), nd.array(yh))
    if step.last_fallback_reason is not None:
        errors.append(f"sharded embed step fell back on compile: "
                      f"{step.last_fallback_reason}")

    sync = registry().counter("prefetch_h2d_sync")
    worst = 0
    worst_sync = 0
    pf = DevicePrefetcher(((Ih, Xh, yh) for _ in range(4)),
                          capture_spec=tr._kvstore)
    try:
        for ib, xb, yb in pf:
            base = sync.value
            profiler.reset_dispatches()
            step(ib, xb, yb)
            worst = max(worst, profiler.dispatch_count())
            worst_sync = max(worst_sync, sync.value - base)
            if step.last_fallback_reason is not None:
                errors.append(f"sharded embed step fell back: "
                              f"{step.last_fallback_reason}")
    finally:
        pf.close()
    if worst > DISPATCH_BUDGET:
        errors.append(f"sharded embed dispatch budget exceeded: "
                      f"{worst}/step (budget {DISPATCH_BUDGET})")
    if worst_sync:
        errors.append(f"device-prefetched integer index batches "
                      f"performed {worst_sync} synchronous H2D "
                      f"transfer(s) (budget 0)")

    # the no-dense-gradient proof: relower the warm executable from its
    # recorded aval skeleton (no python re-trace) and bound its TEMP
    # allocation under one dense (V, D) fp32 table gradient — at
    # vocab >> batch a backward that materialised the O(vocab) cotangent
    # could not fit the bound (actual temps are O(unique_rows * D))
    dense_grad_bytes = V * D * 4
    temp_frac = None
    from mxnet_tpu.observability import compilex
    ij = compilex.instrumented().get("sharded_embed_step")
    if ij is None or ij.last_abstract is None:
        errors.append("sharded_embed_step never registered with the "
                      "compile observatory — the sparse fast path did "
                      "not engage")
    else:
        args, kwargs = ij.last_abstract
        ma = ij.lower(*args, **kwargs).compile().memory_analysis()
        temp_frac = ma.temp_size_in_bytes / dense_grad_bytes
        if ma.temp_size_in_bytes >= dense_grad_bytes:
            errors.append(
                f"sharded embed backward temp allocation "
                f"{ma.temp_size_in_bytes} B >= one dense (V={V}, D={D}) "
                f"table gradient ({dense_grad_bytes} B) — the sparse "
                f"path is materialising an O(vocab) buffer")

    return {
        "embed_mesh": True,
        "embed_dispatches_per_step": worst,
        "embed_sync_h2d_per_step": worst_sync,
        "embed_param_bytes_frac": (None if frac is None
                                   else round(frac, 4)),
        "embed_backward_temp_frac": (None if temp_frac is None
                                     else round(temp_frac, 4)),
    }


def _run_moe_phase(errors):
    """Expert-parallel MoE budget (ISSUE 16): a warm captured step over
    a Dense stem + `ShardedMoE` layer — expert banks row-sharded over
    'tp' on the (2,2) mesh, so the 2-all-to-all token-routing path is
    live — must stay within the <=2 dispatch budget, do ZERO
    synchronous H2D with the device prefetcher staging the batches, and
    must compile as the `moe_step` executable (the routing fast path
    engaged, not the dense fallback). Needs >= 4 devices; skipped
    cleanly below that. Tiny shapes (one MoE layer, ~6 steps) to stay
    inside the tier-1 verify window."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, profiler
    from mxnet_tpu.observability import registry
    from mxnet_tpu.prefetch import DevicePrefetcher

    if len(jax.devices()) < 4:
        return {"moe_mesh": False, "moe_dispatches_per_step": None,
                "moe_sync_h2d_per_step": None}

    B, D = 8, 16
    rng = np.random.RandomState(5)
    Xh = rng.randn(B, D).astype(np.float32)
    yh = rng.randn(B, D).astype(np.float32)

    class _MoENet(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.proj = gluon.nn.Dense(D, in_units=D)
                self.moe = gluon.nn.ShardedMoE(
                    D, 16, num_experts=4, k=2, capacity_factor=1.25)

        def hybrid_forward(self, F_, x):
            return self.moe(self.proj(x))

    mx.random.seed(0)
    net = _MoENet()
    net.initialize(mx.init.Xavier())
    net(nd.array(Xh))
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="ici")
    tr.shard(mesh={"dp": 2, "tp": 2})

    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(nd.array(Xh), nd.array(yh))
    if step.last_fallback_reason is not None:
        errors.append(f"moe step fell back on compile: "
                      f"{step.last_fallback_reason}")

    sync = registry().counter("prefetch_h2d_sync")
    worst = 0
    worst_sync = 0
    pf = DevicePrefetcher(((Xh, yh) for _ in range(4)),
                          capture_spec=tr._kvstore)
    try:
        for xb, yb in pf:
            base = sync.value
            profiler.reset_dispatches()
            step(xb, yb)
            worst = max(worst, profiler.dispatch_count())
            worst_sync = max(worst_sync, sync.value - base)
            if step.last_fallback_reason is not None:
                errors.append(f"moe step fell back: "
                              f"{step.last_fallback_reason}")
    finally:
        pf.close()
    if worst > DISPATCH_BUDGET:
        errors.append(f"moe dispatch budget exceeded: {worst}/step "
                      f"(budget {DISPATCH_BUDGET})")
    if worst_sync:
        errors.append(f"device-prefetched MoE batches performed "
                      f"{worst_sync} synchronous H2D transfer(s) "
                      f"(budget 0)")

    from mxnet_tpu.observability import compilex
    if compilex.instrumented().get("moe_step") is None:
        errors.append("moe_step never registered with the compile "
                      "observatory — the expert-parallel routing path "
                      "did not engage")

    return {
        "moe_mesh": True,
        "moe_dispatches_per_step": worst,
        "moe_sync_h2d_per_step": worst_sync,
    }


def _run_tiered_phase(errors):
    """Tiered-embedding budget (ISSUE 19): a captured DLRM step over a
    `ShardedEmbedding(tiered=True, hbm_rows=...)` table — host-resident
    cold rows, a fixed (hbm_rows, D)-per-shard device hot cache, the
    `RowPrefetcher` resolving next-step rows off the engine's background
    lane — must hold the same <=2 dispatch budget on a warm ALL-HIT step
    and do ZERO synchronous H2D there (the whole point of the tier: a
    hot step touches only cache slots already on device), while a forced
    MISS step's asynchronous row staging stays bounded by the touched-row
    bytes (cold stage + the cached all-hit zero block + one miss stage —
    never O(vocab)). Needs >= 4 devices; skipped cleanly below that.
    Tiny shapes (one table, 5 steps) to stay inside the tier-1 verify
    window."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, profiler
    from mxnet_tpu.observability import registry
    from mxnet_tpu.prefetch import RowPrefetcher
    from mxnet_tpu.shard import tiered as stiered

    if len(jax.devices()) < 4:
        return {"tiered_mesh": False, "tiered_dispatches_per_step": None,
                "tiered_sync_h2d_per_step": None,
                "tiered_async_h2d_bytes": None}

    V, D, B, F = 4096, 16, 16, 4
    HBM_ROWS = 48          # n_slots = tp * 48 = 96 >= B*F touched rows
    rng = np.random.RandomState(11)
    Ah = rng.randint(0, 2048, (B, F)).astype(np.int32)     # resident set
    Bh = rng.randint(2048, 4096, (B, F)).astype(np.int32)  # cold set
    yh = rng.randn(B, 1).astype(np.float32)

    class _DLRM(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = gluon.nn.ShardedEmbedding(
                    V, D, tiered=True, hbm_rows=HBM_ROWS)
                self.top = gluon.nn.Dense(1, in_units=F * D)

        def hybrid_forward(self, F_, i):
            return self.top(self.embed(i).reshape((i.shape[0], -1)))

    mx.random.seed(0)
    net = _DLRM()
    net.initialize(mx.init.Xavier())
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="ici")
    tr.shard(mesh={"dp": 2, "tp": 2})
    step = tr.capture(lambda i, y: lossf(net(i), y).mean())

    # batch sequence: cold-A (compile + first stage), 3x repeat-A (warm
    # ALL-HIT steps — the zero-H2D hot path under test), cold-B (a
    # forced full-miss step whose staging must stay bounded)
    seq = [Ah, Ah, Ah, Ah, Bh]
    src = ((nd.array(i, dtype=np.int32), nd.array(yh)) for i in seq)

    sync = registry().counter("prefetch_h2d_sync")
    worst = 0
    worst_sync = 0
    h2d0 = stiered._h2d_b.value
    pf = RowPrefetcher(src, tr, tables={0: net.embed})
    try:
        for k, (ib, yb) in enumerate(pf):
            base = sync.value
            profiler.reset_dispatches()
            step(ib, yb)
            if k >= 1:                    # every post-compile step
                worst = max(worst, profiler.dispatch_count())
            if 1 <= k <= 3:               # the warm all-hit steps
                worst_sync = max(worst_sync, sync.value - base)
            if step.last_fallback_reason is not None:
                errors.append(f"tiered step fell back: "
                              f"{step.last_fallback_reason}")
    finally:
        pf.close()
    h2d_total = stiered._h2d_b.value - h2d0

    if worst > DISPATCH_BUDGET:
        errors.append(f"tiered dispatch budget exceeded: {worst}/step "
                      f"(budget {DISPATCH_BUDGET})")
    if worst_sync:
        errors.append(f"tiered warm all-hit steps performed "
                      f"{worst_sync} synchronous H2D transfer(s) "
                      f"(budget 0)")
    # bounded async staging: slots (M,) int32 + one (M, D) fp32 row
    # block per stage, three stages total (cold-A, the cached all-hit
    # zero block, cold-B). A tier that shipped O(vocab) rows — or
    # restaged on every all-hit step — cannot fit this bound.
    M = B * F
    stage_bytes = M * 4 + M * D * 4
    bound = 3 * stage_bytes
    if not h2d_total:
        errors.append("tiered async H2D byte counter never moved — the "
                      "row-prefetch staging path did not engage")
    elif h2d_total > bound:
        errors.append(f"tiered async H2D traffic {h2d_total} B exceeds "
                      f"the touched-row bound ({bound} B = 3 stages of "
                      f"{stage_bytes} B) — the hot-cache tier is "
                      f"shipping more than the missed rows")

    return {
        "tiered_mesh": True,
        "tiered_dispatches_per_step": worst,
        "tiered_sync_h2d_per_step": worst_sync,
        "tiered_async_h2d_bytes": int(h2d_total),
    }


def _run_serve_phase(errors):
    """Serve decode-loop budget (ISSUE 6): warm continuous-batching decode
    turns are at most ONE dispatch (the shared paged-decode executable),
    the executable never retraces while slot occupancy and page tables
    vary, and the page pool returns to baseline when the traffic drains."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=4, max_src_len=8,
                          max_new_tokens=12, engine_driven=False)
    sched = srv.scheduler
    rng = np.random.RandomState(0)

    # warm: one request through prefill + a decode step compiles both
    # executables
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4)
    sched.step()
    sched.step()
    warm_traces = srv.runtime.decode_traces

    # mixed-length traffic so occupancy and page-table contents vary
    # between steps (1 -> 3 active, staggered completions)
    for n, mt in ((3, 10), (7, 3), (6, 7), (4, 12), (8, 5)):
        srv.submit(rng.randint(4, 32, (n,)), max_new_tokens=mt)
    worst = 0
    decode_steps = 0
    worst_admit = most_admitted = 0
    worst_ahead = 0
    rows = srv.runtime.prefill_rows
    for _ in range(100):
        if not sched.pending_work():
            break
        profiler.reset_dispatches()
        ahead = sched.lookahead_turns
        r = sched.step()
        if sched.lookahead_turns > ahead:
            # a backlog turn (ISSUE 35: dispatched before the turn in
            # flight was read) still pays ONE decode dispatch
            worst_ahead = max(worst_ahead,
                              profiler.dispatch_count("serve_decode"))
        if r.decoded and not r.admitted:
            # a pure decode turn: the only allowed launch is the decode
            # executable itself
            worst = max(worst, profiler.dispatch_count())
            decode_steps += 1
        elif 0 < r.admitted <= rows:
            # an admission turn of n <= R requests additionally pays ONE
            # prefill dispatch, however many it admits
            worst_admit = max(worst_admit,
                              profiler.dispatch_count("serve_prefill"))
            most_admitted = max(most_admitted, r.admitted)
    # capture BEFORE close(): Scheduler.shutdown clears queue/slots and
    # frees pages, which would mask a wedged scheduler or a leak
    undrained = sched.pending_work()
    retraces = srv.runtime.decode_traces - warm_traces
    prefill_traces = srv.runtime.prefill_traces
    leaked = srv.pool.in_use()
    srv.close()
    if undrained:
        errors.append("serve phase did not drain")
    if decode_steps == 0:
        errors.append("serve phase measured no pure decode turns")
    if worst > 1:
        errors.append(f"serve decode budget exceeded: {worst} "
                      f"dispatches/turn (budget 1)")
    if worst_ahead != 1:
        errors.append(f"serve backlog turns (one in flight) paid "
                      f"{worst_ahead} decode dispatches (budget 1; 0 means "
                      f"the phase never looked ahead)")
    if most_admitted < 2:
        errors.append("serve phase measured no turn of several admissions")
    if worst_admit > 1:
        errors.append(f"serve prefill budget exceeded: {worst_admit} "
                      f"dispatches in an admission turn of <= {rows} "
                      f"requests (budget 1)")
    if prefill_traces != 1:
        errors.append(f"serve prefill executable traced {prefill_traces}x "
                      f"(budget 1)")
    if retraces:
        errors.append(f"serve decode executable retraced {retraces}x "
                      "across occupancy changes (budget 0)")
    if leaked:
        errors.append(f"serve phase leaked {leaked} KV pages")
    return {
        "serve_decode_dispatches_per_step": worst,
        "serve_decode_budget": 1,
        "serve_decode_steps_measured": decode_steps,
        "serve_decode_retraces": retraces,
        "serve_lookahead_dispatches_per_turn": worst_ahead,
        "serve_lookahead_turns": sched.lookahead_turns,
        "serve_prefill_dispatches_per_admit_turn": worst_admit,
        "serve_most_admitted_in_a_turn": most_admitted,
        "serve_prefill_traces": prefill_traces,
        "serve_pages_leaked": leaked,
    }


def _run_serve_fastpath_phase(errors):
    """Serving fast-path budgets (ISSUE 12).

    SPECULATIVE decode: a width-(k+1) server's warm turns stay at ONE
    dispatch each, and the widened verify executable never retraces
    while draft acceptance varies (ragged window lengths are arguments,
    not shapes). Liveness: the run must actually accept drafted tokens
    (accept rate > 0) — a dead proposer would make the retrace zero
    vacuous.

    PREFIX cache: a request whose source+prompt prefix is cached must
    take STRICTLY fewer prefill (decode-turn) dispatches than the cold
    control — and the de-optimised control (cache disabled, identical
    request) must show NO reduction, proving the delta is the cache.
    Pages: after the traffic drains, only cache-held pages remain (each
    at refcount exactly 1), and close() returns the pool to zero."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.models.transformer import TransformerNMT

    def build(**kw):
        mx.random.seed(0)
        model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                               num_heads=2, max_length=48, dropout=0.0)
        model.initialize()
        # num_pages sized generously: page PRESSURE (cache eviction /
        # preemption) is unit-tested in tests/test_serve.py — here it
        # would let an eviction turn the warm request cold and make the
        # strictly-fewer comparison flaky
        return mx.serve.Server(model, slots=2, page_size=4, max_src_len=8,
                               max_new_tokens=8, max_prompt_len=12,
                               num_pages=16, engine_driven=False, **kw)

    rng = np.random.RandomState(3)
    src = rng.randint(4, 32, (6,)).astype(np.int32)
    prompt = rng.randint(4, 32, (9,)).astype(np.int32)

    def drain_turns(srv, *submits):
        handles = [srv.submit(s, max_new_tokens=m, prompt_tokens=p)
                   for s, m, p in submits]
        base = profiler.dispatch_count("serve_decode")
        srv.scheduler.run_until_idle()
        outs = [h.result(timeout=300) for h in handles]
        return outs, profiler.dispatch_count("serve_decode") - base

    # -- speculative server: warm-up compiles verify + prefill ---------
    srv = build(speculative_k=2)
    cold_out, cold_turns = drain_turns(srv, (src, 8, prompt))
    warm_traces = srv.runtime.verify_traces

    # warm request adopts the cached prefix; extra mixed traffic varies
    # occupancy AND draft acceptance (different prompts/sources accept
    # differently) while we hold the per-turn dispatch budget
    for s_, m_, p_ in ((src, 8, prompt),
                       (rng.randint(4, 32, (5,)), 6,
                        rng.randint(4, 32, (6,))),
                       (src, 4, prompt[:6])):
        srv.submit(s_, max_new_tokens=m_, prompt_tokens=p_)
    worst = 0
    decode_steps = 0
    for _ in range(200):
        if not srv.scheduler.pending_work():
            break
        profiler.reset_dispatches()
        r = srv.scheduler.step()
        if r.decoded and not r.admitted:
            worst = max(worst, profiler.dispatch_count())
            decode_steps += 1
    undrained = srv.scheduler.pending_work()
    retraces = srv.runtime.verify_traces - warm_traces
    drafted = srv.scheduler.spec_drafted
    accepted = srv.scheduler.spec_accepted
    accept_rate = accepted / max(drafted, 1)
    # warm twin of the cold request, measured alone for the strict
    # prefill-dispatch comparison
    warm_out, warm_turns = drain_turns(srv, (src, 8, prompt))
    in_use_drained = srv.pool.in_use()
    cache_pages = srv.prefix_cache.pages_held()
    bad_refs = [p for p in range(1, srv.pool.num_pages)
                if srv.pool.ref_count(p) not in (0, 1)]
    srv.close()
    leaked = srv.pool.in_use()

    if undrained:
        errors.append("serve fast-path phase did not drain")
    if decode_steps == 0:
        errors.append("serve fast-path phase measured no pure decode "
                      "turns")
    if worst > 1:
        errors.append(f"speculative decode budget exceeded: {worst} "
                      f"dispatches/turn (budget 1)")
    if retraces:
        errors.append(f"widened verify executable retraced {retraces}x "
                      f"across draft-acceptance variation (budget 0)")
    if accepted <= 0:
        errors.append("speculative phase accepted no drafted tokens "
                      "(the zero-retrace budget would be vacuous)")
    if warm_out != cold_out:
        errors.append("prefix-cached request output differs from the "
                      "cold control (bitwise-greedy contract broken)")
    if not warm_turns < cold_turns:
        errors.append(f"prefix cache did not reduce prefill dispatches: "
                      f"warm {warm_turns} vs cold {cold_turns} decode "
                      f"turns (budget: strictly fewer)")
    if in_use_drained != cache_pages:
        errors.append(f"drained fast-path pool holds {in_use_drained} "
                      f"pages but the cache owns {cache_pages} — "
                      f"stuck request references")
    if bad_refs:
        errors.append(f"pages with refcount > 1 after drain: {bad_refs}")
    if leaked:
        errors.append(f"serve fast-path phase leaked {leaked} KV pages "
                      f"after close()")

    # -- de-optimised control: cache disabled, identical request -------
    ctrl = build(speculative_k=2, prefix_cache=False)
    c1_out, c1_turns = drain_turns(ctrl, (src, 8, prompt))
    c2_out, c2_turns = drain_turns(ctrl, (src, 8, prompt))
    ctrl.close()
    if c1_out != cold_out or c2_out != cold_out:
        errors.append("cache-disabled control output differs (bitwise-"
                      "greedy contract broken)")
    if c2_turns < c1_turns:
        errors.append(f"cache-DISABLED control got faster on repeat "
                      f"({c2_turns} vs {c1_turns} turns) — the prefix "
                      f"reduction above proves nothing")

    return {
        "serve_spec_dispatches_per_turn": worst,
        "serve_spec_retraces": retraces,
        "serve_spec_accept_rate": round(accept_rate, 4),
        "serve_prefix_cold_turns": cold_turns,
        "serve_prefix_warm_turns": warm_turns,
        "serve_prefix_nocache_turns": c2_turns,
        "serve_fastpath_pages_leaked": leaked,
    }


def _run_serve_int8_phase(errors):
    """Quantized-serve budgets (ISSUE 14).

    DISPATCH/RETRACE: an int8-KV server's warm decode turns stay at ONE
    dispatch each and the quantized decode executable never retraces
    while occupancy and page tables vary (the per-page scale arrays are
    donated arguments, not shapes).

    CAPACITY: a fixed HBM byte budget must hold >= 1.9x the TOKENS of
    the fp32 pool (scale arrays included in the arithmetic, so the claim
    is honest — on this toolchain's fp32 pages it is ~3.5x; bf16 pages
    would make it ~1.9x), and the page accounting stays exact at that
    doubled capacity: `kv_pages_in_use` returns to 0 once the traffic
    drains and the server closes."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.models.transformer import TransformerNMT
    from mxnet_tpu.serve.quant import kv_page_bytes, token_capacity

    n_layers, heads, units, psize = 1, 2, 16, 4
    budget = 64 * kv_page_bytes(n_layers, psize, heads, units // heads,
                                "float32")
    cap_fp = token_capacity(budget, n_layers, psize, heads,
                            units // heads, "float32")
    cap_q = token_capacity(budget, n_layers, psize, heads,
                           units // heads, "int8")
    ratio = cap_q / cap_fp
    if ratio < 1.9:
        errors.append(f"int8 KV capacity ratio {ratio:.3f} < 1.9 at a "
                      f"fixed {budget}-byte budget ({cap_q} vs {cap_fp} "
                      f"tokens)")

    mx.random.seed(0)
    model = TransformerNMT(32, units=units, hidden=2 * units,
                           num_layers=n_layers, num_heads=heads,
                           max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=psize, max_src_len=8,
                          max_new_tokens=12, kv_dtype="int8",
                          kv_hbm_bytes=budget, engine_driven=False)
    if srv.pool.capacity * psize != cap_q:
        errors.append(f"kv_hbm_bytes pool sizing disagrees with "
                      f"token_capacity: {srv.pool.capacity * psize} vs "
                      f"{cap_q}")
    sched = srv.scheduler
    rng = np.random.RandomState(0)
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4)
    sched.step()
    sched.step()
    warm_traces = srv.runtime.decode_traces
    for n, mt in ((3, 10), (7, 3), (6, 7), (4, 12), (8, 5)):
        srv.submit(rng.randint(4, 32, (n,)), max_new_tokens=mt)
    worst = 0
    decode_steps = 0
    for _ in range(100):
        if not sched.pending_work():
            break
        profiler.reset_dispatches()
        r = sched.step()
        if r.decoded and not r.admitted:
            worst = max(worst, profiler.dispatch_count())
            decode_steps += 1
    undrained = sched.pending_work()
    retraces = srv.runtime.decode_traces - warm_traces
    # the prefix cache may legitimately hold pages after the drain; the
    # accounting bar is: nothing BEYOND the cache, and zero after close
    held = srv.pool.in_use()
    cache_pages = srv.prefix_cache.pages_held() if srv.prefix_cache \
        else 0
    srv.close()
    leaked = srv.pool.in_use()
    if undrained:
        errors.append("int8 serve phase did not drain")
    if decode_steps == 0:
        errors.append("int8 serve phase measured no pure decode turns")
    if worst > 1:
        errors.append(f"int8 serve decode budget exceeded: {worst} "
                      f"dispatches/turn (budget 1)")
    if retraces:
        errors.append(f"int8 serve decode executable retraced "
                      f"{retraces}x across occupancy changes (budget 0)")
    if held != cache_pages:
        errors.append(f"int8 pool holds {held} pages after drain but "
                      f"the cache owns {cache_pages} — stuck request "
                      f"references at 2x capacity")
    if leaked:
        errors.append(f"int8 serve phase leaked {leaked} KV pages "
                      f"after close()")
    return {
        "serve_int8_dispatches_per_step": worst,
        "serve_int8_retraces": retraces,
        "serve_int8_capacity_ratio": round(ratio, 4),
        "serve_int8_tokens_at_budget": cap_q,
        "serve_fp32_tokens_at_budget": cap_fp,
        "serve_int8_pages_leaked": leaked,
    }


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    steps, budget = DEFAULT_STEPS, DISPATCH_BUDGET
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    if "--budget" in argv:
        budget = int(argv[argv.index("--budget") + 1])
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    res = run(steps=steps, budget=budget)
    print(json.dumps(res))
    for err in res["errors"]:
        print(f"check_dispatch: {err}", file=sys.stderr)
    if res["errors"]:
        print("check_dispatch: FAIL", file=sys.stderr)
        return 1
    shard_txt = ("shard phase skipped (<4 devices)"
                 if not res["shard_mesh"] else
                 f"{res['shard_dispatches_per_step']} dispatch/step "
                 f"sharded (2,2) at "
                 f"{res['shard_param_bytes_frac']}x param bytes/dev; "
                 f"embed {res['embed_dispatches_per_step']} "
                 f"dispatch/step at {res['embed_param_bytes_frac']}x "
                 f"embed bytes/dev, backward temp "
                 f"{res['embed_backward_temp_frac']}x of one dense "
                 f"table grad; moe {res['moe_dispatches_per_step']} "
                 f"dispatch/step, {res['moe_sync_h2d_per_step']} sync "
                 f"H2D; tiered {res['tiered_dispatches_per_step']} "
                 f"dispatch/step, {res['tiered_sync_h2d_per_step']} "
                 f"sync H2D warm, {res['tiered_async_h2d_bytes']} B "
                 f"async staged")
    print(f"check_dispatch: OK ({res['captured_dispatches_per_step']} "
          f"dispatch/step captured vs "
          f"{res['imperative_dispatches_per_step']} imperative; "
          f"{res['prefetch_sync_h2d_per_step']} sync H2D/step with the "
          f"device prefetcher; {shard_txt}; "
          f"{res['serve_decode_dispatches_per_step']} dispatch/decode "
          f"turn, {res['serve_decode_retraces']} retraces serving; "
          f"speculative {res['serve_spec_dispatches_per_turn']} "
          f"dispatch/turn, {res['serve_spec_retraces']} retraces, "
          f"accept rate {res['serve_spec_accept_rate']}; prefix warm "
          f"{res['serve_prefix_warm_turns']} vs cold "
          f"{res['serve_prefix_cold_turns']} turns; int8 KV "
          f"{res['serve_int8_dispatches_per_step']} dispatch/turn at "
          f"{res['serve_int8_capacity_ratio']}x token capacity)",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
