"""DLRM-style recommender bench: sharded embedding tables vs the
replicated dense-take layout (ISSUE 15; docs/PERFORMANCE.md "Sharded
embeddings").

The model is deliberately EMBEDDING-DOMINATED — several categorical
tables holding ~99% of the parameter bytes over a thin dense tower —
because that is the recommender workload's shape: memory capacity, not
FLOPs, is the binding constraint, and the headline metric is
`embed_param_bytes_per_dev` (~= 1/tp of the replicated footprint), not
step time. Categorical index batches are drawn from a Poisson-ish
per-feature distribution (a few hot rows, a long tail — Poisson around
a per-feature hot centre, folded into range), which is what makes the
sparse path's dedup/unique pass earn its keep: hot rows cross the
interconnect once per step no matter how many batch positions hit them.

Two arms on the same model, data and captured-step protocol:

  * sharded — `ShardedEmbedding` tables row-sharded over 'tp' on the
    (2,2) ('dp','tp') DEFAULT_RULES mesh: the captured step lowers the
    lookup to the bucketed all-to-all exchange and the backward to the
    (unique_rows, D) sparse fast path (`sharded_embed_step`);
  * replicated — the same tower with plain `Embedding` tables on a 1-D
    'dp' mesh: tables whole on every device, dense take, dense O(vocab)
    gradient. This is the SURVEY §8 layout the sharded arm retires.

A third arm (ISSUE 19, `--tiered` / `measure_tiered`) trains a tiered
table at a FIXED HBM budget: per-shard rows exceed `hbm_rows`, so the
full table cannot be device-resident and every step runs through the
host tier + engine-prefetched hot cache (`shard/tiered.py`), fed by the
`RowPrefetcher`.

Needs >= 4 devices (a (2,2) mesh); below that `value: None` so the
bench.py JSON fields (`rec_step_throughput`,
`rec_embed_bytes_per_dev`, `rec_vs_replicated`, and the `rec_tiered_*`
set) are omitted honestly rather than faked — the BENCH_SHARD=0
pattern.

Standalone: `python bench_rec.py` prints ONE JSON line;
`python bench_rec.py --tiered` runs the fixed-HBM tiered arm instead.
"""
from __future__ import annotations

import json
import os
import sys
import time

# per-chip samples/s denominator for vs_baseline on a recommender step:
# a DLRM step this size is all-to-all/latency-bound, not compute-bound;
# same spirit as bench_mlp's dispatch-bound denominator
BASELINE_SAMPLES_S = 100_000.0


def _setup():
    """Shared fixture: (tables, dim, batch, steps, index batches, dense
    features, labels). Embedding-dominated: 4 tables x 2048 rows x 32
    dims = 1 MiB of table bytes vs a ~17 KiB dense tower."""
    import jax
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    vocabs = (2048, 2048, 2048, 2048)
    dim = 32
    batch = 256 if on_tpu else 32
    steps = 30 if on_tpu else 4

    rng = np.random.RandomState(0)
    # Poisson-ish categorical traffic: each feature has a hot centre;
    # ids are Poisson around it folded into the vocab range, so a few
    # rows are hit many times per batch and most rows rarely
    idx = []
    for f, V in enumerate(vocabs):
        lam = 16 * (f + 1)
        draws = rng.poisson(lam, size=(8, batch)) % V
        idx.append(draws.astype(np.int32))
    Xd = rng.randn(8, batch, 8).astype(np.float32)
    yb = rng.randn(8, batch).astype(np.float32)
    return vocabs, dim, batch, steps, idx, Xd, yb


def _build(vocabs, dim, batch, sharded):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    class _DLRM(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                cls = (gluon.nn.ShardedEmbedding if sharded
                       else gluon.nn.Embedding)
                self.tables = []
                for V in vocabs:
                    t = cls(V, dim)
                    self.register_child(t)
                    self.tables.append(t)
                self.bot = gluon.nn.Dense(dim, activation="relu",
                                          in_units=8)
                self.top = gluon.nn.Dense(
                    1, in_units=(len(vocabs) + 1) * dim)

        def hybrid_forward(self, F, i0, i1, i2, i3, xd):
            embs = [t(i) for t, i in zip(self.tables, (i0, i1, i2, i3))]
            return self.top(F.concat(*embs, self.bot(xd), dim=1))

    mx.random.seed(0)
    net = _DLRM()
    net.initialize(mx.init.Xavier())
    return net


def measure(on_result=None):
    """The bench.py arm: sharded-vs-replicated captured DLRM steps.
    Returns the `rec_*` contract fields; `value: None` below 4
    devices."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.observability import registry
    from mxnet_tpu.shard import embedding as semb

    if len(jax.devices()) < 4:
        res = {"metric": "rec_step_throughput", "value": None,
               "unit": "samples/sec/chip",
               "skipped": "needs >= 4 devices"}
        print("[bench_rec] skipped (needs >= 4 devices)",
              file=sys.stderr)
        if on_result is not None:
            on_result(res)
        return res

    vocabs, dim, batch, steps, idx, Xd, yb = _setup()
    lossf = gluon.loss.L2Loss()
    a2a = registry().counter("kv_collective_bytes",
                             op="embed_all_to_all")

    def run(sharded):
        net = _build(vocabs, dim, batch, sharded)
        nb = [nd.array(i[0], dtype=np.int32) for i in idx]
        net(*nb, nd.array(Xd[0]))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="ici")
        if sharded:
            plan = tr.shard(mesh={"dp": 2, "tp": 2})
        else:
            from mxnet_tpu.parallel.mesh import make_mesh
            tr._kvstore.set_mesh(make_mesh({"dp": 4}))
            plan = None
        step = tr.capture(
            lambda i0, i1, i2, i3, xd, y:
            lossf(net(i0, i1, i2, i3, xd), y).mean())

        def feed(k):
            k = k % 8
            return ([nd.array(i[k], dtype=np.int32) for i in idx]
                    + [nd.array(Xd[k]), nd.array(yb[k])])

        for k in range(2):
            step(*feed(k))                      # compile + warm
        fallback = step.last_fallback_reason
        t0 = time.monotonic()
        for k in range(steps):
            L = step(*feed(k))
        float(L.asnumpy())
        dt = time.monotonic() - t0

        import re
        from mxnet_tpu.shard.rules import EMBED_WEIGHT_PATTERN
        pat = re.compile(EMBED_WEIGHT_PATTERN)
        embed = {p.name: p.data()._data
                 for p in net.collect_params().values()
                 if pat.search(p.name)}
        total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in embed.values())
        if plan is not None:
            per_dev = plan.param_bytes_per_device(embed)[0]
            frac = semb.embed_param_bytes_frac(
                plan, {p.name: p.data()._data
                       for p in net.collect_params().values()})
        else:
            per_dev, frac = total, 1.0
        return steps / dt, per_dev, total, frac, fallback

    a2a0 = a2a.value
    sh_steps_s, sh_per_dev, embed_total, sh_frac, sh_fb = run(True)
    a2a_bytes = a2a.value - a2a0
    re_steps_s, re_per_dev, _, _, re_fb = run(False)
    if sh_fb is not None:
        print(f"[bench_rec] WARNING: sharded arm fell back ({sh_fb}); "
              f"the ratio measures the imperative path", file=sys.stderr)

    res = {
        "metric": "rec_step_throughput",
        "value": round(sh_steps_s * batch / 4, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sh_steps_s * batch / 4
                             / BASELINE_SAMPLES_S, 4),
        "mesh": {"dp": 2, "tp": 2},
        "rec_steps_s": round(sh_steps_s, 3),
        "replicated_steps_s": round(re_steps_s, 3),
        "rec_vs_replicated": round(sh_steps_s / re_steps_s, 3),
        "rec_embed_bytes_per_dev": int(sh_per_dev),
        "replicated_embed_bytes_per_dev": int(re_per_dev),
        "embed_bytes_total": int(embed_total),
        "embed_param_bytes_frac": round(sh_frac, 4),
        "embed_a2a_bytes_per_step": (None if a2a_bytes == 0
                                     else int(a2a_bytes // (steps + 2))),
        "fallback": sh_fb,
        "replicated_fallback": re_fb,
    }
    print(f"[bench_rec] sharded {sh_steps_s:.2f} steps/s vs "
          f"{re_steps_s:.2f} replicated "
          f"({res['rec_vs_replicated']}x); embed bytes/dev "
          f"{sh_per_dev} vs {re_per_dev} replicated "
          f"({sh_frac:.2f}x of total); "
          f"{res['embed_a2a_bytes_per_step']} all-to-all B/step",
          file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def measure_tiered(on_result=None):
    """The fixed-HBM arm (ISSUE 19): ONE tiered `ShardedEmbedding`
    table whose per-shard rows EXCEED its hbm_rows budget — the full
    table cannot be device-resident, which is the tier's reason to
    exist — trained end-to-end through the `RowPrefetcher`-fed captured
    step (host-resident cold rows, engine-prefetched hot cache;
    docs/PERFORMANCE.md "Tiered embeddings"). Headline is samples/sec/
    chip AT the fixed HBM budget, alongside the cache hit rate the
    Poisson-ish traffic earns and the async H2D row-staging bytes each
    step costs. `value: None` below 4 devices — the omit-honestly
    pattern."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.prefetch import RowPrefetcher
    from mxnet_tpu.shard import tiered as _tiered

    if len(jax.devices()) < 4:
        res = {"metric": "rec_tiered_step_throughput", "value": None,
               "unit": "samples/sec/chip",
               "skipped": "needs >= 4 devices"}
        print("[bench_rec] tiered arm skipped (needs >= 4 devices)",
              file=sys.stderr)
        if on_result is not None:
            on_result(res)
        return res

    on_tpu = jax.default_backend() == "tpu"
    V, D, F = 8192, 32, 4
    HBM_ROWS = 256            # per-'tp'-shard rows = V/2 = 4096 >> 256
    batch = 256 if on_tpu else 32
    steps = 30 if on_tpu else 6

    rng = np.random.RandomState(7)
    # Poisson-ish categorical traffic (hot centre + long tail) so the
    # cache hit rate is a property of the workload, not of uniform draws
    idx = (rng.poisson(64, size=(8, batch, F)) % V).astype(np.int32)
    yb = rng.randn(8, batch, 1).astype(np.float32)

    class _TieredDLRM(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = gluon.nn.ShardedEmbedding(
                    V, D, tiered=True, hbm_rows=HBM_ROWS)
                self.top = gluon.nn.Dense(1, in_units=F * D)

        def hybrid_forward(self, F_, i):
            return self.top(self.embed(i).reshape((i.shape[0], -1)))

    mx.random.seed(0)
    net = _TieredDLRM()
    net.initialize(mx.init.Xavier())
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore="ici")
    tr.shard(mesh={"dp": 2, "tp": 2})
    step = tr.capture(lambda i, y: lossf(net(i), y).mean())
    shard_rows = V // 2       # rows each 'tp' shard owns in the host tier

    def feed(n):
        for k in range(n):
            j = k % 8
            yield nd.array(idx[j], dtype=np.int32), nd.array(yb[j])

    # compile + warm THROUGH the prefetcher: tiered steps only dispatch
    # behind a RowPrefetcher (the loud no-prefetcher error is the point)
    with RowPrefetcher(feed(2), tr, tables={0: net.embed}) as pf:
        for ib, y in pf:
            L = step(ib, y)
    fallback = step.last_fallback_reason

    h2d0 = _tiered._h2d_b.value
    hits0, miss0 = _tiered._hits_c.value, _tiered._miss_c.value
    t0 = time.monotonic()
    with RowPrefetcher(feed(steps), tr, tables={0: net.embed}) as pf:
        for ib, y in pf:
            L = step(ib, y)
    float(L.asnumpy())
    dt = time.monotonic() - t0
    hits = _tiered._hits_c.value - hits0
    miss = _tiered._miss_c.value - miss0
    hit_rate = hits / max(1, hits + miss)
    h2d_step = (_tiered._h2d_b.value - h2d0) / steps
    steps_s = steps / dt
    if fallback is not None:
        print(f"[bench_rec] WARNING: tiered arm fell back ({fallback})",
              file=sys.stderr)

    res = {
        "metric": "rec_tiered_step_throughput",
        "value": round(steps_s * batch / 4, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(steps_s * batch / 4 / BASELINE_SAMPLES_S,
                             4),
        "mesh": {"dp": 2, "tp": 2},
        "rec_tiered_steps_s": round(steps_s, 3),
        "rec_tiered_hit_rate": round(hit_rate, 4),
        "rec_tiered_h2d_bytes_per_step": int(h2d_step),
        "rec_tiered_hbm_rows": HBM_ROWS,
        "rec_tiered_shard_rows": shard_rows,
        "rec_tiered_resident_frac": round(HBM_ROWS / shard_rows, 4),
        "fallback": fallback,
    }
    print(f"[bench_rec] tiered {steps_s:.2f} steps/s at a "
          f"{HBM_ROWS}/{shard_rows}-row HBM budget "
          f"({res['rec_tiered_resident_frac']:.3f}x resident); hit "
          f"rate {hit_rate:.2f}; {int(h2d_step)} async H2D B/step",
          file=sys.stderr)
    if on_result is not None:
        on_result(res)
    return res


def main():
    # fork CPU devices BEFORE jax imports so the (2,2) mesh exists on a
    # laptop/CI run (no-op when jax is already in, e.g. under bench.py)
    if "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu" \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=4")
    if "--tiered" in sys.argv[1:]:
        res = measure_tiered()
    else:
        res = measure()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
