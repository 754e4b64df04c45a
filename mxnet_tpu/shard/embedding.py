"""Model-parallel sparse embedding lookup + sparse-gradient fast path
(ISSUE 15; docs/PERFORMANCE.md "Sharded embeddings").

SURVEY §8 maps sparse embeddings to a dense ``take`` over a REPLICATED
table — fine for BERT vocabularies, fatal for recommendation-scale
tables (10⁸ rows x wide meshes), where memory capacity, not FLOPs, is
the binding constraint. This module row-shards a table over one named
mesh axis (the PR 8 partition-rule machinery assigns the layout) and
moves only the LOOKED-UP rows over the interconnect — the
portable-collective philosophy of arXiv:2112.01075:

  forward  (``gather_rows``, inside the captured step's program):
    1. dedup — ``jnp.unique(size=n)`` over the step's flat index batch,
       so each distinct row crosses the wire once per step regardless of
       how many batch positions reference it;
    2. bucket the deduped ids by owner shard (``plan_buckets``: sort by
       ``id // rows_per_shard``, slot into a static ``(shards, U)``
       layout, out-of-range sentinel pads);
    3. ONE ``all_to_all`` exchanges the index buckets, each owner
       gathers its local rows, ONE more ``all_to_all`` returns the
       vectors — exactly 2 all-to-alls per table per step, the count
       tools/check_fusion.py pins.

  backward (the sparse-gradient fast path, mxnet_tpu/cachedop.py): the
    table is HOISTED OUT of the step's ``jax.vjp`` — the gathered
    ``(U, D)`` row block is the differentiable input instead, so the
    cotangent the backward materialises is ``(unique_rows, D)`` plus an
    index vector, NEVER an O(vocab) dense gradient. XLA's scatter-add
    over the dedup inverse IS the segment-sum of per-position
    cotangents into the touched-row block.

  update (``sparse_row_update``): the multi-tensor optimizer's
    scatter-add arm (optimizer/multi_tensor.py ``sparse_update_rows``)
    runs on the OWNING shard only — touched weight rows and their
    row-shaped optimizer-state rows (momentum, Adam m/v, fp32 masters)
    are gathered, staged through the exact ``apply_param_update``
    numerics, and scattered back in place into the donated, mesh-
    resident buffers. Untouched rows never move and never update
    (MXNet's documented lazy/sparse-update semantics: weight decay and
    momentum-style state decay apply to TOUCHED rows only; plain SGD
    with wd=0 matches the dense path exactly).

Capacity note: bucket capacity is U (the deduped count) per destination
— correctness never depends on the index distribution. Per-step wire
bytes are O(shards * U * D) for the vector return; the memory headline
is ``embed_param_bytes_frac`` ~= 1/axis_size per device.
"""
from __future__ import annotations

import re
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from jax import shard_map
from .exchange import (exchange, local_offsets,  # noqa: F401  (re-export)
                       plan_buckets)

__all__ = ["plan_buckets", "gather_rows", "sparse_row_update",
           "scatter_rows", "SparseLookupContext", "lookup",
           "sparse_eligibility", "embed_param_bytes_frac"]


# how many all-to-alls one sharded lookup lowers to — the forward index
# exchange plus the vector return. tools/check_fusion.py cross-checks
# its pinned count for the (2,2) embedding step against
# `A2A_PER_TABLE * n_tables` so the budget and the exchange math cannot
# drift apart silently. The bucket layout + a2a primitive live in
# shard/exchange.py (shared with the MoE token-routing head).
A2A_PER_TABLE = 2


def gather_rows(table, uniq, mesh, axis):
    """Fetch the deduped rows ``table[uniq]`` from a table row-sharded
    over ``mesh`` axis ``axis``: bucket ids by owner shard, all-to-all
    the index buckets, gather locally on the owner, all-to-all the
    vectors back (2 collectives total). ``uniq`` must be replicated
    (the step deduplicates the GLOBAL index batch); out-of-range ids
    (the unique-pass sentinel) come back as clamped garbage rows that
    no inverse-index slot ever references. Returns ``(U, D)``
    replicated. Axis size 1 degenerates to a local gather."""
    n_shards = int(mesh.shape[axis])
    if n_shards <= 1:
        return jnp.take(table, jnp.clip(uniq, 0, table.shape[0] - 1),
                        axis=0)
    vocab = table.shape[0]
    rows_per = vocab // n_shards

    def local(tab, ids):
        t = jax.lax.axis_index(axis)
        buckets, s_owner, rank, order = plan_buckets(
            ids, n_shards, rows_per, vocab)
        recv_ids = exchange(buckets, axis)
        loc = jnp.clip(recv_ids - t * rows_per, 0, tab.shape[0] - 1)
        send_rows = tab[loc]                       # (n_shards, U, D)
        rows_back = exchange(send_rows, axis)
        got_sorted = rows_back[s_owner, rank]      # (U, D)
        inv_order = jnp.argsort(order, stable=True)
        return got_sorted[inv_order]

    table_spec = P(*([axis] + [None] * (table.ndim - 1)))
    return shard_map(local, mesh=mesh,
                     in_specs=(table_spec, P()),
                     out_specs=P(), check_vma=False)(table, uniq)


def sparse_row_update(table, state_vals, uniq, g_rows, mesh, axis,
                      stage_fn):
    """The scatter-add arm's sharded half: on the OWNING shard only,
    gather the touched weight rows + row-shaped optimizer-state rows,
    run ``stage_fn(w_rows, g_rows, sv_rows) -> (new_rows, new_sv)``
    (the multi-tensor ``apply_param_update`` staging over the row
    block), and scatter the results back in place. Scalar state leaves
    (e.g. Adam's step counter) pass through whole and update
    replicated. Non-owned and sentinel slots scatter with
    ``mode='drop'`` — a shard never writes rows it does not own, and
    untouched rows never change."""
    n_shards = int(mesh.shape[axis])
    row_like = tuple(s.shape == table.shape for s in state_vals)
    if n_shards <= 1:
        cl = jnp.clip(uniq, 0, table.shape[0] - 1)
        valid = uniq < table.shape[0]
        w_rows = table[cl]
        sv_rows = tuple(s[cl] if rl else s
                        for s, rl in zip(state_vals, row_like))
        new_rows, new_sv = stage_fn(w_rows, g_rows, sv_rows)
        safe = jnp.where(valid, cl, table.shape[0])
        new_tab = table.at[safe].set(new_rows, mode="drop")
        out_sv = tuple(
            s.at[safe].set(ns, mode="drop") if rl else ns
            for s, ns, rl in zip(state_vals, new_sv, row_like))
        return new_tab, out_sv

    rows_per = table.shape[0] // n_shards

    def local(tab, sv, ids, g):
        t = jax.lax.axis_index(axis)
        safe, _own = local_offsets(ids, t, rows_per)
        cl = jnp.clip(safe, 0, rows_per - 1)
        w_rows = tab[cl]
        sv_rows = tuple(s[cl] if rl else s
                        for s, rl in zip(sv, row_like))
        new_rows, new_sv = stage_fn(w_rows, g, sv_rows)
        # non-owned and sentinel ids carry safe == rows_per -> drop
        new_tab = tab.at[safe].set(new_rows, mode="drop")
        out_sv = tuple(
            s.at[safe].set(ns, mode="drop") if rl else ns
            for s, ns, rl in zip(sv, new_sv, row_like))
        return new_tab, out_sv

    def spec_of(a, rl):
        if not rl:
            return P()
        return P(*([axis] + [None] * (a.ndim - 1)))

    table_spec = P(*([axis] + [None] * (table.ndim - 1)))
    sv_specs = tuple(spec_of(s, rl)
                     for s, rl in zip(state_vals, row_like))
    return shard_map(
        local, mesh=mesh,
        in_specs=(table_spec, sv_specs, P(), P()),
        out_specs=(table_spec, sv_specs),
        check_vma=False)(table, tuple(state_vals), uniq, g_rows)


def scatter_rows(table, slots, rows, mesh, axis):
    """Write ``rows[i]`` into ``table[slots[i]]`` in place on the owning
    shard — ZERO collectives (every shard receives the replicated
    ``(M,)``/``(M, D)`` blocks and keeps only the slots it owns; the
    sentinel ``table.shape[0]`` and non-owned slots drop). The tiered
    hot cache's in-program scatter-in (shard/tiered.py): the
    RowPrefetcher stages incoming cold rows replicated, and the captured
    step lands them into freed cache slots before the lookup gathers.
    Axis size 1 degenerates to a local drop-scatter."""
    n_shards = int(mesh.shape[axis])
    if n_shards <= 1:
        safe = jnp.where(slots < table.shape[0], slots, table.shape[0])
        return table.at[safe].set(rows.astype(table.dtype), mode="drop")
    rows_per = table.shape[0] // n_shards

    def local(tab, s, r):
        t = jax.lax.axis_index(axis)
        safe, _own = local_offsets(s, t, rows_per)
        return tab.at[safe].set(r.astype(tab.dtype), mode="drop")

    table_spec = P(*([axis] + [None] * (table.ndim - 1)))
    return shard_map(local, mesh=mesh,
                     in_specs=(table_spec, P(), P()),
                     out_specs=table_spec, check_vma=False)(
                         table, slots, rows)


# ------------------------------------------------ capture integration
class SparseLookupContext:
    """Trace-time side channel between the captured step's program build
    (mxnet_tpu/cachedop.py) and `ShardedEmbedding.hybrid_forward`.

    ``record`` mode: the program's discovery pass runs the model trace
    once with this context installed; every sharded-lookup site
    registers its (param, index tracer) pair and returns a correctly-
    shaped ZEROS block WITHOUT touching the table value (the pass's
    outputs are unused, so XLA dead-code-eliminates everything but the
    recorded index extraction — and because lookups never reference the
    table, any remaining reference in the discovery jaxpr is a
    NON-lookup use, which cachedop demotes to the dense path rather
    than silently dropping its gradient). ``consume`` mode:
    inside the vjp'd forward, each site pops its pre-gathered row
    segment instead of touching the table — the table never enters the
    differentiated function, which is what makes the backward
    O(unique_rows) instead of O(vocab). Sites replay in trace order
    (same python, same order)."""

    _tl = threading.local()

    def __init__(self, mode, param_ids):
        self.mode = mode
        self.param_ids = frozenset(param_ids)
        self.sites = {}        # id(param) -> [idx tracer, ...]
        self.consume_plan = {}  # id(param) -> (rows, inv, segments, pos)

    @staticmethod
    def active():
        return getattr(SparseLookupContext._tl, "value", None)

    def __enter__(self):
        self._old = SparseLookupContext.active()
        SparseLookupContext._tl.value = self
        return self

    def __exit__(self, *exc):
        SparseLookupContext._tl.value = self._old

    def handles(self, param):
        return id(param) in self.param_ids

    # record mode -----------------------------------------------------
    def record(self, param, idx):
        self.sites.setdefault(id(param), []).append(idx)
        return None

    # consume mode ----------------------------------------------------
    def set_rows(self, param, rows, inv, segments):
        self.consume_plan[id(param)] = [rows, inv, segments, 0]

    def consume(self, param, idx):
        plan = self.consume_plan[id(param)]
        rows, inv, segments, pos = plan
        if pos >= len(segments):
            raise MXNetError(
                "sharded embedding: more lookup sites than the discovery "
                "pass recorded (non-deterministic model trace?)")
        off, shape = segments[pos]
        plan[3] = pos + 1
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        seg = jax.lax.dynamic_slice_in_dim(inv, off, n)
        return jnp.take(rows, seg, axis=0).reshape(
            tuple(shape) + rows.shape[1:])


def check_index_dtype(dtype):
    """Integer index dtypes pass through untouched; a float index batch
    raises (float32 loses integer exactness above 2**24 — at recommender
    scale that is a silent wrong-row lookup)."""
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        raise MXNetError(
            f"ShardedEmbedding: index batch has dtype {jnp.dtype(dtype)}; "
            f"integer indices are required (float32 cannot represent "
            f"row ids above 2**24 exactly — cast the input pipeline to "
            f"int32/int64 instead)")


def lookup(param, idx, weight):
    """One sharded-embedding lookup over raw jax values, honouring the
    active `SparseLookupContext` (capture path) and degrading to a
    dense integer take everywhere else (eager, imperative fallback,
    eval). `weight` is the table VALUE in the caller's scope (the
    traced override under capture, the live data otherwise)."""
    check_index_dtype(idx.dtype)
    ctx = SparseLookupContext.active()
    if ctx is not None and ctx.handles(param):
        if ctx.mode == "record":
            ctx.record(param, idx)
            # shape/dtype only — the table VALUE stays untouched, so
            # the discovery jaxpr's use-analysis sees lookup-only
            # tables as unreferenced (cachedop's demotion guard)
            return jnp.zeros(tuple(idx.shape) + tuple(weight.shape[1:]),
                             weight.dtype)
        return ctx.consume(param, idx)
    return jnp.take(weight, idx, axis=0)


# ------------------------------------------------------- eligibility
def sparse_eligibility(plan, diff, optimizer):
    """{position-in-diff: {"axis", "vocab", "dim"}} for every trainable
    parameter the sparse fast path can take: marked by
    `ShardedEmbedding` (``p._sharded_embedding``), 2-D, row-sharded by
    its rule over exactly ONE mesh axis that divides the vocab, under
    an elementwise optimizer (the row-block staging IS the dense rule
    restricted to touched rows only for elementwise updates). Anything
    else trains through the dense GSPMD path unchanged."""
    out = {}
    if plan is None or not type(optimizer).elementwise:
        return out
    for k, (i, p) in enumerate(diff):
        if not getattr(p, "_sharded_embedding", None):
            continue
        w = p.data()._data
        if w.ndim != 2:
            continue
        spec = tuple(plan.spec_for(p.name, w.shape))
        if not spec or spec[0] is None or not isinstance(spec[0], str):
            continue
        if any(e is not None for e in spec[1:]):
            continue
        n_ax = int(plan.mesh.shape[spec[0]])
        if n_ax < 1 or w.shape[0] % max(n_ax, 1):
            continue
        out[k] = {"axis": spec[0], "vocab": int(w.shape[0]),
                  "dim": int(w.shape[1])}
    return out


def embed_param_bytes_frac(plan, named_arrays):
    """Per-device / total byte fraction of the EMBEDDING-table subset of
    ``{name: array}`` under ``plan`` — the headline memory metric of the
    recommender workload (~= 1/axis_size when the embed rule row-shards
    every table). Tables are selected by the SAME name pattern the
    DEFAULT_RULES embedding rule shards (`rules.EMBED_WEIGHT_PATTERN` —
    "embedding0", DLRM-style "emb_cat3", ...). None when the set holds
    no embedding tables."""
    from .rules import EMBED_WEIGHT_PATTERN
    pat = re.compile(EMBED_WEIGHT_PATTERN)
    embed = {n: a for n, a in named_arrays.items() if pat.search(n)}
    if not embed:
        return None
    per_dev, total = plan.param_bytes_per_device(embed)
    return per_dev / total if total else None
