"""Expert parallelism: sharded Mixture-of-Experts token routing over
the embedding all-to-all skeleton (ISSUE 16; Switch Transformer
arXiv:2101.03961, GShard arXiv:2006.16668).

A `gluon.nn.ShardedMoE` layer replaces one dense FFN with ``E`` expert
FFNs and a learned top-k router. The expert banks — stacked
``(E, d, h)`` / ``(E, h, d)`` weights — row-shard over one named mesh
axis (the partition rules route ``expert*_weight``/``_bias`` to 'tp' by
default), so each device holds ``E / tp`` experts and the per-device
parameter bytes of the FFN stack shrink by the axis size while
per-token FLOPs stay at ``k`` experts' worth. The dispatch is the
shard/exchange.py skeleton with experts as the owner groups:

  1. gate: top-k softmax over expert logits per token, with the
     load-balancing auxiliary loss ``E * sum_e f_e * P_e`` (f_e =
     fraction of routed (token, choice) pairs on expert e, P_e = mean
     router probability) threaded into the captured loss;
  2. rank each (token, choice) within its expert (`group_ranks`;
     first-choice assignments outrank second choices — GShard
     priority), scatter into a static ``(E, C, d)`` capacity buffer.
     ``C = ceil(capacity_factor * k * tokens_local / E)``; slots past C
     DROP, and every drop is accounted (`moe_tokens_dropped` counter,
     per-layer overflow fraction — never silent);
  3. ONE all-to-all sends each expert's slots to its owner shard, the
     owner runs its local experts' FFNs on ``tp * C`` slots each, ONE
     all-to-all returns the outputs — `A2A_PER_LAYER` = 2 collectives
     per layer per pass, the count tools/check_fusion.py pins;
  4. combine: gather each choice's output slot, zero dropped choices,
     gate-weighted scatter-add back to token order. A dropped token's
     MoE contribution is exactly 0 — with the block's residual
     connection it passes through unchanged, gradients included.

Tokens shard over ``(data_axis, axis)`` jointly when the flat token
count divides — the GShard layout where the expert-axis peers each own
a distinct token slice, so the all-to-alls move real data. Axis size 1
(or a non-divisible token/expert count, reported via the capture tape)
degenerates to pure local dispatch with 0 collectives, mirroring
`gather_rows`.

Unlike the embedding fast path, the expert banks stay INSIDE the
step's ``jax.vjp`` (activations depend on upstream parameters, so
there is nothing to hoist): the backward transposes each all-to-all
into another all-to-all, and a captured training step therefore lowers
``A2A_PER_LAYER * STEP_TRAVERSALS`` = 4 all-to-alls per layer —
forward dispatch/combine plus their exact adjoints. check_fusion pins
that product in-process so neither constant can drift.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from .exchange import exchange, group_ranks

__all__ = ["A2A_PER_LAYER", "STEP_TRAVERSALS", "capacity",
           "routing_layout", "moe_forward", "a2a_bytes_per_step",
           "capture_scope", "current_plan", "report_aux_loss",
           "report_site"]


# Collectives per MoE layer per PASS: the dispatch all-to-all plus the
# combine all-to-all (shard/exchange.py `exchange` calls in
# `_routed_ffn`). A captured TRAINING step traverses each layer
# STEP_TRAVERSALS times — the forward pass and its vjp transpose
# (all_to_all transposes to all_to_all) — so the step executable holds
# A2A_PER_LAYER * STEP_TRAVERSALS all-to-alls per layer.
# tools/check_fusion.py derives its exact `moe_step` pin from these two
# constants and the fixture's layer count; change one without the other
# and the gate fails loudly.
A2A_PER_LAYER = 2
STEP_TRAVERSALS = 2

_ACTS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
         "silu": jax.nn.silu, "swish": jax.nn.silu, "tanh": jnp.tanh}


def capacity(n_tokens, n_experts, k, capacity_factor):
    """Static per-expert slot count for one device's routed tokens:
    ``max(1, ceil(capacity_factor * k * n_tokens / n_experts))`` —
    capacity_factor 1.0 holds a perfectly balanced assignment exactly;
    the headroom above 1.0 absorbs imbalance before dropping."""
    return max(1, int(math.ceil(
        float(capacity_factor) * k * n_tokens / n_experts)))


def routing_layout(n_tokens, n_experts, k, capacity_factor,
                   mesh=None, axis=None, data_axis=None):
    """Resolve the static dispatch geometry for one MoE layer — shared
    by `moe_forward` and the byte/count accounting so they cannot
    drift. Returns a dict:

      ``sharded``      — True when the 2-a2a expert-parallel path runs
      ``reason``       — why not, when it doesn't (``axis_size_1``,
                         ``experts_not_divisible``,
                         ``tokens_not_divisible``, ``no_mesh``)
      ``batch_axes``   — mesh axes the flat token dim shards over
      ``n_exp_shards`` — devices the expert bank splits across
      ``n_tok_shards`` — distinct token slices (dp*tp or tp)
      ``tokens_local`` — tokens routed per device
      ``capacity``     — per-expert slots per source device
    """
    n_exp = 1
    reason = None
    sizes = {}
    if mesh is None or axis is None:
        reason = "no_mesh"
    else:
        sizes = dict(mesh.shape)
        n_exp = int(sizes.get(axis, 1))
        if n_exp <= 1:
            reason, n_exp = "axis_size_1", 1
        elif n_experts % n_exp:
            reason, n_exp = "experts_not_divisible", 1
    batch_axes = ()
    n_tok = 1
    if n_exp > 1:
        n_dp = int(sizes.get(data_axis, 1)) if data_axis else 1
        if n_dp > 1 and n_tokens % (n_dp * n_exp) == 0:
            batch_axes, n_tok = (data_axis, axis), n_dp * n_exp
        elif n_tokens % n_exp == 0:
            batch_axes, n_tok = (axis,), n_exp
        else:
            reason, n_exp = "tokens_not_divisible", 1
    n_loc = n_tokens // n_tok
    return {"sharded": n_exp > 1, "reason": reason,
            "batch_axes": batch_axes, "n_exp_shards": n_exp,
            "n_tok_shards": n_tok, "tokens_local": n_loc,
            "capacity": capacity(n_loc, n_experts, k, capacity_factor)}


def _routed_ffn(x, gate_w, w1, b1, w2, b2, *, n_experts, k, cap, act,
                normalize, axis, n_shards):
    """Per-device gate + dispatch + expert FFN + combine. ``x`` is this
    device's ``(N, d)`` token slice; the expert banks are the LOCAL
    ``E / n_shards`` slice when ``n_shards > 1`` (inside shard_map),
    the full stack otherwise. Returns ``(y, aux, drop_frac, n_drop)``
    with the stats un-reduced (the sharded wrapper pmean/psums them)."""
    N, d = x.shape
    logits = jnp.einsum("nd,ed->ne", x, gate_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)            # (N, k)
    if normalize and k > 1:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)
    # load-balance aux (Switch §2.2, generalised to k choices): both
    # factors are per-expert means, so a uniform router minimises it
    assign = jnp.zeros((n_experts,), probs.dtype)
    assign = assign.at[top_e.reshape(-1)].add(1.0, mode="drop")
    aux = float(n_experts) * jnp.sum(
        (assign / float(N * k)) * jnp.mean(probs, axis=0))

    # k-major flatten: every token's 1st choice outranks ALL 2nd
    # choices when capacity truncates (GShard priority)
    flat_e = top_e.T.reshape(-1)                      # (k*N,)
    tok = jnp.tile(jnp.arange(N), k)
    order, _, rank_sorted = group_ranks(flat_e, n_experts)
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                 # cap slot -> drop
    buf = jnp.zeros((n_experts, cap, d), x.dtype)
    buf = buf.at[flat_e, slot].set(x[tok], mode="drop")

    e_loc = n_experts // n_shards
    if n_shards > 1:
        recv = exchange(buf.reshape(n_shards, e_loc, cap, d), axis)
        xin = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_shards * cap, d)
    else:
        xin = buf                                     # (E, cap, d)
    h = act(jnp.einsum("ecd,edh->ech", xin, w1) + b1[:, None, :])
    y = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    if n_shards > 1:
        send = y.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
        out_buf = exchange(send, axis).reshape(n_experts, cap, d)
    else:
        out_buf = y

    got = out_buf[flat_e, jnp.minimum(slot, cap - 1)]  # (k*N, d)
    got = jnp.where(keep[:, None], got, 0.0)
    comb = jnp.zeros((N, d), x.dtype)
    comb = comb.at[tok].add(got * top_p.T.reshape(-1)[:, None])
    n_drop = jnp.sum((~keep).astype(jnp.float32))
    drop_frac = n_drop / float(N * k)
    return comb, aux, drop_frac, n_drop


def moe_forward(x, gate_w, w1, b1, w2, b2, *, n_experts, k=2,
                capacity_factor=1.25, activation="relu",
                normalize_gates=True, mesh=None, axis=None,
                data_axis=None):
    """One MoE layer over raw jax values: ``x (N, d)``, router
    ``gate_w (E, d)``, expert banks ``w1 (E, d, h)``, ``b1 (E, h)``,
    ``w2 (E, h, d)``, ``b2 (E, d)``. With a mesh whose ``axis`` sizes
    > 1 (and divisible expert/token counts) this lowers the 2-a2a
    expert-parallel path; otherwise a pure local dispatch with zero
    collectives. Returns ``(y, aux_loss, drop_frac, n_dropped)`` —
    ``y (N, d)``, scalars replicated."""
    act = _ACTS[activation]
    lay = routing_layout(int(x.shape[0]), n_experts, k, capacity_factor,
                         mesh=mesh, axis=axis, data_axis=data_axis)
    if not lay["sharded"]:
        return _routed_ffn(x, gate_w, w1, b1, w2, b2,
                           n_experts=n_experts, k=k, cap=lay["capacity"],
                           act=act, normalize=normalize_gates,
                           axis=None, n_shards=1)
    batch_axes = lay["batch_axes"]
    n_exp = lay["n_exp_shards"]
    cap = lay["capacity"]

    def local(xl, gw, w1l, b1l, w2l, b2l):
        y, aux, _, drops = _routed_ffn(
            xl, gw, w1l, b1l, w2l, b2l, n_experts=n_experts, k=k,
            cap=cap, act=act, normalize=normalize_gates, axis=axis,
            n_shards=n_exp)
        # stats discipline (graphlint MXTPU-G03 shaped this): the drop
        # fraction is DERIVED from the psum'd count — frac is
        # drops * const, so reducing it separately duplicates the psum
        # once XLA hoists the multiply. And aux leaves the shard_map
        # UN-reduced as a per-shard (1,) slice, meaned outside: a
        # pmean here would transpose to one all-reduce per layer of
        # the SAME replicated cotangent scalar in the backward —
        # textbook duplicate collectives — while the mean-of-sharded-
        # vector transposes to a collective-free broadcast.
        drops = jax.lax.psum(drops, batch_axes)
        frac = drops / float(lay["n_tok_shards"] * lay["tokens_local"] * k)
        return y, aux.reshape(1), frac, drops

    tok_entry = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    xspec = P(tok_entry, *([None] * (x.ndim - 1)))
    e3, e2 = P(axis, None, None), P(axis, None)
    y, aux_vec, frac, drops = shard_map(
        local, mesh=mesh,
        in_specs=(xspec, P(), e3, e2, e3, e2),
        out_specs=(xspec, P(tok_entry), P(), P()),
        check_vma=False)(x, gate_w, w1, b1, w2, b2)
    return y, jnp.mean(aux_vec), frac, drops


def a2a_bytes_per_step(layout, n_experts, units, itemsize):
    """Forward-pass wire bytes of one layer's dispatch + combine summed
    over the distinct token slices (same convention as the embedding
    path's ``embed_bytes``: forward collectives only, each device's
    full static buffer counted once per a2a). 0 on the local path."""
    if not layout["sharded"]:
        return 0
    buf = n_experts * layout["capacity"] * units * itemsize
    return A2A_PER_LAYER * layout["n_tok_shards"] * buf


# ------------------------------------------------ capture integration
class _CaptureState:
    """Trace-time side channel between the captured step's program
    build (mxnet_tpu/cachedop.py) and `ShardedMoE.hybrid_forward`: the
    active shard plan flows down (so the block can resolve its expert
    axis), aux losses and per-site routing stats flow up (so the step
    adds the losses to the captured loss and prices the collectives)."""
    __slots__ = ("plan", "losses", "sites")

    def __init__(self, plan):
        self.plan = plan
        self.losses = []   # NDArray scalars, already coefficient-scaled
        self.sites = []    # dicts from `report_site`


_tl = threading.local()


def _state():
    return getattr(_tl, "state", None)


@contextmanager
def capture_scope(plan):
    """Install a fresh capture state (nesting restores the outer one).
    cachedop wraps every functional run of loss_fn — the prepass, the
    discovery pass and the program trace — in one of these."""
    prev = _state()
    st = _CaptureState(plan)
    _tl.state = st
    try:
        yield st
    finally:
        _tl.state = prev


def current_plan():
    """The shard plan of the enclosing captured step, or None (eager /
    hybridized / un-planned capture — the local dispatch path)."""
    st = _state()
    return st.plan if st is not None else None


def report_aux_loss(loss_nd):
    """Offer a scaled aux-loss scalar to the enclosing capture. Returns
    True when a capture collected it (the step adds it to the loss
    head); False means no capture is active and the CALLER owns it."""
    st = _state()
    if st is None:
        return False
    st.losses.append(loss_nd)
    return True


def report_site(info):
    """Record one MoE layer's static routing geometry (dict from
    `routing_layout` plus name/bytes) for the step's accounting."""
    st = _state()
    if st is not None:
        st.sites.append(dict(info))
