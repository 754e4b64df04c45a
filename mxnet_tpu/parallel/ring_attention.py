"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

First-class per the build brief (long-context training). Each device holds a
sequence shard of Q/K/V; K/V blocks rotate around the ring with
`lax.ppermute` while the local Q accumulates an online-softmax partial — the
blockwise/flash combine — so attention over sequence length S costs O(S/P)
memory per chip and the K/V transfers ride ICI neighbour links, overlapping
with the block matmuls (Liu et al., Ring Attention; PAPERS.md).

This IS ring *flash* attention (SURVEY #42): on TPU-tiling shard shapes the
per-step block compute is `ops.pallas_kernels.flash_block_attention` — the
Pallas flash kernel returning (out, lse) — and partials merge across ring
steps with the exact logsumexp combine; the backward reuses the Pallas
dq/dk/dv kernels through flash_block's custom vjp (the lse cotangent folds
in as a delta shift). Off-TPU / non-tiling shapes take the same math on the
XLA path inside flash_block_attention.

Causal masking decomposes per ring step by global shard index: the shard's
own block is causal, earlier shards are fully visible, later shards are
skipped (zero contribution) — chosen with `lax.switch` on the rotated
source index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size as _axis_size

from ..ops.pallas_kernels import flash_block_attention

__all__ = ["ring_attention", "ring_attention_sharded"]


def _as_varying(x, axis_name):
    """lax.pcast(x, axis, to='varying') where available; no-op off
    shard_map. NOTE: pcast takes axis_name positionally — the kwarg
    spelling used through round 4 raised TypeError on every call and
    silently fell through to the deprecated `pvary`,
    which is why the suite carried a DeprecationWarning."""
    try:
        from jax.lax import pcast
        return pcast(x, axis_name, to="varying")
    except Exception:
        try:  # pre-pcast JAX: attribute access alone warns, so gate it
            return jax.lax.pvary(x, axis_name)
        except Exception:
            return x


def ring_attention(q, k, v, axis_name="sp", causal=False, sm_scale=None):
    """Call INSIDE shard_map with q,k,v sequence-sharded: (B,H,S/P,D).

    Per ring step the local block attention is flash_block_attention
    (Pallas kernel on TPU shapes) returning a normalized partial + its
    logsumexp; partials merge with the exact combine
        lse' = logaddexp(lse, lse_b)
        o'   = o*exp(lse-lse') + o_b*exp(lse_b-lse')."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n_dev = _axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]
    b, h, _, d = q.shape
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def full_block(k_cur, v_cur):
        out, lse = flash_block_attention(q, k_cur, v_cur, False, sm_scale)
        return out.astype(jnp.float32), lse

    def diag_block(k_cur, v_cur):
        out, lse = flash_block_attention(q, k_cur, v_cur, True, sm_scale)
        return out.astype(jnp.float32), lse

    def skip_block(k_cur, v_cur):
        # zero contribution, derived from the (device-varying) inputs so all
        # switch branches agree on varying-manner WITHOUT a pcast — pcast's
        # transpose is a psum, which breaks under outer shard_maps running
        # check_vma=False (composite 5-axis step)
        zero = q.astype(jnp.float32) * 0.0
        return zero, zero[..., 0] - 1e30

    def step(carry, i):
        k_cur, v_cur, o_acc, lse_acc = carry
        src = (my_idx - i) % n_dev      # which shard this K/V block is
        if causal:
            # later shards (src > my_idx) are wholly in the future: skip;
            # my own shard is the causal diagonal; earlier are fully seen
            branch = jnp.where(src == my_idx, 1,
                               jnp.where(src < my_idx, 0, 2))
            o_b, lse_b = jax.lax.switch(
                branch, [full_block, diag_block, skip_block], k_cur, v_cur)
        else:
            o_b, lse_b = full_block(k_cur, v_cur)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - lse_new)[..., None]
        w_b = jnp.exp(lse_b - lse_new)[..., None]
        o_new = o_acc * w_acc + o_b * w_b
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, o_new, lse_new), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), -1e30, jnp.float32)
    # mark the accumulators device-varying so the scan carry types agree
    # under shard_map's VMA checking (the k/v carries vary via ppermute)
    o0, lse0 = (_as_varying(t, axis_name) for t in (o0, lse0))
    carry, _ = jax.lax.scan(step, (k, v, o0, lse0), jnp.arange(n_dev))
    _, _, o, _lse = carry
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False):
    """Convenience wrapper: shard (B,H,S,D) arrays over S and run the ring."""
    spec = P(None, None, axis_name, None)
    f = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return f(q, k, v)
