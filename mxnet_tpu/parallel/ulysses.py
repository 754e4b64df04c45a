"""All-to-all (Ulysses-style) sequence parallelism over the 'sp' axis.

The second long-context strategy alongside `ring_attention` (the build
brief asks for ring OR all-to-all context parallelism; this framework
ships both — they trade differently):

  * ring: K/V rotate around the ICI ring, O(S/P) memory, P ppermute steps,
    best when S is huge and heads are few;
  * all-to-all (Ulysses, DeepSpeed-style): one stacked `lax.all_to_all`
    swaps the sharded dimension — sequence-sharded q/k/v
    (B, S/P, H, Dh) become head-sharded full-sequence blocks
    (B, S, H/P, Dh) in a single collective over the stacked triple —
    every device runs ordinary full attention (the Pallas flash kernel)
    for its head subset, and one reverse all-to-all restores sequence
    sharding. Communication volume: 4 activation-sized tensors per
    forward (q+k+v in, out back), independent of P, and the attention
    itself needs NO cross-device math — best when H >= P and the
    interconnect does all-to-all well (TPU ICI does).

Both compose with the same outer sharding: inputs/outputs are
sequence-sharded, so either can drop into a tp/dp program unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import PartitionSpec as P

from ..ops.pallas_kernels import flash_block_attention

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name="sp", causal=False, sm_scale=None):
    """Call INSIDE shard_map with q/k/v sequence-sharded: (B, S/P, H, Dh).
    Requires H divisible by the axis size. Returns (B, S/P, H, Dh)."""
    p = _axis_size(axis_name)
    b, s_loc, h, dh = q.shape
    if h % p:
        raise ValueError(f"ulysses_attention: heads {h} not divisible by "
                         f"axis {axis_name!r} size {p}")

    def head_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    # ONE stacked collective for q/k/v instead of three back-to-back
    # all_to_alls (collective launch latency dominates at small shards):
    # (3, B, S/P, H, Dh) -> split heads (axis 3), gather sequence (axis 2)
    qkv = jnp.stack([q, k, v])
    qkv = lax.all_to_all(qkv, axis_name, split_axis=3, concat_axis=2,
                         tiled=True)
    qh, kh, vh = qkv[0], qkv[1], qkv[2]
    # local FULL attention over this device's head subset; flash kernel
    # wants (B, H, S, Dh)
    qt = jnp.swapaxes(qh, 1, 2)
    kt = jnp.swapaxes(kh, 1, 2)
    vt = jnp.swapaxes(vh, 1, 2)
    out, _lse = flash_block_attention(qt, kt, vt, causal, sm_scale)
    out = jnp.swapaxes(out, 1, 2)            # (B, S, H/P, Dh)
    return head_to_seq(out)                   # (B, S/P, H, Dh)


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False):
    """Convenience wrapper: shard (B, S, H, Dh) arrays over S and run the
    all-to-all attention."""
    spec = P(None, axis_name, None, None)
    f = shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return f(q, k, v)
