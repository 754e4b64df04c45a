"""Dropless grouped matmul: every row goes through the weights of the
group it was routed to, however unevenly the rows spread over the groups.

The expert layer of a mixture of experts routes each token to a few of
many experts. `shard/moe.py` gives every expert a buffer of fixed
capacity and drops what does not fit; this file has no capacity. Rows are
laid out group after group, each group's rows starting on a tile of
`tile` rows (`layout`), so a tile belongs to one group, and the matmul
walks the tiles with that tile's weights (`grouped_matmul`): on the TPU a
Pallas kernel whose weight block is chosen by the tile's group, read from
scalar-prefetch memory, with the tile axis innermost so that the tiles of
one group reuse the weights already on the chip; elsewhere the same
product as one batched einsum over tiles. The layout has room for every
row at once (all rows times all choices, plus a tile of padding a group),
so nothing is ever dropped; tiles past the last used one are skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["layout", "grouped_matmul", "rows_capacity"]


def rows_capacity(n_assign, n_groups, tile):
    """Rows of the padded layout that hold `n_assign` rows in `n_groups`
    groups whatever the split: each group wastes less than one tile."""
    return -(-(n_assign + n_groups * (tile - 1)) // tile) * tile


def layout(group, n_groups, tile):
    """Where each routed row goes, and which row lies where. group: (A,)
    int32, the group of each row, `n_groups` for a row that no group here
    takes. Returns (dest (A,) int32 row in the padded layout,
    `rows_capacity` for a row not taken; tile_group (tiles,) int32;
    tiles_used () int32; counts (n_groups,) int32 rows taken by each
    group; src (cap,) int32, the inverse of dest: the row of `group` that
    lies in each row of the layout, row 0 where none does; live (cap,)
    bool, whether one does). The rows of a group keep the order they came
    in. Lay rows out with ONE gather, `x[src]`: a scatter of rows through
    dest pays for every row, taken or not (`models/decoder_lm.py`,
    `mx_moe_dispatch`)."""
    a = group.shape[0]
    cap = rows_capacity(a, n_groups, tile)
    every = jnp.zeros((n_groups + 1,), jnp.int32).at[group].add(1)
    counts = every[:-1]             # the last entry: rows not taken
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    # a row's rank among the rows of its group, in the order given
    order = jnp.argsort(group, stable=True)
    first = jnp.cumsum(every) - every
    rank = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32) - first[group[order]])
    starts = jnp.append(ends - padded, 0)
    dest = jnp.where(group < n_groups, starts[group] + rank, cap)
    tile_start = jnp.arange(cap // tile, dtype=jnp.int32) * tile
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right").astype(jnp.int32),
        n_groups - 1)
    # the inverse of dest; a row not taken has dest == cap and is dropped
    src = jnp.full((cap,), -1, jnp.int32).at[dest].set(
        jnp.arange(a, dtype=jnp.int32), mode="drop")
    return (dest, tile_group, (ends[-1] // tile).astype(jnp.int32), counts,
            jnp.maximum(src, 0), src >= 0)


def _gmm_kernel(tg_ref, used_ref, x_ref, w_ref, o_ref, *, nt):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (1 if nt else 0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _block_n(k, n, itemsize):
    """Widest column block (a multiple of 128 that divides n) whose
    double-buffered weight block stays near 8 MB of fast memory; an n
    that is not whole 128-lane tiles admits only itself as a block."""
    if n % 128:
        return n
    best = 128
    for bn in range(128, n + 1, 128):
        if n % bn == 0 and 2 * k * bn * itemsize <= 8 * 2 ** 20:
            best = bn
    return best


def _gmm_pallas(x, w, tile_group, tiles_used, tile, nt):
    m, k = x.shape
    n = w.shape[1 if nt else 2]
    bn = _block_n(k, n, w.dtype.itemsize)
    tiles = m // tile

    def at(i, used):        # a skipped tile keeps the last used tile's
        return jnp.minimum(i, jnp.maximum(used[0], 1) - 1)   # blocks

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, tiles),
        in_specs=[
            pl.BlockSpec((tile, k), lambda j, i, tg, used: (at(i, used), 0)),
            pl.BlockSpec((1, bn, k) if nt else (1, k, bn),
                         lambda j, i, tg, used: (
                             (tg[at(i, used)], j, 0) if nt
                             else (tg[at(i, used)], 0, j))),
        ],
        out_specs=pl.BlockSpec((tile, bn),
                               lambda j, i, tg, used: (at(i, used), j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nt=nt), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a whole-width weight block, double-buffered, needs its room
            vmem_limit_bytes=max(32 * 2 ** 20,
                                 3 * k * bn * w.dtype.itemsize)),
        interpret=_pk._interpret(),
        name="mxtpu_gmm",
    )(tile_group, tiles_used.reshape(1), x, w)


def _whole(v):
    """A width the kernel takes: whole 128-lane tiles, or more than one of
    them in whole 16-row sublane tiles, taken as ONE block (1856 = 14.5
    x 128)."""
    return v % 128 == 0 or (v > 128 and v % 16 == 0)


def grouped_matmul(x, w, tile_group, tiles_used, tile, nt=False):
    """x: (M, K) rows in `layout`'s order, M whole tiles; w: (G, K, N),
    or with `nt` (G, N, K), each group's matrix (out, in) as a dense
    layer keeps it (the shape to KEEP a bank in whose N is not whole
    128-lane tiles: the device lays an array out row-major only where its
    minor dimension is); tile_group: (M // tile,) the group of each tile;
    tiles_used: () int32. Returns (M, N) in x's dtype: rows of used tiles
    hold x @ w[group] (x @ w[group].T with `nt`), the others anything."""
    m, k = x.shape
    n = w.shape[1 if nt else 2]
    if (_pk.on_tpu() or _pk._interpret()) and tile % 8 == 0 and _whole(k) \
            and (n % 128 == 0 or nt and _whole(n)):
        return _gmm_pallas(x, w, tile_group, tiles_used, tile, nt)
    xt = x.reshape(m // tile, tile, k)
    out = jnp.einsum("tmk,tnk->tmn" if nt else "tmk,tkn->tmn", xt,
                     w[tile_group], preferred_element_type=jnp.float32)
    return out.astype(x.dtype).reshape(m, n)
