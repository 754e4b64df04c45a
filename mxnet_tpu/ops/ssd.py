"""The Mamba-2 state-space recurrence (SSD, arXiv:2405.21060): a scalar
decay a head over a (head_dim, state_size) state, inputs and outputs
through B and C vectors that a GROUP of heads shares.

    S_t = a_t S_{t-1} + delta_t x_t B_t^T,   a_t = exp(delta_t A),  A < 0
    y_t = S_t C_t

x_t: (P,) a head; B_t, C_t: (N,) a group; S: (P, N) a head, float32
whatever the inputs are. The skip term D x_t, the gate and the output
norm belong to the layer (`models/decoder_lm.py`). Two forms:

  * `ssd_step`: one position for a batch of states, what a decode turn
    runs for every slot; `ssd_step_slots` is the same for a server's
    slot-major state `(slots, H, P, N)`: on the TPU one Pallas kernel,
    `mxtpu_ssd_step`, that reads a slot's state once and writes it once
    in place;
  * `ssd_chunked`: a whole sequence in chunks, what prefill runs. With
    G_t the running sum of delta A inside a chunk that starts from S,
        y_t = e^{G_t} S C_t + sum_{s <= t} (C_t . B_s) e^{G_t - G_s}
              delta_s x_s,
    so a chunk is a (chunk, chunk) score matrix a GROUP, masked and
    decayed a head, and three matmuls. Every exponent is G_t - G_s with
    s <= t, never positive.

Positions where `delta == 0` leave the state as it was (padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["ssd_step", "ssd_step_slots", "ssd_chunked"]

_F32 = jnp.float32


def _per_head(a, heads):
    """A group's vectors (..., G, N) for each of its heads (..., H, N):
    head h reads group h // (H / G)."""
    return jnp.repeat(a, heads // a.shape[-2], -2)


def ssd_step(x, delta, a_neg, b, c, state):
    """One position. x: (..., H, P); delta: (..., H), the step size after
    its softplus; a_neg: (H,), A itself (negative); b, c: (..., G, N);
    state: (..., H, P, N) float32. Returns (y (..., H, P) float32, the
    new state). Multiply-and-sum on the vector unit, not a matmul: a
    float32 matmul at the default precision would round the state to
    bfloat16 on the way."""
    x, delta, b, c = (v.astype(_F32) for v in (x, delta, b, c))
    h = x.shape[-2]
    decay = jnp.exp(delta * a_neg.astype(_F32))
    state = decay[..., None, None] * state \
        + (delta[..., None] * x)[..., None] * _per_head(b, h)[..., None, :]
    return jnp.sum(state * _per_head(c, h)[..., None, :], -1), state


def _ssd_step_kernel(b_ref, c_ref, at_ref, xt_ref, s_ref, yt_ref, so_ref,
                     *, heads, per_group):
    """One slot, all its heads. A head's state is (P, N): N runs over the
    lanes, so B and C are rows (one a group), and the decay, the scaled
    input and the output are columns over P, handed in and out
    transposed, (P, H)."""
    for h in range(heads):
        g = h // per_group
        st = s_ref[0, h] * at_ref[0, :, h:h + 1] \
            + xt_ref[0, :, h:h + 1] * b_ref[0, g:g + 1, :]    # (P, N)
        so_ref[0, h] = st
        yt_ref[0, :, h:h + 1] = jnp.sum(st * c_ref[0, g:g + 1, :], axis=-1,
                                        keepdims=True)


def _ssd_step_pallas(dx, decay, b, c, state):
    s, h, p, n = state.shape
    g = b.shape[1]
    rows = pl.BlockSpec((1, g, n), lambda i: (i, 0, 0))
    cols = pl.BlockSpec((1, p, h), lambda i: (i, 0, 0))
    tile = pl.BlockSpec((1, h, p, n), lambda i: (i, 0, 0, 0))
    yt, state = pl.pallas_call(
        functools.partial(_ssd_step_kernel, heads=h, per_group=h // g),
        grid=(s,), in_specs=[rows, rows, cols, cols, tile],
        out_specs=[cols, tile],
        out_shape=[jax.ShapeDtypeStruct((s, p, h), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=_pk._interpret(),
        name="mxtpu_ssd_step",
    )(b, c, jnp.broadcast_to(decay[:, None, :], (s, p, h)),
      dx.transpose(0, 2, 1), state)
    return yt.transpose(0, 2, 1), state


def ssd_step_slots(x, delta, a_neg, b, c, state):
    """`ssd_step` for S slots: x (S, H, P); delta (S, H); a_neg (H,); b,
    c (S, G, N); state (S, H, P, N) float32. Returns (y (S, H, P)
    float32, the new state). On the TPU one kernel, the state aliased in
    place: a read and a write of it."""
    p, n = state.shape[-2:]
    if (_pk.on_tpu() or _pk._interpret()) and p % 8 == 0 and n % 128 == 0:
        x, delta, b, c = (v.astype(_F32) for v in (x, delta, b, c))
        return _ssd_step_pallas(delta[..., None] * x,
                                jnp.exp(delta * a_neg.astype(_F32)), b, c,
                                state)
    return ssd_step(x, delta, a_neg, b, c, state)


def ssd_chunked(x, delta, a_neg, b, c, state, chunk=128, length=None):
    """A sequence of T positions (T a multiple of `chunk`). x: (T, H, P);
    delta: (T, H); a_neg: (H,); b, c: (T, G, N); state: (H, P, N)
    float32. Returns (y (T, H, P) float32, the final state). `length` (a
    traced scalar): only the chunks that hold the first `length`
    positions are run, the outputs past them left zero, so a padded
    prompt costs what its real length costs."""
    x, delta, b, c = (v.astype(_F32) for v in (x, delta, b, c))
    t, h, p = x.shape
    n_c = chunk
    if t % n_c:
        raise ValueError(f"{t} positions are not whole chunks of {n_c}")
    lower = jnp.tril(jnp.ones((n_c, n_c), bool))         # s <= t
    log_a = delta * a_neg.astype(_F32)                   # (T, H), <= 0
    dx = delta[..., None] * x

    def mm(spec, u, v):      # float32 all the way: the state is float32
        return jnp.einsum(spec, u, v, precision=lax.Precision.HIGHEST)

    def one_chunk(i, carry):
        s, out = carry
        gc, dxc, bc, cc = (lax.dynamic_slice_in_dim(v, i * n_c, n_c, 0)
                           for v in (log_a, dx, b, c))
        G = jnp.cumsum(gc, axis=0)                       # (C, H)
        # e^{G_t - G_s} over pairs (t, s): later-minus-earlier only, the
        # rest masked before the exponential
        decay = jnp.exp(jnp.where(lower[..., None],
                                  G[:, None] - G[None, :], -jnp.inf))
        scores = jnp.repeat(mm("tgn,sgn->tsg", cc, bc), h // bc.shape[1],
                            -1) * decay                  # (C, C, H)
        y = mm("tsh,shp->thp", scores, dxc) + jnp.exp(G)[..., None] * mm(
            "thn,hpn->thp", _per_head(cc, h), s)
        to_end = jnp.exp(G[-1] - G)                      # (C, H)
        s = jnp.exp(G[-1])[:, None, None] * s + mm(
            "shp,shn->hpn", dxc * to_end[..., None], _per_head(bc, h))
        return s, lax.dynamic_update_slice_in_dim(out, y, i * n_c, 0)

    n = t // n_c if length is None else (length + n_c - 1) // n_c
    state, y = lax.fori_loop(0, n, one_chunk,
                             (state.astype(_F32), jnp.zeros((t, h, p), _F32)))
    return y, state
