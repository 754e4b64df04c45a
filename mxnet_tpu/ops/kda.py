"""Kimi Delta Attention (KDA, arXiv:2510.26692): the gated delta rule
with a decay per channel, as a recurrence over a (d_k, d_v) state a head.

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        a_t = exp(g_t),  g_t <= 0

Two forms of the same mathematics:

  * `kda_step`: one position for a batch of states, what a decode turn
    runs for every slot; `kda_step_slots` is the same for a server's
    slot-major state kept value-major, `(slots, H, d_v, d_k)`: on the TPU
    one Pallas kernel that reads a slot's state once and writes it once;
  * `kda_chunked`: a whole sequence in chunks of `chunk` positions, what
    prefill runs. With D_t = Diag(a_t) the recurrence is
    S_t = D_t S_{t-1} + k_t u_t^T,  u_t = beta_t (v_t - (D_t S_{t-1})^T k_t),
    so inside a chunk that starts from S, with G_t the running sum of g,
        (I + Diag(beta) A) U = Diag(beta) (V - (K * e^G) S),
        A[t, i] = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}   (i < t)
    is one unit-lower-triangular solve a chunk, and outputs and the next
    state are matmuls. Every exponent is G_t - G_i with i <= t, never
    positive: no decay is inverted, so a strong decay cannot overflow.

The state is float32 whatever the inputs are. Positions where
`beta == 0` and `g == 0` leave the state as it was (padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["kda_step", "kda_step_slots", "kda_chunked", "causal_conv",
           "causal_conv_step"]

_F32 = jnp.float32


def kda_step(q, k, v, g, beta, state):
    """One position. q, k, g: (..., d_k); v: (..., d_v); beta: (...);
    state: (..., d_k, d_v) float32. Returns (o (..., d_v) float32, the
    new state). q and k are both applied to the decayed state, so the new
    state is not read again: o_t = (D S)^T q + u_t (k_t . q_t)."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * state
    # multiply-and-sum on the vector unit, not a matmul: a float32 matmul
    # at the default precision would round the state to bfloat16 on the way
    k_s = jnp.sum(k[..., None] * decayed, -2)
    q_s = jnp.sum(q[..., None] * decayed, -2)
    u = beta[..., None] * (v - k_s)
    o = q_s + u * jnp.sum(k * q, -1, keepdims=True)
    return o, decayed + k[..., :, None] * u[..., None, :]


def _kda_step_kernel(k_ref, q_ref, a_ref, vt_ref, bt_ref, s_ref, ot_ref,
                     so_ref, *, heads):
    """One slot, all its heads. The state is value-major, (d_v, d_k) a
    head, so k, q and the decay are rows (lanes run over d_k) and v, beta
    and the output are columns, handed in and out transposed, (d_v, H)."""
    for h in range(heads):
        k = k_ref[0, h:h + 1, :]                         # (1, dk)
        st = s_ref[0, h] * a_ref[0, h:h + 1, :]          # decayed, (dv, dk)
        k_s = jnp.sum(st * k, axis=-1, keepdims=True)    # (dv, 1)
        u = bt_ref[0, :, h:h + 1] * (vt_ref[0, :, h:h + 1] - k_s)
        st = st + u * k
        so_ref[0, h] = st
        ot_ref[0, :, h:h + 1] = jnp.sum(st * q_ref[0, h:h + 1, :], axis=-1,
                                        keepdims=True)


def _kda_step_pallas(q, k, v, g, beta, state_t):
    s, h, dv, dk = state_t.shape
    rows = pl.BlockSpec((1, h, dk), lambda i: (i, 0, 0))
    cols = pl.BlockSpec((1, dv, h), lambda i: (i, 0, 0))
    tile = pl.BlockSpec((1, h, dv, dk), lambda i: (i, 0, 0, 0))
    ot, state_t = pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=h),
        grid=(s,), in_specs=[rows, rows, rows, cols, cols, tile],
        out_specs=[cols, tile],
        out_shape=[jax.ShapeDtypeStruct((s, dv, h), _F32),
                   jax.ShapeDtypeStruct(state_t.shape, _F32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=_pk._interpret(),
        name="mxtpu_kda_step",
    )(k, q, jnp.exp(g), v.transpose(0, 2, 1),
      jnp.broadcast_to(beta[:, None, :], (s, dv, h)), state_t)
    return ot.transpose(0, 2, 1), state_t


def kda_step_slots(q, k, v, g, beta, state_t):
    """`kda_step` for S slots of H heads whose state is kept value-major:
    q, k, g: (S, H, d_k); v: (S, H, d_v); beta: (S, H); state_t:
    (S, H, d_v, d_k) float32 (each head's state transposed). Returns
    (o (S, H, d_v) float32, the new state_t). On the TPU one kernel, the
    state aliased in place: a read and a write of it, where the unfused
    form reads it three times."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    dv, dk = state_t.shape[-2:]
    if (_pk.on_tpu() or _pk._interpret()) and dv % 8 == 0 and dk % 128 == 0:
        return _kda_step_pallas(q, k, v, g, beta, state_t)
    o, state = kda_step(q, k, v, g, beta, state_t.swapaxes(-1, -2))
    return o, state.swapaxes(-1, -2)


def kda_chunked(q, k, v, g, beta, state, chunk=32, length=None):
    """A sequence of T positions (T a multiple of `chunk`) for H heads.
    q, k, g: (H, T, d_k); v: (H, T, d_v); beta: (H, T); state:
    (H, d_k, d_v) float32. Returns (o (H, T, d_v) float32, final state).
    `length` (a traced scalar): only the chunks that hold the first
    `length` positions are run, the outputs past them left zero, so a
    padded prompt costs what its real length costs."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    h, t, dk = k.shape
    c = chunk
    if t % c:
        raise ValueError(f"{t} positions are not whole chunks of {c}")
    lower = jnp.tril(jnp.ones((c, c), bool))             # i <= t
    strict = jnp.tril(jnp.ones((c, c), bool), -1)

    def mm(spec, a, b):      # float32 all the way: the state is float32
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)

    def one_chunk(i, carry):
        s, out = carry
        qc, kc, vc, gc, bc = (lax.dynamic_slice_in_dim(x, i * c, c, 1)
                              for x in (q, k, v, g, beta))
        G = jnp.cumsum(gc, axis=1)                       # (H, C, dk)
        # e^{G_t - G_i} over pairs (t, i): exponents of later-minus-
        # earlier positions only, the rest masked before the exponential
        diff = G[:, :, None, :] - G[:, None, :, :]       # (H, C, C, dk)
        decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
        # one reduction over the channels for both products
        pairs = jnp.sum(jnp.stack([kc, qc], 1)[:, :, :, None, :]
                        * (kc[:, None, None, :, :] * decay[:, None]), -1)
        kk, qk = pairs[:, 0], pairs[:, 1]                # qk keeps i <= t
        lhs = jnp.eye(c, dtype=_F32) \
            + bc[..., None] * jnp.where(strict, kk, 0)
        k_in = kc * jnp.exp(G)                           # decayed from S
        sol = jax.scipy.linalg.solve_triangular(
            lhs, bc[..., None] * jnp.concatenate([vc, k_in], -1),
            lower=True, unit_diagonal=True)
        w_v, w_k = sol[..., :vc.shape[-1]], sol[..., vc.shape[-1]:]
        g_end = G[:, -1:, :]                             # (H, 1, dk)
        u = w_v - mm("htk,hkv->htv", w_k, s)
        o = mm("htk,hkv->htv", qc * jnp.exp(G), s) + mm("hti,hiv->htv",
                                                        qk, u)
        s = jnp.exp(g_end[:, 0, :])[..., None] * s \
            + mm("htk,htv->hkv", kc * jnp.exp(g_end - G), u)
        return s, lax.dynamic_update_slice_in_dim(out, o, i * c, 1)

    n = t // c if length is None else (length + c - 1) // c
    state, o = lax.fori_loop(
        0, n, one_chunk,
        (state.astype(_F32), jnp.zeros((h, t, v.shape[-1]), _F32)))
    return o, state


def causal_conv(x, weight, bias=None):
    """Causal depthwise convolution over the last K positions. x: (T, C);
    weight: (K, C), weight[K-1] multiplies the current position; bias:
    (C,) or None. Positions before the sequence count as zero."""
    kw = weight.shape[0]
    pad = jnp.concatenate([jnp.zeros((kw - 1, x.shape[1]), x.dtype), x])
    out = sum(pad[j:j + x.shape[0]] * weight[j] for j in range(kw))
    return out if bias is None else out + bias


def causal_conv_step(tail, x, weight, bias=None):
    """One position of `causal_conv` for a batch. tail: (S, K-1, C), the
    K-1 inputs before this one; x: (S, C). Returns (out (S, C), the next
    tail)."""
    window = jnp.concatenate([tail, x[:, None]], 1)      # (S, K, C)
    out = jnp.sum(window * weight, 1)
    return (out if bias is None else out + bias), window[:, 1:]
