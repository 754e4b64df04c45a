"""Feasibility probe for Pallas conv(1x1)+BN-stats epilogue fusion.

An earlier profile said the ResNet-50 step is bandwidth-bound on
BN-stat reduce fusions (the fwd stats pass re-reads every conv output).
A 1x1 NHWC conv is a (B*H*W, Cin) @ (Cin, Cout) matmul, and Pallas can
compute the per-channel fp32 sum/sumsq WHILE the output tile is still
in VMEM — deleting one full HBM read of the activation per fused layer.

This probe times, for the three bottleneck 1x1 shapes of ResNet-50 at
batch 128: (a) XLA conv + separate fused stats reduce (today's path)
vs (b) the Pallas fused kernel. Keep-or-reject evidence for wiring it
into the model.

Usage: python tools/probe_fused_convbn.py [--steps N]
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, y_ref, s_ref, q_ref):
    i = pl.program_id(0)
    x = x_ref[...]                                     # (bm, K) bf16
    w = w_ref[...]                                     # (K, N) bf16
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)   # (bm, N) f32
    y_ref[...] = y.astype(y_ref.dtype)
    s = jnp.sum(y, axis=0)                             # (N,) f32
    q = jnp.sum(y * y, axis=0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    # every row of the (8, N) accumulator gets the same partial: row 0
    # holds the true total at the end (lane-aligned stats block — a
    # (1, N) block would violate Mosaic's (8, 128) min tile)
    s_ref[...] += jnp.broadcast_to(s[None, :], s_ref.shape)
    q_ref[...] += jnp.broadcast_to(q[None, :], q_ref.shape)


@functools.partial(jax.jit, static_argnames=("bm",))
def fused_conv1x1_stats(x2d, w, bm=1024):
    m, k = x2d.shape
    n = w.shape[1]
    pad = (-m) % bm
    xp = jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d
    grid = (xp.shape[0] // bm,)
    y, s, q = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((k, n), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                   pl.BlockSpec((8, n), lambda i: (0, 0)),
                   pl.BlockSpec((8, n), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0], n), x2d.dtype),
                   jax.ShapeDtypeStruct((8, n), jnp.float32),
                   jax.ShapeDtypeStruct((8, n), jnp.float32)],
    )(xp, w)
    inv = 1.0 / m
    return y[:m], s[0] * inv, q[0] * inv   # mean, E[y^2]


@jax.jit
def xla_conv_stats(x2d, w):
    y = jnp.dot(x2d, w, preferred_element_type=jnp.bfloat16)
    yf = y.astype(jnp.float32)
    return y, jnp.mean(yf, 0), jnp.mean(yf * yf, 0)


def bench_one(m, k, n, steps):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16) * 0.05

    def time_fn(fn):
        outs = fn(x, w)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), outs)
        float(outs[1][0])  # host-fetch sync
        t0 = time.monotonic()
        for _ in range(steps):
            outs = fn(x, w)
        float(outs[1][0])
        return (time.monotonic() - t0) / steps * 1e3

    t_xla = time_fn(xla_conv_stats)
    t_pal, best_bm = None, None
    for bm in (256, 512, 1024):
        if m < bm:
            continue
        t = time_fn(functools.partial(fused_conv1x1_stats, bm=bm))
        if t_pal is None or t < t_pal:
            t_pal, best_bm = t, bm
    print(f"  best bm={best_bm}", flush=True)
    # numerics check while we're here
    y0, m0, q0 = xla_conv_stats(x, w)
    y1, m1, q1 = fused_conv1x1_stats(x, w)
    err = float(jnp.abs(m0 - m1).max())
    print(f"M={m} K={k} N={n}: xla {t_xla:.3f} ms  pallas {t_pal:.3f} ms "
          f"({t_xla / t_pal:.2f}x)  mean-err {err:.2e}", flush=True)
    return t_xla, t_pal


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    print(f"backend={jax.default_backend()}", flush=True)
    B = 128
    shapes = [
        (B * 56 * 56, 64, 256),     # stage1 bottleneck expand
        (B * 28 * 28, 512, 128),    # stage2 reduce
        (B * 14 * 14, 1024, 256),   # stage3 reduce
        (B * 7 * 7, 512, 2048),     # stage4 expand
    ]
    tot_x = tot_p = 0.0
    for m, k, n in shapes:
        tx, tp = bench_one(m, k, n, args.steps)
        tot_x += tx
        tot_p += tp
    print(f"TOTAL: xla {tot_x:.3f} ms  pallas {tot_p:.3f} ms "
          f"({tot_x / tot_p:.2f}x)", flush=True)


if __name__ == "__main__":
    main()
