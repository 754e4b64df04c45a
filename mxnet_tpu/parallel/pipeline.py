"""Pipeline parallelism over the 'pp' mesh axis (GPipe schedule).

Reference analogue: example/model-parallel-lstm (manual stage placement).
TPU-native: every device holds one stage's weights; microbatches stream
around the pipeline with `lax.ppermute` inside `shard_map`, the schedule is
a `lax.scan` over n_micro + n_stages - 1 ticks. Forward AND backward are
differentiated through by jax.grad (the scan/ppermute transpose is the
reverse pipeline schedule — XLA generates it, no hand-written bwd schedule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(stage_params_list):
    """Stack per-stage param pytrees along a new leading 'stage' axis so the
    whole pipeline's weights shard with P('pp') on axis 0."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *stage_params_list)


def pipeline_apply(stage_fn, stacked_params, x_micro, mesh, pp_axis="pp"):
    """Run a GPipe pipeline.

    stage_fn(params, x) -> y : one stage's computation (same shape in/out).
    stacked_params: pytree with leading stage axis (sharded P(pp_axis)).
    x_micro: (n_micro, mb, ...) microbatched input (replicated).
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[pp_axis]
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1

    def per_device(params, xm):
        # params: this stage's slice (leading axis length 1) ; xm: full
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(pp_axis)
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        # stage s processes microbatch t-s at tick t; first stage reads
        # xm[t], last stage writes outs[t-(S-1)]
        def tick_indexed(carry, t):
            buf, outs = carry
            x_in = jnp.where(stage == 0, xm[jnp.clip(t, 0, n_micro - 1)], buf)
            active = jnp.logical_and(t - stage >= 0, t - stage < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = jnp.logical_and(stage == n_stages - 1, active)
            outs = jax.lax.cond(
                write,
                lambda o: o.at[out_idx].set(y),
                lambda o: o, outs)
            buf_next = jax.lax.ppermute(y, pp_axis, perm)
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick_indexed, (buf, outs),
                                    jnp.arange(total))
        # every device holds its own partial `outs`; the real outputs live on
        # the last stage — broadcast them to all
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            pp_axis)
        return outs

    f = shard_map(per_device, mesh=mesh,
                  in_specs=(P(pp_axis), P()), out_specs=P(),
                  check_vma=False)
    return f(stacked_params, x_micro)
