"""Composite 5-axis parallelism: dp x pp x tp x sp x ep in ONE train step.

This is the framework's flagship distributed path (reference analogue: the
combination of KVStore dist_sync data parallelism + example/model-parallel
stage placement, re-designed TPU-first). The whole training step is a single
`shard_map` over a 5-axis `jax.sharding.Mesh`:

  dp — batch sharded; gradient psum over 'dp'
  pp — GPipe pipeline: each device group owns L/pp transformer layers,
       microbatch activations rotate with `lax.ppermute` ticks
  tp — Megatron tensor parallelism: QKV/FFN-in column-parallel, out/FFN-out
       row-parallel with forward psum; backward correctness via the
       conjugate f-operator (identity fwd / psum bwd)
  sp — ring attention sequence parallelism (parallel/ring_attention.py)
  ep — MoE experts sharded; dispatch restricted to local experts with a
       forward psum over 'ep'

Gradient reductions are explicit (check_vma=False), following the Megatron
f/g-operator algebra:
  * every parameter gradient is psum'd over ('dp','sp') (data varies there);
  * embedding/pos additionally over 'pp' (only stage-0 devices receive
    cotangents through the pipeline transpose);
  * the MoE gate additionally over 'ep' (each device only backprops its
    local experts' routing);
  * no psum over 'tp'/'ep' elsewhere: branch entries are wrapped in
    `f_identity_bwd_psum`, which makes the residual-stream cotangent
    replicated again — exactly Megatron's f operator.

Correctness is asserted in tests/test_composite.py: loss and updated params
on any mesh factorisation match the single-device run bit-for-nearly-bit
when no MoE tokens are dropped (capacity_factor >= n_experts). With a tight
capacity, MoE routing drops are computed per batch/sequence shard — capacity
is `capacity_factor * local_tokens / n_experts` — so which tokens overflow
depends on the dp/sp factorisation, the same way the reference's per-device
batch statistics do.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention
from .ulysses import ulysses_attention

__all__ = ["CompositeConfig", "make_composite_mesh", "init_composite_params",
           "make_composite_train_step", "f_identity_bwd_psum",
           "composite_param_specs"]

AXES = ("dp", "pp", "tp", "sp", "ep")


class CompositeConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    d_head: int = 16
    d_ff: int = 128
    n_experts: int = 4
    d_expert_ff: int = 64
    n_layers: int = 2
    seq_len: int = 32
    batch: int = 8
    n_micro: int = 2
    capacity_factor: float = 2.0
    lr: float = 0.1
    remat: bool = False   # jax.checkpoint each transformer layer: trade
                          # recompute FLOPs for activation memory (long-seq
                          # / big-batch configs)
    sp_strategy: str = "ring"   # 'ring' (ppermute K/V rotation) or
                                # 'alltoall' (Ulysses head reshuffle);
                                # numerically interchangeable, different
                                # comms profiles — see parallel/ulysses.py


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------
def make_composite_mesh(n_devices, priority=("dp", "tp", "sp", "pp", "ep"),
                        devices=None, n_layers=None):
    """Factorise n_devices over the 5 axes (unused axes get size 1).

    Prime factors are dealt round-robin to `priority` so as many axes as
    possible are >1 (e.g. 8 -> dp2*tp2*sp2; 16 -> dp2*tp2*sp2*pp2).

    Pass `n_layers` to keep the factorisation pp-compatible with your
    model: any factor that would make `pp` stop dividing `n_layers`
    is dealt to the next axis in `priority` instead (GPipe needs
    n_layers % pp == 0 — see make_composite_train_step).
    """
    sizes = {ax: 1 for ax in AXES}
    n = n_devices
    factors = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            factors.append(d)
            n //= d
        d += 1
    if n > 1:
        factors.append(n)
    for i, f in enumerate(sorted(factors, reverse=True)):
        order = [priority[(i + j) % len(priority)]
                 for j in range(len(priority))]
        ax = next((a for a in order
                   if a != "pp" or n_layers is None
                   or n_layers % (sizes["pp"] * f) == 0), "dp")
        sizes[ax] *= f
    devs = devices if devices is not None else jax.devices()[:n_devices]
    import numpy as np
    shape = tuple(sizes[ax] for ax in AXES)
    return Mesh(np.asarray(devs).reshape(shape), AXES)


# ---------------------------------------------------------------------------
# Megatron conjugate operator: forward identity, backward psum(axis)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def f_identity_bwd_psum(x, axis_name):
    """Megatron's `f`: marks entry into an `axis_name`-parallel branch.

    Forward is the identity; backward psums the cotangent over `axis_name`,
    restoring replication of the residual-stream gradient so no manual psum
    over the model-parallel axis is ever needed for upstream parameters.
    """
    return x


def _f_fwd(x, axis_name):
    return x, None


def _f_bwd(axis_name, _, g):
    return (lax.psum(g, axis_name),)


f_identity_bwd_psum.defvjp(_f_fwd, _f_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def g_psum_bwd_identity(x, axis_name):
    """Megatron's `g`: forward psum over the model-parallel axis, backward
    identity. Needed because with check_vma=False jax transposes a bare
    `lax.psum` into another psum, which would scale cotangents by the axis
    size; this conjugate pins the correct algebra explicitly."""
    return lax.psum(x, axis_name)


def _g_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _g_bwd(axis_name, _, g):
    return (g,)


g_psum_bwd_identity.defvjp(_g_fwd, _g_bwd)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_composite_params(key, cfg: CompositeConfig, dtype=jnp.float32):
    """Global (unsharded) parameter pytree. Block params carry a leading
    layer axis of length n_layers that shards over 'pp'."""
    c = cfg
    ks = jax.random.split(key, 16)
    s_d = 1.0 / (c.d_model ** 0.5)
    s_f = 1.0 / (c.d_ff ** 0.5)
    s_h = 1.0 / ((c.n_heads * c.d_head) ** 0.5)
    s_e = 1.0 / (c.d_expert_ff ** 0.5)
    L = c.n_layers

    def rnd(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    blocks = {
        "ln1_g": jnp.ones((L, c.d_model), dtype),
        "ln1_b": jnp.zeros((L, c.d_model), dtype),
        "ln2_g": jnp.ones((L, c.d_model), dtype),
        "ln2_b": jnp.zeros((L, c.d_model), dtype),
        "ln3_g": jnp.ones((L, c.d_model), dtype),
        "ln3_b": jnp.zeros((L, c.d_model), dtype),
        "wq": rnd(ks[0], (L, c.d_model, c.n_heads, c.d_head), s_d),
        "wk": rnd(ks[1], (L, c.d_model, c.n_heads, c.d_head), s_d),
        "wv": rnd(ks[2], (L, c.d_model, c.n_heads, c.d_head), s_d),
        "wo": rnd(ks[3], (L, c.n_heads, c.d_head, c.d_model), s_h),
        "bo": jnp.zeros((L, c.d_model), dtype),
        "w1": rnd(ks[4], (L, c.d_model, c.d_ff), s_d),
        "b1": jnp.zeros((L, c.d_ff), dtype),
        "w2": rnd(ks[5], (L, c.d_ff, c.d_model), s_f),
        "b2": jnp.zeros((L, c.d_model), dtype),
        "gate": rnd(ks[6], (L, c.d_model, c.n_experts), s_d),
        "wi_e": rnd(ks[7], (L, c.n_experts, c.d_model, c.d_expert_ff), s_d),
        "wo_e": rnd(ks[8], (L, c.n_experts, c.d_expert_ff, c.d_model), s_e),
    }
    return {
        "embed": rnd(ks[9], (c.vocab, c.d_model), 1.0),
        "pos": rnd(ks[10], (c.seq_len, c.d_model), 0.02),
        "lnf_g": jnp.ones((c.d_model,), dtype),
        "lnf_b": jnp.zeros((c.d_model,), dtype),
        "lm_head": rnd(ks[11], (c.d_model, c.vocab), s_d),
        "blocks": blocks,
    }


def composite_param_specs():
    """PartitionSpec pytree matching init_composite_params."""
    blocks = {
        "ln1_g": P("pp", None), "ln1_b": P("pp", None),
        "ln2_g": P("pp", None), "ln2_b": P("pp", None),
        "ln3_g": P("pp", None), "ln3_b": P("pp", None),
        "wq": P("pp", None, "tp", None),
        "wk": P("pp", None, "tp", None),
        "wv": P("pp", None, "tp", None),
        "wo": P("pp", "tp", None, None),
        "bo": P("pp", None),
        "w1": P("pp", None, "tp"), "b1": P("pp", "tp"),
        "w2": P("pp", "tp", None), "b2": P("pp", None),
        "gate": P("pp", None, None),
        "wi_e": P("pp", "ep", None, None),
        "wo_e": P("pp", "ep", None, None),
    }
    return {"embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
            "lm_head": P(), "blocks": blocks}


# ---------------------------------------------------------------------------
# per-device model pieces (everything below runs INSIDE shard_map)
# ---------------------------------------------------------------------------
def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * lax.rsqrt(var + eps) * g + b


def _attention(bp, h, cfg):
    """Megatron TP attention with sequence parallelism over 'sp' — ring
    or all-to-all per cfg.sp_strategy.
    h: (mb, S_loc, D) replicated over tp/ep; weights head-sharded over tp."""
    a = _ln(h, bp["ln1_g"], bp["ln1_b"])
    a = f_identity_bwd_psum(a, "tp")
    # (mb, S', Hloc, Dh) -> (mb, Hloc, S', Dh)
    q = jnp.einsum("bsd,dhk->bhsk", a, bp["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", a, bp["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", a, bp["wv"])
    if cfg.sp_strategy == "alltoall":
        # ulysses takes (B, S/P, H, Dh); heads here are the tp-local set
        o = jnp.swapaxes(
            ulysses_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                              jnp.swapaxes(v, 1, 2), axis_name="sp",
                              causal=True), 1, 2)
    else:
        o = ring_attention(q, k, v, axis_name="sp", causal=True)
    out = jnp.einsum("bhsk,hkd->bsd", o, bp["wo"])
    out = g_psum_bwd_identity(out, "tp") + bp["bo"]
    return h + out


def _dense_ffn(bp, h):
    """Column/row-parallel MLP over 'tp'."""
    a = _ln(h, bp["ln2_g"], bp["ln2_b"])
    a = f_identity_bwd_psum(a, "tp")
    u = jax.nn.gelu(jnp.einsum("bsd,df->bsf", a, bp["w1"]) + bp["b1"])
    y = jnp.einsum("bsf,fd->bsd", u, bp["w2"])
    y = g_psum_bwd_identity(y, "tp") + bp["b2"]
    return h + y


def _moe_ffn(bp, h, cfg, ep_size):
    """Top-1 MoE with experts sharded over 'ep'. The dense dispatch tensor is
    computed for ALL experts (routing decisions must be global), then sliced
    to the local expert shard; outputs psum over 'ep'."""
    a = _ln(h, bp["ln3_g"], bp["ln3_b"])
    a = f_identity_bwd_psum(a, "ep")
    mb, s_loc, d = a.shape
    e = cfg.n_experts
    e_loc = e // ep_size
    tokens = mb * s_loc
    capacity = max(int(cfg.capacity_factor * tokens / e), 1)

    logits = jnp.einsum("bsd,de->bse", a, bp["gate"])
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    expert_mask = jax.nn.one_hot(expert_idx, e, dtype=a.dtype)
    gate_val = jnp.sum(probs * expert_mask, axis=-1)

    flat_mask = expert_mask.reshape(tokens, e)
    pos = jnp.cumsum(flat_mask, axis=0) * flat_mask - 1.0
    keep = pos < capacity
    pos = jnp.where(keep, pos, 0.0).astype(jnp.int32)
    flat_mask = flat_mask * keep
    dispatch = (flat_mask[:, :, None]
                * jax.nn.one_hot(pos, capacity, dtype=a.dtype))
    dispatch = dispatch.reshape(mb, s_loc, e, capacity)

    # local expert slice along E; gate multiply after slicing (1/ep the work)
    ep_idx = lax.axis_index("ep")
    disp_loc = lax.dynamic_slice_in_dim(dispatch, ep_idx * e_loc, e_loc, 2)
    gated_loc = disp_loc * gate_val[:, :, None, None]

    expert_in = jnp.einsum("bsec,bsd->ecd", disp_loc, a)
    u = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, bp["wi_e"]))
    expert_out = jnp.einsum("ecf,efd->ecd", u, bp["wo_e"])
    out = jnp.einsum("bsec,ecd->bsd", gated_loc, expert_out)
    out = g_psum_bwd_identity(out, "ep")
    return h + out


def _stage_fn(bp_local, h, cfg, ep_size, layers_per_stage):
    """Apply this device's layers_per_stage transformer layers sequentially.
    bp_local leaves: (layers_per_stage, ...). With cfg.remat each layer is
    rematerialised on backward (jax.checkpoint) so only layer BOUNDARY
    activations are kept live — the standard long-sequence memory/FLOPs
    trade."""
    def one(bp, x):
        x = _attention(bp, x, cfg)
        x = _dense_ffn(bp, x)
        x = _moe_ffn(bp, x, cfg, ep_size)
        return x
    if cfg.remat:
        one = jax.checkpoint(one)
    for i in range(layers_per_stage):   # static unroll: tiny depth
        bp = jax.tree_util.tree_map(lambda p: p[i], bp_local)
        h = one(bp, h)
    return h


def _gpipe(blocks_local, x, cfg, mesh_shape):
    """GPipe over 'pp': microbatches rotate with ppermute.
    x: (B_loc, S_loc, D). blocks_local leaves: (L/pp, ...)."""
    pp = mesh_shape["pp"]
    ep = mesh_shape["ep"]
    lps = cfg.n_layers // pp
    n_micro = cfg.n_micro
    b_loc = x.shape[0]
    mb = b_loc // n_micro
    xm = x.reshape(n_micro, mb, *x.shape[1:])

    if pp == 1:
        out = jax.vmap(lambda m: _stage_fn(blocks_local, m, cfg, ep, lps))(xm)
        return out.reshape(b_loc, *x.shape[1:])

    stage = lax.axis_index("pp")
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    total = n_micro + pp - 1
    buf = jnp.zeros_like(xm[0])
    outs = jnp.zeros_like(xm)

    def tick(carry, t):
        buf, outs = carry
        x_in = jnp.where(stage == 0, xm[jnp.clip(t, 0, n_micro - 1)], buf)
        y = _stage_fn(blocks_local, x_in, cfg, ep, lps)
        active = jnp.logical_and(t - stage >= 0, t - stage < n_micro)
        y = jnp.where(active, y, jnp.zeros_like(y))
        out_idx = jnp.clip(t - (pp - 1), 0, n_micro - 1)
        write = jnp.logical_and(stage == pp - 1, active)
        outs = lax.cond(write, lambda o: o.at[out_idx].set(y),
                        lambda o: o, outs)
        buf = lax.ppermute(y, "pp", perm)
        return (buf, outs), None

    (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(total))
    # real outputs live on the last stage; broadcast to every pp rank
    outs = g_psum_bwd_identity(
        jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), "pp")
    return outs.reshape(b_loc, *x.shape[1:])


# ---------------------------------------------------------------------------
# full train step
# ---------------------------------------------------------------------------
def make_composite_train_step(mesh, cfg: CompositeConfig):
    """Returns (jitted step, shard_params, data_sharding).

    step(params, tokens, targets) -> (new_params, loss): one SGD step of the
    5-axis-parallel causal-LM, compiled as a single XLA program over `mesh`.
    """
    mesh_shape = dict(mesh.shape)
    divisibility = [
        ("n_layers", cfg.n_layers, "pp",
         "pipeline stages each own n_layers/pp layers — rebuild the mesh "
         "with make_composite_mesh(n, n_layers=...) to steer pp"),
        ("n_heads", cfg.n_heads, "tp", "heads are column-split over tp"),
        ("d_ff", cfg.d_ff, "tp", "the MLP hidden dim is split over tp"),
        ("seq_len", cfg.seq_len, "sp", "the sequence is split over sp"),
        ("n_experts", cfg.n_experts, "ep", "experts are sharded over ep"),
    ]
    for name, value, ax, why in divisibility:
        if value % mesh_shape[ax] != 0:
            raise ValueError(
                f"CompositeConfig.{name}={value} is not divisible by the "
                f"mesh's {ax}={mesh_shape[ax]}: {why}")
    if cfg.batch % (mesh_shape["dp"] * cfg.n_micro) != 0:
        raise ValueError(
            f"CompositeConfig.batch={cfg.batch} must be divisible by "
            f"dp*n_micro={mesh_shape['dp']}*{cfg.n_micro} (each dp shard "
            "splits its local batch into n_micro pipeline microbatches)")
    if cfg.sp_strategy not in ("ring", "alltoall"):
        raise ValueError(f"unknown sp_strategy {cfg.sp_strategy!r}")
    if cfg.sp_strategy == "alltoall":
        # ulysses shards the tp-LOCAL head set over 'sp'
        if (cfg.n_heads // mesh_shape["tp"]) % mesh_shape["sp"] != 0:
            raise ValueError(
                f"sp_strategy='alltoall' reshuffles the tp-local head set "
                f"over sp: n_heads/tp={cfg.n_heads // mesh_shape['tp']} "
                f"must be divisible by sp={mesh_shape['sp']} (use "
                f"sp_strategy='ring' or adjust n_heads)")

    n_total_tokens = cfg.batch * cfg.seq_len
    specs = composite_param_specs()

    def per_device(params, tokens, targets):
        s_loc = tokens.shape[1]
        sp_idx = lax.axis_index("sp")

        def loss_fn(p):
            x = p["embed"][tokens]
            pos = lax.dynamic_slice_in_dim(p["pos"], sp_idx * s_loc, s_loc, 0)
            x = x + pos[None]
            x = _gpipe(p["blocks"], x, cfg, mesh_shape)
            x = _ln(x, p["lnf_g"], p["lnf_b"])
            logits = jnp.einsum("bsd,dv->bsv", x, p["lm_head"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
            # differentiate the LOCAL share only: psum here would re-psum the
            # cotangent on transpose (check_vma=False), scaling grads by
            # dp*sp. The cross-device sum happens once, on the grads below.
            return -jnp.sum(ll) / n_total_tokens

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(local_loss, ("dp", "sp"))
        # explicit gradient algebra (see module docstring)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, ("dp", "sp")), grads)
        grads["embed"] = lax.psum(grads["embed"], "pp")
        grads["pos"] = lax.psum(grads["pos"], "pp")
        grads["blocks"]["gate"] = lax.psum(grads["blocks"]["gate"], "ep")
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - cfg.lr * g.astype(p.dtype), params, grads)
        return new_params, loss

    data_spec = P("dp", "sp")
    step = shard_map(
        per_device, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P()),
        check_vma=False)
    jstep = jax.jit(step, donate_argnums=(0,))

    def shard_params(params):
        return jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
            params, specs)

    data_sharding = NamedSharding(mesh, data_spec)
    return jstep, shard_params, data_sharding
