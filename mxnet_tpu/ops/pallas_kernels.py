"""Hand-written TPU Pallas kernels for the hot ops.

Reference parity: the reference fuses attention/layernorm via cuDNN and
hand-written CUDA (src/operator/contrib); here the fused fast paths are
Mosaic/Pallas kernels targeting VMEM + MXU directly.

Kernels:
  * flash_attention — memory-efficient attention, online softmax, O(S) memory,
    grid (batch*heads, q_blocks, kv_blocks) with VMEM accumulators. Forward
    saves per-row logsumexp; backward is the FlashAttention-2 style pair of
    Pallas kernels (dk/dv over kv-blocks, dq over q-blocks) with in-kernel
    recompute of the probabilities — O(S) memory end to end.
  * flash_block_attention — (out, lse) blockwise partial with gradients
    through both outputs; the ring-attention building block (the lse
    cotangent folds into the Pallas backward as a delta shift).
  * fused_layer_norm — single-pass layernorm.

The pure-XLA implementations are chosen off-TPU (CPU test mesh) and for
shapes that don't tile (seq not multiple of block after padding) — by what
the call can observe, never by catching an exception: a kernel the chip's
compiler refuses raises. Set MXTPU_PALLAS_INTERPRET=1 to run the kernels in
Pallas interpret mode on CPU (used by tests to pin the kernel numerics
without a chip).
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .. import _env
from ..observability.metrics_registry import registry as _metrics_registry
from ..tune import overrides as _tune_overrides

__all__ = ["flash_attention", "flash_block_attention", "fused_layer_norm",
           "attention_reference", "on_tpu",
           "single_query_cached_attention", "ragged_paged_attention",
           "latent_paged_attention", "ring_paged_attention",
           "ring_rows_back", "pool_lanes",
           "kernel_mesh"]


def on_tpu():
    return jax.default_backend() == "tpu"


def _interpret():
    """Pallas interpret mode: lets the CPU test mesh execute the real kernel
    bodies (slowly) so their numerics are pinned without TPU hardware."""
    return os.environ.get("MXTPU_PALLAS_INTERPRET") == "1"


def _pallas_ok(seq_len):
    if os.environ.get("MXTPU_PALLAS_DISABLE") == "1":  # A/B vs XLA path
        return False
    return ((on_tpu() or _interpret())
            and seq_len % 128 == 0 and seq_len >= 128)


_breg = _metrics_registry()
_ignored_warned = set()          # (knob, value, dim): warn once each


def _note_ignored(source, knob, val, dim, fallback):
    """A forced block override the kernel cannot honour used to be
    SILENTLY dropped — the tuner (and any operator A/B-ing knobs) then
    measures the default config under the override's label. Count every
    dead override on `pallas_block_override_ignored{knob=}` and warn
    once per (knob, value, dim)."""
    _breg.counter("pallas_block_override_ignored", knob=knob).inc()
    key = (knob, val, dim)
    if key not in _ignored_warned:
        _ignored_warned.add(key)
        import warnings
        warnings.warn(
            f"{knob}={val} (from {source}) is incompatible with size "
            f"{dim}; using {fallback} — the override is DEAD",
            RuntimeWarning, stacklevel=4)


def _knob(name, env):
    """Resolve one tunable kernel knob: the autotuner's thread-local
    override scope (tune/overrides.py) wins, the MXTPU_* env var is the
    operator-facing fallback. Returns (value, source); 0 = unset."""
    cfg = _tune_overrides.current()
    if cfg is not None and name in cfg:
        return int(cfg[name]), "tune override"
    return _env.env_int(env, 0, minimum=0), "env"


def _block_sizes(sq, sk):
    """Largest tiling block (<=512) that divides each sequence length —
    bigger blocks amortise grid overhead and feed the MXU larger dots;
    override with MXTPU_FLASH_BLOCK_Q / MXTPU_FLASH_BLOCK_K (or a
    tune/overrides.py scope). A forced value that does not divide the
    sequence falls back LOUDLY (`pallas_block_override_ignored`)."""
    def auto(s):
        for b in (512, 256, 128):
            if s % b == 0:
                return b
        return 128

    def pick(s, name, env):
        forced, src = _knob(name, env)
        if forced and s % forced == 0:
            return min(forced, s)
        fb = auto(s)
        if forced:
            _note_ignored(src, env, forced, s, fb)
        return fb
    return (pick(sq, "flash_block_q", "MXTPU_FLASH_BLOCK_Q"),
            pick(sk, "flash_block_k", "MXTPU_FLASH_BLOCK_K"))


def _rpa_block_k(psize):
    """Forced K tile of `_rpa_kernel`'s body (ISSUE 20). A grid step
    holds a slot's heads and several whole pages (`_rpa_plan`) and by
    default (= psize) takes a head's keys of the step as one tile; a
    forced value walks them in tiles of `block` rows of a page, inside
    the step: more, narrower softmax updates over the same blocks, no
    more grid steps or DMAs (a step a sub-page tile is what it asked
    for before PR 31). MXTPU_RPA_BLOCK_K / tune override `rpa_block_k`;
    must divide the page size and keep the 8-sublane tile, else the
    default is used loudly."""
    forced, src = _knob("rpa_block_k", "MXTPU_RPA_BLOCK_K")
    if not forced:
        return psize
    if forced % 8 == 0 and 8 <= forced <= psize and psize % forced == 0:
        return forced
    _note_ignored(src, "MXTPU_RPA_BLOCK_K", forced, psize, psize)
    return psize


def _rpa_sublanes(W):
    """Padded query-row count of the WIDENED (multi-query verify) RPA
    launch: default rounds W up to the Mosaic 8-sublane tile; a larger
    forced value (MXTPU_RPA_SUBLANES / tune override `rpa_sublanes`)
    trades padded-row compute for bigger VPU tiles. Must be >= W and a
    multiple of 8, else the default is used loudly."""
    default = max(8, -(-W // 8) * 8)
    forced, src = _knob("rpa_sublanes", "MXTPU_RPA_SUBLANES")
    if not forced:
        return default
    if forced % 8 == 0 and forced >= W:
        return max(forced, 8)
    _note_ignored(src, "MXTPU_RPA_SUBLANES", forced, W, default)
    return default


def _sds(shape, dtype, *refs):
    """ShapeDtypeStruct whose vma is the union of the inputs' varying axes —
    under shard_map(check_vma=True) pallas_call out_shapes must carry vma
    or lowering refuses."""
    vma = None
    try:
        sets = [jax.typeof(r).vma for r in refs]
        vma = frozenset().union(*sets) if sets else None
    except Exception:
        vma = None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# kernels inside a GSPMD-partitioned program
# ---------------------------------------------------------------------------
_mesh_scope = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axis, head_axis=None):
    """Partition the Pallas calls traced inside this scope over `mesh`.

    The partitioner cannot split a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned"), so a program that is one GSPMD `jit`
    over NamedShardings — the rule-sharded captured step (cachedop.py)
    holds this scope while it traces — runs each kernel per shard through
    `shard_map`: the batch dim over `batch_axis`, the heads dim over
    `head_axis`. Attention and layernorm are independent per (batch,
    head) / per row, so any such split is exact; a dim its axis does not
    divide stays whole on every device."""
    prev = getattr(_mesh_scope, "value", None)
    _mesh_scope.value = (mesh, batch_axis, head_axis)
    try:
        yield
    finally:
        _mesh_scope.value = prev


def _over_mesh(fn, args, arg_lead, out_lead):
    """`fn(*args)`, per shard under an active `kernel_mesh` scope.
    arg_lead / out_lead: for each array, how many of its leading dims are
    (batch, heads) — 2, 1 (batch only) or 0 (whole on every device);
    args[0] carries the most."""
    scope = getattr(_mesh_scope, "value", None)
    if scope is None:
        return fn(*args)
    mesh, b_ax, h_ax = scope
    axes = [ax if ax is not None and dim % mesh.shape[ax] == 0 else None
            for ax, dim in zip((b_ax, h_ax), args[0].shape)]
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(P(*axes[:n]) for n in arg_lead),
        out_specs=tuple(P(*axes[:n]) for n in out_lead),
        check_vma=False)(*args)


# ---------------------------------------------------------------------------
# reference XLA attention (also the backward path + CPU fallback)
# ---------------------------------------------------------------------------
def attention_reference(q, k, v, causal=False, sm_scale=None, mask=None):
    """q,k,v: (B, H, S, D). Plain XLA attention — fused well by XLA, used as
    the fallback and as the recompute backward for the Pallas forward.

    mask: boolean (True = attend) or additive float (0 = attend, large
    negative = masked), broadcastable to (B, H, Sq, Sk)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        kj = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(qi >= kj, s, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, -1e30)
        else:  # additive convention
            s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ---------------------------------------------------------------------------
# Pallas flash attention forward
# ---------------------------------------------------------------------------
def _flash_fwd_kernel(*refs, sm_scale, causal, block_q, block_k,
                      num_heads, has_lengths, window=None):
    """has_lengths: a scalar-prefetch (B,) int32 `kv_lengths` ref leads the
    arg list; key positions >= kv_lengths[b] are masked (padding mask) and
    fully-masked kv blocks are skipped dynamically. window (with causal):
    query t sees keys t - window < j <= t, and the kv blocks that lie
    wholly behind a q block's window are skipped as those above the
    diagonal are."""
    if has_lengths:
        (vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        vl_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qb = pl.program_id(1)
    q_start = qb * block_q
    k_start = kb * block_k
    vl = vl_ref[pl.program_id(0) // num_heads] if has_lengths else None

    def compute():
        # dots run in the INPUT dtype (bf16 on the training path — 2x MXU rate
        # vs f32) with fp32 accumulation; softmax math stays fp32
        q = q_ref[0]                               # (bq, d)
        k = k_ref[0]                               # (bk, d)
        v = v_ref[0]                               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qi = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qi >= kj, s, -1e30)
            if window is not None:
                s = jnp.where(qi - kj < window, s, -1e30)
        if has_lengths:
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kj < vl, s, -1e30)

        m_prev = m_scr[:, :1]                      # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (bq, bk) fp32
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # skip kv blocks that are fully masked (above the causal diagonal /
    # entirely beyond the valid length)
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
        if window is not None:
            # the block's last key within the window of its first query
            live = jnp.logical_and(
                live, k_start + block_k - 1 > q_start - window)
    if has_lengths:
        live = jnp.logical_and(live, k_start < vl) if causal \
            else k_start < vl
    if causal or has_lengths:
        @pl.when(live)
        def _():
            compute()
    else:
        compute()

    @pl.when(kb == nk - 1)
    def _finalize():
        # guard: a row with every key masked (kv_length 0) has l == 0
        o_ref[0] = (acc_scr[:] /
                    jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)
        # lse broadcast across the 128-lane minor dim (Mosaic needs the last
        # two block dims (8,128)-aligned, so a (block_q,) vector can't be an
        # output on its own)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _flash_fwd_pallas(q, k, v, causal, sm_scale, lengths=None, window=None):
    """Returns (out, lse); lse is the per-row logsumexp of the scaled
    logits, (B, H, Sq) fp32 — the backward kernels' softmax residual.
    lengths: optional (B,) int32 kv valid lengths (padding mask).
    Sq and Sk may differ (cross-attention); causal requires Sq == Sk.
    window: the sliding window of a causal call (`flash_attention`)."""
    block_q, block_k = _block_sizes(q.shape[2], k.shape[2])
    local = functools.partial(_flash_fwd_local, causal=causal,
                              sm_scale=sm_scale, block_q=block_q,
                              block_k=block_k, window=window)
    if lengths is None:
        return _over_mesh(local, (q, k, v), (2, 2, 2), (2, 2))
    return _over_mesh(local, (q, k, v, lengths), (2, 2, 2, 1), (2, 2))


def _flash_fwd_local(q, k, v, lengths=None, *, causal, sm_scale, block_q,
                     block_k, window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    has_lengths = lengths is not None
    kern = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_heads=h,
        has_lengths=has_lengths, window=window)

    if window is None:
        def kv_at(bh_, i, j, *_):
            return (bh_, j, 0)
    else:
        def kv_at(bh_, i, j, *_):
            # a skipped step names the nearest live block of its q block:
            # the pipeline fetches a block once while its index stands, so
            # what the kernel does not compute is not fetched either
            first = lax.div(jnp.maximum(i * block_q - (window - 1), 0),
                            jnp.int32(block_k))
            last = lax.div(i * block_q + (block_q - 1), jnp.int32(block_k))
            return (bh_, jnp.clip(j, first, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if has_lengths else 0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, i, j, *_: (bh_, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_at),
            pl.BlockSpec((1, block_k, d), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, i, j, *_: (bh_, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh_, i, j, *_: (bh_, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            _sds((bh, sq, d), q.dtype, q, k, v),
            _sds((bh, sq, 128), jnp.float32, q, k, v),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="mxtpu_flash_fwd",
    )
    if has_lengths:
        out, lse = call(lengths.astype(jnp.int32), qr, kr, vr)
    else:
        out, lse = call(qr, kr, vr)
    # the kernel writes lse broadcast across the 128-lane minor dim; keep
    # it compact (128x less HBM held from forward to backward)
    return out.reshape(b, h, sq, d), lse[..., 0].reshape(b, h, sq)


def flash_attention(q, k, v, causal=False, sm_scale=None, kv_lengths=None,
                    window=None):
    """Fused attention. q,k,v: (B, H, S, D) -> (B, H, S, D).

    On TPU with S % 128 == 0 runs the Pallas flash kernel (O(S) memory,
    MXU matmuls in fp32 accumulation); otherwise the XLA reference path.

    kv_lengths: optional (B,) int32 per-sequence valid key length (the
    reference's padding mask expressed TPU-natively — key positions
    >= kv_lengths[b] are masked, and fully-masked kv blocks are skipped
    inside the kernel via scalar prefetch).

    window: optional int, with `causal` and without `kv_lengths`: query t
    attends the `window` keys t - window < j <= t (sliding-window
    attention; the current key is one of them). The kernel neither
    computes nor fetches the key blocks that lie wholly behind a query
    block's window. Forward only on the kernel path (the serving
    prefill); the XLA path differentiates as any masked attention."""
    if window is not None:
        if not causal or kv_lengths is not None:
            raise ValueError("window goes with causal=True and without "
                             "kv_lengths")
        return _flash_window(q, k, v, sm_scale, int(window))
    if kv_lengths is None:
        return _flash_plain(q, k, v, causal, sm_scale)
    return _flash_vl(q, k, v, kv_lengths, causal, sm_scale)


def _flash_window(q, k, v, sm_scale, window):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        return _flash_fwd_pallas(q, k, v, True, sm_scale, window=window)[0]
    t = jnp.arange(q.shape[2])
    return attention_reference(q, k, v, causal=True, sm_scale=sm_scale,
                               mask=t[:, None] - t[None, :] < window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_plain(q, k, v, causal=False, sm_scale=None):
    return _flash_attention_impl(q, k, v, causal, sm_scale)


def _lengths_mask(lengths, seq_len):
    """(B,) lengths -> (B, 1, 1, S) boolean mask for the XLA fallback."""
    pos = jnp.arange(seq_len)[None, :]
    return (pos < lengths[:, None])[:, None, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_vl(q, k, v, lengths, causal=False, sm_scale=None):
    return _flash_vl_impl(q, k, v, lengths, causal, sm_scale)


def _flash_vl_impl(q, k, v, lengths, causal, sm_scale):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        return _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                 lengths=lengths)[0]
    return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                               mask=_lengths_mask(lengths, k.shape[2]))


def _flash_attention_impl(q, k, v, causal, sm_scale):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        return _flash_fwd_pallas(q, k, v, causal, sm_scale)[0]
    return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# Pallas flash attention backward (FlashAttention-2 split):
#   kernel 1 — dk/dv: kv-blocks parallel, q-blocks innermost/sequential
#   kernel 2 — dq:    q-blocks parallel, kv-blocks innermost/sequential
# Both recompute p = exp(s - lse) from the forward's logsumexp, so nothing
# O(S^2) is ever materialised.
# ---------------------------------------------------------------------------
def _flash_bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k,
                          num_heads, has_lengths):
    if has_lengths:
        (vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        vl_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    kb = pl.program_id(1)
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qb * block_q
    k_start = kb * block_k
    vl = vl_ref[pl.program_id(0) // num_heads] if has_lengths else None

    def compute():
        q = q_ref[0]                               # (bq, d) input dtype
        k = k_ref[0]                               # (bk, d)
        v = v_ref[0]                               # (bk, d)
        do = do_ref[0]                             # (bq, d)
        lse = lse_ref[0][:, :1]                    # (bq, 1) lane-broadcast
        delta = delta_ref[0][:, :1]                # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qi = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qi >= kj, s, -1e30)
        if has_lengths:
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kj < vl, s, -1e30)
        p = jnp.exp(s - lse).astype(do.dtype)      # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(          # p^T @ dO
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                  # dO @ V^T
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta)
              * sm_scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(          # dS^T @ Q
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if has_lengths:
        live = jnp.logical_and(live, k_start < vl) if causal \
            else k_start < vl
    if causal or has_lengths:
        @pl.when(live)
        def _():
            compute()
    else:
        compute()

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k,
                         num_heads, has_lengths):
    if has_lengths:
        (vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
    else:
        vl_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qb * block_q
    k_start = kb * block_k
    vl = vl_ref[pl.program_id(0) // num_heads] if has_lengths else None

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qi = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qi >= kj, s, -1e30)
        if has_lengths:
            kj = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kj < vl, s, -1e30)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(          # dS @ K
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if has_lengths:
        live = jnp.logical_and(live, k_start < vl) if causal \
            else k_start < vl
    if causal or has_lengths:
        @pl.when(live)
        def _():
            compute()
    else:
        compute()

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale, lengths=None,
                      delta_shift=None):
    """lse: the forward's (B,H,Sq) residual. delta_shift (B,H,Sq) fp32,
    optional: subtracted from the standard delta = rowsum(dO∘O). Used by
    flash_block_attention to fold an lse cotangent into the backward (dS
    gains +g_lse∘p, i.e. delta -= g_lse)."""
    block_q, block_k = _block_sizes(q.shape[2], k.shape[2])
    if delta_shift is None:
        delta_shift = jnp.zeros(lse.shape, jnp.float32)
    local = functools.partial(_flash_bwd_local, causal=causal,
                              sm_scale=sm_scale, block_q=block_q,
                              block_k=block_k)
    args, lead = (q, k, v, o, lse, g, delta_shift), (2,) * 7
    if lengths is not None:
        args, lead = args + (lengths,), lead + (1,)
    return _over_mesh(local, args, lead, (2, 2, 2))


def _flash_bwd_local(q, k, v, o, lse, g, delta_shift, lengths=None, *,
                     causal, sm_scale, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    gr = g.reshape(bh, sq, d)
    # delta_i = rowsum(dO ∘ O): the softmax-jacobian correction term; cheap
    # elementwise+reduce, left to XLA. Lane-broadcast to 128 like lse so the
    # block shape is Mosaic-tileable.
    delta = (jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
             - delta_shift.astype(jnp.float32)).reshape(bh, sq)
    delta = jnp.broadcast_to(delta[..., None], (bh, sq, 128))
    lse = jnp.broadcast_to(lse.reshape(bh, sq)[..., None], (bh, sq, 128))
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    has_lengths = lengths is not None
    nsp = 1 if has_lengths else 0
    scal = (lengths.astype(jnp.int32),) if has_lengths else ()

    qspec = pl.BlockSpec((1, block_q, d), lambda b_, j, i, *_: (b_, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b_, j, i, *_: (b_, j, 0))
    rowq = pl.BlockSpec((1, block_q, 128), lambda b_, j, i, *_: (b_, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          num_heads=h, has_lengths=has_lengths),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=nsp,
            grid=(bh, nk, nq),
            in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
            out_specs=[kspec, kspec],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[_sds((bh, sk, d), q.dtype, q, k, v, g)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="mxtpu_flash_bwd_dkv",
    )(*scal, qr, kr, vr, gr, lse, delta)

    qspec2 = pl.BlockSpec((1, block_q, d), lambda b_, i, j, *_: (b_, i, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b_, i, j, *_: (b_, j, 0))
    rowq2 = pl.BlockSpec((1, block_q, 128), lambda b_, i, j, *_: (b_, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          num_heads=h, has_lengths=has_lengths),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=nsp,
            grid=(bh, nq, nk),
            in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
            out_specs=qspec2,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=_sds((bh, sq, d), q.dtype, q, k, v, g),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="mxtpu_flash_bwd_dq",
    )(*scal, qr, kr, vr, gr, lse, delta)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _flash_fwd_rule(q, k, v, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        out, lse = _flash_fwd_pallas(q, k, v, causal, scale)
        return out, (q, k, v, out, lse)
    out = attention_reference(q, k, v, causal=causal, sm_scale=scale)
    return out, (q, k, v, None, None)


def _flash_bwd_rule(causal, sm_scale, res, g):
    q, k, v, o, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if o is not None and _pallas_ok(q.shape[2]):
        return _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale)
    # forward took the XLA path: recompute-backward through the reference
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=causal,
                                               sm_scale=scale), q, k, v)
    return vjp(g)


_flash_plain.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _flash_vl_fwd_rule(q, k, v, lengths, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        out, lse = _flash_fwd_pallas(q, k, v, causal, scale,
                                     lengths=lengths)
        return out, (q, k, v, lengths, out, lse)
    out = attention_reference(q, k, v, causal=causal, sm_scale=scale,
                              mask=_lengths_mask(lengths, k.shape[2]))
    return out, (q, k, v, lengths, None, None)


def _flash_vl_bwd_rule(causal, sm_scale, res, g):
    import numpy as np
    q, k, v, lengths, o, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    dlen = np.zeros(lengths.shape, dtype=jax.dtypes.float0)
    if o is not None and _pallas_ok(q.shape[2]):
        dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                       lengths=lengths)
        return dq, dk, dv, dlen
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(
            q_, k_, v_, causal=causal, sm_scale=scale,
            mask=_lengths_mask(lengths, k.shape[2])), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, dlen


_flash_vl.defvjp(_flash_vl_fwd_rule, _flash_vl_bwd_rule)


# ---------------------------------------------------------------------------
# flash block attention: (out, lse) with gradients through BOTH — the ring
# attention building block (partial softmax results merge across ring steps
# via lse, so the lse cotangent is nonzero: d lse/dS = p folds into the
# standard backward as delta -= g_lse).
# ---------------------------------------------------------------------------
def _block_fwd_xla(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        kj = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(qi >= kj, s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)
    return out, lse


def _block_bwd_xla(q, k, v, out, lse, g, g_lse, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        kj = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(qi >= kj, s, -1e30)
    p = jnp.exp(s - lse[..., None])
    gf = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v.astype(jnp.float32))
    delta = (jnp.sum(gf * out.astype(jnp.float32), axis=-1)
             - g_lse.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_block_impl(q, k, v, causal, sm_scale):
    """Shared primal: (out, lse, used_pallas)."""
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _pallas_ok(q.shape[2]) and _pallas_ok(k.shape[2]):
        out, lse = _flash_fwd_pallas(q, k, v, causal, scale)
        return out, lse, True
    out, lse = _block_fwd_xla(q, k, v, causal, scale)
    return out, lse, False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_block_attention(q, k, v, causal=False, sm_scale=None):
    """Blockwise attention partial: returns (out, lse) where `out` is the
    softmax attention over ONLY these keys and `lse` its per-row logsumexp
    of scaled logits. Partials from disjoint key sets merge exactly:
        lse = logaddexp(lse_a, lse_b)
        out = out_a*exp(lse_a-lse) + out_b*exp(lse_b-lse)
    — the combine used by parallel/ring_attention.py. Pallas on TPU-tiling
    shapes, XLA otherwise; differentiable through BOTH outputs."""
    out, lse, _ = _flash_block_impl(q, k, v, causal, sm_scale)
    return out, lse


def _flash_block_fwd_rule(q, k, v, causal, sm_scale):
    out, lse, used_pallas = _flash_block_impl(q, k, v, causal, sm_scale)
    return (out, lse), (q, k, v, out, lse, used_pallas)


def _flash_block_bwd_rule(causal, sm_scale, res, cts):
    q, k, v, out, lse, used_pallas = res
    g, g_lse = cts
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if used_pallas:
        return _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale,
                                 delta_shift=g_lse)
    return _block_bwd_xla(q, k, v, out, lse, g, g_lse, causal, scale)


flash_block_attention.defvjp(_flash_block_fwd_rule, _flash_block_bwd_rule)


# ---------------------------------------------------------------------------
# single-query cached attention + ragged paged attention (ISSUE 6)
#
# `single_query_cached_attention` is the SHARED decode-attention math: the
# dense-cache incremental decoder (models/transformer.py decode_step) and the
# serving engine's paged-KV fallback path both call this exact function, so
# a request decoded through the paged cache is bitwise-identical to one
# decoded through the dense cache (given the same context width).
#
# `ragged_paged_attention` (arXiv:2604.15464 style) lets requests of
# DIFFERENT lengths share one attention launch per decode step: each slot
# owns a page table into a fixed device-resident page pool, and the Pallas
# kernel walks that table with scalar-prefetch index maps (the page id is
# read from SMEM before the DMA is issued, so the gather never materialises
# a dense (S, Lmax) context in HBM). Off-TPU (the CPU test mesh) a pure-lax
# gather fallback reproduces the same numbers through the shared math above.
# ---------------------------------------------------------------------------
def single_query_cached_attention(qh, kc, vc, mask=None):
    """Attention of a single query token over a cached context.

    qh: (B, H, 1, dh); kc/vc: (B, H, L, dh); mask: boolean broadcastable to
    (B, H, 1, L), True = attend (None = attend everywhere). Returns
    (B, H, 1, dh). fp32 score accumulation, softmax in fp32, output in the
    value dtype — the decode-path contract shared by the dense and paged
    decoders."""
    dh = qh.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kc,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(dh))
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vc)


def pool_lanes(head_dim):
    """The row width to keep a head-major (H, P, psize, lanes) pool at.
    On the TPU the head size rounded up to whole 128-lane tiles: the
    client's default layout for such an array is row-major, which is
    what `_rpa_kernel`'s (heads, 1, psize, lanes) blocks read; an array whose
    minor dimension is under 128 it lays out with another dimension in
    the lanes (to save the padding), and every program over it then
    copies the pool into the kernel's layout and back. Elsewhere there
    is no such layout, and the head size itself."""
    return -(-head_dim // 128) * 128 if on_tpu() else head_dim


def _dequant_gathered(pages, page_tables, scales, dtype, dh):
    """Each slot's pages out of a head-major (H, P, psize, lanes) pool
    as the dense (S, H, npages * psize, dh) context the shared math
    takes; with per-page (H, P) `scales` (int8 KV mode, ISSUE 14)
    dequantize the gathered context — never the whole pool — into
    `dtype`."""
    ctx = pages[:, page_tables][..., :dh]     # (H, S, npages, psize, dh)
    if scales is not None:
        ctx = ctx.astype(dtype) * scales[:, page_tables][..., None, None]
    H, S, npages, psize, _ = ctx.shape
    return ctx.reshape(H, S, npages * psize, dh).transpose(1, 0, 2, 3)


def _paged_attention_lax(q, k_pages, v_pages, page_tables, lengths,
                         k_scales=None, v_scales=None):
    """Pure-lax fallback: gather each slot's pages into a dense context,
    then run the SAME shared math as the dense decoder (so CPU serving is
    bitwise-parity with `decode_step` on equal context width).

    q: (S, H, dh); k_pages/v_pages: (H, P, psize, lanes >= dh);
    page_tables: (S, npages) int32; lengths: (S,) int32 valid positions
    (including the current token). k_scales/v_scales: optional (H, P)
    per-head/per-page dequant scales for int8 page pools (ISSUE 14) —
    only the GATHERED context dequantizes, never the pool. Returns
    (S, H, dh)."""
    dh = q.shape[-1]
    kc = _dequant_gathered(k_pages, page_tables, k_scales, q.dtype, dh)
    vc = _dequant_gathered(v_pages, page_tables, v_scales, q.dtype, dh)
    L = kc.shape[2]
    mask = (jnp.arange(L)[None, :] < lengths[:, None])[:, None, None, :]
    return single_query_cached_attention(q[:, :, None, :], kc, vc,
                                         mask)[:, :, 0]


def _paged_attention_lax_multi(q, k_pages, v_pages, page_tables, lengths,
                               k_scales=None, v_scales=None):
    """Pure-lax fallback for the WIDENED (speculative-verify) launch:
    gather each slot's pages into a dense context, then the SAME shared
    math as `_paged_attention_lax`, with one extra query axis.

    q: (S, W, H, dh) — W query tokens per slot at consecutive positions;
    lengths: (S,) int32 keys visible to query 0 (including its own
    position); query i sees exactly `lengths + i` keys, which is the
    ragged-per-slot-query-length shape speculative verification and
    chunked prompt prefill need. Returns (S, W, H, dh)."""
    W = q.shape[1]
    dh = q.shape[-1]
    kc = _dequant_gathered(k_pages, page_tables, k_scales, q.dtype, dh)
    vc = _dequant_gathered(v_pages, page_tables, v_scales, q.dtype, dh)
    L = kc.shape[2]
    vis = lengths[:, None] + jnp.arange(W, dtype=lengths.dtype)[None, :]
    mask = (jnp.arange(L)[None, None, :]
            < vis[:, :, None])[:, None, :, :]        # (S, 1, W, L)
    qh = q.transpose(0, 2, 1, 3)                     # (S, H, W, dh)
    out = single_query_cached_attention(qh, kc, vc, mask)
    return out.transpose(0, 2, 1, 3)


# what the page blocks of one grid step may hold of the chip's fast memory,
# both buffers of the pipeline counted: half of the 16 MiB a kernel gets
_RPA_VMEM_BUDGET = 8 << 20


def _rpa_plan(H, npages, psize, lanes, itemsize):
    """(heads, pages) one grid step of `_rpa_kernel` takes, from the
    shapes alone: all `H` heads of a slot and `min(8, npages)` of its
    pages, as long as the K and V blocks of the step, each
    double-buffered and in whole sublane tiles, fit `_RPA_VMEM_BUDGET`;
    a longer page or more heads take fewer pages a step, then a divisor
    of the heads. The grid is `_rpa_steps` of these."""
    tile = 8 * max(1, 4 // itemsize)        # sublane rows of a tile
    page = 2 * 2 * -(-psize // tile) * tile * lanes * itemsize
    heads = max(h for h in range(1, H + 1)
                if H % h == 0 and (h == 1 or h * page <= _RPA_VMEM_BUDGET))
    pages = max(1, min(8, npages, _RPA_VMEM_BUDGET // (heads * page)))
    return heads, pages


def _rpa_steps(S, H, npages, heads, pages):
    """The grid of one `mxtpu_rpa` call: a row a (slot, group of
    `heads`), a step for each `pages` of the table's width."""
    return S * (H // heads), -(-npages // pages)


def _rpa_row(g, groups):
    """(slot, group of heads) of grid row `g`: the row itself and 0 where
    a slot is one row, else `lax.div` / `lax.rem`, one instruction each
    (`//` and `%` lower to sign corrections that Mosaic traces anew in
    every one of a step's 17 index maps: 3 s of a server's set-up)."""
    if groups == 1:
        return g, 0
    return lax.div(g, jnp.int32(groups)), lax.rem(g, jnp.int32(groups))


def _rpa_kernel(*refs, psize, pps, block_k, heads, groups, window, sm_scale,
                quant=False):
    """Ragged paged attention over head-major (H, P, psize, lanes) pools:
    one SLOT per grid row with `heads` of its heads (all of them, unless
    `_rpa_plan` had to split: `groups` rows a slot then) and `pps` pages
    per inner step, one block spec a page, so the pipeline gathers them
    together: a page's block is the (heads, 1, psize, lanes) of the pool
    where it lies. A short context at small pages is bound by the count
    of grid steps, not by bytes, and this form takes heads x pps fewer
    than one (slot, head, page) a step. The page ids were already
    consumed by the BlockSpec index maps (scalar prefetch); here we only
    need the slot's valid length for masking and for skipping the steps
    past it. The body walks the heads; a head's keys of the step are one
    (pps * psize, lanes) tile, or `block_k`-row tiles where a forced
    `_rpa_block_k` asks for them.

    `window` (ISSUE 12) real query rows per slot, padded to the
    8-sublane tile: query row i masks keys at `len_ref[slot] + i` —
    consecutive positions, so a single per-slot scalar carries the whole
    ragged query-length structure. The one-token decode turn is
    window == 1 of the same kernel: a (1, dh) query block with a (1, 1)
    running max does not lower on the chip (Mosaic has no broadcast
    over sublanes and lanes at once), so every form keeps its running
    max/sum as lane-replicated (rows, 128) like the flash kernels.
    Rows beyond a slot's real window produce garbage nobody commits.

    quant (ISSUE 14): the page pools are int8 and two extra scalar-
    prefetch refs carry the per-page/per-head f32 dequant scales — a
    page's block dequantizes in VMEM right after the DMA, one scalar a
    head and page, so HBM only ever moves int8 bytes."""
    pt_ref, len_ref = refs[:2]
    ks_ref, vs_ref = refs[2:4] if quant else (None, None)
    refs = refs[4 if quant else 2:]
    q_ref, k_refs, v_refs = refs[0], refs[1:1 + pps], refs[1 + pps:1 + 2 * pps]
    o_ref, m_scr, l_scr, acc_scr = refs[1 + 2 * pps:]
    # grid row slot * groups + head group, step of pps pages
    (s_idx, hg), j = _rpa_row(pl.program_id(0), groups), pl.program_id(1)
    length = len_ref[s_idx]                 # keys visible to query row 0
    k_start = j * pps * psize
    wp = q_ref.shape[2]                     # padded query rows (>= 8)
    npages = pt_ref.shape[1]
    # the step's key tiles: [(page of the step, rows of it), ...] each
    if block_k == psize:
        tiles = [[(i, slice(None)) for i in range(pps)]]
    else:
        tiles = [[(i, slice(b, b + block_k))]
                 for i in range(pps) for b in range(0, psize, block_k)]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # steps beyond what the LAST real query row sees are skipped — the
    # ragged part: a 3-token request costs one step of work while its
    # 300-token neighbour walks its whole table, in the same launch
    @pl.when(k_start < length + window - 1)
    def _compute():
        if quant:
            page_ids = [pt_ref[s_idx, jnp.minimum(j * pps + i, npages - 1)]
                        for i in range(pps)]

        def rows_of(pages, scales, h, parts):
            out = []
            for i, rows in parts:
                x = pages[i][h, 0, rows, :]
                if quant:
                    # dequantize in VMEM, same element-wise form as the
                    # lax fallback's gathered dequant (parity pinned in
                    # interpret)
                    x = x.astype(jnp.float32) * scales[
                        hg * heads + h, page_ids[i]]
                out.append(x)
            return jnp.concatenate(out, 0)

        for h in range(heads):
            q = q_ref[0, h]                 # (wp, lanes)
            for t, parts in enumerate(tiles):
                k = rows_of(k_refs, ks_ref, h, parts)
                v = rows_of(v_refs, vs_ref, h, parts)
                nk = k.shape[0]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                qi = lax.broadcasted_iota(jnp.int32, (wp, nk), 0)
                kj = k_start + t * nk + lax.broadcasted_iota(
                    jnp.int32, (wp, nk), 1)
                keep = kj < length + qi
                if npages % pps:
                    # the last step's pages past the table's width
                    keep &= kj < npages * psize
                s = jnp.where(keep, s, -1e30)
                m_prev = m_scr[h, :, :1]    # (wp, 1)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)      # (wp, nk) fp32
                l_new = alpha * l_scr[h, :, :1] + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h] = jnp.broadcast_to(m_new, (wp, 128))
                l_scr[h] = jnp.broadcast_to(l_new, (wp, 128))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # a slot with length 0 (empty decode slot) has l == 0: guard the
        # divide; its output is garbage the scheduler never reads
        o_ref[0] = (acc_scr[:] /
                    jnp.maximum(l_scr[:, :, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "plan", "block_k", "wp", "interpret"))
def _rpa_pallas(q, k_pages, v_pages, page_tables, lengths, k_scales=None,
                v_scales=None, *, sm_scale, plan, block_k, wp, interpret):
    """q: (S, W, H, dh); pools (H, P, psize, lanes), the shape the block
    specs read: a page's block is its (heads, 1, psize, lanes) of the
    pool where it lies (a strided DMA of one tile a head), so nothing of
    a pool's size is made around the kernel; int8 scales (H, P). The
    grid is `_rpa_steps` of `plan`, what `_rpa_plan` gives a step. The
    kernel sees a head `lanes` wide: the query's lanes past dh are zero,
    the output's are dropped. Returns (S, W, H, dh).

    Jitted, so that a decoder's layers, which call it at one shape,
    trace and lower the body and its unrolled heads once and not once a
    layer. Whatever is decided while tracing comes in as a static
    argument for that: the plan, the forced knobs (`block_k`,
    `_rpa_block_k`; `wp`, `_rpa_sublanes`: the query rows padded to the
    8-sublane tile) and interpret mode."""
    S, W, H, dh = q.shape
    psize, lanes = k_pages.shape[2:]
    npages = page_tables.shape[1]
    quant = k_scales is not None
    heads, pps = plan
    groups = H // heads
    qr = q.transpose(0, 2, 1, 3)
    if wp != W or lanes != dh:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, wp - W), (0, lanes - dh)))
    qr = qr.reshape(S * groups, heads, wp, lanes)

    def page_at(i):
        # the paged gather: the page id comes from the scalar-prefetched
        # table, so the DMA fetches exactly the pages the slot owns —
        # never a dense (S, Lmax) context; entries past a slot's length
        # are the null page, and a block whose index stays is not
        # fetched again
        def index(g, j, pt, ln, *_):
            s_idx, hg = _rpa_row(g, groups)
            return hg, pt[s_idx, jnp.minimum(j * pps + i, npages - 1)], 0, 0
        return index
    pages = [pl.BlockSpec((heads, 1, psize, lanes), page_at(i))
             for i in range(pps)]
    block = pl.BlockSpec((1, heads, wp, lanes),
                         lambda g, j, pt, ln, *_: (g, 0, 0, 0))
    scal = (page_tables.astype(jnp.int32), lengths.astype(jnp.int32))
    if quant:
        # (H, P) f32 in SMEM: the kernel reads one scalar a head and page
        scal += (k_scales.astype(jnp.float32),
                 v_scales.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_rpa_kernel, psize=psize, pps=pps,
                          block_k=block_k, heads=heads, groups=groups,
                          window=W, sm_scale=sm_scale, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scal),  # page tables + lengths (+ scales)
            grid=_rpa_steps(S, H, npages, heads, pps),
            in_specs=[block] + pages + pages, out_specs=block,
            scratch_shapes=[pltpu.VMEM((heads, wp, 128), jnp.float32),
                            pltpu.VMEM((heads, wp, 128), jnp.float32),
                            pltpu.VMEM((heads, wp, lanes), jnp.float32)]),
        out_shape=_sds((S * groups, heads, wp, lanes), q.dtype,
                       q, k_pages, v_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mxtpu_rpa",
    )(*scal, qr, *([k_pages] * pps), *([v_pages] * pps))
    return out.reshape(S, H, wp, lanes)[:, :, :W, :dh].transpose(0, 2, 1, 3)


def _rpa_flat_kernel(*refs, psize, pps, kv_heads, rows, window, group,
                     sm_scale, dh):
    """Ragged paged attention over pools kept as (P, psize, H * dh), for a
    head size that fills the 128 lanes: one SLOT per grid row with all
    its KV heads, `pps` pages per inner step (one block spec a page, so
    the pipeline gathers them together): the grid `_rpa_kernel` has over
    head-major pools. A long context at small pages is bound by the
    count of grid steps, not by bytes: this form takes H x pps fewer
    than one (slot, head, page) a step.
    A page's block holds every head; head h's keys are its lanes
    h * dh .. (h + 1) * dh, read where they lie. The query block stacks
    the KV heads' `rows` query rows: a head's rows are (window position,
    query head of its group), position-major, so `group` rows share one
    position's mask and a KV tile is read once for the whole group (8
    query heads a KV head fill the 8-sublane tile that a lone query row
    pads). `window` counts QUERY rows a slot (the widened verify form;
    1 in a decode turn), not keys: every key up to the slot's length is
    attended. The span of KEYS a sliding-window layer's query reads is
    `_rpa_ring_kernel`'s `span`, over a ring and not these pools. On the
    device it is `mxtpu_rpa_flat`, a kernel of its own beside
    `mxtpu_rpa`."""
    pt_ref, len_ref, q_ref = refs[:3]
    k_refs, v_refs = refs[3:3 + pps], refs[3 + pps:3 + 2 * pps]
    o_ref, m_scr, l_scr, acc_scr = refs[3 + 2 * pps:]
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    span = pps * psize
    k_start = j * span

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(k_start < length + window - 1)
    def _compute():
        qi = lax.broadcasted_iota(jnp.int32, (rows, span), 0)
        if group > 1:
            qi = qi // group
        kj = k_start + lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        keep = kj < length + qi
        for h in range(kv_heads):
            r = slice(h * rows, (h + 1) * rows)
            lanes = slice(h * dh, (h + 1) * dh)
            k = jnp.concatenate([ref[0, :, lanes] for ref in k_refs], 0)
            v = jnp.concatenate([ref[0, :, lanes] for ref in v_refs], 0)
            s = jax.lax.dot_general(
                q_ref[0, r, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(keep, s, -1e30)
            m_prev = m_scr[r, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[r, :1] + jnp.sum(p, axis=-1,
                                                   keepdims=True)
            acc_scr[r, :] = acc_scr[r, :] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[r, :] = jnp.broadcast_to(m_new, (rows, 128))
            l_scr[r, :] = jnp.broadcast_to(l_new, (rows, 128))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] /
                    jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _rpa_flat_pallas(q, k_pages, v_pages, page_tables, lengths, sm_scale):
    """q: (S, W, Hq, dh); pools (P, psize, H * dh); returns q's shape."""
    S, W, Hq, dh = q.shape
    psize = k_pages.shape[1]
    H = k_pages.shape[2] // dh
    G = Hq // H
    npages = page_tables.shape[1]
    pps = min(8, npages)
    R = W * G                       # real query rows a (slot, KV head)
    # whole sublane tiles a head: 8 rows of 4 bytes, 16 of 2
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    rows = max(_rpa_sublanes(R), -(-R // tile) * tile)
    qr = q.reshape(S, W, H, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(S, H, R, dh)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))
    qr = qr.reshape(S, H * rows, dh)

    def page_at(i):
        return lambda s, j, pt, ln: (
            pt[s, jnp.minimum(j * pps + i, npages - 1)], 0, 0)
    pages = [pl.BlockSpec((1, psize, H * dh), page_at(i))
             for i in range(pps)]
    block = pl.BlockSpec((1, H * rows, dh), lambda s, j, pt, ln: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_rpa_flat_kernel, psize=psize, pps=pps,
                          kv_heads=H, rows=rows, window=W, group=G,
                          sm_scale=sm_scale, dh=dh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, -(-npages // pps)),
            in_specs=[block] + pages + pages, out_specs=block,
            scratch_shapes=[pltpu.VMEM((H * rows, 128), jnp.float32),
                            pltpu.VMEM((H * rows, 128), jnp.float32),
                            pltpu.VMEM((H * rows, dh), jnp.float32)]),
        out_shape=_sds((S, H * rows, dh), q.dtype, q, k_pages, v_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="mxtpu_rpa_flat",
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32), qr,
      *([k_pages] * pps), *([v_pages] * pps))
    return out.reshape(S, H, rows, dh)[:, :, :R] \
        .reshape(S, H, W, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(S, W, Hq, dh)


# ---------------------------------------------------------------------------
# ring paged attention (sliding-window decode)
# ---------------------------------------------------------------------------
def ring_rows_back(lengths, n):
    """(S, n) int32: how many positions behind the slot's current one
    (`lengths - 1`) the position that ring row r last took lies. Position
    p is kept at ring row p % n (page (p // psize) % R, row p % psize, n =
    R * psize), so row r holds the newest position <= the current one
    that is congruent to it; a row never written reads further back than
    the current position itself."""
    base = (lengths - 1) % n
    d = base[:, None] - jnp.arange(n, dtype=jnp.int32)[None, :]
    return jnp.where(d < 0, d + n, d)


def _ring_attention_lax(q, k_ring, v_ring, lengths, span, sm_scale):
    """Pure-lax form of `ring_paged_attention`: the slot's whole ring as
    a dense context, masked by the position each row holds."""
    S, Hq, dh = q.shape
    n = k_ring.shape[1]
    H = k_ring.shape[2] // dh
    back = ring_rows_back(lengths, n)
    keep = (back < span) & (back < lengths[:, None])
    qg = q.reshape(S, H, Hq // H, dh)
    kc, vc = (r.reshape(S, n, H, dh) for r in (k_ring, v_ring))
    s = jnp.einsum("shgd,snhd->shgn", qg, kc,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(keep[:, None, None, :], s, -1e30), -1)
    out = jnp.einsum("shgn,snhd->shgd", p.astype(vc.dtype), vc)
    return out.reshape(S, Hq, dh)


def _rpa_ring_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, kv_heads, rows,
                     span, sm_scale, dh):
    """Sliding-window decode attention over a per-slot RING: one SLOT a
    grid step with all its KV heads, its whole ring one block of K and
    one of V (the ring is the slot's own contiguous pages, so no page
    table and a block spec a pool, not a page). A row of the ring is
    masked by the position it holds, which follows from the slot's length
    alone (`ring_rows_back`): the `span` newest positions are attended,
    whatever older lap or nothing a row still holds is not. The query
    block stacks the KV heads' `rows` query rows as `_rpa_flat_kernel`'s
    does (where `window` counts QUERY rows a slot; the span of KEYS a
    query may read is `span` here). One softmax over the ring, no running
    maximum: a step sees every key there is."""
    n = k_ref.shape[1]
    length = len_ref[pl.program_id(0)]
    base = lax.rem(length - 1, jnp.int32(n))
    back = base - lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    back = jnp.where(back < 0, back + n, back)
    keep = (back < span) & (back < length)
    for h in range(kv_heads):
        r = slice(h * rows, (h + 1) * rows)
        lanes = slice(h * dh, (h + 1) * dh)
        s = jax.lax.dot_general(
            q_ref[0, r, :], k_ref[0, :, lanes], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(keep, s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        v = v_ref[0, :, lanes]
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, r, :] = (o / jnp.sum(p, axis=-1, keepdims=True)).astype(
            o_ref.dtype)


def _rpa_ring_pallas(q, k_ring, v_ring, lengths, span, sm_scale):
    """q: (S, Hq, dh); rings (S, n, H * dh); returns q's shape."""
    S, Hq, dh = q.shape
    n = k_ring.shape[1]
    H = k_ring.shape[2] // dh
    G = Hq // H
    # whole sublane tiles a KV head: 8 rows of 4 bytes, 16 of 2
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    rows = -(-G // tile) * tile
    qr = q.reshape(S, H, G, dh)
    if rows != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - G), (0, 0)))
    qr = qr.reshape(S, H * rows, dh)
    block = pl.BlockSpec((1, H * rows, dh), lambda s, ln: (s, 0, 0))
    ring = pl.BlockSpec((1, n, H * dh), lambda s, ln: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_rpa_ring_kernel, kv_heads=H, rows=rows,
                          span=span, sm_scale=sm_scale, dh=dh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[block, ring, ring], out_specs=block),
        out_shape=_sds((S, H * rows, dh), q.dtype, q, k_ring, v_ring),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="mxtpu_rpa_ring",
    )(lengths.astype(jnp.int32), qr, k_ring, v_ring)
    return out.reshape(S, H, rows, dh)[:, :, :G].reshape(S, Hq, dh)


def ring_paged_attention(q, k_pages, v_pages, lengths, window,
                         sm_scale=None):
    """Decode attention of a SLIDING-WINDOW layer, one launch a layer and
    decode step, over a cache that is a ring a slot.

    q: (S, Hq, dh) one query a slot; k_pages/v_pages: (S * R, psize,
    H * dh), slot s owning pages s * R .. s * R + R - 1 for its life,
    position p kept at its ring page (p // psize) % R, row p % psize
    (whoever writes the cache keeps that rule: `models.decoder_lm.mx_swa`,
    `serve.lm_runtime`'s prefill, which writes whole pages and so keeps
    one page more than the window fills); R * psize >= window, so that
    the `window` newest positions never share a row. lengths: (S,)
    int32 positions the slot holds INCLUDING the current one, as
    `ragged_paged_attention` takes them. The query at position
    t = lengths - 1 attends positions t - window < j <= t: every ring row
    is masked by the position it holds, computed from `lengths`, not
    stored. Grouped heads: query head h reads KV head h // (Hq // H).
    Returns (S, Hq, dh).

    On the TPU (or MXTPU_PALLAS_INTERPRET=1), for heads of whole 128-lane
    tiles, the Pallas kernel `mxtpu_rpa_ring`: a slot a grid step, its
    ring one block. Elsewhere a lax form with the same numbers."""
    S, _, dh = q.shape
    psize = k_pages.shape[1]
    R = k_pages.shape[0] // S
    if R * psize < window:
        raise ValueError(f"a ring of {R} pages of {psize} cannot hold a "
                         f"window of {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (dh ** 0.5)
    rings = [p.reshape(S, R * psize, p.shape[-1]) for p in (k_pages, v_pages)]
    form = (_rpa_ring_pallas if _rpa_pallas_ok(psize) and dh % 128 == 0
            else _ring_attention_lax)
    return form(q, *rings, lengths, int(window), float(sm_scale))


def _rpa_pallas_ok(psize):
    if os.environ.get("MXTPU_PALLAS_DISABLE") == "1":
        return False
    return ((on_tpu() or _interpret())
            and psize % 8 == 0 and psize >= 8)


def ragged_paged_attention(q, k_pages, v_pages, page_tables, lengths,
                           sm_scale=None, k_scales=None, v_scales=None):
    """One shared attention launch per decode step over a paged KV cache.

    q: (S, H, dh) — ONE query token per decode slot — or (S, W, H, dh)
    (ISSUE 12): W query tokens per slot at CONSECUTIVE positions, the
    ragged per-slot-query-length shape speculative verification and
    chunked prompt prefill use (query i of a slot sees `lengths + i`
    keys; rows past a slot's real window compute garbage nobody reads).
    k_pages/v_pages: fixed-size page pools in the shape their kernel
    reads where they lie, so that a decode program makes nothing of a
    pool's size: head-major (H, P, psize, lanes) for `_rpa_kernel` (a
    slot's heads and eight of its pages a grid step, a page's block the
    (H, 1, psize, lanes) of the pool; a row holds the head's dh values
    and zeros up to `lanes`: KEEP a pool at `pool_lanes(dh)`, any width
    from dh up is read), or page-major with the heads merged into the
    lanes, (P, psize, H * dh), the shape to KEEP a pool in when dh is a
    multiple of 128, because `_rpa_flat_kernel` then takes a slot's
    heads and several pages a step;
    page_tables: (S, npages) int32 page ids per slot (unused entries
    must point at a valid page — the pool's reserved null page 0);
    lengths: (S,) int32 valid cached positions per slot INCLUDING the
    current (first) token. Returns (S, H, dh) or (S, W, H, dh).

    Grouped-query attention: q may have a multiple of the pools' heads;
    query head h reads KV head h // (Hq // H). The flat-pool kernel takes
    the groups as they are; every other form repeats the KV heads and
    takes the plain path.

    k_scales/v_scales (ISSUE 14): per-head/per-page (H, P) f32 dequant
    scales for int8 head-major page pools. The Pallas kernels carry them
    through scalar prefetch (f32 in SMEM) and dequantize each page block
    in VMEM after the DMA — HBM traffic stays int8, the dequant rides
    free inside the kernel; the lax fallback dequantizes only the
    GATHERED context.

    On TPU (or MXTPU_PALLAS_INTERPRET=1) runs the Pallas kernel: the page
    table rides in scalar-prefetch SMEM and the BlockSpec index maps read
    it to DMA exactly the owned pages, skipping pages beyond each slot's
    length — mixed-length slots share one launch. Elsewhere the pure-lax
    gather fallback reproduces the same numbers through
    `single_query_cached_attention` (inference-only; no custom vjp).

    Tunable knobs (ISSUE 20; MXTPU_RPA_BLOCK_K / MXTPU_RPA_SUBLANES or a
    tune/overrides.py scope): a sub-page K tile inside a grid step
    (`_rpa_block_k`) and the padded query-row count of the widened form
    (`_rpa_sublanes`). Invalid values fall back loudly
    (`pallas_block_override_ignored`). The grid itself has no knob: it
    follows from the shapes (`_rpa_plan`)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if k_pages.ndim == 3:
        if _rpa_pallas_ok(k_pages.shape[1]) and q.shape[-1] % 128 == 0 \
                and k_scales is None:
            out = _rpa_flat_pallas(q if q.ndim == 4 else q[:, None],
                                   k_pages, v_pages, page_tables, lengths,
                                   sm_scale)
            return out if q.ndim == 4 else out[:, 0]
        k_pages, v_pages = (
            p.reshape(*p.shape[:2], -1, q.shape[-1]).transpose(2, 0, 1, 3)
            for p in (k_pages, v_pages))
    # grouped KV heads outside the flat kernel (a head size under 128, or
    # head-major pools): the plain path over repeated heads, until a
    # configuration needs a kernel there
    rep = q.shape[-2] // k_pages.shape[0]
    if rep > 1:
        k_pages, v_pages = (jnp.repeat(p, rep, 0) for p in (k_pages, v_pages))
        if k_scales is not None:
            k_scales, v_scales = (jnp.repeat(sc, rep, 0)
                                  for sc in (k_scales, v_scales))
    if rep > 1 or not _rpa_pallas_ok(k_pages.shape[2]):
        lax_fn = (_paged_attention_lax_multi if q.ndim == 4
                  else _paged_attention_lax)
        return lax_fn(q, k_pages, v_pages, page_tables, lengths,
                      k_scales=k_scales, v_scales=v_scales)
    # the one-token decode turn is the W == 1 window of the same kernel
    qw = q if q.ndim == 4 else q[:, None]
    H, _, psize, lanes = k_pages.shape
    out = _rpa_pallas(
        qw, k_pages, v_pages, page_tables, lengths, k_scales, v_scales,
        sm_scale=float(sm_scale), block_k=_rpa_block_k(psize),
        wp=_rpa_sublanes(qw.shape[1]), interpret=_interpret(),
        plan=_rpa_plan(H, page_tables.shape[1], psize, lanes,
                       k_pages.dtype.itemsize))
    return out if q.ndim == 4 else out[:, 0]


# ---------------------------------------------------------------------------
# latent paged attention (MLA decode, the absorbed form)
# ---------------------------------------------------------------------------
def _latent_attention_lax(q, lat_pages, page_tables, lengths, kv_rank):
    """Pure-lax fallback of `latent_paged_attention`: each slot's pages
    gathered into a dense (S, L, lanes) context that is keys and, in its
    first kv_rank values, values."""
    width = q.shape[-1]
    ctx = lat_pages[page_tables]            # (S, npages, psize, lanes)
    ctx = ctx.reshape(ctx.shape[0], -1, ctx.shape[-1])
    s = jnp.einsum("shw,skw->shk", q, ctx[..., :width],
                   preferred_element_type=jnp.float32)
    keep = jnp.arange(ctx.shape[1])[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None, :], s, -1e30), -1)
    return jnp.einsum("shk,skc->shc", p.astype(q.dtype),
                      ctx[..., :kv_rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _mla_decode_kernel(pt_ref, len_ref, q_ref, pool_ref, o_ref, kbuf, sem,
                       par, m_scr, l_scr, acc_scr, *, psize, cpp, kv_rank):
    """One SLOT a grid step with all its query heads; the slot's LIVE
    latent pages fetched by the kernel's own DMAs from the pool left in
    HBM, `cpp` pages (a chunk of cpp * psize rows) at a time into one of
    two VMEM buffers while the other is computed on. A block spec a page
    costs the pipeline about 44 ns whether the page is live or not
    (PERF.md section 6, PR 32: 1.45 of 2.09 ms a call at 32,768 specs for
    10,400 live pages); here a dead page costs nothing, and the next
    slot's first chunk is in flight while this slot's last is computed
    (`par` carries the buffer's parity from step to step, so the grid is
    sequential). A chunk's rows are one (cpp * psize, lanes) tile that
    the heads' queries score against whole (the zero lanes past the rows'
    width add nothing) and whose first `kv_rank` lanes are the values:
    read once, used twice. Rows past the slot's length are masked; what a
    buffer holds there is an older chunk's rows or the zeros it started
    with, never a NaN. Online softmax in float32, running max and sum
    lane-replicated (rows, 128)."""
    s_idx, n_slots = pl.program_id(0), pl.num_programs(0)
    npages = pt_ref.shape[1]
    chunk = cpp * psize
    rows = q_ref.shape[1]

    def copies(slot, c, buf, go):
        """Start (or wait for) the DMAs of chunk `c` of `slot`: a page a
        descriptor, live pages only; start and wait see the same ones."""
        length = len_ref[slot]
        for i in range(cpp):
            pg = c * cpp + i

            @pl.when(pg * psize < length)
            def _():
                dma = pltpu.make_async_copy(
                    pool_ref.at[pt_ref[slot, jnp.minimum(pg, npages - 1)]],
                    kbuf.at[buf, pl.ds(i * psize, psize)], sem.at[buf])
                dma.start() if go else dma.wait()

    @pl.when(s_idx == 0)
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        par[0] = 0
        copies(0, 0, 0, True)

    m_scr[:] = jnp.full_like(m_scr, -1e30)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    length, first = len_ref[s_idx], par[0]
    n_chunks = jnp.maximum(1, lax.div(length + (chunk - 1), chunk))
    nxt = jnp.minimum(s_idx + 1, n_slots - 1)

    def body(c, carry):
        buf = lax.rem(first + c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            copies(s_idx, c + 1, 1 - buf, True)

        @pl.when((c + 1 == n_chunks) & (s_idx + 1 < n_slots))
        def _():
            copies(nxt, 0, 1 - buf, True)

        copies(s_idx, c, buf, False)
        k = kbuf[buf]
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kj = c * chunk + lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        s = jnp.where(kj < length, s, -1e30)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(k.dtype), k[:, :kv_rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    lax.fori_loop(0, n_chunks, body, 0)
    par[0] = lax.rem(first + n_chunks, 2)
    # a slot with length 0 has every row masked: p = 1 over rows that are
    # finite, an output nobody reads
    o_ref[0] = (acc_scr[:] /
                jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_rank", "cpp", "interpret"))
def _mla_decode_pallas(q, lat_pages, page_tables, lengths, *, kv_rank, cpp,
                       interpret):
    """q (S, H, width) pre-scaled; lat_pages (P, psize, lanes >= width);
    returns (S, H, kv_rank). Jitted, as `_rpa_pallas` is and for its
    reason: a decoder's layers trace and lower the body once."""
    S, H, width = q.shape
    psize, lanes = lat_pages.shape[1:]
    # whole sublane tiles of query rows: 8 rows of 4 bytes, 16 of 2
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    rows = -(-H // tile) * tile
    if rows != H or lanes != width:
        q = jnp.pad(q, ((0, 0), (0, rows - H), (0, lanes - width)))
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, psize=psize, cpp=cpp,
                          kv_rank=kv_rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S,),
            in_specs=[pl.BlockSpec((1, rows, lanes),
                                   lambda s, pt, ln: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, kv_rank),
                                   lambda s, pt, ln: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, cpp * psize, lanes), lat_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, kv_rank), jnp.float32)]),
        out_shape=_sds((S, rows, kv_rank), q.dtype, q, lat_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mxtpu_mla_decode",
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      lat_pages)
    return out[:, :H]


# rows a chunk of `mxtpu_mla_decode` holds: whole pages up to this many
# (512 rows of 640 lanes are 0.65 MB a buffer, and two buffers)
_MLA_STEP_KEYS = 512


def latent_paged_attention(q, lat_pages, page_tables, lengths, kv_rank):
    """Decode attention of multi-head latent attention in its absorbed
    form, one launch a layer and decode step over a paged LATENT cache.

    q: (S, H, width) one query a slot and head against cached rows,
    already scaled: `q_lat | q_rope`, width = kv_rank + rope size;
    lat_pages: (P, psize, lanes >= width) ONE pool a layer, a row a token:
    `c_kv | k_rope` and zeros up to `lanes` (keep a pool at
    `pool_lanes(width)`: whole 128-lane tiles on the chip). Every head
    reads the same rows: they are its keys and, in their first `kv_rank`
    values, its values. page_tables (S, npages) int32 and lengths (S,)
    (the current position included) as `ragged_paged_attention` takes
    them. Returns (S, H, kv_rank): P c_kv, before the value up-projection.

    On the TPU (or MXTPU_PALLAS_INTERPRET=1) the Pallas kernel
    `mxtpu_mla_decode`: a slot a grid step, its live pages fetched by the
    kernel's own DMAs `_MLA_STEP_KEYS` rows at a time, the page ids from
    scalar prefetch. Elsewhere a lax gather with the same numbers."""
    psize = lat_pages.shape[1]
    if not _rpa_pallas_ok(psize):
        return _latent_attention_lax(q, lat_pages, page_tables, lengths,
                                     kv_rank)
    cpp = max(1, min(page_tables.shape[1], _MLA_STEP_KEYS // psize))
    return _mla_decode_pallas(q, lat_pages, page_tables, lengths,
                              kv_rank=kv_rank, cpp=cpp,
                              interpret=_interpret())


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------
def _ln_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = xc * rstd
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x, gamma, beta, eps):
    y, _m, _r = _fused_ln_fwd_impl(x, gamma, beta, eps)
    return y


def _fused_ln_fwd_impl(x, gamma, beta, eps):
    d = x.shape[-1]
    lead = x.shape[:-1]
    # rows are independent: under a `kernel_mesh` scope they split over
    # the batch axis (dim 0 of x is the batch, and stays dim-0-major here)
    out, mean, rstd = _over_mesh(
        functools.partial(_ln_rows, eps=eps),
        (x.reshape(-1, d), gamma, beta), (1, 0, 0), (1, 1, 1))
    return (out.reshape(x.shape), mean.reshape(lead + (1,)),
            rstd.reshape(lead + (1,)))


def _ln_rows(x2, gamma, beta, *, eps):
    """(rows, d) layernorm -> (y, mean (rows, 1), rstd (rows, 1)): the
    Pallas kernel where the rows and lanes tile, XLA otherwise."""
    rows, d = x2.shape
    if ((on_tpu() or _interpret()) and d % 128 == 0
            and rows % 8 == 0 and rows >= 8):
        br = min(256, rows)
        while rows % br:
            br //= 2
        return tuple(pl.pallas_call(
            functools.partial(_ln_kernel, eps=eps),
            grid=(rows // br,),
            in_specs=[
                pl.BlockSpec((br, d), lambda i: (i, 0)),
                pl.BlockSpec((d,), lambda i: (0,)),
                pl.BlockSpec((d,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((br, d), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                _sds((rows, d), x2.dtype, x2, gamma, beta),
                _sds((rows, 1), jnp.float32, x2, gamma, beta),
                _sds((rows, 1), jnp.float32, x2, gamma, beta),
            ],
            interpret=_interpret(),
            name="mxtpu_layer_norm",
        )(x2, gamma, beta))
    xf = x2.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = ((xc * rstd) * gamma.astype(jnp.float32)
         + beta.astype(jnp.float32)).astype(x2.dtype)
    return y, mean, rstd


def _fused_ln_vjp_fwd(x, gamma, beta, eps):
    y, mean, rstd = _fused_ln_fwd_impl(x, gamma, beta, eps)
    return y, (x, gamma, mean, rstd)


def _fused_ln_vjp_bwd(eps, res, dy):
    x, gamma, mean, rstd = res
    red = tuple(range(x.ndim - 1))
    xhat = (x.astype(jnp.float32) - mean) * rstd
    dyf = dy.astype(jnp.float32)
    dgamma = jnp.sum(dyf * xhat, axis=red)
    dbeta = jnp.sum(dyf, axis=red)
    dxhat = dyf * gamma.astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (dxhat - m1 - xhat * m2) * rstd
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_fused_ln.defvjp(_fused_ln_vjp_fwd, _fused_ln_vjp_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last axis. Pallas single-pass forward on TPU (XLA
    fallback elsewhere) with a closed-form custom-vjp backward, so it is
    trainable on the Pallas path too."""
    return _fused_ln(x, gamma, beta, float(eps))
