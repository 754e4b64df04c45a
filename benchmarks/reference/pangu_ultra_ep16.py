"""Plain reference of one chip's share of openPangu-Ultra-MoE-718B
(configs/pangu_ultra_ep16.json): token ids in, logits for every position
out, the whole causal forward every time.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
pages, no batching. Sandwich blocks: `x = x + N_b(MLA(N_a(x)))`, then
`x = x + N_d(FFN(N_c(x)))`, RMSNorm everywhere. Multi-head latent
attention in the EXPANDED form only (the program's decode path uses the
absorbed form, so it is checked against different mathematics): every
head's `k_nope` and `v` are made from the normed latent `c_kv`, the
rotary key `k_rope` is one for all heads, scores
`(q_nope . k_nope + q_rope . k_rope) / sqrt(n + r)` under a causal mask,
one head at a time. The feed-forward of a layer is a dense SwiGLU or a
mixture of experts: sigmoid scores over all 256, the 8 largest chosen
(no selection bias, no groups), chosen scores normalised, times the
scaling factor, a loop over the experts HELD (the other experts' terms
are left out, as in the program, and that partial sum goes on), plus one
shared expert. Untied embedding and head over the held slice of the
vocabulary. What the source leaves open is under `assumed` in the
configuration; the rotation pairs value i with value i + r/2.

`weights` is the dict `lib.lm_mla.reference_weights` builds from the
model under test: the very same arrays, in the model's type (no copy: at
9.8 GB a second set would not fit). Each is cast to float32 where it is
used, and a matrix of more than 2**24 values IN BLOCKS of its rows, one
after another (`lax.map`), an expert at a time, a head at a time: 4.9 G
parameters do not fit the chip in float32. Every matrix is (out, in) but
the expert banks, `gate_up` (experts, d, gate | up) and `down` (experts,
width, d), input-major.

`forward` also hands out what a server keeps between turns: the rows
each latent-attention layer would cache, `c_kv` after its norm |
`k_rope` after the rotation, for every position; and the expert ids each
position used with their `slack` (-1 and 0 in a dense layer). `routing`
(layers, T, k) FORCES those ids (an entry under 0 keeps the reference's
own choice), for the reason `reference/solar_open2_ep8.py` gives: a
router that ranks 256 scores flips where two lie closer than the
subject's precision resolves.

Controls, to place a check's limits (PERF.md section 4). `low` computes
below the configuration's precision (bfloat16): "all" rounds every
matmul's inputs to float8_e4m3fn; "cache" rounds only what a cache would
hold (`c_kv`, `k_rope`) to float8_e4m3fn. `leave_out` drops one term:
"rope" (no rotation), "post_norms" (N_b and N_d), "kv_norm" (the norm of
`c_kv`), "shared" (the shared expert), "scaling" (1 in place of
`routed_scaling_factor`). Each has to FAIL the cell's check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
F8 = jnp.float8_e4m3fn
LOW = (None, "all", "cache")
LEAVE_OUT = (None, "rope", "post_norms", "kv_norm", "shared", "scaling")
BLOCK = 1 << 24          # values of a matrix cast to float32 at a time


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


class _How:
    """x @ w^T at full or at lowered precision; the term a control
    leaves out."""

    def __init__(self, low, leave_out):
        if low not in LOW or leave_out not in LEAVE_OUT:
            raise ValueError(f"low = {low!r}, leave_out = {leave_out!r}")
        self.low, self.leave_out = low, leave_out

    def r(self, x):
        x = x.astype(F32)
        return x.astype(F8).astype(F32) if self.low == "all" else x

    def cached(self, x):
        return x.astype(F8).astype(F32) if self.low == "cache" else x

    def mm(self, x, w):
        """x W^T, w stored (out, in); a large w in blocks of its rows."""
        n_out, n_in = w.shape
        nb = 1
        while n_out % nb or (n_out // nb) * n_in > BLOCK:
            nb += 1
        x = self.r(x)
        if nb == 1:
            return x @ self.r(w).T
        y = lax.map(lambda wb: x @ self.r(wb).T,
                    w.reshape(nb, n_out // nb, n_in))
        return jnp.moveaxis(y, 0, 1).reshape(x.shape[0], n_out)

    def xw(self, x, w):
        """x W, w stored (in, out)."""
        return self.r(x) @ self.r(w)


def _rope(x, pos, theta):
    """(x_i + j x_{i + r/2}) e^{j pos theta^(-2i / r)} over the last axis
    of x (T, ..., r)."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), half)
    re, im = x[..., :half], x[..., half:]
    return jnp.concatenate([re * jnp.cos(ang) - im * jnp.sin(ang),
                            im * jnp.cos(ang) + re * jnp.sin(ang)], -1)


def _mla(m, p, dims, x):
    """x (T, d) -> (y, the rows a cache would keep (T, kv_rank + r))."""
    t = x.shape[0]
    h, n, r = dims["heads"], dims["nope_dim"], dims["rope_dim"]
    c, dv, eps = dims["kv_rank"], dims["v_dim"], dims["eps"]
    pos = jnp.arange(t)
    q = m.mm(_rms(m.mm(x, p["qa"]), p["qa_norm"], eps), p["qb"])
    q = q.reshape(t, h, n + r)
    kv = m.mm(x, p["kva"])
    c_kv, k_rope = kv[:, :c], kv[:, c:]
    if m.leave_out != "kv_norm":
        c_kv = _rms(c_kv, p["kv_norm"], eps)
    q_nope, q_rope = q[..., :n], q[..., n:]
    if m.leave_out != "rope":
        q_rope = _rope(q_rope, pos, dims["rope_theta"])
        k_rope = _rope(k_rope, pos, dims["rope_theta"])
    c_kv, k_rope = m.cached(c_kv), m.cached(k_rope)
    k_nope = m.mm(c_kv, p["kb"]).reshape(t, h, n)
    v = m.mm(c_kv, p["vb"]).reshape(t, h, dv)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def head(args):
        qn, qr, kn, vh = args                         # (T, .) of one head
        s = (m.r(qn) @ m.r(kn).T + m.r(qr) @ m.r(k_rope).T) \
            / jnp.sqrt(F32(n + r))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return m.r(a) @ m.r(vh)

    o = lax.map(head, tuple(a.swapaxes(0, 1)
                            for a in (q_nope, q_rope, k_nope, v)))
    y = m.mm(o.swapaxes(0, 1).reshape(t, h * dv), p["o"])
    return y, jnp.concatenate([c_kv, k_rope], -1)


def _swiglu(m, x, gate_up, down):
    """down(SiLU(gate x) * up x); gate_up (2 width, d), gate rows first."""
    g, u = jnp.split(m.mm(x, gate_up), 2, -1)
    return m.mm(jax.nn.silu(g) * u, down)


def _experts(m, p, dims, x, forced):
    """y = sum over the used experts HELD of w_e SwiGLU_e(x), plus the
    shared expert. Returns (y, ids used (T, k), slack (T,))."""
    s = jax.nn.sigmoid(m.mm(x, p["router"]))               # (T, 256)
    top, idx = lax.top_k(s, dims["top_k"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    chosen = jnp.take_along_axis(s, idx, -1)
    slack = top[:, -1] - chosen.min(-1)
    scaling = 1.0 if m.leave_out == "scaling" else dims["scaling"]
    wts = chosen / chosen.sum(-1, keepdims=True) * scaling
    y = jnp.zeros_like(x)
    if m.leave_out != "shared":
        y = _swiglu(m, x, p["shared_gate_up"], p["shared_down"])
    lo = dims["held_lo"]

    def expert(e, y):                                      # experts held
        w_e = jnp.sum(jnp.where(idx == lo + e, wts, 0.0), -1)
        gate, up = jnp.split(p["gate_up"][e], 2, 1)
        return y + w_e[:, None] * m.xw(
            jax.nn.silu(m.xw(x, gate)) * m.xw(x, up), p["down"][e])

    y = lax.fori_loop(0, p["gate_up"].shape[0], expert, y)
    return y, idx.astype(jnp.int32), slack


def forward(weights, dims, tokens, n=None, routing=None, low=None,
            leave_out=None):
    """tokens (T,) int32 -> {"logits" (T, V) float32; "latent": a list
    with a (T, kv_rank + r) array a layer; "routing" (layers, T, k) int32
    and "slack" (layers, T)}. `dims`: a hashable tuple of (name, value)
    pairs (static under jit), see `lib.lm.dims`. `n` is taken and unused
    (the rows of every position are handed out)."""
    dims = dict(dims)
    m = _How(low, leave_out)
    eps, t = dims["eps"], tokens.shape[0]
    post = m.leave_out != "post_norms"
    out = {"latent": [], "routing": [], "slack": []}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for i, p in enumerate(weights["layers"]):
            y, rows = _mla(m, p["mixer"], dims, _rms(x, p["norm1"], eps))
            out["latent"].append(rows)
            x = x + (_rms(y, p["norm1_post"], eps) if post else y)
            h = _rms(x, p["norm2"], eps)
            if "moe" in p:
                y, idx, slack = _experts(
                    m, p["moe"], dims, h,
                    None if routing is None else routing[i])
            else:
                y = _swiglu(m, h, p["ffn"]["gate_up"], p["ffn"]["down"])
                idx = jnp.full((t, dims["top_k"]), -1, jnp.int32)
                slack = jnp.zeros((t,), F32)
            out["routing"].append(idx)
            out["slack"].append(slack)
            x = x + (_rms(y, p["norm2_post"], eps) if post else y)
        out["logits"] = m.mm(_rms(x, weights["final_norm"], eps),
                             weights["head"])
    out["routing"], out["slack"] = (jnp.stack(out[k])
                                    for k in ("routing", "slack"))
    return out


def logits(weights, dims, tokens, low=None, leave_out=None):
    """tokens (T,) int32 -> logits (T, V) float32."""
    return forward(weights, dims, tokens, low=low,
                   leave_out=leave_out)["logits"]
