"""Plain reference of one pipeline stage of Falcon-H1-34B-Instruct
(configs/falcon_h1_34b_l4.json): token ids in, logits out, the whole
forward every time.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
pages, no chunks, no batching. Every layer is a PARALLEL hybrid:

    h  = N1(x)
    x <- x + m_ssm_out Mamba2(m_ssm_in h) + m_attn_out Attn(m_attn_in h)
    x <- x + m_down W_down(W_up N2(x) * SiLU(m_gate W_gate N2(x)))

with N an RMSNorm (eps 1e-5) and one pre-norm shared by both mixers.

  Mamba2  `[z | x | B | C | dt] = (W_in u) * mup`, the muP vector one
        factor a segment (`ssm_multipliers`, in that order); `[x | B | C]`
        through a causal depthwise convolution of 4 taps with a bias,
        then SiLU; x as 32 heads of 128, B and C as 2 groups of 256, head
        h reading group h // 16; `delta = softplus(dt + dt_bias)` (no
        clamp), `a = exp(delta A)`, `A = -exp(A_log)`; the state `S <- a S
        + delta x B^T`, `y = S C + D x`, as a plain `lax.scan` over
        positions; `y <- RMSNorm_group(y * SiLU(z)) * gamma`, the norm
        over each of the 2 groups of 2048 channels AFTER the gate; out =
        W_out y.
  Attn  q = W_q u as 20 heads of 128, k = W_k u and v = W_v u as 4 KV
        heads (query head h reads KV head h // 5); q and k rotated over
        the WHOLE head in the half-split convention (value i pairs with
        value i + 64), the pair i of position t turning by t theta^(-2i /
        128), theta 1e11; k times `key_multiplier`; scores q . k /
        sqrt(128), causal, one softmax; out = W_o [heads]. No bias, no
        gate.

Embedding times `embedding_multiplier`; final RMSNorm; untied head, the
logits times `lm_head_multiplier`. What the source leaves open is under
`assumed` in the configuration; departures from the published modelling
code: the multipliers are applied in float32 (the published code applies
them to bfloat16 activations), and the conv, scan and norms are float32
throughout.

`weights` is the dict `lib.lm_par.reference_weights` builds from the model
under test: the very same arrays in the model's type and packing (no
copy: a second set would not fit beside the server that is being
checked), each cast to float32 where it is used, a large matrix in blocks
of its rows. The packing: `in` rows z | x | B | C | dt; `qkv` rows W_q |
W_k | W_v; `gate_up` rows W_gate | W_up; every matrix (out, in).

`forward` also hands out what a server keeps between turns: each layer's
Mamba-2 state after the first `n` positions (later positions leave it
alone) and the convolution's inputs at the last K - 1 of them; and each
layer's keys after the rotation and the multiplier, (T, Hkv * dh), which
a page holds a position a row. `head_from`, `head_rows`: the logits of
`head_rows` positions from `head_from` only (261,120 rows over 1,039
positions is 1.1 GB that no check reads).

Controls, to place a check's limits (PERF.md section 4). `low` computes
below the configuration's precision: "state" keeps the recurrent state in
bfloat16; "all": that, and every matmul's inputs (q, k and v among them)
rounded to float8_e4m3fn. `knobs` ({name: value}, traced, so that no
control compiles the forward again; `published_knobs` gives the published
values) sets a multiplier to 1 or leaves a term out: a branch's `ssm_out`
/ `attn_out` at 0 drops it, `rotate` 0 no rotation, `theta` 1e4,
`one_norm` 1 one RMSNorm over all 4096 channels for one a group,
`d_skip` 0 no D, `conv_bias` 0 no convolution bias. Each has to FAIL the
cell's check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
LOW = (None, "state", "all")
BLOCK = 32 * 2 ** 20      # a matrix cast to float32 this many values at most


def published_knobs(dims):
    """The published values of everything a control may move, from the
    spec's fields (`dims`)."""
    ssm_in, ssm_out, attn_in, attn_out = dims["par_mult"]
    gate, down = dims["ffn_mult"]
    return {"embed_mult": dims["embed_mult"], "head_mult": dims["head_mult"],
            "key_mult": dims["key_mult"], "gate_mult": gate,
            "down_mult": down, "ssm_in": ssm_in, "ssm_out": ssm_out,
            "attn_in": attn_in, "attn_out": attn_out,
            "ssm_mult": tuple(dims["ssm_mult"]),
            "theta": dims["rope_theta"], "rotate": 1.0, "one_norm": 0.0,
            "d_skip": 1.0, "conv_bias": 1.0}


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


class _How:
    """x @ w^T and the state's type, at full or at lowered precision."""

    def __init__(self, low):
        if low not in LOW:
            raise ValueError(f"low = {low!r}")
        self.low = low == "all"
        self.state_dtype = jnp.bfloat16 if low else F32

    def r(self, x):
        x = x.astype(F32)
        return x.astype(jnp.float8_e4m3fn).astype(F32) if self.low else x

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


def _conv(x, w, bias):
    """Causal depthwise convolution; w (K, C), w[K-1] on the current
    position, zeros before the sequence."""
    kw = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((kw - 1, x.shape[1]), F32), x])
    return sum(pad[j:j + x.shape[0]] * w[j].astype(F32)
               for j in range(kw)) + bias


def _mamba(m, p, dims, kn, u, n):
    """The Mamba-2 half one position at a time. Returns (out, S after the
    first n positions (H, P, N), the convolution's inputs at positions
    n - K + 1 .. n - 1 (zeros before the sequence))."""
    t = u.shape[0]
    h, hd = dims["ssm_heads"], dims["ssm_head_dim"]
    g, ns, eps = dims["ssm_groups"], dims["ssm_state"], dims["eps"]
    inner = h * hd
    kw = p["conv"].shape[0]
    vec = jnp.repeat(jnp.asarray(kn["ssm_mult"], F32),
                     np.array([inner, inner, g * ns, g * ns, h]),
                     total_repeat_length=2 * inner + 2 * g * ns + h)
    z, pre, dt = jnp.split(m.mm(u * kn["ssm_in"], p["in"]) * vec,
                           [inner, 2 * inner + 2 * g * ns], -1)
    conv = jax.nn.silu(_conv(pre, p["conv"],
                             kn["conv_bias"] * p["conv_bias"].astype(F32)))
    x, b, c = jnp.split(conv, [inner, inner + g * ns], -1)
    x = x.reshape(t, h, hd)
    b, c = (jnp.repeat(a.reshape(t, g, ns), h // g, 1) for a in (b, c))
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(F32))      # (T, H)
    decay = jnp.exp(-jnp.exp(p["a_log"].astype(F32)) * delta)

    def step(s_old, xs):
        i, x_t, b_t, c_t, delta_t, a_t = xs
        s = a_t[:, None, None] * s_old.astype(F32) \
            + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        kept = jnp.where(i < n, s.astype(m.state_dtype), s_old)
        return kept, jnp.einsum("hpn,hn->hp", s, c_t)

    state, y = lax.scan(step, jnp.zeros((h, hd, ns), m.state_dtype),
                        (jnp.arange(t), x, b, c, delta, decay))
    y = y + kn["d_skip"] * p["d_skip"].astype(F32)[:, None] * x
    y = y.reshape(t, inner) * jax.nn.silu(z)

    def normed(groups):
        v = y.reshape(t, groups, -1)
        return (v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
                ).reshape(t, inner)

    y = jnp.where(kn["one_norm"] > 0, normed(1), normed(g)) \
        * p["norm"].astype(F32)
    before = jnp.concatenate([jnp.zeros((kw - 1, pre.shape[1]), F32), pre])
    return (kn["ssm_out"] * m.mm(y, p["o"]), state,
            lax.dynamic_slice_in_dim(before, n, kw - 1))


def _rotate(x, theta, on):
    """x (T, heads, dh): the pair (i, i + dh / 2) of position t turned by
    on * t * theta^(-2i / dh)."""
    half = x.shape[-1] // 2
    f = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = on * jnp.arange(x.shape[0], dtype=F32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, p, dims, kn, u):
    """The attention half. Returns (out, the keys as a page holds them
    (T, Hkv * dh))."""
    t = u.shape[0]
    h, hk, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    u = u * kn["attn_in"]
    w_q, w_k, w_v = jnp.split(p["qkv"], [h * dh, (h + hk) * dh])
    q = _rotate(m.mm(u, w_q).reshape(t, h, dh), kn["theta"], kn["rotate"])
    k = _rotate(m.mm(u, w_k).reshape(t, hk, dh), kn["theta"],
                kn["rotate"]) * kn["key_mult"]
    v = m.mm(u, w_v).reshape(t, hk, dh)
    keys = k.reshape(t, hk * dh)
    q, k, v = m.r(q), m.r(k), m.r(v)
    k, v = (jnp.repeat(a, h // hk, 1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", a, v).reshape(t, h * dh)
    return kn["attn_out"] * m.mm(o, p["o"]), keys


def _mlp(m, p, kn, u):
    g, up = jnp.split(m.mm(u, p["gate_up"]), 2, -1)
    return kn["down_mult"] * m.mm(jax.nn.silu(kn["gate_mult"] * g) * up,
                                  p["down"])


def forward(weights, dims, tokens, n=None, knobs=None, low=None,
            head_from=None, head_rows=None):
    """tokens (T,) int32 -> {"logits" (T, V) float32, or (head_rows, V)
    from position head_from; "state", "tails" and "keys": a list with an
    entry a layer}. `dims`: a hashable tuple of (name, value) pairs
    (static under jit), see `lib.lm.dims`; `knobs`: `published_knobs(dims)`
    with a control's changes (traced)."""
    dims = dict(dims)
    kn = published_knobs(dims) if knobs is None else knobs
    m = _How(low)
    t = tokens.shape[0]
    n = t if n is None else n
    out = {"state": [], "tails": [], "keys": []}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32) * kn["embed_mult"]
        for p in weights["layers"]:
            u = _rms(x, p["norm1"], dims["eps"])
            y_s, state, tails = _mamba(m, p["mixer"]["ssm"], dims, kn, u, n)
            y_a, keys = _attention(m, p["mixer"]["attn"], dims, kn, u)
            x = x + y_s + y_a
            x = x + _mlp(m, p["ffn"], kn, _rms(x, p["norm2"], dims["eps"]))
            out["state"].append(state)
            out["tails"].append(tails)
            out["keys"].append(keys)
        if head_rows is not None:
            x = lax.dynamic_slice_in_dim(x, head_from, head_rows)
        out["logits"] = kn["head_mult"] * m.mm(
            _rms(x, weights["final_norm"], dims["eps"]), weights["head"])
    return out


def logits(weights, dims, tokens, low=None):
    """tokens (T,) int32 -> logits (T, V) float32."""
    return forward(weights, dims, tokens, low=low)["logits"]
