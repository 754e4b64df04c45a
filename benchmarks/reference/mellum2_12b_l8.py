"""Plain reference of one pipeline stage of Mellum2-12B-A2.5B-Instruct
(configs/mellum2_12b_l8.json): token ids in, logits out, the whole
forward every time.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no ring,
no pages. Every layer is the pair

    h  = x + Attn_l(RMSNorm(x))
    x' = h + Experts(RMSNorm(h))

  Attn  q = W_q u as H heads of dh, k = W_k u and v = W_v u as Hkv heads
        (query head h reads KV head h // (H / Hkv)); q and k rotated over
        the WHOLE head in the half-split convention (value i pairs with
        value i + dh / 2), the pair i of position t turning by t * f_l[i]
        with cos and sin times m_l. A sliding-window layer ("swa"):
        f[i] = theta^(-2i / dh), m = 1, and the softmax runs over the
        keys t - window < j <= t. A full layer ("gqa"): YaRN, c(n) =
        dh ln(orig / (2 pi n)) / (2 ln theta), low = floor(c(beta_fast)),
        high = ceil(c(beta_slow)) kept inside 0 .. dh - 1, ramp[i] =
        clip((i - low) / (high - low), 0, 1), f[i] = (1 - ramp[i])
        theta^(-2i / dh) + ramp[i] theta^(-2i / dh) / factor, m the
        configuration's attention factor; the softmax runs over j <= t.
        Scores q . k / sqrt(dh); one masked softmax over the whole
        sequence, a query head at a time (`lax.map`: a (T, T) block, not
        H of them); out = W_o [heads]. No gate, no QK norm, no bias.
  Experts  p = softmax(W_r u) over ALL experts; the k largest chosen;
        weights p_chosen / sum(p_chosen); a loop over the experts HELD,
        one at a time (`lax.fori_loop`), W_down (SiLU(W_gate u) * W_up u)
        each; no shared expert, no selection bias, no scaling.

Untied embedding and head. What the source leaves open is under `assumed`
in the configuration.

`weights` is the dict `lib.lm_swa.reference_weights` builds from the model
under test: the very same arrays, in the model's type and packing (no
copy: a second set would not fit beside the server that is being
checked), each cast to float32 where it is used, an expert at a time. The
packing: `qkv` rows W_q | W_k | W_v; an expert bank `gate_up` (experts, d,
2 * width), columns W_gate | W_up, and `down` (experts, width, d); every
other matrix (out, in).

`forward` also hands out what a server keeps between turns: each layer's
keys after the rotation, (T, Hkv * dh), which a ring or a page holds a
position a row; and the expert ids each position used with their `slack`.
`routing` (layers, T, k) FORCES those ids (an entry under 0 keeps the
reference's own choice), as `reference/solar_open2_ep8.py` says why.
`head_from`, `head_rows`: the logits of `head_rows` positions from
`head_from` only (a vocabulary of 98,304 over 4,111 positions is 1.6 GB
that no check reads).

Controls, to place a check's limits (PERF.md section 4). `low="all"`
computes below the configuration's precision: every matmul's inputs
rounded to float8_e4m3fn, the keys and values an attention reads (what a
cache holds) rounded to it too. `leave_out` drops or swaps one term:
"rotation" (no positional term), "yarn" (the plain table on full layers),
"attn_factor" (m = 1), "window" (sliding-window layers attend every
position), "sigmoid" (a sigmoid an expert for the softmax), "renorm" (the
chosen probabilities as they are). Each has to FAIL the cell's check.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LOW = (None, "all")
LEAVE_OUT = (None, "rotation", "yarn", "attn_factor", "window", "sigmoid",
             "renorm")


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


class _How:
    """x @ w^T at full or at lowered precision; the term a control leaves
    out."""

    def __init__(self, low, leave_out):
        if low not in LOW or leave_out not in LEAVE_OUT:
            raise ValueError(f"low = {low!r}, leave_out = {leave_out!r}")
        self.low = low == "all"
        self.leave_out = leave_out

    def r(self, x):
        x = x.astype(F32)
        return x.astype(jnp.float8_e4m3fn).astype(F32) if self.low else x

    def mm(self, x, w):
        """x W^T, w stored (out, in)."""
        return self.r(x) @ self.r(w).T

    def xw(self, x, w):
        """x W, w stored (in, out)."""
        return self.r(x) @ self.r(w)

    def without(self, name):
        return self.leave_out == name


def rope_table(dims, kind, m):
    """(f (dh / 2,), the factor on cos and sin) of a layer of `kind`."""
    dh, theta = dims["head_dim"], dims["rope_theta"]
    i = jnp.arange(dh // 2, dtype=F32)
    f = theta ** (-2.0 * i / dh)
    if kind == "swa" or not dims["rope_yarn"] or m.without("yarn"):
        return f, 1.0
    factor, orig, fast, slow, attention_factor = dims["rope_yarn"]

    def c(n):
        return dh * math.log(orig / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(fast)), 0)
    high = min(math.ceil(c(slow)), dh - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * f + ramp * f / factor,
            1.0 if m.without("attn_factor") else attention_factor)


def _rotate(x, f, scale):
    """x (T, heads, dh): the pair (i, i + dh / 2) of position t turned by
    t * f[i], cos and sin times `scale`."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * f
    cos, sin = (jnp.cos(ang) * scale)[:, None], (jnp.sin(ang) * scale)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, p, dims, kind, x):
    """x (T, d). Returns (out, the keys as a cache holds them
    (T, Hkv * dh))."""
    t = x.shape[0]
    h, hk, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    w_q, w_k, w_v = jnp.split(p["qkv"], [h * dh, (h + hk) * dh])
    q = m.mm(x, w_q).reshape(t, h, dh)
    k = m.mm(x, w_k).reshape(t, hk, dh)
    v = m.mm(x, w_v).reshape(t, hk, dh)
    if dims["attn_rope"] and not m.without("rotation"):
        f, scale = rope_table(dims, kind, m)
        q, k = _rotate(q, f, scale), _rotate(k, f, scale)
    keys = k.reshape(t, hk * dh)
    # what a cache holds, at the control's precision
    k, v = m.r(k), m.r(v)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = back >= 0
    if kind == "swa" and not m.without("window"):
        seen = seen & (back < dims["window"])

    def one_head(args):
        q_h, g = args                                   # (T, dh), KV head
        s = q_h @ k[:, g].T / jnp.sqrt(F32(dh))
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1) @ v[:, g]

    o = lax.map(one_head, (q.transpose(1, 0, 2),
                           jnp.arange(h) // (h // hk)))
    return m.mm(o.transpose(1, 0, 2).reshape(t, h * dh), p["o"]), keys


def _experts(m, p, dims, x, forced):
    """y = sum over the used experts HELD of w_e expert_e(x). Returns
    (y, ids used (T, k), slack (T,))."""
    logits = m.mm(x, p["router"])                          # (T, E)
    s = jax.nn.sigmoid(logits) if m.without("sigmoid") \
        else jax.nn.softmax(logits, -1)
    top, idx = lax.top_k(s, dims["top_k"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    chosen = jnp.take_along_axis(s, idx, -1)
    slack = top[:, -1] - chosen.min(-1)
    wts = chosen if m.without("renorm") \
        else chosen / chosen.sum(-1, keepdims=True)
    lo = dims["held_lo"]

    def add_expert(e, y):                                  # experts held
        w_e = jnp.sum(jnp.where(idx == lo + e, wts, 0.0), -1)
        g, u = jnp.split(m.xw(x, p["gate_up"][e]), 2, -1)
        return y + w_e[:, None] * m.xw(jax.nn.silu(g) * u, p["down"][e])

    y = lax.fori_loop(0, p["gate_up"].shape[0], add_expert,
                      jnp.zeros_like(x))
    return y, idx.astype(jnp.int32), slack


def forward(weights, dims, tokens, n=None, routing=None, low=None,
            leave_out=None, head_from=None, head_rows=None):
    """tokens (T,) int32 -> {"logits" (T, V) float32, or (head_rows, V)
    from position head_from; "keys": a list with a (T, Hkv * dh) array a
    layer, the rotated keys; "routing" (layers, T, k) int32 and "slack"
    (layers, T)}. `dims`: a hashable tuple of (name, value) pairs (static
    under jit), see `lib.lm.dims`. `n` is the siblings' argument (how
    many positions a recurrent state has seen) and reads nothing here: an
    attention cache holds a row a position."""
    dims = dict(dims)
    m = _How(low, leave_out)
    out = {"keys": [], "routing": [], "slack": []}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for i, (kind, p) in enumerate(zip(dims["pattern"],
                                          weights["layers"])):
            y, keys = _attention(m, p["mixer"], dims, kind,
                                 _rms(x, p["norm1"], dims["eps"]))
            x = x + y
            y, idx, slack = _experts(
                m, p["moe"], dims, _rms(x, p["norm2"], dims["eps"]),
                None if routing is None else routing[i])
            x = x + y
            out["keys"].append(keys)
            out["routing"].append(idx)
            out["slack"].append(slack)
        if head_rows is not None:
            x = lax.dynamic_slice_in_dim(x, head_from, head_rows)
        out["logits"] = m.mm(_rms(x, weights["final_norm"], dims["eps"]),
                             weights["head"])
    out["routing"], out["slack"] = (jnp.stack(out[k])
                                    for k in ("routing", "slack"))
    return out


def logits(weights, dims, tokens, low=None):
    """tokens (T,) int32 -> logits (T, V) float32."""
    return forward(weights, dims, tokens, low=low)["logits"]
