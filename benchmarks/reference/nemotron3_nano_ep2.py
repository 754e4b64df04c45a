"""Plain reference of one chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B
(configs/nemotron3_nano_ep2.json): token ids in, logits for every
position out, the whole forward every time.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no pages,
no chunks. Every layer is ONE sub-layer, `x <- x + f(RMSNorm(x))`, with f
by the published pattern's letter:

  M  a Mamba-2 state-space layer: `[z | c | dt] = W_in u`; `c' =
     SiLU(conv4(c) + b)`, depthwise and causal; `c' = [x | B | C]`, x as
     heads of P, B and C as G groups of N, head h reading group h // (H /
     G); `delta = softplus(dt + dt_bias)`, `a = exp(delta A)`, `A =
     -exp(A_log)`; the state `S <- a S + delta x B^T`, `y = S C + D x`,
     as a plain `lax.scan` over positions; `y <- RMSNorm_group(y *
     SiLU(z)) * gamma`, the norm over each of the G groups of channels
     after the gate; `f = W_out y`.
  E  an expert layer: sigmoid scores over all 128, the 6 largest of score
     + selection bias chosen, chosen scores normalised, times 2.5; a
     loop over the experts HELD, one at a time (`lax.fori_loop`: one body
     to compile, not sixty-four), `W_down relu(W_up u)^2` each (the
     other experts' terms are left out, as in the program, and that
     partial sum goes on); one shared expert of its own width.
  *  softmax attention, 32 query heads over 2 KV heads, causal, no
     positional term, no gate.

Untied embedding and head over the held slice of the vocabulary. What the
source leaves open is under `assumed` in the configuration.

`weights` is the dict `lib.lm_ssm.reference_weights` builds from the
model under test: the very same arrays, in the model's type and packing
(no copy: a second set would not fit beside the server that is being
checked), each cast to float32 where it is used, an expert at a time; a
layer is {"norm": its RMSNorm's gain, "f": its sub-layer's arrays}. The
packing: `in` rows W_z | W_c | W_dt, the convolution's columns x | B | C;
`qkv` rows W_q | W_k | W_v; an expert bank `up` (experts, width, d) and
`down` (experts, width, d); every other matrix (out, in).

`forward` also hands out what a server keeps between turns: each Mamba-2
layer's state after the first `n` positions (later positions leave it
alone) and the convolution's inputs at the last K - 1 of them; and the
expert ids each position used with their `slack`, -1 and 0 in a layer
without experts. `routing` (layers, T, k) FORCES those ids (an entry
under 0 keeps the reference's own choice), as
`reference/solar_open2_ep8.py` says why.

Controls, to place a check's limits (PERF.md section 4). `low` computes
below the configuration's precision: "state" keeps the recurrent state in
bfloat16; "all": that, and every matmul's inputs rounded to
float8_e4m3fn. `leave_out` drops or swaps one term: "d_skip" (D x),
"gate" (SiLU(z)), "conv_bias", "dt_bias", "relu" (relu for relu^2),
"shared" (the shared expert), "scaling" (1 for 2.5), "one_norm" (one
RMSNorm over all the channels for one a group). Each has to FAIL the
cell's check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LOW = (None, "state", "all")
LEAVE_OUT = (None, "d_skip", "gate", "conv_bias", "dt_bias", "relu",
             "shared", "scaling", "one_norm")


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


class _How:
    """x @ w^T and the state's type, at full or at lowered precision; the
    term a control leaves out."""

    def __init__(self, low, leave_out):
        if low not in LOW or leave_out not in LEAVE_OUT:
            raise ValueError(f"low = {low!r}, leave_out = {leave_out!r}")
        self.low = low == "all"
        self.state_dtype = jnp.bfloat16 if low else F32
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

    def act(self, x):
        x = jax.nn.relu(x)
        return x if self.without("relu") else x * x


def _attention(m, p, dims, x):
    """x (T, d). o = softmax(q k^T / sqrt(dh) + causal) v per query head,
    head h reading KV head h // (H / Hkv); out = W_o o."""
    t = x.shape[0]
    h, hk, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    w_q, w_k, w_v = jnp.split(p["qkv"], [h * dh, (h + hk) * dh])
    q = m.mm(x, w_q).reshape(t, h, dh)
    k = m.mm(x, w_k).reshape(t, hk, dh)
    v = m.mm(x, w_v).reshape(t, hk, dh)
    k, v = (jnp.repeat(a, h // hk, 1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    return m.mm(jnp.einsum("hqk,khd->qhd", a, v).reshape(t, h * dh), p["o"])


def _conv(x, w, bias):
    """Causal depthwise convolution; w (K, C), w[K-1] on the current
    position, zeros before the sequence; bias (C,) or None."""
    kw = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((kw - 1, x.shape[1]), F32), x])
    out = sum(pad[j:j + x.shape[0]] * w[j].astype(F32) for j in range(kw))
    return out if bias is None else out + bias.astype(F32)


def _mamba(m, p, dims, u, n):
    """The state-space layer one position at a time. Returns (f, S after
    the first n positions (H, P, N), the convolution's inputs at
    positions n - K + 1 .. n - 1 (zeros before the sequence))."""
    t = u.shape[0]
    h, hd = dims["ssm_heads"], dims["ssm_head_dim"]
    g, ns, eps = dims["ssm_groups"], dims["ssm_state"], dims["eps"]
    inner = h * hd
    kw = p["conv"].shape[0]
    z, pre, dt = jnp.split(m.mm(u, p["in"]), [inner, 2 * inner + 2 * g * ns],
                           -1)
    conv = jax.nn.silu(_conv(pre, p["conv"], None if m.without("conv_bias")
                             else p["conv_bias"]))
    x, b, c = jnp.split(conv, [inner, inner + g * ns], -1)
    x = x.reshape(t, h, hd)
    b, c = (jnp.repeat(a.reshape(t, g, ns), h // g, 1) for a in (b, c))
    if not m.without("dt_bias"):
        dt = dt + p["dt_bias"].astype(F32)
    delta = jax.nn.softplus(dt)                            # (T, H)
    decay = jnp.exp(-jnp.exp(p["a_log"].astype(F32)) * delta)

    def step(s_old, xs):
        i, x_t, b_t, c_t, delta_t, a_t = xs
        s = a_t[:, None, None] * s_old.astype(F32) \
            + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        kept = jnp.where(i < n, s.astype(m.state_dtype), s_old)
        return kept, jnp.einsum("hpn,hn->hp", s, c_t)

    state, y = lax.scan(step, jnp.zeros((h, hd, ns), m.state_dtype),
                        (jnp.arange(t), x, b, c, delta, decay))
    if not m.without("d_skip"):
        y = y + p["d_skip"].astype(F32)[:, None] * x
    y = y.reshape(t, inner)
    if not m.without("gate"):
        y = y * jax.nn.silu(z)
    y = y.reshape(t, 1 if m.without("one_norm") else g, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(t, inner) * p["norm"].astype(F32)
    before = jnp.concatenate([jnp.zeros((kw - 1, pre.shape[1]), F32), pre])
    return m.mm(y, p["o"]), state, lax.dynamic_slice_in_dim(before, n,
                                                            kw - 1)


def _experts(m, p, dims, x, forced):
    """y = sum over the used experts HELD of w_e W_down,e relu(W_up,e
    x)^2, plus the shared expert. Returns (y, ids used (T, k), slack
    (T,))."""
    s = jax.nn.sigmoid(m.mm(x, p["router"]))               # (T, 128)
    ranked = s + p["router_bias"].astype(F32)
    top, idx = lax.top_k(ranked, dims["top_k"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    slack = top[:, -1] - jnp.take_along_axis(ranked, idx, -1).min(-1)
    chosen = jnp.take_along_axis(s, idx, -1)
    wts = chosen / chosen.sum(-1, keepdims=True) \
        * (1.0 if m.without("scaling") else dims["scaling"])
    y = jnp.zeros_like(x)
    if not m.without("shared"):
        y = m.mm(m.act(m.mm(x, p["shared_up"])), p["shared_down"])
    lo = dims["held_lo"]

    def add_expert(e, y):                                  # experts held
        w_e = jnp.sum(jnp.where(idx == lo + e, wts, 0.0), -1)
        return y + w_e[:, None] * m.xw(m.act(m.mm(x, p["up"][e])),
                                       p["down"][e])

    y = lax.fori_loop(0, p["up"].shape[0], add_expert, y)
    return y, idx.astype(jnp.int32), slack


def forward(weights, dims, tokens, n=None, routing=None, low=None,
            leave_out=None):
    """tokens (T,) int32 -> {"logits" (T, V) float32; "state" and "tails":
    a list with an entry a Mamba-2 layer, after the first `n` positions
    (all of them by default); "routing" (layers, T, k) int32 and "slack"
    (layers, T), -1 and 0 in a layer without experts}. `dims`: a hashable
    tuple of (name, value) pairs (static under jit), see `lib.lm.dims`."""
    dims = dict(dims)
    m = _How(low, leave_out)
    t = tokens.shape[0]
    n = t if n is None else n
    out = {"state": [], "tails": [], "routing": [], "slack": []}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for i, (kind, p) in enumerate(zip(dims["pattern"],
                                          weights["layers"])):
            u = _rms(x, p["norm"], dims["eps"])
            idx = jnp.full((t, dims["top_k"]), -1, jnp.int32)
            slack = jnp.zeros((t,), F32)
            if kind == "mamba":
                y, state, tails = _mamba(m, p["f"], dims, u, n)
                out["state"].append(state)
                out["tails"].append(tails)
            elif kind == "gqa":
                y = _attention(m, p["f"], dims, u)
            else:
                y, idx, slack = _experts(
                    m, p["f"], dims, u,
                    None if routing is None else routing[i])
            out["routing"].append(idx)
            out["slack"].append(slack)
            x = x + y
        out["logits"] = m.mm(_rms(x, weights["final_norm"], dims["eps"]),
                             weights["head"])
    out["routing"], out["slack"] = (jnp.stack(out[k])
                                    for k in ("routing", "slack"))
    return out


def logits(weights, dims, tokens, low=None):
    """tokens (T,) int32 -> logits (T, V) float32."""
    return forward(weights, dims, tokens, low=low)["logits"]
