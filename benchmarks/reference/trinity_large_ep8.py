"""Plain reference of one chip's share of Trinity-Large-Preview
(configs/trinity_large_ep8.json): token ids in, logits out, the whole
forward every time.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no ring,
no pages, no batching. The embedding's rows are times sqrt(hidden) (muP),
then every layer is the sandwich pair

    h  = x + N_b(Attn_l(N_a(x)))
    x' = h + N_d(FFN_l(N_c(h)))

with RMSNorm everywhere.

  Attn  q = W_q u as H heads of dh, the gate g = W_g u (H * dh), k = W_k u
        and v = W_v u as Hkv heads (query head h reads KV head
        h // (H / Hkv)); each head of q and of k RMS-normed over its dh
        values (one gain vector each); on a sliding-window layer ("swa")
        q and k then rotated over the WHOLE head in the half-split
        convention (value i pairs with value i + dh / 2, the pair of
        position t turning by t theta^(-2i / dh)); a full layer ("gqa")
        has no positional term. Scores q . k / sqrt(dh), causal; a
        window layer's query at t attends t - window < j <= t. One masked
        softmax over the whole sequence, a query head and a block of
        query rows at a time. out = W_o (attn * sigmoid(g)).
  FFN   a dense SwiGLU (a leading dense layer), or the experts: s =
        sigmoid(W_r u) over ALL experts in float32, the k largest of s + b
        chosen (b the selection-only bias), weights s_chosen / sum
        (s_chosen) times route_scale; a loop over the experts HELD, one at
        a time, W_down (SiLU(W_gate u) * W_up u) each (the other experts'
        terms left out, as in the program), plus one shared SwiGLU expert.

Untied embedding and head over the held slice of the vocabulary. What the
source leaves open is under `assumed` in the configuration.

`weights` is the dict `lib.lm_afmoe.reference_weights` builds from the
model under test: the very same arrays, in the model's type and packing
(no copy: a second set would not fit beside the server that is being
checked), each cast to float32 where it is used. The packing: `qkv` rows
W_q | W_g | W_k | W_v; an expert bank `gate_up` (experts, d, 2 * width),
columns W_gate | W_up, and `down` (experts, width, d); every other matrix
(out, in), a SwiGLU's rows gate | up. The row-wise parts of a layer run
in blocks of positions (`lax.map`), so that the float32 forward of 8,207
positions fits beside the server.

`forward` also hands out what a server keeps between turns: each layer's
keys after the norm and the rotation, (T, Hkv * dh), which a ring or a
page holds a position a row; and the expert ids each position used with
their `slack` (-1 and 0 in a dense layer). `routing` (layers, T, k)
FORCES those ids (an entry under 0 keeps the reference's own choice), as
`reference/solar_open2_ep8.py` says why. `head_from`, `head_rows`: the
logits of `head_rows` positions from `head_from` only.

Controls, to place a check's limits (PERF.md section 6). `low="all"`
computes below the configuration's precision: every matmul's inputs
rounded to float8_e4m3fn, the keys and values an attention reads (what a
cache holds) rounded to it too. `leave_out` drops or swaps one term:
"qk_norm" (no per-head norm of q and k), "nope" (full layers rotate
too), "gate" (no output gate), "post_norms" (no N_b and N_d), "bias"
(the top k of s, no selection bias), "route_scale" (1 in its place),
"sigmoid" (a softmax over the experts for the sigmoid), "embed_mult" (no
embedding multiplier), "window" (window layers attend every position).
Each has to FAIL the cell's check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LOW = (None, "all")
LEAVE_OUT = (None, "qk_norm", "nope", "gate", "post_norms", "bias",
             "route_scale", "sigmoid", "embed_mult", "window")
ROWS = 1024              # positions a block of the row-wise parts


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


def _by_rows(fn, *xs):
    """fn over blocks of `ROWS` positions of the (T, ...) arrays `xs`,
    the last block padded; its outputs (a tuple of (block, ...) arrays)
    put back together and cut to T."""
    t = xs[0].shape[0]
    rows = min(ROWS, t)
    nb = -(-t // rows)
    blocks = [jnp.pad(x, ((0, nb * rows - t),) + ((0, 0),) * (x.ndim - 1))
              .reshape(nb, rows, *x.shape[1:]) for x in xs]
    out = lax.map(lambda b: fn(*b), tuple(blocks))
    return tuple(o.reshape(nb * rows, *o.shape[2:])[:t] for o in out)


def _rotate(x, theta):
    """x (T, heads, dh): the pair (i, i + dh / 2) of position t turned by
    t theta^(-2i / dh)."""
    half = x.shape[-1] // 2
    f = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, p, dims, kind, x):
    """x (T, d). Returns (out, the keys as a cache holds them
    (T, Hkv * dh))."""
    t = x.shape[0]
    h, hk, dh, eps = (dims["heads"], dims["kv_heads"], dims["head_dim"],
                      dims["eps"])
    proj, = _by_rows(lambda u: (m.mm(u, p["qgkv"]),), x)
    q, g, k, v = jnp.split(proj, [h * dh, 2 * h * dh, (2 * h + hk) * dh], -1)
    q, k, v = q.reshape(t, h, dh), k.reshape(t, hk, dh), v.reshape(t, hk, dh)
    if not m.without("qk_norm"):
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if kind == "swa" or m.without("nope"):
        q, k = _rotate(q, dims["rope_theta"]), _rotate(k, dims["rope_theta"])
    keys = k.reshape(t, hk * dh)
    # what a cache holds, at the control's precision
    k, v = m.r(k), m.r(v)
    window = dims["window"] if kind == "swa" and not m.without("window") \
        else None

    def one_head(args):
        q_h, kv = args                                  # (T, dh), KV head
        k_h, v_h = k[:, kv], v[:, kv]

        def block(qb, at):                              # (rows, dh), (rows,)
            back = at[:, None] - jnp.arange(t)[None, :]
            seen = back >= 0
            if window is not None:
                seen = seen & (back < window)
            s = qb @ k_h.T / jnp.sqrt(F32(dh))
            return (jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1) @ v_h,)

        return _by_rows(block, q_h, jnp.arange(t))[0]

    o = lax.map(one_head, (q.transpose(1, 0, 2),
                           jnp.arange(h) // (h // hk)))
    a = o.transpose(1, 0, 2).reshape(t, h * dh)
    if not m.without("gate"):
        a = a * jax.nn.sigmoid(g)
    y, = _by_rows(lambda u: (m.mm(u, p["o"]),), a)
    return y, keys


def _swiglu(m, x, gate_up, down):
    """down(SiLU(gate x) * up x); gate_up (2 width, d), gate rows first."""
    g, u = jnp.split(m.mm(x, gate_up), 2, -1)
    return m.mm(jax.nn.silu(g) * u, down)


def _experts(m, p, dims, x, forced):
    """A block of positions x (rows, d): y = the sum over the used experts
    HELD of w_e SwiGLU_e(x), plus the shared expert. Returns (y, ids used
    (rows, k), slack (rows,)): how far the lowest ranked score used lies
    under the k-th largest."""
    logits = m.mm(x, p["router"])                          # (rows, E)
    s = jax.nn.softmax(logits, -1) if m.without("sigmoid") \
        else jax.nn.sigmoid(logits)
    ranked = s if m.without("bias") else s + p["router_bias"].astype(F32)
    top, idx = lax.top_k(ranked, dims["top_k"])
    idx = jnp.where(forced >= 0, forced, idx)
    slack = top[:, -1] - jnp.take_along_axis(ranked, idx, -1).min(-1)
    chosen = jnp.take_along_axis(s, idx, -1)
    scale = 1.0 if m.without("route_scale") else dims["scaling"]
    wts = chosen / chosen.sum(-1, keepdims=True) * scale
    lo = dims["held_lo"]

    def add_expert(e, y):                                  # experts held
        w_e = jnp.sum(jnp.where(idx == lo + e, wts, 0.0), -1)
        g, u = jnp.split(m.xw(x, p["gate_up"][e]), 2, -1)
        return y + w_e[:, None] * m.xw(jax.nn.silu(g) * u, p["down"][e])

    y = _swiglu(m, x, p["shared_gate_up"], p["shared_down"])
    y = lax.fori_loop(0, p["gate_up"].shape[0], add_expert, y)
    return y, idx.astype(jnp.int32), slack


def forward(weights, dims, tokens, n=None, routing=None, low=None,
            leave_out=None, head_from=None, head_rows=None):
    """tokens (T,) int32 -> {"logits" (T, V) float32, or (head_rows, V)
    from position head_from; "keys": a list with a (T, Hkv * dh) array a
    layer, the keys as cached; "routing" (layers, T, k) int32 and "slack"
    (layers, T)}. `dims`: a hashable tuple of (name, value) pairs (static
    under jit), see `lib.lm.dims`. `n` is the siblings' argument and
    reads nothing here: an attention cache holds a row a position."""
    dims = dict(dims)
    m = _How(low, leave_out)
    eps, t, top_k = dims["eps"], tokens.shape[0], dims["top_k"]
    ffn = dims["ffn"] or ("moe",) * len(dims["pattern"])
    mult = 1.0 if m.without("embed_mult") else dims["embed_mult"]
    out = {"keys": [], "routing": [], "slack": []}

    def post(y, gamma):
        return y if m.without("post_norms") else _rms(y, gamma, eps)

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32) * mult
        for i, (kind, f, p) in enumerate(zip(dims["pattern"], ffn,
                                             weights["layers"])):
            y, keys = _attention(m, p["mixer"], dims, kind,
                                 _rms(x, p["norm1"], eps))
            x = x + post(y, p["norm1_post"])
            h = _rms(x, p["norm2"], eps)
            if f == "moe":
                forced = jnp.full((t, top_k), -1, jnp.int32) \
                    if routing is None \
                    else routing[i]
                y, idx, slack = _by_rows(
                    lambda u, fr, p=p: _experts(m, p["moe"], dims, u, fr),
                    h, forced)
            else:
                y, = _by_rows(lambda u, p=p: (_swiglu(
                    m, u, p["ffn"]["gate_up"], p["ffn"]["down"]),), h)
                idx = jnp.full((t, top_k), -1, jnp.int32)
                slack = jnp.zeros((t,), F32)
            x = x + post(y, p["norm2_post"])
            out["keys"].append(keys)
            out["routing"].append(idx)
            out["slack"].append(slack)
        if head_rows is not None:
            x = lax.dynamic_slice_in_dim(x, head_from, head_rows)
        out["logits"] = m.mm(_rms(x, weights["final_norm"], eps),
                             weights["head"])
    out["routing"], out["slack"] = (jnp.stack(out[k])
                                    for k in ("routing", "slack"))
    return out


def logits(weights, dims, tokens, low=None, leave_out=None):
    """tokens (T,) int32 -> logits (T, V) float32."""
    return forward(weights, dims, tokens, low=low,
                   leave_out=leave_out)["logits"]
