"""Plain reference of the Transformer (Vaswani et al. 2017, base)
teacher-forced forward: source and target-so-far in, logits for every
target position out.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
paging. Post-LN encoder and decoder, sinusoidal positions added to
embeddings scaled by sqrt(d_model), ReLU feed-forward, one embedding
matrix shared by source, target and the output projection (the paper's
section 3.4; `transformer_base()` ships it so). No dropout (inference).

`weights` is the dict `lib.models.nmt_reference_weights` builds from the
model under test: the same arrays in float32, dense weights (out, in).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense(p, x):
    return x @ p["w"].T + p["b"]


def _ln(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _attend(q, k, v, heads, keep):
    """q (B, Tq, D), k/v (B, Tk, D), keep (B, Tq, Tk) bool."""
    b, tq, d = q.shape
    dh = d // heads

    def split(t):
        return t.reshape(b, t.shape[1], heads, dh).transpose(0, 2, 1, 3)

    sc = split(q) @ split(k).transpose(0, 1, 3, 2) / jnp.sqrt(
        jnp.float32(dh))
    sc = jnp.where(keep[:, None], sc, -jnp.inf)
    out = jax.nn.softmax(sc, axis=-1) @ split(v)
    return out.transpose(0, 2, 1, 3).reshape(b, tq, d)


def _embed(weights, tokens):
    d = weights["embed"].shape[1]
    t = tokens.shape[1]
    return weights["embed"][tokens] * jnp.sqrt(jnp.float32(d)) \
        + weights["pos"][:t][None]


def logits(weights, heads, eps, src, src_len, tgt_in):
    """src (B, S) int32 padded, src_len (B,), tgt_in (B, T) int32 (BOS
    first) -> logits (B, T, V): row t predicts the token after tgt_in[t]."""
    with jax.default_matmul_precision("highest"):
        s, t = src.shape[1], tgt_in.shape[1]
        src_keep = jnp.arange(s)[None, :] < src_len[:, None]     # (B, S)
        x = _embed(weights, src)
        for L in weights["encoder"]:
            q, k, v = jnp.split(_dense(L["qkv"], x), 3, axis=-1)
            a = _attend(q, k, v, heads,
                        jnp.broadcast_to(src_keep[:, None, :],
                                         (src.shape[0], s, s)))
            x = _ln(L["ln1"], x + _dense(L["proj"], a), eps)
            f = _dense(L["ffn2"], jax.nn.relu(_dense(L["ffn1"], x)))
            x = _ln(L["ln2"], x + f, eps)
        memory = x
        causal = jnp.tril(jnp.ones((t, t), bool))[None]
        cross_keep = jnp.broadcast_to(src_keep[:, None, :],
                                      (src.shape[0], t, s))
        y = _embed(weights, tgt_in)
        for L in weights["decoder"]:
            q, k, v = jnp.split(_dense(L["qkv"], y), 3, axis=-1)
            a = _attend(q, k, v, heads,
                        jnp.broadcast_to(causal, (src.shape[0], t, t)))
            y = _ln(L["ln1"], y + _dense(L["sproj"], a), eps)
            k, v = jnp.split(_dense(L["kv"], memory), 2, axis=-1)
            c = _attend(_dense(L["q"], y), k, v, heads, cross_keep)
            y = _ln(L["ln2"], y + _dense(L["cproj"], c), eps)
            f = _dense(L["ffn2"], jax.nn.relu(_dense(L["ffn1"], y)))
            y = _ln(L["ln3"], y + f, eps)
        return y @ weights["embed"].T
