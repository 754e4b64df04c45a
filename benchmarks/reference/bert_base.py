"""Plain reference of BERT pretraining's forward and loss.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no fused QKV
tricks beyond the split, no cache. Follows Devlin et al. 2018 (post-LN
encoder, learned positions, tied MLM decoder with its own bias, NSP on the
tanh pooler of position 0). Departures, all the program's and stated in
configs/bert_base.json `assumed`: GELU in its tanh form, layernorm epsilon
1e-5, no dropout (the comparison is made in predict mode).

`weights` is the dict `lib.models.bert_reference_weights` builds from the
model under test: the same arrays, cast to float32. Dense weights are
(out, in), as the program stores them.
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


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def forward(weights, heads, eps, tokens, segments, valid_length, positions):
    """(MLM logits (B, P, V), NSP logits (B, 2))."""
    b, s = tokens.shape
    x = weights["word"][tokens] + weights["type"][segments] \
        + weights["pos"][:s][None]
    x = _ln(weights["emb_ln"], x, eps)
    d = x.shape[-1]
    dh = d // heads
    keep = jnp.arange(s)[None, :] < valid_length[:, None]       # (B, S)
    for L in weights["layers"]:
        q, k, v = jnp.split(_dense(L["qkv"], x), 3, axis=-1)
        q, k, v = (t.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        sc = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(dh))
        sc = jnp.where(keep[:, None, None, :], sc, -jnp.inf)
        a = jax.nn.softmax(sc, axis=-1) @ v
        a = a.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = _ln(L["ln1"], x + _dense(L["proj"], a), eps)
        f = _dense(L["ffn2"], _gelu(_dense(L["ffn1"], x)))
        x = _ln(L["ln2"], x + f, eps)
    pooled = jnp.tanh(_dense(weights["pooler"], x[:, 0]))
    at = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    h = _ln(weights["mlm_ln"], _gelu(_dense(weights["mlm_dense"], at)), eps)
    mlm = h @ weights["word"].T + weights["mlm_bias"]
    return mlm, _dense(weights["nsp"], pooled)


def loss(weights, heads, eps, tokens, segments, valid_length, positions,
         mlm_labels, nsp_labels):
    """Mean MLM cross-entropy over all masked positions + mean NSP
    cross-entropy, as the pretraining job sums them."""
    with jax.default_matmul_precision("highest"):
        mlm, nsp = forward(weights, heads, eps, tokens, segments,
                           valid_length, positions)
        return _xent(mlm, mlm_labels).mean() + _xent(nsp, nsp_labels).mean()
