"""Construction of the systems under test from a configuration file.

A copy of `chip_smoke.py`'s construction code (the program may change
later; the yardstick may not), minus its eager forward and its
kernel-vs-reference sweeps: the models' parameters all have known shapes,
so the weights are made on the device by ONE jitted call from the seed, in
the type they are trained or served in, and bound with `set_data`.

Each builder returns the model; `*_reference_weights` walks the model's
blocks and hands the plain reference (benchmarks/reference/) the very same
arrays, cast to float32, in a plain dict.
"""
from __future__ import annotations

import numpy as np


def seed_key(seed):
    """A raw uint32[2] jax key from any non-negative whole seed."""
    import jax.numpy as jnp
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def small_seed(seed):
    """The seed folded under 2**31 for APIs that take a C int."""
    return int(seed) % (2 ** 31 - 1)


def set_weights_from_seed(model, seed, dtype, std=0.02):
    """Give every parameter of `model` its value: N(0, std) for matrices,
    biases and betas, 1 + N(0, std) for gammas. One jitted call."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray

    params = list(model.collect_params().values())
    specs = tuple((tuple(p.shape), p.name.endswith("gamma")) for p in params)

    def make(key):
        out = []
        for i, (shape, is_gamma) in enumerate(specs):
            v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out.append((1.0 + v if is_gamma else v).astype(dtype))
        return out

    values = jax.jit(make)(seed_key(seed))
    for p, v in zip(params, values):
        p.set_data(NDArray(v))
    return model


# ------------------------------------------------------------------- BERT
def build_bert(cfg, seed, max_length):
    import mxnet_tpu as mx
    from mxnet_tpu.models.bert import BERTForPretraining, BERTModel
    mx.random.seed(small_seed(seed))
    model = BERTForPretraining(BERTModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], max_length=max_length,
        dropout=cfg["hidden_dropout_prob"]))
    model.cast(cfg["param_dtype"])
    return set_weights_from_seed(model, seed, cfg["param_dtype"])


def _f32(x):
    import jax.numpy as jnp
    return x.data()._data.astype(jnp.float32)


def _dense(d):
    return {"w": _f32(d.weight), "b": _f32(d.bias)}


def _ln(ln):
    return {"g": _f32(ln.gamma), "b": _f32(ln.beta)}


def bert_reference_weights(model):
    bert = model.bert
    enc = bert.encoder
    return {
        "word": _f32(bert.word_embed.weight),
        "type": _f32(bert.token_type_embed.weight),
        "pos": _f32(enc.position_weight),
        "emb_ln": _ln(enc.ln),
        "layers": [{"qkv": _dense(l.attention.qkv),
                    "proj": _dense(l.attention.proj), "ln1": _ln(l.ln1),
                    "ffn1": _dense(l.ffn.ffn1), "ffn2": _dense(l.ffn.ffn2),
                    "ln2": _ln(l.ln2)} for l in enc.layers],
        "pooler": _dense(bert.pooler),
        "mlm_dense": _dense(bert.mlm_dense),
        "mlm_ln": _ln(bert.mlm_ln),
        "mlm_bias": _f32(bert.mlm_bias),
        "nsp": _dense(model.nsp),
    }


def bert_batches(cfg, seed, n, batch, seq, masked, valid_length):
    """`n` seeded host batches (numpy int32) of the pretraining inputs:
    tokens, segments, valid lengths, masked positions, MLM labels, NSP
    labels. Every seed draws the same sizes; only the values differ."""
    rng = np.random.default_rng(int(seed))
    lo, hi = valid_length
    vocab = cfg["vocab_size"]
    out = []
    for _ in range(n):
        vl = rng.integers(lo, hi + 1, (batch,)).astype(np.int32)
        seg = (np.arange(seq)[None, :] >= (vl // 2)[:, None])
        out.append((
            rng.integers(0, vocab, (batch, seq)).astype(np.int32),
            seg.astype(np.int32), vl,
            rng.integers(0, lo, (batch, masked)).astype(np.int32),
            rng.integers(0, vocab, (batch, masked)).astype(np.int32),
            rng.integers(0, 2, (batch,)).astype(np.int32)))
    return out


def bert_loss_fn(model, cfg):
    """The pretraining loss as a job writes it, over the program's own
    NDArray operations (what `Trainer.capture` traces)."""
    import mxnet_tpu as mx
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    vocab = cfg["vocab_size"]

    def loss_fn(tok, seg, vl, pos, mlm_y, nsp_y):
        mlm, nsp = model(tok, seg, vl, pos)
        mlm = mlm.astype("float32").reshape((-1, vocab))
        return (ce(mlm, mlm_y.reshape((-1,))).mean()
                + ce(nsp.astype("float32"), nsp_y).mean())

    return loss_fn


def bert_loss_of(outputs, mlm_y, nsp_y, cfg):
    """The same loss from the raw (MLM, NSP) outputs of the model's pure
    forward, in float32."""
    import jax
    import jax.numpy as jnp

    def xent(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    mlm, nsp = outputs
    return xent(mlm, mlm_y) + xent(nsp, nsp_y)


# -------------------------------------------------------------------- NMT
def build_nmt(cfg, seed, max_length):
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT
    mx.random.seed(small_seed(seed))
    if cfg["encoder_layers"] != cfg["decoder_layers"]:
        raise ValueError("TransformerNMT builds equal encoder and decoder "
                         "depth")
    model = TransformerNMT(
        cfg["vocab_size"], units=cfg["d_model"], hidden=cfg["ffn_dim"],
        num_layers=cfg["encoder_layers"], num_heads=cfg["attention_heads"],
        max_length=max_length, dropout=0.0)
    model.cast(cfg["param_dtype"])
    return set_weights_from_seed(model, seed, cfg["param_dtype"])


def nmt_reference_weights(model):
    import jax.numpy as jnp
    return {
        "embed": _f32(model.embed.weight),
        "pos": jnp.asarray(model.decoder._pos, jnp.float32),
        "encoder": [{"qkv": _dense(l.attn.qkv), "proj": _dense(l.attn.proj),
                     "ln1": _ln(l.ln1), "ffn1": _dense(l.ffn.ffn1),
                     "ffn2": _dense(l.ffn.ffn2), "ln2": _ln(l.ln2)}
                    for l in model.encoder.layers],
        "decoder": [{"qkv": _dense(l.self_attn.qkv),
                     "sproj": _dense(l.self_attn.proj), "ln1": _ln(l.ln1),
                     "q": _dense(l.cross_attn.q),
                     "kv": _dense(l.cross_attn.kv),
                     "cproj": _dense(l.cross_attn.proj), "ln2": _ln(l.ln2),
                     "ffn1": _dense(l.ffn.ffn1), "ffn2": _dense(l.ffn.ffn2),
                     "ln3": _ln(l.ln3)} for l in model.decoder.layers],
    }
