"""BERT MLM+NSP pretraining on synthetic data — flash attention + bf16.

Usage: python examples/bert_pretrain.py [--smoke]
The attention path rides the Pallas flash kernels on TPU (padding masks
as per-row kv lengths).
"""
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
import _smoke  # noqa: F401,E402 — forces CPU under --smoke
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.batch_size, args.seq_len, args.steps = 2, 64, 2

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.models.bert import BERTForPretraining, BERTModel

    mx.random.seed(0)
    vocab = 1000
    model = BERTForPretraining(BERTModel(
        vocab_size=vocab, units=128, hidden_size=256, num_layers=2,
        num_heads=4, max_length=args.seq_len, dropout=0.1))
    model.initialize()

    rng = np.random.RandomState(0)
    B, S, P = args.batch_size, args.seq_len, max(args.seq_len // 8, 1)
    tok = nd.array(rng.randint(0, vocab, (B, S)).astype(np.int32))
    seg = nd.array(np.zeros((B, S), np.int32))
    vl = nd.array(rng.randint(S // 2, S + 1, (B,)).astype(np.int32))
    pos = nd.array(rng.randint(0, S, (B, P)).astype(np.int32))
    mlm_y = nd.array(rng.randint(0, vocab, (B, P)).astype(np.int32))
    nsp_y = nd.array(rng.randint(0, 2, (B,)).astype(np.int32))

    trainer = mx.gluon.Trainer(model.collect_params(), "adam",
                               {"learning_rate": 1e-4})
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    for i in range(args.steps):
        with autograd.record():
            mlm, nsp = model(tok, seg, vl, pos)
            loss = ce(mlm.reshape((-1, vocab)),
                      mlm_y.reshape((-1,))).mean() + ce(nsp, nsp_y).mean()
        loss.backward()
        trainer.step(B)
        print(f"step {i}: loss={float(loss.asnumpy()):.4f}")


if __name__ == "__main__":
    main()
