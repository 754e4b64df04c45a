"""Transformer NMT training throughput, tokens/sec/chip (BASELINE.json
config 4: "Transformer NMT WMT En-De (Sockeye / gluon seq2seq)").

One jitted bf16 train step: transformer-base (6+6 layers, 512 units,
2048 hidden, 8 heads, vocab 36548, tied src/tgt/softmax embedding —
Sockeye's weight-tying=src_trg_softmax), teacher forcing, seq 64 src +
64 tgt, SGD-momentum (same optimizer as the other benches so the
numbers are comparable), donated buffers. tok/s counts BOTH streams
(src+tgt), the Sockeye convention.

Baseline denominator, derived like bench_bert.py's (BASELINE.json
"published" is empty): transformer-base costs ~0.42 GFLOP/token
(6 * ~70M matmul params incl. the tied projection on the target side;
S=64 attention adds <5%). A tuned A100 fp16 transformer runs ~35% MFU
(0.35 * 312 TFLOP/s) -> 0.35*312e12/0.42e9 ~= 260k tokens/sec/chip.

Off by default in bench.py's driver line; enable with BENCH_NMT=1.
Standalone: `python bench_nmt.py` prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_TOK_S = 260_000.0
SEQ = 64


def build_step(batch, seq, vocab=36548):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.models.transformer import transformer_base

    model = transformer_base(vocab_size=vocab, max_length=seq, dropout=0.0)
    model.initialize()
    model.cast("bfloat16")

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    src = mx.nd.NDArray(jax.random.randint(k1, (batch, seq), 0, vocab))
    tgt = mx.nd.NDArray(jax.random.randint(k2, (batch, seq), 0, vocab))
    vl = mx.nd.NDArray(jnp.full((batch,), seq, jnp.int32))
    model(src, tgt, vl)  # materialise params
    fwd, params = extract_pure_fn(model, src, tgt, vl, training=True)
    aux_idx = list(fwd.aux_indices)
    labels = jax.random.randint(k3, (batch, seq), 0, vocab)

    def loss_fn(p, s, t, v, y):
        logits, aux = fwd(p, s, t, v)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, y[..., None], -1)), aux

    from bench_util import make_sgd_step
    step = make_sgd_step(loss_fn, aux_idx, lr=1e-3, mu=0.9)
    mom = [jnp.zeros_like(p) for p in params]
    data = (src._data, tgt._data, vl._data, labels)
    return step, params, mom, data


def _measure_one(batch, steps, seq):
    step, params, mom, data = build_step(batch, seq)
    from bench_util import timed_measure
    return timed_measure(step, params, mom, data, steps,
                         batch * seq * 2,  # src+tgt tokens
                         tag=f"bench_nmt b{batch}")


def measure(batch=None, steps=None, on_result=None):
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if batch is None:
        candidates = [64, 128] if on_tpu else [2]
    else:
        candidates = list(batch) if isinstance(batch, (list, tuple)) \
            else [batch]
    if steps is None:
        steps = 20 if on_tpu else 2
    seq = SEQ if on_tpu else 16
    print(f"[bench_nmt] backend={jax.default_backend()} "
          f"candidates={candidates} seq={seq} steps={steps}",
          file=sys.stderr)

    from bench_util import sweep
    SWEEP_BUDGET_S = 150

    best, _ = sweep(candidates, SWEEP_BUDGET_S,
                    lambda b: _measure_one(b, steps, seq),
                    on_best=None if on_result is None
                    else (lambda v: on_result(_result(v))),
                    tag="bench_nmt")
    return _result(best)


def _result(tok_s):
    return {
        "metric": "transformer_nmt_train_throughput",
        "value": round(tok_s, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 4),
    }


def main():
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(
        os.path.dirname(os.path.abspath(__file__)))
    batch = os.environ.get("BENCH_NMT_BATCH")
    steps = os.environ.get("BENCH_NMT_STEPS")
    res = measure([int(b) for b in batch.split(",")] if batch else None,
                  int(steps) if steps else None)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
