"""BERT-base MLM pretraining throughput, tokens/sec/chip (BASELINE.json's
second headline metric).

One jitted bf16 train step: BERT-base (12x768x12, vocab 30522) MLM at
seq_len 512, Pallas flash attention, 76 masked positions/sequence (15%),
AdamW-free SGD-momentum update (same optimizer as the ResNet bench so the
two headline numbers are comparable), donated buffers.

Baseline denominator: no published per-chip MXNet/GluonNLP A100 number
exists in BASELINE.json ("published": {}), so the reference class is derived
the same way SURVEY.md §6 derives the ResNet one — A100 fp16-class sustained
transformer throughput. BERT-base training costs ~0.72 GFLOP/token at
seq 512 (6*110e6 params-matmul + 12 layers * 12*S*d attention / 3 passes);
NVIDIA's tuned BERT runs at ~35% MFU on A100 (312 TFLOPs peak) ->
0.35*312e12/0.72e9 ~= 150k tokens/s/chip. We use 150000.

Run directly, or via `python bench.py` which merges this metric into its
single JSON line. Prints ONE JSON line when run standalone. Measures on a
TPU backend only and refuses any other; `--smoke` is a CPU control-flow
check at a tiny size whose JSON line says so and carries no rate.
"""
from __future__ import annotations

import json
import os
import sys

BASELINE_TOK_S = 150_000.0
SEQ, MASKED = 512, 76
ROOT = os.path.dirname(os.path.abspath(__file__))


def build_step(batch, seq, masked, unroll=None):
    """Build the jitted BERT MLM train step. Returns (step, params, mom,
    data, unroll) — shared by measure() and tools/profile_bert.py."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx  # noqa: F401  (registers dtypes/ops)
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.models.bert import BERTForPretraining, bert_base

    model = BERTForPretraining(bert_base(max_length=seq, dropout=0.0))
    model.initialize()
    model.cast("bfloat16")

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    tok = mx.nd.NDArray(jax.random.randint(k1, (batch, seq), 0, 30522))
    seg = mx.nd.NDArray(jnp.zeros((batch, seq), jnp.int32))
    vl = mx.nd.NDArray(jnp.full((batch,), seq, jnp.int32))
    pos = mx.nd.NDArray(jax.random.randint(k2, (batch, masked), 0, seq))
    model(tok, seg, vl, pos)  # materialise params
    fwd, params = extract_pure_fn(model, tok, seg, vl, pos, training=True)
    aux_idx = list(fwd.aux_indices)

    mlm_labels = jax.random.randint(k3, (batch, masked), 0, 30522)
    nsp_labels = jax.random.randint(k4, (batch,), 0, 2)

    def loss_fn(p, t, s, v, mp, ml, nl):
        (mlm, nsp), aux = fwd(p, t, s, v, mp)
        mlm = mlm.astype(jnp.float32)
        nsp = nsp.astype(jnp.float32)
        lp = jax.nn.log_softmax(mlm, axis=-1)
        l_mlm = -jnp.mean(jnp.take_along_axis(lp, ml[..., None], -1))
        lp2 = jax.nn.log_softmax(nsp, axis=-1)
        l_nsp = -jnp.mean(jnp.take_along_axis(lp2, nl[:, None], -1))
        return l_mlm + l_nsp, aux

    lr, mu = 1e-3, 0.9
    # same lever as bench.py's BENCH_UNROLL: k steps per dispatch, at k
    # times the compile
    if unroll is None:
        unroll = max(1, int(os.environ.get("BENCH_BERT_UNROLL", "4")))
    from bench_util import make_sgd_step
    step = make_sgd_step(loss_fn, aux_idx, lr, mu, unroll)
    mom = [jnp.zeros_like(p) for p in params]
    data = (tok._data, seg._data, vl._data, pos._data, mlm_labels, nsp_labels)
    return step, params, mom, data, unroll


def _measure_one(batch, steps, seq, masked):
    # unroll comes back from build_step so the tok/s numerator can never
    # disagree with what was actually compiled
    step, params, mom, data, unroll = build_step(batch, seq, masked)
    from bench_util import timed_measure
    return timed_measure(step, params, mom, data, steps,
                         batch * seq * unroll,
                         tag=f"bench_bert b{batch}")


def measure(batch=None, steps=20, on_result=None):
    """`on_result(result_dict)` fires whenever the best-so-far improves —
    bench.py uses it to checkpoint its merged JSON line so a failing
    later candidate can't lose this metric."""
    # batch 16 alone by default; BENCH_BERT_BATCH=a[,b] opens a sweep
    candidates = [16] if batch is None else (
        list(batch) if isinstance(batch, (list, tuple)) else [batch])
    print(f"[bench_bert] candidates={candidates} seq={SEQ} steps={steps}",
          file=sys.stderr)

    from bench_util import sweep
    SWEEP_BUDGET_S = 150

    def run_one(b):
        return _measure_one(b, steps, SEQ, MASKED)

    best, _ = sweep(candidates, SWEEP_BUDGET_S, run_one,
                    on_best=None if on_result is None
                    else (lambda tok_s: on_result(_result(tok_s))),
                    tag="bench_bert")
    return _result(best)


def _result(tok_s):
    return {
        "metric": "bert_base_mlm_train_throughput",
        "value": round(tok_s, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 4),
    }


def main():
    from bench_util import smoke_line, tpu_or_smoke
    from mxnet_tpu.observability import compilex
    device, smoke = tpu_or_smoke("bench_bert")
    compilex.entry_compilation_cache(ROOT)
    if smoke:
        step, params, mom, data, _ = build_step(2, 64, 8, unroll=1)
        for _ in range(2):
            params, mom, loss = step(params, mom, *data)
        print(smoke_line(device, 2, loss))
        return 0
    batch = os.environ.get("BENCH_BERT_BATCH")
    res = measure([int(b) for b in batch.split(",")] if batch else None,
                  int(os.environ.get("BENCH_BERT_STEPS", 20)))
    res["device"] = device
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
