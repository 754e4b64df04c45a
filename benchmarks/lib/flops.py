"""Operations and bytes from shapes, and the table of peaks.

What the algorithm needs, not what an implementation spends: recomputed
operations and padded or lane-replicated buffers do not count, so a share
of a peak computed from these cannot be flattered by waste.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind):
    """The published peaks of one chip; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}: add a row with its source")
    return table[device_kind]


def bert_train_flops_per_token(cfg, seq, masked):
    """Forward + backward matmul operations per input token of BERT
    pretraining: per layer the QKV, output and two FFN projections plus
    the two attention matmuls over `seq` keys; the MLM transform and tied
    vocabulary projection on `masked` of `seq` positions; backward = 2 x
    forward. Embedding lookups, layernorms, pooler and NSP are left out
    (under 0.1%)."""
    d, h = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 2 * (4 * d * d + 2 * d * h) + 4 * seq * d
    mlm = (2 * d * d + 2 * d * cfg["vocab_size"]) * masked / seq
    return 3 * (cfg["num_hidden_layers"] * layer + mlm)


def flash_train_cost(batch, heads, seq, head_dim, itemsize=2):
    """(operations, bytes) one layer's attention needs in a training
    step at full length: forward S = QK^T and PV (4 B H S^2 dh), backward
    S, dP, dV, dK, dQ once each (10 B H S^2 dh) although the two backward
    kernels each recompute S and dP; bytes are Q, K, V, O, dO, dQ, dK, dV
    once each way they must move (forward reads 3 writes 1, backward
    reads 5 writes 3) plus the f32 row statistics."""
    bhs = batch * heads * seq
    ops = 14 * bhs * seq * head_dim
    tensor = bhs * head_dim * itemsize
    stats = bhs * 4
    return ops, 12 * tensor + 3 * stats


def least_seconds(ops, nbytes, peak):
    """The roofline's least time and which side bounds it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return max(t_ops, t_mem), ("compute" if t_ops >= t_mem else "memory")
