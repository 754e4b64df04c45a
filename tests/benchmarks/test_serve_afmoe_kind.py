"""The `serve_afmoe_backlog` kind on a tiny configuration on the CPU
(window 32, pages of 16, a ring of 3 pages a slot, layers S(dense) S F S
S), through its own run() and check, and the reader of
`swa_prefill_roofline` (the command line still refuses a non-TPU
backend: test_harness.py)."""
import time

import numpy as np
import pytest

from benchmarks.lib import harness, swa_flops

ROOT = harness.ROOT
SEED = 4400000043
CONFIG = "benchmarks/configs/trinity_large_ep8.json"


def tiny_config():
    cfg = harness.load_json(ROOT, CONFIG)
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, vocab_size=200, moe_intermediate_size=32,
               intermediate_size=96, num_experts=8, router_width=16,
               experts_held=[0, 8], num_experts_per_tok=2,
               sliding_window=32, param_dtype="float32")
    cfg["server"].update(slots=4, page_size=16, max_prompt_len=100,
                         max_new_tokens=16)
    return cfg


def tiny_traffic():
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "long_ctx_backlog.json")
    traffic["lengths"].update(prompt_median=40, prompt_clip=[4, 100],
                              out_median=6, out_clip=[2, 16])
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0)
    traffic["logit_check"].update(prompt_from=10)
    return traffic


def test_the_configuration_keeps_every_published_width():
    cfg = harness.load_json(ROOT, CONFIG)
    from benchmarks.lib import lm_afmoe
    spec = lm_afmoe.spec_of(cfg)
    assert (spec.hidden, spec.heads, spec.kv_heads, spec.head_dim) \
        == (3072, 48, 8, 128)
    assert (spec.num_experts, spec.top_k, spec.expert_width,
            spec.dense_width) == (256, 4, 3072, 12288)
    assert (spec.held_lo, spec.held_n, spec.scaling, spec.eps) \
        == (0, 32, 2.448, 1e-5)
    assert (spec.window, spec.attn_rope, spec.rope_theta, spec.rope_yarn) \
        == (4096, ("swa",), 10000.0, ())
    assert (spec.qk_norm, spec.attn_gate, spec.sandwich, spec.router_bias,
            spec.shared_expert, spec.shared_width, spec.router_score) \
        == (True, True, True, True, True, 0, "sigmoid")
    assert spec.embed_mult == pytest.approx(55.42562584220407)
    # published layers 5-9: the last leading dense layer, then a period
    assert spec.pattern == ("swa", "swa", "gqa", "swa", "swa")
    assert spec.ffn == ("dense", "moe", "moe", "moe", "moe")
    assert cfg["layer_types"][5:10] == ["sliding_attention"] * 2 \
        + ["full_attention"] + ["sliding_attention"] * 2
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 60
    assert cfg["reduced"] == ["layers_held", "num_experts", "vocab_size"]
    assert cfg["published"] == {"layers": 60, "num_experts": 256,
                                "vocab_size": 200192}
    assert cfg["vocab_rows_held"] == [0, 25024] and 8 * 25024 == 200192
    # 48 slots: 48 x 4 / 256 = 0.75 rows a held expert a turn
    assert cfg["server"]["slots"] * 4 / 256 == 0.75
    assert cfg["server"]["prefix_cache"] is False
    # every number of the catalog row's config but the two cut
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 3072, "intermediate_size": 12288,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "moe_intermediate_size": 3072, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts_per_tok": 4,
        "num_hidden_layers": 60, "num_key_value_heads": 8,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.448,
        "sliding_window": 4096, "topk_group": 1}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_experts"], cfg["vocab_size"]) == (32, 25024)
    for item in ("embedding", "attention", "qk_norm", "rotation", "nope",
                 "window", "scale", "gate", "sandwich", "router", "init",
                 "eos_id", "slots", "layers_held"):
        assert item in cfg["assumed"]


def test_corpus_is_a_fixed_set_reordered_by_the_seed():
    from benchmarks.kinds import serve_afmoe_backlog as kind
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "long_ctx_backlog.json")
    a = kind.corpus(traffic, 1, 25024)
    b = kind.corpus(traffic, SEED, 25024)
    assert sorted((len(p), o) for p, o in a) \
        == sorted((len(p), o) for p, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    plen = np.array([len(p) for p, _ in a])
    out = np.array([o for _, o in a])
    assert plen.min() == 1024 and plen.max() == 8192
    assert out.min() == 512 and out.max() == 4096
    assert 3900 < np.median(plen) < 4300 and 1940 < np.median(out) < 2160
    # half the prompts are past the window
    assert 0.4 < (plen > 4096).mean() < 0.6
    assert all(p.min() >= 4 and p.max() < 25024 for p, _ in a[:64])


def test_serve_afmoe_backlog_runs_a_tiny_configuration():
    import jax
    from benchmarks.kinds import serve_afmoe_backlog as kind
    from benchmarks.metrics import (decode_turn_ms, expert_tokens_cv,
                                    rpa_ring_roofline, swa_prefill_roofline)
    harness.CompileWatch.install()
    cfg, traffic, log = tiny_config(), tiny_traffic(), []
    out = kind.run({
        "cell": {"name": "tiny", "chips": 1}, "config": cfg,
        "traffic": traffic, "seed": SEED, "seconds": 1.5,
        "trace": True, "say": log.append, "t_start": time.perf_counter(),
        "device": {"kind": "TPU v5 lite"}, "devices": jax.devices()})
    assert out["problems"] == [], (out["problems"], log)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert c["window"]["compilations"] == 0 and c["decode_turns"] > 0
    moe = c["window_moe"]
    rows = np.array(moe["rows"])
    # the leading dense layer takes no rows
    assert rows.shape == (5, 8) and not rows[0].any() and rows[1:].all()
    assert moe["dispatches"][0] == 0
    ring = c["window_ring"]
    assert ring["turns"] <= ring["ring_tokens"] <= ring["turns"] * 4 * 32
    # a ring of 48 rows is one block, fetched whole for a running slot
    assert ring["ring_rows"] % 48 == 0
    assert 48 * ring["turns"] <= ring["ring_rows"] <= 48 * 4 * ring["turns"]
    pre = c["window_prefill"]
    assert pre["prefills"] > 0
    assert pre["prompt_tokens"] <= pre["window_keys"] \
        <= 32 * pre["prompt_tokens"]
    assert c["slice_prefill"]["prefills"] > 0
    assert any("the program against the float32 reference" in line
               for line in log)
    ts = out["trace"]
    info = {"window": ts.window, "config": cfg, "traffic": traffic,
            "chips": 1, "device": {"kind": "TPU v5 lite"},
            "workload": "tiny"}
    assert 0 < decode_turn_ms.reduce(ts.events, ts.spans, c, info) < 1500
    assert expert_tokens_cv.reduce(ts.events, ts.spans, c, info) >= 0
    # no device plane on the CPU: the device readers find nothing
    for reader in (rpa_ring_roofline, swa_prefill_roofline):
        assert reader.reduce(ts.events, ts.spans, c, info) is None


def test_the_prefill_reader_returns_nothing_without_the_counter():
    """What the parent gives it: no `window_keys` among the prefill
    counts, no slice counts at all."""
    from benchmarks.metrics import swa_prefill_roofline
    info = {"window": (0, 10 ** 9), "config": tiny_config(), "chips": 1,
            "device": {"kind": "TPU v5 lite"}}
    events = [("/device:TPU:0", "XLA Ops", "mxtpu_flash_fwd.1", 0.0, 1e6)]
    for counters in ({}, {"slice_prefill": None},
                     {"slice_prefill": {"prefills": 3, "prompt_tokens": 9}}):
        assert swa_prefill_roofline.reduce(events, [], counters, info) \
            is None


def test_the_prefill_reader_on_a_synthetic_trace(monkeypatch):
    """A decode run, a prefill run with a window layer's flash call and a
    full layer's: only the one under `mx_swa_seq` counts, against the
    runtime's keys."""
    from benchmarks.lib import program_share as ps, trace_reduce as tr
    from benchmarks.metrics import swa_prefill_roofline
    from mxnet_tpu.observability import compilex
    dev, ms = "/device:TPU:0", 1e6
    events = [
        (tr.HOST_PLANE, "main", tr.WINDOW, 0.0, 140 * ms),
        (dev, tr.MODULES, "jit__decode_program(1)", 0, 40 * ms),
        (dev, tr.MODULES, "jit__prefill_program(2)", 40 * ms, 60 * ms),
        (dev, tr.OPS, "mxtpu_flash_fwd.9", 10 * ms, 5 * ms),   # decode's
        (dev, tr.OPS, "mxtpu_flash_fwd.3", 50 * ms, 8 * ms),   # mx_swa_seq
        (dev, tr.OPS, "mxtpu_flash_fwd.4", 60 * ms, 9 * ms),   # mx_gqa_seq
        (dev, tr.OPS, "mxtpu_flash_fwd.5", 70 * ms, 8 * ms)]   # mx_swa_seq
    monkeypatch.setattr(compilex, "_inspections", {
        "serve_lm_decode": {"module": "jit__decode_program",
                            "op_scopes": {"mxtpu_flash_fwd.9": ("mx_swa",)},
                            "op_names": {}},
        "serve_lm_prefill": {
            "module": "jit__prefill_program",
            "op_scopes": {"mxtpu_flash_fwd.3": ("mx_swa_seq",),
                          "mxtpu_flash_fwd.4": ("mx_gqa_seq",),
                          "mxtpu_flash_fwd.5": ("mx_swa_seq",)},
            "op_names": {}}})
    monkeypatch.setattr(ps, "_last", [None, None, None])
    assert swa_flops.scoped_kernel_seconds(
        events, 0, 140 * ms, "mxtpu_flash_fwd", "mx_swa_seq") \
        == (2, pytest.approx(16e-3))
    cfg = harness.load_json(ROOT, CONFIG)
    info = {"window": (0, 140 * ms), "config": cfg, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "workload": "synthetic"}
    # one prefill of 6000 positions: 4096 x 4097 / 2 + 1904 x 4096 keys
    keys = 4096 * 4097 // 2 + 1904 * 4096
    pre = {"prefills": 1, "prompt_tokens": 6000, "window_keys": keys,
           "rung_tokens": 8192}
    ops, nbytes = swa_flops.swa_prefill_cost(keys, 6000, 48, 8, 128)
    assert ops == 4 * keys * 48 * 128
    assert nbytes == 2 * 6000 * 56 * 128 * 2
    least = ops / 197e12                         # compute-bound
    assert nbytes / 819e9 < least
    got = swa_prefill_roofline.reduce(events, [], {"slice_prefill": pre},
                                      info)
    assert got == pytest.approx(100 * 2 * least / 16e-3, rel=1e-6)
