"""The `serve_par_backlog` kind on a tiny configuration on the CPU,
through its own run(), its check against the plain reference with every
control, and the readers of the per-layer metrics that come with it (the
command line still refuses a non-TPU backend: test_harness.py)."""
import time

import numpy as np
import pytest

from benchmarks.lib import harness, ssm_flops

ROOT = harness.ROOT
SEED = 4000000043
CONFIG = "benchmarks/configs/falcon_h1_34b_l4.json"


def tiny_config():
    """The configuration at a tiny size: every multiplier as published,
    5 query heads a KV head, 2 groups, a chunk of the scan spanning two
    pages; float32."""
    cfg = harness.load_json(ROOT, CONFIG)
    cfg.update(hidden_size=64, num_attention_heads=10, num_key_value_heads=2,
               head_dim=16, mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32,
               mamba_d_state=16, mamba_chunk_size=16, intermediate_size=96,
               vocab_size=200, layers_held=2, layers_held_range=[0, 2],
               param_dtype="float32")
    cfg["server"].update(slots=4, page_size=8, max_prompt_len=32,
                         max_new_tokens=16)
    return cfg


def tiny_traffic():
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "instruct_backlog.json")
    traffic["lengths"].update(prompt_median=8, prompt_clip=[2, 32],
                              out_median=6, out_clip=[2, 16])
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0)
    traffic["logit_check"]["prompt_from"] = 2
    traffic["logit_check"]["limits"].update(logits=1e-4, state=1e-4,
                                            tails=1e-4, pages=1e-4)
    return traffic


def test_the_configuration_keeps_every_published_width():
    cfg = harness.load_json(ROOT, CONFIG)
    from benchmarks.lib import lm_par
    from mxnet_tpu.models.decoder_lm import PAR
    spec = lm_par.spec_of(cfg)
    assert (spec.hidden, spec.heads, spec.kv_heads, spec.head_dim) \
        == (5120, 20, 4, 128)
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_groups, spec.ssm_chunk, spec.conv_kernel) \
        == (32, 128, 256, 2, 128, 4)
    assert spec.ssm_dims() == (4096, 4096 + 1024)
    assert spec.dense_width == 21504 and spec.eps == 1e-5
    assert spec.pattern == (PAR,) * 4 and spec.ffn == ("dense",) * 4
    assert (spec.rope_theta, spec.attn_rope, spec.rope_yarn,
            spec.attn_gate) == (1e11, True, (), False)
    assert (spec.embed_mult, spec.head_mult, spec.key_mult) == (
        cfg["embedding_multiplier"], 0.0078125, cfg["key_multiplier"])
    assert spec.ffn_mult == tuple(cfg["mlp_multipliers"])
    assert spec.ssm_mult == tuple(cfg["ssm_multipliers"])
    assert spec.par_mult == (0.25, cfg["ssm_out_multiplier"], 1.0, 0.0375)
    assert cfg["server"]["prefix_cache"] is False
    assert cfg["reduced"] == ["layers_held"]
    # every number of the catalog row's config, at its published value
    published = {
        "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_size": 5120, "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
        "mamba_n_heads": 32, "max_position_embeddings": 262144,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "num_attention_heads": 20, "num_hidden_layers": 72,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845, "vocab_size": 261120}
    assert {k: cfg[k] for k in published} == published


def test_the_init_brings_every_term_to_unit_scale():
    """`row_scales`: a segment, a branch or the logits at unit RMS after
    the multipliers; q and k at qk_gain."""
    from benchmarks.lib import lm_par
    spec = lm_par.spec_of(harness.load_json(ROOT, CONFIG))
    s = lm_par.row_scales(spec, 2.0)
    d = 5120 ** 0.5
    seg = s["ssm_in_weight"] * 0.25 * d
    m = spec.ssm_mult
    assert seg.shape == (9248,)
    for lo, hi, f in ((0, 4096, m[0]), (4096, 8192, m[1]),
                      (8192, 8704, m[2]), (8704, 9216, m[3]),
                      (9216, 9248, m[4])):
        np.testing.assert_allclose(seg[lo:hi] * f, 1.0)
    qkv = s["attn_qkv_weight"] * d
    np.testing.assert_allclose(qkv[:2560], 2.0)
    np.testing.assert_allclose(qkv[2560:3072] * spec.key_mult, 2.0)
    np.testing.assert_allclose(qkv[3072:], 1.0)
    assert s["ssm_o_weight"] * spec.par_mult[1] * 64 == pytest.approx(1)
    assert s["head_weight"] * 0.0078125 * d == pytest.approx(1)
    assert s["embed_weight"] * spec.embed_mult == pytest.approx(1)


def test_corpus_is_a_fixed_set_reordered_by_the_seed():
    from benchmarks.kinds import serve_par_backlog as kind
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "instruct_backlog.json")
    a = kind.corpus(traffic, 1, 261120)
    b = kind.corpus(traffic, SEED, 261120)
    assert sorted((len(p), o) for p, o in a) \
        == sorted((len(p), o) for p, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    plen = np.array([len(p) for p, _ in a])
    out = np.array([o for _, o in a])
    assert plen.min() == 64 and plen.max() == 1024
    assert out.min() >= 64 and out.max() == 1024
    assert 470 < np.median(plen) < 555 and 470 < np.median(out) < 555
    assert all(p.min() >= 4 and p.max() < 261120 for p, _ in a[:64])


def test_serve_par_backlog_runs_a_tiny_configuration():
    import jax
    from benchmarks.kinds import serve_par_backlog as kind
    from benchmarks.metrics import (decode_turn_ms, par_attn_share_pct,
                                    par_ssd_roofline, par_ssm_share_pct,
                                    rpa_flat_roofline)
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
    assert c["ssm_shape"] == {"heads": 4, "head_dim": 8, "state": 16,
                              "groups": 2}
    # no expert layer: nothing counted, no dispatch
    assert np.array(c["window_moe"]["rows"]).size == 0
    assert not any(c["window_moe"]["dispatches"])
    assert any("the program against the float32 reference" in line
               for line in log)
    assert any(line.startswith("slice accounting") for line in log)
    ts = out["trace"]
    info = {"window": ts.window, "config": cfg, "traffic": traffic,
            "chips": 1, "device": {"kind": "TPU v5 lite"},
            "workload": "tiny"}
    assert 0 < decode_turn_ms.reduce(ts.events, ts.spans, c, info) < 1500
    # no device plane on the CPU: the device readers find nothing
    for reader in (par_ssm_share_pct, par_attn_share_pct, par_ssd_roofline,
                   rpa_flat_roofline):
        assert reader.reduce(ts.events, ts.spans, c, info) is None


def test_readers_return_nothing_where_the_program_has_no_counter():
    """What the parent gives them: no scope of these names, no recorded
    state shape."""
    from benchmarks.metrics import (par_attn_share_pct, par_ssd_roofline,
                                    par_ssm_share_pct)
    dev, ms = "/device:TPU:0", 1e6
    events = [(dev, "XLA Modules", "jit__decode_program(1)", 0, 40 * ms),
              (dev, "XLA Ops", "mxtpu_ssd_step.1", 4 * ms, 6 * ms)]
    info = {"window": (0, 10 ** 9), "config": tiny_config(), "chips": 1,
            "device": {"kind": "TPU v5 lite"}}
    assert par_ssd_roofline.reduce(events, [], {}, info) is None
    for reader in (par_attn_share_pct, par_ssm_share_pct):
        assert reader.reduce([], [], {}, info) is None


def test_the_new_readers_on_a_synthetic_trace(monkeypatch):
    """Two decode runs and a prefill run between them: each half's share
    over BOTH programs, each op joined in its own program's map; the
    state updates' least time over `mxtpu_ssd_step`'s time at the
    recorded shape."""
    from benchmarks.lib import program_share
    from benchmarks.metrics import (par_attn_share_pct, par_ssd_roofline,
                                    par_ssm_share_pct)
    dev, ms = "/device:TPU:0", 1e6
    events = [
        (dev, "XLA Modules", "jit__decode_program(1)", 0, 40 * ms),
        (dev, "XLA Modules", "jit__prefill_program(2)", 40 * ms, 60 * ms),
        (dev, "XLA Modules", "jit__decode_program(1)", 100 * ms, 40 * ms),
        (dev, "XLA Ops", "fusion.7", 0, 4 * ms),           # mx_par_ssm
        (dev, "XLA Ops", "mxtpu_ssd_step.1", 4 * ms, 6 * ms),
        (dev, "XLA Ops", "mxtpu_rpa_flat.1", 10 * ms, 2 * ms),
        (dev, "XLA Ops", "fusion.7", 50 * ms, 30 * ms),    # mx_par_seq_attn
        (dev, "XLA Ops", "fusion.7", 100 * ms, 4 * ms),
        (dev, "XLA Ops", "mxtpu_ssd_step.1", 104 * ms, 6 * ms),
        (dev, "XLA Ops", "mxtpu_rpa_flat.1", 110 * ms, 2 * ms)]
    maps = {"serve_lm_decode": {
                "module": "jit__decode_program",
                "op_scopes": {"fusion.7": ("mx_par", "mx_par_ssm"),
                              "mxtpu_ssd_step.1": ("mx_par", "mx_par_ssm"),
                              "mxtpu_rpa_flat.1": ("mx_par",
                                                   "mx_par_attn")},
                "op_names": {}},
            "serve_lm_prefill": {
                "module": "jit__prefill_program",
                "op_scopes": {"fusion.7": ("mx_par_seq",
                                           "mx_par_seq_attn")},
                "op_names": {}}}
    monkeypatch.setattr(program_share, "inspections", lambda: maps)
    monkeypatch.setattr(program_share, "_last", [None, None, None])
    cfg = harness.load_json(ROOT, CONFIG)
    info = {"window": (0, 140 * ms), "config": cfg, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "workload": "synthetic"}
    # busy 54 ms: the SSM half 20 ms of decode; the attention half 4 ms
    # of decode and 30 of prefill
    assert par_ssm_share_pct.reduce(events, [], {}, info) \
        == pytest.approx(100 * 20 / 54)
    assert par_attn_share_pct.reduce(events, [], {}, info) \
        == pytest.approx(100 * 34 / 54)
    shape = {"heads": 32, "head_dim": 128, "state": 256, "groups": 2}
    ops, nbytes = ssm_flops.ssd_step_cost(128, 32, 128, 256, 2)
    assert 2 * 128 * 32 * 128 * 256 * 4 < nbytes \
        < 2.02 * 128 * 32 * 128 * 256 * 4         # 4.19 MB a slot, twice
    least = nbytes / 819e9                         # memory-bound
    assert par_ssd_roofline.reduce(events, [], {"ssm_shape": shape}, info) \
        == pytest.approx(100 * 2 * least / 12e-3, rel=1e-3)


# ------------------------------------------- the check against the reference
@pytest.fixture(scope="module")
def tiny_server():
    from benchmarks.lib import lm_par
    cfg = tiny_config()
    model, srv = lm_par.build_server(cfg, SEED, 8)
    yield cfg, model, srv
    srv.close()


def _finish(tiny_server, control):
    from benchmarks.kinds import serve_par_backlog as kind
    cfg, model, srv = tiny_server
    problems, log = [], []
    read = kind.finish(srv, model, cfg, tiny_traffic(), SEED, [],
                       log.append, problems, control=control)
    return read, problems, log


def test_the_check_passes_the_program_on_every_figure(tiny_server):
    read, problems, log = _finish(tiny_server, None)
    assert problems == [], log
    assert read["state_bf16_share"] < 1e-3
    assert 0 < read["logits"] < 1e-4 and 0 < read["state"] < 1e-4
    assert 0 <= read["tails"] < 1e-4 and 0 <= read["pages"] < 1e-4


def _controls():
    from benchmarks.tools.par_precision_readings import CONTROLS
    return CONTROLS


@pytest.mark.parametrize("name", list(_controls()))
def test_the_check_fails_every_control(tiny_server, name):
    """The reference below the configuration's precision, with a
    multiplier at 1 or with a term left out, through the cell's own
    finish(): not correct, by logits (bfloat16 state alone: by
    state_bf16_share)."""
    read, problems, log = _finish(tiny_server, _controls()[name])
    by = "state_bf16_share" if name == "low_state" else "logits"
    assert any(p.startswith(by + " off the reference") for p in problems), \
        (read, problems)


def test_the_check_judges_each_kind_of_state_a_layer_keeps(tiny_server):
    """One request's state, one tail row, one cached key: each moves its
    figure, whatever the rest read."""
    import jax
    from benchmarks.kinds import serve_par_backlog as kind
    from benchmarks.lib import lm, lm_par
    from benchmarks.reference import falcon_h1_34b_l4 as ref
    cfg, model, srv = tiny_server
    check = tiny_traffic()["logit_check"]
    steps = check["positions"]
    seqs, plen = kind.check_sequences(srv.runtime, cfg["vocab_size"], 7,
                                      check)
    assert plen[0] == 2 and plen[-1] == srv.runtime.max_src_len
    weights, dims = lm_par.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("head_rows",))

    def forward(tokens, n, head_from):
        return jitted(weights, dims, tokens, n, head_from=head_from,
                      head_rows=steps)

    got = kind.program_readings(srv, seqs, plen, steps)
    assert srv.pool.in_use() == 0
    assert len(got["state"]) == len(got["tails"]) == len(got["keys"]) == 2
    assert got["state"][0].shape == (4, 4, 8, 16)
    assert got["tails"][0].shape == (4, 3, 32 + 2 * 2 * 16)
    assert got["keys"][0].shape == (4, 32 + steps - 1, 2 * 16)

    def read(g):
        return kind.figures(g, kind.reference_readings(forward, seqs, plen,
                                                       steps))

    clean = read(got)
    assert max(clean[k] for k in ("logits", "state", "tails", "pages")) \
        < 1e-4
    bad = dict(got, state=[s.copy() for s in got["state"]])
    bad["state"][1][3] *= 0.5                     # one request, one layer
    assert read(bad)["state"] > 0.4
    bad = dict(got, tails=[t.copy() for t in got["tails"]])
    bad["tails"][0][0] = np.roll(bad["tails"][0][0], 1, 0)   # off by one
    assert read(bad)["tails"] > 0.1
    bad = dict(got, keys=[k.copy() for k in got["keys"]])
    bad["keys"][1][2, 20] = 0.0                   # one row of one layer
    assert read(bad)["pages"] > 0.05
