"""The `serve_ssm_backlog` kind on a tiny configuration on the CPU,
through its own run(), its check against the plain reference with every
control, and the readers of the per-layer metrics that come with it (the
command line still refuses a non-TPU backend: test_harness.py)."""
import time

import numpy as np
import pytest

from benchmarks.lib import harness, lm_flops, ssm_flops

ROOT = harness.ROOT
SEED = 3400000043


def tiny_config():
    cfg = harness.load_json(ROOT,
                            "benchmarks/configs/nemotron3_nano_ep2.json")
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, vocab_size=200, moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=48, n_routed_experts=4,
               router_width=16, experts_held=[0, 4], num_experts_per_tok=4,
               mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
               n_groups=2, chunk_size=8, param_dtype="float32")
    cfg["server"].update(slots=4, page_size=8, max_prompt_len=32,
                         max_new_tokens=16)
    return cfg


def tiny_traffic():
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "reason_ssm_backlog.json")
    traffic["lengths"].update(prompt_median=8, prompt_clip=[2, 32],
                              out_median=6, out_clip=[2, 16])
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0)
    traffic["logit_check"]["limits"].update(logits=1e-4, state=1e-4,
                                            tails=1e-4, routing=1e-4)
    return traffic


def test_the_configuration_keeps_every_published_width():
    cfg = harness.load_json(ROOT,
                            "benchmarks/configs/nemotron3_nano_ep2.json")
    from benchmarks.lib import lm_ssm
    spec = lm_ssm.spec_of(cfg)
    assert (spec.hidden, spec.heads, spec.kv_heads, spec.head_dim) \
        == (2688, 32, 2, 128)
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_groups, spec.ssm_chunk, spec.conv_kernel) \
        == (64, 64, 128, 8, 128, 4)
    assert spec.ssm_dims() == (4096, 6144)
    assert (spec.num_experts, spec.top_k, spec.expert_width,
            spec.shared_width) == (128, 6, 1856, 3712)
    assert (spec.held_lo, spec.held_n, spec.scaling) == (0, 64, 2.5)
    assert (spec.paired, spec.attn_gate, spec.expert_act, spec.eps) \
        == (False, False, "relu2", 1e-5)
    assert "".join({"mamba": "M", "moe": "E", "gqa": "*"}[k]
                   for k in spec.pattern) == "MEMEM*EME" \
        == cfg["hybrid_override_pattern"][:9]
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert cfg["vocab_size"] * 2 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 2 == cfg["published"][
        "n_routed_experts"] == cfg["router_width"]
    # each held expert sees the deployment's load
    assert cfg["server"]["slots"] * 6 // cfg["router_width"] == 12
    assert cfg["server"]["prefix_cache"] is False
    # every number of the catalog row's config, at its published value,
    # but the cut keys
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-5, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "max_position_embeddings": 262144,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-5,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "ssm_state_size": 128, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["layers_held", "n_routed_experts",
                              "vocab_size"]


def test_corpus_is_a_fixed_set_reordered_by_the_seed():
    from benchmarks.kinds import serve_ssm_backlog as kind
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "reason_ssm_backlog.json")
    a = kind.corpus(traffic, 1, 65536)
    b = kind.corpus(traffic, SEED, 65536)
    assert sorted((len(p), o) for p, o in a) \
        == sorted((len(p), o) for p, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    plen = np.array([len(p) for p, _ in a])
    out = np.array([o for _, o in a])
    assert plen.min() >= 16 and plen.max() == 512
    assert out.min() >= 64 and out.max() == 1024
    assert 110 < np.median(plen) < 150 and 470 < np.median(out) < 555
    assert all(p.min() >= 4 and p.max() < 65536 for p, _ in a[:64])


def test_serve_ssm_backlog_runs_a_tiny_configuration():
    import jax
    from benchmarks.kinds import serve_ssm_backlog as kind
    from benchmarks.metrics import (decode_turn_ms, expert_tokens_cv,
                                    gmm_relu2_roofline, mamba_share_pct,
                                    rpa_flat_roofline, ssd_step_roofline)
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
    experts = [1, 3, 6, 8]                       # the E of MEMEM*EME
    others = [0, 2, 4, 5, 7]
    assert rows.shape == (9, 4) and rows[experts].sum(1).all()
    assert not rows[others].any()
    assert [moe["dispatches"][i] for i in others] == [0] * 5
    assert all(moe["dispatches"][i] >= c["decode_turns"] for i in experts)
    # a prefill stops after the last Mamba-2 layer: no last expert layer
    assert moe["dispatches"][8] < moe["dispatches"][6]
    assert any("the program against the float32 reference" in line
               for line in log)
    assert any(line.startswith("slice accounting") for line in log)
    ts = out["trace"]
    info = {"window": ts.window, "config": cfg, "traffic": traffic,
            "chips": 1, "device": {"kind": "TPU v5 lite"},
            "workload": "tiny"}
    assert 0 < decode_turn_ms.reduce(ts.events, ts.spans, c, info) < 1500
    assert expert_tokens_cv.reduce(ts.events, ts.spans, c, info) >= 0
    steps = [s for s in ts.spans if s[0] == "serve.decode_step"]
    assert steps and all("cached_tokens" in s[3] for s in steps)
    # no device plane on the CPU: the device readers find nothing
    for reader in (mamba_share_pct, rpa_flat_roofline, ssd_step_roofline,
                   gmm_relu2_roofline):
        assert reader.reduce(ts.events, ts.spans, c, info) is None


def test_readers_return_nothing_where_the_program_has_no_counter():
    """What the parent gives them: no scope map of this name, no kernel
    of this name, no expert counter."""
    from benchmarks.metrics import (gmm_relu2_roofline, mamba_share_pct,
                                    ssd_step_roofline)
    info = {"window": (0, 10 ** 9), "config": tiny_config(), "chips": 1,
            "device": {"kind": "TPU v5 lite"}}
    spans = [("serve.decode_step", 0.0, 5.0, {"active": 3})]
    for reader in (gmm_relu2_roofline, mamba_share_pct, ssd_step_roofline):
        assert reader.reduce([], spans, {"decode_turns": 3}, info) is None


def test_the_new_readers_on_a_synthetic_trace(monkeypatch):
    """A device plane with two decode runs and a prefill run between
    them: the kernels' calls and time, and the scope's share joined
    inside the DECODE program's runs only."""
    from benchmarks.lib import scope_share
    from benchmarks.metrics import (gmm_relu2_roofline, mamba_share_pct,
                                    ssd_step_roofline)
    dev, ms = "/device:TPU:0", 1e6
    events = [
        (dev, "XLA Modules", "jit__decode_program(1)", 0, 40 * ms),
        (dev, "XLA Modules", "jit__prefill_program(2)", 40 * ms, 60 * ms),
        (dev, "XLA Modules", "jit__decode_program(1)", 100 * ms, 40 * ms),
        (dev, "XLA Ops", "fusion.7", 0, 4 * ms),               # mx_mamba
        (dev, "XLA Ops", "mxtpu_ssd_step.1", 4 * ms, 6 * ms),
        (dev, "XLA Ops", "mxtpu_gmm.1", 10 * ms, 2 * ms),
        (dev, "XLA Ops", "mxtpu_gmm.2", 12 * ms, 2 * ms),
        (dev, "XLA Ops", "fusion.7", 50 * ms, 30 * ms),        # prefill's
        (dev, "XLA Ops", "fusion.7", 100 * ms, 4 * ms),
        (dev, "XLA Ops", "mxtpu_ssd_step.1", 104 * ms, 6 * ms),
        (dev, "XLA Ops", "mxtpu_gmm.1", 110 * ms, 2 * ms),
        (dev, "XLA Ops", "mxtpu_gmm.2", 112 * ms, 2 * ms)]
    cfg = harness.load_json(ROOT,
                            "benchmarks/configs/nemotron3_nano_ep2.json")
    info = {"window": (0, 140 * ms), "config": cfg, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "workload": "synthetic"}
    monkeypatch.setattr(scope_share, "step_scopes", lambda name: {
        "fusion.7": ("mx_mamba",), "mxtpu_ssd_step.1": ("mx_mamba",),
        "mxtpu_gmm.1": ("mx_moe",)})
    # busy 58 ms; the decode runs' mx_mamba ops 20 ms; prefill's fusion.7
    # has the same NAME and is not the decode program's
    assert mamba_share_pct.reduce(events, [], {}, info) \
        == pytest.approx(100 * 20 / 58)
    ops, nbytes = ssm_flops.ssd_step_cost(256, 64, 64, 128, 8)
    assert nbytes > 2 * 256 * 64 * 64 * 128 * 4       # a read and a write
    assert nbytes < 2.05 * 256 * 64 * 64 * 128 * 4
    assert ops == 6 * 256 * 64 * 64 * 128
    least = nbytes / 819e9                           # memory-bound
    assert ssd_step_roofline.reduce(events, [], {}, info) \
        == pytest.approx(100 * 2 * least / 12e-3, rel=1e-3)
    moe = {"dispatches": [0, 1, 0, 1], "rows": [[0, 0], [700, 836],
                                                [0, 0], [800, 736]],
           "touched": [0, 64, 0, 64]}
    pair = ssm_flops.relu2_pair_cost(1536, 64, 2688, 1856)
    assert pair == [lm_flops.gmm_cost(1536, 64, 2688, 1856),
                    lm_flops.gmm_cost(1536, 64, 1856, 2688)]
    least = sum(b for _, b in pair) / 819e9
    got = gmm_relu2_roofline.reduce(events, [], {"slice_moe": moe}, info)
    assert got == pytest.approx(100 * 2 * least / 8e-3, rel=1e-3)
    # the gated reader would reckon a (d, 2w) first call: 1.5 times this
    from benchmarks.metrics import gmm_roofline
    gated = gmm_roofline.reduce(events, [], {"slice_moe": moe}, info)
    assert gated == pytest.approx(1.5 * got, rel=0.01)


# ------------------------------------------- the check against the reference
@pytest.fixture(scope="module")
def tiny_server():
    from benchmarks.lib import lm_ssm
    cfg = tiny_config()
    model, srv = lm_ssm.build_server(cfg, SEED, 8)
    # weights large enough that every term of every layer shows
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray
    rng = np.random.default_rng(5)
    for p in model.collect_params().values():
        v = 0.3 * rng.normal(size=p.shape).astype(np.float32)
        p.set_data(NDArray(jnp.asarray(
            1 + v if p.name.endswith("gamma") else v)))
    srv.close()
    import mxnet_tpu as mx
    srv = mx.serve.Server(model, max_queue=8, **cfg["server"])
    yield cfg, model, srv
    srv.close()


def _finish(tiny_server, control):
    from benchmarks.kinds import serve_ssm_backlog as kind
    cfg, model, srv = tiny_server
    problems, log = [], []
    read = kind.finish(srv, model, cfg, tiny_traffic(), SEED, [],
                       log.append, problems, control=control)
    return read, problems, log


def test_the_check_passes_the_program_on_every_figure(tiny_server):
    read, problems, log = _finish(tiny_server, None)
    assert problems == [], log
    assert read["routing"] == 0.0 and read["state_bf16_share"] < 1e-3
    assert 0 < read["logits"] < 1e-4 and 0 < read["state"] < 1e-4
    assert 0 <= read["tails"] < 1e-4


@pytest.mark.parametrize("control,by", [
    ({"low": "all"}, "logits"), ({"low": "state"}, "state_bf16_share"),
    ({"leave_out": "d_skip"}, "logits"), ({"leave_out": "gate"}, "logits"),
    ({"leave_out": "conv_bias"}, "state"), ({"leave_out": "dt_bias"},
                                            "state"),
    ({"leave_out": "relu"}, "logits"), ({"leave_out": "shared"}, "logits"),
    ({"leave_out": "scaling"}, "logits"), ({"leave_out": "one_norm"},
                                           "logits")],
    ids=["low_all", "low_state", "no_d_skip", "no_gate", "no_conv_bias",
         "no_dt_bias", "relu_for_relu2", "no_shared", "scaling_1",
         "one_norm"])
def test_the_check_fails_every_control(tiny_server, control, by):
    """The reference below the configuration's precision, or with a term
    left out, through the cell's own finish(): not correct."""
    read, problems, log = _finish(tiny_server, control)
    assert any(p.startswith(by + " off the reference") for p in problems), \
        (read, problems)


def test_the_check_judges_the_largest_position_and_the_slots_state(
        tiny_server):
    """One wrong position of 64, one request's state, one expert id: each
    moves its figure, whatever the other 63 read."""
    import jax
    from benchmarks.kinds import serve_ssm_backlog as kind
    from benchmarks.lib import lm, lm_ssm
    from benchmarks.reference import nemotron3_nano_ep2 as ref
    cfg, model, srv = tiny_server
    check = tiny_traffic()["logit_check"]
    steps = check["positions"]
    seqs, plen = kind.check_sequences(srv.runtime, cfg["vocab_size"], 7,
                                      check)
    assert plen[0] <= 16 and plen[-1] == srv.runtime.max_src_len
    weights, dims = lm_ssm.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,))

    def forward(tokens, n, routing):
        return jitted(weights, dims, tokens, n, routing)

    got = kind.program_readings(srv, seqs, plen, steps)
    assert srv.pool.in_use() == 0
    end = plen[0] + steps - 1
    experts, others = [1, 3, 6, 8], [0, 2, 4, 5, 7]
    assert (got["routing"][0, :, end:] == -1).all()
    assert (got["routing"][0, others] == -1).all()
    assert (got["routing"][0, experts[:-1], :end] >= 0).all()
    # prefill stops after the last Mamba-2 layer: nothing reads layer 8
    assert (got["routing"][0, 8, :plen[0] - 1] == -1).all()
    assert (got["routing"][0, 8, plen[0] - 1:end] >= 0).all()
    assert len(got["state"]) == len(got["tails"]) == 4
    assert got["state"][0].shape == (4, 4, 8, 16)
    assert got["state"][0].dtype == np.float32
    assert got["tails"][0].shape == (4, 3, 32 + 2 * 2 * 16)

    def read(g):
        want = kind.reference_readings(forward, seqs, plen, steps,
                                       g["routing"])
        return kind.figures(g, want)

    clean = read(got)
    assert max(clean[k] for k in ("logits", "state", "tails")) < 1e-4
    assert clean["routing"] == 0.0
    bad = dict(got, logits=got["logits"].copy())
    bad["logits"][2, 5] += 1.0                    # one position of 64
    r = read(bad)
    assert r["logits"] > 0.05 and r["logits_mid"] < 1e-4
    bad = dict(got, state=[s.copy() for s in got["state"]])
    bad["state"][1][3] *= 0.5                     # one request, one layer
    assert read(bad)["state"] > 0.4
    bad = dict(got, state=[np.asarray(jax.numpy.asarray(s, "bfloat16"),
                                      np.float32) for s in got["state"]])
    r = read(bad)
    assert r["state_bf16_share"] == 1.0 and r["state"] < 1e-2
    bad = dict(got, tails=[t.copy() for t in got["tails"]])
    bad["tails"][0][0] = np.roll(bad["tails"][0][0], 1, 0)   # off by one
    assert read(bad)["tails"] > 0.1
    bad = dict(got, routing=got["routing"].copy())
    bad["routing"][3, 3, 4, 0] = (bad["routing"][3, 3, 4].max() + 1) % 16
    r = read(bad)        # another expert than it chose: the reference,
    assert r["routing"] > 0 or r["logits"] > 1e-3   # forced on it, moves
