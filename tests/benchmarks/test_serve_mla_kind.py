"""The `serve_mla_backlog` kind on a tiny configuration on the CPU,
through its own run(), its check against the plain reference with every
control, and the readers of the per-layer metrics that come with it (the
command line still refuses a non-TPU backend: test_harness.py)."""
import time

import numpy as np
import pytest

from benchmarks.lib import harness, mla_flops

ROOT = harness.ROOT
SEED = 3200000023


def tiny_config():
    cfg = harness.load_json(ROOT, "benchmarks/configs/pangu_ultra_ep16.json")
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
               kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               vocab_size=200, n_routed_experts=4, router_width=8,
               experts_held=[0, 4], num_experts_per_tok=2, layers_held=3,
               layers_held_range=[2, 5], param_dtype="float32")
    cfg["server"].update(slots=4, page_size=8, max_prompt_len=32,
                         max_new_tokens=16)
    return cfg


def tiny_traffic():
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "reason_long_backlog.json")
    traffic["lengths"].update(prompt_median=8, prompt_clip=[2, 32],
                              out_median=6, out_clip=[2, 16])
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0)
    traffic["logit_check"].update(prompt_from=4)
    traffic["logit_check"]["limits"] = dict.fromkeys(
        traffic["logit_check"]["limits"], 1e-4)
    return traffic


def test_the_configuration_keeps_every_published_width():
    cfg = harness.load_json(ROOT, "benchmarks/configs/pangu_ultra_ep16.json")
    from benchmarks.lib import lm_mla
    spec = lm_mla.spec_of(cfg)
    assert (spec.hidden, spec.heads, spec.q_rank, spec.kv_rank) \
        == (7680, 128, 1536, 512)
    assert (spec.nope_dim, spec.rope_dim, spec.v_dim) == (128, 64, 128)
    assert (spec.num_experts, spec.top_k, spec.expert_width) \
        == (256, 8, 2048)
    assert (spec.held_lo, spec.held_n, spec.scaling) == (0, 16, 2.5)
    assert spec.dense_width == 18432 and spec.rope_theta == 25600000.0
    assert spec.sandwich and not spec.router_bias and spec.eps == 1e-5
    assert spec.pattern == ("mla",) * 5
    assert spec.ffn == ("dense", "moe", "moe", "moe", "moe")
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["published"][
        "n_routed_experts"] == cfg["router_width"]
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["server"]["slots"] * 8 // cfg["router_width"] == 8
    assert cfg["server"]["prefix_cache"] is False
    # every number of the catalog row's config, at its published value,
    # but the four cut keys
    published = {
        "first_k_dense_replace": 3, "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "n_shared_experts": 1, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-5, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["layers_held", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]


def test_corpus_is_a_fixed_set_reordered_by_the_seed():
    from benchmarks.kinds import serve_mla_backlog as kind
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "reason_long_backlog.json")
    a = kind.corpus(traffic, 1, 19200)
    b = kind.corpus(traffic, SEED, 19200)
    assert sorted((len(p), o) for p, o in a) \
        == sorted((len(p), o) for p, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    plen = np.array([len(p) for p, _ in a])
    out = np.array([o for _, o in a])
    assert plen.min() >= 32 and plen.max() == 1024
    assert out.min() >= 64 and out.max() == 1024
    assert 230 < np.median(plen) < 285 and 470 < np.median(out) < 555
    assert all(p.min() >= 4 and p.max() < 19200 for p, _ in a[:64])


def test_serve_mla_backlog_runs_a_tiny_configuration():
    import jax
    from benchmarks.kinds import serve_mla_backlog as kind
    from benchmarks.metrics import (decode_turn_ms, expert_tokens_cv,
                                    gmm_roofline, mla_decode_roofline,
                                    mla_share_pct, moe_share_pct)
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
    # the dense layer counts no row, no touched expert and no dispatch
    assert rows.shape == (3, 4) and rows[0].sum() == 0 and rows[1].sum() > 0
    assert moe["dispatches"][0] == 0 and moe["touched"][0] == 0
    assert moe["dispatches"][1] >= c["decode_turns"]
    assert moe["dispatches"][-1] < moe["dispatches"][1]  # prefills: no last
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
    for reader in (mla_share_pct, mla_decode_roofline, moe_share_pct,
                   gmm_roofline):
        assert reader.reduce(ts.events, ts.spans, c, info) is None


def test_the_new_readers_on_synthetic_events(monkeypatch):
    """A device plane with two decode programs, each holding a latent
    attention kernel and a fusion of the `mx_mla` scope."""
    from benchmarks.lib import flops, scope_share
    from benchmarks.metrics import mla_decode_roofline, mla_share_pct
    cfg = harness.load_json(ROOT, "benchmarks/configs/pangu_ultra_ep16.json")
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [(host, "t", "bench.window", 0, 100e6)]
    for t in (10e6, 50e6):
        events += [
            (dev, "XLA Modules", "jit__decode_program(1)", t, 30e6),
            (dev, "XLA Ops", "mxtpu_mla_decode.3", t + 1e6, 2e6),
            (dev, "XLA Ops", "fusion.7", t + 4e6, 6e6),
            (dev, "XLA Ops", "fusion.9", t + 12e6, 12e6)]
    spans = [("serve.decode_step", 0.0, 5.0,
              {"active": 256, "cached_tokens": 170000 - 256})] * 2
    info = {"window": (0, 100e6), "config": cfg, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "workload": "synthetic"}
    got = mla_decode_roofline.reduce(events, spans, {}, info)
    ops, nbytes = mla_flops.mla_decode_cost(170000, 256, 128, 512, 64)
    least, side = flops.least_seconds(ops, nbytes,
                                      flops.peaks("TPU v5 lite"))
    assert got == pytest.approx(100 * least / 2e-3) and 0 < got < 100
    # the rows alone sit at the ridge; the slots' queries and outputs
    # (128 heads x 1088 values each) tip it to the memory side
    assert side == "memory" and least == nbytes / 819e9
    assert ops / 197e12 == pytest.approx(170000 * 576 * 2 / 819e9, rel=0.02)
    scopes = {"mxtpu_mla_decode.3": ("mx_mla",), "fusion.7": ("mx_mla",),
              "fusion.9": ("mx_moe",)}
    monkeypatch.setattr(scope_share, "step_scopes", lambda name: scopes)
    assert mla_share_pct.reduce(events, spans, {}, info) \
        == pytest.approx(100 * 8 / 20)
    # a prefill program that takes more of the slice than the decode
    # program does, with an op of the same name: the share stays the
    # decode program's ops over everything the device did
    longer = events + [
        (dev, "XLA Modules", "jit__prefill_program(2)", 41e6, 8e6),
        (dev, "XLA Modules", "jit__prefill_program(2)", 81e6, 18e6),
        (dev, "XLA Ops", "fusion.7", 82e6, 10e6)]
    longer = [e if e[2] != "jit__decode_program(1)" else
              e[:4] + (12e6,) for e in longer]
    assert mla_share_pct.reduce(longer, spans, {}, info) \
        == pytest.approx(100 * 16 / 50)
    # what the parent gives them: no kernel of that name, no inspection
    monkeypatch.setattr(scope_share, "step_scopes", lambda name: None)
    assert mla_share_pct.reduce(events, spans, {}, info) is None
    assert mla_decode_roofline.reduce(
        [e for e in events if "mla" not in e[2]], spans, {}, info) is None
    assert mla_decode_roofline.reduce(
        events, [("serve.decode_step", 0.0, 5.0, {"active": 3})], {},
        info) is None


def test_costs_from_shapes():
    ops, nbytes = mla_flops.mla_decode_cost(1000, 4, 128, 512, 64)
    assert ops == 2 * 1000 * 128 * (576 + 512)
    assert nbytes == (1000 * 576 + 4 * 128 * (576 + 512)) * 2
    # 128 query rows a latent row: about 242 operations a byte, the ridge
    ops, nbytes = mla_flops.mla_decode_cost(10 ** 6, 1, 128, 512, 64)
    assert 235 < ops / nbytes < 245


# ------------------------------------------- the check against the reference
@pytest.fixture(scope="module")
def tiny_server():
    from benchmarks.lib import lm_mla
    cfg = tiny_config()
    model, srv = lm_mla.build_server(cfg, SEED, 8)
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
    from benchmarks.kinds import serve_mla_backlog as kind
    cfg, model, srv = tiny_server
    problems, log = [], []
    read = kind.finish(srv, model, cfg, tiny_traffic(), SEED, [],
                       log.append, problems, control=control)
    return read, problems, log


def test_the_check_passes_the_program_on_every_figure(tiny_server):
    read, problems, log = _finish(tiny_server, None)
    assert problems == [], log
    assert read["routing"] == 0.0
    assert 0 < read["logits"] < 1e-4
    assert read["latent_c"] < 1e-4 and read["latent_rope"] < 1e-4


@pytest.mark.parametrize("control,by", [
    ({"low": "all"}, "logits"), ({"low": "cache"}, "latent_c"),
    ({"low": "cache"}, "latent_rope"), ({"leave_out": "rope"},
                                        "latent_rope"),
    ({"leave_out": "post_norms"}, "logits"),
    ({"leave_out": "kv_norm"}, "latent_c"),
    ({"leave_out": "shared"}, "logits"),
    ({"leave_out": "scaling"}, "logits")],
    ids=["low_all", "low_cache_c", "low_cache_rope", "no_rope",
         "no_post_norms", "no_kv_norm", "no_shared", "scaling_1"])
def test_the_check_fails_every_control(tiny_server, control, by):
    """The reference below the configuration's precision, or with a term
    left out, through the cell's own finish(): not correct."""
    read, problems, log = _finish(tiny_server, control)
    assert any(p.startswith(by + " off the reference") for p in problems), \
        (read, problems)


def test_the_check_judges_the_largest_position_and_every_cached_row(
        tiny_server):
    """One wrong position of 64, one cached row of one layer, one expert
    id: each moves its figure, whatever the others read."""
    import jax
    from benchmarks.kinds import serve_mla_backlog as kind
    from benchmarks.lib import lm, lm_mla
    from benchmarks.reference import pangu_ultra_ep16 as ref
    cfg, model, srv = tiny_server
    check = tiny_traffic()["logit_check"]
    steps = check["positions"]
    seqs, plen = kind.check_sequences(srv.runtime, cfg["vocab_size"], 7,
                                      check)
    assert plen[0] == 4 and plen[-1] == srv.runtime.max_src_len
    weights, dims = lm_mla.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,))

    def forward(tokens, n, routing):
        return jitted(weights, dims, tokens, n, routing)

    got = kind.program_readings(srv, seqs, plen, steps)
    assert srv.pool.in_use() == 0
    end = plen[0] + steps - 1
    assert (got["routing"][0, :, end:] == -1).all()
    assert (got["routing"][0, 0] == -1).all()          # the dense layer
    assert (got["routing"][0, 1, :end] >= 0).all()
    # prefill runs no experts in the last layer: nothing reads them
    assert (got["routing"][0, -1, :plen[0] - 1] == -1).all()
    assert (got["routing"][0, -1, plen[0] - 1:end] >= 0).all()
    assert len(got["latent"]) == 3 and got["latent"][0].shape[-1] == 32
    assert not got["latent"][2][0, end:].any()
    assert got["latent"][2][0, :end].all(-1).all()      # every row written

    def read(g):
        want = kind.reference_readings(forward, seqs, plen, steps,
                                       g["routing"])
        return kind.figures(g, want, model.spec.kv_rank)

    clean = read(got)
    assert max(clean[k] for k in ("logits", "latent_c", "latent_rope")) \
        < 1e-4
    assert clean["routing"] == 0.0
    bad = dict(got, logits=got["logits"].copy())
    bad["logits"][2, 5] += 1.0                    # one position of 64
    r = read(bad)
    assert r["logits"] > 0.05 and r["logits_mid"] < 1e-4
    bad = dict(got, latent=[a.copy() for a in got["latent"]])
    bad["latent"][1][3, 17, :24] = bad["latent"][1][3, 16, :24]  # one row
    r = read(bad)
    assert r["latent_c"] > 0.1 and r["latent_rope"] < 1e-4
    bad = dict(got, latent=[a.copy() for a in got["latent"]])
    bad["latent"][2][0, 3, 24:] *= -1.0
    r = read(bad)
    assert r["latent_rope"] > 0.1 and r["latent_c"] < 1e-4
    bad = dict(got, routing=got["routing"].copy())
    bad["routing"][3, 2, 4, 0] = (bad["routing"][3, 2, 4].max() + 1) % 8
    r = read(bad)        # another expert than it chose: the reference,
    assert r["routing"] > 0 or r["logits"] > 1e-3   # forced on it, moves
