"""The `serve_swa_backlog` kind on a tiny configuration on the CPU (window
32, pages of 16, a ring of 3 pages a slot), through its own run(), its
check against the plain reference past two laps of the ring with every
control, and the readers of the per-layer metrics that come with it (the
command line still refuses a non-TPU backend: test_harness.py)."""
import time

import numpy as np
import pytest

from benchmarks.lib import harness, lm_flops

ROOT = harness.ROOT
SEED = 3800000043
CONFIG = "benchmarks/configs/mellum2_12b_l8.json"


def tiny_config():
    cfg = harness.load_json(ROOT, CONFIG)
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, vocab_size=200, moe_intermediate_size=32,
               num_experts=8, router_width=8, experts_held=[0, 8],
               num_experts_per_tok=2, sliding_window=32,
               param_dtype="float32")
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=64)
    cfg["server"].update(slots=4, page_size=16, max_prompt_len=100,
                         max_new_tokens=16)
    return cfg


def tiny_traffic():
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "code_ctx_backlog.json")
    traffic["lengths"].update(prompt_median=40, prompt_clip=[4, 100],
                              out_median=6, out_clip=[2, 16])
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0)
    traffic["logit_check"].update(prompt_from=10)
    traffic["logit_check"]["limits"].update(logits=1e-4, routing=1e-4,
                                            ring=1e-4, pages=1e-4)
    return traffic


def test_the_configuration_keeps_every_published_width():
    cfg = harness.load_json(ROOT, CONFIG)
    from benchmarks.lib import lm_swa
    spec = lm_swa.spec_of(cfg)
    assert (spec.hidden, spec.heads, spec.kv_heads, spec.head_dim) \
        == (2304, 32, 4, 128)
    assert (spec.num_experts, spec.top_k, spec.expert_width) == (64, 8, 896)
    assert (spec.held_lo, spec.held_n, spec.scaling) == (0, 64, 1.0)
    assert (spec.router_score, spec.shared_expert, spec.router_bias,
            spec.attn_gate, spec.paired, spec.eps) \
        == ("softmax", False, False, False, True, 1e-6)
    assert (spec.window, spec.attn_rope, spec.rope_theta) \
        == (1024, True, 500000.0)
    assert spec.rope_yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert spec.pattern == ("swa", "swa", "swa", "gqa") * 2
    assert cfg["layer_types"][:8] == ["sliding_attention"] * 3 \
        + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 28
    assert cfg["reduced"] == ["layers_held"]
    assert cfg["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["vocab_rows_held"] == [0, cfg["vocab_size"]]
    # every expert sees 16 rows a turn
    assert cfg["server"]["slots"] * 8 // 64 == 16
    assert cfg["server"]["prefix_cache"] is False
    # every number of the catalog row's config, at its published value
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "moe_intermediate_size": 896, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "sliding_window": 1024, "vocab_size": 98304}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}
    for item in ("router", "qk_norm", "rotation", "yarn", "window",
                 "unused_keys", "mtp", "dtype", "init", "eos_id", "slots"):
        assert item in cfg["assumed"]


def test_corpus_is_a_fixed_set_reordered_by_the_seed():
    from benchmarks.kinds import serve_swa_backlog as kind
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "code_ctx_backlog.json")
    a = kind.corpus(traffic, 1, 98304)
    b = kind.corpus(traffic, SEED, 98304)
    assert sorted((len(p), o) for p, o in a) \
        == sorted((len(p), o) for p, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    plen = np.array([len(p) for p, _ in a])
    out = np.array([o for _, o in a])
    assert plen.min() >= 256 and plen.max() == 4096
    assert out.min() >= 64 and out.max() == 1024
    assert 1400 < np.median(plen) < 1700 and 470 < np.median(out) < 555
    # three prompts in four are longer than the window
    assert 0.68 < (plen > 1024).mean() < 0.82
    assert all(p.min() >= 4 and p.max() < 98304 for p, _ in a[:64])


def test_serve_swa_backlog_runs_a_tiny_configuration():
    import jax
    from benchmarks.kinds import serve_swa_backlog as kind
    from benchmarks.metrics import (decode_turn_ms, expert_tokens_cv,
                                    gmm_roofline, rpa_flat_roofline,
                                    rpa_ring_roofline, swa_share_pct)
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
    assert rows.shape == (8, 8) and rows.sum(1).all()
    assert all(d >= c["decode_turns"] for d in moe["dispatches"])
    # a prefill stops after the last attention: no last expert layer
    assert moe["dispatches"][7] < moe["dispatches"][6]
    ring = c["window_ring"]
    assert ring["turns"] >= c["decode_turns"]
    # a slot reads at most the window, at least its one position
    assert ring["turns"] <= ring["ring_tokens"] <= ring["turns"] * 4 * 32
    assert c["slice_ring"]["turns"] > 0
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
    for reader in (swa_share_pct, rpa_ring_roofline, rpa_flat_roofline,
                   gmm_roofline):
        assert reader.reduce(ts.events, ts.spans, c, info) is None


def test_readers_return_nothing_where_the_program_has_no_counter():
    """What the parent gives them: no scope of this name, no kernel of
    this name, no window counter."""
    from benchmarks.metrics import rpa_ring_roofline, swa_share_pct
    info = {"window": (0, 10 ** 9), "config": tiny_config(), "chips": 1,
            "device": {"kind": "TPU v5 lite"}}
    spans = [("serve.decode_step", 0.0, 5.0, {"active": 3})]
    events = [("/device:TPU:0", "XLA Ops", "mxtpu_rpa_ring.1", 0.0, 1e6)]
    for reader in (rpa_ring_roofline, swa_share_pct):
        assert reader.reduce([], spans, {"decode_turns": 3}, info) is None
    assert rpa_ring_roofline.reduce(events, spans, {"decode_turns": 3},
                                    info) is None


def test_the_new_readers_on_a_synthetic_trace(monkeypatch):
    """A device plane with two decode runs and a prefill run between
    them: the ring kernel's calls and time against the counter's keys,
    and the window layers' share over BOTH programs, each op joined in
    its own program's map."""
    from benchmarks.lib import program_share as ps, trace_reduce as tr
    from benchmarks.metrics import (rpa_flat_roofline, rpa_ring_roofline,
                                    swa_share_pct)
    from mxnet_tpu.observability import compilex
    dev, ms = "/device:TPU:0", 1e6
    events = [
        (tr.HOST_PLANE, "main", tr.WINDOW, 0.0, 140 * ms),
        (dev, tr.MODULES, "jit__decode_program(1)", 0, 40 * ms),
        (dev, tr.MODULES, "jit__prefill_program(2)", 40 * ms, 60 * ms),
        (dev, tr.MODULES, "jit__decode_program(1)", 100 * ms, 40 * ms),
        (dev, tr.OPS, "fusion.7", 0, 4 * ms),                  # mx_swa
        (dev, tr.OPS, "mxtpu_rpa_ring.1", 4 * ms, 3 * ms),
        (dev, tr.OPS, "mxtpu_rpa_ring.2", 7 * ms, 3 * ms),
        (dev, tr.OPS, "mxtpu_rpa_flat.1", 10 * ms, 8 * ms),    # mx_gqa
        (dev, tr.OPS, "fusion.7", 50 * ms, 30 * ms),   # prefill's: mx_norm
        (dev, tr.OPS, "mxtpu_flash_fwd.3", 80 * ms, 10 * ms),  # mx_swa_seq
        (dev, tr.OPS, "fusion.7", 100 * ms, 4 * ms),
        (dev, tr.OPS, "mxtpu_rpa_ring.1", 104 * ms, 3 * ms),
        (dev, tr.OPS, "mxtpu_rpa_ring.2", 107 * ms, 3 * ms),
        (dev, tr.OPS, "mxtpu_rpa_flat.1", 110 * ms, 8 * ms)]
    monkeypatch.setattr(compilex, "_inspections", {
        "serve_lm_decode": {
            "module": "jit__decode_program",
            "op_scopes": {"fusion.7": ("mx_swa",),
                          "mxtpu_rpa_ring.1": ("mx_swa",),
                          "mxtpu_rpa_ring.2": ("mx_swa",),
                          "mxtpu_rpa_flat.1": ("mx_gqa",)},
            "op_names": {}},
        "serve_lm_prefill": {
            "module": "jit__prefill_program",
            "op_scopes": {"fusion.7": ("mx_norm",),
                          "mxtpu_flash_fwd.3": ("mx_swa_seq",)},
            "op_names": {}}})
    monkeypatch.setattr(ps, "_last", [None, None, None])
    cfg = harness.load_json(ROOT, CONFIG)
    info = {"window": (0, 140 * ms), "config": cfg, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "workload": "synthetic"}
    # busy 2 x 18 + 40 = 76 ms; mx_swa 2 x 10 ms, mx_swa_seq 10 ms; the
    # prefill's fusion.7 has the decode program's name and another scope
    assert swa_share_pct.reduce(events, [], {}, info) \
        == pytest.approx(100 * 30 / 76)
    # 128 slots that each read a full window
    ring = {"turns": 2, "ring_tokens": 2 * 128 * 1024}
    ops, nbytes = lm_flops.rpa_decode_cost(128 * 1024, 128, 32, 4, 128)
    assert nbytes == (2 * 128 * 1024 * 512 + 2 * 128 * 4096) * 2
    least = nbytes / 819e9                           # memory-bound
    assert ops / 197e12 < least
    got = rpa_ring_roofline.reduce(events, [], {"slice_ring": ring}, info)
    assert got == pytest.approx(100 * 4 * least / 12e-3, rel=1e-3)
    # the full layers' kernel keeps its own name and its own reader: the
    # ring kernel's name does not contain it
    spans = [("serve.decode_step", 0.0, 5.0,
              {"active": 128, "cached_tokens": 128 * 2100})]
    flat = rpa_flat_roofline.reduce(events, spans, {}, info)
    ops, nbytes = lm_flops.rpa_decode_cost(128 * 2101, 128, 32, 4, 128)
    assert flat == pytest.approx(100 * 2 * (nbytes / 819e9) / 16e-3,
                                 rel=1e-3)


# ------------------------------------------- the check against the reference
@pytest.fixture(scope="module")
def tiny_server():
    from benchmarks.lib import lm_swa
    cfg = tiny_config()
    model, srv = lm_swa.build_server(cfg, SEED, 8)
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
    from benchmarks.kinds import serve_swa_backlog as kind
    cfg, model, srv = tiny_server
    problems, log = [], []
    read = kind.finish(srv, model, cfg, tiny_traffic(), SEED, [],
                       log.append, problems, control=control)
    return read, problems, log


def test_the_check_passes_the_program_past_two_laps(tiny_server):
    cfg, model, srv = tiny_server
    rt = srv.runtime
    assert rt.ring == 3 and rt.ring * rt.page_size == 48
    assert [k.shape for k, _ in rt.ring_pages] == [(4 * 3, 16, 32)] * 6
    assert len(rt.kv_pages) == 2
    read, problems, log = _finish(tiny_server, None)
    assert problems == [], log
    assert read["routing"] == 0.0
    assert 0 < read["logits"] < 1e-4
    assert 0 < read["ring"] < 1e-4 and 0 < read["pages"] < 1e-4
    assert rt.decode_traces == rt.prefill_traces == 1


@pytest.mark.parametrize("control,by", [
    ({"low": "all"}, "logits"), ({"leave_out": "rotation"}, "ring"),
    ({"leave_out": "yarn"}, "pages"), ({"leave_out": "attn_factor"},
                                       "pages"),
    ({"leave_out": "window"}, "logits"), ({"leave_out": "sigmoid"},
                                          "logits"),
    ({"leave_out": "renorm"}, "logits")],
    ids=["low_all", "no_rotation", "plain_table_on_full", "attn_factor_1",
         "no_window", "sigmoid_for_softmax", "no_renorm"])
def test_the_check_fails_every_control(tiny_server, control, by):
    """The reference below the configuration's precision, or with a term
    left out, through the cell's own finish(): not correct."""
    read, problems, log = _finish(tiny_server, control)
    assert any(p.startswith(by + " off the reference") for p in problems), \
        (read, problems)


def test_the_check_reads_the_ring_in_position_order(tiny_server):
    """One wrong position of 64, one ring row, one page row: each moves
    its figure alone; the request under the window has not wrapped, the
    others have lapped the ring twice."""
    import jax
    from benchmarks.kinds import serve_swa_backlog as kind
    from benchmarks.lib import lm, lm_swa
    from benchmarks.reference import mellum2_12b_l8 as ref
    cfg, model, srv = tiny_server
    check = tiny_traffic()["logit_check"]
    steps = check["positions"]
    seqs, plen = kind.check_sequences(srv.runtime, cfg["vocab_size"], SEED,
                                      check)
    assert plen[0] + steps - 1 < 32 and plen[-1] == 100 > 2 * 48
    weights, dims = lm_swa.reference_weights(model), lm.dims(model.spec)
    jitted = jax.jit(ref.forward, static_argnums=(1,),
                     static_argnames=("head_rows",))

    def forward(tokens, head_from, routing):
        return jitted(weights, dims, tokens, None, routing,
                      head_from=head_from, head_rows=steps)

    spec = model.spec
    got = kind.program_readings(srv, seqs, plen, steps)
    assert srv.pool.in_use() == 0
    assert len(got["keys"]) == 8 and got["keys"][0].shape == (4, 115, 32)
    end = plen[-1] + steps - 1
    # a window layer's ring gives the last 32 positions and no other
    assert not got["keys"][0][3, :end - 32].any()
    assert got["keys"][0][3, end - 32:end].all()
    assert got["keys"][3][3, :end].all()          # a full layer: all

    def read(g):
        want = kind.reference_readings(forward, seqs, plen, steps,
                                       spec.window, spec.pattern,
                                       g["routing"])
        return kind.figures(g, want, spec.pattern)

    clean = read(got)
    assert max(clean[k] for k in ("logits", "ring", "pages")) < 1e-4
    assert clean["routing"] == 0.0
    bad = dict(got, logits=got["logits"].copy())
    bad["logits"][2, 5] += 1.0                    # one position of 64
    r = read(bad)
    assert r["logits"] > 0.05 and r["logits_mid"] < 1e-4
    bad = dict(got, keys=[k.copy() for k in got["keys"]])
    bad["keys"][1][3, end - 32:end] = np.roll(
        bad["keys"][1][3, end - 32:end], 1, 0)    # the ring off by a row
    r = read(bad)
    assert r["ring"] > 0.1 and r["pages"] < 1e-4
    bad = dict(got, keys=[k.copy() for k in got["keys"]])
    bad["keys"][7][0, 3] *= 0.5                   # one row of one page
    r = read(bad)
    assert r["pages"] > 0.05 and r["ring"] < 1e-4
