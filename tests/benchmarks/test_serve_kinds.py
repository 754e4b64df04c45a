"""The two serving kinds' runners on a tiny configuration on the CPU,
through their own run() (the command line still refuses a non-TPU
backend: test_harness.py)."""
import time

import pytest

from benchmarks.lib import harness, serving

ROOT = harness.ROOT


def _ctx(traffic_name, trace):
    import jax
    cfg = harness.load_json(ROOT, "benchmarks/configs/nmt_base.json")
    cfg.update(d_model=64, encoder_layers=2, decoder_layers=2,
               attention_heads=2, ffn_dim=128, vocab_size=200)
    cfg["server"].update(slots=4, page_size=8)
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                traffic_name + ".json")
    traffic.update(warm_s=0.5, trace_after_s=0.1, trace_s=1.0,
                   rate_rps=30.0, lead_s=0.5)
    traffic["logit_check"]["tolerance"] = 1e-5   # float32 on the CPU
    harness.CompileWatch.install()
    log = []
    return {"cell": {"name": "tiny", "chips": 1}, "config": cfg,
            "traffic": traffic, "seed": 3000000019, "seconds": 1.5,
            "trace": trace, "say": log.append,
            "t_start": time.perf_counter(),
            "device": {"kind": "TPU v5 lite"},
            "devices": jax.devices()}, log


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


def test_serve_backlog_runs_a_tiny_configuration(interpret):
    from benchmarks.kinds import serve_backlog
    from benchmarks.metrics import decode_turn_ms
    ctx, log = _ctx("wmt_backlog", trace=True)
    out = serve_backlog.run(ctx)
    assert out["problems"] == [], (out["problems"], log)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    c = out["counters"]
    assert c["window"]["compilations"] == 0 and c["decode_turns"] > 0
    ts = out["trace"]
    per_turn = decode_turn_ms.reduce(ts.events, ts.spans, c,
                                     {"window": ts.window})
    assert 0 < per_turn < 1e3 * 1.0 * 1.5


def test_serve_open_loop_runs_a_tiny_configuration(interpret):
    from benchmarks.kinds import serve_open_loop
    from benchmarks.metrics import decode_step_ms, prefill_ms
    ctx, log = _ctx("wmt_steady", trace=True)
    out = serve_open_loop.run(ctx)
    assert out["problems"] == [], (out["problems"], log)
    # 30 requests a second for 1.5 s, give or take the Poisson draw
    assert 20 <= out["attempted"] <= 80 and out["failed"] == 0
    assert out["end_to_end"]["serve_ttft_p95_ms"] > 0
    assert out["end_to_end"]["serve_tpot_p95_ms"] > 0
    assert out["counters"]["window"]["compilations"] == 0
    ts = out["trace"]
    info = {"window": ts.window}
    assert prefill_ms.reduce(ts.events, ts.spans, {}, info) > 0
    assert decode_step_ms.reduce(ts.events, ts.spans, {}, info) > 0
    assert any("the collector ran" in line for line in log)


def test_settled_heap_freezes_what_is_there_and_logs_what_comes():
    import gc
    junk = [[i] for i in range(1000)]            # what set-up built
    pauses, before = [], list(gc.callbacks)
    with serving.settled_heap(pauses):
        assert gc.get_freeze_count() >= len(junk)
        ring = []
        ring.append(ring)                        # garbage made while it runs
        del ring
        assert gc.collect() >= 1                 # is still collected
    assert gc.get_freeze_count() == 0 and gc.callbacks == before
    assert (2, pauses[-1][1]) == pauses[-1] and 0 < pauses[-1][1] < 1.0


def test_every_seed_draws_the_same_sizes_in_another_order():
    traffic = harness.load_json(ROOT, "benchmarks/traffic/wmt_steady.json")
    sizes = lambda c: sorted((len(s), o) for s, o in c)   # noqa: E731
    a = serving.corpus(traffic, 1, 36548)
    b = serving.corpus(traffic, 3000000019, 36548)
    assert sizes(a) == sizes(b)
    assert [len(s) for s, _ in a] != [len(s) for s, _ in b]
    lens = sorted(len(s) for s, _ in a)
    assert lens[0] >= 4 and lens[-1] <= 128
    assert 22 <= lens[len(lens) // 2] <= 30           # median 26
    assert all(4 <= o <= 128 for _, o in a)
    # an open-loop window: the same number of arrivals, the same gaps and
    # the same requests in it whatever the seed
    from benchmarks.kinds import serve_open_loop
    cfg = {"vocab_size": 36548}
    lead, rate = traffic["lead_s"], traffic["rate_rps"]
    seen = []
    for seed in (1, 2, 3000000019):
        reqs, dues = serve_open_loop.schedule(cfg, traffic, seed, 10.0)
        inside = [i for i, d in enumerate(dues) if lead <= d < lead + 10.0]
        assert len(inside) == round(rate * 10.0)
        assert inside[0] == round(rate * lead)
        gaps = sorted(round(g, 9) for g in
                      (dues[inside] - dues[[i - 1 for i in inside]]))
        seen.append((sizes([reqs[i] for i in inside]), gaps,
                     [len(reqs[i][0]) for i in inside]))
        assert abs(dues[inside[-1]] - dues[inside[0] - 1] - 10.0) < 1e-9
    assert seen[0][:2] == seen[1][:2] == seen[2][:2]
    assert seen[0][2] != seen[1][2]
    with pytest.raises(ValueError):
        serving.corpus(traffic, 1, 36548, blocks=(4000, 200))
    assert serving.percentile([1, 2, 3, 4], 50) == 2
    assert serving.percentile(list(range(1, 101)), 95) == 95


class _Handle:
    """What `tokens_in_whole_turns` reads of a finished request."""
    state = "done"

    def __init__(self, first_turn, n, turn=0.05, lag=0.0004):
        self.t_first_token = first_turn * turn
        self.t_done = (first_turn + n - 1) * turn + lag
        self.tokens = [0] * n


def test_tokens_in_whole_turns_counts_exactly():
    # three slots always busy for turns 1..60: 3 tokens a turn, with
    # requests of length 1 (first token and completion in one turn), long
    # ones across both edges, and turn 31 without any first token
    hs = [_Handle(1, 60)]
    hs += [_Handle(t, 1) for t in range(1, 30)] + [_Handle(30, 2)]
    hs += [_Handle(t, 1) for t in range(32, 61)]
    hs += [_Handle(1, 29), _Handle(30, 31)]
    assert sum(len(h.tokens) for h in hs) == 180
    # opening edge at the end of turn 10 (0.5 s), 1 s of window: turns
    # 11..30, wherever in turn 10-11 the clock happened to be
    for t0 in (0.4501, 0.47, 0.4999, 0.5):
        tokens, span = serving.tokens_in_whole_turns(hs, t0, 1.0)
        assert tokens == 60 and span == pytest.approx(1.0, abs=1e-3)
    # a turn later, the same: a rate over whole turns does not depend on
    # where the clock cut
    assert serving.tokens_in_whole_turns(hs, 0.5201, 1.0)[0] == 60
    with pytest.raises(RuntimeError):
        serving.tokens_in_whole_turns(hs, 2.9, 1.0)
