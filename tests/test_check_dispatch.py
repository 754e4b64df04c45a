"""Captured-step dispatch budget wired into tier-1 (ISSUE 4 acceptance):
a warm captured step must stay within <=2 trainer-issued dispatches and
match the imperative path's numerics (same pattern as chaos_check /
check_trace)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_dispatch  # noqa: E402


def test_captured_dispatch_budget_and_parity():
    res = check_dispatch.run(steps=4)
    assert res["ok"], res["errors"]
    assert res["captured_dispatches_per_step"] <= res["budget"] == 2
    # the captured step really is ONE launch in steady state
    assert set(res["captured_per_step"]) == {1}
    assert res["max_rel_dev"] < 1e-3
    # ISSUE 5: the warm-step budget also covers the input side — the
    # device prefetcher makes synchronous H2D exactly zero, and the
    # detector provably fires on the host-path control
    assert res["prefetch_sync_h2d_per_step"] == 0
    assert res["prefetch_detector_fires"] is True
    # conftest forks 8 CPU devices, so the MESH placement path is what
    # ran (the configuration where the per-step device_put used to live)
    assert res["prefetch_mesh"] is True
    # ISSUE 8: the rule-sharded (2,2) captured step stays within the
    # same budget, feeds transfer-free from the device prefetcher, and
    # genuinely shrinks per-device parameter bytes
    assert res["shard_mesh"] is True
    assert res["shard_dispatches_per_step"] <= res["budget"]
    assert res["shard_sync_h2d_per_step"] == 0
    assert res["shard_param_bytes_frac"] < 1.0
    # ISSUE 15: the sharded-embedding captured step (DLRM, vocab >>
    # batch) holds the same budget warm, stages integer index batches
    # transfer-free, shrinks per-device embedding bytes to ~1/tp, and
    # its backward temp allocation fits far under one dense (V, D)
    # table gradient — the no-O(vocab)-gradient proof
    assert res["embed_mesh"] is True
    assert res["embed_dispatches_per_step"] <= res["budget"]
    assert res["embed_sync_h2d_per_step"] == 0
    assert res["embed_param_bytes_frac"] <= 0.5 + 1e-9
    assert res["embed_backward_temp_frac"] < 1.0
    # ISSUE 16: the expert-parallel MoE captured step (Dense stem +
    # ShardedMoE on (2,2)) holds the same warm budget and stages its
    # batches transfer-free through the device prefetcher
    assert res["moe_mesh"] is True
    assert res["moe_dispatches_per_step"] <= res["budget"]
    assert res["moe_sync_h2d_per_step"] == 0
    # ISSUE 19: the TIERED embedding captured step (host-resident cold
    # rows + device hot cache, RowPrefetcher-fed) holds the same warm
    # budget on an all-hit step with ZERO synchronous H2D, and its
    # forced-miss async staging moved — bounded — row bytes
    assert res["tiered_mesh"] is True
    assert res["tiered_dispatches_per_step"] <= res["budget"]
    assert res["tiered_sync_h2d_per_step"] == 0
    assert res["tiered_async_h2d_bytes"] > 0
    # ISSUE 6: the serve decode loop is ONE dispatch per warm decode
    # turn, never retraces across varying slot occupancy, and returns
    # every KV page when the traffic drains
    assert res["serve_decode_dispatches_per_step"] <= 1
    assert res["serve_decode_retraces"] == 0
    assert res["serve_pages_leaked"] == 0
    assert res["serve_decode_steps_measured"] > 0
    # ISSUE 35: the phase's backlog (5 requests, 3 slots) looks ahead, and
    # a turn dispatched before the previous read is still ONE dispatch of
    # the one executable
    assert res["serve_lookahead_turns"] > 0
    assert res["serve_lookahead_dispatches_per_turn"] == 1
    # PR 33: a turn's admissions (several, or the 1 would be vacuous)
    # share ONE dispatch of the one prefill executable
    assert res["serve_most_admitted_in_a_turn"] >= 2
    assert res["serve_prefill_dispatches_per_admit_turn"] == 1
    assert res["serve_prefill_traces"] == 1
    # ISSUE 12: the serving fast path — speculative decode holds the
    # same one-dispatch/zero-retrace budget while draft acceptance
    # varies (and genuinely accepts drafts), the prefix cache strictly
    # reduces prefill dispatches vs the cold control while the cache-
    # disabled control shows no reduction, and refcounted pages all
    # come home
    assert res["serve_spec_dispatches_per_turn"] <= 1
    assert res["serve_spec_retraces"] == 0
    assert res["serve_spec_accept_rate"] > 0
    assert res["serve_prefix_warm_turns"] < res["serve_prefix_cold_turns"]
    assert res["serve_prefix_nocache_turns"] >= \
        res["serve_prefix_cold_turns"]
    assert res["serve_fastpath_pages_leaked"] == 0
    # ISSUE 14: the QUANTIZED serve path — int8-KV decode turns hold
    # the same one-dispatch/zero-retrace budget, a fixed HBM byte
    # budget holds >= 1.9x the fp32 pool's tokens, and the page
    # accounting stays exact at that capacity (zero leaked pages)
    assert res["serve_int8_dispatches_per_step"] <= 1
    assert res["serve_int8_retraces"] == 0
    assert res["serve_int8_capacity_ratio"] >= 1.9
    assert res["serve_int8_pages_leaked"] == 0


def test_check_dispatch_cli_smoke():
    assert callable(check_dispatch.main)
    assert check_dispatch.DISPATCH_BUDGET == 2
