"""HLO fusion/collective budget gate wired into tier-1 (ISSUE 11; same
pattern as test_check_dispatch): the captured step's optimized-HLO
structure holds replicated AND under the (2,2) shard plan (collective
mix exactly the rule-derived budget, every donated buffer aliased), the
serve executables hold their bands, and a deliberately de-fused control
trips the gate — so an HLO regression fails CI instead of silently
costing chip time."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_fusion  # noqa: E402


def test_fusion_budgets_hold_and_control_trips():
    res = check_fusion.run()
    assert res["ok"], res["errors"]
    # replicated captured step: one executable, no collectives, every
    # donated param/state buffer aliased in place
    assert res["captured"]["collective_total"] == 0
    assert res["captured"]["aliased_inputs"] == \
        check_fusion.BUDGETS["captured_step"]["aliased_inputs"]
    lo, hi = check_fusion.BUDGETS["captured_step"]["fusions"]
    assert lo <= res["captured"]["fusions"] <= hi
    # conftest forks 8 CPU devices, so the (2,2) shard phase really ran
    assert res["shard_mesh"] is True
    assert res["sharded"]["collectives"] == \
        check_fusion.BUDGETS["sharded_step"]["collectives"]
    assert res["sharded_kinds_consistent"] is True
    # serve: both executables inside budget, decode compiled exactly once
    assert res["serve_decode"]["collective_total"] == 0
    assert res["serve_decode_traces"] == 1
    # ISSUE 12: the widened speculative-verify executable holds its
    # fusion AND copy bands, keeps both page pools donated in place,
    # and compiled exactly once across varying draft acceptance
    lo, hi = check_fusion.BUDGETS["serve_verify"]["fusions"]
    assert lo <= res["serve_verify"]["fusions"] <= hi
    clo, chi = check_fusion.BUDGETS["serve_verify"]["copies"]
    assert clo <= res["serve_verify"]["copies"] <= chi
    assert res["serve_verify"]["aliased_inputs"] == 2
    assert res["serve_verify"]["collective_total"] == 0
    assert res["serve_verify_traces"] == 1
    # ISSUE 14: the quantized-serve executables — int8 KV pages with
    # per-page scales + per-channel int8 weights — hold their fusion
    # and copy bands (dequant fused into the dots, not a copy pass) and
    # keep all FOUR donated pool buffers (pages + scales) aliased
    for name in ("serve_decode_int8", "serve_verify_int8"):
        lo, hi = check_fusion.BUDGETS[name]["fusions"]
        assert lo <= res[name]["fusions"] <= hi
        clo, chi = check_fusion.BUDGETS[name]["copies"]
        assert clo <= res[name]["copies"] <= chi
        assert res[name]["aliased_inputs"] == 4
        assert res[name]["collective_total"] == 0
    assert res["serve_int8_traces"] == 2
    # ISSUE 15: the sharded-embedding step — the sparse fast path costs
    # EXACTLY 2 all-to-alls per table (bucketed index exchange + vector
    # return; 2 tables in the fixture), the pin agrees with the
    # exchange math, and the donated tables alias in place
    from mxnet_tpu.shard import embedding as semb
    assert res["sharded_embed"]["collectives"]["all-to-all"] == \
        check_fusion.BUDGETS["sharded_embed_step"]["all_to_all"] == \
        semb.A2A_PER_TABLE * 2
    assert res["sharded_embed_a2a_consistent"] is True
    assert res["sharded_embed"]["aliased_inputs"] == 4
    # ISSUE 16: the expert-parallel MoE step — dispatch + combine cost
    # EXACTLY A2A_PER_LAYER per traversal, forward and backward (the
    # banks sit inside the vjp), 2 layers in the fixture; the pin
    # agrees with the routing constants in-process
    from mxnet_tpu.shard import moe as smoe
    assert res["moe"]["collectives"]["all-to-all"] == \
        check_fusion.BUDGETS["moe_step"]["all_to_all"] == \
        smoe.A2A_PER_LAYER * smoe.STEP_TRAVERSALS * 2
    assert res["moe_a2a_consistent"] is True
    assert res["moe"]["aliased_inputs"] == \
        check_fusion.BUDGETS["moe_step"]["aliased_inputs"]
    # the gate provably bites: the fusion-pass-disabled control landed
    # outside the band and tripped the SAME budget table (the installed
    # XLA leaves each un-fused op in a fusion of its own, so the count
    # leaves the band at the top, not at zero)
    assert res["control_tripped"] is True
    lo, hi = check_fusion.BUDGETS["captured_step"]["fusions"]
    assert not lo <= res["control_fusions"] <= hi


def test_sharded_collectives_match_rule_derived_expectation():
    """Plan vs no-plan HLO counting: the (2,2) sharded step's collective
    count changes exactly as the rules predict (0 -> the pinned
    rule-derived mix); mirrors the check_dispatch shard-phase skip
    below 4 devices."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices for a (2,2) mesh")
    os.environ["MXTPU_HLO_TELEMETRY"] = "always"
    try:
        plain, _, _, _ = check_fusion.captured_step_info(sharded=False)
        sharded, _, plan, params = \
            check_fusion.captured_step_info(sharded=True)
    finally:
        os.environ["MXTPU_HLO_TELEMETRY"] = "auto"
    assert plain["collective_total"] == 0
    budget = check_fusion.BUDGETS["sharded_step"]["collectives"]
    assert sharded["collectives"] == budget
    assert sharded["collective_total"] == sum(budget.values())
    # the pinned mix stays consistent with what the rules imply
    kinds = check_fusion.expected_collective_kinds(plan, params)
    assert kinds <= set(sharded["collectives"])


def test_every_framework_executable_reports_compile_and_hlo_series():
    """ISSUE 11 acceptance: after one warm run of each, the metrics
    snapshot carries compile_seconds AND hlo_fusions for the captured
    step, sharded step, serve prefill/decode and the bucket kernels.

    The captured/sharded/serve executables already compiled (inspected)
    in this file's gate test above — the registry is process-global and
    tier-1 pins file order (-p no:randomly), so only the bucket-kernel
    and cached-backward executables still need a warm run here."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.observability import registry

    def _have():
        snap = registry().snapshot()
        sets = []
        for family in ("compile_seconds", "hlo_fusions"):
            sets.append({dict(s["labels"]).get("executable")
                         for s in snap.get(family, [])})
        return sets[0] & sets[1]

    os.environ["MXTPU_HLO_TELEMETRY"] = "always"
    try:
        # standalone safety net: (re)compile only what this process has
        # not already inspected
        have = _have()
        if "captured_step" not in have:
            check_fusion.captured_step_info(sharded=False, steps=1)
        if "sharded_step" not in have and len(jax.devices()) >= 4:
            check_fusion.captured_step_info(sharded=True, steps=1)
        if not {"serve_decode", "serve_prefill"} <= have:
            check_fusion._serve_infos()
        # bucket kernels + the cached jitted backward via a short fused
        # imperative loop (the backward cache compiles after repeats)
        rng = np.random.RandomState(0)
        X = nd.array(rng.randn(8, 16).astype(np.float32))
        y = nd.array(rng.randint(0, 4, 8).astype(np.float32))
        lossf = gluon.loss.SoftmaxCrossEntropyLoss()
        mx.random.seed(0)
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(X)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        for _ in range(autograd._VJP_COMPILE_AFTER + 1):
            with autograd.record():
                L = lossf(net(X), y).mean()
            L.backward()
            tr.step(8)
    finally:
        os.environ["MXTPU_HLO_TELEMETRY"] = "auto"

    snap = registry().snapshot()
    want = {"captured_step", "serve_prefill", "serve_decode",
            "fused_update", "autograd_backward"}
    if len(jax.devices()) >= 4:
        want.add("sharded_step")
    for family in ("compile_seconds", "hlo_fusions"):
        have = {dict(s["labels"]).get("executable")
                for s in snap.get(family, [])}
        missing = want - have
        assert not missing, f"{family} missing executables: {missing}"
    # compile_seconds snapshots expose the p95 the profiler reports
    for s in snap["compile_seconds"]:
        if dict(s["labels"]).get("executable") in want:
            assert "p95" in s["value"] and s["value"]["count"] >= 1


def test_hlo_counting_handles_tpu_layout_annotations():
    """inspect_hlo_text must count instructions whose shapes carry TPU
    layout/tiling and memory-space annotations (`{1,0:T(8,128)S(1)}`) —
    the exact platform this telemetry exists for — and still keep the
    async -start/-done convention."""
    from mxnet_tpu.observability.compilex import inspect_hlo_text

    txt = """HloModule jit_step, input_output_alias={ {0}: (1, {}, may-alias) }
  %p0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %f.1 = bf16[8,128]{1,0:T(8,128)S(1)} fusion(%p0), kind=kLoop
  %ar = bf16[8,128]{1,0:T(8,128)} all-reduce-start(%f.1), replica_groups={}
  %ard = bf16[8,128]{1,0:T(8,128)} all-reduce-done(%ar)
  %cp = bf16[8,128]{1,0} copy(%ard)
  %ag = bf16[16,128]{1,0:T(8,128)} all-gather(%cp), dimensions={0}
"""
    info = inspect_hlo_text(txt)
    assert info["fusions"] == 1
    assert info["collectives"] == {"all-reduce": 1, "all-gather": 1}
    assert info["copies"] == 1
    assert info["aliased_inputs"] == 1


def test_check_fusion_cli_smoke():
    assert callable(check_fusion.main)
    assert set(check_fusion.BUDGETS) == {
        "captured_step", "sharded_step", "sharded_embed_step",
        "moe_step", "serve_decode", "serve_prefill",
        "serve_verify", "serve_decode_int8", "serve_verify_int8"}
