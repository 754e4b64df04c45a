"""Graft-lint gate wired into tier-1 (ISSUE 13; same pattern as
test_check_dispatch / test_check_fusion): zero non-baselined findings
at HEAD, every AST and graph rule demonstrably fires on its seeded
control, and MXTPU-E01 runs baseline-free — so a static regression (a
raw env parse, a swallowed cancellation, a donation leak, a dead collective)
fails CI instead of costing a landing-pass review cycle."""
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
import check_static  # noqa: E402


def test_static_gate_clean_at_head_and_controls_fire():
    res = check_static.run()
    assert res["ok"], res["errors"]
    # zero NEW findings: HEAD carries only baselined/suppressed
    # acceptances, each with a one-line justification in
    # tools/static_baseline.json
    assert res["ast_new"] == []
    assert res["graph_new"] == []
    # every AST rule + the suppression machinery fired on its seeded
    # violation — the gate provably measures something
    assert set(res["ast_controls"]) == set(
        list(check_static.AST_CONTROLS) + ["suppression"])
    assert all(res["ast_controls"].values())
    # every graph rule fired on its control (text fixtures for the pure
    # analyzers, live jax programs for donation + strong consts)
    assert {"MXTPU-G01", "MXTPU-G02", "MXTPU-G03-dup", "MXTPU-G03-dead",
            "MXTPU-G04", "MXTPU-G05"} == set(res["graph_controls"])
    assert all(res["graph_controls"].values())
    # the graph phase linted the framework's REAL executables
    want = {"captured_step", "serve_prefill", "serve_decode",
            "serve_verify", "serve_page_remap", "fused_update",
            "autograd_backward"}
    if len(jax.devices()) >= 4:   # tier-1 conftest forks 8
        want.add("sharded_step")
    assert want <= set(res["graph_executables"]), \
        res["graph_executables"]


def test_e01_is_baseline_free_by_construction():
    """The acceptance pin: zero raw numeric env parses remain in
    mxnet_tpu/ (all routed through _env.py), and the baseline file is
    FORBIDDEN from ever parking an E01 finding."""
    from mxnet_tpu.analysis import astlint

    findings, _ = astlint.lint_tree(astlint.package_root())
    e01 = [f for f in findings if f.rule == "MXTPU-E01"
           and not f.suppressed]
    assert e01 == [], [str(f) for f in e01]
    baseline = astlint.load_baseline(check_static.BASELINE_PATH)
    assert all(e["rule"] != "MXTPU-E01" for e in baseline["ast"])


def test_baseline_entries_all_carry_justifications():
    from mxnet_tpu.analysis import astlint

    baseline = astlint.load_baseline(check_static.BASELINE_PATH)
    for e in baseline["ast"] + baseline["graph"]:
        assert e.get("why", "").strip(), e


def test_static_row_lands_in_profiler_dumps():
    """ISSUE 13 satellite: after a gate run (the first test in this
    file; tier-1 pins file order), profiler.dumps() surfaces the
    [static] drift row."""
    from mxnet_tpu import profiler
    from mxnet_tpu.observability import registry

    if not any(g.value for g in registry().series("static_rules_run")):
        check_static.run(graph=False)    # standalone safety net
    out = profiler.dumps()
    assert "[static]" in out
    line = next(ln for ln in out.splitlines() if ln.startswith("[static]"))
    assert "rules=" in line and "baseline=" in line and "new=0" in line


def test_check_static_cli_smoke():
    assert callable(check_static.main)
    assert set(check_static.AST_CONTROLS) == {
        "MXTPU-E01", "MXTPU-E02", "MXTPU-E03", "MXTPU-E04", "MXTPU-E05",
        "MXTPU-E06"}
