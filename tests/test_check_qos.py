"""Engine QoS gate wired into tier-1 (ISSUE 7 acceptance): mixed
serve+train load with injected faults and mid-flight group cancellation
must show zero decode-class turns starved past the aging bound, bitwise-
stable decode output, and zero leaked KV pages / task groups / staging
slots (same pattern as chaos_check / check_dispatch / check_trace)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_qos  # noqa: E402


def test_qos_fairness_and_chaos_soak():
    res = check_qos.run()
    assert res["ok"], res["errors"]
    # both engine implementations passed the deterministic fairness pin
    assert set(res["fairness_engines"]) >= {"py"}
    # the FIFO control PROVES the starvation bound bites: without QoS the
    # same flood blows it, with QoS zero turns cross it
    assert res["fifo_control_worst_wait_s"] > res["starve_bound_s"]
    assert res["soak_starved_turns"] == 0
    assert res["soak_probe_turns"] > 0
    assert res["decode_dispatch_p99_s"] < res["starve_bound_s"]
    # leak gates: pages, groups (staging depth asserted inside run())
    assert res["soak_leaked_pages"] == 0
    assert res["soak_live_groups"] == 0


def test_check_qos_cli_smoke():
    assert callable(check_qos.main)
    assert check_qos.STARVE_BOUND_S > 0


def test_background_load_raises_when_its_producer_died():
    """A flood whose producer thread died would let the soak pass its
    contention assertions vacuously: leaving the block raises instead."""
    from mxnet_tpu import engine

    def push_fails(*args, **kwargs):
        raise ValueError("push refused")

    load = check_qos.BackgroundEngineLoad(4)
    load.group.push = push_fails
    with pytest.raises(RuntimeError, match="flood thread died.*push refused"):
        with load:
            load._thread.join(timeout=10)
    assert not load._thread.is_alive()
    assert engine.active_groups() == 0
