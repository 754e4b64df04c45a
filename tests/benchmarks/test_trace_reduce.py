"""`benchmarks/lib/trace_reduce.py` on a hand-written list of events and
on a small excerpt recorded on the chip (benchmarks/testdata/): busy
union, idle share, module median, kernel sums, op-family names, idle gaps
by host span."""
import json
import os

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

D0, D1, H = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE


def op(name, start, dur, plane=D0):
    return (plane, tr.OPS, name, float(start), float(dur))


HAND = [
    (H, "main", tr.WINDOW, 0.0, 1000.0),
    (H, "main", "Trainer.captured_step", 0.0, 400.0),
    (H, "worker", "serve.decode_step", 500.0, 300.0),
    (H, "worker", "serve.prefill", 600.0, 100.0),
    (D0, tr.MODULES, "jit_program(1)", 100.0, 300.0),
    (D0, tr.MODULES, "jit_program(1)", 500.0, 200.0),
    (D0, tr.MODULES, "jit_program(1)", 950.0, 400.0),   # cut by the window
    (D0, tr.MODULES, "jit__unstack(2)", 90.0, 5.0),
    op("fusion.1", 100, 100), op("fusion.2", 150, 100),  # overlap: 100-250
    op("jvp_mxtpu_flash_fwd_.3", 300, 100),
    op("copy.4", 500, 50), op("all-reduce-start.1", 550, 50),
    op("all-reduce-done.1", 600, 10),
    op("transpose_jvp_mxtpu_flash_bwd_dkv__.7", 650, 50),
    op("fusion.9", 950, 100),                            # 50 inside
    op("fusion.1", 0, 500, D1),
]


@pytest.mark.parametrize("name, want", [
    ("fusion.16", "fusion"), ("copy", "copy"),
    ("jvp_mxtpu_flash_fwd_.12", "mxtpu_flash_fwd"),
    ("transpose_jvp_mxtpu_flash_bwd_dkv__.7", "mxtpu_flash_bwd_dkv"),
    ("transpose_jvp_mxtpu_flash_bwd_dq__.1", "mxtpu_flash_bwd_dq"),
    ("jvp_mxtpu_layer_norm_.26", "mxtpu_layer_norm"),
    ("mxtpu_rpa.5", "mxtpu_rpa"), ("convolution_add_fusion", 
                                   "convolution_add_fusion"),
    ("all-reduce-start.3", "all-reduce-start"), ("pad.1.clone", "pad.1.clone"),
    ("slice_reduce_fusion.2", "slice_reduce_fusion"),
    ("add_add_fusion_1", "add_add_fusion"),
])
def test_family_names(name, want):
    assert tr.family(name) == want


def test_op_name_keeps_the_instruction_name():
    text = ("%fusion.16 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) "
            "fusion(u32[2]{0:T(128)} %key.1), kind=kLoop")
    assert tr.op_name(text) == "fusion.16"


def test_hand_written_events():
    t0, t1 = tr.window(HAND)
    assert (t0, t1) == (0.0, 1000.0)
    assert tr.device_planes(HAND) == [D0, D1]
    assert tr.busy_intervals(HAND, D0, t0, t1) == [
        (100.0, 250.0), (300.0, 400.0), (500.0, 610.0), (650.0, 700.0),
        (950.0, 1000.0)]
    # device 0 busy 460 ns, device 1 500 ns: the mean of the two
    assert tr.busy_seconds(HAND, t0, t1) == pytest.approx(480e-9)
    assert tr.idle_pct(HAND, t0, t1) == pytest.approx(52.0)
    assert tr.dominant_module(HAND, t0, t1) == "jit_program(1)"
    # the third step is cut by the window's edge and left out
    assert tr.module_median_ms(HAND, t0, t1) == pytest.approx(250e-6)
    assert tr.kernel_seconds(HAND, "mxtpu_flash", t0, t1) == (
        1.0, pytest.approx(75e-9))      # 2 calls, 150 ns, over 2 planes
    fam = tr.family_seconds(HAND, t0, t1)
    assert fam["fusion"] == pytest.approx(250e-9)       # 100 + 100 + 50
    assert fam["mxtpu_flash_fwd"] == pytest.approx(100e-9)
    assert tr.collective_seconds(HAND, t0, t1) == pytest.approx(60e-9)
    assert tr.top(fam, 2)[0][0] == "fusion" and len(tr.top(fam, 2)) == 2
    gaps = tr.idle_gaps(HAND, t0, t1, min_ns=20)
    assert gaps == {"Trainer.captured_step": pytest.approx(150e-9),
                    "(no span)": pytest.approx(350e-9),
                    # 610-650 lies in both serve spans: the inner one
                    "serve.prefill": pytest.approx(40e-9)}
    assert tr.span_median_ms(HAND, "serve.prefill", t0, t1) \
        == pytest.approx(100e-6)
    assert tr.span_median_ms(HAND, "serve.nothing", t0, t1) is None


def test_empty_trace_reads_as_nothing():
    assert tr.window([]) is None
    assert tr.busy_seconds([], 0, 1) == 0.0
    assert tr.module_median_ms([], 0, 1) is None
    assert tr.idle_gaps([], 0, 1) == {}


def test_recorded_excerpt():
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "train_excerpt.json")) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    t0, t1 = rec["ops_window_ns"]
    ops = tr.clip(tr.select(events, line=tr.OPS), t0, t1)
    assert len(tr.select(events, line=tr.OPS)) == 420   # 13 of 0 ns
    # one core runs its ops one after another: the union is their sum
    busy = tr.busy_seconds(events, t0, t1)
    assert busy == pytest.approx(sum(e[4] for e in ops) / 1e9, rel=1e-9)
    assert tr.idle_pct(events, t0, t1) == pytest.approx(
        100 * (1 - busy * 1e9 / (t1 - t0)))
    assert 3.0 < tr.idle_pct(events, t0, t1) < 4.5
    # four whole steps of the captured BERT-base step, 104.6 ms each
    mods = tr.select(events, line=tr.MODULES)
    w0, w1 = min(e[3] for e in mods), max(e[3] + e[4] for e in mods)
    assert tr.dominant_module(events, w0, w1).startswith("jit_program(")
    assert tr.module_median_ms(events, w0, w1) == pytest.approx(104.6,
                                                                abs=0.05)
    calls, seconds = tr.kernel_seconds(events, "mxtpu_flash_fwd", t0, t1)
    assert calls == 2 and seconds / calls == pytest.approx(978.8e-6,
                                                           rel=1e-3)
    fam = dict(tr.top(tr.family_seconds(events, t0, t1), 10))
    assert list(fam)[0] == "mxtpu_flash_fwd"
    assert {"copy", "fusion", "mxtpu_layer_norm"} <= set(
        tr.family_seconds(events, t0, t1))
    assert not any(k[-1].isdigit() and "." in k[-4:] for k in fam)
    gaps = tr.idle_gaps(events, t0, t1)
    assert set(gaps) <= {"Trainer.captured_step", "(no span)",
                         "(gaps under 20 us)"}
    assert sum(gaps.values()) == pytest.approx((t1 - t0) / 1e9 - busy)
