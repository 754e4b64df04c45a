"""`lib/program_share.py` on hand-written events: two programs that both
own a `fusion.1` under different scopes, a `while` beside its body, a
fusion that mixes two scopes, an op outside any module run; and the
readers built on it, which find nothing where the program says nothing."""
import pytest

from benchmarks.lib import program_share as ps, trace_reduce as tr
from benchmarks.metrics import (cross_attn_share_pct, decode_launch_ms,
                                host_busy_pct, moe_dispatch_share_pct,
                                prefill_share_pct, unscoped_share_pct)

D0, D1, H = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE
DECODE, PREFILL, OTHER = ("jit__decode_program(11)",
                          "jit__prefill_program(22)", "jit__unstack(33)")


def op(name, start, dur, plane=D0):
    return (plane, tr.OPS, name, float(start), float(dur))


def run(name, start, dur):
    return (D0, tr.MODULES, name, float(start), float(dur))


EVENTS = [
    (H, "main", tr.WINDOW, 0.0, 1000.0),
    run(DECODE, 100, 300), run(PREFILL, 400, 200), run(OTHER, 620, 30),
    run(DECODE, 700, 400),                       # cut by the window at 1000
    # the decode program: its own fusion.1, a mixed fusion, an unnamed copy
    op("fusion.1", 100, 100), op("fusion.2", 200, 100), op("copy.3", 300, 50),
    # the prefill program: ITS fusion.1, a while beside its body's two ops
    op("fusion.1", 400, 40), op("while.4", 440, 150),
    op("fusion.5", 450, 60), op("fusion.6", 520, 50),
    op("fusion.1", 620, 30),                     # a module nobody inspected
    op("fusion.1", 660, 20),                     # outside any module run
    op("fusion.1", 700, 100), op("fusion.2", 950, 100),   # 50 inside
    op("fusion.1", 0, 1000, D1),                 # not the first device
]
INSPECTIONS = {
    "serve_lm_decode": {
        "module": "jit__decode_program", "fusions": 2, "copies": 1,
        "aliased_inputs": 3, "ops": {"fusion": 2, "copy": 1},
        "op_scopes": {"fusion.1": ("mx_moe", "mx_moe_dispatch"),
                      "fusion.2": ("mx_moe", "mx_moe_combine", "mx_norm"),
                      "never_ran.9": ("mx_head",)},
        "op_names": {"copy.3": "jit(_decode_program)/transpose"}},
    "serve_lm_prefill": {
        "module": "jit__prefill_program",
        "op_scopes": {"fusion.1": ("mx_embed",),
                      "fusion.5": ("mx_moe", "mx_moe_dispatch"),
                      "fusion.6": ("mx_cache_write",)},
        "op_names": {"while.4": "jit(_prefill_program)/while"}},
    "from_before": {"op_scopes": {"fusion.1": ("mx_update",)}},
}


@pytest.fixture
def inspected(monkeypatch):
    from mxnet_tpu.observability import compilex
    monkeypatch.setattr(compilex, "_inspections", dict(INSPECTIONS))
    monkeypatch.setattr(ps, "_last", [None, None, None])
    return compilex


def test_exclusive_gives_every_instant_to_the_op_that_started_last():
    got = ps.exclusive([(0, 100, "while"), (0, 40, "a"), (50, 90, "b"),
                        (200, 300, "c"), (250, 350, "d")])
    assert got == {"while": 20, "a": 40, "b": 40, "c": 50, "d": 100}
    assert sum(got.values()) == 250              # the union
    assert ps.exclusive([]) == {}
    assert ps.leaves(("mx_moe", "mx_moe_route", "mx_norm")) \
        == ("mx_moe_route", "mx_norm")
    assert ps.leaves(("mx_gqa",)) == ("mx_gqa",)


def test_ops_join_the_map_of_the_program_they_ran_in(inspected):
    w = tr.window(EVENTS)
    s = ps.reduce(EVENTS, *w)
    # busy: 100-350, 400-590, 620-650, 660-680, 700-800, 950-1000
    assert s.busy_ns == 250 + 190 + 30 + 20 + 100 + 50 == 640
    assert s.programs == {"serve_lm_decode": 600, "serve_lm_prefill": 200}
    assert s.program_pct("prefill") == pytest.approx(100 * 200 / 640)
    assert s.program_pct("decode") == pytest.approx(100 * 600 / 640)
    assert s.program_pct("verify") is None
    # dispatch or combine: decode's fusion.1 100 + 100 and fusion.2 100 +
    # 50, prefill's fusion.5 60; NOT the other three fusion.1s
    assert s.scope_pct(("mx_moe_dispatch", "mx_moe_combine")) \
        == pytest.approx(100 * 410 / 640)
    assert s.scope_pct(("mx_moe_dispatch",), "decode") \
        == pytest.approx(100 * 200 / 640)
    assert s.scope_pct(("mx_embed",)) == pytest.approx(100 * 40 / 640)
    assert s.scope_pct(("mx_embed",), "decode") == 0.0
    assert s.scope_pct(("mx_embed",), "verify") is None
    # under no scope: the copy 50, the while's own 40 (150 less its body's
    # 110), the uninspected module's 30, the 20 outside any run
    assert s.unscoped_pct() == pytest.approx(100 * 140 / 640)


def test_the_exclusive_table_sums_to_busy_and_names_what_has_no_scope(
        inspected):
    s = ps.reduce(EVENTS, *tr.window(EVENTS))
    assert sum(s.rows.values()) == pytest.approx(s.busy_ns)
    assert s.rows == {
        ("serve_lm_decode", "mx_moe_dispatch"): 200,
        ("serve_lm_decode", "mx_moe_combine+mx_norm"): 150,   # mixed
        ("serve_lm_decode", ps.NO_SCOPE): 50,
        ("serve_lm_prefill", "mx_embed"): 40,
        ("serve_lm_prefill", "mx_moe_dispatch"): 60,
        ("serve_lm_prefill", "mx_cache_write"): 50,
        ("serve_lm_prefill", ps.NO_SCOPE): 40,                # the loop
        ("jit__unstack (not inspected)", ps.NO_SCOPE): 30,
        (ps.OUTSIDE, ps.NO_SCOPE): 20}
    # what has no scope, the instances of one source op together
    assert s.unnamed == [
        (50, "serve_lm_decode", "copy", "jit(_decode_program)/transpose", 1),
        (40, "serve_lm_prefill", "while", "jit(_prefill_program)/while", 1),
        (30, "jit__unstack (not inspected)", "fusion", None, 1),
        (20, ps.OUTSIDE, "fusion", None, 1)]
    text = "\n".join(s.table())
    assert "9 rows sum to" in text and "mx_moe_combine+mx_norm" in text
    assert "serve_lm_prefill = jit__prefill_program" in text
    assert "serve_lm_decode copy x 1: " in text
    assert "jit(_decode_program)/transpose" in text
    assert "(no op_name)" in text                # the uninspected fusion.1


def test_the_readers_read_the_join_and_print_the_table(inspected, capsys):
    cell = {"window": tr.window(EVENTS), "workload": "tiny"}
    args = (EVENTS, [], {}, cell)
    assert prefill_share_pct.reduce(*args) == pytest.approx(100 * 200 / 640)
    assert moe_dispatch_share_pct.reduce(*args) \
        == pytest.approx(100 * 410 / 640)
    assert cross_attn_share_pct.reduce(*args) == 0.0
    assert capsys.readouterr().out == ""
    assert unscoped_share_pct.reduce(*args) == pytest.approx(100 * 140 / 640)
    out = capsys.readouterr().out
    assert "[bench tiny] inspected serve_lm_decode (jit__decode_program): " \
        "2 fusions, 1 copies, 3 aliased inputs, 3 instructions of 2 " \
        "opcodes (crc32 " in out
    assert "[bench tiny] inspected from_before (None)" in out
    assert "rows sum to" in out and "without a scope" in out


def test_the_readers_find_nothing_where_the_program_says_nothing(
        inspected, monkeypatch, capsys):
    cell = {"window": tr.window(EVENTS), "workload": "tiny"}
    readers = (prefill_share_pct, moe_dispatch_share_pct,
               cross_attn_share_pct, unscoped_share_pct)
    # a program from before `module`: nothing joins, nothing is zero
    inspected._inspections.clear()
    inspected._inspections["serve_lm_decode"] = {
        "op_scopes": {"fusion.1": ("mx_moe",)}}
    assert [r.reduce(EVENTS, [], {}, cell) for r in readers] == [None] * 4
    # an executable whose inspection was skipped is named in the log
    from mxnet_tpu.observability import registry
    registry().counter("hlo_inspect_skipped", executable="serve_decode").inc()
    assert unscoped_share_pct.reduce(EVENTS, [], {}, cell) is None
    assert "NOT inspected: serve_decode (hlo_inspect_skipped)" \
        in capsys.readouterr().out
    registry().reset("hlo_inspect_skipped")
    # no device plane (a CPU run), no busy time
    inspected._inspections.update(INSPECTIONS)
    monkeypatch.setattr(ps, "_last", [None, None, None])
    host_only = [e for e in EVENTS if e[0] == H]
    assert [r.reduce(host_only, [], {}, cell) for r in readers] == [None] * 4
    # a program without `last_inspections` at all
    monkeypatch.delattr(inspected, "last_inspections")
    monkeypatch.setattr(ps, "_last", [None, None, None])
    assert [r.reduce(EVENTS, [], {}, cell) for r in readers] == [None] * 4


def span(name, start, dur, args=None):
    return (name, float(start), float(dur), args)


def test_host_busy_pct_and_decode_launch_ms_read_the_two_children():
    # four turns of 100 us; the whole ones (2nd, 3rd) wait 30 and 50 us
    spans = []
    for i, wait in enumerate((10, 30, 50, 70)):
        t = 1000 + 200 * i
        spans += [span("serve.turn", t, 100),
                  span("serve.decode_step", t + 20, 70),
                  span("serve.decode_launch", t + 20, 15, {"active": 4}),
                  span("serve.decode_read", t + 36, wait, {"lookahead": 1})]
    spans.append(span("serve.turn", 1900, 50))   # nothing to read: left out
    assert host_busy_pct.reduce([], spans, {}, {}) \
        == pytest.approx(100 * (1 - 80 / 200))
    # the parent's spans: no `serve.decode_read` anywhere
    old = [s for s in spans if s[0] != "serve.decode_read"]
    assert host_busy_pct.reduce([], old, {}, {}) is None
    events = [(H, "t", "serve.decode_launch", 1e3 * k, 1e3 * d)
              for k, d in ((0, 3), (10, 1), (20, 2), (995, 50))]
    cell = {"window": (0.0, 1e6)}
    assert decode_launch_ms.reduce(events, [], {}, cell) == 2e-3
    assert decode_launch_ms.reduce(events[:0], [], {}, cell) is None
