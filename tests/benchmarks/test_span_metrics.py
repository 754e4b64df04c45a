"""`benchmarks/lib/span_reduce.py`, `lib/scope_share.py` and the five
readers that use them, on hand-made spans, events and `op_scopes`; then
the serve readers on the trace of one tiny `serve_backlog` run."""
import pytest

from benchmarks.lib import scope_share, span_reduce as sr, trace_reduce as tr
from benchmarks.metrics import (dropout_share_pct, queue_wait_ms,
                                turn_host_ms, update_share_pct)

D0, D1, H = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE


def span(name, start, dur, args=None):
    return (name, float(start), float(dur), args)


def turn(start, dur, step_at=None, step_dur=0.0, n=0):
    """One `serve.turn` with its phases; `step_at` None: an idle turn."""
    out = [span("serve.turn", start, dur, {"turn": n, "queued": 3}),
           span("serve.admit", start + 10, 100)]
    if step_at is not None:
        out += [span("serve.prefill", start + 20, 40, {"slot": 1}),
                span("serve.plan", start + 120, 30),
                span("serve.decode_step", step_at, step_dur, {"active": 2}),
                span("serve.commit", step_at + step_dur + 5, 50)]
    return out


# five turns: the first and the last are the slice's edges, the third
# admitted nobody and decoded nothing
TURNS = (turn(0, 1000, 200, 700, 0) + turn(1000, 2000, 1300, 1500, 1)
         + turn(3000, 150, n=2) + turn(3200, 3000, 3500, 2000, 2)
         + turn(6200, 900, 6400, 300, 3))
ADMITTED = [span("serve.admitted", 1015, 0, {"id": i, "slot": i,
                                             "queue_wait_ms": w,
                                             "cached_tokens": 0})
            for i, w in enumerate([4.0, 30.0, 9.0])]


def test_children_lie_inside_and_self_time_is_what_they_leave():
    whole = sr.named(TURNS, "serve.turn")
    assert [t[3]["turn"] for t in whole] == [0, 1, 2, 2, 3]
    t = whole[1]
    kids = sr.inside(TURNS, t)
    assert [k[0] for k in kids] == [
        "serve.admit", "serve.prefill", "serve.plan", "serve.decode_step",
        "serve.commit"]
    assert t not in kids
    # the prefill lies inside admit: covered once, not twice
    assert sr.covered_us(kids) == 100 + 30 + 1500 + 50
    assert sr.self_us(t, TURNS) == 2000 - 1680
    assert sr.self_us(t, sr.named(TURNS, "serve.decode_step")) == 500
    # overlapping and touching children, and one that sticks out
    mixed = [span("a", 0, 10), span("b", 5, 10), span("c", 15, 5),
             span("d", 30, 100)]
    assert sr.covered_us(mixed[:3]) == 20
    assert sr.inside(mixed, span("p", 0, 50)) == mixed[:3]
    assert sr.covered_us([]) == 0 and sr.named(TURNS, "serve.none") == []


def test_turn_host_ms_leaves_out_the_edges_and_the_idle_turn(capsys):
    got = turn_host_ms.reduce([], TURNS + ADMITTED, {}, {"workload": "w"})
    # turns 1 and 2 (by start 1000 and 3200): (500 + 1000) us over two
    assert got == pytest.approx(0.75)
    said = capsys.readouterr().out
    assert "2 whole turns" in said and "decode_step 1.750" in said
    # a slice that holds no whole turn with a decode step: nothing to read
    assert turn_host_ms.reduce([], TURNS[:8], {}, {}) is None
    assert turn_host_ms.reduce([], turn(0, 10) * 3, {}, {}) is None
    assert turn_host_ms.reduce([], [], {}, {}) is None


def test_queue_wait_ms_is_the_median_of_the_admitted_instants():
    assert queue_wait_ms.reduce([], TURNS + ADMITTED, {}, {}) == 9.0
    assert queue_wait_ms.reduce([], ADMITTED[:2], {}, {}) == 17.0
    assert queue_wait_ms.reduce([], TURNS, {}, {}) is None


def op(name, start, dur, plane=D0):
    return (plane, tr.OPS, name, float(start), float(dur))


STEP, OTHER = "jit_program(1)", "jit__unstack(2)"
EVENTS = [
    (H, "main", tr.WINDOW, 0.0, 1000.0),
    (D0, tr.MODULES, STEP, 100.0, 400.0),
    (D0, tr.MODULES, STEP, 600.0, 500.0),        # cut by the window
    (D0, tr.MODULES, OTHER, 510.0, 60.0),
    op("fusion.1", 100, 100), op("fusion.2", 200, 50),
    op("conditional.3", 300, 150), op("fusion.4", 310, 100),
    op("fusion.2", 520, 40),                     # the other module's
    op("fusion.2", 600, 100), op("copy.5", 700, 100),
    op("fusion.1", 950, 100),                    # 50 inside the window
    op("fusion.1", 0, 1000, D1),                 # not the first device
]
SCOPES = {"fusion.1": ("mx_dropout",), "fusion.2": ("mx_dropout",
                                                    "mx_update"),
          "conditional.3": ("mx_update",), "fusion.4": ("mx_update",),
          "never_ran.9": ("mx_update",)}


def test_scope_share_counts_the_step_modules_ops_once():
    w = tr.window(EVENTS)
    # busy: 100-250, 300-450, 520-560, 600-800, 950-1000 = 590
    busy = sum(b - a for a, b in tr.busy_intervals(EVENTS, D0, *w))
    assert busy == 590
    # dropout: fusion.1 100 + 50, fusion.2 50 + 100, not the other module's
    assert scope_share.share_pct(EVENTS, *w, SCOPES, "mx_dropout") \
        == pytest.approx(100 * 300 / 590)
    # update: fusion.2 50 + 100, the conditional with its body inside 150
    assert scope_share.share_pct(EVENTS, *w, SCOPES, "mx_update") \
        == pytest.approx(100 * 300 / 590)
    assert scope_share.share_pct(EVENTS, *w, SCOPES, "mx_other") == 0.0
    assert scope_share.share_pct(EVENTS, *w, {}, "mx_update") is None
    assert scope_share.share_pct(EVENTS[:1], *w, SCOPES, "mx_update") is None
    no_ops = [e for e in EVENTS if e[1] != tr.OPS]
    assert scope_share.share_pct(no_ops, *w, SCOPES, "mx_update") is None


def test_the_train_readers_join_the_trace_with_the_programs_map(monkeypatch):
    from mxnet_tpu.observability import compilex
    cell = {"window": tr.window(EVENTS)}
    monkeypatch.setattr(compilex, "_inspections", {})
    # no inspection was published: the metric is left out, never zero
    assert update_share_pct.reduce(EVENTS, [], {}, cell) is None
    assert dropout_share_pct.reduce(EVENTS, [], {}, cell) is None
    compilex._inspections["captured_step"] = {"fusions": 4}   # an old map
    assert update_share_pct.reduce(EVENTS, [], {}, cell) is None
    compilex._inspections["captured_step"] = {"op_scopes": SCOPES}
    assert update_share_pct.reduce(EVENTS, [], {}, cell) \
        == pytest.approx(100 * 300 / 590)
    assert dropout_share_pct.reduce(EVENTS, [], {}, cell) \
        == pytest.approx(100 * 300 / 590)
    # a program from before the map has no `last_inspections` at all
    monkeypatch.delattr(compilex, "last_inspections")
    assert dropout_share_pct.reduce(EVENTS, [], {}, cell) is None


def test_the_serve_readers_on_a_tiny_backlog_run(monkeypatch, capsys):
    from test_serve_kinds import _ctx
    from benchmarks.kinds import serve_backlog
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    ctx, log = _ctx("wmt_backlog", trace=True)
    out = serve_backlog.run(ctx)
    assert out["problems"] == [], (out["problems"], log)
    ts = out["trace"]
    info = {"window": ts.window, "workload": "tiny"}
    turns = sr.named(ts.spans, "serve.turn")
    steps = sr.named(ts.spans, "serve.decode_step")
    assert turns and steps
    # a loaded machine may fit no whole turn into the slice of one second
    host = turn_host_ms.reduce(ts.events, ts.spans, {}, info)
    whole = [t for t in turns[1:-1] if sr.inside(steps, t)]
    if whole:
        assert 0 < host < max(t[2] for t in whole) / 1e3
        assert "whole turns" in capsys.readouterr().out
    else:
        assert host is None
    # a request of the backlog waited for one of the four slots; a slow
    # machine may admit nobody in a slice of one second
    waits = [s[3]["queue_wait_ms"] for s in ts.spans
             if s[0] == "serve.admitted"]
    wait = queue_wait_ms.reduce(ts.events, ts.spans, {}, info)
    assert (wait is None and not waits) or min(waits) <= wait <= max(waits)
    assert all(w > 0 for w in waits)
    # the mirrored annotations put a whole turn's spans on the device
    # trace's clock, where `idle_gaps` attributes to them
    names = {e[2] for e in tr.host_spans(ts.events)}
    assert not whole or {"serve.turn", "serve.admit", "serve.plan",
                         "serve.decode_step", "serve.commit"} <= names
    # no device plane on the CPU: the device shares have nothing to read
    assert update_share_pct.reduce(ts.events, ts.spans, {}, info) is None
