"""The serve turn's spans (ISSUE 26): `serve.turn` and its phases, the
request's `submit -> admitted -> request_done` chain, queue wait where the
request leaves the queue, and nothing recorded with the tracer off. ISSUE
36: `serve.decode_step`'s two children, the launch of a turn and the read
of a turn's tokens."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.fault import injection as finj
from mxnet_tpu.observability import registry, tracer

PHASES = ["serve.admit", "serve.plan", "serve.decode_step", "serve.commit"]


def _server(**kw):
    from mxnet_tpu.models.transformer import TransformerNMT
    mx.random.seed(11)
    m = TransformerNMT(50, units=32, hidden=64, num_layers=2, num_heads=4,
                       max_length=32, dropout=0.0)
    m.initialize()
    kw.setdefault("engine_driven", False)
    kw.setdefault("slots", 2)
    kw.setdefault("max_new_tokens", 6)
    return mx.serve.Server(m, page_size=4, max_src_len=16, **kw)


def _sources(n=5):
    rng = np.random.RandomState(3)
    return [rng.randint(4, 50, (int(k),)) for k in rng.randint(3, 9, n)]


@pytest.fixture(autouse=True)
def _quiet():
    finj.clear()
    tracer.stop()
    tracer.clear()
    yield
    finj.clear()
    tracer.stop()
    tracer.clear()


def _events():
    return [e for e in tracer.to_chrome_trace()["traceEvents"]
            if e["ph"] != "M"]


def _spans(events):
    """[(name, start, end, tid, args)] of the B/E pairs, by start."""
    out, stack = [], {}
    for e in events:
        if e["ph"] == "B":
            stack.setdefault(e["tid"], []).append(e)
        elif e["ph"] == "E":
            b = stack[e["tid"]].pop()
            out.append((b["name"], b["ts"], e["ts"], b["tid"],
                        b.get("args")))
    return sorted(out, key=lambda s: s[1])


def _turn_trees(spans):
    """[(the turn's span, its phases by start, everything else inside)]."""
    out = []
    for turn in (s for s in spans if s[0] == "serve.turn"):
        inside = [s for s in spans if s[0] != "serve.turn"
                  and turn[1] <= s[1] and s[2] <= turn[2] and s[3] == turn[3]]
        out.append((turn, [s for s in inside if s[0] in PHASES], inside))
    return out


def _children(step, inside):
    """`serve.decode_step`'s children, by start: the launch and the read,
    wholly inside it and one after the other."""
    kids = [s for s in inside
            if s[0] in ("serve.decode_launch", "serve.decode_read")
            and step[1] <= s[1] and s[2] <= step[2]]
    assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    return kids


def _check_phases(phases, inside):
    # nested in time: each phase ends before the next begins
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    for s in inside:
        if s[0] == "serve.prefill":
            assert phases[0][1] <= s[1] and s[2] <= phases[0][2]


def test_every_turn_holds_its_phases_in_order_on_one_thread():
    """The serial order (no request waits for a slot after a turn's
    admission): every `serve.turn` is one committed turn and holds admit,
    plan, decode_step, commit."""
    srv = _server()
    tracer.start()
    for wave in (_sources()[:2], _sources()[2:4]):
        hs = [srv.submit(s) for s in wave]
        srv.scheduler.run_until_idle()
    tracer.stop()
    assert srv.scheduler.lookahead_turns == 0
    spans = _spans(_events())
    trees = _turn_trees(spans)
    assert len(trees) == srv.scheduler.decode_turns > 0
    assert [t[4]["turn"] for t, _, _ in trees] == list(range(len(trees)))
    assert trees[0][0][4]["queued"] == len(hs)
    for _, phases, inside in trees:
        assert [s[0] for s in phases] == PHASES
        _check_phases(phases, inside)
        # the serial turn: its launch, then the read of ITS tokens with
        # no later turn in flight
        launch, read = _children(phases[2], inside)
        assert launch[0] == "serve.decode_launch"
        assert launch[4] == {"active": phases[2][4]["active"]}
        assert (read[0], read[4]) == ("serve.decode_read", {"lookahead": 0})
    assert len({t[3] for t, _, _ in trees}) == 1
    assert {s[0] for s in spans} <= set(PHASES) | {
        "serve.turn", "serve.prefill", "serve.decode_launch",
        "serve.decode_read"}
    srv.close()


def test_a_backlog_turn_keeps_the_four_phases_around_the_turn_in_flight():
    """ISSUE 35: with requests waiting for a slot, a turn dispatches the
    NEXT decode step and reads the one in flight inside the one
    `serve.decode_step` (which keeps `active` and `cached_tokens`), then
    commits: still admit, plan, decode_step, commit under `serve.turn`.
    Only the turn that starts a run of lookahead has nothing to commit
    yet, and the one that ends it (the queue went empty) reads without
    planning or dispatching; commits and decode turns stay one to one."""
    srv = _server()
    sched = srv.scheduler
    tracer.start()
    hs = [srv.submit(s) for s in _sources()]
    sched.run_until_idle()
    tracer.stop()
    assert sched.lookahead_turns > 0
    trees = _turn_trees(_spans(_events()))
    shapes = [tuple(s[0][6:] for s in phases) for _, phases, _ in trees]
    whole = ("admit", "plan", "decode_step", "commit")
    assert set(shapes) <= {whole, whole[:3], whole[:1] + whole[2:]}
    assert shapes.count(whole) >= sched.lookahead_turns
    # a run also ends where every slot of the turn in flight ends with it
    # (nothing to dispatch: a whole turn whose decode_step only reads)
    assert 1 <= shapes.count(whole[:3]) >= shapes.count(whole[:1] + whole[2:])
    assert sum("commit" in s for s in shapes) == sched.decode_turns
    turn_no = [t[4]["turn"] for t, _, _ in trees]
    assert turn_no == sorted(turn_no) and turn_no[-1] == sched.decode_turns - 1
    for (_, phases, inside), shape in zip(trees, shapes):
        _check_phases(phases, inside)
        step = phases[shape.index("decode_step")]
        kids = _children(step, inside)
        if step[4]["active"]:
            assert step[4]["cached_tokens"] >= 0
            assert kids[0][0] == "serve.decode_launch"
            assert kids[0][4] == {"active": step[4]["active"]}
            # the turn that starts a run of lookahead reads nothing; every
            # other launch is followed by ONE read: of the turn before,
            # which waited with this one in flight (`lookahead` 1: as
            # many as the scheduler counts, below), or of this one where
            # the order is serial
            assert [k[0] for k in kids[1:]] \
                == ["serve.decode_read"] * ("commit" in shape)
            assert all(k[4] in ({"lookahead": 0}, {"lookahead": 1})
                       for k in kids[1:])
        else:
            assert step[4] == {"active": 0}      # a read, no dispatch
            assert "commit" in shape
            assert [(k[0], k[4]) for k in kids] \
                == [("serve.decode_read", {"lookahead": 0})]
    reads = [s for _, _, inside in trees for s in inside
             if s[0] == "serve.decode_read"]
    assert len(reads) == sched.decode_turns      # every turn is read once
    assert sum(r[4]["lookahead"] for r in reads) == sched.lookahead_turns
    assert all(len(h.result()) == 6 for h in hs)
    srv.close()


def test_a_drain_is_a_read_alone_and_a_widened_turn_reads_in_its_launch():
    """`defrag` (as `shutdown`, a dry pool, a fault) first reads the turn
    in flight: a `serve.decode_step` of `active` 0 that holds ONE child,
    the read, with nothing in flight behind it. A widened (speculative)
    turn's tokens come back with its dispatch: launch and read are both
    there, and the read has nothing left to wait for."""
    srv = _server()
    sched = srv.scheduler
    hs = [srv.submit(s) for s in _sources()]
    while sched._inflight is None:
        sched.step()
    tracer.start()
    sched.defrag()
    tracer.stop()
    spans = _spans(_events())
    (step,) = [s for s in spans if s[0] == "serve.decode_step"]
    assert step[4] == {"active": 0}
    assert [(k[0], k[4]) for k in _children(step, spans)] \
        == [("serve.decode_read", {"lookahead": 0})]
    assert not [s for s in spans if s[0] == "serve.decode_launch"]
    sched.run_until_idle()
    assert all(len(h.result()) == 6 for h in hs)
    srv.close()

    srv = _server(speculative_k=2, max_prompt_len=8)
    tracer.clear()
    tracer.start()
    hs = [srv.submit(s) for s in _sources()]
    srv.scheduler.run_until_idle()
    tracer.stop()
    assert srv.scheduler.lookahead_turns == 0    # a widened turn is serial
    spans = _spans(_events())
    steps = [s for s in spans if s[0] == "serve.decode_step"]
    assert len(steps) == srv.scheduler.decode_turns > 0
    for step in steps:
        launch, read = _children(step, spans)
        assert (launch[0], read[0]) == ("serve.decode_launch",
                                        "serve.decode_read")
        assert read[4] == {"lookahead": 0}
    srv.close()


def test_a_request_is_one_chain_of_instants_and_queue_wait_is_its_own():
    srv = _server()
    tracer.start()
    hs = [srv.submit(s) for s in _sources()]
    srv.scheduler.run_until_idle()
    tracer.stop()
    inst = [e for e in _events() if e["ph"] == "i"]
    for h in hs:
        mine = [e for e in inst if e["args"]["id"] == h.id]
        assert [e["name"] for e in mine] == [
            "serve.submit", "serve.admitted", "serve.request_done"]
        adm = mine[1]["args"]
        assert adm["queue_wait_ms"] == (h.t_admit - h.t_submit) * 1e3
        assert adm["slot"] in (0, 1) and adm["cached_tokens"] == 0
        assert h.t_submit <= h.t_admit <= h.t_first_token <= h.t_done
    # two slots, five requests: the later ones waited for a slot
    waits = [h.t_admit - h.t_submit for h in hs]
    assert max(waits[2:]) > max(waits[:2])
    srv.close()


def test_a_batch_is_one_prefill_span_with_its_rows_inside_admit():
    """Five admissions in one turn: ONE `serve.prefill` span, a child of
    `serve.admit` inside `serve.turn`, carrying the batch's `rows` and
    its longest source; every request of the batch is stamped after the
    dispatch and its `queue_wait_ms` is its own `t_admit - t_submit`."""
    srv = _server(slots=8, max_new_tokens=3)
    srcs = _sources()
    tracer.start()
    hs = [srv.submit(s) for s in srcs]
    srv.scheduler.step()
    tracer.stop()
    events = _events()
    spans = _spans(events)
    (turn,) = [s for s in spans if s[0] == "serve.turn"]
    (admit,) = [s for s in spans if s[0] == "serve.admit"]
    (fill,) = [s for s in spans if s[0] == "serve.prefill"]
    assert turn[1] <= admit[1] <= fill[1] <= fill[2] <= admit[2] <= turn[2]
    assert fill[4] == {"rows": len(srcs),
                       "src_len": max(s.size for s in srcs)}
    admitted = {e["args"]["id"]: e for e in events
                if e["name"] == "serve.admitted"}
    assert sorted(admitted) == [h.id for h in hs]
    for h in hs:
        e = admitted[h.id]
        assert e["args"]["queue_wait_ms"] == (h.t_admit - h.t_submit) * 1e3
        # stamped after the dispatch that holds it, still inside admit
        assert fill[2] <= e["ts"] <= admit[2]
    srv.scheduler.run_until_idle()
    srv.close()


def test_a_requeued_request_is_stamped_again():
    srv = _server(max_retries=2)
    finj.inject("serve.decode", at=[2])      # die after one emitted token
    tracer.start()
    h = srv.submit(_sources(1)[0])
    sched = srv.scheduler
    sched.step()
    first = h.t_admit
    assert first is not None
    sched.step()                             # fault -> requeue
    assert h.state == "queued" and h.t_admit is None
    sched.run_until_idle(max_steps=200)
    tracer.stop()
    assert h.state == "done" and h.t_admit > first
    admitted = [e for e in _events() if e["name"] == "serve.admitted"]
    assert len(admitted) == 2
    assert admitted[1]["args"]["queue_wait_ms"] \
        == (h.t_admit - h.t_submit) * 1e3 > admitted[0]["args"][
            "queue_wait_ms"]
    srv.close()


def test_tracer_off_records_nothing_and_the_counters_still_fill():
    hist = registry().histogram("serve_queue_wait_seconds")
    n0, sum0 = hist.count, hist.sum
    srv = _server()
    hs = [srv.submit(s) for s in _sources()]
    srv.scheduler.run_until_idle()
    off = [h.result() for h in hs]
    assert tracer.events_recorded() == 0
    assert srv.scheduler.lookahead_turns > 0     # counted all the same
    assert all(h.t_admit is not None for h in hs)
    assert hist.count == n0 + len(hs)
    assert hist.sum - sum0 == pytest.approx(
        sum(h.t_admit - h.t_submit for h in hs))
    srv.close()

    srv = _server()
    tracer.start()
    hs = [srv.submit(s) for s in _sources()]
    srv.scheduler.run_until_idle()
    tracer.stop()
    assert tracer.events_recorded() > 0
    assert [h.result() for h in hs] == off   # greedy tokens identical
    srv.close()


def test_an_engine_driven_turn_lies_inside_the_engines_own_task_span():
    # the engine already records one span a burst of the serve loop
    # (`engine:<module.fn>`): the turns need no loop span of their own
    srv = _server(engine_driven=True)
    tracer.start()
    hs = [srv.submit(s) for s in _sources()]
    assert srv.wait(hs, timeout=60)
    srv.wait(timeout=60)
    tracer.stop()
    spans = _spans(_events())
    bursts = [s for s in spans if s[0].startswith("engine:")
              and s[0].endswith("EngineLoop._loop_task")]
    turns = [s for s in spans if s[0] == "serve.turn"]
    assert bursts and turns
    for t in turns:
        assert any(b[1] <= t[1] and t[2] <= b[2] and b[3] == t[3]
                   for b in bursts)
    srv.close()
