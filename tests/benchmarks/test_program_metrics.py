"""The readers ISSUE 36 added, on a tiny backlog run on the CPU (as
`test_span_metrics.test_the_serve_readers_on_a_tiny_backlog_run` does for
the older ones): the two host readers read the scheduler's new spans, and
the device readers, which have no device plane to read here, find the
program's side of their join in place."""
from benchmarks.lib import program_share, span_reduce as sr, trace_reduce as tr
from benchmarks.metrics import (cross_attn_share_pct, decode_launch_ms,
                                host_busy_pct, moe_dispatch_share_pct,
                                prefill_share_pct, unscoped_share_pct)


def test_the_new_readers_on_a_tiny_backlog_run(monkeypatch, capsys):
    from test_serve_kinds import _ctx
    from benchmarks.kinds import serve_backlog
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    ctx, log = _ctx("wmt_backlog", trace=True)
    out = serve_backlog.run(ctx)
    assert out["problems"] == [], (out["problems"], log)
    ts = out["trace"]
    info = {"window": ts.window, "workload": "tiny"}
    args = (ts.events, ts.spans, out["counters"], info)

    # the host's side: a loaded machine may fit no whole turn into the
    # slice of one second
    reads = sr.named(ts.spans, "serve.decode_read")
    launches = sr.named(ts.spans, "serve.decode_launch")
    steps = sr.named(ts.spans, "serve.decode_step")
    assert reads and launches
    # (the slice's edges may cut a step away from under its child)
    for turn in sr.named(ts.spans, "serve.turn")[1:-1]:
        held = sr.inside(steps, turn)
        for child in sr.inside(reads + launches, turn):
            assert any(child in sr.inside(ts.spans, s) for s in held)
    assert all(s[3]["lookahead"] in (0, 1) for s in reads)
    # four slots and twelve requests outstanding: turns ran in flight
    assert any(s[3]["lookahead"] for s in reads)
    whole = [t for t in sr.named(ts.spans, "serve.turn")[1:-1]
             if sr.inside(reads, t)]
    busy = host_busy_pct.reduce(*args)
    assert (busy is None and not whole) or 0 < busy <= 100
    # the mirrored annotations put both children on the trace's clock,
    # where `idle_gaps` gives a gap to the innermost span
    names = {e[2] for e in tr.host_spans(ts.events)}
    assert {"serve.decode_launch", "serve.decode_read"} <= names
    launch = decode_launch_ms.reduce(*args)
    assert 0 < launch < max(s[2] for s in steps) / 1e3

    # the device's side: both programs say which module they are and hold
    # their scopes; without a device plane the readers find nothing
    found = program_share.inspections()
    assert found["serve_decode"]["module"] == "jit__decode_program"
    assert found["serve_prefill"]["module"] == "jit__prefill_program"
    held = {s for v in found["serve_decode"]["op_scopes"].values()
            for s in v}
    assert "mx_cross_attn" in held
    capsys.readouterr()
    for reader in (prefill_share_pct, moe_dispatch_share_pct,
                   cross_attn_share_pct, unscoped_share_pct):
        assert reader.reduce(*args) is None
    said = capsys.readouterr().out
    assert "[bench tiny] inspected serve_decode (jit__decode_program): " \
        in said
    assert "rows sum to" not in said
