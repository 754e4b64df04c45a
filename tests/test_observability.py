"""Unified tracing & metrics subsystem (mx.observability + profiler wiring):
Chrome-trace schema, metrics registry semantics, engine/KVStore/Trainer
instrumentation, satellites (pause/resume, Scope tally, Monitor handles,
device-side numeric checks), and the disabled-path overhead smoke test."""
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, nd, profiler
from mxnet_tpu.observability import metrics_registry, registry, tracer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_trace  # noqa: E402

CHECK_TRACE = os.path.join(os.path.dirname(__file__), "..", "tools",
                           "check_trace.py")


@pytest.fixture(autouse=True)
def _quiesce_tracer():
    yield
    profiler._state["running"] = False
    profiler._state["jax_paused"] = False
    tracer.set_jax_annotation(False)
    tracer.stop()
    tracer.clear()


def _tiny_trainer(fused=True, kvstore="ici"):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    X = nd.array(np.random.randn(4, 6).astype(np.float32))
    net(X)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, fused=fused,
                       kvstore=kvstore)
    lossf = gluon.loss.L2Loss()
    y = nd.array(np.zeros((4, 4), np.float32))

    def step():
        with autograd.record():
            L = lossf(net(X), y).mean()
        L.backward()
        tr.step(4)
    return step


# ------------------------------------------------------------- tracer core
def test_chrome_trace_schema_valid(tmp_path):
    path = str(tmp_path / "profile.json")
    profiler.set_config(filename=path)
    profiler.start()
    with tracer.span("outer", args={"k": 1}):
        with tracer.span("inner"):
            tracer.instant("marker")
        tracer.counter("queue", 3)

    def worker():
        with tracer.span("worker-span"):
            pass
    t = threading.Thread(target=worker, name="obs-worker")
    t.start()
    t.join()
    profiler.stop()
    out = profiler.dump()
    assert out == path and os.path.exists(path)   # full path preserved
    assert check_trace.validate_file(path) == []
    trace = json.load(open(path))
    events = trace["traceEvents"]
    names = {e.get("name") for e in events}
    assert {"outer", "inner", "marker", "queue"} <= names
    # required keys + monotonic ts on the duration events
    body = [e for e in events if e["ph"] != "M"]
    for e in body:
        assert {"ph", "ts", "pid", "tid"} <= set(e)
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    assert len([e for e in body if e["ph"] == "B"]) == \
        len([e for e in body if e["ph"] == "E"])
    # per-thread tracks: worker span on its own tid with thread_name meta
    wtid = [e["tid"] for e in body if e.get("name") == "worker-span"][0]
    thread_names = [e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any("obs-worker" in n for n in thread_names)
    assert wtid != [e["tid"] for e in body if e.get("name") == "outer"][0]


def test_ring_buffer_bounded_and_balance_repaired(tmp_path):
    tracer.start(buffer_size=64)
    for i in range(500):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.events_recorded() <= 64
    path = tracer.dump(str(tmp_path / "ring.json"))
    assert check_trace.validate_file(path) == []   # orphan E repaired
    tracer.stop()


def test_check_trace_cli_and_rejects_malformed(tmp_path):
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"traceEvents": [
        {"ph": "B", "ts": 1, "pid": 1, "tid": 0, "name": "a"},
        {"ph": "E", "ts": 2, "pid": 1, "tid": 0},
    ]}))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "B", "ts": 5, "pid": 1, "tid": 0, "name": "a"},
        {"ph": "B", "ts": 4, "pid": 1, "tid": 0, "name": "b"},   # ts back
        {"ph": "X", "ts": 6, "pid": 1, "tid": 0, "name": "x"},   # no dur
    ]}))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    assert subprocess.run([sys.executable, CHECK_TRACE, str(good)],
                          env=env, capture_output=True).returncode == 0
    proc = subprocess.run([sys.executable, CHECK_TRACE, str(bad)],
                          env=env, capture_output=True, text=True)
    assert proc.returncode == 1
    assert "backwards" in proc.stderr and "unclosed" in proc.stderr
    assert check_trace.validate({"nope": 1}) != []
    errs = check_trace.validate_file(str(bad))
    assert any("dur" in e for e in errs)


# ------------------------------------------------------------- metrics
def test_metrics_registry_semantics(tmp_path):
    reg = metrics_registry.MetricsRegistry()
    c = reg.counter("requests", route="push")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("requests", route="push") is c      # cached handle
    c2 = reg.counter("requests", route="pull")             # labels split
    c2.inc()
    assert [m.value for m in reg.series("requests")] == [5, 1]
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7
    h = reg.histogram("lat")
    for v in (0.001, 0.002, 0.004, 0.4):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4 and abs(snap["sum"] - 0.407) < 1e-9
    assert snap["min"] == 0.001 and snap["max"] == 0.4
    assert 0.001 <= snap["p50"] <= 0.01 and snap["p99"] >= 0.1
    # quantile-snapshot satellite (ISSUE 6): p95 in the snapshot, and
    # quantiles() walks the buckets once for all requested points,
    # agreeing with the one-at-a-time quantile() estimates
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    qs = h.quantiles((0.5, 0.95, 0.99))
    assert qs == {0.5: h.quantile(0.5), 0.95: h.quantile(0.95),
                  0.99: h.quantile(0.99)}
    # empty histogram: quantiles are 0.0 (separate registry so this
    # test's series/JSONL counts below stay unchanged)
    empty = metrics_registry.MetricsRegistry().histogram("lat_empty")
    assert empty.quantiles((0.5,)) == {0.5: 0.0}
    full = reg.snapshot()
    assert {"requests", "depth", "lat"} <= set(full)
    assert {s["labels"]["route"] for s in full["requests"]} == \
        {"push", "pull"}
    # kind conflict on the same (name, labels) is an error
    with pytest.raises(TypeError):
        reg.gauge("requests", route="push")
    # JSONL sink: one line per series, parseable
    p = str(tmp_path / "metrics.jsonl")
    reg.dump_jsonl(p)
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 4
    assert {ln["name"] for ln in lines} == {"requests", "depth", "lat"}
    # reset zeroes values but keeps handles valid
    reg.reset()
    assert c.value == 0 and g.value is None and h.count == 0
    c.inc()
    assert reg.counter("requests", route="push").value == 1


def test_profiler_counters_ride_the_registry():
    profiler.reset_dispatches()
    profiler.record_dispatch("unit_test_site", 3)
    profiler.record_jit_cache(True)
    assert profiler.dispatch_count("unit_test_site") == 3
    assert profiler.jit_cache_stats() == (1, 0)
    snap = registry().snapshot()
    sites = {s["labels"]["site"]: s["value"] for s in snap["dispatch"]}
    assert sites["unit_test_site"] == 3
    assert "[dispatch] unit_test_site=3" in profiler.dumps()
    profiler.dumps(reset=True)
    assert profiler.dispatch_count() == 0
    assert profiler.jit_cache_stats() == (0, 0)
    assert "[dispatch]" not in profiler.dumps()


# ------------------------------------------------------------- engine
def test_engine_queue_depth_gauge_under_concurrent_push():
    gauge = registry().gauge("engine_queue_depth")
    busy = registry().counter("engine_busy_seconds")
    engine.wait_for_all()
    assert gauge.value == 0
    busy0 = busy.value
    release = threading.Event()
    seen = []

    def pusher():
        engine.push(lambda: (release.wait(5), seen.append(1)))

    threads = [threading.Thread(target=pusher) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert gauge.value == 6          # all queued/blocked, none finished
    release.set()
    engine.wait_for_all()
    assert gauge.value == 0
    assert len(seen) == 6
    assert busy.value > busy0        # worker busy time accumulated
    assert registry().gauge("engine_workers").value >= 1


def test_engine_task_span_named_by_dispatch_site(tmp_path):
    tracer.start()

    def my_io_task():
        return 42
    fut = engine.push(my_io_task)
    engine.wait_for_all()
    assert fut.result() == 42
    path = tracer.dump(str(tmp_path / "engine.json"))
    tracer.stop()
    assert check_trace.validate_file(path) == []
    names = [e.get("name") for e in json.load(open(path))["traceEvents"]]
    assert any(n and n.startswith("engine:") and "my_io_task" in n
               for n in names)
    # var-wait latency histogram observed something
    v = engine.Var()
    engine.push(lambda: time.sleep(0.01), write_vars=[v])
    engine.wait_for_var(v)
    assert registry().histogram("engine_var_wait_seconds").count >= 1


# ------------------------------------------------------------- kvstore
def test_kvstore_collective_span_labels(tmp_path):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = np.asarray(jax.devices())
    if devs.size < 2:
        pytest.skip("needs the multi-device CPU mesh")
    mesh = Mesh(devs, ("dp",))
    kv = mx.kv.create("ici").set_mesh(mesh)
    n = devs.size
    stacked = jax.device_put(np.ones((n, 4), np.float32),
                             NamedSharding(mesh, P("dp")))
    bytes0 = registry().counter("kv_collective_bytes",
                                op="psum_stacked").value
    tracer.start()
    out = kv.allreduce_([stacked], layout="stacked")
    kv.allreduce_flat([np.ones((3,), np.float32)] * 2)
    path = tracer.dump(str(tmp_path / "kv.json"))
    tracer.stop()
    np.testing.assert_allclose(np.asarray(out), np.full((4,), n))
    assert check_trace.validate_file(path) == []
    events = json.load(open(path))["traceEvents"]
    span = [e for e in events if e.get("name") == "kv.psum_stacked"
            and e["ph"] == "B"][0]
    assert span["args"]["bytes"] == n * 4 * 4
    assert span["args"]["devices"] == n
    assert span["args"]["axis"] == "dp"
    flat = [e for e in events if e.get("name") == "kv.allreduce_flat"
            and e["ph"] == "B"][0]
    assert flat["args"]["arrays"] == 2 and flat["args"]["bytes"] == 24
    # always-on byte accounting moved too
    assert registry().counter("kv_collective_bytes",
                              op="psum_stacked").value - bytes0 == n * 16


# ------------------------------------------------- trainer + acceptance
def test_train_steps_produce_valid_trace_with_all_span_kinds(tmp_path):
    path = str(tmp_path / "profile.json")
    step = _tiny_trainer()
    step()                                   # warm compile outside trace
    profiler.set_config(filename=path)
    tracer.set_op_sample_rate(2)             # tiny net: few imperative ops
    try:
        profiler.start()
        for _ in range(3):
            step()
        engine.push(lambda: None)
        engine.wait_for_all()
        profiler.stop()
    finally:
        tracer.set_op_sample_rate(16)
    assert profiler.dump() == path
    assert check_trace.validate_file(path) == []
    events = json.load(open(path))["traceEvents"]
    names = [e.get("name") for e in events if e["ph"] in "BX"]
    steps = [e for e in events if e.get("name") == "Trainer.step"
             and e["ph"] == "B"]
    assert len(steps) == 3
    assert steps[0]["args"] == {"batch_size": 4, "params": 4, "fused": True}
    assert any(n == "Trainer.fused_bucket" for n in names)
    assert any(n == "Trainer.allreduce_grads" for n in names)
    assert any(n == "kv.allreduce_flat" for n in names)   # collective span
    assert any(n and n.startswith("engine:") for n in names)
    assert any(n and n.startswith("nd.") for n in names)   # sampled ops
    # gauges fed by the instrumented step
    assert registry().gauge("trainer_steps_per_s").value > 0
    # set async on the step path; snapshot coerces the device scalar
    norm = registry().gauge("trainer_grad_norm").snapshot()
    assert isinstance(norm, float) and norm >= 0
    assert registry().counter("trainer_steps").value >= 4
    rep = mx.observability.summary()
    assert "Trainer.step" in rep and "trainer_steps_per_s" in rep


def test_compile_spans_in_trace_and_summary(tmp_path):
    """ISSUE 11 satellite: a compile that happens while tracing lands a
    `compile.<executable>` span the Chrome-trace validator accepts
    (balanced like every other track — 'X' events carry their own dur),
    the compile/HLO series ride the registry with p95s in snapshot and
    summary(), and profiler.dumps() prints the [compile] breakdown."""
    path = str(tmp_path / "compile_trace.json")
    rng = np.random.RandomState(3)
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
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    tracer.start()
    step(X, y)                           # compiles INSIDE the trace
    step(X, y)
    tracer.stop()
    assert tracer.dump(path) == path
    assert check_trace.validate_file(path) == []
    events = json.load(open(path))["traceEvents"]
    comp = [e for e in events if str(e.get("name", ""))
            .startswith("compile.")]
    assert comp, "no compile span recorded"
    assert comp[0]["ph"] == "X" and comp[0]["dur"] > 0
    assert comp[0]["args"]["executable"] == "captured_step"
    # registry: compile_seconds histogram with a p95 in its snapshot
    snap = registry().snapshot()
    series = [s for s in snap["compile_seconds"]
              if dict(s["labels"]).get("executable") == "captured_step"]
    assert series and series[0]["value"]["count"] >= 1
    assert "p95" in series[0]["value"]
    # summary() and profiler.dumps() render the new families
    rep = mx.observability.summary()
    assert "compile_seconds" in rep
    dump = profiler.dumps()
    assert "[compile] captured_step:" in dump and "p95=" in dump


def test_sampled_op_spans_feed_host_tally(tmp_path):
    tracer.set_op_sample_rate(1)             # deterministic: every op
    try:
        profiler.set_config(filename=str(tmp_path / "p.json"))
        profiler.start()
        (nd.ones((4,)) + 1).asnumpy()
        profiler.stop()
        assert "nd." in profiler.dumps()     # Scope/op tally sees ops now
    finally:
        tracer.set_op_sample_rate(16)
        profiler.dumps(reset=True)


def test_disabled_path_overhead_smoke():
    """With tracing off the instrumented paths reduce to one module-attr
    check; nothing records, and a trainer step still runs full speed."""
    assert not tracer.ACTIVE
    step = _tiny_trainer()
    step()
    before = tracer.events_recorded()
    t0 = time.perf_counter()
    for _ in range(3):
        step()
    wall = time.perf_counter() - t0
    assert tracer.events_recorded() == before == 0
    # the disabled fast path itself: ~1e5 gate checks in well under a
    # second even on a loaded CI box (generous 50x headroom)
    t0 = time.perf_counter()
    for _ in range(100_000):
        if tracer.ACTIVE:
            raise AssertionError
    assert time.perf_counter() - t0 < 1.0
    assert wall < 60.0


# ------------------------------------------------------------- satellites
def test_pause_resume_suspends_both_traces(tmp_path):
    path = str(tmp_path / "profile.json")
    profiler.set_config(filename=path)
    profiler.start()
    with tracer.span("before-pause"):
        pass
    profiler.pause()
    assert not tracer.ACTIVE
    assert not profiler._state["jax_trace"]    # device trace closed too
    with tracer.span("while-paused"):
        pass
    profiler.resume()
    assert tracer.ACTIVE
    with tracer.span("after-resume"):
        pass
    profiler.stop()
    profiler.dump()
    names = {e.get("name")
             for e in json.load(open(path))["traceEvents"]}
    assert "before-pause" in names and "after-resume" in names
    assert "while-paused" not in names
    # stop() must finalize FROM the paused state too (stale jax_paused
    # would let a later resume() silently reopen recording)
    profiler.start()
    profiler.pause()
    profiler.stop()
    assert not tracer.ACTIVE
    assert not profiler._state["jax_paused"]
    # resume() after stop() must NOT silently reopen recording
    profiler.resume()
    assert not tracer.ACTIVE and not profiler._state["running"]


def test_set_config_preserves_full_target_path(tmp_path):
    target = tmp_path / "nested" / "dir" / "my_trace.json"
    profiler.set_config(filename=str(target))
    profiler.start()
    profiler.stop()
    assert profiler.dump() == str(target)
    assert target.exists()                    # not truncated to the dir


def test_scope_records_into_host_tally(tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.start()
    with profiler.Scope("my_region"):
        time.sleep(0.002)
    profiler.stop()
    dump = profiler.dumps(reset=True)
    line = [ln for ln in dump.splitlines() if ln.startswith("my_region")]
    assert line and int(line[0].split()[1]) == 1
    assert float(line[0].split()[2]) >= 1.0   # >= 1ms recorded


def test_monitor_handles_removable():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(3), gluon.nn.Dense(2))
    net.initialize()
    X = nd.ones((2, 4))
    net(X)
    mon = mx.monitor.Monitor(1, pattern=".*").install(net)
    assert len(mon.handles) >= 3              # root + children
    mon.tic()
    net(X)
    assert len(mon.toc()) > 0
    mon.remove()
    assert mon.handles == []
    assert net._forward_hooks == []           # actually detached
    mon.tic()
    net(X)
    assert mon.toc() == []
    mon.remove()                              # idempotent


def test_hook_handle_detach():
    from mxnet_tpu.gluon.utils import HookHandle
    net = gluon.nn.Dense(2)
    calls = []
    h = net.register_forward_hook(lambda b, i, o: calls.append(1))
    assert isinstance(h, HookHandle)
    net.initialize()
    net(nd.ones((1, 3)))
    assert calls == [1]
    h.detach()
    h.detach()
    net(nd.ones((1, 3)))
    assert calls == [1]
    with net.register_forward_pre_hook(lambda b, i: calls.append(2)):
        net(nd.ones((1, 3)))
    assert calls == [1, 2]
    net(nd.ones((1, 3)))                      # context exit detached it
    assert calls == [1, 2]


def test_check_numerics_on_device():
    ok = nd.array(np.array([1.0, 2.0], np.float32))
    assert mx.monitor.check_numerics(ok, "w") is ok
    ints = nd.array(np.array([1, 2], np.int32))
    assert mx.monitor.check_numerics(ints, "i") is ints
    bad = nd.array(np.array([1.0, np.nan, np.inf], np.float32))
    with pytest.raises(mx.MXNetError, match="1 NaN and 1 Inf"):
        mx.monitor.check_numerics(bad, "g")
    with pytest.raises(mx.MXNetError, match="plain has"):
        mx.monitor.check_numerics(np.array([np.nan]), "plain")


def test_nan_detector_scans_without_host_pull():
    net = gluon.nn.Dense(2)
    net.initialize()
    X = nd.ones((1, 3))
    with autograd.record():
        L = net(X).sum()
    L.backward()
    det = mx.monitor.NanDetector(net.collect_params())
    assert det.check()
    p = list(net.collect_params().values())[0]
    p._grad._rebind(p._grad._data * np.nan)
    with pytest.raises(mx.MXNetError, match="_grad"):
        det.check()


# ------------------------------------- ISSUE 26: step phases and op scopes
def _captured_dropout_step(rate=0.5, mesh=None):
    rng = np.random.RandomState(5)
    X = nd.array(rng.randn(8, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 4, 8).astype(np.float32))
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dropout(rate),
            gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(X)
    opt = {"learning_rate": 1e-3, "wd": 0.01}
    if mesh is None:
        tr = gluon.Trainer(net.collect_params(), "adamw", opt)
    else:
        tr = gluon.Trainer(net.collect_params(), "adamw", opt, kvstore="ici")
        tr.shard(mesh=mesh)
    return tr.capture(lambda a, b: lossf(net(a), b).mean()), X, y


def test_captured_step_host_phases_lie_inside_the_step_span():
    step, X, y = _captured_dropout_step()
    step(X, y)                                   # compile outside the trace
    tracer.start()
    step(X, y)
    step(X, y)
    tracer.stop()
    events = [e for e in tracer.to_chrome_trace()["traceEvents"]
              if e["ph"] in "BE"]
    spans, stack = [], []
    for e in events:
        if e["ph"] == "B":
            stack.append(e)
        else:
            b = stack.pop()
            spans.append((b["name"], b["ts"], e["ts"], len(stack)))
    outer = [s for s in spans if s[0] == "Trainer.captured_step"]
    assert len(outer) == 2
    phases = ["Trainer.step_key", "Trainer.step_stage",
              "Trainer.step_launch", "Trainer.step_writeback"]
    for _, t0, t1, depth in outer:
        kids = sorted((s for s in spans
                       if s[3] == depth + 1 and t0 <= s[1] and s[2] <= t1),
                      key=lambda s: s[1])
        assert [k[0] for k in kids] == phases
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    tracer.clear()
    step(X, y)                                   # tracer off: nothing kept
    assert tracer.events_recorded() == 0


def test_op_scopes_name_the_update_and_dropout_of_a_captured_step(
        monkeypatch):
    from mxnet_tpu.observability import compilex
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    step, X, y = _captured_dropout_step()
    step(X, y)
    scopes = step.hlo_info()["op_scopes"]
    held = {s for v in scopes.values() for s in v}
    assert held == {"mx_update", "mx_dropout"}
    assert all(v == tuple(sorted(v)) and v for v in scopes.values())
    # the wrapper dies with the step; the inspection outlives it
    del step
    assert compilex.last_inspections()["captured_step"]["op_scopes"] \
        == scopes


def _lowered_text(step):
    """The program as jax hands it to XLA: there the bit generator is still
    one op (the CPU's compiler expands it; the TPU's keeps it)."""
    ij = step._cache[step._last_key][0]
    args, kwargs = ij.last_abstract
    return ij.lower(*args, **kwargs).as_text()


def test_mx_dropout_holds_the_bit_generator_and_no_threefry():
    step, X, y = _captured_dropout_step()
    step(X, y)
    funcs = dict(re.findall(
        r"func\.func \w+ @([\w.]+)\((.*?)\n  \}", _lowered_text(step), re.S))
    # forward and backward of the one site, and what they call
    held = [n for n in funcs if n.startswith("mx_dropout")]
    assert len(held) == 2
    for n in held:
        held += [c for c in re.findall(r"call @([\w.]+)", funcs[n])
                 if c not in held]
    body = "\n".join(funcs[n] for n in held)
    assert body.count("stablehlo.rng_bit_generator") == 1
    assert "threefry" not in body
    # the site's key still comes from the step's by a threefry split
    assert any("threefry" in n for n in funcs)


def test_dropout_adds_no_collective_to_a_dp4_step(monkeypatch):
    import jax
    from mxnet_tpu.shard import as_mesh
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")

    def collectives(rate):
        step, X, y = _captured_dropout_step(
            rate, as_mesh((4, 1), devices=jax.devices()[:4]))
        assert np.isfinite(float(step(X, y).asnumpy()))
        assert ("stablehlo.rng_bit_generator" in _lowered_text(step)) \
            == (rate > 0)
        return step.hlo_info()["collectives"]

    assert collectives(0.1) == collectives(0.0)


def test_op_scopes_gives_a_fusion_the_scopes_of_what_it_calls():
    from mxnet_tpu.observability import compilex
    text = """HloModule jit_program, entry_computation_layout={()->f32[4]}

FunctionNames
1 "mx_not_an_instruction"

%fused_computation.7 (param_0.1: f32[4], param_1.2: u32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %param_1.2 = u32[4]{0} parameter(1)
  %convert.3 = f32[4]{0} convert(%param_1.2), metadata={op_name="jit(program)/jvp(mx_dropout)/jit(_bernoulli)/convert"}
  %mul.9 = f32[4]{0} multiply(%param_0.1, %convert.3), metadata={op_name="jit(program)/transpose(jvp(mx_dropout))/mul" source_file="/x/mx_other.py"}
  ROOT %add.2 = f32[4]{0} add(%mul.9, %param_0.1), metadata={op_name="jit(program)/mx_update/cond/branch_1_fun/add"}
}

%fused_computation.8 (param_0.3: f32[4]) -> f32[4] {
  %param_0.3 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0.3), metadata={op_name="jit(program)/jvp(dense)/neg"}
}

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.6 = f32[] add(%a, %b), metadata={op_name="jit(program)/reduce_sum"}
}

%body.9 (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element(%c), index=1
  %fusion.18 = f32[4]{0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(program)/while/body/jit(mx_moe)/jit(mx_moe_route)/neg"}
  %call.3 = f32[4]{0} call(%fusion.18), to_apply=%callee.2
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%c, %call.3)
}

%callee.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%x), metadata={op_name="jit(program)/while/body/LONG"}
}

%cond.9 (c.1: (s32[], f32[4])) -> pred[] {
  %c.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true), metadata={op_name="jit(program)/while/cond/lt"}
}

%branch_a.1 (t: f32[4]) -> f32[4] {
  %t = f32[4]{0} parameter(0)
  ROOT %sin.1 = f32[4]{0} sine(%t), metadata={op_name="jit(program)/cond/branch_0_fun/sin"}
}

%branch_b.1 (u: f32[4]) -> f32[4] {
  %u = f32[4]{0} parameter(0)
  ROOT %cos.1 = f32[4]{0} cosine(%u), metadata={op_name="jit(program)/cond/branch_1_fun/cos"}
}

ENTRY %main.20 (p0: f32[4], p1: u32[4]) -> f32[4] {
  %p0 = f32[4]{0:T(128)} parameter(0)
  %p1 = u32[4]{0:T(128)} parameter(1)
  %fusion.16 = f32[4]{0:T(128)} fusion(%p0, %p1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/mx_update/cond/branch_1_fun/add"}
  %fusion.17 = f32[4]{0:T(128)} fusion(%fusion.16), kind=kLoop, calls=%fused_computation.8
  %copy.4 = f32[4]{0:T(128)} copy(%fusion.17), metadata={op_name="jit(program)/mx_update/copy"}
  %reduce.7 = f32[] reduce(%copy.4, %p0), dimensions={0}, to_apply=%region_0.5, metadata={op_name="jit(program)/reduce_sum"}
  %while.8 = (s32[], f32[4]{0}) while(%copy.4), condition=%cond.9, body=%body.9, metadata={op_name="jit(program)/while"}
  %conditional.9 = f32[4]{0} conditional(%p1, %copy.4, %copy.4), branch_computations={%branch_a.1, %branch_b.1}, metadata={op_name="jit(program)/cond"}
  ROOT %tanh.5 = f32[4]{0:T(128)} tanh(%copy.4), metadata={op_name="jit(program)/tanh" source_file="/x/mx_update.py"}
}
""".replace("LONG", "x" * 200)
    got = compilex.inspect_hlo_text(text)
    assert got["fusions"] == 3
    assert got["op_scopes"] == {
        "fusion.16": ("mx_dropout", "mx_update"),    # mixed: under both
        "convert.3": ("mx_dropout",), "mul.9": ("mx_dropout",),
        "add.2": ("mx_update",), "copy.4": ("mx_update",),
        # a scope inside another is held with it: the path names both
        "fusion.18": ("mx_moe", "mx_moe_route")}
    assert compilex.op_scopes("") == {}
    # the module's name, as the profiler prints it on `XLA Modules`
    assert got["module"] == "jit_program"
    assert compilex.inspect_hlo_text("")["module"] is None
    # where each instruction a trace can show came from: the entry's, and
    # the bodies of while / conditional / call; not a fusion's inside
    # (convert.3, neg.1), not a reducer's (add.6); cut to 120 characters
    names = got["op_names"]
    assert names == {
        "fusion.16": "jit(program)/mx_update/cond/branch_1_fun/add",
        "copy.4": "jit(program)/mx_update/copy",
        "reduce.7": "jit(program)/reduce_sum",
        "while.8": "jit(program)/while", "conditional.9": "jit(program)/cond",
        "tanh.5": "jit(program)/tanh",
        # a fusion without metadata of its own takes its root's
        "fusion.17": "jit(program)/jvp(dense)/neg",
        "fusion.18":
            "jit(program)/while/body/jit(mx_moe)/jit(mx_moe_route)/neg",
        "exp.1": ("jit(program)/while/body/" + "x" * 200)[:120],
        "lt.1": "jit(program)/while/cond/lt",
        "sin.1": "jit(program)/cond/branch_0_fun/sin",
        "cos.1": "jit(program)/cond/branch_1_fun/cos"}
    assert len(names["exp.1"]) == compilex.OP_NAME_CHARS == 120
    assert compilex.op_names("") == {}


def test_op_names_are_parsed_when_an_inspection_is_first_read(monkeypatch):
    """A compile leaves the module text compressed; `op_names` is parsed
    from it when `last_hlo` / `last_inspections()` is first read (a
    training step's parse at compile time moved the host collector's
    pauses into its steps: ISSUE 36's chip runs)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import compilex
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    monkeypatch.setattr(compilex, "_inspections", {})
    fn = compilex.instrument(jax.jit(lambda x: jnp.tanh(x) * 2),
                             "test_deferred_names")
    fn(jnp.ones((8, 8)))
    kept = compilex._inspections["test_deferred_names"]
    assert "op_names" not in kept and isinstance(kept["hlo_z"], bytes)
    assert kept["module"].startswith("jit_") and kept["fusions"] >= 0
    got = compilex.last_inspections()["test_deferred_names"]
    assert got is kept and "hlo_z" not in got
    assert any(v.startswith("jit(") for v in got["op_names"].values())
    assert fn.last_hlo["op_names"] == got["op_names"]
    # the pure function and the gates' `analyze_jit` parse at once
    eager = compilex.analyze_jit(fn, jnp.ones((8, 8)))
    assert eager["op_names"] == got["op_names"] and "hlo_z" not in eager


@pytest.mark.parametrize("committed", [False, True],
                         ids=["uncommitted", "committed"])
def test_the_inspection_reads_the_executable_the_call_compiled(monkeypatch,
                                                               committed):
    """An instrumented call that compiles is inspected from the
    executable the call compiled: ONE backend compile (or, with a
    persistent cache, one load) for the call and its inspection together,
    whether the arguments are committed to a device or not, donated
    ones included. An inspection that named a sharding the call never saw
    compiled the program again: each serving program was loaded twice in
    set-up."""
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring
    from mxnet_tpu.observability import compilex
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    put = (lambda a: jax.device_put(a, jax.devices()[0])) if committed \
        else (lambda a: a)
    fn = compilex.instrument(
        jax.jit(lambda s, x, n: ({"a": s["a"] + x.sum() * n}, x * 2),
                donate_argnums=(0,)), f"test_one_compile_{committed}")
    args = ({"a": put(jnp.zeros((4,)))}, put(jnp.ones((8,))),
            put(jnp.int32(3)))
    seen = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    monitoring.register_event_duration_secs_listener(on)
    try:
        fn(*args)
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert fn.compile_count == 1 and fn.last_hlo["fusions"] >= 0
    assert len(seen) == 1


def test_a_recording_starts_with_its_own_ring_and_thread_names():
    # neither the ring size one caller asked for nor the name of a thread
    # that has exited (idents are reused) outlives its recording
    tracer.start(buffer_size=8)
    t = threading.Thread(target=lambda: tracer.instant("early"),
                         name="gone-by-then")
    t.start()
    t.join()
    tracer.stop()
    tracer.start()
    for i in range(100):
        tracer.instant(f"i{i}")
    tracer.stop()
    assert tracer.events_recorded() == 100
    names = [e["args"]["name"] for e in
             tracer.to_chrome_trace()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert names and "gone-by-then" not in names
    tracer.clear()
