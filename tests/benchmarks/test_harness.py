"""The benchmark's manifest and harness, without a chip: BENCHMARK.json
obeys the driver's rules of form, every cell's files are found by name,
the `train_job` runner runs a tiny configuration on the CPU through its
own functions, and the command line refuses a non-TPU backend. (The serve
kinds: test_serve_kinds.py.)"""
import importlib
import os
import re
import subprocess
import sys

import pytest

from benchmarks.lib import harness

ROOT = harness.ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = harness.manifest()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _line(s, lo=1, hi=200):
    return lo <= len(s) <= hi and s.isprintable() and "\t" not in s


def test_manifest_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    files = [c["file"] for c in BENCH["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        body = harness.load_json(ROOT, c["file"])
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not re.search(
                r"(_dim|_rank|hidden|intermediate|d_model|ffn)", key)
        for key in ("assumed", "deployment"):
            assert key in body


def test_workloads():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"]), (w["name"], len(w["why"]))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    assert len(e2e) == len(BENCH["end_to_end"])
    assert len(layer) == len(BENCH["per_layer"])
    assert not set(e2e) & set(layer)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        # the metric it moves is reported wherever this one is
        cells = set(m.get("workloads", CELLS))
        assert cells <= set(e2e[m["moves"]].get("workloads", CELLS))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for cell in CELLS:
        mine = harness.metrics_for(cell, "end_to_end", BENCH)
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert harness.metrics_for(cell, "per_layer", BENCH)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    entry, cfg, traffic = harness.find_cell(cell, BENCH)
    assert cfg["name"] == entry["config"]
    kind = importlib.import_module(f"benchmarks.kinds.{traffic['kind']}")
    assert callable(kind.run)
    importlib.import_module(f"benchmarks.reference.{cfg['name']}")
    for m in harness.metrics_for(cell, "per_layer", BENCH):
        reader = importlib.import_module(
            "benchmarks.metrics." + m["name"].split(".", 1)[0])
        assert callable(reader.reduce)
    for p in BENCH["paths"]:
        for dirpath, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                assert PATH.match(os.path.relpath(
                    os.path.join(dirpath, f), ROOT))


def test_peaks_table_refuses_an_unknown_device():
    from benchmarks.lib import flops
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    cfg = harness.load_json(ROOT, "benchmarks/configs/bert_base.json")
    per_token = flops.bert_train_flops_per_token(cfg, 512, 76)
    assert 0.58e9 < per_token < 0.60e9
    ops, nbytes = flops.flash_train_cost(32, 12, 512, 64)
    assert ops == 14 * 32 * 12 * 512 * 512 * 64
    assert flops.least_seconds(ops, nbytes, flops.peaks("TPU v5 lite"))[1] \
        == "compute"


def _tiny_ctx(cfg, traffic, say, trace=False):
    import time
    import jax
    harness.CompileWatch.install()
    return {"cell": {"name": "tiny", "chips": 1}, "config": cfg,
            "traffic": traffic, "seed": 3000000019, "seconds": 1.0,
            "trace": trace, "say": say,
            "t_start": time.perf_counter(),
            "device": {"kind": "TPU v5 lite"}, "devices": jax.devices()}


def test_train_job_runs_a_tiny_configuration(monkeypatch):
    """The narrowest shapes the kernel branches admit (interpret mode),
    through the kind's own run(): counts are right, the loss agrees with
    the reference, nothing compiles in the window, and a traced slice
    hands the readers the program's spans."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from benchmarks.kinds import train_job
    from benchmarks.lib import trace_reduce as tr
    cfg = harness.load_json(ROOT, "benchmarks/configs/bert_base.json")
    cfg.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
               intermediate_size=256, vocab_size=512)
    traffic = harness.load_json(ROOT,
                                "benchmarks/traffic/pretrain_s512.json")
    traffic.update(batch=2, seq=128, masked=8, valid_length=[96, 128],
                   trace_steps=8)
    log = []
    out = train_job.run(_tiny_ctx(cfg, traffic, log.append, trace=True))
    assert out["problems"] == [], (out["problems"], log)
    assert out["attempted"] % traffic["log_every"] == 0
    assert out["attempted"] >= 3 * traffic["log_every"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["counters"]["window"]["compilations"] == 0
    ts = out["trace"]
    t0, t1 = ts.window
    assert len(tr.host_spans(ts.events, "Trainer.captured_step")) == 8
    assert any(s[0] == "Trainer.captured_step" for s in ts.spans)
    # no device plane on the CPU: the readers find nothing and say so
    from benchmarks.metrics import device_idle_pct, flash_roofline
    info = {"window": (t0, t1), "config": cfg, "traffic": traffic,
            "chips": 1, "device": {"kind": "TPU v5 lite"}}
    assert flash_roofline.reduce(ts.events, ts.spans, out["counters"],
                                 info) is None
    assert device_idle_pct.reduce(ts.events, ts.spans, out["counters"],
                                  info) == 100.0


def test_importing_the_benchmark_touches_no_device():
    """Every module of the benchmark imports without importing the
    program and without initialising a jax backend, so none asks for a
    device or a topology while it is imported."""
    code = (
        "import sys, os, importlib; sys.path.insert(0, %r)\n"
        "for d, _, fs in os.walk(os.path.join(%r, 'benchmarks')):\n"
        "    for f in fs:\n"
        "        if f.endswith('.py') and f != '__init__.py':\n"
        "            rel = os.path.relpath(os.path.join(d, f), %r)\n"
        "            importlib.import_module(rel[:-3].replace(os.sep, '.'))\n"
        "assert 'mxnet_tpu' not in sys.modules\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        % (ROOT, ROOT, ROOT))
    proc = subprocess.run([sys.executable, "-c", code],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]


@pytest.mark.parametrize("cell", CELLS[:1] + CELLS[-1:])
def test_command_refuses_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, *BENCH["command"][1:]),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace",
         "0"], stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    for line in proc.stdout.decode().splitlines():
        assert not line.startswith("{"), line
    assert "refusing" in proc.stderr.decode()
