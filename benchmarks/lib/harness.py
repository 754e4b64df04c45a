"""What every kind shares: the cell's files found by name, the device
record, compile counters, and the result a kind hands back to run.py."""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest():
    return load_json(ROOT, "BENCHMARK.json")


def find_cell(name, bench=None):
    """The cell's manifest entry, configuration and traffic, by name."""
    bench = bench or manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(f"{name}: BENCHMARK.json says {cell['chips']} "
                         f"chip(s), the traffic file {traffic['chips']}")
    return cell, cfg, traffic


def metrics_for(name, group, bench=None):
    """The manifest's metrics of `group` that this cell reports."""
    bench = bench or manifest()
    return [m for m in bench[group]
            if "workloads" not in m or name in m["workloads"]]


def device_record():
    """The devices as jax reports them, with no mention of memory."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(n_devices):
    """(peak bytes on the fullest of the first `n_devices`, its parts).
    The TPU client counts live buffers (`peak_bytes_in_use`) and what
    compiled programs reserve for their temporaries
    (`peak_bytes_reserved`) apart; a chip holds both at once."""
    import jax
    best, parts = 0, {}
    for d in jax.devices()[:n_devices]:
        s = d.memory_stats() or {}
        total = int(s.get("peak_bytes_in_use", 0)) \
            + int(s.get("peak_bytes_reserved", 0))
        if total >= best:
            best = total
            parts = {k: int(s[k]) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
                if k in s}
    return best, parts


class CompileWatch:
    """Compilations between two points. `install()` (once, by the entry
    point) listens to jax's own monitoring stream, as the program's
    compile observatory does: every backend compile or load from the
    persistent cache reports its seconds there. Beside it, the program's
    count of persistent-cache hits and misses."""

    _events = {"n": 0, "seconds": 0.0}
    _installed = False

    @classmethod
    def install(cls):
        if cls._installed:
            return
        from jax._src import monitoring

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls._events["n"] += 1
                cls._events["seconds"] += duration

        monitoring.register_event_duration_secs_listener(on_duration)
        cls._installed = True

    def __init__(self):
        self.mark()

    @classmethod
    def _now(cls):
        from mxnet_tpu.observability import compilex
        hits, misses = compilex.compile_cache_stats()
        return hits, misses, cls._events["n"], cls._events["seconds"]

    def mark(self):
        self._at = self._now()

    def since(self):
        hits, misses, n, secs = (b - a for a, b in
                                 zip(self._at, self._now()))
        return {"cache_hits": hits, "cache_misses": misses,
                "compilations": n, "compile_s": secs}


def compiled(counts):
    """True if `CompileWatch.since()` saw any compile or cache lookup."""
    return bool(counts["compilations"] or counts["cache_hits"]
                or counts["cache_misses"])
