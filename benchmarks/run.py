"""The benchmark's entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and, by name, its files:
configs/<config>.json, traffic/<traffic>.json, kinds/<kind>.py (the
traffic file names its kind), and with --trace 1 metrics/<metric>.py for
each per-layer metric of the cell (the part of the metric's name before
its first dot names the file). The last line of stdout is the result;
earlier lines are the run's log. Needs a TPU with the chips the cell asks
for: without one it exits 1 before any metric. See README.md.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # before any heavy import: set-up
                                    # counts from the start of the process
import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.lib import harness
    bench = harness.manifest()
    cell, cfg, traffic = harness.find_cell(args.workload, bench)
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    def say(msg):
        print(f"[bench {cell['name']}] {msg}", flush=True)

    device = harness.device_record()
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"[bench {cell['name']}] refusing: the cell needs "
              f"{cell['chips']} TPU chip(s), jax reports {device}",
              file=sys.stderr)
        return 1

    import jax
    from mxnet_tpu.observability import compilex
    cache = compilex.entry_compilation_cache(ROOT)
    harness.CompileWatch.install()
    say(f"device {device}; compile cache {cache}; kind {traffic['kind']}; "
        f"seed {args.seed}; window {seconds} s; trace {args.trace}")

    kind = importlib.import_module(f"benchmarks.kinds.{traffic['kind']}")
    out = kind.run({
        "cell": cell, "config": cfg, "traffic": traffic, "seed": args.seed,
        "seconds": float(seconds), "trace": bool(args.trace), "say": say,
        "t_start": T_START, "device": device,
        "devices": jax.devices()})

    setup = out["counters"]["setup"]
    say(f"set-up {out['setup_s']:.2f} s; compile cache hits "
        f"{setup['cache_hits']}, misses {setup['cache_misses']}; "
        f"compile or load seconds in set-up {setup['compile_s']:.2f}")
    for p in out["problems"]:
        say(f"PROBLEM: {p}")

    peak, parts = harness.memory_peak(cell["chips"])
    dev = {**device, "memory_peak_bytes": peak, "memory_parts": parts}
    values = {"setup_s": out["setup_s"], **out["end_to_end"]}
    # the contract's keys first, in its order
    result = {"correct": not out["problems"],
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": None, "device": dev}
    if args.trace:
        from benchmarks.lib import trace_reduce as tr
        ts = out["trace"]
        t0, t1 = ts.window
        dev["busy_s"] = tr.busy_seconds(ts.events, t0, t1)
        dev["window_s"] = (t1 - t0) / 1e9
        say(f"traced slice {dev['window_s']:.3f} s, device busy "
            f"{dev['busy_s']:.3f} s; {len(ts.events)} events reduced in "
            f"{ts.reduce_s:.1f} s")
        for name, v in values.items():
            say(f"under tracing, {name} = {v} (an untraced run's is the "
                f"metric; the difference is what tracing costs)")
        info = {"workload": cell["name"], "config": cfg, "traffic": traffic,
                "device": device, "chips": cell["chips"], "window": (t0, t1)}
        metrics = {}
        for m in harness.metrics_for(cell["name"], "per_layer", bench):
            reader = importlib.import_module(
                "benchmarks.metrics." + m["name"].split(".", 1)[0])
            v = reader.reduce(ts.events, ts.spans, out["counters"], info)
            if v is None:
                say(f"per-layer metric {m['name']}: nothing to read, "
                    f"left out")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": tr.top(tr.family_seconds(ts.events, t0, t1)),
            "idle_gaps": tr.top(tr.idle_gaps(ts.events, t0, t1))}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in harness.metrics_for(cell["name"], "end_to_end",
                                                bench)}
    result.update(metrics=metrics, workload=cell["name"], seed=args.seed,
                  problems=out["problems"])
    say(f"whole run {time.perf_counter() - T_START:.1f} s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
