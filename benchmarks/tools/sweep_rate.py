"""Find the knee of an open-loop cell once, on the chip: one server, one
process, a window at each of several request rates.

    python3 benchmarks/tools/sweep_rate.py --workload nmt_base.wmt_steady \
        --rates 100,150,200,250,300 --seconds 8

The knee is the highest rate whose backlog does not grow: few requests
without a first token when the window closes and a time to first token
that stays near the lower rates'. The cell's traffic file then takes 0.8
of it as `rate_rps`. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmarks.kinds import serve_open_loop
    from benchmarks.lib import harness, serving
    from mxnet_tpu.observability import compilex
    cell, cfg, traffic = harness.find_cell(args.workload)
    if harness.device_record()["platform"] != "tpu":
        print("refusing: a sweep needs a TPU", file=sys.stderr)
        return 1
    compilex.entry_compilation_cache(ROOT)

    def say(msg):
        print(f"[sweep {cell['name']}] {msg}", flush=True)

    model, srv = serving.build_server(cfg, args.seed, traffic["max_queue"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        at = {**traffic, "rate_rps": rate}
        reqs, dues = serve_open_loop.schedule(cfg, at, args.seed + i,
                                              args.seconds)
        if i == 0:
            serving.warm(srv, reqs, traffic["warm_requests"])
        say(f"--- {rate} requests a second")
        w = serve_open_loop.open_loop(srv, reqs, dues, at, args.seconds)
        serve_open_loop.describe(w, say)
        say(f"{w['tokens'] / (w['t1'] - w['t0']):.1f} output tokens a "
            f"second from the sample")
        serving.drain(srv, [h for _, _, h, _ in w["log"] if h is not None])
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
