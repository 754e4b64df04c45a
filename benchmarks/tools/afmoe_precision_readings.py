"""The readings the QK-normed window-and-NoPE expert cell's limits are
set between (not a benchmark run), as `swa_precision_readings.py` gives
them for the softmax-routed window cell: for each seed, the program
against the float32 reference, and then each control, the reference
itself computed below the configuration's precision or with a term left
out or swapped, every one through the cell's own `finish()`
(`kinds/serve_afmoe_backlog.py`, which is `serve_swa_backlog`'s) and its
limits. A control has to come out NOT correct.

    python3 benchmarks/tools/afmoe_precision_readings.py --workload <cell> --seeds a,b,c
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = {"low_all": {"low": "all"},
            "no_qk_norm": {"leave_out": "qk_norm"},
            "rope_on_full": {"leave_out": "nope"},
            "no_gate": {"leave_out": "gate"},
            "no_post_norms": {"leave_out": "post_norms"},
            "no_bias": {"leave_out": "bias"},
            "no_route_scale": {"leave_out": "route_scale"},
            "softmax_for_sigmoid": {"leave_out": "sigmoid"},
            "no_embed_mult": {"leave_out": "embed_mult"},
            "no_window": {"leave_out": "window"}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS),
                    help="which controls, of " + ", ".join(CONTROLS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # a process a seed, this one off the chip: a second 14 GB server
        # does not fit beside what the first leaves behind
        return max(subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seeds", str(s), "--controls", args.controls])
            for s in seeds)
    seed = seeds[0]
    from benchmarks.kinds import serve_afmoe_backlog as kind
    from benchmarks.lib import harness, lm_afmoe
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(ROOT)
    cell, cfg, traffic = harness.find_cell(args.workload)

    def say(msg):
        print(f"[seed {seed}] {msg}", flush=True)

    model, srv = lm_afmoe.build_server(cfg, seed, 8)
    for name in [None] + [c for c in args.controls.split(",") if c]:
        problems = []
        kind.finish(srv, model, cfg, traffic, seed, [], say, problems,
                    control=CONTROLS[name] if name else None)
        say(f"{name or 'program'}: correct = {not problems} {problems}")
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
