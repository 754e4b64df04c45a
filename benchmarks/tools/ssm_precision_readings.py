"""The readings a state-space cell's limits are set between (not a
benchmark run), as `lm_precision_readings.py` gives them for the
KDA-hybrid cell: for each seed, the program against the float32
reference, and then each control, the reference itself computed below
the configuration's precision or with a term left out or swapped, every
one through the cell's own `finish()` (`kinds/serve_ssm_backlog.py`) and
its limits. A control has to come out NOT correct.

    python3 benchmarks/tools/ssm_precision_readings.py --workload <cell> --seeds a,b,c
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

CONTROLS = {"low_all": {"low": "all"}, "low_state": {"low": "state"},
            "no_d_skip": {"leave_out": "d_skip"},
            "no_gate": {"leave_out": "gate"},
            "no_conv_bias": {"leave_out": "conv_bias"},
            "no_dt_bias": {"leave_out": "dt_bias"},
            "relu_for_relu2": {"leave_out": "relu"},
            "no_shared": {"leave_out": "shared"},
            "scaling_1": {"leave_out": "scaling"},
            "one_norm": {"leave_out": "one_norm"}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS),
                    help="which controls, of " + ", ".join(CONTROLS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # a process a seed, this one off the chip: a second 9 GB server
        # does not fit beside what the first leaves behind
        return max(subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seeds", str(s), "--controls", args.controls])
            for s in seeds)
    seed = seeds[0]
    from benchmarks.kinds import serve_ssm_backlog as kind
    from benchmarks.lib import harness, lm_ssm
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(ROOT)
    cell, cfg, traffic = harness.find_cell(args.workload)

    def say(msg):
        print(f"[seed {seed}] {msg}", flush=True)

    model, srv = lm_ssm.build_server(cfg, seed, 8)
    for name in [None] + [c for c in args.controls.split(",") if c]:
        problems = []
        kind.finish(srv, model, cfg, traffic, seed, [], say, problems,
                    control=CONTROLS[name] if name else None)
        say(f"{name or 'program'}: correct = {not problems} {problems}")
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
