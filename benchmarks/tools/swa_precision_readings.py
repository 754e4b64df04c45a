"""The readings a sliding-window cell's limits are set between (not a
benchmark run), as `lm_precision_readings.py` gives them for the
KDA-hybrid cell: for each seed, the program against the float32
reference, and then each control, the reference itself computed below
the configuration's precision or with a term left out or swapped, every
one through the cell's own `finish()` (`kinds/serve_swa_backlog.py`) and
its limits. A control has to come out NOT correct.

    python3 benchmarks/tools/swa_precision_readings.py --workload <cell> --seeds a,b,c
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
            "no_rotation": {"leave_out": "rotation"},
            "plain_table_on_full": {"leave_out": "yarn"},
            "attn_factor_1": {"leave_out": "attn_factor"},
            "no_window": {"leave_out": "window"},
            "sigmoid_for_softmax": {"leave_out": "sigmoid"},
            "no_renorm": {"leave_out": "renorm"}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS),
                    help="which controls, of " + ", ".join(CONTROLS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # a process a seed, this one off the chip: a second 12 GB server
        # does not fit beside what the first leaves behind
        return max(subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seeds", str(s), "--controls", args.controls])
            for s in seeds)
    seed = seeds[0]
    from benchmarks.kinds import serve_swa_backlog as kind
    from benchmarks.lib import harness, lm_swa
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(ROOT)
    cell, cfg, traffic = harness.find_cell(args.workload)

    def say(msg):
        print(f"[seed {seed}] {msg}", flush=True)

    model, srv = lm_swa.build_server(cfg, seed, 8)
    for name in [None] + [c for c in args.controls.split(",") if c]:
        problems = []
        kind.finish(srv, model, cfg, traffic, seed, [], say, problems,
                    control=CONTROLS[name] if name else None)
        say(f"{name or 'program'}: correct = {not problems} {problems}")
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
