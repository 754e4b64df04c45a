"""The readings a parallel-hybrid cell's limits are set between (not a
benchmark run), as `ssm_precision_readings.py` gives them for the
sequential hybrid: for each seed, the program against the float32
reference, and then each control, the reference itself computed below
the configuration's precision, with a multiplier set to 1 or with a term
left out, every one through the cell's own `finish()`
(`kinds/serve_par_backlog.py`) and its limits. A control has to come out
NOT correct. attention_in_multiplier is published as 1, so it has no
control.

    python3 benchmarks/tools/par_precision_readings.py
        --workload <cell> --seeds a,b,c [--controls ""]
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


def _knob(**kw):
    return {"knobs": kw}


CONTROLS = {"low_all": {"low": "all"}, "low_state": {"low": "state"},
            "embed_mult_1": _knob(embed_mult=1.0),
            "head_mult_1": _knob(head_mult=1.0),
            "key_mult_1": _knob(key_mult=1.0),
            "gate_mult_1": _knob(gate_mult=1.0),
            "down_mult_1": _knob(down_mult=1.0),
            "ssm_in_1": _knob(ssm_in=1.0), "ssm_out_1": _knob(ssm_out=1.0),
            "attn_out_1": _knob(attn_out=1.0),
            "ssm_mult_1": _knob(ssm_mult=(1.0,) * 5),
            "no_ssm": _knob(ssm_out=0.0), "no_attn": _knob(attn_out=0.0),
            "no_rotation": _knob(rotate=0.0),
            "theta_1e4": _knob(theta=1e4),
            "one_norm": _knob(one_norm=1.0),
            "no_d_skip": _knob(d_skip=0.0),
            "no_conv_bias": _knob(conv_bias=0.0)}


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
    from benchmarks.kinds import serve_par_backlog as kind
    from benchmarks.lib import harness, lm_par
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(ROOT)
    cell, cfg, traffic = harness.find_cell(args.workload)

    def say(msg):
        print(f"[seed {seed}] {msg}", flush=True)

    model, srv = lm_par.build_server(cfg, seed, 8)
    for name in [None] + [c for c in args.controls.split(",") if c]:
        problems = []
        kind.finish(srv, model, cfg, traffic, seed, [], say, problems,
                    control=CONTROLS[name] if name else None)
        say(f"{name or 'program'}: correct = {not problems} {problems}")
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
