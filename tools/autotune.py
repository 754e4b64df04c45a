#!/usr/bin/env python
"""Compile-space autotuner driver (ISSUE 20).

Builds the framework's OWN gated executables — the reference-MLP
captured training step (the check_fusion/check_dispatch zoo model) and
the tiny-transformer serve decode turn — records one real dispatch of
each into a replayable workload (`tune.capture_workload`), then runs
the measured search (`tune.search`) over the curated XLA flag allowlist
(`tune.default_flag_candidates`). Neither executable runs a kernel with
a block knob (`tune.overrides.KNOBS` are the flash kernels'), so the
search has no Pallas dimension here.

Each executable's check_fusion BUDGETS row rides along as guard 1, so
a winner here is by construction a build the tier-1 fusion gate would
accept. Non-baseline winners persist to the `TuneStore` (--dir,
MXTPU_TUNE_DIR, or beside the compilation cache); a fresh process with
`MXTPU_AUTOTUNE=<dir>` then applies them at lowering time — see
docs/PERFORMANCE.md "Autotuning".

Standalone:

    JAX_PLATFORMS=cpu python tools/autotune.py --dir /tmp/tune --trials 3

Progress goes to stderr; stdout carries ONE JSON summary line (per-
executable winner/speedup/rejections + the store path). exit 0 =
search completed (baseline winning is a valid outcome), 1 = a
workload could not be built or its baseline failed its own budget.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------- workloads
def _captured_step_workload():
    """The check_fusion `captured_step` fixture (reference MLP, sgd with
    momentum, replicated), warmed, with one step recorded."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, tune

    rng = np.random.RandomState(0)
    X = nd.array(rng.randn(16, 32).astype(np.float32))
    y = nd.array(rng.randint(0, 8, 16).astype(np.float32))
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(X)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    step(X, y)                        # warm: the compile happens here
    with tune.capture_workload("captured_step") as caught:
        step(X, y)                    # the recorded dispatch
    wl = caught.get("captured_step")
    # keep the net/trainer alive with the workload (the jit closure
    # holds what it needs, but the ij registry is weak)
    if wl is not None:
        wl._anchor = (net, tr, step)
    return wl


def _serve_workloads(spec=False):
    """The check_fusion tiny-transformer server, warmed through one
    request, with the decode turn of a second request recorded.
    `spec=True` uses a speculative server instead and records the
    widened `serve_verify` executable (the multi-query kernel form)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import tune
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=32, dropout=0.0)
    model.initialize()
    kw = dict(slots=3, page_size=16, max_src_len=8, max_new_tokens=12,
              engine_driven=False)
    if spec:
        kw.update(speculative_k=2, max_prompt_len=8, max_new_tokens=8)
    srv = mx.serve.Server(model, **kw)
    rng = np.random.RandomState(0)

    def _turn(n_new):
        sub = dict(max_new_tokens=n_new)
        if spec:
            sub["prompt_tokens"] = rng.randint(4, 32, (6,))
        srv.submit(rng.randint(4, 32, (5,)), **sub).result(timeout=300)

    exe = "serve_verify" if spec else "serve_decode"
    _turn(2)                          # warm
    with tune.capture_workload(exe) as caught:
        _turn(4)                      # the recorded turn
    wl = caught.get(exe)
    if wl is not None:
        wl._anchor = srv              # keep pools/weights alive
    return wl, srv


# ----------------------------------------------------------------- run
def _search_one(name, wl, trials, store):
    from mxnet_tpu import tune
    from check_fusion import BUDGETS

    budget = BUDGETS.get(name)
    cands = tune.default_flag_candidates()
    _log(f"[autotune] {name}: {len(cands)} candidate(s) + baseline, "
         f"trials={trials}, budget={'yes' if budget else 'no'}")
    res = tune.search(wl, candidates=cands, trials=trials,
                      budget=budget, log=_log)
    entry = res.winner_entry()
    if entry is not None:
        store.record(entry)
    summary = {
        "executable": name,
        "platform": res.platform,
        "shape_class": res.shape_class,
        "baseline_ms": round(res.baseline.score_ms, 4),
        "winner": res.winner.candidate.name,
        "winner_ms": round(res.winner.score_ms, 4),
        "speedup": round(res.speedup, 4),
        "improved": res.improved,
        "persisted": entry is not None,
        "dimensions_searched": sorted(
            {"flags" if c.candidate.flags else "pallas"
             for c in res.candidates if not c.candidate.is_baseline}),
        "rejected": {c.candidate.name: c.rejected
                     for c in res.candidates if c.rejected},
    }
    _log(f"[autotune] {name}: winner={summary['winner']} "
         f"({summary['baseline_ms']}ms -> {summary['winner_ms']}ms, "
         f"x{summary['speedup']})")
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="winner-store directory (default: "
                         "MXTPU_TUNE_DIR, else beside the compilation "
                         "cache)")
    ap.add_argument("--trials", type=int, default=5,
                    help="timed dispatches per candidate (median "
                         "scored)")
    ap.add_argument("--spec", action="store_true",
                    help="tune the speculative serve_verify executable "
                         "(multi-query kernel form) instead of "
                         "serve_decode")
    ap.add_argument("--skip-serve", action="store_true",
                    help="tune only the captured training step")
    args = ap.parse_args(argv)

    from mxnet_tpu.tune import TuneStore
    store = TuneStore(args.dir)
    if store.dir is None:
        _log("[autotune] no store directory resolvable — pass --dir, "
             "set MXTPU_TUNE_DIR, or enable the compilation cache")
        return 1

    out = {"store": store.dir, "results": []}
    failures = 0

    wl = _captured_step_workload()
    if wl is None:
        _log("[autotune] captured_step dispatch was not recorded")
        failures += 1
    else:
        out["results"].append(_search_one(
            "captured_step", wl, args.trials, store))

    if not args.skip_serve:
        exe = "serve_verify" if args.spec else "serve_decode"
        wl, srv = _serve_workloads(spec=args.spec)
        if wl is None:
            _log(f"[autotune] {exe} dispatch was not recorded")
            failures += 1
        else:
            out["results"].append(_search_one(exe, wl, args.trials, store))
        srv.close()

    if any(r["persisted"] for r in out["results"]):
        store.save()
        _log(f"[autotune] winners saved to {store.dir}")
    else:
        _log("[autotune] baseline won everywhere — nothing persisted")

    print(json.dumps(out))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
