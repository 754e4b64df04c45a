"""Compile-space autotuner (ISSUE 20): winner store round-trip and
staleness, shape-class keying, the measured search with its guard
stack, and winner application at lowering time.

The load-bearing guarantees pinned here:

  * the winner store survives a process round-trip and REJECTS entries
    recorded under a different jax/jaxlib or shard-plan signature —
    loudly (`tune_stale{reason=}`); a corrupt store degrades to empty
    with `tune_store_corrupt`, never an exception;
  * the search winner is never slower than the measured baseline
    beyond the structural tie band, a seeded HLO-regressing flag and a
    numerics-breaking flag are both rejected by the guards (not by the
    allowlist), and the winner's HLO honours the fusion-gate budget;
  * `mx.set_autotune` applies a persisted winner on first dispatch
    (`tune_applied` counts it), warm dispatches hit the memo without
    recompiling, and outputs match the executable's contract — also
    from a COLD process via `MXTPU_AUTOTUNE` (the fleet path).
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, tune
from mxnet_tpu.observability import compilex, registry


def _counter(name, **labels):
    return registry().counter(name, **labels).value


# ----------------------------------------------------------- the store
def _entry(executable="toy_exe", platform="cpu", shape_class="abc123",
           **over):
    e = {"executable": executable, "platform": platform,
         "shape_class": shape_class, "plan": None,
         "pallas": {}, "flags": {"xla_cpu_enable_fast_min_max": True},
         "score_ms": 1.0, "baseline_ms": 2.0, "trials": 3}
    e.update(over)
    return e


def test_store_round_trip(tmp_path):
    st = tune.TuneStore(tmp_path)
    key = st.record(_entry())
    assert key == "toy_exe|cpu|abc123"
    st.save()
    assert os.path.exists(os.path.join(tmp_path, "autotune_winners.json"))

    fresh = tune.TuneStore(tmp_path)           # cold read
    got = fresh.lookup("toy_exe", "cpu", "abc123")
    assert got is not None
    assert got["flags"] == {"xla_cpu_enable_fast_min_max": True}
    import jax
    assert got["jax"] == jax.__version__       # stamped on record
    assert fresh.lookup("toy_exe", "cpu", "other") is None
    assert fresh.lookup("toy_exe", "tpu", "abc123") is None


def test_store_stale_jax_version_and_plan_rejected(tmp_path):
    st = tune.TuneStore(tmp_path)
    st.record(_entry(shape_class="aa"))
    st.record(_entry(shape_class="bb", plan="plan-A"))
    st.save()
    # doctor one entry's toolchain stamp the way an upgrade would
    p = os.path.join(tmp_path, "autotune_winners.json")
    data = json.load(open(p))
    data["entries"]["toy_exe|cpu|aa"]["jax"] = "0.0.0"
    json.dump(data, open(p, "w"))

    fresh = tune.TuneStore(tmp_path)
    s0 = _counter("tune_stale", reason="jax_version")
    with pytest.warns(RuntimeWarning, match="stale"):
        assert fresh.lookup("toy_exe", "cpu", "aa") is None
    assert _counter("tune_stale", reason="jax_version") == s0 + 1
    # the warning fires once per key; the counter keeps counting
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert fresh.lookup("toy_exe", "cpu", "aa") is None
    assert _counter("tune_stale", reason="jax_version") == s0 + 2

    p0 = _counter("tune_stale", reason="plan")
    with pytest.warns(RuntimeWarning, match="stale"):
        assert fresh.lookup("toy_exe", "cpu", "bb", plan="plan-B") is None
    assert _counter("tune_stale", reason="plan") == p0 + 1
    # matching plan signature: the entry is served
    assert fresh.lookup("toy_exe", "cpu", "bb", plan="plan-A") is not None


def test_store_corrupt_degrades_loudly(tmp_path):
    p = os.path.join(tmp_path, "autotune_winners.json")
    open(p, "w").write("{ not json")
    c0 = _counter("tune_store_corrupt")
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert tune.TuneStore(tmp_path).entries() == {}
    assert _counter("tune_store_corrupt") == c0 + 1
    # a future-format store is equally unreadable from this build
    json.dump({"format": 99, "entries": {}}, open(p, "w"))
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert tune.TuneStore(tmp_path).entries() == {}
    assert _counter("tune_store_corrupt") == c0 + 2


def test_shape_class_keys_on_skeleton_not_values():
    import jax.numpy as jnp
    a = jnp.zeros((4, 8), jnp.float32)
    b = jnp.ones((4, 8), jnp.float32)
    # same skeleton, different values / python scalar values: one class
    # (a decayed lr must NOT fork a new store key)
    assert tune.shape_class((a, 0.1), {}) == tune.shape_class((b, 0.01), {})
    # different shape, dtype, or tree structure: different classes
    assert tune.shape_class((a,), {}) != \
        tune.shape_class((a.reshape(8, 4),), {})
    assert tune.shape_class((a,), {}) != \
        tune.shape_class((a.astype(jnp.bfloat16),), {})
    assert tune.shape_class((a,), {}) != tune.shape_class((a,), {"k": a})


# ---------------------------------------------------------- the search
# the check_fusion captured_step budget row (tools/ is not importable
# from the suite; tests/test_check_fusion.py pins this copy against the
# tool's table)
_CAPTURED_BUDGET = {"fusions": (10, 40), "collective_total": 0,
                    "aliased_inputs": 8}


def _captured_workload():
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
    step(X, y)
    with tune.capture_workload("captured_step") as caught:
        step(X, y)
    wl = caught["captured_step"]
    wl._anchor = (net, tr, step)
    return wl


def test_search_winner_guards_and_budget(tmp_path):
    """Bounded 3-candidate search on the real captured step: the winner
    is >= baseline within the tie band, the seeded copy-inflating flag
    is rejected by the HLO-regression guard (the allowlist contains it
    — the GUARD keeps it honest), and the winner's HLO holds the
    fusion-gate budget."""
    wl = _captured_workload()
    cands = [
        tune.Candidate("flag:copy_region",
                       flags={"xla_cpu_copy_insertion_use_region_analysis":
                              True}),
        # seeded bad candidate: measured to inflate copies 5 -> 7 on
        # this executable with the pinned toolchain
        tune.Candidate("flag:eigen_off",
                       flags={"xla_cpu_multi_thread_eigen": False}),
    ]
    res = tune.search(wl, candidates=cands, trials=2,
                      budget=_CAPTURED_BUDGET)
    assert res.baseline.rejected is None
    from mxnet_tpu.tune.search import TIE_BAND
    assert res.winner.score_ms <= res.baseline.score_ms * (1.0 + TIE_BAND)
    by_name = {r.candidate.name: r for r in res.candidates}
    assert by_name["flag:eigen_off"].rejected is not None
    assert by_name["flag:eigen_off"].rejected.startswith("hlo_regression")
    # guard 1 held on the winner — the fusion gate would accept it
    assert tune.check_budget(res.winner.hlo, _CAPTURED_BUDGET) == []
    # a persisted winner round-trips through the store
    entry = res.winner_entry()
    if entry is not None:
        st = tune.TuneStore(tmp_path)
        st.record(entry)
        st.save()
        assert tune.TuneStore(tmp_path).lookup(
            "captured_step", res.platform, res.shape_class) is not None


def test_search_rejects_numerics_break_under_bitwise_contract():
    """A flag that changes output bits is rejected when the executable's
    contract is bitwise — regardless of how fast it is."""
    import jax
    import jax.numpy as jnp

    ij = compilex.instrument(
        jax.jit(lambda x, w: jax.nn.log_softmax(jnp.tanh(x @ w))),
        "tune_toy_bitwise")
    rng = np.random.RandomState(3)
    xv = rng.randn(32, 64).astype(np.float32)
    wv = rng.randn(64, 64).astype(np.float32)

    def make_args():
        return (jnp.asarray(xv), jnp.asarray(wv)), {}

    wl = tune.Workload(ij, make_args, contract=("bitwise",))
    res = tune.search(wl, candidates=[
        tune.Candidate("flag:opt0",
                       flags={"xla_backend_optimization_level": 0}),
    ], trials=1)
    by_name = {r.candidate.name: r for r in res.candidates}
    assert by_name["flag:opt0"].rejected is not None
    assert by_name["flag:opt0"].rejected.startswith("numerics[bitwise]")
    assert res.winner.candidate.is_baseline


def test_search_rejects_dead_pallas_override():
    """A Pallas candidate whose override the kernel picker IGNORED is
    measuring the default config under a wrong label: rejected."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk

    prev = os.environ.get("MXTPU_PALLAS_INTERPRET")
    os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
    try:
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
                   for _ in range(3))

        ij = compilex.instrument(
            jax.jit(lambda *a: pk.flash_attention(*a)), "tune_toy_flash")
        wl = tune.Workload(ij, lambda: ((q, k, v), {}),
                           contract=("allclose", 2e-6, 2e-6))
        res = tune.search(wl, candidates=[
            # 96 does not divide the 128-long sequence: the picker falls
            # back to the default and says so
            tune.Candidate("pallas:dead", pallas={"flash_block_q": 96}),
            tune.Candidate("pallas:bq128", pallas={"flash_block_q": 128}),
        ], trials=1)
        by_name = {r.candidate.name: r for r in res.candidates}
        assert by_name["pallas:dead"].rejected == "dead_pallas_override"
        # the VALID block config was read, compiled and honestly judged
        # (guard 2 may still refuse its HLO: not a dead knob)
        assert by_name["pallas:bq128"].rejected in (None,) or \
            by_name["pallas:bq128"].rejected.startswith(
                ("numerics", "hlo_regression: copies"))
    finally:
        if prev is None:
            os.environ.pop("MXTPU_PALLAS_INTERPRET", None)
        else:
            os.environ["MXTPU_PALLAS_INTERPRET"] = prev


# ----------------------------------------------------------- the apply
def test_set_autotune_applies_winner_without_retrace(tmp_path):
    """A persisted winner is applied on first dispatch (tune_applied),
    warm dispatches hit the per-signature memo (no further compiles),
    outputs match the untuned path bitwise, and disabling restores the
    plain jit route."""
    import jax
    import jax.numpy as jnp

    traces = [0]

    def f(x, w):
        traces[0] += 1
        return jnp.tanh(x @ w)

    ij = compilex.instrument(jax.jit(f), "tune_toy_apply")
    rng = np.random.RandomState(7)
    xv = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    wv = jnp.asarray(rng.randn(16, 16).astype(np.float32))
    want = np.asarray(ij(xv, wv))
    compiles0 = ij._compiles.value

    st = tune.TuneStore(tmp_path)
    st.record(_entry(executable="tune_toy_apply", platform="cpu",
                     shape_class=tune.shape_class((xv, wv), {})))
    st.save()

    a0 = tune.applied_count()
    assert tune.set_autotune(tmp_path) == str(tmp_path)
    try:
        traces[0] = 0
        out = ij(xv, wv)                   # first dispatch: AOT compile
        assert np.array_equal(np.asarray(out), want)
        assert tune.applied_count() == a0 + 1
        assert _counter("tune_applied", executable="tune_toy_apply") == 1
        # a flags-only winner shares the jit's cached trace — the AOT
        # route costs AT MOST one extra trace, here zero
        assert traces[0] <= 1
        compiles1 = ij._compiles.value
        assert compiles1 == compiles0 + 1
        for _ in range(3):                 # warm: memo hit, no retrace
            ij(xv, wv)
        assert traces[0] <= 1
        assert ij._compiles.value == compiles1
        assert tune.applied_count() == a0 + 1
    finally:
        tune.set_autotune(enabled=False)
    assert tune.autotune_dir() is None
    assert np.array_equal(np.asarray(ij(xv, wv)), want)


def test_apply_miss_and_empty_entry_fall_back(tmp_path):
    """No entry for the signature -> plain jit path, zero applications,
    negative-cached so the store is probed once."""
    import jax
    import jax.numpy as jnp

    ij = compilex.instrument(jax.jit(lambda x: x * 2), "tune_toy_miss")
    a0 = tune.applied_count()
    assert tune.set_autotune(tmp_path) is not None
    try:
        x = jnp.arange(4.0)
        assert np.allclose(np.asarray(ij(x)), [0, 2, 4, 6])
        ij(x)
    finally:
        tune.set_autotune(enabled=False)
    assert tune.applied_count() == a0


_WORKER = textwrap.dedent("""
    import json
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import tune
    from mxnet_tpu.observability import compilex, registry

    ij = compilex.instrument(
        jax.jit(lambda x, w: jnp.tanh(x @ w)), "tune_toy_proc")
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 16).astype(np.float32))
    out1 = np.asarray(ij(x, w))
    out2 = np.asarray(ij(x, w))
    print(json.dumps({
        "dir": tune.autotune_dir(),
        "applied": tune.applied_count(),
        "compiles": int(ij._compiles.value),
        "out_equal": bool(np.array_equal(out1, out2)),
        "checksum": float(out1.sum()),
    }))
""")


def test_cross_process_reuse(tmp_path):
    """The fleet path: this process persists a winner; a COLD process
    with MXTPU_AUTOTUNE applies it (tune_applied >= 1, exactly one
    compile) and computes the same numbers as an untuned cold process."""
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 16).astype(np.float32))
    st = tune.TuneStore(tmp_path / "tune")
    st.record(_entry(executable="tune_toy_proc", platform="cpu",
                     shape_class=tune.shape_class((x, w), {})))
    st.save()

    def run(autotune):
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        env = dict(os.environ)
        repo = os.path.join(os.path.dirname(__file__), "..")
        env["PYTHONPATH"] = os.path.abspath(repo) + os.pathsep + \
            env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["MXTPU_HLO_TELEMETRY"] = "0"
        env.pop("MXTPU_TUNE_DIR", None)
        if autotune:
            env["MXTPU_AUTOTUNE"] = str(tmp_path / "tune")
        else:
            env.pop("MXTPU_AUTOTUNE", None)
        proc = subprocess.run([sys.executable, str(script)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL,
                              env=env, timeout=300)
        assert proc.returncode == 0, proc.stdout.decode(errors="replace")
        line = [l for l in proc.stdout.decode().splitlines()
                if l.strip().startswith("{")][-1]
        return json.loads(line)

    tuned = run(autotune=True)
    assert tuned["dir"] == str(tmp_path / "tune")
    assert tuned["applied"] == 1
    assert tuned["compiles"] == 1          # zero extra retraces/compiles
    assert tuned["out_equal"]

    plain = run(autotune=False)
    assert plain["dir"] is None and plain["applied"] == 0
    # the applied flag set keeps this executable's numerics contract
    assert tuned["checksum"] == plain["checksum"]
