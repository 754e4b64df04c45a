"""bench_util protocol tests: the shared candidate sweep and the shared
SGD-momentum step builder the bench scripts compile."""
import sys
import os
import numpy as np
import pytest
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench_util  # noqa: E402
from bench_util import make_sgd_step  # noqa: E402


def _quad_loss(p, x):
    # d(loss)/dp0 = p0 - x  -> SGD converges p0 -> x; p1 is an aux slot
    return 0.5 * jnp.sum((p[0] - x) ** 2), [p[1] + 1.0]


def test_make_sgd_step_momentum_and_aux():
    p = [jnp.zeros(3), jnp.zeros(())]
    mom = [jnp.zeros(3), jnp.zeros(())]
    x = jnp.array([1.0, 2.0, 3.0])
    step = make_sgd_step(_quad_loss, aux_idx=[1], lr=0.1, mu=0.9)
    p1, mom1, loss = step([jnp.array(v) for v in p],
                          [jnp.array(v) for v in mom], x)
    # first step: g = -x, mom = g, p0 = 0.1*x
    np.testing.assert_allclose(np.asarray(p1[0]), 0.1 * np.asarray(x),
                               rtol=1e-6)
    # aux splice: slot 1 got the returned aux value, NOT an SGD update
    assert float(p1[1]) == 1.0
    assert float(loss) == 7.0  # 0.5*(1+4+9)


def test_make_sgd_step_unroll_equals_sequential():
    x = jnp.array([1.0, -2.0])

    def run(unroll, n_dispatch):
        step = make_sgd_step(_quad_loss, aux_idx=[1], lr=0.05, mu=0.9,
                             unroll=unroll)
        p = [jnp.zeros(2), jnp.zeros(())]
        m = [jnp.zeros(2), jnp.zeros(())]
        for _ in range(n_dispatch):
            p, m, loss = step(p, m, x)
        return np.asarray(p[0]), float(p[1]), float(loss)

    p_seq, aux_seq, l_seq = run(1, 6)
    p_unr, aux_unr, l_unr = run(3, 2)
    np.testing.assert_allclose(p_unr, p_seq, rtol=1e-6)
    # aux (BN running stats in the real benches) advances once per REAL
    # step: 6 sequential dispatches == 2 dispatches of 3 unrolled steps
    assert aux_seq == 6.0 and aux_unr == 6.0
    np.testing.assert_allclose(l_unr, l_seq, rtol=1e-6)


# ------------------------------------------------------------- sweep unit
def test_sweep_skips_failures_and_reports_best():
    seen = []
    results = {8: 10.0, 16: RuntimeError("oom"), 32: 30.0}

    def run_one(c):
        r = results[c]
        if isinstance(r, Exception):
            raise r
        return r
    best, cand = bench_util.sweep([8, 16, 32], 1e9, run_one,
                                  on_best=seen.append)
    assert (best, cand) == (30.0, 32)
    assert seen == [10.0, 30.0]       # checkpoint per improvement


def test_sweep_budget_gates_later_candidates(monkeypatch):
    clock = {"t": 0.0}
    monkeypatch.setattr(bench_util.time, "monotonic",
                        lambda: clock["t"])

    def run_one(c):
        clock["t"] += 400.0           # each candidate is slow
        return float(c)
    best, cand = bench_util.sweep([1, 2, 3], 300.0, run_one)
    assert (best, cand) == (1.0, 1)   # 2 and 3 never start


def test_sweep_raises_when_nothing_lands():
    def always_fail(c):
        raise ValueError("x")
    with pytest.raises(RuntimeError, match="no sweep candidate"):
        bench_util.sweep([1, 2], 1e9, always_fail)
