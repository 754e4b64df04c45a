"""The Mamba-2 state-space recurrence (`ops/ssd.py`) at a small size on
the CPU: the chunked scan against the per-position recurrence, prefill's
final state carried on by one-position steps, the `mxtpu_ssd_step` kernel
in interpret mode against its `lax` form, and the convolution's bias."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import kda, ssd

H, P, G, N = 8, 8, 2, 128


def _inputs(rng, t):
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731
    return (f(t, H, P), jax.nn.softplus(f(t, H)), -jnp.exp(0.3 * f(H)),
            f(t, G, N), f(t, G, N))


def _recurrence(x, delta, a_neg, b, c, state):
    def step(s, xs):
        y, s = ssd.ssd_step(xs[0], xs[1], a_neg, xs[2], xs[3], s)
        return s, y
    state, y = jax.lax.scan(step, state, (x, delta, b, c))
    return y, state


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("t,chunk", [(64, 16), (96, 32), (128, 128)])
def test_chunked_scan_equals_the_per_position_recurrence(t, chunk):
    rng = np.random.default_rng(t)
    x, delta, a_neg, b, c = _inputs(rng, t)
    s0 = jnp.asarray(rng.normal(size=(H, P, N)).astype(np.float32))
    y0, s_end0 = _recurrence(x, delta, a_neg, b, c, s0)
    y1, s_end1 = ssd.ssd_chunked(x, delta, a_neg, b, c, s0, chunk=chunk)
    scale = float(jnp.abs(y0).max())
    np.testing.assert_allclose(y1, y0, atol=2e-5 * scale)
    np.testing.assert_allclose(s_end1, s_end0,
                               atol=2e-5 * float(jnp.abs(s_end0).max()))
    assert y1.dtype == s_end1.dtype == jnp.float32


@pytest.mark.parametrize("n", [1, 19, 32, 45])
def test_a_valid_prefix_stops_the_scan_at_the_prompts_end(n):
    """Lengths that are and are not whole chunks: positions past `n`
    (delta 0) leave the state alone, the chunks that hold none are not
    run, and the outputs before `n` are the recurrence's."""
    rng = np.random.default_rng(n)
    t, chunk = 64, 16
    x, delta, a_neg, b, c = _inputs(rng, t)
    zero = jnp.zeros((H, P, N), jnp.float32)
    masked = jnp.where(jnp.arange(t)[:, None] < n, delta, 0.0)
    y, s_end = ssd.ssd_chunked(x, masked, a_neg, b, c, zero, chunk=chunk,
                               length=jnp.int32(n))
    y0, s0 = _recurrence(x[:n], delta[:n], a_neg, b[:n], c[:n], zero)
    np.testing.assert_allclose(y[:n], y0, atol=2e-5 * float(jnp.abs(y0).max()))
    np.testing.assert_allclose(s_end, s0, atol=2e-5 * float(jnp.abs(s0).max()))
    whole = -(-n // chunk) * chunk
    assert not np.asarray(y[whole:]).any()       # chunks that were not run
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_chunked(x[:50], delta[:50], a_neg, b[:50], c[:50], zero,
                        chunk=chunk)


def test_prefills_state_then_one_position_steps_equal_the_whole_scan():
    rng = np.random.default_rng(3)
    t, n, chunk = 64, 37, 16
    x, delta, a_neg, b, c = _inputs(rng, t)
    zero = jnp.zeros((H, P, N), jnp.float32)
    y_all, s_all = ssd.ssd_chunked(x, delta, a_neg, b, c, zero, chunk=chunk)
    masked = jnp.where(jnp.arange(t)[:, None] < n, delta, 0.0)
    _, s = ssd.ssd_chunked(x, masked, a_neg, b, c, zero, chunk=chunk,
                           length=jnp.int32(n))
    s = s[None]                                   # one slot
    for i in range(n, t):
        y, s = ssd.ssd_step_slots(x[i][None], delta[i][None], a_neg,
                                  b[i][None], c[i][None], s)
        np.testing.assert_allclose(y[0], y_all[i],
                                   atol=3e-5 * float(jnp.abs(y_all).max()))
    np.testing.assert_allclose(s[0], s_all,
                               atol=3e-5 * float(jnp.abs(s_all).max()))
    assert s.dtype == jnp.float32


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ssd_step_kernel_equals_the_plain_step(interpret, dtype):
    """`mxtpu_ssd_step` (interpret mode) against `ssd_step`, five slots;
    the state stays float32 whatever the inputs are and is written where
    it was (the call aliases it)."""
    rng = np.random.default_rng(4)
    x, delta, a_neg, b, c = _inputs(rng, 5)
    x, b, c = (v.astype(dtype) for v in (x, b, c))
    state = jnp.asarray(rng.normal(size=(5, H, P, N)).astype(np.float32))
    y0, s0 = ssd.ssd_step(x, delta, a_neg, b, c, state)
    jaxpr = str(jax.make_jaxpr(ssd.ssd_step_slots)(x, delta, a_neg, b, c,
                                                   state))
    assert "mxtpu_ssd_step" in jaxpr and "input_output_aliases" in jaxpr
    y1, s1 = ssd.ssd_step_slots(x, delta, a_neg, b, c, state)
    np.testing.assert_allclose(y1, y0, atol=1e-5 * float(jnp.abs(y0).max()))
    np.testing.assert_allclose(s1, s0, atol=1e-6 * float(jnp.abs(s0).max()))
    assert y1.dtype == s1.dtype == jnp.float32


def test_the_plain_step_runs_where_the_kernel_cannot(monkeypatch):
    """Off the TPU, or a state whose last axis is not whole lane tiles:
    no kernel, the same numbers."""
    monkeypatch.delenv("MXTPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(5)
    x, delta, a_neg, b, c = _inputs(rng, 3)
    state = jnp.zeros((3, H, P, N), jnp.float32)
    assert "pallas" not in str(jax.make_jaxpr(ssd.ssd_step_slots)(
        x, delta, a_neg, b, c, state))
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    assert "pallas" not in str(jax.make_jaxpr(ssd.ssd_step_slots)(
        x, delta, a_neg, b[..., :16], c[..., :16], state[..., :16]))


def test_a_head_reads_its_groups_b_and_c():
    """Head h reads group h // (H / G): swapping the two groups' vectors
    is the same as swapping the heads' halves."""
    rng = np.random.default_rng(6)
    x, delta, a_neg, b, c = _inputs(rng, 2)
    a_neg = jnp.full((H,), -0.5)
    state = jnp.zeros((2, H, P, N), jnp.float32)
    y, _ = ssd.ssd_step(x, delta, a_neg, b, c, state)
    half = H // G
    flip = lambda v: jnp.concatenate([v[:, half:], v[:, :half]], 1)  # noqa: E731
    y2, _ = ssd.ssd_step(flip(x), flip(delta), a_neg, b[:, ::-1], c[:, ::-1],
                         state)
    np.testing.assert_allclose(flip(y2), y, atol=1e-6)


def test_causal_conv_with_a_bias_continues_the_sequence():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(11, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(6,)).astype(np.float32))
    full = kda.causal_conv(x, w, bias)
    np.testing.assert_allclose(full, kda.causal_conv(x, w) + bias, atol=1e-6)
    tail = jnp.zeros((1, 3, 6), jnp.float32)
    for i in range(11):
        out, tail = kda.causal_conv_step(tail, x[i][None], w, bias)
        np.testing.assert_allclose(out[0], full[i], atol=1e-5)
    np.testing.assert_array_equal(tail[0], x[-3:])
