"""Pallas kernel tests (SURVEY.md §2 #42). On the CPU test mesh the kernels
fall back to the XLA reference path — these tests pin the numerics and the
custom-vjp wiring; the kernels are compiled for the chip in
test_tpu_compile.py and run on it by chip_smoke.py and benchmarks/run.py."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                          attention_reference,
                                          fused_layer_norm, on_tpu)
from mxnet_tpu.ops.nn_ops import layer_norm


def _qkv(b=2, h=2, s=128, d=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), dtype) for k in ks)


def test_attention_reference_is_softmax_attention():
    q, k, v = _qkv(s=8)
    out = attention_reference(q, k, v)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_flash_matches_reference():
    q, k, v = _qkv()
    for causal in (False, True):
        got = flash_attention(q, k, v, causal)
        want = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_flash_causality():
    """Future K/V must not influence causal outputs."""
    q, k, v = _qkv(s=16)
    out1 = flash_attention(q, k, v, True)
    k2 = k.at[:, :, 8:].set(999.0)
    v2 = v.at[:, :, 8:].set(-999.0)
    out2 = flash_attention(q, k2, v2, True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :8]),
                               np.asarray(out2[:, :, :8]), rtol=1e-5)


def test_flash_grad_matches_reference_grad():
    q, k, v = _qkv(s=32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, True) ** 2).sum()

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_fused_layer_norm_matches_unfused():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    g = jax.random.normal(jax.random.PRNGKey(1), (128,))
    b = jax.random.normal(jax.random.PRNGKey(2), (128,))
    got = fused_layer_norm(x, g, b)
    want = layer_norm(x, g, b, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    want = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_flash_odd_length_fallback():
    """Non-128-multiple sequence takes the XLA path but stays correct."""
    q, k, v = _qkv(s=100)
    got = flash_attention(q, k, v, True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# interpret-mode tests: run the REAL Pallas kernel bodies on the CPU mesh
# (MXTPU_PALLAS_INTERPRET=1) so the fwd + bwd kernel numerics are pinned
# without a chip. Slow per-call, so shapes stay minimal (1 head, S=256).
# ---------------------------------------------------------------------------
@pytest.fixture
def _pallas_interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_fwd_interpret(_pallas_interpret, causal):
    q, k, v = _qkv(b=1, h=1, s=256, d=64)
    got = flash_attention(q, k, v, causal)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_bwd_interpret(_pallas_interpret, causal):
    """dq/dk/dv Pallas kernels (in-kernel recompute from saved lse) must
    match the XLA attention gradient."""
    q, k, v = _qkv(b=1, h=1, s=256, d=64)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal) * w).sum()

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) * w).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def test_flash_kv_lengths_matches_masked_reference():
    """kv_lengths fallback path == boolean-masked reference (CPU path)."""
    q, k, v = _qkv(s=128)
    vl = jnp.array([64, 128])
    got = flash_attention(q, k, v, kv_lengths=vl)
    pos = jnp.arange(128)[None, :]
    mask = (pos < vl[:, None])[:, None, None, :]
    want = attention_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_attention_reference_additive_mask_convention():
    """Additive masks (0 = keep, -1e9 = drop) must mask the RIGHT positions
    (regression: the boolean interpretation inverted them)."""
    q, k, v = _qkv(s=8)
    vl = jnp.array([4, 8])
    pos = jnp.arange(8)[None, :]
    keep = pos < vl[:, None]
    additive = jnp.where(keep, 0.0, -1e9)[:, None, None, :]
    boolean = keep[:, None, None, :]
    got = attention_reference(q, k, v, mask=additive)
    want = attention_reference(q, k, v, mask=boolean)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # and masked != unmasked (the mask actually does something)
    unmasked = attention_reference(q, k, v)
    assert not np.allclose(np.asarray(got), np.asarray(unmasked))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_lengths_interpret(_pallas_interpret, causal):
    """The scalar-prefetch masked kernel (fwd + bwd) == masked XLA attention,
    including combined with causal."""
    q, k, v = _qkv(b=2, h=1, s=256, d=64)
    vl = jnp.array([100, 256])
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    pos = jnp.arange(256)[None, :]
    mask = (pos < vl[:, None])[:, None, None, :]

    got = flash_attention(q, k, v, causal, kv_lengths=vl)
    want = attention_reference(q, k, v, causal=causal, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal, kv_lengths=vl) * w).sum()

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal, mask=mask)
                * w).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def test_flash_kernel_rectangular_interpret(_pallas_interpret):
    """Cross-attention shape: Sq != Sk rides the kernel (fwd + bwd)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, 1, 128, 64))
    k = jax.random.normal(ks[1], (1, 1, 256, 64))
    v = jax.random.normal(ks[2], (1, 1, 256, 64))
    w = jax.random.normal(ks[3], q.shape)
    got = flash_attention(q, k, v)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    g1 = jax.grad(lambda *a: (flash_attention(*a) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (attention_reference(*a) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def test_fused_ln_kernel_interpret(_pallas_interpret):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    g = jax.random.normal(jax.random.PRNGKey(1), (128,))
    b = jax.random.normal(jax.random.PRNGKey(2), (128,))
    got = fused_layer_norm(x, g, b)
    want = layer_norm(x, g, b, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
