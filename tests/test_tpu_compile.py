"""The Pallas kernels of the two main paths, compiled by the chip's own
compiler for a DESCRIBED `v5e:2x2` chip (no chip attached), at the widths
`chip_smoke.py` runs: BERT-base attention/layernorm and the server's ragged
paged attention. Interpret mode cannot show what Mosaic refuses (an
unaligned slice, a (1,1)->(8,128) broadcast, a scalar bitcast); this file
does, at about two seconds a case and no chip time.

The one file that describes the chip: the topology is described inside a
module-scoped fixture (never at import — see the on-chip-measurement
guide, section 2), in the test's own process, with the persistent
compilation cache off around the compiles. A compile that passes is not
a chip run.
"""
import os
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_kernels as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chip_smoke import kernel_calls  # noqa: E402  (what the chip run checks)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """compile(fn, *(shape, dtype)) -> optimized-HLO text, for the
    described chip: `on_tpu()` steered to the kernel branch, interpret
    mode off, persistent cache off (an entry written for a described
    device cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    monkeypatch.delenv("MXTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("MXTPU_PALLAS_DISABLE", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *avals):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in avals]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _scalar(f):
    return lambda *a: f(*a).astype(jnp.float32).sum()


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
QKV = ((16, 12, 512, 64), BF16)          # BERT-base, 16 x 512 tokens


@pytest.mark.parametrize("form", ["plain", "kv_lengths", "causal"])
def test_flash_attention_fwd_bwd_compiles(chip_compile, form):
    if form == "kv_lengths":
        def attn(q, k, v, vl):
            return pk.flash_attention(q, k, v, kv_lengths=vl)
        avals = (QKV, QKV, QKV, ((16,), I32))
    else:
        def attn(q, k, v):
            return pk.flash_attention(q, k, v, causal=form == "causal")
        avals = (QKV, QKV, QKV)
    text = chip_compile(jax.grad(_scalar(attn), argnums=(0, 1, 2)), *avals)
    assert kernel_calls(text, ("mxtpu_flash_fwd", "mxtpu_flash_bwd_dkv",
                               "mxtpu_flash_bwd_dq")) == {
        "mxtpu_flash_fwd": 1, "mxtpu_flash_bwd_dkv": 1,
        "mxtpu_flash_bwd_dq": 1}, form


def test_fused_layer_norm_fwd_bwd_compiles(chip_compile):
    text = chip_compile(
        jax.grad(_scalar(pk.fused_layer_norm), argnums=(0, 1, 2)),
        ((8192, 768), BF16), ((768,), BF16), ((768,), BF16))
    assert kernel_calls(text, ("mxtpu_layer_norm",)) == {
        "mxtpu_layer_norm": 1}


# the server's shapes: 8 slots, 8 heads of 64, pages of 16 tokens
S, H, DH, PSIZE, NPAGES, POOL = 8, 8, 64, 16, 4, 33


@pytest.mark.parametrize("window,q_dtype,kv_dtype", [
    (None, BF16, BF16), (None, F32, F32), (None, F32, I8),
    (4, BF16, BF16), (4, F32, I8),
], ids=["single-bf16", "single-f32", "single-int8",
        "w4-bf16", "w4-int8"])
def test_ragged_paged_attention_compiles(chip_compile, window, q_dtype,
                                         kv_dtype):
    """`window=None` is the one-token decode turn `serve.Server` runs
    every step — the form Mosaic refused before PR 22."""
    q = ((S, H, DH) if window is None else (S, window, H, DH), q_dtype)
    pages = ((POOL, PSIZE, H, DH), kv_dtype)
    avals = [q, pages, pages, ((S, NPAGES), I32), ((S,), I32)]
    if kv_dtype == I8:
        avals += [((POOL, H), F32)] * 2

        def attn(q, k, v, pt, ln, ks, vs):
            return pk.ragged_paged_attention(q, k, v, pt, ln,
                                             k_scales=ks, v_scales=vs)
    else:
        attn = pk.ragged_paged_attention
    assert kernel_calls(chip_compile(attn, *avals),
                        ("mxtpu_rpa",)) == {"mxtpu_rpa": 1}
