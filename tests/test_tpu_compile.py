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
    pages = ((H, POOL, PSIZE, pk.pool_lanes(DH)), kv_dtype)
    avals = [q, pages, pages, ((S, NPAGES), I32), ((S,), I32)]
    if kv_dtype == I8:
        avals += [((H, POOL), F32)] * 2

        def attn(q, k, v, pt, ln, ks, vs):
            return pk.ragged_paged_attention(q, k, v, pt, ln,
                                             k_scales=ks, v_scales=vs)
    else:
        attn = pk.ragged_paged_attention
    assert kernel_calls(chip_compile(attn, *avals),
                        ("mxtpu_rpa",)) == {"mxtpu_rpa": 1}


# the benchmark server's own shapes (benchmarks/configs/nmt_base.json): 256
# slots, 2049 pages, 8 pages a slot; or heads and pages past what one
# grid step's fast memory holds
def pallas_grids(fn, *args):
    """The grid of every `pallas_call` in fn's jaxpr."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("window,kv_dtype,heads,dh,psize,npages,plan", [
    (None, F32, 8, 64, 16, 8, (8, 8)), (None, I8, 8, 64, 16, 8, (8, 8)),
    (4, F32, 8, 64, 16, 8, (8, 8)), (4, I8, 8, 64, 16, 8, (8, 8)),
    (None, F32, 8, 64, 16, 20, (8, 8)), (None, F32, 32, 128, 128, 8, (32, 1)),
    (None, F32, 64, 128, 256, 4, (16, 1)),
], ids=["single-f32", "single-int8", "w4-f32", "w4-int8", "20-pages",
        "32-heads-of-128-tokens", "64-heads-of-256-tokens"])
def test_ragged_paged_attention_grid_at_the_servers_shapes(
        chip_compile, window, kv_dtype, heads, dh, psize, npages, plan):
    """One `mxtpu_rpa` call takes a slot's heads and eight of its pages
    a grid step (PR 31): 256 steps at the benchmark server's shapes,
    where one (slot, head, page) a step took 16,384. The steps follow
    from the shapes through `_rpa_plan`, which `_rpa_pallas` itself
    uses: a table eight pages do not divide, and blocks past the fast
    memory's budget, take fewer pages, then fewer heads, a step, and
    compile."""
    slots, pool = 256, 2049 if psize == 16 else 65
    lanes = pk.pool_lanes(dh)
    assert pk._rpa_plan(heads, npages, psize, lanes,
                        jnp.dtype(kv_dtype).itemsize) == plan
    grid = pk._rpa_steps(slots, heads, npages, *plan)
    steps = grid[0] * grid[1]
    assert steps == slots * (heads // plan[0]) * -(-npages // plan[1])
    if (heads, npages) == (8, 8):
        assert steps == 256
    q = ((slots, heads, dh) if window is None
         else (slots, window, heads, dh), F32)
    pages = ((heads, pool, psize, lanes), kv_dtype)
    avals = [q, pages, pages, ((slots, npages), I32), ((slots,), I32)]
    if kv_dtype == I8:
        avals += [((heads, pool), F32)] * 2

        def attn(q, k, v, pt, ln, ks, vs):
            return pk.ragged_paged_attention(q, k, v, pt, ln,
                                             k_scales=ks, v_scales=vs)
    else:
        attn = pk.ragged_paged_attention
    assert kernel_calls(chip_compile(attn, *avals),
                        ("mxtpu_rpa",)) == {"mxtpu_rpa": 1}
    assert pallas_grids(attn, *(jax.ShapeDtypeStruct(s, d)
                                for s, d in avals)) == [grid]


# ------------------------------------------- the pools stay where they lie
def pool_sized_results(text, elems):
    """{opcode: count} over the instructions of one optimized-HLO text
    whose result holds at least `elems` elements (a layer pool's), a
    fusion named by its root: `fusion:scatter` updates its operand in
    place, any other fusion of that size writes a pool anew."""
    import collections
    import math
    import re
    instr = re.compile(r"\s+(ROOT\s+)?%?[\w.\-]+ = \w+\[([\d,]*)\][^ ]* "
                       r"([a-z][a-z0-9\-]*)\((.*)")
    roots, comp, found = {}, None, []
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.endswith("{"):
                comp = line.split(" (", 1)[0].split()[-1].lstrip("%")
            continue
        m = instr.match(line)
        if m is None:
            continue
        if m.group(1):
            roots[comp] = m.group(3)
        if math.prod(int(d) for d in m.group(2).split(",") if d) >= elems:
            found.append((m.group(3), m.group(4)))
    out = collections.Counter()
    for op, rest in found:
        if op == "fusion":
            op += ":" + roots[re.search(r"calls=%?([\w.\-]+)", rest).group(1)]
        out[op] += 1
    return dict(out)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp32", "int8"])
@pytest.mark.parametrize("width", [1, 4], ids=["decode", "verify"])
def test_serve_programs_make_nothing_of_a_pools_size(one_chip, chip_compile,
                                                     width, kv_dtype):
    """`DecodeRuntime`'s decode and verify programs at the server's head
    shapes (8 heads of 64, 16-token pages): every pool is donated into
    its result, and besides the parameters, their bitcasts and the
    in-place scatters of the page writes no instruction's result has a
    layer pool's size: no copy, slice, transpose or fusion stands
    between the resident pool and `mxtpu_rpa`. (PR 28's pools, (L, P,
    psize, H, dh) in the device's default layout, fail this with 16
    `copy`, 12 `slice` and 12 `fusion:bitcast` results at the server's
    size. What may remain is a `copy-start`/`copy-done` pair: the
    compiler's own move of a pool into fast memory and back in the same
    layout, which it chooses for an int8 pool of these 33 MB and cannot
    for the float32 server's 134 MB.)"""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import (TransformerNMT,
                                              decoder_weights,
                                              encoder_weights)
    from mxnet_tpu.observability import compilex
    from mxnet_tpu.serve.decode import DecodeRuntime
    # the benchmark server's 2049 pages: a pool of more elements than
    # any weight or slot memory here, and of more bytes than the
    # compiler would move into fast memory whole
    layers, slots, pages = 2, S, 2049
    mx.random.seed(0)
    model = TransformerNMT(96, units=H * DH, hidden=256, num_layers=layers,
                           num_heads=H, max_length=128, dropout=0.0)
    model.initialize()
    rt = DecodeRuntime(decoder_weights(model), encoder_weights(model),
                       slots=slots, num_pages=pages, page_size=PSIZE,
                       max_pages_per_slot=8, max_src_len=32, width=width,
                       kv_dtype=kv_dtype)

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    ints = [((slots, 8), I32), ((slots,), I32),
            ((slots,) if width == 1 else (slots, width), I32)]
    # active, then the previous step's tokens (decode) or qlens (verify)
    ints += [((slots,), I32)] * 2
    inputs = tuple(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                   for s, d in ints)
    inputs += jax.tree_util.tree_map(aval, (rt.mem_k, rt.mem_v, rt.mem_vl))
    fn = rt._decode_fn if width == 1 else rt._verify_fn
    text = fn._jfn.lower(jax.tree_util.tree_map(aval, rt._pools()),
                         inputs).compile().as_text()
    assert kernel_calls(text, ("mxtpu_rpa",)) == {"mxtpu_rpa": layers}
    info = compilex.inspect_hlo_text(text)
    assert info["aliased_inputs"] == (4 if kv_dtype else 2) * layers
    made = pool_sized_results(text, H * pages * PSIZE * DH)
    assert made["fusion:scatter"] == (4 if kv_dtype else 2) * layers, made
    rewrites = {op: n for op, n in made.items()
                if op in ("copy", "transpose", "slice", "dynamic-slice",
                          "concatenate", "reshape", "pad", "convert")
                or op.startswith("fusion:") and op != "fusion:scatter"}
    assert not rewrites, made


def test_prefill_program_writes_its_memory_buffers_in_place(one_chip,
                                                           chip_compile):
    """`DecodeRuntime`'s prefill program at cell 2's shapes (256 slots,
    sources of 128, 6 layers of 8 heads of 64, float32): the three
    memory buffers (402.7 MB each for K and V) are donated into the
    results and nothing else in the program has their size: a trip of
    the device loop writes its slot by `dynamic-update-slice` into the
    carried buffer. A form XLA serves by copying (PR 29 met a scatter
    whose window was not minor-most) shows here as a `copy` or another
    fusion of that size (1.6 GB of traffic a dispatch). The runtime is built without its
    device state: the program is lowered from shapes."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import (TransformerNMT,
                                              decoder_weights,
                                              encoder_weights)
    from mxnet_tpu.observability import compilex
    from mxnet_tpu.serve.decode import DecodeRuntime

    class Shapes(DecodeRuntime):
        def reset_pages(self):
            self.k_pages = self.v_pages = []
            self.k_scales = self.v_scales = None

        def reset_mem(self):
            pass

    layers, slots, src = 6, 256, 128
    mx.random.seed(0)
    model = TransformerNMT(96, units=H * DH, hidden=2048, num_layers=layers,
                           num_heads=H, max_length=src, dropout=0.0)
    model.initialize()
    rt = Shapes(decoder_weights(model), encoder_weights(model), slots=slots,
                num_pages=POOL, page_size=PSIZE, max_pages_per_slot=8,
                max_src_len=src)
    rows = rt.prefill_rows
    assert rows == 32

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mem = aval((layers, slots, H, src, DH), F32)
    compiled = rt._prefill_fn._jfn.lower(
        mem, mem, aval((slots,), I32), aval((rows, src + 2), I32)).compile()
    text = compiled.as_text()
    assert rt.prefill_traces == 1
    assert kernel_calls(text, ("mxtpu_flash_fwd",)) \
        == {"mxtpu_flash_fwd": layers}
    assert compilex.inspect_hlo_text(text)["aliased_inputs"] == 3
    made = pool_sized_results(text, layers * slots * H * src * DH)
    moved = {op: n for op, n in made.items()
             if op not in ("parameter", "get-tuple-element", "bitcast",
                           "dynamic-update-slice", "scatter",
                           "fusion:dynamic-update-slice",
                           "fusion:scatter")}
    assert not moved and made, made
    # beside the 805 MB of aliased buffers the program keeps a row's
    # activations and little else (7.8 MB when this was written)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


# the decoder-only server's shapes (benchmarks/configs/solar_open2_ep8.json):
# 64 query heads over 8 KV heads of 128, 16-token pages, 64 pages a slot,
# 40 experts of width 1280 under hidden 4096, KDA state 64 x 128 x 128
def test_grouped_kv_paged_attention_compiles_at_the_flat_pool(chip_compile):
    slots, pool = 16, 16 * 64 + 1
    text = chip_compile(
        pk.ragged_paged_attention, ((slots, 64, 128), BF16),
        ((pool, 16, 8 * 128), BF16), ((pool, 16, 8 * 128), BF16),
        ((slots, 64), I32), ((slots,), I32))
    assert kernel_calls(text, ("mxtpu_rpa_flat",)) == {"mxtpu_rpa_flat": 1}
    # the pool is read where it lies: nothing of its size is made
    import re
    assert not re.search(r"= bf16\[1025,16,\d+[^=]* (copy|transpose|"
                         r"reshape|fusion)\(", text)


def test_grouped_kv_paged_attention_compiles_head_major(chip_compile):
    """Grouped KV heads over head-major pools (head size 64): the plain
    path over repeated heads, no kernel, until a configuration needs one."""
    text = chip_compile(
        pk.ragged_paged_attention, ((S, 4 * H, DH), BF16),
        ((H, POOL, PSIZE, DH), BF16), ((H, POOL, PSIZE, DH), BF16),
        ((S, NPAGES), I32), ((S,), I32))
    assert kernel_calls(text, ("mxtpu_rpa",)) == {"mxtpu_rpa": 0}


@pytest.mark.parametrize("k,n", [(4096, 2560), (1280, 4096)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(chip_compile, monkeypatch, k, n):
    from mxnet_tpu.ops import grouped_matmul as gmm
    tile, rows = 32, gmm.rows_capacity(256 * 8, 40, 32)

    def fn(x, w, tile_group, used):
        return gmm.grouped_matmul(x, w, tile_group, used, tile)

    text = chip_compile(fn, ((rows, k), BF16), ((40, k, n), BF16),
                        ((rows // tile,), I32), ((), I32))
    assert kernel_calls(text, ("mxtpu_gmm",)) == {"mxtpu_gmm": 1}


def test_expert_dispatch_gathers_its_rows_at_the_latent_servers_shape(
        chip_compile):
    """`mx_moe_dispatch` at pangu_ultra_ep16's decode shape (256 slots x
    top-8 pairs, rows of 7680, 16 experts held of 256): the tiles' rows
    come from a gather; no scatter has an operand of their size."""
    import re
    from mxnet_tpu.models import decoder_lm as dlm
    from mxnet_tpu.ops import grouped_matmul as gmm
    spec = dlm.LMSpec(hidden=7680, heads=8, kv_heads=2, head_dim=16,
                      kda_heads=4, kda_head_dim=16, conv_kernel=4,
                      num_experts=256, top_k=8, expert_width=2048,
                      held_lo=16, held_n=16, scaling=2.5, eps=1e-5,
                      pattern=("gqa",))
    cap = gmm.rows_capacity(256 * 8, 16, 32)
    text = chip_compile(
        lambda x, idx, valid: dlm.mx_moe_dispatch(x, idx, valid, spec=spec,
                                                  tile=32),
        ((256, 7680), BF16), ((256, 8), I32), ((256,), jnp.bool_))
    assert re.search(rf"bf16\[{cap},7680\]", text)      # the rows are made
    wide = [line for line in text.splitlines() if "scatter(" in line
            and re.search(rf"\[{cap},7680\]", line)]
    assert not wide, wide
    assert "scatter(" in text       # layout's int32 scatters are seen


def test_kda_step_compiles(chip_compile):
    from mxnet_tpu.ops import kda
    vec = ((16, 64, 128), F32)
    text = chip_compile(
        lambda *a: kda.kda_step_slots(*a)[1], vec, vec, vec, vec,
        ((16, 64), F32), ((16, 64, 128, 128), F32))
    assert kernel_calls(text, ("mxtpu_kda_step",)) == {"mxtpu_kda_step": 1}


# the latent-attention server's shapes (benchmarks/configs/
# pangu_ultra_ep16.json): 128 query heads against ONE latent row a token,
# 512 + 64 values in 640 lanes, 16-token pages, 128 pages a slot
def test_latent_paged_attention_compiles(chip_compile):
    slots, pool = 16, 16 * 128 + 1
    text = chip_compile(
        lambda *a: pk.latent_paged_attention(*a, 512),
        ((slots, 128, 576), BF16), ((pool, 16, 640), BF16),
        ((slots, 128), I32), ((slots,), I32))
    assert kernel_calls(text, ("mxtpu_mla_decode",)) \
        == {"mxtpu_mla_decode": 1}
    # the pool is read where it lies: nothing of its size is made
    import re
    assert not re.search(r"= bf16\[2049,16,\d+[^=]* (copy|transpose|"
                         r"reshape|fusion)\(", text)


# the state-space server's shapes (benchmarks/configs/
# nemotron3_nano_ep2.json): a Mamba-2 state of 64 heads x 64 x 128 float32
# a slot, 8 groups of B and C; 64 ungated experts of 1856 (14.5 lane
# tiles) under hidden 2688, the first matrix kept (experts, width, d)
def test_ssd_step_compiles(chip_compile):
    from mxnet_tpu.ops import ssd
    text = chip_compile(
        lambda *a: ssd.ssd_step_slots(*a)[1], ((16, 64, 64), F32),
        ((16, 64), F32), ((64,), F32), ((16, 8, 128), F32),
        ((16, 8, 128), F32), ((16, 64, 64, 128), F32))
    assert kernel_calls(text, ("mxtpu_ssd_step",)) == {"mxtpu_ssd_step": 1}


def test_grouped_matmul_compiles_at_a_width_of_half_lane_tiles(chip_compile):
    """Up (`nt`: the bank (experts, 1856, 2688), its minor dimension whole
    lane tiles, so the device keeps it row-major and the kernel reads it
    where it lies) then relu^2 then down: two kernels, no copy of a bank."""
    import re
    from mxnet_tpu.models.decoder_lm import relu2
    from mxnet_tpu.ops import grouped_matmul as gmm
    tile, rows = 32, gmm.rows_capacity(256 * 6, 64, 32)

    def fn(x, up, down, tile_group, used):
        u = gmm.grouped_matmul(x, up, tile_group, used, tile, nt=True)
        return gmm.grouped_matmul(relu2(u), down, tile_group, used, tile)

    text = chip_compile(fn, ((rows, 2688), BF16), ((64, 1856, 2688), BF16),
                        ((64, 1856, 2688), BF16), ((rows // tile,), I32),
                        ((), I32))
    assert kernel_calls(text, ("mxtpu_gmm",)) == {"mxtpu_gmm": 2}
    assert not re.search(r"= bf16\[64,\d+,\d+[^=]* (copy|transpose|fusion)\(",
                         text)


# the window-attention server's shapes (benchmarks/configs/
# mellum2_12b_l8.json): 32 query heads over 4 KV heads of 128, a window of
# 1024 over 16-token pages: a ring of 65 pages (1040 rows, not whole lane
# tiles) a slot; prompts padded to the static 4096
def test_ring_paged_attention_compiles(chip_compile):
    slots = 16
    text = chip_compile(
        lambda *a: pk.ring_paged_attention(*a, 1024),
        ((slots, 32, 128), BF16), ((slots * 65, 16, 512), BF16),
        ((slots * 65, 16, 512), BF16), ((slots,), I32))
    assert kernel_calls(text, ("mxtpu_rpa_ring", "mxtpu_rpa_flat")) \
        == {"mxtpu_rpa_ring": 1, "mxtpu_rpa_flat": 0}
    # a slot's ring is read where it lies (the 3-D pool seen as a ring a
    # slot is a bitcast): nothing of a pool's size is made
    import re
    assert not re.search(r"= bf16\[(1040|16),(16|1040),512[^=]* (copy|"
                         r"transpose|fusion)\(", text)


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "w1024"])
def test_prefill_flash_compiles_at_the_static_prompt(chip_compile, window):
    qkv = ((1, 32, 4096, 128), BF16)
    text = chip_compile(
        lambda q, k, v: pk.flash_attention(q, k, v, causal=True,
                                           window=window), qkv, qkv, qkv)
    assert kernel_calls(text, ("mxtpu_flash_fwd",)) == {"mxtpu_flash_fwd": 1}
