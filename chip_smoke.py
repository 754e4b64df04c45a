"""Chip smoke: the trainer and the server, end to end, on one TPU chip.

    python chip_smoke.py            # one chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: the (dp=2, tp=2) sharded
                                    # step against the one-chip step, only

Drives the two main paths through the entry points a user calls, at the
full width of models the repo ships, with weights and data from --seed:

  train  BERT-base (12 x 768 x 12, vocab 30522) bf16, 16 x 512 tokens,
         76 masked positions, `gluon.Trainer` SGD-momentum +
         `Trainer.capture`, 4 steps on one fixed batch.
  serve  `transformer_base()` (512 wide, 6+6 layers, 8 heads) behind
         `mx.serve.Server` (engine-driven), 12 mixed-length requests,
         then the same 12 on an int8-KV server.
  shard  (--chips 4 only) the train phase's step under
         `tr.shard(mesh={"dp": 2, "tp": 2})`, 3 steps, against the same
         3 steps of the one-chip captured step in the same process.

Every phase raises on failure. The last stdout line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`;
without a TPU the script refuses (`"ok": false`, exit 1) — it never sets
JAX_PLATFORMS itself. The times it prints are smoke values (did it start,
did it compile once), not benchmark metrics. One process, no children.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# pallas_call names (ops/pallas_kernels.py) as they appear in compiled HLO
TRAIN_KERNELS = ("mxtpu_flash_fwd", "mxtpu_flash_bwd_dkv",
                 "mxtpu_flash_bwd_dq", "mxtpu_layer_norm")
SERVE_KERNELS = ("mxtpu_rpa",)

BERT_BASE = dict(num_layers=12, units=768, hidden_size=3072, num_heads=12,
                 vocab_size=30522)
TRAIN_SHAPE = dict(batch=16, seq=512, masked=76)
NMT_BASE = dict(vocab_size=36548, units=512, hidden=2048, num_layers=6,
                num_heads=8)
SERVE_SHAPE = dict(slots=8, page_size=16, max_src_len=32, max_new_tokens=32)


def device_record():
    """The device as jax reports it; goes into the result line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def compiled_text(executable):
    """Optimized-HLO text of a live instrumented executable, re-lowered
    from the aval skeleton of its last compile (no python re-trace; a
    persistent-cache hit when the cache is on)."""
    from mxnet_tpu.observability import compilex
    ij = compilex.instrumented().get(executable)
    check(ij is not None and ij.last_abstract is not None,
          f"{executable} never compiled through the compile observatory")
    args, kwargs = ij.last_abstract
    return ij.lower(*args, **kwargs).compile().as_text()


def kernel_calls(text, names):
    """{kernel name: number of tpu_custom_call instructions carrying it}."""
    lines = [l for l in text.splitlines() if "tpu_custom_call" in l]
    return {n: sum(n in l for l in lines) for n in names}


def _total(name):
    """Sum of a registry counter over all its label sets."""
    from mxnet_tpu.observability import registry
    return sum(c.value for c in registry().series(name))


# ---------------------------------------------- kernels against references
def _agree(name, got, want, tol=2e-2):
    """Finite, same shape, and within `tol` of the reference relative to
    the reference's largest magnitude — bf16-level: on the chip an f32
    matmul of the XLA reference runs bf16 passes by default too."""
    import numpy as np
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    check(got.shape == want.shape, f"{name}: shape {got.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite values")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    say(f"kernel {name}: max error {err:.2e} of the reference's range")
    check(err <= tol, f"{name}: off the XLA reference by {err:.2e}")


def check_train_kernels(seed=0, heads=12, seq=512, units=768):
    """Flash attention (padding-mask form, as BERT calls it) forward and
    backward, and the fused layernorm, against their XLA references on a
    small input at the model's head width."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dh = units // heads
    q, k, v, w = (jax.random.normal(kk, (2, heads, seq, dh), jnp.bfloat16)
                  for kk in ks[:4])
    vl = jnp.array([seq, seq // 2 + 3], jnp.int32)
    mask = pk._lengths_mask(vl, seq)

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, kv_lengths=vl)

    def ref(q, k, v):
        return pk.attention_reference(q, k, v, mask=mask)

    def grads(attn):
        return jax.grad(lambda q, k, v: (
            attn(q, k, v).astype(jnp.float32)
            * w.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)

    _agree("flash fwd", flash(q, k, v), ref(q, k, v))
    for n, a, b in zip(("dq", "dk", "dv"), grads(flash), grads(ref)):
        _agree(f"flash {n}", a, b)

    x = jax.random.normal(ks[4], (1024, units), jnp.bfloat16) * 3 + 1
    g = jax.random.normal(ks[5], (units,), jnp.bfloat16)

    def ln_ref(x, g, b):
        xf = x.astype(jnp.float32)
        xc = xf - xf.mean(-1, keepdims=True)
        y = xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True) + 1e-5)
        return (y * g.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)

    for n, f in (("fwd", lambda f: f), ("dx", lambda f: jax.grad(
            lambda x, g, b: f(x, g, b).astype(jnp.float32).sum()))):
        _agree(f"layernorm {n}", f(pk.fused_layer_norm)(x, g, g),
               f(ln_ref)(x, g, g))


def check_serve_kernels(seed=0, slots=8, heads=8, dh=64, page_size=16):
    """The one-token ragged paged attention launch, full-precision and
    int8 pages, against the lax gather reference at the server's shapes."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    rng = np.random.RandomState(seed)
    npages, pool = 4, 4 * slots + 1
    q = jnp.asarray(rng.randn(slots, heads, dh).astype(np.float32))
    # head-major, as the server keeps its pools: on the chip, rows of
    # whole lane tiles, and the lanes past the head hold noise
    shape = (heads, pool, page_size, pk.pool_lanes(dh))
    pt = jnp.asarray(1 + rng.permutation(pool - 1)[:slots * npages]
                     .reshape(slots, npages).astype(np.int32))
    lens = jnp.asarray(rng.randint(1, npages * page_size + 1, (slots,))
                       .astype(np.int32))
    kp, vp = (jnp.asarray(rng.randn(*shape).astype(np.float32))
              for _ in range(2))
    _agree("paged attention", pk.ragged_paged_attention(q, kp, vp, pt, lens),
           pk._paged_attention_lax(q, kp, vp, pt, lens))
    kq, vq = (jnp.asarray(rng.randint(-127, 128, shape).astype(np.int8))
              for _ in range(2))
    sc = [jnp.asarray((rng.rand(heads, pool) * 0.05 + 1e-3)
                      .astype(np.float32)) for _ in range(2)]
    _agree("paged attention int8",
           pk.ragged_paged_attention(q, kq, vq, pt, lens,
                                     k_scales=sc[0], v_scales=sc[1]),
           pk._paged_attention_lax(q, kq, vq, pt, lens,
                                   k_scales=sc[0], v_scales=sc[1]))


# ------------------------------------------------------------------ train
def _bert_and_batch(seed, model_cfg, batch, seq, masked):
    """BERT pretraining model (bf16, params materialised by one eager
    forward under `autograd.record`) and one fixed batch from `seed`.
    Returns (model, batch tuple, loss_fn, eager loss of that batch)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.models.bert import BERTForPretraining, BERTModel

    mx.random.seed(seed)
    vocab = model_cfg["vocab_size"]
    model = BERTForPretraining(BERTModel(max_length=seq, dropout=0.0,
                                         **model_cfg))
    model.initialize()
    model.cast("bfloat16")

    rng = np.random.RandomState(seed)
    data = (
        nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.int32)),
        nd.array(np.zeros((batch, seq), np.int32)),
        nd.array(rng.randint(seq // 2, seq + 1, (batch,)).astype(np.int32)),
        nd.array(rng.randint(0, seq, (batch, masked)).astype(np.int32)),
        nd.array(rng.randint(0, vocab, (batch, masked)).astype(np.int32)),
        nd.array(rng.randint(0, 2, (batch,)).astype(np.int32)),
    )
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(tok, seg, vl, pos, mlm_y, nsp_y):
        mlm, nsp = model(tok, seg, vl, pos)
        mlm = mlm.astype("float32").reshape((-1, vocab))
        return (ce(mlm, mlm_y.reshape((-1,))).mean()
                + ce(nsp.astype("float32"), nsp_y).mean())

    with autograd.record():
        eager = loss_fn(*data)
    return model, data, loss_fn, float(eager.asnumpy())


def _run_steps(step, data, steps):
    """Call the captured step `steps` times on one batch; returns
    (losses, seconds per call), each call timed to `wait_to_read`."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(*data)
        loss.wait_to_read()
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss.asnumpy()))
    return losses, secs


def phase_train(seed=0, model_cfg=BERT_BASE, steps=4, lr=0.01,
                **shape):
    """BERT pretraining through `gluon.Trainer` + `Trainer.capture`."""
    import math
    import mxnet_tpu as mx
    from mxnet_tpu import engine
    from mxnet_tpu.observability import compilex

    shape = {**TRAIN_SHAPE, **shape}
    dev = device_record()
    on_chip = dev["platform"] == "tpu"
    say(f"train: device {dev['kind']} x{dev['count']}; engine "
        f"{'native' if engine.native_engine_loaded() else 'python'}; "
        f"compile cache {compilex.compilation_cache_dir()}")
    compiles0 = compilex.executables().get("captured_step", 0)
    hits0 = _total("cachedop_cache_hits")
    fallbacks0 = _total("cachedop_fallbacks")
    ignored0 = _total("pallas_block_override_ignored")

    check_train_kernels(seed, heads=model_cfg["num_heads"],
                        seq=shape["seq"], units=model_cfg["units"])
    model, data, loss_fn, eager = _bert_and_batch(seed, model_cfg, **shape)
    tr = mx.gluon.Trainer(model.collect_params(), "sgd",
                          {"learning_rate": lr, "momentum": 0.9})
    step = tr.capture(loss_fn)
    losses, secs = _run_steps(step, data, steps)
    say(f"train: eager loss {eager:.4f}; captured losses "
        + " ".join(f"{l:.4f}" for l in losses))
    say(f"train: compile+first step {secs[0]:.2f} s "
        f"(observatory {step.last_compile_seconds:.2f} s); later steps "
        + " ".join(f"{s:.4f}" for s in secs[1:]) + f" s on {dev['kind']}")

    check(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    # bf16 forward, ~11 nats: eager op-by-op and the fused program round
    # differently, well inside 2%
    check(abs(losses[0] - eager) <= 0.02 * abs(eager),
          f"step-1 loss {losses[0]} != eager loss {eager}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(step.last_fallback_reason is None and step.cache_size == 1,
          f"captured step fell back ({step.last_fallback_reason}) or "
          f"retraced (cache {step.cache_size})")
    compiles = compilex.executables().get("captured_step", 0) - compiles0
    hits = _total("cachedop_cache_hits") - hits0
    check(compiles == 1 and hits == steps - 1,
          f"captured step compiled {compiles}x, {hits} cache hits in "
          f"{steps} steps")
    check(_total("cachedop_fallbacks") == fallbacks0,
          "cachedop_fallbacks moved")
    check(_total("pallas_block_override_ignored") == ignored0,
          "pallas_block_override_ignored moved")
    if on_chip:
        found = kernel_calls(compiled_text("captured_step"), TRAIN_KERNELS)
        say(f"train: tpu_custom_call per kernel {found}")
        check(all(found.values()), f"kernel missing from the step: {found}")
        peak = mx.utils.memory_stats(0)["peak_bytes_in_use"]
        say(f"train: peak_bytes_in_use {peak} on {dev['kind']}")
    hits, misses = compilex.compile_cache_stats()
    say(f"train: persistent compile cache hits {hits} misses {misses}")
    return {"losses": losses, "eager": eager, "step_seconds": secs}


# ------------------------------------------------------------------ serve
def _requests(seed, vocab, max_src_len, n=12):
    import numpy as np
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        n_src = int(rng.randint(4, max_src_len + 1))
        reqs.append({
            "src": rng.randint(4, vocab, (n_src,)).astype(np.int32),
            "budget": int(rng.choice([4, 8, 16, 32])),
            "stream": i in (3, 7),
        })
    return reqs


def _serve_all(srv, reqs):
    """Submit every request, consume them (two streamed), drain. Returns
    (token lists, seconds from submit to each request's first token)."""
    handles = [srv.submit(r["src"], max_new_tokens=r["budget"])
               for r in reqs]
    out, ttft = [], []
    for r, h in zip(reqs, handles):
        if r["stream"]:
            toks = list(h.stream(timeout=600))
        else:
            toks = list(h.result(timeout=600))
        out.append([int(t) for t in toks])
        ttft.append(h.ttft)
    check(srv.wait(timeout=600), "server did not drain")
    return out, ttft


def _beam1_reference(model, reqs, max_src_len, max_new_tokens, eos_id=3):
    """Greedy reference: `beam_search_cached` with beam 1 over all
    requests in one batch (sources padded to the server's static length
    and masked by valid length, as the server's prefill does)."""
    import numpy as np
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer import beam_search_cached
    src = np.zeros((len(reqs), max_src_len), np.int32)
    for i, r in enumerate(reqs):
        src[i, :r["src"].size] = r["src"]
    vl = np.array([r["src"].size for r in reqs], np.int32)
    tokens, _ = beam_search_cached(model, nd.array(src), nd.array(vl),
                                   beam_size=1,
                                   max_length=max_new_tokens + 1)
    want = []
    for r, row in zip(reqs, tokens.asnumpy()[:, 0]):
        toks = [int(t) for t in row[1:1 + r["budget"]]]   # row[0] is BOS
        if eos_id in toks:
            toks = toks[:toks.index(eos_id) + 1]
        want.append(toks)
    return want


def phase_serve(seed=0, model_cfg=NMT_BASE, **shape):
    """`mx.serve.Server` on `transformer_base`: 12 mixed requests against
    the beam-1 dense-cache decoder, then the same 12 with int8 KV."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    shape = {**SERVE_SHAPE, **shape}
    dev = device_record()
    on_chip = dev["platform"] == "tpu"
    check_serve_kernels(seed, slots=shape["slots"],
                        heads=model_cfg["num_heads"],
                        dh=model_cfg["units"] // model_cfg["num_heads"],
                        page_size=shape["page_size"])
    mx.random.seed(seed)
    model = TransformerNMT(max_length=64, dropout=0.0, **model_cfg)
    model.initialize()
    reqs = _requests(seed, model_cfg["vocab_size"], shape["max_src_len"])
    want = _beam1_reference(model, reqs, shape["max_src_len"],
                            shape["max_new_tokens"])

    results = {}
    for kv_dtype, exe in ((None, "serve_decode"),
                          ("int8", "serve_decode_int8")):
        tag = kv_dtype or "fp"
        with mx.serve.Server(model, kv_dtype=kv_dtype, **shape) as srv:
            got, ttft = _serve_all(srv, reqs)
            traces = srv.runtime.decode_traces
            leaked = srv.pool.in_use()
        say(f"serve[{tag}]: tokens per request {[len(g) for g in got]}")
        say(f"serve[{tag}]: smoke value, seconds to first token "
            + " ".join(f"{t:.3f}" for t in ttft)
            + f" on {dev['kind']} (first includes compilation)")
        check(all(got) and all(len(g) <= r["budget"]
                               for g, r in zip(got, reqs)),
              f"serve[{tag}]: empty or over-budget answer")
        check(traces == 1, f"serve[{tag}]: decode traced {traces}x")
        check(leaked == 0, f"serve[{tag}]: {leaked} KV pages leaked")
        if on_chip:
            found = kernel_calls(compiled_text(exe), SERVE_KERNELS)
            say(f"serve[{tag}]: tpu_custom_call per kernel {found}")
            check(all(found.values()),
                  f"serve[{tag}]: kernel missing from decode: {found}")
        results[tag] = got
    same = sum(g == w for g, w in zip(results["fp"], want))
    say(f"serve[fp]: {same}/{len(reqs)} requests token-equal to beam-1 "
        f"beam_search_cached")
    differ = sum(a != b for a, b in zip(results["fp"], results["int8"]))
    say(f"serve[int8]: {differ}/{len(reqs)} requests differ from the "
        f"full-precision KV server")
    check(same == len(reqs),
          f"serve[fp]: only {same}/{len(reqs)} requests equal the "
          f"beam-1 reference")
    return results


# ------------------------------------------------------------------ shard
def phase_shard(seed=0, model_cfg=BERT_BASE, steps=3, lr=0.01,
                mesh_shape=None, **shape):
    """The captured BERT step under `tr.shard(mesh={"dp": 2, "tp": 2})`
    against the same steps of the one-chip captured step, same seed."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.observability import compilex

    shape = {**TRAIN_SHAPE, **shape}
    mesh_shape = mesh_shape or {"dp": 2, "tp": 2}
    n_dev = 1
    for v in mesh_shape.values():
        n_dev *= v
    check(len(jax.devices()) >= n_dev,
          f"need {n_dev} devices, have {len(jax.devices())}")
    devices = jax.devices()[:n_dev]

    model, data, loss_fn, _ = _bert_and_batch(seed, model_cfg, **shape)
    tr = mx.gluon.Trainer(model.collect_params(), "sgd",
                          {"learning_rate": lr, "momentum": 0.9})
    one, _ = _run_steps(tr.capture(loss_fn), data, steps)
    say("shard: one-chip losses " + " ".join(f"{l:.4f}" for l in one))
    del model, tr

    model, data, loss_fn, _ = _bert_and_batch(seed, model_cfg, **shape)
    tr = mx.gluon.Trainer(model.collect_params(), "sgd",
                          {"learning_rate": lr, "momentum": 0.9},
                          kvstore="ici")
    from mxnet_tpu.shard import as_mesh
    tr.shard(mesh=as_mesh(mesh_shape, devices=devices))
    step = tr.capture(loss_fn)
    sharded, secs = _run_steps(step, data, steps)
    say(f"shard: {mesh_shape} losses "
        + " ".join(f"{l:.4f}" for l in sharded)
        + f"; first call {secs[0]:.2f} s, later "
        + " ".join(f"{s:.4f}" for s in secs[1:]) + " s")
    check(step.last_fallback_reason is None, "sharded step fell back")
    for a, b in zip(one, sharded):
        check(abs(a - b) <= 0.02 * abs(a),
              f"sharded loss {sharded} != one-chip loss {one}")

    # the parameters really spread: a tp-sharded weight lives on every
    # device, and no device holds the whole model
    w = model.bert.encoder.layers[0].ffn.ffn1.weight.data()._data
    on = {s.device for s in w.addressable_shards}
    check(len(on) == n_dev, f"ffn1 weight on {len(on)} devices: {on}")
    per_dev = {d: 0 for d in devices}
    total = 0
    for p in model.collect_params().values():
        a = p.data()._data
        total += a.nbytes
        for s in a.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    say(f"shard: parameter bytes per device "
        f"{[per_dev[d] for d in devices]} of {total} total")
    check(max(per_dev.values()) < total, "a device holds every parameter")
    text = compiled_text("sharded_step")
    found = kernel_calls(text, TRAIN_KERNELS)
    say(f"shard: collectives "
        f"{compilex.inspect_hlo_text(text)['collectives']}; "
        f"tpu_custom_call per kernel {found}")
    if device_record()["platform"] == "tpu":
        check(all(found.values()),
              f"kernel missing from the sharded step: {found}")
    return {"one": one, "sharded": sharded}


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_record()
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        say(f"refusing: needs {args.chips} TPU chip(s), jax reports {dev}")
        print(json.dumps({"ok": False, "device": dev}))
        return 1
    from mxnet_tpu.observability import compilex
    compilex.entry_compilation_cache(ROOT)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_shard(seed=args.seed)
    else:
        phase_train(seed=args.seed)
        phase_serve(seed=args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
