"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

One jitted train step (forward + backward + SGD-momentum update, donated
buffers), bf16 NHWC — the MXU-native layout. `vs_baseline` divides by the
reference class number from SURVEY.md §6: MXNet+cuDNN on A100 ~= 2500
images/sec/chip fp16 ResNet-50.

One process, one chip: run it through the chip tool
(`python bench.py`). It measures on a TPU backend only and refuses any
other; `--smoke` is a CPU control-flow check of the same code at a tiny
size, whose one JSON line says so and carries no rate. Prints exactly ONE
final JSON line on stdout (checkpoint lines of the same shape may precede
it: the last line is the result).
"""
from __future__ import annotations

import json
import os
import sys

BASELINE_IMG_S = 2500.0
ROOT = os.path.dirname(os.path.abspath(__file__))


def main():
    # perf lever (BENCH_XLA_FLAGS=1): XLA latency-hiding scheduler +
    # async collectives — must land in env BEFORE backend init
    if os.environ.get("BENCH_XLA_FLAGS") == "1":
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "") +
            " --xla_tpu_enable_latency_hiding_scheduler=true")
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import extract_pure_fn
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    from bench_util import smoke_line, tpu_or_smoke
    from mxnet_tpu.observability import compilex

    device, smoke = tpu_or_smoke("bench")
    print(f"[bench] compile cache: "
          f"{compilex.entry_compilation_cache(ROOT)}", file=sys.stderr)
    # larger batches lose on a v5e chip, so the default measures 128
    # only; BENCH_BATCH=a or BENCH_BATCH=a,b re-opens the sweep
    candidates, steps = ([8], 3) if smoke else ([128], 30)
    if os.environ.get("BENCH_BATCH"):
        candidates = [int(b) for b in
                      os.environ["BENCH_BATCH"].split(",")]
    steps = int(os.environ.get("BENCH_STEPS", steps))
    print(f"[bench] device={device} candidates={candidates} "
          f"steps={steps}", file=sys.stderr)

    net = resnet50_v1(layout="NHWC", stem_s2d=True)
    net.initialize()
    net.cast("bfloat16")
    # materialise deferred-shape params ONCE (eager forward at the
    # smallest batch) — per-candidate eager forwards would burn sweep
    # budget for nothing
    warm = mx.nd.random.uniform(shape=(8, 224, 224, 3), dtype="bfloat16")
    net(warm)

    lr, mu = 0.1, 0.9
    # perf lever (BENCH_FUSED_SGD=1): the multi-tensor update in place of
    # the per-tensor one
    fused = os.environ.get("BENCH_FUSED_SGD") == "1"
    # perf lever (BENCH_UNROLL=k): k train steps per jitted dispatch —
    # amortises per-dispatch host overhead AND lets XLA pipeline across
    # step boundaries, at k times the compile
    full_unroll = max(1, int(os.environ.get("BENCH_UNROLL",
                                            "1" if smoke else "8")))
    # later candidates only start while inside this budget — a
    # half-finished sweep must never eat the whole run
    SWEEP_BUDGET_S = 300

    def build(batch, unroll):
        """(step, params, mom, data) of one candidate."""
        x = mx.nd.random.uniform(shape=(batch, 224, 224, 3),
                                 dtype="bfloat16")
        fwd, params = extract_pure_fn(net, x, training=True)
        # donate COPIES: donation deletes the input buffers on TPU, and
        # the net's own parameter arrays must survive for the next
        # sweep candidate's trace
        params = [jnp.array(p) for p in params]
        key = jax.random.PRNGKey(0)
        labels = jax.random.randint(key, (batch,), 0, 1000)
        images = x._data
        aux_idx = list(fwd.aux_indices)

        def loss_fn(p, xb, yb):
            logits, aux = fwd(p, xb)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1)), aux

        from bench_util import make_sgd_step
        if fused:
            # the multi-tensor lever replaces the whole per-tensor
            # update, so it keeps its own step body
            def train_step_1(p, mom, xb, yb):
                (loss, aux), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, xb, yb)
                from mxnet_tpu.optimizer.optimizer import \
                    fused_sgd_mom_kernel
                new_p, new_mom = fused_sgd_mom_kernel(p, mom, g, lr, mu)
                for i, v in zip(aux_idx, aux):  # BN running stats carry
                    new_p[i] = v
                return new_p, new_mom, loss

            def train_step(p, mom, xb, yb):
                loss = None
                for _ in range(unroll):
                    p, mom, loss = train_step_1(p, mom, xb, yb)
                return p, mom, loss

            step = jax.jit(train_step, donate_argnums=(0, 1))
        else:
            step = make_sgd_step(loss_fn, aux_idx, lr, mu, unroll)
        mom = [jnp.zeros(p.shape, jnp.float32) if fused
               else jnp.zeros_like(p) for p in params]
        return step, params, mom, (images, labels)

    if smoke:
        step, params, mom, data = build(candidates[0], full_unroll)
        for _ in range(steps):
            params, mom, loss = step(params, mom, *data)
        print(smoke_line(device, steps, loss), flush=True)
        return 0

    def measure(batch, unroll=None, steps=steps):
        if unroll is None:
            unroll = full_unroll
        from bench_util import timed_measure
        step, params, mom, data = build(batch, unroll)
        return timed_measure(step, params, mom, data, steps,
                             batch * unroll, tag=f"bench b{batch}")

    from bench_util import sweep

    def checkpoint_resnet(img_s):
        print(json.dumps({
            "metric": "resnet50_train_throughput",
            "value": round(img_s, 2),
            "unit": "images/sec/chip",
            "device": device,
            "vs_baseline": round(img_s / BASELINE_IMG_S, 4)}), flush=True)

    # Staged measurement: land a fast unroll=1 number FIRST, so a failure
    # during the long full-unroll compile cannot zero the run (the last
    # parseable stdout line is the result). BENCH_STAGED=0 disables.
    stage1_img_s = 0.0
    if full_unroll > 1 and os.environ.get("BENCH_STAGED") != "0":
        try:
            stage1_img_s = measure(candidates[0], unroll=1, steps=10)
            checkpoint_resnet(stage1_img_s)
        except Exception as e:
            print(f"[bench] stage-1 (unroll=1) failed: {e!r}",
                  file=sys.stderr)

    try:
        best_img_s, best_batch = sweep(candidates, SWEEP_BUDGET_S,
                                       measure,
                                       on_best=checkpoint_resnet,
                                       tag="bench")
    except RuntimeError:
        # full-unroll sweep landed nothing — fall back to the stage-1
        # number so the later metrics still get their shot
        if stage1_img_s <= 0:
            raise
        # fallback ONLY: the stage-1 number is 10 steps of unroll=1 —
        # never let it outvote a completed full-unroll measurement
        best_img_s, best_batch = stage1_img_s, candidates[0]
    print(f"[bench] best: batch={best_batch} {best_img_s:.1f} img/s",
          file=sys.stderr)
    result = {
        "metric": "resnet50_train_throughput",
        "value": round(best_img_s, 2),
        "unit": "images/sec/chip",
        "device": device,
        "vs_baseline": round(best_img_s / BASELINE_IMG_S, 4),
    }

    # Captured one-executable step (ISSUE 4): steps/s + dispatches/step of
    # `Trainer.capture` on the reference MLP, recorded alongside the
    # headline metric on every non-smoke run (cheap: a few MLP steps).
    try:
        import bench_mlp
        cres = bench_mlp.measure_captured()
        result["captured_step_throughput"] = cres
        # ISSUE 11: compile cost + persistent-cache outcome of the
        # captured step as first-class fields of bench.py's JSON line —
        # the perf trajectory records compile cost alongside steps/s
        result["compile_seconds"] = cres.get("compile_seconds")
        result["compile_cache_hit"] = cres.get("compile_cache_hit")
    except Exception as e:  # pragma: no cover
        print(f"[bench] captured-step bench failed: {e!r}",
              file=sys.stderr)

    # Compile-space autotuner (ISSUE 20): measured winner of the XLA
    # flag search on the same captured step, as first-class bench.py
    # fields. Same honesty contract as the serve fields: OMITTED when
    # the search fails, never faked (speedup 1.0 means the defaults
    # won — a valid, recorded outcome).
    try:
        import bench_mlp
        ares = bench_mlp.measure_autotune()
        result["autotune_speedup"] = ares["value"]
        result["autotune_trials"] = ares["autotune_trials"]
    except Exception as e:  # pragma: no cover
        print(f"[bench] autotune bench failed: {e!r}",
              file=sys.stderr)

    # Rule-sharded captured step (ISSUE 8): steps/s + per-device param
    # bytes of the (dp,tp) shard plan vs the replicated captured step,
    # as first-class bench.py fields. Needs >= 4 devices (a (2,2)
    # mesh); below that the fields are omitted rather than faked.
    # BENCH_SHARD=0 disables.
    if os.environ.get("BENCH_SHARD") != "0":
        try:
            import bench_mlp
            shres = bench_mlp.measure_shard()
            if shres.get("value") is not None:
                result["shard_step_throughput"] = shres["value"]
                result["shard_param_bytes_per_dev"] = \
                    shres["shard_param_bytes_per_dev"]
                result["shard_vs_replicated"] = \
                    shres["shard_vs_replicated"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] shard bench failed: {e!r}", file=sys.stderr)
        # ISSUE 15: the recommender workload — sharded-embedding DLRM
        # steps/s + per-device embedding bytes vs the replicated
        # dense-take layout. Same honesty contract: the fields are
        # OMITTED below 4 devices (bench_rec reports value None), never
        # faked; own guard so a rec failure can't take down the shard
        # fields above.
        try:
            import bench_rec
            rres = bench_rec.measure()
            if rres.get("value") is not None:
                result["rec_step_throughput"] = rres["value"]
                result["rec_embed_bytes_per_dev"] = \
                    rres["rec_embed_bytes_per_dev"]
                result["rec_vs_replicated"] = rres["rec_vs_replicated"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] rec bench failed: {e!r}", file=sys.stderr)
        # ISSUE 19: the tiered-embedding arm — DLRM steps/s at a FIXED
        # HBM budget (per-shard rows >> hbm_rows, so the table cannot
        # be device-resident), with the hot-cache hit rate and the
        # async H2D row-staging bytes each step costs. Same honesty
        # contract: fields OMITTED below 4 devices, never faked; own
        # guard so a tiered failure can't take down the rec fields
        # above.
        try:
            import bench_rec
            tres = bench_rec.measure_tiered()
            if tres.get("value") is not None:
                result["rec_tiered_step_throughput"] = tres["value"]
                result["rec_tiered_hit_rate"] = \
                    tres["rec_tiered_hit_rate"]
                result["rec_tiered_h2d_bytes_per_step"] = \
                    tres["rec_tiered_h2d_bytes_per_step"]
                result["rec_tiered_resident_frac"] = \
                    tres["rec_tiered_resident_frac"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] tiered rec bench failed: {e!r}",
                  file=sys.stderr)
        # ISSUE 18: the elastic grow-back episode — shrink/regrow
        # resharding latency plus the fleet counters of a supervised
        # shrink -> regrow round trip. Same honesty contract: fields
        # OMITTED below 4 devices (bench_mlp reports value None), never
        # faked; fleet_restarts is 0 in-process by construction (only
        # the launcher's respawn path increments it). BENCH_FLEET=0
        # disables; own guard so a fleet failure can't take down the
        # shard fields above.
        if os.environ.get("BENCH_FLEET") != "0":
            try:
                flres = bench_mlp.measure_fleet()
                if flres.get("value") is not None:
                    result["fleet_regrow_ms"] = flres["value"]
                    result["fleet_regrows"] = flres["fleet_regrows"]
                    result["fleet_restarts"] = flres["fleet_restarts"]
            except Exception as e:  # pragma: no cover
                print(f"[bench] fleet bench failed: {e!r}",
                      file=sys.stderr)
        # ISSUE 16: expert parallelism — sharded-MoE steps/s vs the
        # equal-parameter dense FFN, with the capacity-overflow drop
        # fraction the run suffered. Same honesty contract: fields
        # OMITTED below 4 devices (bench_moe reports value None), never
        # faked; own guard so an MoE failure can't take down the rec/
        # shard fields above.
        try:
            import bench_moe
            mres = bench_moe.measure()
            if mres.get("value") is not None:
                result["moe_step_throughput"] = mres["value"]
                result["moe_vs_dense_ffn"] = mres["moe_vs_dense_ffn"]
                result["moe_drop_frac"] = mres["moe_drop_frac"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] moe bench failed: {e!r}", file=sys.stderr)

    # Serving headline (ISSUE 6): continuous-batching tokens/s + p99
    # latency under Poisson arrivals, recorded as first-class fields of
    # bench.py's JSON contract alongside the training metric (a serve
    # failure must not take down the headline). BENCH_SERVE=0 disables.
    if os.environ.get("BENCH_SERVE") != "0":
        try:
            import bench_serve
            sres = bench_serve.measure()
            # scalar contract fields only — the BERT block below assigns
            # (not appends) extra_metrics, so serve stays out of that list
            result["serve_tokens_per_s"] = sres["value"]
            result["serve_p99_ms"] = sres["p99_ms"]
            result["serve_speedup_vs_static"] = sres["speedup_vs_static"]
            # ISSUE 7: decode p99 while a background-train flood contends
            # for the engine — the QoS win a serving tenant sees when it
            # shares chips with training (FIFO twin rides along)
            if "p99_contended_ms" in sres:
                result["serve_p99_contended_ms"] = sres["p99_contended_ms"]
                result["serve_p99_contended_fifo_ms"] = \
                    sres["p99_contended_fifo_ms"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] serve bench failed: {e!r}", file=sys.stderr)
        # ISSUE 12: the serving fast path — prefix-cache speedup on the
        # shared-system-prompt mix + speculative acceptance/turns. Own
        # guard: a fast-path failure must not take down the headline
        # serve fields already recorded above.
        try:
            import bench_serve
            fres = bench_serve.measure_fastpath()
            result["serve_prefix_hit_rate"] = fres["prefix_hit_rate"]
            result["serve_prefix_speedup"] = fres["prefix_speedup"]
            result["serve_spec_accept_rate"] = fres["spec_accept_rate"]
            result["serve_decode_turns_per_token"] = \
                fres["spec_turns_per_token"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] serve fast-path bench failed: {e!r}",
                  file=sys.stderr)
        # ISSUE 14: low-precision serving — int8-KV tokens/s ratio +
        # token capacity at a fixed HBM budget, with the accuracy
        # contract (greedy token match vs fp32) riding the same JSON so
        # the speed ratio never ships without it. Own guard, as above.
        try:
            import bench_serve
            ires = bench_serve.measure_int8kv()
            result["serve_int8_kv_speedup"] = ires["speedup_vs_fp"]
            result["serve_int8_token_match"] = ires["token_match"]
            result["serve_int8_capacity_ratio"] = \
                ires["capacity_tokens_ratio"]
        except Exception as e:  # pragma: no cover
            print(f"[bench] serve int8 bench failed: {e!r}",
                  file=sys.stderr)

    # Second headline metric (BASELINE.json): BERT-base MLM tokens/sec/chip.
    # Merged into the same single JSON line so the driver's one-line parse
    # still works; a BERT failure must not take down the ResNet metric.
    if os.environ.get("BENCH_SKIP_BERT") != "1":
        try:
            import bench_bert

            def checkpoint(bert_res):
                merged = dict(result)
                merged["extra_metrics"] = [bert_res]
                print(json.dumps(merged), flush=True)

            result["extra_metrics"] = [
                bench_bert.measure(on_result=checkpoint)]
        except Exception as e:  # pragma: no cover
            print(f"[bench] bert bench failed: {e!r}", file=sys.stderr)

    # remaining BASELINE configs, opt-in so the
    # driver's default line stays fast; a failure can't take down the
    # headline metrics. BENCH_DET=1 runs BOTH halves of BASELINE config
    # 5 (SSD-512 and Faster-RCNN).
    extra_measures = []
    if os.environ.get("BENCH_MLP") == "1":
        extra_measures.append(("bench_mlp", "measure"))
    if os.environ.get("BENCH_PREFETCH") == "1":
        extra_measures.append(("bench_mlp", "measure_prefetch"))
    if os.environ.get("BENCH_INT8") == "1":
        extra_measures.append(("bench_int8", "measure"))
    if os.environ.get("BENCH_NMT") == "1":
        extra_measures.append(("bench_nmt", "measure"))
    if os.environ.get("BENCH_DET") == "1":
        extra_measures.append(("bench_det", "measure"))
        extra_measures.append(("bench_det", "measure_rcnn"))
    for modname, fn in extra_measures:
        try:
            mod = __import__(modname)
            result.setdefault("extra_metrics", []).append(
                getattr(mod, fn)())
            print(json.dumps(result), flush=True)  # checkpoint
        except Exception as e:  # pragma: no cover
            print(f"[bench] {modname}.{fn} failed: {e!r}", file=sys.stderr)

    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
