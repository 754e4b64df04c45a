#!/usr/bin/env python
"""HLO fusion/collective budget gate (ISSUE 11; same tier-1 wiring
pattern as check_dispatch).

Raw TPU speed is decided by what XLA fuses and how many collectives /
copies survive lowering (arXiv:2301.13062) — hardware-independent HLO
structure is a perf currency that needs no chip. This gate compiles the framework's own executables through the
compile observatory (observability/compilex.py) and budgets their
optimized-HLO counts:

  * captured step (replicated, single executable): fusion count inside a
    pinned band, ZERO collectives, and every donated parameter/optimizer
    buffer aliased input->output (donation held — no cross-program copy
    of the update path; 4 params + 4 momentum buffers = 8 aliases for
    the reference MLP);
  * captured step under the (2,2) ('dp','tp') DEFAULT_RULES shard plan:
    the collective mix must EXACTLY match the budget derived from the
    rules (gradient reduction over dp -> all-reduce; rule-sharded
    weights gathered before use -> all-gather; batch/layout resharding
    -> all-to-all / collective-permute), fusion band holds, donation
    aliases hold. Needs >= 4 devices (tier-1 conftest forks 8); skipped
    cleanly below that;
  * the sharded-embedding captured step (ISSUE 15; >= 4 devices): the
    sparse-lookup fast path's all-to-all count pinned EXACTLY at 2 per
    table (bucketed index exchange + vector return), cross-checked
    in-process against shard/embedding.py's A2A_PER_TABLE, with every
    donated table/tower buffer aliased;
  * the expert-parallel MoE captured step (ISSUE 16; >= 4 devices): the
    token-routing all-to-all count pinned EXACTLY at 2 per layer per
    traversal x 2 traversals (forward + vjp), cross-checked in-process
    against shard/moe.py's A2A_PER_LAYER * STEP_TRAVERSALS, with every
    donated expert-bank buffer aliased;
  * serve decode + prefill executables: fusion bands, zero collectives,
    and the donated KV-page pools / encoder-memory buffers aliased;
  * a deliberately DE-FUSED control: a subprocess compiles the same
    captured step with XLA's fusion pass disabled
    (--xla_disable_hlo_passes=fusion) and the same budget must TRIP on
    it — proving the gate bites, not just that the numbers were copied
    from a passing run.

ALL budgets live in BUDGETS below — a legitimate fusion-count shift is a
one-line reviewed edit here, not a scattered test hunt
(tests/test_check_fusion.py asserts against this same table).

Standalone:

    JAX_PLATFORMS=cpu python tools/check_fusion.py

exit 0 = within budget, 1 = violation (details on stderr). Prints one
JSON line with the measured counts on stdout.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

# ---------------------------------------------------------------------
# THE budget table (the one place; see module doc). Bands are (lo, hi)
# inclusive; scalar entries are exact. On the installed toolchain (jax
# 0.9.0 CPU): captured 28 fusions, sharded 39, decode 30, prefill 20 —
# bands leave ~±60% headroom for benign drift while still rejecting a
# de-fused build outright (fusion pass off: 99 one-op fusions).
BUDGETS = {
    "captured_step": {
        "fusions": (10, 40),
        "collective_total": 0,       # no mesh -> no collectives, exactly
        "aliased_inputs": 8,         # 4 params + 4 momenta, all donated
    },
    "sharded_step": {
        "fusions": (18, 70),
        # rule-derived mix for the reference MLP under the (2,2) plan:
        #   all-reduce        — dp gradient/loss reduction
        #   all-gather        — rule-sharded weights gathered before use
        #   all-to-all /      — batch + layout resharding between the
        #   collective-permute  dp-split batch and tp-sharded matmuls
        # (a count pin: what these rules give under the installed XLA
        # partitioner, jax 0.9.0 — re-derive it when either changes)
        "collectives": {"all-reduce": 5, "all-gather": 6,
                        "all-to-all": 3, "collective-permute": 4},
        "aliased_inputs": 8,
    },
    "serve_decode": {
        "fusions": (14, 56),
        "collective_total": 0,
        "aliased_inputs": 2,         # donated K/V page pools
    },
    # PR 33: ONE dispatch encodes a turn's admissions in a device loop
    # over its rows. Measured 22 fusions / 5 copies whatever R (the loop
    # body is the parent's one-row program: 20 / 3); a form that unrolls
    # the rows grows with R (65 / 37 at 16) and one that copies the
    # donated memory buffers shows in the copy band first.
    "serve_prefill": {
        "fusions": (8, 36),
        "collective_total": 0,
        "copies": (0, 10),
        "aliased_inputs": 3,         # donated mem_k / mem_v / mem_vl
    },
    # ISSUE 12: the WIDENED speculative-verify decode executable
    # ((slots, k+1) window per turn). Measured 35 fusions / 10 copies on
    # the pinned toolchain; the copy band additionally guards the
    # donation path — a widened program that starts materialising its
    # page pools out of place would show up here first.
    "serve_verify": {
        "fusions": (16, 60),
        "collective_total": 0,
        "copies": (0, 24),
        "aliased_inputs": 2,         # donated K/V page pools
    },
    # ISSUE 14: the QUANTIZED-serve executables (int8 KV pages with
    # per-page scales + per-channel int8 weights, one server covering
    # both dequant paths). Measured 65 fusions / 22 copies (decode) and
    # 68 / 22 (verify) on the pinned toolchain — the running-max
    # requantising page writes cost scatters, not copy passes, and the
    # weight/KV dequant must stay fused into the dots (a copy-band trip
    # here is the dequant materialising). All FOUR donated pool buffers
    # (K/V pages + K/V scales) must alias or the in-place page-write
    # story is fiction at 2x token capacity.
    "serve_decode_int8": {
        "fusions": (30, 110),
        "collective_total": 0,
        "copies": (0, 40),
        "aliased_inputs": 4,         # donated K/V pages + K/V scales
    },
    "serve_verify_int8": {
        "fusions": (32, 115),
        "collective_total": 0,
        "copies": (0, 40),
        "aliased_inputs": 4,
    },
    # ISSUE 15: the sharded-embedding captured step (two ShardedEmbedding
    # tables + a dense tower on the (2,2) DEFAULT_RULES mesh). The
    # headline pin is `all_to_all`: the sparse fast path lowers each
    # table's lookup to EXACTLY one bucketed index exchange plus one
    # vector return (shard/embedding.py A2A_PER_TABLE == 2), so the
    # fixture's two tables must cost exactly 4 all-to-alls — run()
    # cross-checks this pin against A2A_PER_TABLE * n_tables, so the
    # budget and the exchange math cannot drift apart silently. The
    # other collective kinds are GSPMD's dense-tower/replication
    # plumbing and stay un-pinned (the mix shifts benignly with XLA
    # versions; a sparse-path regression shows up in the a2a count or
    # the copy band first). Measured 89 fusions / 34 copies on the
    # pinned toolchain. All 4 donated buffers (2 tables + dense W/b)
    # must alias — table donation is the mesh-residency story.
    "sharded_embed_step": {
        "fusions": (45, 135),
        "all_to_all": 4,
        "copies": (0, 68),
        "aliased_inputs": 4,
    },
    # ISSUE 16: the expert-parallel MoE captured step (a Dense stem +
    # two ShardedMoE layers on the (2,2) DEFAULT_RULES mesh). The
    # headline pin is again `all_to_all`: each MoE layer costs EXACTLY
    # 2 all-to-alls per traversal (token dispatch + expert-output
    # return; shard/moe.py A2A_PER_LAYER == 2) and the training step
    # traverses twice (forward + the vjp, whose transposes are
    # themselves all-to-alls; STEP_TRAVERSALS == 2), so the fixture's
    # two layers must cost exactly 2*2*2 = 8 — run() cross-checks the
    # pin against A2A_PER_LAYER * STEP_TRAVERSALS * n_layers so the
    # budget and the routing math cannot drift apart silently. The
    # Dense stem is load-bearing: without a layer below the first MoE
    # its input cotangent is dead and XLA deletes one backward a2a —
    # pin 8, not 7, because real stacks always have live dx. Measured
    # 168 fusions / 94 copies on the pinned toolchain. All 12
    # differentiable params (stem W/b + per-layer gate + 4 expert
    # banks, plain SGD) must alias — expert-bank donation is the
    # mesh-residency story.
    "moe_step": {
        "fusions": (85, 250),
        "all_to_all": 8,
        "copies": (0, 188),
        "aliased_inputs": 12,
    },
}

CONTROL_TIMEOUT_S = 240


def check_budget(name, info, budget=None):
    """Evaluate one executable's HLO counts against its BUDGETS entry;
    returns a list of violation strings (empty = within budget)."""
    budget = budget if budget is not None else BUDGETS[name]
    errors = []
    if info is None:
        return [f"{name}: no HLO inspection available (compile observatory "
                f"disabled or inspection failed)"]
    lo, hi = budget["fusions"]
    if not lo <= info["fusions"] <= hi:
        errors.append(f"{name}: fusion count {info['fusions']} outside "
                      f"the pinned band [{lo}, {hi}]")
    if "collective_total" in budget \
            and info["collective_total"] != budget["collective_total"]:
        errors.append(f"{name}: {info['collective_total']} collective(s) "
                      f"(expected exactly {budget['collective_total']}: "
                      f"{info['collectives']})")
    if "collectives" in budget and info["collectives"] \
            != budget["collectives"]:
        errors.append(f"{name}: collective mix {info['collectives']} != "
                      f"rule-derived budget {budget['collectives']}")
    if "all_to_all" in budget and info["collectives"].get(
            "all-to-all", 0) != budget["all_to_all"]:
        errors.append(
            f"{name}: {info['collectives'].get('all-to-all', 0)} "
            f"all-to-all(s) (expected exactly {budget['all_to_all']} — "
            f"the exchange math pins 2 per sharded table per lookup "
            f"and 2 per MoE layer per traversal: one dispatch + one "
            f"return)")
    if "copies" in budget:
        lo, hi = budget["copies"]
        if not lo <= info["copies"] <= hi:
            errors.append(f"{name}: copy count {info['copies']} outside "
                          f"the pinned band [{lo}, {hi}]")
    if "aliased_inputs" in budget \
            and info["aliased_inputs"] != budget["aliased_inputs"]:
        errors.append(f"{name}: {info['aliased_inputs']} donated input(s) "
                      f"aliased (expected {budget['aliased_inputs']} — a "
                      f"shortfall means XLA copies the donated update "
                      f"path instead of updating in place)")
    return errors


def expected_collective_kinds(plan, params):
    """The collective-op KINDS the shard plan's rules imply must appear
    in the lowered program: dp-reduction of gradients/loss is always an
    all-reduce; any rule that shards a weight dim forces a gather before
    use. The exact counts are pinned in BUDGETS; this derivation guards
    that the pinned mix stays CONSISTENT with the rules."""
    kinds = {"all-reduce"}
    for name, arr in params.items():
        spec = plan.spec_for(name, arr.shape)
        if any(e is not None for e in tuple(spec)):
            kinds.add("all-gather")
            break
    return kinds


# ------------------------------------------------------------- fixtures
def _strip(info):
    """Drop the verbose per-opcode histogram for JSON output."""
    if info is None:
        return None
    return {k: v for k, v in info.items() if k != "ops"}


def captured_step_info(sharded=False, steps=2):
    """Build the reference MLP (the check_dispatch zoo model), capture
    its training step (optionally under the (2,2) DEFAULT_RULES shard
    plan), run `steps` steps and return (hlo_info, step, plan, params)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    rng = np.random.RandomState(0)
    X = nd.array(rng.randn(16, 32).astype(np.float32))
    y = nd.array(rng.randint(0, 8, 16).astype(np.float32))
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(X)

    plan = None
    if sharded:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9},
                           kvstore="ici")
        plan = tr.shard(mesh={"dp": 2, "tp": 2})
    else:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    for _ in range(steps):
        step(X, y)
    params = {p.name: p.data()._data
              for p in net.collect_params().values()}
    return step.hlo_info(), step, plan, params


def sharded_embed_step_info(steps=2):
    """Build a tiny two-table DLRM (two `ShardedEmbedding` tables + a
    dense tower), capture its training step under the (2,2)
    DEFAULT_RULES shard plan — the tables row-shard over 'tp', so the
    sparse fast path is live and the step publishes as
    `sharded_embed_step` — run `steps` steps and return
    (hlo_info, step, n_tables). Needs >= 4 devices (callers skip below
    that, like the sharded phase). check_static.py reuses this fixture
    so its copy allowance guards a program the gate deterministically
    compiled."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    rng = np.random.RandomState(0)
    V1, V2, D, B, F = 64, 96, 8, 8, 3
    I1 = nd.array(rng.randint(0, V1, (B, F)).astype(np.int32),
                  dtype=np.int32)
    I2 = nd.array(rng.randint(0, V2, (B,)).astype(np.int32),
                  dtype=np.int32)
    Xd = nd.array(rng.randn(B, 4).astype(np.float32))
    yh = nd.array(rng.randn(B).astype(np.float32))

    class _DLRM(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.emb_a = gluon.nn.ShardedEmbedding(V1, D)
                self.emb_b = gluon.nn.ShardedEmbedding(V2, D)
                self.top = gluon.nn.Dense(1, in_units=(F + 1) * D + 4)

        def hybrid_forward(self, F_, i1, i2, xd):
            a = self.emb_a(i1).reshape((i1.shape[0], -1))
            b = self.emb_b(i2)
            return self.top(F_.concat(a, b, xd, dim=1))

    mx.random.seed(0)
    net = _DLRM()
    net.initialize(mx.init.Xavier())
    net(I1, I2, Xd)
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="ici")
    tr.shard(mesh={"dp": 2, "tp": 2})
    step = tr.capture(lambda a, b, c, d: lossf(net(a, b, c), d).mean())
    for _ in range(steps):
        step(I1, I2, Xd, yh)
    return step.hlo_info(), step, 2


def moe_step_info(steps=2):
    """Build a Dense stem + two `ShardedMoE` layers, capture the
    training step under the (2,2) DEFAULT_RULES plan — the expert
    banks row-shard over 'tp', so the 2-a2a-per-layer expert-parallel
    path is live and the step publishes as `moe_step` — run `steps`
    steps and return (hlo_info, step, n_moe_layers). The Dense stem
    keeps the first MoE layer's input cotangent live (see the BUDGETS
    comment). Needs >= 4 devices; callers skip below that.
    check_static.py reuses this fixture."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    rng = np.random.RandomState(0)
    B, D = 8, 16
    X = nd.array(rng.randn(B, D).astype(np.float32))
    y = nd.array(rng.randn(B, D).astype(np.float32))

    class _MoENet(gluon.nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.proj = gluon.nn.Dense(D, in_units=D)
                self.moe_a = gluon.nn.ShardedMoE(
                    D, 16, num_experts=4, k=2, capacity_factor=1.25)
                self.moe_b = gluon.nn.ShardedMoE(
                    D, 16, num_experts=4, k=2, capacity_factor=1.25)

        def hybrid_forward(self, F_, x):
            return self.moe_b(self.moe_a(self.proj(x)))

    mx.random.seed(0)
    net = _MoENet()
    net.initialize(mx.init.Xavier())
    net(X)
    lossf = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="ici")
    tr.shard(mesh={"dp": 2, "tp": 2})
    step = tr.capture(lambda a, b: lossf(net(a), b).mean())
    for _ in range(steps):
        step(X, y)
    return step.hlo_info(), step, 2


def _serve_infos():
    """Warm one tiny server (the check_dispatch serve model) and return
    (decode_info, prefill_info, decode_traces)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=4, max_src_len=8,
                          max_new_tokens=12, engine_driven=False)
    rng = np.random.RandomState(0)
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4)
    srv.scheduler.step()
    srv.scheduler.step()
    dec = srv.runtime._decode_fn.last_hlo
    pre = srv.runtime._prefill_fn.last_hlo
    traces = srv.runtime.decode_traces
    srv.close()
    return dec, pre, traces


def _serve_verify_info():
    """Warm a SPECULATIVE server (ISSUE 12: width = k+1 widened verify
    executable) and return (verify_info, verify_traces)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=4, max_src_len=8,
                          max_new_tokens=8, max_prompt_len=8,
                          speculative_k=2, engine_driven=False)
    rng = np.random.RandomState(0)
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4,
               prompt_tokens=rng.randint(4, 32, (6,))).result(timeout=300)
    info = srv.runtime._verify_fn.last_hlo
    traces = srv.runtime.verify_traces
    srv.close()
    return info, traces


def _serve_int8_infos():
    """Warm ONE quantized server (ISSUE 14: int8 KV pages + per-channel
    int8 weights, speculative width 3 so both the 1-wide and widened
    quantized programs exist) and return (decode_info, verify_info,
    decode_traces + verify_traces)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import TransformerNMT

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=48, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=4, max_src_len=8,
                          max_new_tokens=8, max_prompt_len=12,
                          num_pages=16, speculative_k=2, kv_dtype="int8",
                          weight_dtype="int8", engine_driven=False)
    rng = np.random.RandomState(0)
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4,
               prompt_tokens=rng.randint(4, 32, (6,))).result(timeout=300)
    ver = srv.runtime._verify_fn.last_hlo
    traces = srv.runtime.decode_traces + srv.runtime.verify_traces
    srv.close()

    mx.random.seed(0)
    model = TransformerNMT(32, units=16, hidden=32, num_layers=1,
                           num_heads=2, max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, slots=3, page_size=4, max_src_len=8,
                          max_new_tokens=12, kv_dtype="int8",
                          weight_dtype="int8", engine_driven=False)
    srv.submit(rng.randint(4, 32, (5,)), max_new_tokens=4).result(
        timeout=300)
    dec = srv.runtime._decode_fn.last_hlo
    traces += srv.runtime.decode_traces
    srv.close()
    return dec, ver, traces


def _run_control():
    """Compile the SAME captured step in a subprocess with XLA's fusion
    pass disabled and return its HLO counts — the gate's liveness
    control (budget must trip on it)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_disable_hlo_passes=fusion")
    env["MXTPU_HLO_TELEMETRY"] = "always"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--control"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        timeout=CONTROL_TIMEOUT_S)
    line = None
    for raw in proc.stdout.decode(errors="replace").splitlines():
        raw = raw.strip()
        if raw.startswith("{"):
            line = raw
    if proc.returncode != 0 or line is None:
        raise RuntimeError(f"control subprocess failed "
                           f"(rc={proc.returncode})")
    return json.loads(line)


# ------------------------------------------------------------------ run
def run():
    # the gate measures its OWN compiles: force inspection regardless of
    # the process-wide sampling policy, restore on exit
    prev_pol = os.environ.get("MXTPU_HLO_TELEMETRY")
    os.environ["MXTPU_HLO_TELEMETRY"] = "always"
    try:
        return _run_impl()
    finally:
        if prev_pol is None:
            os.environ.pop("MXTPU_HLO_TELEMETRY", None)
        else:
            os.environ["MXTPU_HLO_TELEMETRY"] = prev_pol


def _run_impl():
    import jax

    errors = []

    # -- captured (replicated, single executable) ----------------------
    cap_info, _, _, _ = captured_step_info(sharded=False)
    errors += check_budget("captured_step", cap_info)

    # -- (2,2) rule-sharded (>= 4 devices; mirror check_dispatch's
    # shard-phase skip) -----------------------------------------------
    shard_mesh = len(jax.devices()) >= 4
    sh_info = None
    kinds_ok = None
    if shard_mesh:
        sh_info, _, plan, params = captured_step_info(sharded=True)
        errors += check_budget("sharded_step", sh_info)
        if sh_info is not None:
            kinds = expected_collective_kinds(plan, params)
            kinds_ok = kinds <= set(sh_info["collectives"])
            if not kinds_ok:
                errors.append(
                    f"sharded_step: rule-derived collective kinds "
                    f"{sorted(kinds)} missing from lowered program "
                    f"{sorted(sh_info['collectives'])}")

    # -- sharded-embedding step (ISSUE 15; >= 4 devices, same skip) ----
    emb_info = None
    emb_a2a_consistent = None
    if shard_mesh:
        emb_info, emb_step, n_tables = sharded_embed_step_info()
        errors += check_budget("sharded_embed_step", emb_info)
        if emb_step.last_fallback_reason is not None:
            errors.append(f"sharded embed step fell back: "
                          f"{emb_step.last_fallback_reason}")
        # cross-check the pinned all-to-all count against the bucketed-
        # exchange math: 2 per table (index exchange + vector return)
        from mxnet_tpu.shard import embedding as _semb
        expect_a2a = _semb.A2A_PER_TABLE * n_tables
        if BUDGETS["sharded_embed_step"]["all_to_all"] != expect_a2a:
            errors.append(
                f"sharded_embed_step: pinned all_to_all budget "
                f"{BUDGETS['sharded_embed_step']['all_to_all']} "
                f"disagrees with the exchange math "
                f"A2A_PER_TABLE * n_tables = {expect_a2a} — fix the "
                f"budget or the exchange, not one of them")
        emb_a2a_consistent = \
            BUDGETS["sharded_embed_step"]["all_to_all"] == expect_a2a

    # -- expert-parallel MoE step (ISSUE 16; >= 4 devices, same skip) --
    moe_info = None
    moe_a2a_consistent = None
    if shard_mesh:
        moe_info, moe_step, n_moe_layers = moe_step_info()
        errors += check_budget("moe_step", moe_info)
        if moe_step.last_fallback_reason is not None:
            errors.append(f"moe step fell back: "
                          f"{moe_step.last_fallback_reason}")
        # cross-check the pinned all-to-all count against the routing
        # math: 2 per layer per traversal (dispatch + return), 2
        # traversals per training step (forward + vjp transposes)
        from mxnet_tpu.shard import moe as _smoe
        expect_moe = (_smoe.A2A_PER_LAYER * _smoe.STEP_TRAVERSALS
                      * n_moe_layers)
        if BUDGETS["moe_step"]["all_to_all"] != expect_moe:
            errors.append(
                f"moe_step: pinned all_to_all budget "
                f"{BUDGETS['moe_step']['all_to_all']} disagrees with "
                f"the routing math A2A_PER_LAYER * STEP_TRAVERSALS * "
                f"n_moe_layers = {expect_moe} — fix the budget or the "
                f"routing, not one of them")
        moe_a2a_consistent = \
            BUDGETS["moe_step"]["all_to_all"] == expect_moe

    # -- serve decode / prefill ----------------------------------------
    dec_info, pre_info, dec_traces = _serve_infos()
    errors += check_budget("serve_decode", dec_info)
    errors += check_budget("serve_prefill", pre_info)
    if dec_traces != 1:
        errors.append(f"serve decode executable traced {dec_traces}x "
                      f"during the warm-up (expected exactly 1 — HLO "
                      f"inspection must not retrace)")

    # -- widened speculative-verify executable (ISSUE 12) --------------
    ver_info, ver_traces = _serve_verify_info()
    errors += check_budget("serve_verify", ver_info)
    if ver_traces != 1:
        errors.append(f"serve verify executable traced {ver_traces}x "
                      f"during the warm-up (expected exactly 1 — draft "
                      f"acceptance variation must not retrace)")

    # -- quantized-serve executables (ISSUE 14) ------------------------
    qdec_info, qver_info, q_traces = _serve_int8_infos()
    errors += check_budget("serve_decode_int8", qdec_info)
    errors += check_budget("serve_verify_int8", qver_info)
    if q_traces != 2:
        errors.append(f"quantized serve executables traced {q_traces}x "
                      f"during warm-up (expected exactly 2: one decode "
                      f"+ one verify compilation)")

    # -- de-fused control: the SAME budget must trip -------------------
    control_fusions = None
    control_tripped = None
    try:
        ctrl_info = _run_control()
        control_fusions = ctrl_info.get("fusions")
        control_tripped = bool(check_budget("captured_step", ctrl_info))
        if not control_tripped:
            errors.append(
                f"de-fused control (fusion pass disabled, "
                f"{control_fusions} fusions) did NOT trip the captured "
                f"budget — the gate is not measuring anything")
    except Exception as e:
        errors.append(f"de-fused control failed to run: {e!r}")

    res = {
        "captured": _strip(cap_info),
        "shard_mesh": shard_mesh,
        "sharded": _strip(sh_info),
        "sharded_kinds_consistent": kinds_ok,
        "sharded_embed": _strip(emb_info),
        "sharded_embed_a2a_consistent": emb_a2a_consistent,
        "moe": _strip(moe_info),
        "moe_a2a_consistent": moe_a2a_consistent,
        "serve_decode": _strip(dec_info),
        "serve_prefill": _strip(pre_info),
        "serve_decode_traces": dec_traces,
        "serve_verify": _strip(ver_info),
        "serve_verify_traces": ver_traces,
        "serve_decode_int8": _strip(qdec_info),
        "serve_verify_int8": _strip(qver_info),
        "serve_int8_traces": q_traces,
        "control_fusions": control_fusions,
        "control_tripped": control_tripped,
        "budgets": BUDGETS,
        "errors": errors,
        "ok": not errors,
    }
    return res


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    if "--control" in argv:
        # de-fused control mode: compile the captured step under the
        # inherited --xla_disable_hlo_passes=fusion and report counts
        os.environ["MXTPU_HLO_TELEMETRY"] = "always"
        info, _, _, _ = captured_step_info(sharded=False)
        print(json.dumps(_strip(info) or {}))
        return 0 if info is not None else 1
    res = run()
    print(json.dumps(res))
    for err in res["errors"]:
        print(f"check_fusion: {err}", file=sys.stderr)
    if res["errors"]:
        print("check_fusion: FAIL", file=sys.stderr)
        return 1
    shard_txt = ("shard phase skipped (<4 devices)" if not res["shard_mesh"]
                 else f"sharded {res['sharded']['fusions']} fusions / "
                      f"{res['sharded']['collectives']}; embed step "
                      f"{res['sharded_embed']['collectives'].get('all-to-all', 0)} "
                      f"all-to-alls / "
                      f"{res['sharded_embed']['aliased_inputs']} aliased; "
                      f"moe step "
                      f"{res['moe']['collectives'].get('all-to-all', 0)} "
                      f"all-to-alls / "
                      f"{res['moe']['aliased_inputs']} aliased")
    print(f"check_fusion: OK (captured {res['captured']['fusions']} "
          f"fusions / {res['captured']['collective_total']} collectives "
          f"/ {res['captured']['aliased_inputs']} aliased; {shard_txt}; "
          f"decode {res['serve_decode']['fusions']} fusions; verify "
          f"{res['serve_verify']['fusions']} fusions / "
          f"{res['serve_verify']['copies']} copies; int8 decode "
          f"{res['serve_decode_int8']['fusions']} fusions / "
          f"{res['serve_decode_int8']['copies']} copies / "
          f"{res['serve_decode_int8']['aliased_inputs']} aliased; "
          f"de-fused control tripped at {res['control_fusions']} "
          f"fusions)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
