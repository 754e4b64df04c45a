"""The serving programs' named device-time scopes (ISSUE 36): the
inspections of a tiny `LMRuntime`'s and a tiny `DecodeRuntime`'s decode and
prefill executables hold each scope, a scope inside `mx_moe` is held with
it, and the scopes change nothing of the optimized HLO but names and
metadata: the same programs traced with the new named functions taken
away (each replaced by the plain function it wraps, which is the form the
programs had before them) have the same opcode histogram, fusions, copies
and aliased inputs."""
import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu.models import decoder_lm as dlm
from mxnet_tpu.observability import compilex
from mxnet_tpu.serve import decode as sdecode, lm_runtime

MOE = ["mx_moe_route", "mx_moe_dispatch", "mx_moe_experts",
       "mx_moe_combine", "mx_moe_shared"]
# the named functions ISSUE 36 added, by module
# (the translation runtime's are plain functions that its programs jit
# through `serve.decode._scope`, with the weights bound)
ADDED = {dlm: MOE,
         lm_runtime: ["mx_embed", "mx_norm", "mx_join", "mx_head",
                      "mx_cache_write"]}
COUNTS = ("ops", "fusions", "copies", "aliased_inputs")

LM_SPECS = {
    "kda": dict(hidden=64, heads=8, kv_heads=2, head_dim=16, kda_heads=4,
                kda_head_dim=16, conv_kernel=4, num_experts=16, top_k=4,
                expert_width=32, held_lo=0, held_n=4, scaling=1.0, eps=1e-5,
                pattern=("gqa", "kda")),
    "mla": dict(hidden=64, heads=4, kv_heads=0, head_dim=0, kda_heads=0,
                kda_head_dim=0, conv_kernel=0, num_experts=8, top_k=2,
                expert_width=32, held_lo=0, held_n=4, scaling=2.5, eps=1e-5,
                pattern=("mla",) * 3, q_rank=32, kv_rank=24, nope_dim=16,
                rope_dim=8, v_dim=16, rope_theta=25600000.0,
                ffn=("dense", "moe", "moe"), dense_width=96, sandwich=True,
                router_bias=False),
    "ssm": dict(hidden=64, heads=32, kv_heads=2, head_dim=16, kda_heads=0,
                kda_head_dim=0, conv_kernel=4, num_experts=16, top_k=4,
                expert_width=32, held_lo=0, held_n=4, scaling=2.5, eps=1e-5,
                pattern=("mamba", "moe", "gqa", "moe"), paired=False,
                attn_gate=False, expert_act="relu2", shared_width=48,
                ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
                ssm_chunk=8),
}
# what each program of each model must hold, beside the mixers' own
LM_SCOPES = {
    "decode": {"mx_embed", "mx_norm", "mx_join", "mx_head", "mx_moe", *MOE},
    "prefill": {"mx_embed", "mx_norm", "mx_join", "mx_cache_write",
                "mx_moe", *MOE}}
MIXERS = {"kda": ({"mx_gqa", "mx_kda"}, {"mx_gqa_seq", "mx_kda_seq"}),
          "mla": ({"mx_mla", "mx_ffn"}, {"mx_mla_seq", "mx_ffn"}),
          "ssm": ({"mx_gqa", "mx_mamba"}, {"mx_gqa_seq", "mx_mamba_seq"})}


@pytest.fixture(autouse=True)
def _inspect_every_compile(monkeypatch):
    monkeypatch.setenv("MXTPU_HLO_TELEMETRY", "always")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def unscoped(monkeypatch):
    """The programs as they were before the added scopes: each added
    named function replaced by the plain function it wraps. jax keeps the
    trace of an enclosing jitted function (`mx_moe`) by its identity, so
    its caches go before and after."""
    jax.clear_caches()
    for module, names in ADDED.items():
        for name in names:
            monkeypatch.setattr(module, name,
                                getattr(module, name).__wrapped__)
    monkeypatch.setattr(sdecode, "_scope", lambda name, fn, *bound: (
        lambda *arrays: fn(*bound, *arrays)))
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _scopes(info):
    return {s for held in info["op_scopes"].values() for s in held}


def _lm_inspections(kind):
    mx.random.seed(3)
    model = dlm.DecoderLM(50, dlm.LMSpec(**LM_SPECS[kind]))
    model.initialize()
    srv = mx.serve.Server(model, slots=2, page_size=8, max_prompt_len=24,
                          max_new_tokens=4, eos_id=-1, prefix_cache=False,
                          engine_driven=False)
    rng = np.random.RandomState(0)
    hs = [srv.submit(rng.randint(4, 50, (n,))) for n in (5, 17, 9)]
    srv.scheduler.run_until_idle()
    tokens = [h.result() for h in hs]
    srv.close()
    last = compilex.last_inspections()
    return (last["serve_lm_decode"], last["serve_lm_prefill"]), tokens


def _nmt_inspections(**kw):
    from mxnet_tpu.models.transformer import TransformerNMT
    mx.random.seed(11)
    model = TransformerNMT(50, units=32, hidden=64, num_layers=2,
                           num_heads=4, max_length=32, dropout=0.0)
    model.initialize()
    srv = mx.serve.Server(model, page_size=4, max_src_len=16, slots=2,
                          max_new_tokens=5, engine_driven=False, **kw)
    rng = np.random.RandomState(3)
    hs = [srv.submit(rng.randint(4, 50, (n,))) for n in (5, 7, 9, 4)]
    srv.scheduler.run_until_idle()
    tokens = [h.result() for h in hs]
    srv.close()
    last = compilex.last_inspections()
    int8 = "_int8" if kw.get("kv_dtype") == "int8" else ""
    # a widened server runs every turn through its verify program
    turn = "serve_verify" if kw.get("speculative_k") else "serve_decode"
    return (last[turn + int8], last["serve_prefill"]), tokens


@pytest.mark.parametrize("kind", sorted(LM_SPECS))
def test_a_decoder_only_runtimes_programs_hold_every_scope(kind):
    (decode, prefill), _ = _lm_inspections(kind)
    assert decode["module"] == "jit__decode_program"
    assert prefill["module"] == "jit__prefill_program"
    for info, want, mixers in zip((decode, prefill),
                                  (LM_SCOPES["decode"], LM_SCOPES["prefill"]),
                                  MIXERS[kind]):
        assert _scopes(info) == want | mixers
        # a scope inside `mx_moe` is held WITH it, so `moe_share_pct`
        # reads what it read
        inside = [held for held in info["op_scopes"].values()
                  if any(s.startswith("mx_moe_") for s in held)]
        assert inside and all("mx_moe" in held for held in inside)
        assert info["op_names"]
    assert "mx_head" not in _scopes(prefill)        # it gives no logits
    assert "mx_cache_write" not in _scopes(decode)


@pytest.mark.parametrize("kw", [{}, {"kv_dtype": "int8"},
                                {"speculative_k": 2, "max_prompt_len": 8}],
                         ids=["float", "int8", "speculative"])
def test_the_translation_runtimes_programs_hold_every_scope(kw):
    (turn, prefill), _ = _nmt_inspections(**kw)
    if kw.get("speculative_k"):
        # the widened program shares all but its cross-attention
        assert turn["module"] == "jit__verify_program"
        assert _scopes(turn) == {"mx_embed", "mx_self_attn", "mx_ffn",
                                 "mx_head"}
    else:
        assert turn["module"] == "jit__decode_program"
        assert _scopes(turn) == {"mx_embed", "mx_self_attn",
                                 "mx_cross_attn", "mx_ffn", "mx_head"}
    assert prefill["module"] == "jit__prefill_program"
    assert _scopes(prefill) == {"mx_encoder", "mx_memory_kv"}
    # the loop's body ops are what a trace shows: they carry the scopes
    named = {n for n, held in prefill["op_scopes"].items()
             if n in prefill["op_names"]}
    assert {s for n in named for s in prefill["op_scopes"][n]} \
        == {"mx_encoder", "mx_memory_kv"}


def _counts(infos):
    return [{k: info[k] for k in COUNTS} for info in infos]


@pytest.mark.parametrize("kind", sorted(LM_SPECS))
def test_scopes_leave_a_decoder_only_programs_instructions_alone(
        kind, request):
    scoped, tokens = _lm_inspections(kind)
    request.getfixturevalue("unscoped")
    plain, same = _lm_inspections(kind)
    assert tokens == same
    assert _counts(scoped) == _counts(plain)
    assert all(_scopes(p) <= {"mx_gqa", "mx_kda", "mx_mla", "mx_mamba",
                              "mx_moe", "mx_ffn", "mx_gqa_seq", "mx_kda_seq",
                              "mx_mla_seq", "mx_mamba_seq"} for p in plain)


@pytest.mark.parametrize("kw", [{}, {"kv_dtype": "int8"},
                                {"speculative_k": 2, "max_prompt_len": 8}],
                         ids=["float", "int8", "speculative"])
def test_scopes_leave_the_translation_programs_instructions_alone(
        kw, request):
    scoped, tokens = _nmt_inspections(**kw)
    request.getfixturevalue("unscoped")
    plain, same = _nmt_inspections(**kw)
    assert tokens == same
    assert _counts(scoped) == _counts(plain)
    assert all(_scopes(p) == set() for p in plain)
