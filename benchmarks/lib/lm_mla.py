"""A latent-attention decoder-only model from a configuration file, for
the kind that serves it and the tests: what `lib/lm.py` is for the
KDA-hybrid configuration (whose `spec_of` reads `linear_attn_config` and
`gqa_layers`), for configurations whose layers are all multi-head latent
attention: the program's `DecoderLM` at the configuration's sizes, and
the same arrays handed to the plain reference under its names."""
from __future__ import annotations

from . import models


def spec_of(cfg):
    """The program's `LMSpec` for a configuration in the source's keys.
    `layers_held` lists which published layers run here: one below
    `first_k_dense_replace` is a dense SwiGLU of `intermediate_size`, the
    others route over `router_width` experts of which `n_routed_experts`
    are HELD."""
    from mxnet_tpu.models.decoder_lm import LMSpec
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    first, last = cfg["layers_held_range"]
    if last - first != cfg["layers_held"]:
        raise ValueError("layers_held_range and layers_held disagree")
    if cfg["n_shared_experts"] != 1:
        raise ValueError("the expert layer has exactly one shared expert")
    layers = range(first, last)
    return LMSpec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=0, head_dim=0, kda_heads=0, kda_head_dim=0, conv_kernel=0,
        num_experts=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], held_lo=lo,
        held_n=hi - lo, scaling=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"], pattern=("mla",) * len(layers),
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        ffn=tuple("dense" if i < cfg["first_k_dense_replace"] else "moe"
                  for i in layers),
        dense_width=cfg["intermediate_size"],
        sandwich=bool(cfg["sandwich_norm"]), router_bias=False)


def build_server(cfg, seed, max_queue):
    """(model, server): every array made on the device from the seed."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.decoder_lm import DecoderLM
    mx.random.seed(models.small_seed(seed))
    model = DecoderLM(cfg["vocab_size"], spec_of(cfg))
    model.cast(cfg["param_dtype"])
    # a served model: no gradient buffers (a second copy of every array)
    model.collect_params().setattr("grad_req", "null")
    models.set_weights_from_seed(model, seed, cfg["param_dtype"])
    return model, mx.serve.Server(model, max_queue=max_queue,
                                  **cfg["server"])


def reference_weights(model):
    """The model's own arrays under the reference's names, uncopied and
    uncast (the reference casts where it uses them)."""
    from mxnet_tpu.models.decoder_lm import lm_weights
    w = lm_weights(model)

    def short(name):
        for end in ("_weight", "_gamma"):
            name = name.removesuffix(end)
        return name.removeprefix("experts_")

    def named(d):
        return {short(k): (named(v) if isinstance(v, dict) else v)
                for k, v in d.items()}

    return {"embed": w["embed"], "head": w["head"],
            "final_norm": w["final_norm_gamma"],
            "layers": [named(L) for L in w["layers"]]}
