"""A decoder-only model of sliding-window and full attention layers mixed,
QK-normed and gated, in sandwich norms, with leading dense layers and
then sigmoid-routed experts with a selection bias and a shared expert,
from a configuration file in the source's keys (`model_type` afmoe), for
the kind that serves it and the tests: what `lib/lm_swa.py` is for the
softmax-routed window configuration. The spec composes what the program
already has (the sigmoid router with its bias and scaling, the shared
expert, sandwich norms, a leading dense layer, the gated output, the
muP embedding) with the per-head q/k norm and rotation by layer kind:
the program's `DecoderLM` at the configuration's sizes, and the same
arrays handed to the plain reference under its names."""
from __future__ import annotations

import math

from . import lm_mla, models

KINDS = {"sliding_attention": "swa", "full_attention": "gqa"}


def spec_of(cfg):
    """The program's `LMSpec` for a configuration in the source's keys.
    `layers_held_range` says which entries of `layer_types` run here,
    each an attention and a feed-forward: a dense SwiGLU of
    `intermediate_size` below `num_dense_layers`, else the experts. The
    router scores `router_width` experts of which `experts_held` are HELD
    (`num_experts` of them). Window layers rotate by the plain table at
    `rope_theta`; full layers have no positional term. With
    `mup_enabled` the embedding's rows are times sqrt(hidden_size)."""
    from mxnet_tpu.models.decoder_lm import LMSpec
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    first, last = cfg["layers_held_range"]
    if last - first != cfg["layers_held"]:
        raise ValueError("layers_held_range and layers_held disagree")
    if cfg["num_shared_experts"] != 1:
        raise ValueError("the expert layer has exactly one shared expert")
    if (cfg["score_func"], cfg["route_norm"], cfg["n_group"],
            cfg["topk_group"]) != ("sigmoid", True, 1, 1):
        raise ValueError("sigmoid scores, normalised, no expert groups")
    if cfg["rope_scaling"] is not None:
        raise ValueError("the plain rotary table, unscaled")
    layers = range(first, last)
    return LMSpec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=0, kda_head_dim=0, conv_kernel=0,
        num_experts=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], held_lo=lo,
        held_n=hi - lo, scaling=float(cfg["route_scale"]),
        eps=cfg["rms_norm_eps"],
        pattern=tuple(KINDS[cfg["layer_types"][i]] for i in layers),
        rope_theta=float(cfg["rope_theta"]),
        ffn=tuple("dense" if i < cfg["num_dense_layers"] else "moe"
                  for i in layers),
        dense_width=cfg["intermediate_size"], sandwich=True,
        router_bias=True, attn_gate=True, window=cfg["sliding_window"],
        attn_rope=("swa",), qk_norm=True, router_score="sigmoid",
        shared_expert=True,
        embed_mult=math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
        else 1.0)


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


# the model's own arrays under the reference's names (the suffixes
# `_weight`, `_gamma` and the prefix `experts_` dropped): `lm_mla`'s
reference_weights = lm_mla.reference_weights
