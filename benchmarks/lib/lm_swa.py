"""A decoder-only model of sliding-window and full attention layers mixed,
each with a softmax-routed expert layer, from a configuration file, for
the kind that serves it and the tests: what `lib/lm.py` is for the
KDA-hybrid configuration, `lib/lm_mla.py` for latent attention and
`lib/lm_ssm.py` for state-space layers: the program's `DecoderLM` at the
configuration's sizes, and the same arrays handed to the plain reference
under its names."""
from __future__ import annotations

from . import lm_mla, models

KINDS = {"sliding_attention": "swa", "full_attention": "gqa"}


def spec_of(cfg):
    """The program's `LMSpec` for a configuration in the source's keys.
    `layers_held_range` says which entries of `layer_types` run here,
    each a pair of its attention and an expert layer; the router scores
    `router_width` experts of which `experts_held` are HELD (all, in the
    published configuration: a pipeline stage holds whole layers).
    `rope_parameters` gives each layer type its positional term: the
    window layers the plain table, the full layers YaRN where it says
    so."""
    from mxnet_tpu.models.decoder_lm import LMSpec
    lo, hi = cfg["experts_held"]
    first, last = cfg["layers_held_range"]
    if last - first != cfg["layers_held"]:
        raise ValueError("layers_held_range and layers_held disagree")
    if cfg["router_width"] != cfg["num_experts"]:
        raise ValueError("router_width is the published num_experts")
    if set(cfg["mlp_layer_types"][first:last]) != {"sparse"}:
        raise ValueError("every held layer has an expert layer")
    rope = cfg["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if window["rope_type"] != "default" \
            or full["rope_theta"] != window["rope_theta"]:
        raise ValueError("window layers rotate by the plain table, at the "
                         "full layers' theta")
    yarn = ()
    if full["rope_type"] == "yarn":
        yarn = (float(full["factor"]),
                int(full["original_max_position_embeddings"]),
                float(full["beta_fast"]), float(full["beta_slow"]),
                float(full["attention_factor"]))
    elif full["rope_type"] != "default":
        raise ValueError(f"rope_type {full['rope_type']!r}")
    return LMSpec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=0, kda_head_dim=0, conv_kernel=0,
        num_experts=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], held_lo=lo,
        held_n=hi - lo, scaling=1.0, eps=cfg["rms_norm_eps"],
        pattern=tuple(KINDS[t] for t in cfg["layer_types"][first:last]),
        rope_theta=float(full["rope_theta"]), router_bias=False,
        attn_gate=False, window=cfg["sliding_window"], attn_rope=True,
        rope_yarn=yarn, router_score="softmax", shared_expert=False)


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


# the model's own arrays under the reference's names (short names, nested
# dicts as the layers nest): `lm_mla`'s, whose reference names them alike
reference_weights = lm_mla.reference_weights
