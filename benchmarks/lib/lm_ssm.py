"""A decoder-only model of ONE sub-layer a layer (Mamba-2 state-space
layers, expert layers, attention) from a configuration file, for the kind
that serves it and the tests: what `lib/lm.py` is for the KDA-hybrid
configuration and `lib/lm_mla.py` for latent attention: the program's
`DecoderLM` at the configuration's sizes, and the same arrays handed to
the plain reference under its names."""
from __future__ import annotations

from . import models

KINDS = {"M": "mamba", "E": "moe", "*": "gqa"}


def spec_of(cfg):
    """The program's `LMSpec` for a configuration in the source's keys.
    `layers_held_range` says which letters of `hybrid_override_pattern`
    run here, each a layer of one sub-layer; the router scores
    `router_width` experts of which `n_routed_experts` are HELD."""
    from mxnet_tpu.models.decoder_lm import LMSpec
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    first, last = cfg["layers_held_range"]
    if last - first != cfg["layers_held"]:
        raise ValueError("layers_held_range and layers_held disagree")
    if cfg["n_shared_experts"] != 1 or cfg["mlp_hidden_act"] != "relu2":
        raise ValueError("one shared expert and relu2 experts")
    return LMSpec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=0, kda_head_dim=0, conv_kernel=cfg["conv_kernel"],
        num_experts=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], held_lo=lo,
        held_n=hi - lo, scaling=float(cfg["routed_scaling_factor"]),
        eps=cfg["layer_norm_epsilon"],
        pattern=tuple(KINDS[c] for c in
                      cfg["hybrid_override_pattern"][first:last]),
        paired=False, attn_gate=False, expert_act="relu2",
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
        ssm_chunk=cfg["chunk_size"])


def set_mamba_vectors_from_seed(model, cfg, seed):
    """Give the small parameters of every Mamba-2 layer the values their
    PUBLISHED initialisation draws (mamba_ssm's `Mamba2`, which the
    config's `time_step_*` keys parameterise), from the seed: the
    convolution's weight and bias U(-1/2, 1/2) (1 / sqrt(conv_kernel)),
    `dt_bias` the inverse softplus of a step log-uniform in
    [time_step_min, time_step_max] and at least time_step_floor, `A`
    U(1, 16), `D` ones. At the matrices' N(0, 0.02) they would leave x, B
    and C near 0.02 and the gated output's mean square (1e-7) under the
    norm's epsilon (1e-5): the grouped norm a constant factor, the step
    sizes' bias and the groups invisible (the configuration's `assumed`
    has the readings)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    bound = cfg["conv_kernel"] ** -0.5
    layers = [b.mixer for b in model.layers
              if b._mixer and hasattr(b.mixer, "dt_bias")]
    heads, shape = layers[0].dt_bias.shape, layers[0].conv_weight.shape

    def make(key):
        out = []
        for i in range(len(layers)):
            k = jax.random.split(jax.random.fold_in(key, i), 4)
            step = jnp.exp(jax.random.uniform(k[2], heads)
                           * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
            step = jnp.maximum(step, cfg["time_step_floor"])
            out.append({
                "conv_weight": jax.random.uniform(k[0], shape, jnp.float32,
                                                  -bound, bound),
                "conv_bias": jax.random.uniform(k[1], shape[1:], jnp.float32,
                                                -bound, bound),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(k[3], heads, jnp.float32,
                                                    1.0, 16.0)),
                "d_skip": jnp.ones(heads, jnp.float32)})
        return jax.tree_util.tree_map(
            lambda v: v.astype(cfg["param_dtype"]), out)

    # another stream than the matrices': the seed's key folded once more
    values = jax.jit(make)(jax.random.fold_in(models.seed_key(seed), 2 ** 20))
    for mixer, named in zip(layers, values):
        for name, v in named.items():
            getattr(mixer, name).set_data(NDArray(v))


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
    set_mamba_vectors_from_seed(model, cfg, seed)
    return model, mx.serve.Server(model, max_queue=max_queue,
                                  **cfg["server"])


def reference_weights(model):
    """The model's own arrays under the reference's names, uncopied and
    uncast (the reference casts where it uses them): a layer is its
    norm's gain and `f`, its one sub-layer's arrays."""
    from mxnet_tpu.models.decoder_lm import lm_weights
    w = lm_weights(model)

    def short(name):
        for end in ("_weight", "_gamma"):
            name = name.removesuffix(end)
        return name.removeprefix("experts_")

    def layer(L):
        (norm,) = (v for k, v in L.items() if k.endswith("_gamma"))
        (sub,) = (v for v in L.values() if isinstance(v, dict))
        return {"norm": norm, "f": {short(k): v for k, v in sub.items()}}

    return {"embed": w["embed"], "head": w["head"],
            "final_norm": w["final_norm_gamma"],
            "layers": [layer(L) for L in w["layers"]]}
