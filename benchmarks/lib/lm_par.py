"""A decoder-only model of PARALLEL hybrid layers (a Mamba-2 and an
attention mixer side by side on one normed input, then a dense SwiGLU),
with the muP multipliers of a model trained so, from a configuration file
in the source's keys: what `lib/lm_ssm.py` is for the sequential hybrid
and `lib/lm_swa.py` for sliding-window attention: the program's
`DecoderLM` at the configuration's sizes, its weights from the seed, and
the same arrays handed to the plain reference under its names."""
from __future__ import annotations

import numpy as np

from . import lm_mla, models


def spec_of(cfg):
    """The program's `LMSpec` for a configuration in the source's keys
    (`model_type` falcon_h1). `layers_held` of the published
    `num_hidden_layers` run here, each a parallel layer and a dense one;
    every multiplier as the configuration gives it."""
    from mxnet_tpu.models.decoder_lm import PAR, LMSpec
    first, last = cfg["layers_held_range"]
    if last - first != cfg["layers_held"]:
        raise ValueError("layers_held_range and layers_held disagree")
    heads, head_dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if heads * head_dim != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    # what the program builds and no key may say otherwise
    fixed = {"attention_bias": False, "mamba_conv_bias": True,
             "mamba_norm_before_gate": False, "mamba_rms_norm": True,
             "mamba_proj_bias": False, "mlp_bias": False,
             "projectors_bias": False, "tie_word_embeddings": False,
             "rope_scaling": None, "attn_layer_indices": None,
             "mamba_use_mlp": True, "hidden_act": "silu"}
    off = {k: cfg[k] for k, v in fixed.items() if cfg[k] != v}
    if off:
        raise ValueError(f"the program builds none of {off}")
    layers = cfg["layers_held"]
    return LMSpec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=0, kda_head_dim=0, conv_kernel=cfg["mamba_d_conv"],
        num_experts=0, top_k=0, expert_width=0, held_lo=0, held_n=0,
        scaling=1.0, eps=cfg["rms_norm_eps"], pattern=(PAR,) * layers,
        rope_theta=float(cfg["rope_theta"]), ffn=("dense",) * layers,
        dense_width=cfg["intermediate_size"], router_bias=False,
        attn_gate=False, ssm_heads=heads, ssm_head_dim=head_dim,
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_chunk=cfg["mamba_chunk_size"], attn_rope=True,
        embed_mult=float(cfg["embedding_multiplier"]),
        head_mult=float(cfg["lm_head_multiplier"]),
        key_mult=float(cfg["key_multiplier"]),
        ffn_mult=tuple(float(m) for m in cfg["mlp_multipliers"]),
        ssm_mult=tuple(float(m) for m in cfg["ssm_multipliers"]),
        par_mult=(float(cfg["ssm_in_multiplier"]),
                  float(cfg["ssm_out_multiplier"]),
                  float(cfg["attention_in_multiplier"]),
                  float(cfg["attention_out_multiplier"])))


def row_scales(spec, qk_gain):
    """{parameter name's end: the standard deviation of its rows}, the
    configuration's `assumed.init`: a matrix (out, in) N(0, 1 / sqrt(in))
    divided by the multipliers on its way in and out, so that every
    segment, branch and logit reaches its place at unit RMS as trained
    muP weights bring it there; q and k `qk_gain` each on top (scores of
    std qk_gain^2)."""
    d, inner = spec.hidden, spec.ssm_heads * spec.ssm_head_dim
    gn, h = spec.ssm_groups * spec.ssm_state, spec.ssm_heads
    m_si, m_so, m_ai, m_ao = spec.par_mult
    gate, down = spec.ffn_mult
    seg = np.repeat(np.asarray(spec.ssm_mult, np.float64),
                    [inner, inner, gn, gn, h])
    q, kv = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    qkv = np.concatenate([np.full(q, qk_gain),
                          np.full(kv, qk_gain / spec.key_mult),
                          np.ones(kv)]) / m_ai
    w = spec.dense_width
    return {
        "embed_weight": 1 / spec.embed_mult,
        "head_weight": 1 / (spec.head_mult * d ** 0.5),
        "ssm_in_weight": 1 / (m_si * seg * d ** 0.5),
        "ssm_o_weight": 1 / (m_so * inner ** 0.5),
        "attn_qkv_weight": qkv / d ** 0.5,
        "attn_o_weight": 1 / (m_ao * q ** 0.5),
        "ffn_gate_up_weight": np.concatenate(
            [np.full(w, 1 / gate), np.ones(w)]) / d ** 0.5,
        "ffn_down_weight": 1 / (down * w ** 0.5)}


def set_weights_from_seed(model, cfg, seed):
    """Give every parameter its value from the seed in ONE jitted call:
    the matrices by `row_scales` (N(0, 1) times the row's deviation),
    gains 1 + N(0, 0.02), and the Mamba-2 halves' small parameters as
    mamba_ssm's `Mamba2` draws them (`cfg["init"]`; `assumed.init` says
    why): the convolution's weight and bias U(-1/2, 1/2), `dt_bias` the
    inverse softplus of a step log-uniform in [time_step_min,
    time_step_max] floored at time_step_floor, `A` U(1, 16), `D` ones."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray
    init = cfg["init"]
    scales = row_scales(model.spec, init["qk_gain"])
    lo, hi = init["time_step_min"], init["time_step_max"]
    floor, bound = init["time_step_floor"], cfg["mamba_d_conv"] ** -0.5
    params = list(model.collect_params().values())

    def end(name):
        return next((k for k in (*scales, "conv_weight", "conv_bias",
                                 "dt_bias", "a_log", "d_skip", "gamma")
                     if name.endswith(k)), None)

    ends = [end(p.name) for p in params]
    missing = [p.name for p, e in zip(params, ends) if e is None]
    if missing:
        raise ValueError(f"no initial value for {missing}")
    specs = tuple((tuple(p.shape), e) for p, e in zip(params, ends))

    def make(key):
        out = []
        for i, (shape, e) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if e == "gamma":
                v = 1.0 + 0.02 * jax.random.normal(k, shape)
            elif e in ("conv_weight", "conv_bias"):
                v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif e == "dt_bias":
                step = jnp.exp(jax.random.uniform(k, shape)
                               * (np.log(hi) - np.log(lo)) + np.log(lo))
                step = jnp.maximum(step, floor)
                v = step + jnp.log(-jnp.expm1(-step))
            elif e == "a_log":
                v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                               16.0))
            elif e == "d_skip":
                v = jnp.ones(shape, jnp.float32)
            else:
                s = np.asarray(scales[e], np.float32)
                s = s.reshape(-1, *([1] * (len(shape) - 1))) if s.ndim \
                    else s
                v = jax.random.normal(k, shape) * s
            out.append(v.astype(cfg["param_dtype"]))
        return out

    values = jax.jit(make)(models.seed_key(seed))
    for p, v in zip(params, values):
        p.set_data(NDArray(v))
    return model


def build_server(cfg, seed, max_queue):
    """(model, server): every array made on the device from the seed."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.decoder_lm import DecoderLM
    mx.random.seed(models.small_seed(seed))
    model = DecoderLM(cfg["vocab_size"], spec_of(cfg))
    model.cast(cfg["param_dtype"])
    # a served model: no gradient buffers (a second copy of every array)
    model.collect_params().setattr("grad_req", "null")
    set_weights_from_seed(model, cfg, seed)
    return model, mx.serve.Server(model, max_queue=max_queue,
                                  **cfg["server"])


# the model's own arrays under the reference's names (short names, nested
# dicts as the layers nest): `lm_mla`'s, whose reference names them alike
reference_weights = lm_mla.reference_weights
