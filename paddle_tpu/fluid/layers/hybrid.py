"""Builders for the ops of hybrid decoders (`ops/hybrid_ops.py`): RMS
norm and L2 norm, the causal depthwise convolution, the chunked
state-space scan of a Mamba-2 mixer, the chunked gated delta rule of a
Gated DeltaNet mixer, a partial rotary embedding, the gated (SwiGLU)
activation, the router of a routed-expert layer and the experts a chip
holds. Parameters are created by the caller (`layers.create_parameter`)
and handed in, as a `layers.Scan` body hands its slices to
`layer_norm`."""
from __future__ import annotations

from ..initializer import ConstantInitializer
from ..layer_helper import LayerHelper, apply_op

__all__ = ["rms_norm", "l2_norm", "swiglu", "rotary_embedding",
           "causal_conv1d", "ssd_chunk_scan", "gated_delta_rule",
           "moe_router", "moe_experts"]


def rms_norm(input, scale=True, epsilon=1e-5, groups=1, param_attr=None,
             name=None, zero_centered=False):
    """x * rsqrt(mean(x^2) + epsilon) * scale over the last axis, or
    over each of `groups` equal parts of it. `scale` may be a Variable
    (an existing weight), True (a weight is created) or False. Where
    `zero_centered` the weight is w in `(1 + w)` and starts at zero,
    else it is the factor itself and starts at one."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    inputs = {"X": [input]}
    if scale is True:
        scale = helper.create_parameter(
            helper.param_attr, shape=[int(input.shape[-1])],
            dtype=input.dtype,
            default_initializer=ConstantInitializer(
                0.0 if zero_centered else 1.0))
    if scale is not False and scale is not None:
        inputs["Scale"] = [scale]
    return apply_op(helper, "rms_norm", inputs,
                    {"epsilon": float(epsilon), "groups": int(groups),
                     "scale_offset": float(bool(zero_centered))},
                    ["Y"], out_dtype=input.dtype)[0]


def l2_norm(input, epsilon=1e-6):
    """x * rsqrt(sum(x^2) + epsilon) over the last axis."""
    return apply_op("l2_norm", "l2_norm", {"X": [input]},
                    {"epsilon": float(epsilon)}, ["Y"],
                    out_dtype=input.dtype)[0]


def swiglu(input):
    """silu(g) * u for `input` = [g | u] halved along its last axis."""
    return apply_op("swiglu", "swiglu", {"X": [input]}, {}, ["Out"],
                    out_dtype=input.dtype)[0]


#: a published `rope_parameters` entry's YaRN keys -> the op's attributes
_YARN_KEYS = {"factor": "yarn_factor",
              "original_max_position_embeddings": "yarn_original_max_position",
              "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
              "attention_factor": "yarn_attention_factor"}


def rotary_embedding(input, rotary_dim, theta, scaling=None):
    """Rotary position embedding (rotate-half) on the first
    `rotary_dim` of the last axis of `input` [B, S, H, D], positions
    0 .. S-1, base `theta`. `scaling` is a published `rope_parameters`
    entry: `rope_type` "yarn" with `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow` and
    `attention_factor`; None (or `rope_type` "default") is none."""
    attrs = {"rotary_dim": int(rotary_dim), "theta": float(theta)}
    kind = (scaling or {}).get("rope_type", "default")
    if kind == "yarn":
        attrs.update({a: float(scaling[k]) for k, a in _YARN_KEYS.items()})
    elif kind != "default":
        raise ValueError("rotary scaling %r: only yarn is built" % kind)
    return apply_op("rotary_embedding", "rotary_embedding", {"X": [input]},
                    attrs, ["Out"], out_dtype=input.dtype)[0]


def causal_conv1d(input, filter, bias=None, activation=""):
    """Depthwise causal convolution along axis 1 of [B, S, C] with
    `filter` [C, K] and `bias` [C]; `activation` "silu" or none."""
    inputs = {"X": [input], "Filter": [filter]}
    if bias is not None:
        inputs["Bias"] = [bias]
    return apply_op("causal_conv1d", "causal_conv1d", inputs,
                    {"activation": activation}, ["Out"],
                    out_dtype=input.dtype)[0]


def ssd_chunk_scan(x, dt, dt_bias, a_log, b, c, d, chunk_size=128):
    """The state-space scan of a Mamba-2 mixer, computed in chunks:
    x [B, S, H, P], dt [B, S, H] (before softplus), b and c
    [B, S, G, N], dt_bias, a_log, d [H] -> [B, S, H, P]."""
    return apply_op(
        "ssd_chunk_scan", "ssd_chunk_scan",
        {"X": [x], "Dt": [dt], "DtBias": [dt_bias], "ALog": [a_log],
         "B": [b], "C": [c], "D": [d]},
        {"chunk_size": int(chunk_size)}, ["Out"], out_dtype=x.dtype)[0]


def gated_delta_rule(q, k, v, a, b, a_log, dt_bias):
    """The gated delta rule of a Gated DeltaNet mixer, computed in
    chunks: q, k [B, S, Hk, dk] (normalised and scaled by the caller),
    v [B, S, Hv, dv], a and b [B, S, Hv] (the decay is
    exp(-exp(a_log) softplus(a + dt_bias)), the writing strength
    sigmoid(b)), a_log, dt_bias [Hv] -> [B, S, Hv, dv]. With a of
    [B, S, Hv, dk] and dt_bias [Hv * dk] each key channel decays on its
    own (Kimi Delta Attention), a_log [Hv] a head's factor over its
    channels."""
    return apply_op(
        "gated_delta_rule", "gated_delta_rule",
        {"Q": [q], "K": [k], "V": [v], "A": [a], "B": [b],
         "ALog": [a_log], "DtBias": [dt_bias]}, {}, ["Out"],
        out_dtype=v.dtype)[0]


def moe_router(input, weight, bias=None, top_k=1, norm_topk_prob=True,
               routed_scaling_factor=1.0, score_function="sigmoid"):
    """Scores (`score_function` "sigmoid", or "softmax" over the
    experts) over all of `weight`'s [H, E] experts in float32, the
    `top_k` largest of score + `bias` chosen. Returns (expert numbers
    [T, k] int32, their weights [T, k] float32)."""
    inputs = {"X": [input], "W": [weight]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper = LayerHelper("moe_router")
    idx = helper.create_variable_for_type_inference("int32")
    idx.stop_gradient = True
    wgt = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe_router", inputs=inputs,
        outputs={"TopkIdx": [idx], "TopkWeight": [wgt]},
        attrs={"top_k": int(top_k), "norm_topk_prob": bool(norm_topk_prob),
               "routed_scaling_factor": float(routed_scaling_factor),
               "score_function": score_function})
    return idx, wgt


def moe_experts(input, topk_idx, topk_weight, w_up, w_down, held_start,
                num_experts, activation="relu2"):
    """The part of a routed layer that the experts [held_start,
    held_start + w_up.shape[0]) of `num_experts` give, no pair dropped:
    this chip's share of an expert-parallel layer (on one chip, with
    every expert held, the whole layer). Returns (out like `input`,
    pairs computed [1], fullest held expert's pairs over the mean [1],
    rows of sorted pairs made [1]: whole row blocks, as many as the
    step's routing fills). With `activation` "swiglu" the experts are
    gated: `w_up` [E, H, 2 F] holds the gate's and the up projection's
    matrices side by side, `w_down` [E, F, H]."""
    helper = LayerHelper("moe_experts")
    out = helper.create_variable_for_type_inference(input.dtype)
    counters = []
    for _ in range(3):
        v = helper.create_variable_for_type_inference("float32")
        v.stop_gradient = True
        counters.append(v)
    helper.append_op(
        type="moe_experts",
        inputs={"X": [input], "TopkIdx": [topk_idx],
                "TopkWeight": [topk_weight], "WUp": [w_up],
                "WDown": [w_down]},
        outputs={"Out": [out], "HeldPairs": [counters[0]],
                 "LoadMaxOverMean": [counters[1]],
                 "RowsMade": [counters[2]]},
        attrs={"held_start": int(held_start),
               "num_experts": int(num_experts),
               "activation": activation})
    return (out, *counters)
