"""A gated-delta-rule / gated-attention / routed-expert decoder (the
Qwen3-Next family: Qwen3-Next-80B-A3B-Instruct) in the fluid static
graph API. Every layer is a mixer and a routed feed-forward:
`h <- h + Mixer_i(N(h))`, then `h <- h + MoE_i(N(h))`; layer i mixes by
gated softmax attention where `(i + 1) % full_attention_interval == 0`
and by a Gated DeltaNet (a linear-attention state updated by the gated
delta rule) otherwise; a last norm, an untied head and next-token
cross-entropy. `N(x) = x rsqrt(mean(x^2) + eps) (1 + w)`, w starting
at zero.

Its own: the configuration, the Gated DeltaNet and gated attention
mixers and the plan. From `models/decoder.py`: the zero-centred norm,
the routed layer (softmax router, `swiglu` experts, a sigmoid-gated
shared expert), the skeleton and the loss, with what it says of
recompute and expert parallelism.
"""
from __future__ import annotations

import math

import numpy as np

from ..fluid import initializer, layers
from .decoder import causal_lm_loss, cut, param, rms_norm_block, routed_layer


class Qwen3NextConfig:
    """The published `config.json` keys the model reads (defaults:
    Qwen3-Next-80B-A3B-Instruct). `experts_held` is (first expert, how
    many) of the `num_experts` this chip holds; None holds them all."""

    ZERO_CENTRED_NORM = True
    ROUTER_SCORE, EXPERT_ACTIVATION = "softmax", "swiglu"
    SHARED_EXPERT_GATED = True

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, full_attention_interval=4,
                 num_attention_heads=16, num_key_value_heads=2,
                 head_dim=256, partial_rotary_factor=0.25,
                 rope_theta=10000000.0, linear_num_key_heads=16,
                 linear_num_value_heads=32, linear_key_head_dim=128,
                 linear_value_head_dim=128, linear_conv_kernel_dim=4,
                 num_experts=512, num_experts_per_tok=10,
                 moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, norm_topk_prob=True,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 experts_held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.full_attention_interval = full_attention_interval
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = \
            shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, num_experts))
        #: the routed weights' factor (`decoder.routed_layer` reads it):
        #: the published config has none
        self.routed_scaling_factor = 1.0
        if linear_num_value_heads % linear_num_key_heads:
            raise ValueError("%d value heads on %d key heads"
                             % (linear_num_value_heads,
                                linear_num_key_heads))

    def is_attention(self, i):
        return (i + 1) % self.full_attention_interval == 0

    @staticmethod
    def tiny(**over):
        """Both kinds of layer at widths a CPU test affords."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                  full_attention_interval=4, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  partial_rotary_factor=0.25, linear_num_key_heads=2,
                  linear_num_value_heads=4, linear_key_head_dim=8,
                  linear_value_head_dim=8, linear_conv_kernel_dim=4,
                  num_experts=8, num_experts_per_tok=3,
                  moe_intermediate_size=16,
                  shared_expert_intermediate_size=24)
        kw.update(over)
        return Qwen3NextConfig(**kw)


def _dt_bias_init(heads):
    """The inverse softplus of steps spread log-uniformly over 0.001
    to 0.1, the published initialisation's range."""
    dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), heads))
    return initializer.NumpyArrayInitializer(
        (dt + np.log(-np.expm1(-dt))).astype("float32"))


def _a_log_init(heads):
    """A spread over (0, 16], the published initialisation's range."""
    return initializer.NumpyArrayInitializer(
        np.log(np.linspace(16.0 / heads, 16.0, heads)).astype("float32"))


def gated_delta_net_mixer(x, cfg, name):
    """`in_proj_qkvz` gives each key head its q, k [dk] and its value
    heads' v, z [r dv]; `in_proj_ba` their b, a. q, k, v pass the causal
    depthwise convolution and silu; q and k are L2-normalised, q scaled
    by dk^-0.5; the gated delta rule; the norm over each head's dv, its
    gate silu(z); the output projection."""
    h = cfg.hidden_size
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, hv // hk
    key_dim, value_dim = hk * dk, hv * dv
    w_qkvz = param(name + ".in_proj_qkvz", [h, 2 * key_dim + 2 * value_dim],
                   cfg)
    w_ba = param(name + ".in_proj_ba", [h, 2 * hv], cfg)
    conv_w = param(name + ".conv.w",
                   [2 * key_dim + value_dim, cfg.linear_conv_kernel_dim], cfg,
                   initializer.Uniform(-0.5, 0.5))
    a_log = param(name + ".A_log", [hv], cfg, _a_log_init(hv))
    dt_bias = param(name + ".dt_bias", [hv], cfg, _dt_bias_init(hv))
    norm_w = param(name + ".gate_norm", [dv], cfg, 1.0)
    w_out = param(name + ".out_proj", [value_dim, h], cfg)

    qkvz = layers.reshape(layers.matmul(x, w_qkvz),
                          [0, 0, hk, 2 * dk + 2 * r * dv])
    q, k, v, z = cut(qkvz, 3, [dk, dk, r * dv, r * dv])
    b, a = cut(layers.reshape(layers.matmul(x, w_ba), [0, 0, hk, 2 * r]),
               3, [r, r])
    mixed = layers.causal_conv1d(
        layers.concat([layers.reshape(q, [0, 0, key_dim]),
                       layers.reshape(k, [0, 0, key_dim]),
                       layers.reshape(v, [0, 0, value_dim])], axis=2),
        conv_w, activation="silu")
    q, k, v = cut(mixed, 2, [key_dim, key_dim, value_dim])
    q = layers.scale(layers.l2_norm(layers.reshape(q, [0, 0, hk, dk])),
                     scale=dk ** -0.5)
    k = layers.l2_norm(layers.reshape(k, [0, 0, hk, dk]))
    out = layers.gated_delta_rule(
        q, k, layers.reshape(v, [0, 0, hv, dv]),
        layers.reshape(a, [0, 0, hv]), layers.reshape(b, [0, 0, hv]),
        a_log, dt_bias)
    out = layers.elementwise_mul(
        layers.rms_norm(out, scale=norm_w, epsilon=cfg.rms_norm_eps),
        layers.silu(layers.reshape(z, [0, 0, hv, dv])))
    return layers.matmul(layers.reshape(out, [0, 0, value_dim]), w_out)


def gated_attention_mixer(x, cfg, name):
    """Causal grouped-query attention whose `q_proj` gives every head a
    query and a gate: the zero-centred norm over the head axis of q and
    of k, the rotary embedding on the first `partial_rotary_factor` of
    the head, attention through `scaled_dot_product_attention` (K and V
    keep their own few heads into the kernel), the output times
    sigmoid(gate), the output projection."""
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    rotary = {"rotary_dim": int(d * cfg.partial_rotary_factor),
              "theta": cfg.rope_theta}
    w_q = param(name + ".q_proj", [h, nq * 2 * d], cfg)
    w_k = param(name + ".k_proj", [h, nkv * d], cfg)
    w_v = param(name + ".v_proj", [h, nkv * d], cfg)
    w_o = param(name + ".o_proj", [nq * d, h], cfg)

    q, gate = cut(layers.reshape(layers.matmul(x, w_q), [0, 0, nq, 2 * d]),
                  3, [d, d])
    k = layers.reshape(layers.matmul(x, w_k), [0, 0, nkv, d])
    v = layers.reshape(layers.matmul(x, w_v), [0, 0, nkv, d])
    q = layers.rotary_embedding(rms_norm_block(q, name + ".q_norm", cfg),
                                **rotary)
    k = layers.rotary_embedding(rms_norm_block(k, name + ".k_norm", cfg),
                                **rotary)
    ctx = layers.scaled_dot_product_attention(
        *(layers.transpose(t, [0, 2, 1, 3]) for t in (q, k, v)),
        causal=True, sm_scale=1.0 / math.sqrt(d), is_test=True)
    ctx = layers.elementwise_mul(
        layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, nq * d]),
        layers.sigmoid(layers.reshape(gate, [0, 0, nq * d])))
    return layers.matmul(ctx, w_o)


def plan(cfg):
    """Layer i: gated attention or a Gated DeltaNet behind
    `.input_norm`, then the routed layer behind `.post_mixer_norm`."""
    return [[(".input_norm", gated_attention_mixer if cfg.is_attention(i)
              else gated_delta_net_mixer),
             (".post_mixer_norm", routed_layer)]
            for i in range(cfg.num_hidden_layers)]


def qwen3_next_loss(cfg, seq_len, checkpoints_out=None):
    """`decoder.causal_lm_loss` of the stack `plan` lays out."""
    return causal_lm_loss(cfg, seq_len, plan(cfg), checkpoints_out)
