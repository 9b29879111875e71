"""A window/full-attention decoder with routed experts (poolside's
Laguna-S-2.1) in the fluid static graph API. Every layer is
`h <- h + Attn_i(N(h))`, then `h <- h + FFN_i(N(h))`, `N` the plain RMS
norm. Layer i's attention is of its `layer_types` kind: full causal
attention, or causal within a sliding window of `sliding_window` keys;
each kind has its own rotary embedding (`rope_parameters`: YaRN on half
of a full layer's head, the default on all of a window layer's) and its
layers their own count of query heads (`num_attention_heads_per_layer`)
on the same key/value heads, and every head's output is scaled by a
sigmoid gate of its own (one scalar a head and token). The layers in
`mlp_only_layers` have a dense SwiGLU feed-forward, the others routed
experts. A last norm, an untied head and next-token cross-entropy.

Its own: the configuration, the attention mixer and the plan. From
`models/decoder.py`: the norm, the dense feed-forward, the routed layer
(softmax router, top-k renormalised and scaled, `swiglu` experts, a
sigmoid-gated shared expert), the skeleton and the loss, with what it
says of recompute and expert parallelism.

A chip's share of a deployment: besides its share of the experts, a
configuration whose head counts are the chip's share of a
tensor-parallel pair (half of the query heads with their gates and
`o_proj` rows, half of the key/value heads) builds that share of every
attention layer, whose output is then the layer's partial sum.
"""
from __future__ import annotations

import functools
import math

from ..fluid import layers
from .decoder import causal_lm_loss, dense_layer, param, routed_layer

FULL, SLIDING = "full_attention", "sliding_attention"


class LagunaConfig:
    """The published `config.json` keys the model reads (defaults:
    Laguna-S-2.1). `layer_types` and `num_attention_heads_per_layer`
    may be longer than `num_hidden_layers`: the first that many are
    built. `experts_held` is (first expert, how many) of the
    `num_experts` this chip holds; None holds them all."""

    ZERO_CENTRED_NORM = False
    ROUTER_SCORE, EXPERT_ACTIVATION = "softmax", "swiglu"
    SHARED_EXPERT_GATED = True

    def __init__(self, vocab_size=100352, hidden_size=3072,
                 intermediate_size=12288, num_hidden_layers=48,
                 num_attention_heads=48, num_key_value_heads=8,
                 head_dim=128, rms_norm_eps=1e-6, num_experts=256,
                 num_experts_per_tok=10, moe_intermediate_size=1024,
                 shared_expert_intermediate_size=1024, norm_topk_prob=True,
                 mlp_only_layers=(0,), sliding_window=512,
                 rope_parameters=None, layer_types=None,
                 num_attention_heads_per_layer=None,
                 moe_routed_scaling_factor=2.5, initializer_range=0.02,
                 experts_held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = \
            shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.mlp_only_layers = tuple(mlp_only_layers)
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters or {
            FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 128,
                   "original_max_position_embeddings": 8192,
                   "beta_fast": 32, "beta_slow": 1,
                   "attention_factor": 1.4852030263919618,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}}
        self.layer_types = list(layer_types or (
            [FULL, SLIDING, SLIDING, SLIDING] * -(-num_hidden_layers // 4)))
        self.num_attention_heads_per_layer = list(
            num_attention_heads_per_layer or (
                [num_attention_heads if t == FULL
                 else num_attention_heads * 3 // 2
                 for t in self.layer_types]))
        # `decoder.routed_layer` reads it so
        self.routed_scaling_factor = moe_routed_scaling_factor
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, num_experts))
        if len(self.layer_types) < num_hidden_layers or len(
                self.num_attention_heads_per_layer) < num_hidden_layers:
            raise ValueError("%d layers, %d kinds and %d head counts"
                             % (num_hidden_layers, len(self.layer_types),
                                len(self.num_attention_heads_per_layer)))
        for heads in self.num_attention_heads_per_layer:
            if heads % num_key_value_heads:
                raise ValueError("%d query heads on %d key/value heads"
                                 % (heads, num_key_value_heads))

    def is_dense(self, i):
        return i in self.mlp_only_layers

    def window(self, i):
        """Layer i's sliding window, or None where it attends to all."""
        return self.sliding_window if self.layer_types[i] == SLIDING \
            else None

    @staticmethod
    def tiny(**over):
        """Both kinds of attention (a window under the test's length,
        YaRN's ramp crossing the rotary columns), unequal head counts,
        the dense layer and two routed ones at widths a CPU test
        affords."""
        kw = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                  num_hidden_layers=3, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  num_experts=8, num_experts_per_tok=3,
                  moe_intermediate_size=16,
                  shared_expert_intermediate_size=16, sliding_window=8,
                  rope_parameters={
                      FULL: {"rope_type": "yarn", "rope_theta": 10000,
                             "factor": 4,
                             "original_max_position_embeddings": 64,
                             "beta_fast": 32, "beta_slow": 1,
                             "attention_factor": 1.1386294361119891,
                             "partial_rotary_factor": 0.5},
                      SLIDING: {"rope_type": "default", "rope_theta": 10000,
                                "partial_rotary_factor": 1}},
                  layer_types=[FULL, SLIDING, FULL],
                  num_attention_heads_per_layer=[4, 6, 4])
        kw.update(over)
        return LagunaConfig(**kw)


def attention_mixer(x, cfg, name, layer):
    """Grouped-query attention of layer `layer`'s kind and head count:
    the rotary embedding of its kind on q and k, causal attention
    through `scaled_dot_product_attention` (within the window on a
    window layer; K and V keep their own few heads into the kernel),
    every head's output times sigmoid(x W_g) of its own, the output
    projection."""
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads_per_layer[layer], \
        cfg.num_key_value_heads
    rope = cfg.rope_parameters[cfg.layer_types[layer]]
    rotary = {"rotary_dim": int(d * rope.get("partial_rotary_factor", 1)),
              "theta": rope["rope_theta"], "scaling": rope}
    w_q = param(name + ".q_proj", [h, nq * d], cfg)
    w_k = param(name + ".k_proj", [h, nkv * d], cfg)
    w_v = param(name + ".v_proj", [h, nkv * d], cfg)
    w_g = param(name + ".g_proj", [h, nq], cfg)
    w_o = param(name + ".o_proj", [nq * d, h], cfg)

    q = layers.rotary_embedding(
        layers.reshape(layers.matmul(x, w_q), [0, 0, nq, d]), **rotary)
    k = layers.rotary_embedding(
        layers.reshape(layers.matmul(x, w_k), [0, 0, nkv, d]), **rotary)
    v = layers.reshape(layers.matmul(x, w_v), [0, 0, nkv, d])
    ctx = layers.scaled_dot_product_attention(
        *(layers.transpose(t, [0, 2, 1, 3]) for t in (q, k, v)),
        causal=True, sm_scale=1.0 / math.sqrt(d), is_test=True,
        window=cfg.window(layer))
    ctx = layers.elementwise_mul(
        layers.transpose(ctx, [0, 2, 1, 3]),
        layers.sigmoid(layers.reshape(layers.matmul(x, w_g),
                                      [0, 0, nq, 1])))
    return layers.matmul(layers.reshape(ctx, [0, 0, nq * d]), w_o)


def plan(cfg):
    """Layer i: its attention behind `.input_norm`, then the dense or the
    routed feed-forward behind `.post_attention_norm`."""
    return [[(".input_norm", functools.partial(attention_mixer, layer=i)),
             (".post_attention_norm",
              dense_layer if cfg.is_dense(i) else routed_layer)]
            for i in range(cfg.num_hidden_layers)]


def laguna_loss(cfg, seq_len, checkpoints_out=None):
    """`decoder.causal_lm_loss` of the stack `plan` lays out."""
    return causal_lm_loss(cfg, seq_len, plan(cfg), checkpoints_out)
