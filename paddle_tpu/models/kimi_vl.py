"""A latent-attention / routed-expert decoder (the language model of
Kimi-VL-A3B-Instruct: DeepSeek-V2's multi-head latent attention without
a query compression, DeepSeek-V3's sigmoid router with a selection
bias) in the fluid static graph API. Every layer is
`h <- h + Attn_i(N(h))`, then `h <- h + FFN_i(N(h))`, `N` the plain RMS
norm; the first `first_k_dense_replace` layers have a dense SwiGLU
feed-forward, the others routed experts and ungated shared ones; a last
norm, an untied head and next-token cross-entropy.

Latent attention, as the architecture's paper gives it for training:
keys and values are DECOMPRESSED from the latent (`kv_b_proj`) and the
attention is `scaled_dot_product_attention` over queries and keys of
`qk_nope_head_dim + qk_rope_head_dim` (192) and values of `v_head_dim`
(128). The rotary part of the key is ONE head that every query head
reads; it is joined to each head's unrotated part in HBM (the flash
kernels read K `[B, heads, S, 192]`). The text model alone: no vision
tower, no projector.

Its own: the configuration, the latent attention mixer and the plan.
From `models/decoder.py`: the norm, the dense feed-forward, the routed
layer (sigmoid router with a selection bias, `swiglu` experts, ungated
shared experts of `n_shared_experts` times the expert width), the
skeleton and the loss, with what it says of recompute and expert
parallelism.
"""
from __future__ import annotations

import math

from ..fluid import layers
from .decoder import (causal_lm_loss, cut, dense_layer, param,
                      rms_norm_block, routed_layer)


class KimiVLConfig:
    """The published `config.json` keys the text model reads (defaults:
    Kimi-VL-A3B-Instruct). `experts_held` is (first expert, how many)
    of the `n_routed_experts` this chip holds; None holds them all."""

    ZERO_CENTRED_NORM = False
    ROUTER_SCORE, EXPERT_ACTIVATION = "sigmoid", "swiglu"
    SHARED_EXPERT_GATED = False

    def __init__(self, vocab_size=163840, hidden_size=2048,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 num_attention_heads=16, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                 rope_theta=800000.0, intermediate_size=11264,
                 moe_intermediate_size=1408, n_routed_experts=64,
                 n_shared_experts=2, num_experts_per_tok=6,
                 routed_scaling_factor=2.446, norm_topk_prob=True,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 experts_held=None, mla_use_nope=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.rope_theta = rope_theta
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, n_routed_experts))
        #: no rotary: the `qk_rope_head_dim` parts pass unturned
        #: (Kimi-Linear's latent attention; positions come from its
        #: delta-rule layers)
        self.mla_use_nope = mla_use_nope

    def is_dense(self, i):
        return i < self.first_k_dense_replace

    # the names `models/decoder.py`'s routed layer reads
    num_experts = property(lambda self: self.n_routed_experts)
    shared_expert_intermediate_size = property(
        lambda self: self.n_shared_experts * self.moe_intermediate_size)

    @staticmethod
    def tiny(**over):
        """Both kinds of layer, unequal head sizes and a one-head
        rotary key at widths a CPU test affords."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                  first_k_dense_replace=1, num_attention_heads=4,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
                  kv_lora_rank=20, intermediate_size=48,
                  moe_intermediate_size=16, n_routed_experts=8,
                  n_shared_experts=2, num_experts_per_tok=3)
        kw.update(over)
        return KimiVLConfig(**kw)


def latent_attention_mixer(x, cfg, name):
    """`q_proj` gives every head `q_nope | q_rope`; `kv_a_proj` the
    latent `c` and the one rotary key head `k_r`; `c` passes a norm of
    its own and `kv_b_proj` decompresses it into every head's
    `k_nope | v`. `q_rope` and `k_r` are turned by the rotary embedding
    (left as they are where `cfg.mla_use_nope`); a head's key is
    `k_nope | k_r`, `k_r` the same for all heads.
    Causal attention through `scaled_dot_product_attention`, scaled by
    (nope + rope) ** -0.5, values and output of `v_head_dim`; the
    output projection."""
    h, nq = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank

    def turn(t):
        if cfg.mla_use_nope:
            return t
        return layers.rotary_embedding(t, rotary_dim=dr, theta=cfg.rope_theta)

    w_q = param(name + ".q_proj", [h, nq * (dn + dr)], cfg)
    w_kv_a = param(name + ".kv_a_proj", [h, rank + dr], cfg)
    w_kv_b = param(name + ".kv_b_proj", [rank, nq * (dn + dv)], cfg)
    w_o = param(name + ".o_proj", [nq * dv, h], cfg)

    q_nope, q_rope = cut(
        layers.reshape(layers.matmul(x, w_q), [0, 0, nq, dn + dr]),
        3, [dn, dr])
    latent, k_rope = cut(layers.matmul(x, w_kv_a), 2, [rank, dr])
    latent = rms_norm_block(latent, name + ".kv_a_norm", cfg)
    k_nope, v = cut(layers.reshape(layers.matmul(latent, w_kv_b),
                                   [0, 0, nq, dn + dv]), 3, [dn, dv])
    q = layers.concat([q_nope, turn(q_rope)], axis=3)
    k_rope = turn(layers.reshape(k_rope, [0, 0, 1, dr]))
    k = layers.concat(
        [k_nope, layers.expand(k_rope, [1, 1, nq, 1])], axis=3)
    ctx = layers.scaled_dot_product_attention(
        *(layers.transpose(t, [0, 2, 1, 3]) for t in (q, k, v)),
        causal=True, sm_scale=1.0 / math.sqrt(dn + dr), is_test=True)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, 0, nq * dv])
    return layers.matmul(ctx, w_o)


def plan(cfg):
    """Layer i: latent attention behind `.input_norm`, then the dense or
    the routed feed-forward behind `.post_attention_norm`."""
    return [[(".input_norm", latent_attention_mixer),
             (".post_attention_norm",
              dense_layer if cfg.is_dense(i) else routed_layer)]
            for i in range(cfg.num_hidden_layers)]


def kimi_vl_loss(cfg, seq_len, checkpoints_out=None):
    """`decoder.causal_lm_loss` of the stack `plan` lays out."""
    return causal_lm_loss(cfg, seq_len, plan(cfg), checkpoints_out)
