"""A delta-rule / latent-attention decoder with routed experts
(moonshotai's Kimi-Linear-48B-A3B: Kimi Delta Attention among layers of
multi-head latent attention without position, arXiv:2510.26692) in the
fluid static graph API. Every layer is `h <- h + Mix_i(N(h))`, then
`h <- h + FFN_i(N(h))`, `N` the plain RMS norm. Layer i (counted from 1
in `linear_attn_config`) mixes by Kimi Delta Attention where it is in
`kda_layers` and by `models/kimi_vl.py`'s latent attention, unturned
(`mla_use_nope`), where it is in `full_attn_layers`. The first
`first_k_dense_replace` layers have a dense SwiGLU feed-forward, the
others routed experts. A last norm, an untied head and next-token
cross-entropy.

Kimi Delta Attention (`kda_mixer`) is the gated delta rule with a decay
per key channel: `gated_delta_rule` handed a of [B, S, H, dk] and a
dt_bias of [H * dk].

Its own: the configuration's published names, the KDA mixer and the
plan. From `models/kimi_vl.py`: the configuration's class and the
latent attention. From `models/decoder.py`: the norm, the dense
feed-forward, the routed layer (kimi-vl's: sigmoid router over all
experts, the top few renormalised and scaled, `swiglu` experts, ungated
shared experts), the skeleton and the loss, with what it says of
recompute and expert parallelism.
"""
from __future__ import annotations

from ..fluid import initializer, layers
from .decoder import (a_log_init, causal_lm_loss, dense_layer, param,
                      routed_layer)
from .kimi_vl import KimiVLConfig, latent_attention_mixer

KDA, MLA = "kda", "mla"


class KimiLinearConfig(KimiVLConfig):
    """The published `config.json` keys the model reads (defaults:
    Kimi-Linear-48B-A3B-Instruct), held under `KimiVLConfig`'s names
    where the latent attention and the shared blocks read them.
    `linear_attn_config` lists the layers of each kind, counted
    from 1; it may list more layers than `num_hidden_layers`: the first
    that many are built. `experts_held` is (first expert, how many) of
    the `num_experts` this chip holds; None holds them all."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 num_attention_heads=32, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                 mla_use_nope=True, rope_theta=10000.0,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_experts=256, num_shared_experts=1,
                 num_experts_per_token=8, routed_scaling_factor=2.446,
                 moe_renormalize=True, rms_norm_eps=1e-5,
                 linear_attn_config=None, initializer_range=0.02,
                 experts_held=None):
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            num_hidden_layers=num_hidden_layers,
            first_k_dense_replace=first_k_dense_replace,
            num_attention_heads=num_attention_heads,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            kv_lora_rank=kv_lora_rank, rope_theta=rope_theta,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=num_experts,
            n_shared_experts=num_shared_experts,
            num_experts_per_tok=num_experts_per_token,
            routed_scaling_factor=routed_scaling_factor,
            norm_topk_prob=moe_renormalize, rms_norm_eps=rms_norm_eps,
            initializer_range=initializer_range, experts_held=experts_held,
            mla_use_nope=mla_use_nope)
        self.linear_attn_config = dict(linear_attn_config or {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "kda_layers": [i for i in range(1, 28) if i % 4 and i != 27],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4})
        kinds = {i: KDA for i in self.linear_attn_config["kda_layers"]}
        kinds.update({i: MLA for i in
                      self.linear_attn_config["full_attn_layers"]})
        missing = [i for i in range(1, num_hidden_layers + 1)
                   if i not in kinds]
        if missing:
            raise ValueError("layers %s are of no kind" % missing)
        self.layer_kinds = [kinds[i] for i in range(1, num_hidden_layers + 1)]

    @staticmethod
    def tiny(**over):
        """A whole period of three delta-rule layers to one latent
        attention layer after the dense one, heads of 128 (the kernels'
        whole lane tiles) at widths a CPU test affords."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=5,
                  first_k_dense_replace=1, num_attention_heads=2,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
                  kv_lora_rank=20, intermediate_size=48,
                  moe_intermediate_size=16, num_experts=8,
                  num_shared_experts=1, num_experts_per_token=3,
                  linear_attn_config={
                      "full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                      "head_dim": 128, "num_heads": 2,
                      "short_conv_kernel_size": 4})
        kw.update(over)
        return KimiLinearConfig(**kw)


def kda_mixer(x, cfg, name):
    """Kimi Delta Attention: `q_proj`, `k_proj`, `v_proj` each pass a
    causal depthwise convolution of its own and silu; q and k are
    L2-normalised a head, q scaled by dk^-0.5; the decay a key channel
    g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias) (float32 in the
    op), the writing strength sigmoid(x W_b); the gated delta rule; the
    norm over each head's dv times sigmoid(x W_ga W_gb + b_g); the output
    projection."""
    lin = cfg.linear_attn_config
    h, heads, d = cfg.hidden_size, lin["num_heads"], lin["head_dim"]
    wide, taps = heads * d, lin["short_conv_kernel_size"]
    w_q, w_k, w_v = (param(name + "." + p, [h, wide], cfg)
                     for p in ("q_proj", "k_proj", "v_proj"))
    convs = [param(name + "." + p, [wide, taps], cfg,
                   initializer.Uniform(-0.5, 0.5))
             for p in ("q_conv", "k_conv", "v_conv")]
    w_fa = param(name + ".f_a_proj", [h, d], cfg)
    w_fb = param(name + ".f_b_proj", [d, wide], cfg)
    w_b = param(name + ".b_proj", [h, heads], cfg)
    a_log = param(name + ".A_log", [heads], cfg, a_log_init(heads))
    dt_bias = param(name + ".dt_bias", [wide], cfg, 0.0)
    w_ga = param(name + ".g_a_proj", [h, d], cfg)
    w_gb = param(name + ".g_b_proj", [d, wide], cfg)
    b_g = param(name + ".g_b_bias", [wide], cfg, 0.0)
    norm_w = param(name + ".o_norm", [d], cfg, 1.0)
    w_o = param(name + ".o_proj", [wide, h], cfg)

    q, k, v = (layers.reshape(layers.causal_conv1d(
        layers.matmul(x, w), conv, activation="silu"), [0, 0, heads, d])
        for w, conv in zip((w_q, w_k, w_v), convs))
    q = layers.scale(layers.l2_norm(q), scale=d ** -0.5)
    k = layers.l2_norm(k)
    a = layers.reshape(layers.matmul(layers.matmul(x, w_fa), w_fb),
                       [0, 0, heads, d])
    out = layers.gated_delta_rule(q, k, v, a, layers.matmul(x, w_b), a_log,
                                  dt_bias)
    gate = layers.elementwise_add(
        layers.matmul(layers.matmul(x, w_ga), w_gb), b_g)
    out = layers.elementwise_mul(
        layers.rms_norm(out, scale=norm_w, epsilon=cfg.rms_norm_eps),
        layers.sigmoid(layers.reshape(gate, [0, 0, heads, d])))
    return layers.matmul(layers.reshape(out, [0, 0, wide]), w_o)


def plan(cfg):
    """Layer i: KDA or latent attention behind `.input_norm`, then the
    dense or the routed feed-forward behind `.post_attention_norm`."""
    return [[(".input_norm", kda_mixer if cfg.layer_kinds[i] == KDA
              else latent_attention_mixer),
             (".post_attention_norm",
              dense_layer if cfg.is_dense(i) else routed_layer)]
            for i in range(cfg.num_hidden_layers)]


def kimi_linear_loss(cfg, seq_len, checkpoints_out=None):
    """`decoder.causal_lm_loss` of the stack `plan` lays out."""
    return causal_lm_loss(cfg, seq_len, plan(cfg), checkpoints_out)
