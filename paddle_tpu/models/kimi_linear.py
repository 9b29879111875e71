"""A delta-rule / latent-attention decoder with routed experts
(moonshotai's Kimi-Linear-48B-A3B: Kimi Delta Attention among layers of
multi-head latent attention without position, arXiv:2510.26692) in the
fluid static graph API. Every layer is `h <- h + Mix_i(N(h))`, then
`h <- h + FFN_i(N(h))`, `N` the plain RMS norm. Layer i (counted from 1
in `linear_attn_config`) mixes by Kimi Delta Attention where it is in
`kda_layers` and by `models/kimi_vl.py`'s latent attention, unturned
(`mla_use_nope`), where it is in `full_attn_layers`. The first
`first_k_dense_replace` layers have a dense SwiGLU feed-forward, the
others kimi-vl's routed layer (sigmoid router over all experts, the top
few renormalised and scaled, ungated shared experts). A last norm, an
untied head and next-token cross-entropy. Trained like
`models/kimi_vl.py`: an optimizer's `minimize` under
`mixed_precision.decorate`, `Executor.run` a step.

Kimi Delta Attention (`kda_mixer`) is the gated delta rule with a decay
per key channel: `gated_delta_rule` handed a of [B, S, H, dk] and a
dt_bias of [H * dk].

Per-layer recompute: the layers are of several kinds, so the stack is
unrolled and every mixer's and every feed-forward's output is a
checkpoint (`kimi_linear_loss(..., checkpoints_out=[])` hands them to
`RecomputeOptimizer`).

Expert parallelism: `experts_held=(first, count)` builds the chip's
share of every routed layer (`parallel.planner.experts_held`; the
router scores all `num_experts`). The share's output is the layer's
partial sum; nothing stands in for the other chips.
"""
from __future__ import annotations

import numpy as np

from ..fluid import initializer, layers
from ..fluid.param_attr import ParamAttr
from .kimi_vl import (KimiVLConfig, _norm, dense_layer,
                      latent_attention_mixer, routed_layer)
from .nemotron_h import _par, next_token_loss, routed_counters

KDA, MLA = "kda", "mla"


class KimiLinearConfig(KimiVLConfig):
    """The published `config.json` keys the model reads (defaults:
    Kimi-Linear-48B-A3B-Instruct), held under `KimiVLConfig`'s names
    where kimi-vl's latent attention, dense layer and routed layer read
    them. `linear_attn_config` lists the layers of each kind, counted
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


def _a_log_init(heads):
    """A spread over [1, 16], A_log its logarithm: the family's draw's
    range (flash-linear-attention's `KimiDeltaAttention`)."""
    return initializer.NumpyArrayInitializer(
        np.log(np.linspace(1.0, 16.0, heads)).astype("float32"))


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
    w_q, w_k, w_v = (_par(name + "." + p, [h, wide], cfg)
                     for p in ("q_proj", "k_proj", "v_proj"))
    convs = [_par(name + "." + p, [wide, taps], cfg,
                  initializer.Uniform(-0.5, 0.5))
             for p in ("q_conv", "k_conv", "v_conv")]
    w_fa = _par(name + ".f_a_proj", [h, d], cfg)
    w_fb = _par(name + ".f_b_proj", [d, wide], cfg)
    w_b = _par(name + ".b_proj", [h, heads], cfg)
    a_log = _par(name + ".A_log", [heads], cfg, _a_log_init(heads))
    dt_bias = _par(name + ".dt_bias", [wide], cfg,
                   initializer.Constant(0.0))
    w_ga = _par(name + ".g_a_proj", [h, d], cfg)
    w_gb = _par(name + ".g_b_proj", [d, wide], cfg)
    b_g = _par(name + ".g_b_bias", [wide], cfg, initializer.Constant(0.0))
    norm_w = _par(name + ".o_norm", [d], cfg, initializer.Constant(1.0))
    w_o = _par(name + ".o_proj", [wide, h], cfg)

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


def kimi_linear_decoder(ids, cfg, checkpoints_out=None, counters=None):
    """ids [B, S] -> hidden states [B, S, H] after the final norm."""
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=ParamAttr(
                             name="embed", initializer=initializer
                             .TruncatedNormal(0.0, cfg.initializer_range)))
    for i in range(cfg.num_hidden_layers):
        name = "l%d" % i
        mixer = kda_mixer if cfg.layer_kinds[i] == KDA else \
            latent_attention_mixer
        feed_forward = (dense_layer, {}) if cfg.is_dense(i) else \
            (routed_layer, {"counters": counters})
        for norm, fn, extra in (
                (".input_norm", mixer, {}),
                (".post_attention_norm",) + feed_forward):
            h = layers.elementwise_add(
                h, fn(_norm(h, name + norm, cfg), cfg, name, **extra))
            if checkpoints_out is not None:
                checkpoints_out.append(h)
    return _norm(h, "final_norm", cfg)


def kimi_linear_loss(cfg, seq_len, checkpoints_out=None):
    """Next-token cross-entropy over feed vars `ids` and `labels`
    [B, seq_len] (the caller shifts: labels[t] is the token after
    ids[t]), the mean over all positions. Returns (loss, counters,
    feeds): `counters` is what `nemotron_h.routed_counters` gives of the
    routed layers, to fetch with the loss where wanted."""
    ids = layers.data(name="ids", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len], dtype="int64")
    per_layer = []
    hidden = kimi_linear_decoder(ids, cfg, checkpoints_out, per_layer)
    loss = next_token_loss(hidden, labels, cfg)
    return loss, routed_counters(per_layer), ["ids", "labels"]
