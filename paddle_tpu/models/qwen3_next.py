"""A gated-delta-rule / gated-attention / routed-expert decoder (the
Qwen3-Next family: Qwen3-Next-80B-A3B-Instruct) in the fluid static
graph API. Every layer is a mixer and a routed feed-forward:
`h <- h + Mixer_i(N(h))`, then `h <- h + MoE_i(N(h))`; layer i mixes by
gated softmax attention where `(i + 1) % full_attention_interval == 0`
and by a Gated DeltaNet (a linear-attention state updated by the gated
delta rule) otherwise; a last norm, an untied head and next-token
cross-entropy. `N(x) = x rsqrt(mean(x^2) + eps) (1 + w)`, w starting
at zero. Trained like `models/nemotron_h.py`: an optimizer's
`minimize` under `mixed_precision.decorate`, `Executor.run` a step.

Per-layer recompute: the layers are of two kinds, so the stack is
unrolled and every mixer's and every routed layer's output is a
checkpoint (`qwen3_next_loss(..., checkpoints_out=[])` hands them to
`RecomputeOptimizer`).

Expert parallelism: `experts_held=(first, count)` builds the chip's
share of every routed layer (`parallel.planner.experts_held`): the
router scores all `num_experts`, the layer computes its own experts'
part for the tokens routed to them and the shared expert, and that
partial sum is the layer's output. Nothing stands in for the other
chips.
"""
from __future__ import annotations

import math

import numpy as np

from ..fluid import initializer, layers
from ..fluid.param_attr import ParamAttr
from .nemotron_h import next_token_loss, routed_counters


class Qwen3NextConfig:
    """The published `config.json` keys the model reads (defaults:
    Qwen3-Next-80B-A3B-Instruct). `experts_held` is (first expert, how
    many) of the `num_experts` this chip holds; None holds them all."""

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


def _par(name, shape, cfg, init=None):
    init = init or initializer.TruncatedNormal(0.0, cfg.initializer_range)
    return layers.create_parameter(
        shape=shape, dtype="float32", name=name,
        attr=ParamAttr(name=name, initializer=init))


def _const(value):
    return initializer.Constant(float(value))


def _norm(x, name, cfg):
    """The zero-centred RMS norm over the last axis, its own weight."""
    return layers.rms_norm(
        x, scale=_par(name, [int(x.shape[-1])], cfg, _const(0.0)),
        epsilon=cfg.rms_norm_eps, zero_centered=True)


def _cut(x, axis, sizes):
    """`x` cut along `axis` into consecutive parts of `sizes`."""
    edges = np.cumsum([0] + list(sizes))
    return [layers.slice(x, axes=[axis], starts=[int(lo)], ends=[int(hi)])
            for lo, hi in zip(edges, edges[1:])]


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
    w_qkvz = _par(name + ".in_proj_qkvz", [h, 2 * key_dim + 2 * value_dim],
                  cfg)
    w_ba = _par(name + ".in_proj_ba", [h, 2 * hv], cfg)
    conv_w = _par(name + ".conv.w",
                  [2 * key_dim + value_dim, cfg.linear_conv_kernel_dim], cfg,
                  initializer.Uniform(-0.5, 0.5))
    a_log = _par(name + ".A_log", [hv], cfg, _a_log_init(hv))
    dt_bias = _par(name + ".dt_bias", [hv], cfg, _dt_bias_init(hv))
    norm_w = _par(name + ".gate_norm", [dv], cfg, _const(1.0))
    w_out = _par(name + ".out_proj", [value_dim, h], cfg)

    qkvz = layers.reshape(layers.matmul(x, w_qkvz),
                          [0, 0, hk, 2 * dk + 2 * r * dv])
    q, k, v, z = _cut(qkvz, 3, [dk, dk, r * dv, r * dv])
    b, a = _cut(layers.reshape(layers.matmul(x, w_ba), [0, 0, hk, 2 * r]),
                3, [r, r])
    mixed = layers.causal_conv1d(
        layers.concat([layers.reshape(q, [0, 0, key_dim]),
                       layers.reshape(k, [0, 0, key_dim]),
                       layers.reshape(v, [0, 0, value_dim])], axis=2),
        conv_w, activation="silu")
    q, k, v = _cut(mixed, 2, [key_dim, key_dim, value_dim])
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
    w_q = _par(name + ".q_proj", [h, nq * 2 * d], cfg)
    w_k = _par(name + ".k_proj", [h, nkv * d], cfg)
    w_v = _par(name + ".v_proj", [h, nkv * d], cfg)
    w_o = _par(name + ".o_proj", [nq * d, h], cfg)

    q, gate = _cut(layers.reshape(layers.matmul(x, w_q), [0, 0, nq, 2 * d]),
                   3, [d, d])
    k = layers.reshape(layers.matmul(x, w_k), [0, 0, nkv, d])
    v = layers.reshape(layers.matmul(x, w_v), [0, 0, nkv, d])
    q = layers.rotary_embedding(_norm(q, name + ".q_norm", cfg), **rotary)
    k = layers.rotary_embedding(_norm(k, name + ".k_norm", cfg), **rotary)
    ctx = layers.scaled_dot_product_attention(
        *(layers.transpose(t, [0, 2, 1, 3]) for t in (q, k, v)),
        causal=True, sm_scale=1.0 / math.sqrt(d), is_test=True)
    ctx = layers.elementwise_mul(
        layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, nq * d]),
        layers.sigmoid(layers.reshape(gate, [0, 0, nq * d])))
    return layers.matmul(ctx, w_o)


def routed_layer(x, cfg, name, counters=None):
    """The shared expert (times its own sigmoid gate) for every token
    plus this chip's share of the routed experts, all gated
    (`swiglu`): softmax scores over all experts, the top few
    renormalised. `counters` collects the layer's (pairs computed,
    fullest expert over the mean, rows made)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    fs = cfg.shared_expert_intermediate_size
    first, count = cfg.experts_held
    w_r = _par(name + ".router", [h, cfg.num_experts], cfg)
    w_sgu = _par(name + ".shared_gate_up", [h, 2 * fs], cfg)
    w_sd = _par(name + ".shared_down", [fs, h], cfg)
    w_sg = _par(name + ".shared_gate", [h, 1], cfg)
    w_gu = _par(name + ".experts_gate_up", [count, h, 2 * f], cfg)
    w_down = _par(name + ".experts_down", [count, f, h], cfg)
    idx, weight = layers.moe_router(
        x, w_r, top_k=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, score_function="softmax")
    routed, *counted = layers.moe_experts(
        x, idx, weight, w_gu, w_down, held_start=first,
        num_experts=cfg.num_experts, activation="swiglu")
    if counters is not None:
        counters.append(counted)
    shared = layers.matmul(layers.swiglu(layers.matmul(x, w_sgu)), w_sd)
    shared = layers.elementwise_mul(
        shared, layers.sigmoid(layers.matmul(x, w_sg)))
    return layers.elementwise_add(shared, routed)


def qwen3_next_decoder(ids, cfg, checkpoints_out=None, counters=None):
    """ids [B, S] -> hidden states [B, S, H] after the final norm."""
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=ParamAttr(
                             name="embed", initializer=initializer
                             .TruncatedNormal(0.0, cfg.initializer_range)))
    for i in range(cfg.num_hidden_layers):
        name = "l%d" % i
        mixer = (gated_attention_mixer if cfg.is_attention(i)
                 else gated_delta_net_mixer)
        for norm, fn, extra in (
                (".input_norm", mixer, {}),
                (".post_mixer_norm", routed_layer, {"counters": counters})):
            h = layers.elementwise_add(
                h, fn(_norm(h, name + norm, cfg), cfg, name, **extra))
            if checkpoints_out is not None:
                checkpoints_out.append(h)
    return _norm(h, "final_norm", cfg)


def qwen3_next_loss(cfg, seq_len, checkpoints_out=None):
    """Next-token cross-entropy over feed vars `ids` and `labels`
    [B, seq_len] (the caller shifts: labels[t] is the token after
    ids[t]), the mean over all positions. Returns (loss, counters,
    feeds): `counters` is what `nemotron_h.routed_counters` gives of the
    routed layers, to fetch with the loss where wanted."""
    ids = layers.data(name="ids", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len], dtype="int64")
    per_layer = []
    hidden = qwen3_next_decoder(ids, cfg, checkpoints_out, per_layer)
    loss = next_token_loss(hidden, labels, cfg)
    return loss, routed_counters(per_layer), ["ids", "labels"]
