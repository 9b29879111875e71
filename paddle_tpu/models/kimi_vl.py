"""A latent-attention / routed-expert decoder (the language model of
Kimi-VL-A3B-Instruct: DeepSeek-V2's multi-head latent attention without
a query compression, DeepSeek-V3's sigmoid router with a selection
bias) in the fluid static graph API. Every layer is
`h <- h + Attn_i(N(h))`, then `h <- h + FFN_i(N(h))`, `N` the plain RMS
norm; the first `first_k_dense_replace` layers have a dense SwiGLU
feed-forward, the others routed experts and ungated shared ones; a last
norm, an untied head and next-token cross-entropy. Trained like
`models/qwen3_next.py`: an optimizer's `minimize` under
`mixed_precision.decorate`, `Executor.run` a step.

Latent attention, as the architecture's paper gives it for training:
keys and values are DECOMPRESSED from the latent (`kv_b_proj`) and the
attention is `scaled_dot_product_attention` over queries and keys of
`qk_nope_head_dim + qk_rope_head_dim` (192) and values of `v_head_dim`
(128). The rotary part of the key is ONE head that every query head
reads; it is joined to each head's unrotated part in HBM (the flash
kernels read K `[B, heads, S, 192]`). The text model alone: no vision
tower, no projector.

Per-layer recompute: the layers are of two kinds, so the stack is
unrolled and every mixer's and every feed-forward's output is a
checkpoint (`kimi_vl_loss(..., checkpoints_out=[])` hands them to
`RecomputeOptimizer`).

Expert parallelism: `experts_held=(first, count)` builds the chip's
share of every routed layer (`parallel.planner.experts_held`): the
router scores all `n_routed_experts`, the layer computes its own
experts' part for the tokens routed to them plus the shared experts,
and that partial sum is the layer's output. Nothing stands in for the
other chips.
"""
from __future__ import annotations

import math

from ..fluid import initializer, layers
from ..fluid.param_attr import ParamAttr
from .nemotron_h import _par, next_token_loss, routed_counters
from .qwen3_next import _cut


class KimiVLConfig:
    """The published `config.json` keys the text model reads (defaults:
    Kimi-VL-A3B-Instruct). `experts_held` is (first expert, how many)
    of the `n_routed_experts` this chip holds; None holds them all."""

    def __init__(self, vocab_size=163840, hidden_size=2048,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 num_attention_heads=16, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                 rope_theta=800000.0, intermediate_size=11264,
                 moe_intermediate_size=1408, n_routed_experts=64,
                 n_shared_experts=2, num_experts_per_tok=6,
                 routed_scaling_factor=2.446, norm_topk_prob=True,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 experts_held=None):
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

    def is_dense(self, i):
        return i < self.first_k_dense_replace

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


def _norm(x, name, cfg):
    """The plain RMS norm over the last axis, its own weight."""
    return layers.rms_norm(
        x, scale=_par(name, [int(x.shape[-1])], cfg,
                      initializer.Constant(1.0)),
        epsilon=cfg.rms_norm_eps)


def latent_attention_mixer(x, cfg, name):
    """`q_proj` gives every head `q_nope | q_rope`; `kv_a_proj` the
    latent `c` and the one rotary key head `k_r`; `c` passes a norm of
    its own and `kv_b_proj` decompresses it into every head's
    `k_nope | v`. `q_rope` and `k_r` are turned by the rotary embedding;
    a head's key is `k_nope | k_r`, `k_r` the same for all heads.
    Causal attention through `scaled_dot_product_attention`, scaled by
    (nope + rope) ** -0.5, values and output of `v_head_dim`; the
    output projection."""
    h, nq = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    rotary = {"rotary_dim": dr, "theta": cfg.rope_theta}
    w_q = _par(name + ".q_proj", [h, nq * (dn + dr)], cfg)
    w_kv_a = _par(name + ".kv_a_proj", [h, rank + dr], cfg)
    w_kv_b = _par(name + ".kv_b_proj", [rank, nq * (dn + dv)], cfg)
    w_o = _par(name + ".o_proj", [nq * dv, h], cfg)

    q_nope, q_rope = _cut(
        layers.reshape(layers.matmul(x, w_q), [0, 0, nq, dn + dr]),
        3, [dn, dr])
    latent, k_rope = _cut(layers.matmul(x, w_kv_a), 2, [rank, dr])
    k_nope, v = _cut(
        layers.reshape(
            layers.matmul(_norm(latent, name + ".kv_a_norm", cfg), w_kv_b),
            [0, 0, nq, dn + dv]),
        3, [dn, dv])
    q = layers.concat(
        [q_nope, layers.rotary_embedding(q_rope, **rotary)], axis=3)
    k_rope = layers.rotary_embedding(
        layers.reshape(k_rope, [0, 0, 1, dr]), **rotary)
    k = layers.concat(
        [k_nope, layers.expand(k_rope, [1, 1, nq, 1])], axis=3)
    ctx = layers.scaled_dot_product_attention(
        *(layers.transpose(t, [0, 2, 1, 3]) for t in (q, k, v)),
        causal=True, sm_scale=1.0 / math.sqrt(dn + dr), is_test=True)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, 0, nq * dv])
    return layers.matmul(ctx, w_o)


def _gated_mlp(x, w_gate_up, w_down):
    return layers.matmul(layers.swiglu(layers.matmul(x, w_gate_up)), w_down)


def dense_layer(x, cfg, name):
    """A leading layer's feed-forward: one SwiGLU of
    `intermediate_size`."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    return _gated_mlp(x, _par(name + ".gate_up", [h, 2 * f], cfg),
                      _par(name + ".down", [f, h], cfg))


def routed_layer(x, cfg, name, counters=None):
    """The shared experts (one SwiGLU of `n_shared_experts` times the
    expert width, added ungated) for every token plus this chip's share
    of the routed experts: sigmoid scores over all experts, the top few
    of score + bias (the bias steers the choice only), renormalised and
    scaled. `counters` collects the layer's (pairs computed, fullest
    expert over the mean, rows made)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    fs = cfg.n_shared_experts * f
    first, count = cfg.experts_held
    w_r = _par(name + ".router", [h, cfg.n_routed_experts], cfg)
    b_r = _par(name + ".router_bias", [cfg.n_routed_experts], cfg,
               initializer.Constant(0.0), trainable=False)
    w_sgu = _par(name + ".shared_gate_up", [h, 2 * fs], cfg)
    w_sd = _par(name + ".shared_down", [fs, h], cfg)
    w_gu = _par(name + ".experts_gate_up", [count, h, 2 * f], cfg)
    w_down = _par(name + ".experts_down", [count, f, h], cfg)
    idx, weight = layers.moe_router(
        x, w_r, b_r, top_k=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor)
    routed, *counted = layers.moe_experts(
        x, idx, weight, w_gu, w_down, held_start=first,
        num_experts=cfg.n_routed_experts, activation="swiglu")
    if counters is not None:
        counters.append(counted)
    return layers.elementwise_add(_gated_mlp(x, w_sgu, w_sd), routed)


def kimi_vl_decoder(ids, cfg, checkpoints_out=None, counters=None):
    """ids [B, S] -> hidden states [B, S, H] after the final norm."""
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=ParamAttr(
                             name="embed", initializer=initializer
                             .TruncatedNormal(0.0, cfg.initializer_range)))
    for i in range(cfg.num_hidden_layers):
        name = "l%d" % i
        feed_forward = (dense_layer, {}) if cfg.is_dense(i) else \
            (routed_layer, {"counters": counters})
        for norm, fn, extra in (
                (".input_norm", latent_attention_mixer, {}),
                (".post_attention_norm",) + feed_forward):
            h = layers.elementwise_add(
                h, fn(_norm(h, name + norm, cfg), cfg, name, **extra))
            if checkpoints_out is not None:
                checkpoints_out.append(h)
    return _norm(h, "final_norm", cfg)


def kimi_vl_loss(cfg, seq_len, checkpoints_out=None):
    """Next-token cross-entropy over feed vars `ids` and `labels`
    [B, seq_len] (the caller shifts: labels[t] is the token after
    ids[t]), the mean over all positions. Returns (loss, counters,
    feeds): `counters` is what `nemotron_h.routed_counters` gives of the
    routed layers, to fetch with the loss where wanted."""
    ids = layers.data(name="ids", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len], dtype="int64")
    per_layer = []
    hidden = kimi_vl_decoder(ids, cfg, checkpoints_out, per_layer)
    loss = next_token_loss(hidden, labels, cfg)
    return loss, routed_counters(per_layer), ["ids", "labels"]
