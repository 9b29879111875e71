"""A hybrid state-space / routed-expert decoder (the Nemotron-H family:
NVIDIA-Nemotron-3-Nano-30B-A3B and its siblings) in the fluid static
graph API: a stack of blocks `h <- h + Mixer_i(RMSNorm(h))`, each mixer
one of Mamba-2 (`M`), grouped-query causal attention (`*`) or routed
experts with a shared expert (`E`), as `hybrid_override_pattern` spells
them; one RMS norm after the last block, an untied head and next-token
cross-entropy.

Its own: the configuration, the Mamba-2 and attention mixers and the
plan of one block a layer. From `models/decoder.py`: the norm, the
routed layer (sigmoid router with a selection bias, `relu2` experts, an
ungated shared expert), the skeleton and the loss, with what it says of
recompute and expert parallelism.
"""
from __future__ import annotations

import math

from ..fluid import initializer, layers
from .decoder import a_log_init, causal_lm_loss, cut, param, routed_layer

KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


class NemotronHConfig:
    """The published `config.json` keys the model reads (defaults:
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16). `experts_held` is (first
    expert, how many) of the `n_routed_experts` this chip holds; None
    holds them all."""

    ZERO_CENTRED_NORM = False
    ROUTER_SCORE, EXPERT_ACTIVATION = "sigmoid", "relu2"
    SHARED_EXPERT_GATED = False

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*"
                 "EMEMEM*EMEMEMEM*EMEMEMEME",
                 mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
                 n_groups=8, conv_kernel=4, chunk_size=128,
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, n_routed_experts=128, num_experts_per_tok=6,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 layer_norm_epsilon=1e-5, initializer_range=0.02,
                 experts_held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.layer_norm_epsilon = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, n_routed_experts))
        unknown = set(hybrid_override_pattern) - set(KINDS)
        if unknown:
            raise ValueError("hybrid_override_pattern holds %r; known: %r"
                             % (sorted(unknown), sorted(KINDS)))

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    # the names `models/decoder.py`'s blocks read
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)
    num_experts = property(lambda self: self.n_routed_experts)
    shared_expert_intermediate_size = property(
        lambda self: self.moe_shared_expert_intermediate_size)

    @staticmethod
    def tiny(**over):
        """Every kind of layer at widths a CPU test affords."""
        kw = dict(vocab_size=96, hidden_size=32,
                  hybrid_override_pattern="ME*E", mamba_num_heads=4,
                  mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                  conv_kernel=4, chunk_size=8, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=8, n_routed_experts=8,
                  num_experts_per_tok=3, moe_intermediate_size=24,
                  moe_shared_expert_intermediate_size=40)
        kw.update(over)
        return NemotronHConfig(**kw)


def mamba2_mixer(x, cfg, name):
    """[z | xBC | dt] = x W_in; xBC through the causal depthwise
    convolution and silu; the chunked state-space scan; the gate
    (y * silu(z)) and then the norm over each of `n_groups` parts of
    it; the output projection."""
    h, inner = cfg.hidden_size, cfg.mamba_inner
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    conv_dim = inner + 2 * g * n
    w_in = param(name + ".in_proj", [h, inner + conv_dim + heads], cfg)
    conv_w = param(name + ".conv.w", [conv_dim, cfg.conv_kernel], cfg,
                   initializer.Uniform(-0.5, 0.5))
    conv_b = param(name + ".conv.b", [conv_dim], cfg, 0.0)
    dt_bias = param(name + ".dt_bias", [heads], cfg, -4.0)
    a_log = param(name + ".A_log", [heads], cfg, a_log_init(heads))
    d = param(name + ".D", [heads], cfg, 1.0)
    norm_w = param(name + ".mixer_norm", [inner], cfg, 1.0)
    w_out = param(name + ".out_proj", [inner, h], cfg)

    z, xbc, dt = cut(layers.matmul(x, w_in), 2, [inner, conv_dim, heads])
    xbc = layers.causal_conv1d(xbc, conv_w, conv_b, activation="silu")
    xs, bm, cm = cut(xbc, 2, [inner, g * n, g * n])
    y = layers.ssd_chunk_scan(
        layers.reshape(xs, [0, 0, heads, p]), dt, dt_bias, a_log,
        layers.reshape(bm, [0, 0, g, n]), layers.reshape(cm, [0, 0, g, n]),
        d, chunk_size=cfg.chunk_size)
    y = layers.reshape(y, [0, 0, inner])
    y = layers.rms_norm(layers.elementwise_mul(y, layers.silu(z)),
                        scale=norm_w, epsilon=cfg.rms_norm_eps, groups=g)
    return layers.matmul(y, w_out)


def attention_mixer(x, cfg, name):
    """Causal grouped-query attention without bias and without a
    rotary embedding (the family's attention layers apply none: the
    Mamba layers carry position). K and V keep their own few heads all
    the way into the kernel."""
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads

    def heads(proj, n):
        t = layers.matmul(x, param(name + proj, [h, n * d], cfg))
        return layers.transpose(layers.reshape(t, [0, 0, n, d]),
                                [0, 2, 1, 3])

    q, k, v = heads(".q_proj", nq), heads(".k_proj", nkv), \
        heads(".v_proj", nkv)
    w_o = param(name + ".o_proj", [nq * d, h], cfg)
    ctx = layers.scaled_dot_product_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d), is_test=True)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, nq * d])
    return layers.matmul(ctx, w_o)


_BLOCKS = {"M": mamba2_mixer, "*": attention_mixer, "E": routed_layer}


def plan(cfg):
    """One block a layer, of the kind `hybrid_override_pattern` spells,
    behind the norm `.norm`."""
    return [[(".norm", _BLOCKS[kind])] for kind in cfg.hybrid_override_pattern]


def nemotron_h_loss(cfg, seq_len, checkpoints_out=None):
    """`decoder.causal_lm_loss` of the stack `plan` lays out."""
    return causal_lm_loss(cfg, seq_len, plan(cfg), checkpoints_out)
