"""A hybrid state-space / routed-expert decoder (the Nemotron-H family:
NVIDIA-Nemotron-3-Nano-30B-A3B and its siblings) in the fluid static
graph API: a stack of blocks `h <- h + Mixer_i(RMSNorm(h))`, each mixer
one of Mamba-2 (`M`), grouped-query causal attention (`*`) or routed
experts with a shared expert (`E`), as `hybrid_override_pattern` spells
them; one RMS norm after the last block, an untied head and next-token
cross-entropy. Trained like `models/bert.py`: an optimizer's
`minimize` under `mixed_precision.decorate`, `Executor.run` a step.

Per-layer recompute: the blocks are of three kinds, so the stack is
unrolled and every block's output is a checkpoint
(`nemotron_h_loss(..., checkpoints_out=[])` hands them to
`RecomputeOptimizer`); a `layers.Scan` covers equal layers only.

Expert parallelism: `experts_held=(first, count)` builds the chip's
share of every routed layer (`parallel.planner.experts_held`): the
router scores all `n_routed_experts`, the layer computes its own
experts' part for the tokens routed to them and the shared expert, and
that partial sum is the block's output. Nothing stands in for the
other chips.
"""
from __future__ import annotations

import math

import numpy as np

from ..fluid import initializer, layers
from ..fluid.param_attr import ParamAttr

KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


class NemotronHConfig:
    """The published `config.json` keys the model reads (defaults:
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16). `experts_held` is (first
    expert, how many) of the `n_routed_experts` this chip holds; None
    holds them all."""

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


def _par(name, shape, cfg, init=None, trainable=True):
    init = init or initializer.TruncatedNormal(0.0, cfg.initializer_range)
    return layers.create_parameter(
        shape=shape, dtype="float32", name=name,
        attr=ParamAttr(name=name, initializer=init, trainable=trainable))


def _const(value):
    return initializer.Constant(float(value))


def _a_log_init(heads):
    """A = -(1 .. 16) spread over the heads, the published
    initialisation's range."""
    return initializer.NumpyArrayInitializer(
        np.log(np.linspace(1.0, 16.0, heads)).astype("float32"))


def mamba2_mixer(x, cfg, name):
    """[z | xBC | dt] = x W_in; xBC through the causal depthwise
    convolution and silu; the chunked state-space scan; the gate
    (y * silu(z)) and then the norm over each of `n_groups` parts of
    it; the output projection."""
    h, inner = cfg.hidden_size, cfg.mamba_inner
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    conv_dim = inner + 2 * g * n
    w_in = _par(name + ".in_proj", [h, inner + conv_dim + heads], cfg)
    conv_w = _par(name + ".conv.w", [conv_dim, cfg.conv_kernel], cfg,
                  initializer.Uniform(-0.5, 0.5))
    conv_b = _par(name + ".conv.b", [conv_dim], cfg, _const(0.0))
    dt_bias = _par(name + ".dt_bias", [heads], cfg, _const(-4.0))
    a_log = _par(name + ".A_log", [heads], cfg, _a_log_init(heads))
    d = _par(name + ".D", [heads], cfg, _const(1.0))
    norm_w = _par(name + ".mixer_norm", [inner], cfg, _const(1.0))
    w_out = _par(name + ".out_proj", [inner, h], cfg)

    zxbcdt = layers.matmul(x, w_in)
    cuts = [0, inner, inner + conv_dim, inner + conv_dim + heads]
    z, xbc, dt = (layers.slice(zxbcdt, axes=[2], starts=[lo], ends=[hi])
                  for lo, hi in zip(cuts, cuts[1:]))
    xbc = layers.causal_conv1d(xbc, conv_w, conv_b, activation="silu")
    cuts = [0, inner, inner + g * n, conv_dim]
    xs, bm, cm = (layers.slice(xbc, axes=[2], starts=[lo], ends=[hi])
                  for lo, hi in zip(cuts, cuts[1:]))
    y = layers.ssd_chunk_scan(
        layers.reshape(xs, [0, 0, heads, p]), dt, dt_bias, a_log,
        layers.reshape(bm, [0, 0, g, n]), layers.reshape(cm, [0, 0, g, n]),
        d, chunk_size=cfg.chunk_size)
    y = layers.reshape(y, [0, 0, inner])
    y = layers.rms_norm(layers.elementwise_mul(y, layers.silu(z)),
                        scale=norm_w, epsilon=cfg.layer_norm_epsilon,
                        groups=g)
    return layers.matmul(y, w_out)


def attention_mixer(x, cfg, name):
    """Causal grouped-query attention without bias and without a
    rotary embedding (the family's attention layers apply none: the
    Mamba layers carry position). K and V keep their own few heads all
    the way into the kernel."""
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads

    def heads(proj, n):
        t = layers.matmul(x, _par(name + proj, [h, n * d], cfg))
        return layers.transpose(layers.reshape(t, [0, 0, n, d]),
                                [0, 2, 1, 3])

    q, k, v = heads(".q_proj", nq), heads(".k_proj", nkv), \
        heads(".v_proj", nkv)
    w_o = _par(name + ".o_proj", [nq * d, h], cfg)
    ctx = layers.scaled_dot_product_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d), is_test=True)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, nq * d])
    return layers.matmul(ctx, w_o)


def experts_mixer(x, cfg, name, counters=None):
    """The shared expert for every token plus this chip's share of the
    routed experts. `counters` collects the layer's (pairs computed,
    fullest expert over the mean, rows made)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    fs = cfg.moe_shared_expert_intermediate_size
    first, count = cfg.experts_held
    w_r = _par(name + ".router", [h, cfg.n_routed_experts], cfg)
    b_r = _par(name + ".router_bias", [cfg.n_routed_experts], cfg,
               _const(0.0), trainable=False)
    w_su = _par(name + ".shared_up", [h, fs], cfg)
    w_sd = _par(name + ".shared_down", [fs, h], cfg)
    w_up = _par(name + ".experts_up", [count, h, f], cfg)
    w_down = _par(name + ".experts_down", [count, f, h], cfg)
    idx, weight = layers.moe_router(
        x, w_r, b_r, top_k=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor)
    routed, *counted = layers.moe_experts(
        x, idx, weight, w_up, w_down, held_start=first,
        num_experts=cfg.n_routed_experts, activation="relu2")
    if counters is not None:
        counters.append(counted)
    shared = layers.matmul(layers.relu2(layers.matmul(x, w_su)), w_sd)
    return layers.elementwise_add(shared, routed)


_MIXERS = {"M": mamba2_mixer, "*": attention_mixer, "E": experts_mixer}


def nemotron_h_decoder(ids, cfg, checkpoints_out=None, counters=None):
    """ids [B, S] -> hidden states [B, S, H] after the final norm."""
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=ParamAttr(
                             name="embed", initializer=initializer
                             .TruncatedNormal(0.0, cfg.initializer_range)))
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        name = "l%d" % i
        normed = layers.rms_norm(
            h, scale=_par(name + ".norm", [cfg.hidden_size], cfg,
                          _const(1.0)), epsilon=cfg.layer_norm_epsilon)
        extra = {"counters": counters} if kind == "E" else {}
        h = layers.elementwise_add(h, _MIXERS[kind](normed, cfg, name,
                                                    **extra))
        if checkpoints_out is not None:
            checkpoints_out.append(h)
    return layers.rms_norm(
        h, scale=_par("final_norm", [cfg.hidden_size], cfg, _const(1.0)),
        epsilon=cfg.layer_norm_epsilon)


def next_token_loss(hidden, labels, cfg):
    """The mean over all positions of the next-token cross-entropy of
    `hidden` [B, S, H] under an untied head `lm_head` [H, vocab]."""
    per_tok = layers.loss.fused_linear_softmax_xent(
        layers.reshape(hidden, [-1, cfg.hidden_size]),
        layers.reshape(labels, [-1, 1]), cfg.vocab_size,
        param_attr=ParamAttr(
            name="lm_head", initializer=initializer.TruncatedNormal(
                0.0, cfg.initializer_range)),
        bias_attr=False)
    return layers.mean(per_tok)


def routed_counters(per_layer):
    """{"moe.held_pairs": var, "moe.load_max_over_mean": var,
    "moe.rows_made": var} from every routed layer's (pairs computed,
    fullest expert over the mean, rows made): the pairs summed, the
    fullest-over-mean at its worst, the rows of sorted pairs summed
    (whole row blocks, so no fewer than the pairs); empty without a
    routed layer."""
    if not per_layer:
        return {}
    pairs, load, made = per_layer[0]
    for p, ld, m in per_layer[1:]:
        pairs = layers.elementwise_add(pairs, p)
        load = layers.elementwise_max(load, ld)
        made = layers.elementwise_add(made, m)
    return {"moe.held_pairs": pairs, "moe.load_max_over_mean": load,
            "moe.rows_made": made}


def nemotron_h_loss(cfg, seq_len, checkpoints_out=None):
    """Next-token cross-entropy over feed vars `ids` and `labels`
    [B, seq_len] (the caller shifts: labels[t] is the token after
    ids[t]), the mean over all positions. Returns (loss, counters,
    feeds): `counters` is what `routed_counters` gives of the routed
    layers, to fetch with the loss where wanted."""
    ids = layers.data(name="ids", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len], dtype="int64")
    per_layer = []
    hidden = nemotron_h_decoder(ids, cfg, checkpoints_out, per_layer)
    loss = next_token_loss(hidden, labels, cfg)
    return loss, routed_counters(per_layer), ["ids", "labels"]
