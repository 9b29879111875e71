"""The blocks the decoders share (`nemotron_h.py`, `qwen3_next.py`,
`kimi_vl.py`, `laguna.py`, `kimi_linear.py`), and the decoder they make,
in the fluid static graph API: the embedding `embed`; per layer a short
plan of blocks, each `h <- h + Block(N(h))` with `N` a pre-norm of its
own weight; a last norm `final_norm`, an untied head `lm_head` and
next-token cross-entropy. A model file gives its configuration, its own
mixers and its plan. Trained like `models/bert.py`: an optimizer's
`minimize` under `mixed_precision.decorate`, `Executor.run` a step.

A configuration's class states its variant of the shared blocks, each a
fact of the published architecture: `ZERO_CENTRED_NORM` (the norm's
weight is w in `(1 + w)`, from zero, or the factor itself, from one);
where the model routes, `ROUTER_SCORE` ("sigmoid" with an untrained
selection bias `router_bias`, or "softmax"), `EXPERT_ACTIVATION`
("relu2", or "swiglu" whose `*_gate_up` weights hold the gate's and the
up projection's matrices side by side) and `SHARED_EXPERT_GATED` (the
shared expert's output times sigmoid(x `shared_gate`)).

Per-layer recompute: a stack's layers are of several kinds, so it is
unrolled and every block's output is a checkpoint
(`*_loss(..., checkpoints_out=[])` hands them to `RecomputeOptimizer`);
a `layers.Scan` covers equal layers only.

Expert parallelism: `experts_held=(first, count)` builds the chip's
share of every routed layer (`parallel.planner.experts_held`): the
router scores all `num_experts`, the layer computes its own experts'
part for the tokens routed to them and the shared expert, and that
partial sum is the layer's output. Nothing stands in for the other
chips.

The blocks create their parameters and append their ops in a fixed
order: the temporaries' names follow from it, and so do the step's
program and its compiled-cache entry.
"""
from __future__ import annotations

import numpy as np

from ..fluid import initializer, layers
from ..fluid.param_attr import ParamAttr

_ACTIVATIONS = {"relu2": layers.relu2, "swiglu": layers.swiglu}


def param(name, shape, cfg, init=None, trainable=True):
    """The float32 parameter `name`: `init` an initializer, a number (a
    constant) or None (the configuration's truncated normal)."""
    if init is None:
        init = initializer.TruncatedNormal(0.0, cfg.initializer_range)
    elif not isinstance(init, initializer.Initializer):
        init = initializer.Constant(float(init))
    return layers.create_parameter(
        shape=shape, dtype="float32", name=name,
        attr=ParamAttr(name=name, initializer=init, trainable=trainable))


def a_log_init(heads):
    """A spread over [1, 16], A_log its logarithm: the range of the
    published initialisations of Mamba-2 and Kimi Delta Attention."""
    return initializer.NumpyArrayInitializer(
        np.log(np.linspace(1.0, 16.0, heads)).astype("float32"))


def cut(x, axis, sizes):
    """`x` cut along `axis` into consecutive parts of `sizes`."""
    edges = np.cumsum([0] + list(sizes))
    return [layers.slice(x, axes=[axis], starts=[int(lo)], ends=[int(hi)])
            for lo, hi in zip(edges, edges[1:])]


def rms_norm_block(x, name, cfg):
    """The RMS norm over the last axis with its own weight `name`."""
    zero = cfg.ZERO_CENTRED_NORM
    return layers.rms_norm(
        x, scale=param(name, [int(x.shape[-1])], cfg, 0.0 if zero else 1.0),
        epsilon=cfg.rms_norm_eps, zero_centered=zero)


def _feed_forward(x, w_up, w_down, activation):
    """x W_up, its `activation`, W_down."""
    return layers.matmul(_ACTIVATIONS[activation](layers.matmul(x, w_up)),
                         w_down)


def dense_layer(x, cfg, name):
    """A dense layer's feed-forward: one SwiGLU of
    `intermediate_size`."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    return _feed_forward(x, param(name + ".gate_up", [h, 2 * f], cfg),
                         param(name + ".down", [f, h], cfg), "swiglu")


def routed_layer(x, cfg, name, counters=None):
    """This chip's share of the routed experts plus the shared expert
    (of `shared_expert_intermediate_size`) for every token: the router's
    scores over all `num_experts`, the top `num_experts_per_tok` (of
    score + `router_bias` where it has one: the bias steers the choice
    only), renormalised where `norm_topk_prob` and times
    `routed_scaling_factor`. `counters` collects the layer's (pairs
    computed, fullest expert over the mean, rows made)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    fs, act = cfg.shared_expert_intermediate_size, cfg.EXPERT_ACTIVATION
    first, count = cfg.experts_held
    up, wide = ("_gate_up", 2) if act == "swiglu" else ("_up", 1)
    w_r = param(name + ".router", [h, cfg.num_experts], cfg)
    bias = param(name + ".router_bias", [cfg.num_experts], cfg, 0.0,
                 trainable=False) if cfg.ROUTER_SCORE == "sigmoid" else None
    w_su = param(name + ".shared" + up, [h, wide * fs], cfg)
    w_sd = param(name + ".shared_down", [fs, h], cfg)
    w_sg = param(name + ".shared_gate", [h, 1], cfg) \
        if cfg.SHARED_EXPERT_GATED else None
    w_up = param(name + ".experts" + up, [count, h, wide * f], cfg)
    w_down = param(name + ".experts_down", [count, f, h], cfg)
    idx, weight = layers.moe_router(
        x, w_r, bias, top_k=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        score_function=cfg.ROUTER_SCORE)
    routed, *counted = layers.moe_experts(
        x, idx, weight, w_up, w_down, held_start=first,
        num_experts=cfg.num_experts, activation=act)
    if counters is not None:
        counters.append(counted)
    shared = _feed_forward(x, w_su, w_sd, act)
    if w_sg is not None:
        shared = layers.elementwise_mul(
            shared, layers.sigmoid(layers.matmul(x, w_sg)))
    return layers.elementwise_add(shared, routed)


def decoder(ids, cfg, plan, checkpoints_out=None, counters=None):
    """ids [B, S] -> hidden states [B, S, H] after the final norm.
    `plan[i]` is layer i's blocks, each (norm suffix, block):
    `h <- h + block(N(h), cfg, "l<i>")`, N of the weight
    "l<i><suffix>"; every such h goes to `checkpoints_out`, and a
    `routed_layer`'s counters to `counters`."""
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=ParamAttr(
                             name="embed", initializer=initializer
                             .TruncatedNormal(0.0, cfg.initializer_range)))
    for i, blocks in enumerate(plan):
        name = "l%d" % i
        for norm, block in blocks:
            extra = {"counters": counters} if block is routed_layer else {}
            h = layers.elementwise_add(h, block(
                rms_norm_block(h, name + norm, cfg), cfg, name, **extra))
            if checkpoints_out is not None:
                checkpoints_out.append(h)
    return rms_norm_block(h, "final_norm", cfg)


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


def causal_lm_loss(cfg, seq_len, plan, checkpoints_out=None):
    """Next-token cross-entropy of the decoder `plan` lays out, over
    feed vars `ids` and `labels` [B, seq_len] (the caller shifts:
    labels[t] is the token after ids[t]), the mean over all positions.
    Returns (loss, counters, feeds): `counters` is what
    `routed_counters` gives of the routed layers, to fetch with the loss
    where wanted."""
    ids = layers.data(name="ids", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len], dtype="int64")
    per_layer = []
    hidden = decoder(ids, cfg, plan, checkpoints_out, per_layer)
    loss = next_token_loss(hidden, labels, cfg)
    return loss, routed_counters(per_layer), ["ids", "labels"]
