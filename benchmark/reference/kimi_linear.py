"""Plain reference for a delta-rule / latent-attention decoder with
routed experts: moonshotai's Kimi-Linear-48B-A3B (Kimi Delta Attention,
arXiv:2510.26692, as flash-linear-attention's `KimiDeltaAttention`
builds it; latent attention without position; kimi-vl's sigmoid router).
The forward pass, next-token cross-entropy, its gradients and Adam in
straightforward `jax.numpy`, float32 at `highest` matmul precision. No
kernels, no casts, no chunks: the delta rule is written token by token
(`lax.scan` over positions), attention is the whole masked square, the
routed experts are a dense loop over the experts held with a mask. It
imports nothing of the program under test; the latent attention's
rotary form (a planted fault), the feed-forwards and the router are the
kimi-vl reference's.

Blocked so that 16,384 positions fit beside the parameters, one
gradient and Adam's two moments (16 bytes a parameter), as the qwen3-next
and kimi-vl references are: sequences one at a time, every mixer and
every feed-forward made again in the backward pass, the recurrence
keeping its state every `TIME_BLOCK` positions, attention a query head
and `kimi_vl.QUERY_BLOCK` queries at a time, the feed-forwards and the
loss head `kimi_vl.TOKEN_BLOCK` tokens at a time. `train` takes
`weights` over and hands the first gradient and the starting weights to
the host.

The equations (config keys in brackets). A layer is
`h <- h + Mix(N(h))`, then `h <- h + FFN(N(h))`, with
`N(x) = x rsqrt(mean(x^2) + rms_norm_eps) w`. Layer i (from 1) mixes by
KDA where `linear_attn_config.kda_layers` lists it, by latent attention
where `full_attn_layers` does.

- KDA, `num_heads` heads of `head_dim` d: `q~ = silu(conv(x W_q))`,
  `k~`, `v` alike, each conv causal and depthwise of
  `short_conv_kernel_size` taps without bias; `q = L2(q~_h) d^-0.5`,
  `k = L2(k~_h)` (eps 1e-6); per key channel
  `g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)`, `beta =
  sigmoid(x W_b)_h`. Per head from S_0 = 0 in R^{d x d}:
  `S' = Diag(exp(g_t)) S_{t-1}`; `u_t = beta_t (v_t - S'^T k_t)`;
  `S_t = S' + k_t u_t^T`; `o_t = S_t^T q_t`. Then
  `o <- rmsnorm(o) w_o sigmoid(x W_ga W_gb + b_g)` over each head's d
  and `o_proj`.
- Latent attention (`q_lora_rank` null): kimi-vl's, with neither the
  queries' nor the shared key's 64 rope columns turned where
  `mla_use_nope` (planted fault: turned at `rope_theta`).
- Feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`; the others sigmoid scores over all experts, the
  `num_experts_per_token` largest, renormalised (`moe_renormalize`) and
  times `routed_scaling_factor`, plus `num_shared_experts` ungated
  shared SwiGLU. Given `held = (first, count)`, only those experts' part
  is computed: the partial sum is the layer's output.

Planted faults a test hands over in the configuration: `mla_use_nope`
false (the rotary form), and `kda_decay` "head_mean" (each head's decay
one scalar, the mean of g over its channels: the scalar-gated rule).
Departures from the published model are the configuration file's
`assumed` and `reduced`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common
from . import kimi_vl as mla

#: positions between two kept states of the token-by-token recurrence
TIME_BLOCK = 128


def layer_kind(cfg, i):
    """"kda" or "mla" for layer i, counted from 0."""
    return "kda" if i + 1 in cfg["linear_attn_config"]["kda_layers"] \
        else "mla"


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def param_spec(cfg):
    """[(name, shape, kind, scale)] of the trained parameters from the
    configuration alone. kind `normal` is a truncated normal of
    standard deviation scale; `uniform` is uniform in +-scale; `ones`
    and `zeros` are constants. `A_log` is drawn uniform in +-1 here;
    who makes the weights maps it onto the family's range
    (`spread_decay_init`)."""
    std = float(cfg["initializer_range"])
    v, h, nq = cfg["vocab_size"], cfg["hidden_size"], cfg[
        "num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * f
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    wide, taps = heads * d, lin["short_conv_kernel_size"]
    out = [("embed", (v, h), "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d." % i
        out.append((pre + "input_norm", (h,), "ones", 0.0))
        if layer_kind(cfg, i) == "kda":
            out += [(pre + p, (h, wide), "normal", std)
                    for p in ("q_proj", "k_proj", "v_proj")]
            out += [(pre + p, (wide, taps), "uniform", 0.5)
                    for p in ("q_conv", "k_conv", "v_conv")]
            out += [(pre + "f_a_proj", (h, d), "normal", std),
                    (pre + "f_b_proj", (d, wide), "normal", std),
                    (pre + "b_proj", (h, heads), "normal", std),
                    (pre + "A_log", (heads,), "uniform", 1.0),
                    (pre + "dt_bias", (wide,), "zeros", 0.0),
                    (pre + "g_a_proj", (h, d), "normal", std),
                    (pre + "g_b_proj", (d, wide), "normal", std),
                    (pre + "g_b_bias", (wide,), "zeros", 0.0),
                    (pre + "o_norm", (d,), "ones", 0.0),
                    (pre + "o_proj", (wide, h), "normal", std)]
        else:
            out += [(pre + "q_proj", (h, nq * (dn + dr)), "normal", std),
                    (pre + "kv_a_proj", (h, rank + dr), "normal", std),
                    (pre + "kv_a_norm", (rank,), "ones", 0.0),
                    (pre + "kv_b_proj", (rank, nq * (dn + dv)), "normal",
                     std),
                    (pre + "o_proj", (nq * dv, h), "normal", std)]
        out.append((pre + "post_attention_norm", (h,), "ones", 0.0))
        if is_dense(cfg, i):
            wide_ff = cfg["intermediate_size"]
            out += [(pre + "gate_up", (h, 2 * wide_ff), "normal", std),
                    (pre + "down", (wide_ff, h), "normal", std)]
        else:
            out += [
                (pre + "router", (h, routed), "normal", std),
                (pre + "shared_gate_up", (h, 2 * fs), "normal", std),
                (pre + "shared_down", (fs, h), "normal", std),
                (pre + "experts_gate_up", (held, h, 2 * f), "normal", std),
                (pre + "experts_down", (held, f, h), "normal", std)]
    out += [("final_norm", (h,), "ones", 0.0),
            ("lm_head", (h, v), "normal", std)]
    return out


def spread_decay_init(weights):
    """`A_log` from uniform in +-1 onto the family's initialisation: A
    uniform in [1, 16], A_log its logarithm."""
    return {k: jnp.log(8.5 + 7.5 * v) if k.endswith(".A_log") else v
            for k, v in weights.items()}


def leaves(tree):
    """The model's leaves as published: every parameter is one."""
    return dict(tree)


def kda_rule(q, k, v, g, beta):
    """The delta rule with a decay per key channel, token by token:
    q, k, g [S, H, dk], v [S, H, dv], beta [S, H] -> o [S, H, dv], each
    head's state [dk, dv] from zero, its rows decayed each by its own
    channel's exp(g_t)."""
    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum(
            "hde,hd->he", state, k_t, precision=common.HIGHEST))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t,
                                 precision=common.HIGHEST)

    n, blk = mla._blocks(q.shape[0], TIME_BLOCK)
    _, out = jax.lax.scan(
        jax.checkpoint(lambda st, inp: jax.lax.scan(step, st, inp)),
        jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
        tuple(t.reshape((n, blk) + t.shape[1:]) for t in (q, k, v, g, beta)))
    return out.reshape(v.shape)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def _kda(x, p, cfg, quant):
    """x [S, H] of one sequence. Three parts, each made again in the
    backward pass on its own (the projections and the convolutions, the
    recurrence, the gated norm and the output projection)."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    taps, s = lin["short_conv_kernel_size"], x.shape[0]

    def project(x, p):
        qkv = []
        for name in ("q", "k", "v"):
            y = jnp.pad(mla._mm(x, p[name + "_proj"], quant),
                        ((taps - 1, 0), (0, 0)))
            qkv.append(jax.nn.silu(sum(y[i:i + s] * p[name + "_conv"][:, i]
                                       for i in range(taps))))
        f = mla._mm(mla._mm(x, p["f_a_proj"], quant), p["f_b_proj"], quant)
        gate = mla._mm(mla._mm(x, p["g_a_proj"], quant), p["g_b_proj"],
                       quant) + p["g_b_bias"]
        return (*qkv, f, mla._mm(x, p["b_proj"], quant), gate)

    def recur(q, k, v, f, b, p):
        # q, k and v are matmul operands in the program's chunked form:
        # rounded alike in the control
        q = common.operand(_l2_norm(q.reshape(s, heads, d)) * d ** -0.5,
                           quant)
        k = common.operand(_l2_norm(k.reshape(s, heads, d)), quant)
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            f.reshape(s, heads, d) + p["dt_bias"].reshape(heads, d))
        if cfg.get("kda_decay") == "head_mean":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        return kda_rule(q, k, common.operand(v.reshape(s, heads, d), quant),
                        g, jax.nn.sigmoid(b))

    def out(o, gate, p):
        o = o * jax.lax.rsqrt(jnp.mean(
            jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
        o = o * p["o_norm"] * jax.nn.sigmoid(gate.reshape(s, heads, d))
        return mla._mm(o.reshape(s, heads * d), p["o_proj"], quant)

    q, k, v, f, b, gate = jax.checkpoint(project)(x, p)
    return jax.checkpoint(out)(jax.checkpoint(recur)(q, k, v, f, b, p),
                               gate, p)


def latent_attention(x, p, cfg, quant=None):
    """x [S, H] of one sequence -> [S, H]: kimi-vl's latent attention
    with no rotary (`mla_use_nope`), or with it at `rope_theta`."""
    if not cfg["mla_use_nope"]:
        return mla.latent_attention(x, p, cfg, quant)
    nq = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, s = cfg["kv_lora_rank"], x.shape[0]
    q = mla._mm(x, p["q_proj"], quant).reshape(s, nq, dn + dr)
    kv_a = mla._mm(x, p["kv_a_proj"], quant)
    kv = mla._mm(mla._norm(kv_a[:, :rank], p["kv_a_norm"],
                           cfg["rms_norm_eps"]),
                 p["kv_b_proj"], quant).reshape(s, nq, dn + dv)
    # every head's key: its own 128 and the one shared 64, unturned
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kv_a[:, None, rank:], (s, nq, dr))], axis=-1)
    v = kv[..., dn:]
    n, blk = mla._blocks(s, mla.QUERY_BLOCK)
    cols = jnp.arange(s)

    def square(args):
        j, i = args
        q_ji = jax.lax.dynamic_slice_in_dim(jnp.take(q, j, axis=1),
                                            i * blk, blk)
        scores = jnp.matmul(
            common.operand(q_ji, quant),
            common.operand(jnp.take(k, j, axis=1), quant).T,
            precision=common.HIGHEST) / math.sqrt(dn + dr)
        seen = (i * blk + jnp.arange(blk))[:, None] >= cols[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.matmul(common.operand(probs, quant),
                          common.operand(jnp.take(v, j, axis=1), quant),
                          precision=common.HIGHEST)

    ctx = jax.lax.map(jax.checkpoint(square),
                      (jnp.repeat(jnp.arange(nq), n),
                       jnp.tile(jnp.arange(n), nq)))     # [nq * n, blk, dv]
    ctx = jnp.transpose(ctx.reshape(nq, s, dv), (1, 0, 2)).reshape(s, nq * dv)
    return mla._mm(ctx, p["o_proj"], quant)


def hidden_states(params, ids, *, cfg, quant, held):
    """One sequence through the layers and the final norm; each mixer
    and each feed-forward is made again in the backward pass."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed"], ids, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        mixer = _kda if layer_kind(cfg, i) == "kda" else latent_attention

        def mix(h, p, mixer=mixer):
            return h + mixer(mla._norm(h, p["input_norm"], eps), p, cfg,
                             quant)

        def feed_forward(h, p, dense=is_dense(cfg, i)):
            x = mla._norm(h, p["post_attention_norm"], eps)
            return h + (mla.dense_layer(x, p, cfg, quant) if dense
                        else mla.routed_layer(x, p, cfg, quant, held))

        p = mla._layer_params(params, i)
        h = jax.checkpoint(feed_forward)(jax.checkpoint(mix)(h, p), p)
    return mla._norm(h, params["final_norm"], eps)


def sequence_loss(params, ids, labels, counted, *, n_tokens, cfg, quant,
                  held):
    """One sequence's share of the batch loss: the sum of its counted
    tokens' cross-entropies over the batch's count of them."""
    h = hidden_states(params, ids, cfg=cfg, quant=quant, held=held)

    def tokens(args):
        h_b, labels_b, counted_b = args
        # the head keeps float32 operands in the control too, as the
        # other families' references have it
        logits = mla._mm(h_b, params["lm_head"], None)
        per_tok = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels_b[:, None], axis=1)[:, 0]
        return jnp.sum(per_tok * counted_b)

    n, blk = mla._blocks(h.shape[0], mla.TOKEN_BLOCK)
    return jnp.sum(jax.lax.map(jax.checkpoint(tokens), (
        h.reshape(n, blk, -1), labels.reshape(n, blk),
        counted.reshape(n, blk)))) / n_tokens


def static(cfg):
    """What the traced functions read of a configuration, under the
    kimi-vl reference's names where its functions read them."""
    out = {k: cfg[k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "rope_theta", "routed_scaling_factor", "rms_norm_eps",
        "mla_use_nope")}
    out["linear_attn_config"] = mla._Frozen(
        {k: tuple(v) if isinstance(v, list) else v
         for k, v in cfg["linear_attn_config"].items()})
    out["num_experts_per_tok"] = cfg["num_experts_per_token"]
    out["norm_topk_prob"] = cfg["moe_renormalize"]
    out["kda_decay"] = cfg.get("kda_decay", "per_channel")
    return mla._Frozen(out)


@functools.partial(jax.jit, static_argnames=("cfg", "quant", "held"))
def _batch_value_and_grad(params, ids, labels, counted, *, cfg, quant, held):
    n_tokens = jnp.sum(counted)

    def loss(params):
        one = jax.checkpoint(functools.partial(
            sequence_loss, n_tokens=n_tokens, cfg=cfg,
            quant=common.QUANT[quant], held=held))
        return jnp.sum(jax.lax.map(lambda a: one(params, *a),
                                   (ids, labels, counted)))

    return jax.value_and_grad(loss)(params)


def held_range(cfg):
    """(first expert, how many) held, as the configuration states it."""
    dep = cfg.get("deployment") or {}
    return (int(dep.get("first_expert_held", 0)), int(cfg["num_experts"]))


def loss_and_grad(params, batch, cfg, quant=None, keep=None, held=None):
    """Loss and gradient of one batch, sequence by sequence. `keep`
    plants the half-batch fault: a slice of sequences, of which only
    those count, or for a batch of one document a whole number, of
    which only that many leading positions count; the mean is taken
    over what counts."""
    ids, labels = jnp.asarray(batch["ids"]), jnp.asarray(batch["labels"])
    counted = jnp.ones(ids.shape, jnp.float32)
    if isinstance(keep, slice):
        ids, labels, counted = ids[keep], labels[keep], counted[keep]
    elif keep is not None:
        counted = counted.at[:, int(keep):].set(0.0)
    return _batch_value_and_grad(
        params, ids, labels, counted, cfg=static(cfg), quant=quant,
        held=tuple(held or held_range(cfg)))


def train(weights, batches, cfg, recipe, quant=None, keep=None,
          adam_ahead=0, held=None):
    """Follow `len(batches)` Adam steps from `weights`, which this takes
    over: they are the first step's parameters and are donated to it.
    Returns the losses, the first gradient leaf by leaf (on the host)
    with its norms, and the per-leaf norms of the parameters' change
    over all the steps. `adam_ahead` plants a fault: step t's bias
    corrected as step t + adam_ahead's."""
    import numpy as np

    start = {k: np.asarray(v) for k, v in weights.items()}
    params = weights
    m1, m2 = common.zeros_like_tree(params), common.zeros_like_tree(params)
    losses, grads = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grad = loss_and_grad(params, batch, cfg, quant, keep, held)
        if grads is None:
            grad_norms = common.leaf_norms(leaves(grad))
            grads = {k: np.asarray(v) for k, v in leaves(grad).items()}
        params, m1, m2 = common.adam_step(
            params, grad, m1, m2, jnp.int32(t + adam_ahead),
            lr=float(recipe["learning_rate"]), b1=float(recipe["beta1"]),
            b2=float(recipe["beta2"]), eps=float(recipe["epsilon"]))
        del grad
        losses.append(loss)
    del m1, m2
    change = common.diff_norms(leaves(params), leaves(start))
    return {"losses": [float(x) for x in losses], "grads": grads,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "change_norms": {k: float(v) for k, v in change.items()}}
