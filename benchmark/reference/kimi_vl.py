"""Plain reference for a latent-attention / routed-expert decoder: the
language model of Kimi-VL-A3B-Instruct (the published implementation is
`modeling_deepseek.py` of DeepSeek-V3, which the model's repository
carries: multi-head latent attention with `q_lora_rank` null, a sigmoid
router with a selection bias). The forward pass, next-token
cross-entropy, its gradients and Adam in straightforward `jax.numpy`,
float32 at `highest` matmul precision. No kernels, no casts: attention
is the masked square, the routed experts are a dense loop over the
experts held with a mask. It imports nothing of the program under test.

Blocked so that 16,384 positions fit beside the parameters, one
gradient and Adam's two moments (16 bytes a parameter): the loss is a
sum over sequences taken one at a time (`lax.map`), every mixer and
every feed-forward is made again in the backward pass, attention goes a
query head and `QUERY_BLOCK` queries at a time, the feed-forwards and
the loss head `TOKEN_BLOCK` tokens at a time (no token reads another
there). `train` takes `weights` over (they are donated to the first
Adam step) and hands the first gradient and the starting weights to
the host.

The equations (config keys in brackets). A layer is
`h <- h + Attn(N(h))`, then `h <- h + FFN(N(h))`, with
`N(x) = x rsqrt(mean(x^2) + rms_norm_eps) w`.

- Attention, `num_attention_heads` heads: `q = x W_q`, a head
  `q_nope [qk_nope_head_dim] | q_rope [qk_rope_head_dim]`;
  `[c | k_r] = x W_kv_a`, `c [kv_lora_rank]`, `k_r [qk_rope_head_dim]`;
  `c <- N(c)` with a weight of its own; `[k_nope | v] = c W_kv_b`, a
  head `k_nope [qk_nope_head_dim] | v [v_head_dim]`. `q_rope` and `k_r`
  are turned by the rotary embedding (base `rope_theta`, positions
  0 .. S-1), `k_r` being ONE head that every query head reads.
  `score_h = (q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(qk_nope_head_dim
  + qk_rope_head_dim)`, causal softmax, `o_h = P_h v_h`,
  `out = concat(o) W_o`. No bias.
- The first `first_k_dense_replace` layers' feed-forward:
  `W_down (silu(W_gate x) W_up x)` at `intermediate_size`.
- The others: `s = sigmoid(x W_r)` over all experts; the
  `num_experts_per_tok` largest of `s + b`; weights
  `s_k / (sum_k s_k + 1e-20) x routed_scaling_factor`;
  `y = sum_k w_k E_k(x) + E_shared(x)`, `E` a SwiGLU of
  `moe_intermediate_size`, `E_shared` one SwiGLU of `n_shared_experts`
  times that, added ungated. Given `held = (first, count)`, only those
  experts' part and the shared experts are computed: the partial sum is
  the layer's output.

Departures from the published model (the configuration file's
`assumed` and `reduced` say why): the rotary pairs are (i, i + d/2)
(rotate-half) where the published code pairs (2i, 2i + 1), the same
function of seeded weights under one fixed permutation of the rotary
columns of `W_q` and `W_kv_a`; the selection bias `b` is held at zero,
so the choice is of `s`; no auxiliary loss; keys and values are
decompressed (no absorbed form); text only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common

#: queries a block of the masked square
QUERY_BLOCK = 2048
#: tokens a block of a feed-forward and of the loss head
TOKEN_BLOCK = 2048


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def param_spec(cfg):
    """[(name, shape, kind, scale)] of the trained parameters from the
    configuration alone. kind `normal` is a truncated normal of
    standard deviation scale; `ones` is a constant."""
    std = float(cfg["initializer_range"])
    v, h, nq = cfg["vocab_size"], cfg["hidden_size"], cfg[
        "num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    held, routed = cfg["n_routed_experts"], cfg["published"][
        "n_routed_experts"]
    out = [("embed", (v, h), "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d." % i
        out += [(pre + "input_norm", (h,), "ones", 0.0),
                (pre + "q_proj", (h, nq * (dn + dr)), "normal", std),
                (pre + "kv_a_proj", (h, rank + dr), "normal", std),
                (pre + "kv_a_norm", (rank,), "ones", 0.0),
                (pre + "kv_b_proj", (rank, nq * (dn + dv)), "normal", std),
                (pre + "o_proj", (nq * dv, h), "normal", std),
                (pre + "post_attention_norm", (h,), "ones", 0.0)]
        if is_dense(cfg, i):
            wide = cfg["intermediate_size"]
            out += [(pre + "gate_up", (h, 2 * wide), "normal", std),
                    (pre + "down", (wide, h), "normal", std)]
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


def leaves(tree):
    """The model's leaves as published: every parameter is one."""
    return dict(tree)


def _blocks(n, block):
    """`n` in blocks of `block`, or whole where that does not divide."""
    return (n // block, block) if n % block == 0 else (1, n)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def _mm(x, w, quant):
    return jnp.matmul(common.operand(x, quant), common.operand(w, quant),
                      precision=common.HIGHEST)


def _gated_mlp(x, w_gate_up, w_down, quant):
    gate, up = jnp.split(_mm(x, w_gate_up, quant), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down, quant)


def rotary(x, theta):
    """Rotate-half rotary embedding over the whole last axis of
    x [S, heads, D], positions 0 .. S-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def latent_attention(x, p, cfg, quant=None):
    """x [S, H] of one sequence -> [S, H]."""
    nq = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    s = x.shape[0]
    q = _mm(x, p["q_proj"], quant).reshape(s, nq, dn + dr)
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], theta)
    kv_a = _mm(x, p["kv_a_proj"], quant)
    latent, k_rope = kv_a[:, :rank], rotary(kv_a[:, None, rank:], theta)
    kv = _mm(_norm(latent, p["kv_a_norm"], cfg["rms_norm_eps"]),
             p["kv_b_proj"], quant).reshape(s, nq, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    n, blk = _blocks(s, QUERY_BLOCK)
    cols = jnp.arange(s)

    def square(args):
        # every query head reads its own k_nope and the one k_rope
        j, i = args
        rows = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=i * blk, slice_size=blk)
        scores = (
            jnp.matmul(common.operand(rows(jnp.take(q_nope, j, axis=1)),
                                      quant),
                       common.operand(jnp.take(k_nope, j, axis=1), quant).T,
                       precision=common.HIGHEST)
            + jnp.matmul(common.operand(rows(jnp.take(q_rope, j, axis=1)),
                                        quant),
                         common.operand(k_rope[:, 0], quant).T,
                         precision=common.HIGHEST)) / math.sqrt(dn + dr)
        seen = (i * blk + jnp.arange(blk))[:, None] >= cols[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.matmul(common.operand(probs, quant),
                          common.operand(jnp.take(v, j, axis=1), quant),
                          precision=common.HIGHEST)

    ctx = jax.lax.map(jax.checkpoint(square),
                      (jnp.repeat(jnp.arange(nq), n),
                       jnp.tile(jnp.arange(n), nq)))     # [nq * n, blk, dv]
    ctx = jnp.transpose(ctx.reshape(nq, s, dv), (1, 0, 2)).reshape(s, nq * dv)
    return _mm(ctx, p["o_proj"], quant)


def routing(x, w_router, cfg):
    """(expert numbers [S, k], weights [S, k]) of every token: sigmoid
    scores over ALL experts, never rounded (the program keeps them
    float32); the selection bias is held at zero."""
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=common.HIGHEST))
    w, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def _token_blocks(fn, x):
    n, blk = _blocks(x.shape[0], TOKEN_BLOCK)
    return jax.lax.map(jax.checkpoint(fn),
                       x.reshape(n, blk, -1)).reshape(x.shape)


def dense_layer(x, p, cfg, quant=None):
    return _token_blocks(
        lambda x: _gated_mlp(x, p["gate_up"], p["down"], quant), x)


def routed_layer(x, p, cfg, quant=None, held=None):
    """The shared experts for every token plus the part the experts
    `held = (first, count)` give (all of them where None)."""
    first, count = held or (0, p["experts_gate_up"].shape[0])

    def tokens(x):
        idx, w = routing(x, p["router"], cfg)
        out = _gated_mlp(x, p["shared_gate_up"], p["shared_down"], quant)
        for e in range(count):
            w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
            out = out + w_e[:, None] * _gated_mlp(
                x, p["experts_gate_up"][e], p["experts_down"][e], quant)
        return out

    return _token_blocks(tokens, x)


def _layer_params(params, i):
    pre = "l%d." % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(params, ids, *, cfg, quant, held):
    """One sequence through the layers and the final norm; each mixer
    and each feed-forward is made again in the backward pass."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed"], ids, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        def mix(h, p):
            return h + latent_attention(_norm(h, p["input_norm"], eps), p,
                                        cfg, quant)

        def feed_forward(h, p, dense=is_dense(cfg, i)):
            x = _norm(h, p["post_attention_norm"], eps)
            return h + (dense_layer(x, p, cfg, quant) if dense
                        else routed_layer(x, p, cfg, quant, held))

        p = _layer_params(params, i)
        h = jax.checkpoint(feed_forward)(jax.checkpoint(mix)(h, p), p)
    return _norm(h, params["final_norm"], eps)


def sequence_loss(params, ids, labels, counted, *, n_tokens, cfg, quant,
                  held):
    """One sequence's share of the batch loss: the sum of its counted
    tokens' cross-entropies over the batch's count of them."""
    h = hidden_states(params, ids, cfg=cfg, quant=quant, held=held)

    def tokens(args):
        h_b, labels_b, counted_b = args
        # the head keeps float32 operands in the control too, as the
        # other families' references have it
        logits = _mm(h_b, params["lm_head"], None)
        per_tok = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels_b[:, None], axis=1)[:, 0]
        return jnp.sum(per_tok * counted_b)

    n, blk = _blocks(h.shape[0], TOKEN_BLOCK)
    return jnp.sum(jax.lax.map(jax.checkpoint(tokens), (
        h.reshape(n, blk, -1), labels.reshape(n, blk),
        counted.reshape(n, blk)))) / n_tokens


class _Frozen(dict):
    """A configuration as a static argument of `jit`."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def static(cfg):
    keys = ("num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps")
    return _Frozen({k: cfg[k] for k in keys if k in cfg})


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
    return (int(dep.get("first_expert_held", 0)),
            int(cfg["n_routed_experts"]))


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
