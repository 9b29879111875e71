"""Plain reference for a gated-delta-rule / gated-attention /
routed-expert decoder of the Qwen3-Next family
(Qwen3-Next-80B-A3B-Instruct; the published implementation is
`transformers`' `modeling_qwen3_next.py`): the forward pass, next-token
cross-entropy, its gradients and Adam in straightforward `jax.numpy`,
float32 at `highest` matmul precision. No kernels, no casts, no chunks:
the delta rule is written token by token (`lax.scan` over positions),
attention is the whole masked square, the routed experts are a dense
loop over the experts held with a mask. It imports nothing of the
program under test.

Blocked so that 16,384 positions fit beside the parameters, one
gradient and Adam's two moments (16 bytes a parameter): the loss is a
sum over sequences taken one at a time (`lax.map`), every mixer and
every routed layer is made again in the backward pass, the recurrence
keeps its state every `TIME_BLOCK` positions and makes the steps
between again, attention goes a query head and `QUERY_BLOCK` queries at
a time, the routed layer and the loss head `TOKEN_BLOCK` tokens at a
time (no token reads another there). `train` takes `weights` over (they
are donated to the first Adam step) and hands the first gradient and
the starting weights to the host.

The equations (config keys in brackets). A layer is
`h <- h + Mixer(N(h))`, then `h <- h + MoE(N(h))`, with
`N(x) = x rsqrt(mean(x^2) + eps) (1 + w)`; layer i is gated attention
where `(i + 1) % full_attention_interval == 0`, else Gated DeltaNet.

- Gated DeltaNet: `in_proj_qkvz(x)` gives each of `linear_num_key_heads`
  key heads q, k [`linear_key_head_dim`] and, for its
  `linear_num_value_heads / linear_num_key_heads` value heads, v and z
  [`linear_value_head_dim`] each; `in_proj_ba(x)` their b and a. q, k, v
  pass a causal depthwise convolution of `linear_conv_kernel_dim` taps
  (no bias) and silu. `beta = sigmoid(b)`,
  `g = -exp(A_log) softplus(a + dt_bias)`. q and k are L2-normalised
  (eps 1e-6), q scaled by dk^-0.5. Per value head, from S_0 = 0 in
  R^{dk x dv}: `S' = exp(g_t) S_{t-1}`; `u_t = beta_t (v_t - S'^T k_t)`;
  `S_t = S' + k_t u_t^T`; `o_t = S_t^T q_t`. Then
  `o <- rmsnorm(o) w_n silu(z)` over each head's dv (a plain weight)
  and `out_proj`.
- Gated attention: `q_proj(x)` gives each of `num_attention_heads` a
  query and a gate [`head_dim`]; `num_key_value_heads` key/value heads;
  N over the head axis of q and k; the rotary embedding (rotate-half,
  base `rope_theta`) on the first `partial_rotary_factor` of the head;
  causal softmax attention scaled head_dim^-0.5;
  `o <- o sigmoid(gate)`; `o_proj`.
- Routed layer: `p = softmax(x W_r)` over all experts; the
  `num_experts_per_tok` largest; weights `p_k / sum_k p_k`; expert
  `e(x) = W_down (silu(W_gate x) W_up x)`; a shared expert of the same
  form times `sigmoid(x w_s)`. Given `held = (first, count)`, only those
  experts' part is computed: the partial sum is the layer's output.

Departures from the published model are the configuration file's
`assumed` and `reduced`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common


def is_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def param_spec(cfg):
    """[(name, shape, kind, scale)] of the parameters from the
    configuration alone. kind `normal` is a truncated normal of
    standard deviation scale; `uniform` is uniform in +-scale; `ones`
    and `zeros` are constants. `dt_bias` and `A_log` are drawn uniform
    in +-1 here; who makes the weights maps them onto the published
    initialisation's ranges (`spread_decay_init`)."""
    std = float(cfg["initializer_range"])
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, fs = (cfg["moe_intermediate_size"],
             cfg["shared_expert_intermediate_size"])
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    out = [("embed", (v, h), "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d." % i
        out.append((pre + "input_norm", (h,), "zeros", 0.0))
        if is_attention(cfg, i):
            out += [(pre + "q_proj", (h, nq * 2 * d), "normal", std),
                    (pre + "k_proj", (h, nkv * d), "normal", std),
                    (pre + "v_proj", (h, nkv * d), "normal", std),
                    (pre + "o_proj", (nq * d, h), "normal", std),
                    (pre + "q_norm", (d,), "zeros", 0.0),
                    (pre + "k_norm", (d,), "zeros", 0.0)]
        else:
            out += [
                (pre + "in_proj_qkvz", (h, 2 * key_dim + 2 * value_dim),
                 "normal", std),
                (pre + "in_proj_ba", (h, 2 * hv), "normal", std),
                (pre + "conv.w", (2 * key_dim + value_dim,
                                  cfg["linear_conv_kernel_dim"]),
                 "uniform", 0.5),
                (pre + "A_log", (hv,), "uniform", 1.0),
                (pre + "dt_bias", (hv,), "uniform", 1.0),
                (pre + "gate_norm", (dv,), "ones", 0.0),
                (pre + "out_proj", (value_dim, h), "normal", std)]
        out += [(pre + "post_mixer_norm", (h,), "zeros", 0.0),
                (pre + "router", (h, routed), "normal", std),
                (pre + "shared_gate_up", (h, 2 * fs), "normal", std),
                (pre + "shared_down", (fs, h), "normal", std),
                (pre + "shared_gate", (h, 1), "normal", std),
                (pre + "experts_gate_up", (held, h, 2 * f), "normal", std),
                (pre + "experts_down", (held, f, h), "normal", std)]
    out += [("final_norm", (h,), "zeros", 0.0),
            ("lm_head", (h, v), "normal", std)]
    return out


def spread_decay_init(weights):
    """`dt_bias` and `A_log` from uniform in +-1 onto the ranges of the
    published initialisation: the step dt log-uniform in [1e-3, 1e-1]
    with dt_bias its inverse softplus (about -6.9 to -2.25); A uniform
    in (0, 16) with A_log its logarithm (A held above 1e-3, where the
    draw's lowest thousandth would otherwise send the logarithm off)."""
    out = dict(weights)
    for k, v in weights.items():
        if k.endswith(".dt_bias"):
            dt = jnp.exp(math.log(1e-2) + math.log(10.0) * v)
            out[k] = dt + jnp.log(-jnp.expm1(-dt))
        elif k.endswith(".A_log"):
            out[k] = jnp.log(jnp.maximum(8.0 + 8.0 * v, 1e-3))
    return out


def leaves(tree):
    """The model's leaves as published: every parameter is one."""
    return dict(tree)


#: positions between two kept states of the token-by-token recurrence
TIME_BLOCK = 128
#: queries a block of the masked square
QUERY_BLOCK = 2048
#: tokens a block of the routed layer and of the loss head
TOKEN_BLOCK = 2048


def _blocks(n, block):
    """`n` in blocks of `block`, or whole where that does not divide."""
    return (n // block, block) if n % block == 0 else (1, n)


def _norm(x, w, eps):
    """The zero-centred RMS norm: the weight is w in (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _mm(x, w, quant):
    return jnp.matmul(common.operand(x, quant), common.operand(w, quant),
                      precision=common.HIGHEST)


def _gated_mlp(x, w_gate_up, w_down, quant):
    gate, up = jnp.split(_mm(x, w_gate_up, quant), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down, quant)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token: q, k [S, H, dk], v
    [S, H, dv], g and beta [S, H] -> o [S, H, dv], each head's state
    [dk, dv] from zero."""
    s = q.shape[0]

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum(
            "hde,hd->he", state, k_t, precision=common.HIGHEST))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t,
                                 precision=common.HIGHEST)

    n, blk = _blocks(s, TIME_BLOCK)
    _, out = jax.lax.scan(
        jax.checkpoint(lambda st, inp: jax.lax.scan(step, st, inp)),
        jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
        tuple(t.reshape((n, blk) + t.shape[1:])
              for t in (q, k, v, g, beta)))
    return out.reshape(v.shape)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def _gated_delta_net(x, p, cfg, quant):
    """x [S, H] of one sequence. Three parts, each made again in the
    backward pass on its own (the projections and the convolution, the
    recurrence, the gate and the output projection), so that only one
    part's float32 values of 16,384 positions are alive at a time."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r, key_dim, value_dim = hv // hk, hk * dk, hv * dv
    taps, s = cfg["linear_conv_kernel_dim"], x.shape[0]

    def project(x, p):
        qkvz = _mm(x, p["in_proj_qkvz"], quant).reshape(s, hk, -1)
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        ba = _mm(x, p["in_proj_ba"], quant).reshape(s, hk, 2 * r)
        mixed = jnp.concatenate(
            [q.reshape(s, key_dim), k.reshape(s, key_dim),
             v.reshape(s, value_dim)], axis=-1)
        padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(padded[i:i + s] * p["conv.w"][:, i]
                                for i in range(taps)))
        return (mixed, z.reshape(s, hv, dv), ba[..., :r].reshape(s, hv),
                ba[..., r:].reshape(s, hv))

    def recur(mixed, b, a, p):
        q, k, v = jnp.split(mixed, [key_dim, 2 * key_dim], axis=-1)
        # q, k and v are matmul operands in the program's chunked form:
        # rounded alike in the control
        q = common.operand(_l2_norm(q.reshape(s, hk, dk)) * dk ** -0.5,
                           quant)
        k = common.operand(_l2_norm(k.reshape(s, hk, dk)), quant)
        return delta_rule(
            jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1),
            common.operand(v.reshape(s, hv, dv), quant),
            -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]),
            jax.nn.sigmoid(b))

    def gate(out, z, p):
        out = out * jax.lax.rsqrt(jnp.mean(
            jnp.square(out), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
        out = out * p["gate_norm"] * jax.nn.silu(z)
        return _mm(out.reshape(s, value_dim), p["out_proj"], quant)

    mixed, z, b, a = jax.checkpoint(project)(x, p)
    return jax.checkpoint(gate)(jax.checkpoint(recur)(mixed, b, a, p), z, p)


def rotary(x, rotary_dim, theta):
    """Rotate-half rotary embedding on the first `rotary_dim` of the
    last axis of x [S, heads, D], positions 0 .. S-1."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                    / rotary_dim)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _gated_attention(x, p, cfg, quant):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s, eps = x.shape[0], cfg["rms_norm_eps"]
    rd = int(d * cfg["partial_rotary_factor"])
    qg = _mm(x, p["q_proj"], quant).reshape(s, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(s, nq * d)
    k = _mm(x, p["k_proj"], quant).reshape(s, nkv, d)
    v = _mm(x, p["v_proj"], quant).reshape(s, nkv, d)
    q = rotary(_norm(q, p["q_norm"], eps), rd, float(cfg["rope_theta"]))
    k = rotary(_norm(k, p["k_norm"], eps), rd, float(cfg["rope_theta"]))
    n, blk = _blocks(s, QUERY_BLOCK)
    cols = jnp.arange(s)

    def square(args):
        # query head j reads key/value head j // (nq / nkv)
        j, i = args
        q_ji = jax.lax.dynamic_slice_in_dim(jnp.take(q, j, axis=1),
                                            i * blk, blk)
        k_j = jnp.take(k, j // (nq // nkv), axis=1)
        v_j = jnp.take(v, j // (nq // nkv), axis=1)
        scores = jnp.matmul(common.operand(q_ji, quant),
                            common.operand(k_j, quant).T,
                            precision=common.HIGHEST) / math.sqrt(d)
        seen = (i * blk + jnp.arange(blk))[:, None] >= cols[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.matmul(common.operand(probs, quant),
                          common.operand(v_j, quant),
                          precision=common.HIGHEST)

    ctx = jax.lax.map(jax.checkpoint(square),
                      (jnp.repeat(jnp.arange(nq), n),
                       jnp.tile(jnp.arange(n), nq)))     # [nq * n, blk, d]
    ctx = jnp.transpose(ctx.reshape(nq, s, d), (1, 0, 2)).reshape(s, nq * d)
    return _mm(ctx * jax.nn.sigmoid(gate), p["o_proj"], quant)


def routing(x, w_router, cfg):
    """(expert numbers [S, k], weights [S, k]) of every token: softmax
    scores over ALL experts, never rounded (the program keeps them
    float32)."""
    p = jax.nn.softmax(jnp.matmul(x, w_router, precision=common.HIGHEST),
                       axis=-1)
    w, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def _routed_layer(x, p, cfg, quant, held, tap=None):
    first, count = held
    if tap is not None:
        tap.append(routing(x, p["router"], cfg)[0])

    def tokens(x):
        idx, w = routing(x, p["router"], cfg)
        out = _gated_mlp(x, p["shared_gate_up"], p["shared_down"], quant) \
            * jax.nn.sigmoid(_mm(x, p["shared_gate"], quant))
        for e in range(count):
            w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
            out = out + w_e[:, None] * _gated_mlp(
                x, p["experts_gate_up"][e], p["experts_down"][e], quant)
        return out

    n, blk = _blocks(x.shape[0], TOKEN_BLOCK)
    return jax.lax.map(jax.checkpoint(tokens),
                       x.reshape(n, blk, -1)).reshape(x.shape)


def _layer_params(params, i):
    pre = "l%d." % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(params, ids, *, cfg, quant, held, tap=None):
    """One sequence through the layers and the final norm. Each mixer
    and each routed layer is made again in the backward pass, unless
    `tap` (a list) collects every routed layer's choice of experts
    [S, k] on the way."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed"], ids, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        mixer = _gated_attention if is_attention(cfg, i) else _gated_delta_net

        def mix(h, p, mixer=mixer):
            return h + mixer(_norm(h, p["input_norm"], eps), p, cfg, quant)

        def route(h, p):
            return h + _routed_layer(_norm(h, p["post_mixer_norm"], eps), p,
                                     cfg, quant, held, tap)

        p = _layer_params(params, i)
        if tap is None:
            mix, route = jax.checkpoint(mix), jax.checkpoint(route)
        h = route(mix(h, p), p)
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


def _static(cfg):
    keys = ("num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")
    return _Frozen({k: cfg[k] for k in keys if k in cfg})


@functools.partial(jax.jit, static_argnames=("cfg", "quant", "held"))
def _routings(params, ids, *, cfg, quant, held):
    tap = []
    hidden_states(params, ids, cfg=cfg, quant=common.QUANT[quant],
                  held=held, tap=tap)
    return tap


def routings(params, ids, cfg, quant=None, held=None):
    """Every routed layer's choice of experts for one sequence `ids`
    [S]: a list of [S, k], layer by layer. With `quant` the matmul
    operands before each router are rounded, so that two calls count
    the choices a lower precision flips."""
    return _routings(params, jnp.asarray(ids), cfg=_static(cfg),
                     quant=quant, held=tuple(held or held_range(cfg)))


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
        params, ids, labels, counted, cfg=_static(cfg), quant=quant,
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
