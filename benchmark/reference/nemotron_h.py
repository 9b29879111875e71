"""Plain reference for a hybrid state-space / routed-expert decoder of
the Nemotron-H family (NVIDIA-Nemotron-3-Nano-30B-A3B): the forward
pass, next-token cross-entropy, its gradients and Adam in
straightforward `jax.numpy`, float32 at `highest` matmul precision. No
kernels, no casts, no chunks: the Mamba-2 recurrence is written step by
step over time (`lax.scan` over positions), the routed experts are a
dense loop over the experts held with a mask. It imports nothing of the
program under test.

Blocked over SEQUENCES: the loss is a mean over all positions of all
sequences and no layer mixes sequences, so a batch's loss is a sum over
its sequences, taken one at a time (`lax.map`), each block of each
sequence made again in the backward pass; attention goes a query head
at a time and the recurrence keeps its state every `TIME_BLOCK`
positions and makes the steps between again. That keeps the float32
activations of 8,192 positions beside the parameters, one gradient and
Adam's two moments (16 bytes a parameter) inside one chip: `train`
takes `weights` over (they are donated to the first Adam step) and
hands the first gradient and the starting weights to the host.

The equations (config keys in brackets), a block being
`h <- h + Mixer(RMSNorm(h))`, `RMSNorm(x) = x rsqrt(mean(x^2) + eps) w`:

- `M`, Mamba-2: `[z | xBC | dt] = x W_in`; `xBC <- silu(conv(xBC) + b)`,
  a causal depthwise convolution of `conv_kernel` taps; `x` [heads x
  head_dim], `B`, `C` [`n_groups` x `ssm_state_size`], head j reading
  group j // (heads / n_groups); `dt <- softplus(dt + dt_bias)`,
  `A = -exp(A_log)`; `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`,
  `y_t = S_t C_t + D x_t`; `y <- RMSNorm_groups(y silu(z)) w` (the gate
  first, the norm over each of `n_groups` parts); `out = y W_out`.
- `*`, attention: `num_attention_heads` query heads on
  `num_key_value_heads` key/value heads of `head_dim`, causal, no bias,
  no rotary embedding.
- `E`, routed experts: `s = sigmoid(x W_r)` over all experts; the
  `num_experts_per_tok` largest of `s + b` (b held at zero); weights
  `s_k / sum_k s_k` times `routed_scaling_factor`; expert
  `e(x) = W_down relu(x W_up)^2`; a shared expert of the same form for
  every token. Given `held = (first, count)`, only those experts' part
  is computed: the partial sum is the layer's output.

Departures from the published model are the configuration file's
`assumed` and `reduced`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common


def param_spec(cfg):
    """[(name, shape, kind, scale)] of the parameters from the
    configuration alone. kind `normal` is a truncated normal of
    standard deviation scale; `uniform` is uniform in +-scale; `ones`
    and `zeros` are constants. `dt_bias` and `A_log` are drawn uniform
    in +-1 here; who makes the weights maps them onto the published
    initialisation's ranges (`spread_ssm_init`)."""
    std = float(cfg["initializer_range"])
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, conv_dim = heads * p, heads * p + 2 * g * n
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    held = cfg["n_routed_experts"]
    routed = cfg["published"]["n_routed_experts"]
    out = [("embed", (v, h), "normal", std)]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = "l%d." % i
        out.append((pre + "norm", (h,), "ones", 0.0))
        if kind == "M":
            out += [
                (pre + "in_proj", (h, inner + conv_dim + heads), "normal",
                 std),
                (pre + "conv.w", (conv_dim, cfg["conv_kernel"]), "uniform",
                 0.5),
                (pre + "conv.b", (conv_dim,), "zeros", 0.0),
                (pre + "dt_bias", (heads,), "uniform", 1.0),
                (pre + "A_log", (heads,), "uniform", 1.0),
                (pre + "D", (heads,), "ones", 0.0),
                (pre + "mixer_norm", (inner,), "ones", 0.0),
                (pre + "out_proj", (inner, h), "normal", std)]
        elif kind == "*":
            out += [(pre + "q_proj", (h, nq * d), "normal", std),
                    (pre + "k_proj", (h, nkv * d), "normal", std),
                    (pre + "v_proj", (h, nkv * d), "normal", std),
                    (pre + "o_proj", (nq * d, h), "normal", std)]
        elif kind == "E":
            out += [(pre + "router", (h, routed), "normal", std),
                    (pre + "shared_up", (h, fs), "normal", std),
                    (pre + "shared_down", (fs, h), "normal", std),
                    (pre + "experts_up", (held, h, f), "normal", std),
                    (pre + "experts_down", (held, f, h), "normal", std)]
        else:
            raise ValueError("unknown layer kind %r" % kind)
    out += [("final_norm", (h,), "ones", 0.0),
            ("lm_head", (h, v), "normal", std)]
    return out


def spread_ssm_init(weights):
    """`dt_bias` and `A_log` from uniform in +-1 onto the ranges of the
    published initialisation (Mamba-2: the step size dt log-uniform in
    [1e-3, 1e-1] with dt_bias its inverse softplus, so about -6.9 to
    -2.25; A uniform in [1, 16] with A_log its logarithm)."""
    out = dict(weights)
    for k, v in weights.items():
        if k.endswith(".dt_bias"):
            out[k] = -4.6 + 2.3 * v
        elif k.endswith(".A_log"):
            out[k] = jnp.log(8.5 + 7.5 * v)
    return out


def leaves(tree):
    """The model's leaves as published: every parameter is one."""
    return dict(tree)


#: positions between two kept states of the step-by-step recurrence
TIME_BLOCK = 128


def _rms_norm(x, w, eps, groups=1):
    xg = x.reshape(x.shape[:-1] + (groups, -1))
    y = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
                           + eps)
    return y.reshape(x.shape) * w


def _mm(x, w, quant):
    return jnp.matmul(common.operand(x, quant), common.operand(w, quant),
                      precision=common.HIGHEST)


def _mamba2(x, p, cfg, quant):
    """x [S, H] of one sequence."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, k = heads * hd, cfg["conv_kernel"]
    conv_dim = inner + 2 * g * n
    s = x.shape[0]
    zxbcdt = _mm(x, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                  zxbcdt[:, inner + conv_dim:])
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + s] * p["conv.w"][:, i]
                          for i in range(k)) + p["conv.b"])
    xs = xbc[:, :inner].reshape(s, heads, hd)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(s, g, n),
                    heads // g, axis=1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(s, g, n),
                    heads // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [S, heads]
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        # the outer product's operands are matmul operands in the
        # program's chunked form: rounded alike in the control
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + common.operand(dt_t[:, None] * x_t, quant)[:, :, None]
                 * common.operand(b_t, quant)[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state,
                                 common.operand(c_t, quant),
                                 precision=common.HIGHEST)

    blk = TIME_BLOCK if s % TIME_BLOCK == 0 else s
    _, y = jax.lax.scan(
        jax.checkpoint(lambda st, inp: jax.lax.scan(step, st, inp)),
        jnp.zeros((heads, hd, n), jnp.float32),
        tuple(t.reshape((s // blk, blk) + t.shape[1:])
              for t in (xs, dt, bm, cm)))
    y = (y.reshape(xs.shape) + p["D"][:, None] * xs).reshape(s, inner)
    y = _rms_norm(y * jax.nn.silu(z), p["mixer_norm"],
                  cfg["layer_norm_epsilon"], groups=g)
    return _mm(y, p["out_proj"], quant)


def _attention(x, p, cfg, quant):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s = x.shape[0]
    q = _mm(x, p["q_proj"], quant).reshape(s, nq, d)
    k = _mm(x, p["k_proj"], quant).reshape(s, nkv, d)
    v = _mm(x, p["v_proj"], quant).reshape(s, nkv, d)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(j):
        # query head j reads key/value head j // (nq / nkv)
        q_j = q[:, j]
        k_j = jnp.take(k, j // (nq // nkv), axis=1)
        v_j = jnp.take(v, j // (nq // nkv), axis=1)
        scores = jnp.matmul(common.operand(q_j, quant),
                            common.operand(k_j, quant).T,
                            precision=common.HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(common.operand(probs, quant),
                          common.operand(v_j, quant),
                          precision=common.HIGHEST)

    ctx = jax.lax.map(jax.checkpoint(head), jnp.arange(nq))   # [nq, S, d]
    return _mm(jnp.transpose(ctx, (1, 0, 2)).reshape(s, nq * d),
               p["o_proj"], quant)


def _relu2_mlp(x, w_up, w_down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, quant))), w_down, quant)


def routing(x, w_router, cfg):
    """(expert numbers [S, k], weights [S, k]) of every token: scores
    over ALL experts, never rounded (the program keeps them float32)."""
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=common.HIGHEST))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def _experts(x, p, cfg, quant, held, tap=None):
    first, count = held
    idx, w = routing(x, p["router"], cfg)
    if tap is not None:
        tap.append(idx)
    out = _relu2_mlp(x, p["shared_up"], p["shared_down"], quant)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _relu2_mlp(
            x, p["experts_up"][e], p["experts_down"][e], quant)
    return out


_MIXERS = {"M": _mamba2, "*": _attention}


def _layer_params(params, i):
    pre = "l%d." % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(params, ids, *, cfg, quant, held, tap=None):
    """One sequence through the blocks and the final norm. Each block
    is made again in the backward pass, unless `tap` (a list) collects
    every routed layer's choice of experts [S, k] on the way."""
    eps = cfg["layer_norm_epsilon"]
    h = jnp.take(params["embed"], ids, axis=0)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        def block(h, p, kind=kind):
            x = _rms_norm(h, p["norm"], eps)
            if kind == "E":
                return h + _experts(x, p, cfg, quant, held, tap)
            return h + _MIXERS[kind](x, p, cfg, quant)

        if tap is None:
            block = jax.checkpoint(block)
        h = block(h, _layer_params(params, i))
    return _rms_norm(h, params["final_norm"], eps)


def sequence_loss(params, ids, labels, *, n_tokens, cfg, quant, held):
    """One sequence's share of the batch loss: the sum of its tokens'
    cross-entropies over the batch's token count."""
    h = hidden_states(params, ids, cfg=cfg, quant=quant, held=held)
    # the head keeps float32 operands in the control too: the program's
    # float8 list leaves its fused loss head out
    logits = _mm(h, params["lm_head"], None)
    per_tok = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=1)[:, 0]
    return jnp.sum(per_tok) / n_tokens


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


class _Frozen(dict):
    """A configuration as a static argument of `jit`."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("cfg", "quant", "held"))
def _batch_value_and_grad(params, ids, labels, *, cfg, quant, held):
    n_tokens = jnp.float32(ids.shape[0] * ids.shape[1])

    def loss(params):
        one = jax.checkpoint(functools.partial(
            sequence_loss, n_tokens=n_tokens, cfg=cfg,
            quant=common.QUANT[quant], held=held))
        return jnp.sum(jax.lax.map(lambda a: one(params, *a),
                                   (ids, labels)))

    return jax.value_and_grad(loss)(params)


def _static(cfg):
    keys = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "routed_scaling_factor",
            "norm_topk_prob", "layer_norm_epsilon")
    return _Frozen({k: cfg[k] for k in keys if k in cfg})


def held_range(cfg):
    """(first expert, how many) held, as the configuration states it."""
    dep = cfg.get("deployment") or {}
    return (int(dep.get("first_expert_held", 0)),
            int(cfg["n_routed_experts"]))


def loss_and_grad(params, batch, cfg, quant=None, keep=None, held=None):
    """Loss and gradient of one batch, sequence by sequence. `keep` (a
    slice of sequences) plants the half-batch fault: only those count,
    and the mean is taken over them."""
    if keep is not None:
        batch = {k: v[keep] for k, v in batch.items()}
    return _batch_value_and_grad(
        params, jnp.asarray(batch["ids"]), jnp.asarray(batch["labels"]),
        cfg=_static(cfg), quant=quant, held=tuple(held or held_range(cfg)))


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
