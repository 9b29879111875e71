"""Plain reference for BERT pretraining: the forward pass, the masked-LM
plus next-sentence loss, its gradients and Adam, in straightforward
`jax.numpy` and float32 at `highest` matmul precision. No kernels, no
casts, no fusion; it imports nothing of the program under test.

Blocked over ROWS: both losses are weighted means over rows (the
masked-LM term divides by the sum of the mask weights of the whole
batch, the next-sentence term by the number of rows), so the loss and
the gradient of a batch are sums over blocks of its rows. That keeps
the float32 activations of 512 x 128 tokens inside one chip's memory;
inside a block the layers are one `lax.scan` with the body recomputed
in the backward pass.

Departures from the published model, each as the configuration's file
states it: layer-norm epsilon, no dropout, an output matrix of its own
on the masked-LM head, q/k/v held as one [H, 3H] matrix per layer (the
same mathematics as three)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common

ENC = ("enc_qkv.w", "enc_qkv.b", "enc_attn_out.w", "enc_attn_out.b",
       "enc_post_att_ln.scale", "enc_post_att_ln.bias", "enc_ffn0.w",
       "enc_ffn0.b", "enc_ffn1.w", "enc_ffn1.b",
       "enc_post_ffn_ln.scale", "enc_post_ffn_ln.bias")


def param_spec(cfg, max_pos):
    """[(name, shape, kind)] of BERT's parameters from the configuration
    alone; kind is `normal` (truncated normal of initializer_range),
    `ones` or `zeros`."""
    v, h, f = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    n = cfg["num_hidden_layers"]
    t = cfg["type_vocab_size"]
    return [
        ("word_embedding", (v, h), "normal"),
        ("pos_embedding", (max_pos, h), "normal"),
        ("sent_embedding", (t, h), "normal"),
        ("pre_encoder_ln.scale", (h,), "ones"),
        ("pre_encoder_ln.bias", (h,), "zeros"),
        ("enc_qkv.w", (n, h, 3 * h), "normal"),
        ("enc_qkv.b", (n, 3 * h), "zeros"),
        ("enc_attn_out.w", (n, h, h), "normal"),
        ("enc_attn_out.b", (n, h), "zeros"),
        ("enc_post_att_ln.scale", (n, h), "ones"),
        ("enc_post_att_ln.bias", (n, h), "zeros"),
        ("enc_ffn0.w", (n, h, f), "normal"),
        ("enc_ffn0.b", (n, f), "zeros"),
        ("enc_ffn1.w", (n, f, h), "normal"),
        ("enc_ffn1.b", (n, h), "zeros"),
        ("enc_post_ffn_ln.scale", (n, h), "ones"),
        ("enc_post_ffn_ln.bias", (n, h), "zeros"),
        ("mlm_trans.w", (h, h), "normal"),
        ("mlm_trans.b", (h,), "zeros"),
        ("mlm_ln.scale", (h,), "ones"),
        ("mlm_ln.bias", (h,), "zeros"),
        ("mlm_out.w", (h, v), "normal"),
        ("mlm_out.b", (v,), "zeros"),
        ("pooler.w", (h, h), "normal"),
        ("pooler.b", (h,), "zeros"),
        ("nsp.w", (h, 2), "normal"),
        ("nsp.b", (2,), "zeros"),
    ]


def leaves(tree):
    """The model's leaves as published: the fused q/k/v matrix and bias
    of a layer count as three each. (The key's bias has a gradient of
    nought to rounding under softmax; inside the fused array it would
    hide in a leaf that does move.)"""
    out = {}
    for name, value in tree.items():
        if name.startswith("enc_qkv."):
            h = value.shape[-1] // 3
            for i, part in enumerate("qkv"):
                out["%s.%s" % (name, part)] = value[..., i * h:(i + 1) * h]
        else:
            out[name] = value
    return out


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _dense(x, w, b, quant):
    return jnp.matmul(common.operand(x, quant), common.operand(w, quant),
                      precision=common.HIGHEST) + b


def _encoder_layer(x, key_bias, p, n_head, eps, quant):
    b, s, h = x.shape
    d = h // n_head
    qkv = _dense(x, p["enc_qkv.w"], p["enc_qkv.b"], quant)
    q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, n_head, d)
               .transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=common.HIGHEST) / math.sqrt(d)
    scores = scores + key_bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                     precision=common.HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    attn = _dense(ctx, p["enc_attn_out.w"], p["enc_attn_out.b"], quant)
    y = _layer_norm(x + attn, p["enc_post_att_ln.scale"],
                    p["enc_post_att_ln.bias"], eps)
    ffn = jax.nn.gelu(_dense(y, p["enc_ffn0.w"], p["enc_ffn0.b"], quant),
                      approximate=False)
    ffn = _dense(ffn, p["enc_ffn1.w"], p["enc_ffn1.b"], quant)
    return _layer_norm(y + ffn, p["enc_post_ffn_ln.scale"],
                       p["enc_post_ffn_ln.bias"], eps)


def block_loss(params, rows, mlm_denom, n_rows, *, n_head, eps, quant):
    """This block's share of the batch loss: its masked-LM terms over the
    whole batch's mask weight, its next-sentence terms over the whole
    batch's rows."""
    x = (jnp.take(params["word_embedding"], rows["src_ids"], axis=0)
         + jnp.take(params["pos_embedding"], rows["pos_ids"], axis=0)
         + jnp.take(params["sent_embedding"], rows["sent_ids"], axis=0))
    x = _layer_norm(x, params["pre_encoder_ln.scale"],
                    params["pre_encoder_ln.bias"], eps)
    key_bias = (1.0 - rows["input_mask"]) * -10000.0

    def body(carry, layer):
        return _encoder_layer(carry, key_bias, layer, n_head, eps,
                              quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        {k: params[k] for k in ENC})

    h = x.shape[-1]
    picked = jnp.take_along_axis(x, rows["mask_pos"][..., None], axis=1)
    picked = picked.reshape(-1, h)
    trans = jax.nn.gelu(_dense(picked, params["mlm_trans.w"],
                               params["mlm_trans.b"], quant),
                        approximate=False)
    trans = _layer_norm(trans, params["mlm_ln.scale"],
                        params["mlm_ln.bias"], eps)
    # the output matrix keeps float32 operands in the control too: the
    # program's float8 list leaves its fused loss head out
    logits = _dense(trans, params["mlm_out.w"], params["mlm_out.b"], None)
    labels = rows["mask_label"].reshape(-1)
    per_tok = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=1)[:, 0]
    mlm = jnp.sum(per_tok * rows["mask_weight"].reshape(-1)) / mlm_denom

    pooled = jnp.tanh(_dense(x[:, 0, :], params["pooler.w"],
                             params["pooler.b"], quant))
    nsp_logits = _dense(pooled, params["nsp.w"], params["nsp.b"], quant)
    nsp_lbl = rows["nsp_label"].reshape(-1)
    nsp = jnp.sum(jax.nn.logsumexp(nsp_logits, axis=-1)
                  - jnp.take_along_axis(nsp_logits, nsp_lbl[:, None],
                                        axis=1)[:, 0]) / n_rows
    return mlm + nsp


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "quant"))
def _block_value_and_grad(params, rows, mlm_denom, n_rows, *, n_head, eps,
                          quant):
    return jax.value_and_grad(block_loss)(
        params, rows, mlm_denom, n_rows, n_head=n_head, eps=eps,
        quant=common.QUANT[quant])


def loss_and_grad(params, batch, cfg, block_rows, quant=None, keep=None):
    """Loss and gradient of one batch, block of rows by block of rows.
    `keep` (a slice of rows) plants the half-batch fault: only those rows
    count, and the means are taken over them."""
    if keep is not None:
        batch = {k: v[keep] for k, v in batch.items()}
    n_rows = batch["src_ids"].shape[0]
    block_rows = min(block_rows, n_rows)
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    mlm_denom = jnp.float32(float(batch["mask_weight"].sum()) + 1e-6)
    loss, grad = None, None
    for lo in range(0, n_rows, block_rows):
        rows = {k: jnp.asarray(v[lo:lo + block_rows])
                for k, v in batch.items()}
        l_blk, g_blk = _block_value_and_grad(
            params, rows, mlm_denom, jnp.float32(n_rows),
            n_head=cfg["num_attention_heads"],
            eps=float(cfg["layer_norm_eps"]), quant=quant)
        loss = l_blk if loss is None else loss + l_blk
        grad = g_blk if grad is None else common.tree_add(grad, g_blk)
    return loss, grad


def train(weights, batches, cfg, recipe, block_rows, quant=None,
          keep=None, adam_ahead=0):
    """Follow `len(batches)` Adam steps from `weights`. Returns the
    losses, the first gradient leaf by leaf with its norms, and the
    per-leaf norms of the parameters' change over all the steps.
    `adam_ahead` plants a fault: step t's bias corrected as step
    t + adam_ahead's."""
    start = weights
    params = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(weights)
    m1, m2 = common.zeros_like_tree(params), common.zeros_like_tree(params)
    losses, grads = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grad = loss_and_grad(params, batch, cfg, block_rows, quant,
                                   keep)
        if grads is None:
            grads = leaves(grad)
            grad_norms = common.leaf_norms(grads)
        params, m1, m2 = common.adam_step(
            params, grad, m1, m2, jnp.int32(t + adam_ahead),
            lr=float(recipe["learning_rate"]), b1=float(recipe["beta1"]),
            b2=float(recipe["beta2"]), eps=float(recipe["epsilon"]))
        losses.append(loss)
    change = common.diff_norms(leaves(params), leaves(start))
    return {"losses": [float(x) for x in losses], "grads": grads,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "change_norms": {k: float(v) for k, v in change.items()}}
