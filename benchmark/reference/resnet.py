"""Plain reference for ResNet50 image classification: the forward pass
(7x7 stem, max pool, four stages of bottleneck blocks, global average
pool, one dense layer), the softmax cross-entropy loss, its gradients
and heavy-ball momentum, in straightforward `jax.numpy` / `lax.conv`
and float32 at `highest` precision. No kernels, no casts, no fusion; it
imports nothing of the program under test.

NOT blocked over rows: batch normalization takes its mean and variance
over all the rows of a batch, so the gradient of a batch is not a sum
over blocks of its rows. The whole batch goes through at once; each
bottleneck block is recomputed in the backward pass (`jax.checkpoint`),
so that what is kept between the two passes is one block input a block
and the float32 activations of 256 images fit one chip.

Departures from He et al. (arXiv:1512.03385), each as the
configuration's file states it: the stride of a stage's first block
sits on its 3x3 convolution (the Paddle model zoo's variant), batch
norm's epsilon is 1e-5 and its variance the biased one."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import common


def conv_table(cfg):
    """Every convolution of the network in forward order as
    (name, out_channels, in_channels, kernel, stride, output_side), from
    the configuration alone. `name` is the layer's name; its filter is
    `<name>_weights`, its batch norm `<name>_bn_scale/_bn_offset`."""
    side = int(cfg["image_size"])
    stem = int(cfg["stem_width"])
    k = int(cfg["stem_kernel"])
    side = (side + 2 * ((k - 1) // 2) - k) // 2 + 1
    table = [("conv1", stem, 3, k, 2, side)]
    side = (side + 2 - 3) // 2 + 1          # 3x3 max pool, stride 2, pad 1
    cin, exp = stem, int(cfg["expansion"])
    for stage, (width, count) in enumerate(zip(cfg["stage_widths"],
                                               cfg["stage_blocks"])):
        for blk in range(count):
            stride = 2 if (blk == 0 and stage != 0) else 1
            out_side = (side - 1) // stride + 1
            name = "res%d_%d" % (stage + 2, blk)
            table.append((name + "_branch2a", width, cin, 1, 1, side))
            table.append((name + "_branch2b", width, width, 3, stride,
                          out_side))
            table.append((name + "_branch2c", width * exp, width, 1, 1,
                          out_side))
            if cin != width * exp or stride != 1:
                table.append((name + "_branch1", width * exp, cin, 1,
                              stride, out_side))
            cin, side = width * exp, out_side
    return table


def param_spec(cfg):
    """[(name, shape, kind, scale)]: filters are normal (cut at two
    sigma) of scale sqrt(2 / fan_in) (He et al. 2015, arXiv:1502.01852), batch
    norm scales one and offsets nought, the dense layer uniform in
    +-1/sqrt(fan_in) with a bias of nought."""
    spec = []
    for name, cout, cin, k, _, _ in conv_table(cfg):
        spec.append((name + "_weights", (cout, cin, k, k), "normal",
                     (2.0 / (cin * k * k)) ** 0.5))
        spec.append((name + "_bn_scale", (cout,), "ones", 0.0))
        spec.append((name + "_bn_offset", (cout,), "zeros", 0.0))
    feat = int(cfg["stage_widths"][-1]) * int(cfg["expansion"])
    spec.append(("fc_weights", (feat, int(cfg["num_classes"])), "uniform",
                 feat ** -0.5))
    spec.append(("fc_offset", (int(cfg["num_classes"]),), "zeros", 0.0))
    return spec


def leaves(tree):
    return dict(tree)


def _conv_bn(x, p, name, stride, relu, eps, quant):
    w = p[name + "_weights"]
    pad = (w.shape[-1] - 1) // 2
    y = jax.lax.conv_general_dilated(
        common.operand(x, quant), common.operand(w, quant),
        (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=common.HIGHEST)
    mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
    y = ((y - mean) / jnp.sqrt(var + eps)
         * p[name + "_bn_scale"][None, :, None, None]
         + p[name + "_bn_offset"][None, :, None, None])
    return jnp.maximum(y, 0.0) if relu else y


def _bottleneck(x, p, *, name, stride, project, eps, quant):
    y = _conv_bn(x, p, name + "_branch2a", 1, True, eps, quant)
    y = _conv_bn(y, p, name + "_branch2b", stride, True, eps, quant)
    y = _conv_bn(y, p, name + "_branch2c", 1, False, eps, quant)
    if project:
        x = _conv_bn(x, p, name + "_branch1", stride, False, eps, quant)
    return jnp.maximum(x + y, 0.0)


def loss_fn(params, image, label, *, blocks, eps, quant):
    """Mean softmax cross-entropy of the batch. `blocks` is the static
    plan ((name, stride, project), ...) of the bottleneck blocks."""
    x = _conv_bn(image, params, "conv1", 2, True, eps, quant)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])
    for name, stride, project in blocks:
        mine = {k: v for k, v in params.items() if k.startswith(name + "_")}
        x = jax.checkpoint(functools.partial(
            _bottleneck, name=name, stride=stride, project=project,
            eps=eps, quant=quant))(x, mine)
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.matmul(common.operand(x, quant),
                        common.operand(params["fc_weights"], quant),
                        precision=common.HIGHEST) + params["fc_offset"]
    lbl = label.reshape(-1)
    per_row = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, lbl[:, None], axis=1)[:, 0]
    return jnp.mean(per_row)


def block_plan(cfg):
    """((block name, stride, has a projection shortcut), ...)"""
    table = conv_table(cfg)
    projected = {n[:-len("_branch1")] for n, *_ in table
                 if n.endswith("_branch1")}
    return tuple((n[:-len("_branch2b")], stride,
                  n[:-len("_branch2b")] in projected)
                 for n, _, _, _, stride, _ in table
                 if n.endswith("_branch2b"))


@functools.partial(jax.jit, static_argnames=("blocks", "eps", "quant"))
def _value_and_grad(params, image, label, *, blocks, eps, quant):
    return jax.value_and_grad(loss_fn)(
        params, image, label, blocks=blocks, eps=eps,
        quant=common.QUANT[quant])


def loss_and_grad(params, batch, cfg, quant=None, keep=None):
    """`keep` (a slice of rows) plants the half-batch fault: only those
    rows count, and the mean is taken over them."""
    if keep is not None:
        batch = {k: v[keep] for k, v in batch.items()}
    return _value_and_grad(
        params, jnp.asarray(batch["image"]), jnp.asarray(batch["label"]),
        blocks=block_plan(cfg), eps=float(cfg["bn_eps"]), quant=quant)


def train(weights, batches, cfg, recipe, quant=None, keep=None):
    """Follow `len(batches)` momentum steps from `weights`. Returns the
    losses, the first gradient leaf by leaf with its norms, and the
    per-leaf norms of the parameters' change over all the steps."""
    start = weights
    params = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(weights)
    velocity = common.zeros_like_tree(params)
    losses, grads = [], None
    for batch in batches:
        loss, grad = loss_and_grad(params, batch, cfg, quant, keep)
        if grads is None:
            grads = grad
            grad_norms = common.leaf_norms(grads)
        params, velocity = common.momentum_step(
            params, grad, velocity, lr=float(recipe["learning_rate"]),
            mu=float(recipe["momentum"]))
        losses.append(loss)
    change = common.diff_norms(params, start)
    return {"losses": [float(x) for x in losses], "grads": grads,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "change_norms": {k: float(v) for k, v in change.items()}}
