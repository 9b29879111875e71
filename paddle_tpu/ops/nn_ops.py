"""Neural-network operators: conv/pool/norm/activation/softmax/dropout/embedding.

Reference parity: `paddle/fluid/operators/conv_op.cc`+`conv_cudnn_op.cu`,
`pool_op.cc`, `batch_norm_op.{cc,cu}`, `layer_norm_op.{cc,cu}`,
`softmax_with_cross_entropy_op.cu`, `activation_op.*`, `dropout_op.*`,
`lookup_table(_v2)_op.*`. TPU-native notes: convs/matmuls map to the MXU via
`lax.conv_general_dilated`/`jnp.matmul`; the cudnn algorithm-search attrs
(exhaustive_search, workspace limits) are obsolete — XLA autotunes; dropout
uses counter-based stateless PRNG (threefry) instead of the reference's
curand states.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import remat_names
from .registry import register_op


# ---------------------------------------------------------------------------
# Activations (reference: operators/activation_op.cc lists ~30)
# ---------------------------------------------------------------------------

def _register_act(name, fn):
    @register_op(name)
    def _act(ins, attrs, _fn=fn):
        return {"Out": _fn(ins["X"][0], attrs)}


_register_act("relu", lambda x, a: jax.nn.relu(x))
_register_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_register_act("tanh", lambda x, a: jnp.tanh(x))
_register_act("sqrt", lambda x, a: jnp.sqrt(x))
_register_act("rsqrt", lambda x, a: lax.rsqrt(x))
_register_act("square", lambda x, a: jnp.square(x))
_register_act("exp", lambda x, a: jnp.exp(x))
_register_act("log", lambda x, a: jnp.log(x))
_register_act("log2", lambda x, a: jnp.log2(x))
_register_act("log10", lambda x, a: jnp.log10(x))
_register_act("log1p", lambda x, a: jnp.log1p(x))
_register_act("abs", lambda x, a: jnp.abs(x))
_register_act("ceil", lambda x, a: jnp.ceil(x))
_register_act("floor", lambda x, a: jnp.floor(x))
_register_act("round", lambda x, a: jnp.round(x))
_register_act("reciprocal", lambda x, a: 1.0 / x)
_register_act("sin", lambda x, a: jnp.sin(x))
_register_act("cos", lambda x, a: jnp.cos(x))
_register_act("asin", lambda x, a: jnp.arcsin(x))
_register_act("acos", lambda x, a: jnp.arccos(x))
_register_act("atan", lambda x, a: jnp.arctan(x))
_register_act("sinh", lambda x, a: jnp.sinh(x))
_register_act("cosh", lambda x, a: jnp.cosh(x))
_register_act("erf", lambda x, a: jax.scipy.special.erf(x))
_register_act("softplus", lambda x, a: jax.nn.softplus(x))
_register_act("softsign", lambda x, a: x / (1 + jnp.abs(x)))
_register_act("relu6", lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0)))
_register_act("leaky_relu", lambda x, a: jnp.where(
    x >= 0, x, x * a.get("alpha", 0.02)))
_register_act("elu", lambda x, a: jnp.where(
    x >= 0, x, a.get("alpha", 1.0) * (jnp.exp(x) - 1)))
_register_act("gelu", lambda x, a: jax.nn.gelu(
    x, approximate=a.get("approximate", False)))
_register_act("swish", lambda x, a: x * jax.nn.sigmoid(
    a.get("beta", 1.0) * x))
_register_act("silu", lambda x, a: jax.nn.silu(x))
_register_act("relu2", lambda x, a: jnp.square(jax.nn.relu(x)))
_register_act("mish", lambda x, a: x * jnp.tanh(jax.nn.softplus(x)))
_register_act("hard_sigmoid", lambda x, a: jnp.clip(
    a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0))
_register_act("hard_swish", lambda x, a: x * jnp.clip(
    x + a.get("offset", 3.0), 0.0, a.get("threshold", 6.0))
    / a.get("scale", 6.0))
_register_act("thresholded_relu", lambda x, a: jnp.where(
    x > a.get("threshold", 1.0), x, jnp.zeros_like(x)))
_register_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_register_act("sign", lambda x, a: jnp.sign(x))
_register_act("stanh", lambda x, a: a.get("scale_b", 1.7159)
              * jnp.tanh(a.get("scale_a", 0.67) * x))


@register_op("prelu")
def _prelu(ins, attrs):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": jnp.where(x >= 0, x, x * alpha)}


@register_op("softmax")
def _softmax(ins, attrs):
    return {"Out": jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1))}


@register_op("log_softmax")
def _log_softmax(ins, attrs):
    return {"Out": jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1))}


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

@register_op("cross_entropy")
def _cross_entropy(ins, attrs):
    # reference: operators/cross_entropy_op.cc — input X is probabilities.
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-9
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        idx = label.reshape(label.shape[:-1]).astype(jnp.int32)
        picked = jnp.take_along_axis(x, idx[..., None], axis=-1)
        loss = -jnp.log(picked + eps)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(idx[..., None] == ignore, jnp.zeros_like(loss), loss)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy")
def _softmax_ce(ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    softmax = jax.nn.softmax(logits, axis=axis)
    logp = jax.nn.log_softmax(logits, axis=axis)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        idx = label.astype(jnp.int32)
        squeeze = (idx.ndim == logits.ndim and idx.shape[axis] == 1)
        if squeeze:
            idx = jnp.squeeze(idx, axis=axis)
        loss = -jnp.take_along_axis(logp, idx[..., None], axis=axis)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(idx[..., None] == ignore, jnp.zeros_like(loss), loss)
    return {"Softmax": softmax, "Loss": loss}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, jnp.zeros_like(loss), loss)
    if attrs.get("normalize", False):
        n = jnp.sum((label != ignore).astype(loss.dtype))
        loss = loss / jnp.maximum(n, 1.0)
    return {"Out": loss}


@register_op("square_error_cost")
def _square_error(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": jnp.square(x - y)}


@register_op("huber_loss")
def _huber(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))
    return {"Out": loss, "Residual": r}


@register_op("smooth_l1_loss")
def _smooth_l1(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    a = jnp.abs(diff)
    elem = jnp.where(a < 1.0 / s2, 0.5 * s2 * diff * diff, a - 0.5 / s2)
    return {"Out": jnp.sum(elem, axis=-1, keepdims=True), "Diff": diff}


@register_op("kldiv_loss")
def _kldiv(ins, attrs):
    x, target = ins["X"][0], ins["Target"][0]
    loss = jnp.where(target > 0, target * (jnp.log(target) - x),
                     jnp.zeros_like(target))
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif red == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif red == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return {"Loss": loss}


# ---------------------------------------------------------------------------
# Convolution / pooling
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


@register_op("conv2d")
def _conv2d(ins, attrs):
    # reference: operators/conv_op.cc (NCHW input, OIHW filter)
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    if len(paddings) == 2:
        pads = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    else:
        pads = [(paddings[0], paddings[1]), (paddings[2], paddings[3])]
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=None)
    return {"Output": out}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ins, attrs):
    return _conv2d(ins, attrs)


@register_op("conv2d_transpose")
def _conv2d_transpose(ins, attrs):
    """Transposed conv as the gradient-of-conv: lhs-dilated conv with
    the spatially flipped kernel (weight layout (in, out/groups, kh, kw)
    matching operators/conv_transpose_op.cc). Verified against
    torch.conv_transpose2d for stride/padding/dilation combinations."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    kh, kw = w.shape[2], w.shape[3]
    if groups != 1:
        # (in, out/g, kh, kw) -> (in/g, out, kh, kw) with the group index
        # folded MAJOR into the O dim, matching XLA's feature_group_count
        # contract (lhs group i consumes kernel O slice i)
        cin, og = w.shape[0], w.shape[1]
        w = (w.reshape(groups, cin // groups, og, kh, kw)
             .transpose(1, 0, 2, 3, 4)
             .reshape(cin // groups, groups * og, kh, kw))
    pads = [(dilations[0] * (kh - 1) - paddings[0],) * 2,
            (dilations[1] * (kw - 1) - paddings[1],) * 2]
    out = lax.conv_general_dilated(
        x, jnp.flip(w, (2, 3)), window_strides=(1, 1), padding=pads,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dilations),
        dimension_numbers=("NCHW", "IOHW", "NCHW"),
        feature_group_count=groups)
    return {"Output": out}


@register_op("pool2d")
def _pool2d(ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ksize))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    ceil_mode = attrs.get("ceil_mode", False)
    exclusive = attrs.get("exclusive", True)
    adaptive = attrs.get("adaptive", False)
    if attrs.get("global_pooling", False) or (
            adaptive and tuple(ksize) == (1, 1)):
        if ptype == "max":
            return {"Out": jnp.max(x, axis=(2, 3), keepdims=True)}
        return {"Out": jnp.mean(x, axis=(2, 3), keepdims=True)}
    if adaptive:
        # adaptive pooling to output size ksize: split into equal windows
        n, c, h, wdt = x.shape
        oh, ow = ksize
        assert h % oh == 0 and wdt % ow == 0, "adaptive pool needs divisible"
        xr = x.reshape(n, c, oh, h // oh, ow, wdt // ow)
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": red(xr, axis=(3, 5))}

    h, w_ = x.shape[2], x.shape[3]
    pads = []
    for dim, k, s, p in ((h, ksize[0], strides[0], paddings[0]),
                         (w_, ksize[1], strides[1], paddings[1])):
        if ceil_mode:
            out_d = -(-(dim + 2 * p - k) // s) + 1
        else:
            out_d = (dim + 2 * p - k) // s + 1
        extra = max(0, (out_d - 1) * s + k - dim - p)
        pads.append((p, extra))
    window = (1, 1) + tuple(ksize)
    strides4 = (1, 1) + tuple(strides)
    pad4 = [(0, 0), (0, 0)] + pads
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides4, pad4)
    else:
        ssum = lax.reduce_window(x, 0.0, lax.add, window, strides4, pad4)
        if exclusive:
            ones = jnp.ones(x.shape[2:], x.dtype)
            cnt = lax.reduce_window(ones, 0.0, lax.add, tuple(ksize),
                                    tuple(strides), pads)
            out = ssum / cnt[None, None]
        else:
            out = ssum / float(ksize[0] * ksize[1])
    return {"Out": out}


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

@register_op("batch_norm")
def _batch_norm(ins, attrs):
    # reference: operators/batch_norm_op.cc — running stats update:
    # mean_out = mean * momentum + batch_mean * (1 - momentum)
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == "NCHW" else x.ndim - 1))
    cshape = [1] * x.ndim
    cshape[1 if layout == "NCHW" else -1] = -1
    cshape = tuple(cshape)

    if is_test or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, 1.0 / jnp.sqrt(var + eps)
        mean_out, var_out = mean, var
    else:
        f32 = x.astype(jnp.float32)
        bmean = jnp.mean(f32, axis=axes)
        bvar = jnp.mean(jnp.square(f32), axis=axes) - jnp.square(bmean)
        use_mean, use_var = bmean, bvar
        mean_out = mean * momentum + bmean.astype(mean.dtype) * (1 - momentum)
        var_out = var * momentum + bvar.astype(var.dtype) * (1 - momentum)
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)

    inv = (1.0 / jnp.sqrt(use_var.astype(jnp.float32) + eps))
    y = (x.astype(jnp.float32) - use_mean.reshape(cshape)) \
        * inv.reshape(cshape) * scale.astype(jnp.float32).reshape(cshape) \
        + bias.astype(jnp.float32).reshape(cshape)
    if attrs.get("fused_act") == "relu":
        # fuse_bn_act_pass folded a trailing relu into this op
        y = jnp.maximum(y, 0.0)
    return {"Y": y.astype(x.dtype), "MeanOut": mean_out,
            "VarianceOut": var_out, "SavedMean": saved_mean,
            "SavedVariance": saved_var}


@register_op("layer_norm")
def _layer_norm(ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    f32 = x.astype(jnp.float32)
    mean = jnp.mean(f32, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(f32 - mean), axis=axes, keepdims=True)
    y = (f32 - mean) / jnp.sqrt(var + eps)
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(jnp.float32).reshape(norm_shape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].astype(jnp.float32).reshape(norm_shape)
    red_shape = tuple(x.shape[:begin])
    return {"Y": y.astype(x.dtype),
            "Mean": mean.reshape(red_shape).astype(jnp.float32),
            "Variance": var.reshape(red_shape).astype(jnp.float32)}


@register_op("instance_norm")
def _instance_norm(ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    cshape = (1, -1) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(cshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(cshape)
    return {"Y": y, "SavedMean": mean.reshape(x.shape[:2]),
            "SavedVariance": (1.0 / jnp.sqrt(var + eps)).reshape(x.shape[:2])}


@register_op("group_norm")
def _group_norm(ins, attrs):
    x = ins["X"][0]
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    cshape = (1, -1) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(cshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(cshape)
    return {"Y": y, "Mean": mean.reshape(n, groups),
            "Variance": var.reshape(n, groups)}


# ---------------------------------------------------------------------------
# Dropout (stateless threefry PRNG; reference uses curand states)
# ---------------------------------------------------------------------------

@register_op("dropout", needs_rng=True)
def _dropout(ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones(x.shape, jnp.uint8)}
    key = attrs["_rng_key"]
    keep = remat_names.keep(jax.random.bernoulli(key, 1.0 - p, x.shape),
                            remat_names.DROPOUT_MASK)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
    else:
        out = jnp.where(keep, x, jnp.zeros_like(x))
    return {"Out": out, "Mask": keep.astype(jnp.uint8)}


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def _lookup(w, ids, padding_idx):
    out = jnp.take(w, ids.astype(jnp.int32), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros_like(out), out)
    return out


@register_op("lookup_table")
def _lookup_table(ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    # v1 ids carry a trailing [..., 1] dim (LoD heritage); squeeze it.
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    return {"Out": _lookup(w, ids, attrs.get("padding_idx", -1))}


@register_op("lookup_table_v2")
def _lookup_table_v2(ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    return {"Out": _lookup(w, ids, attrs.get("padding_idx", -1))}


@register_op("embedding")
def _embedding(ins, attrs):
    return _lookup_table_v2(ins, attrs)


@register_op("one_hot")
def _one_hot(ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    ids = x.reshape(x.shape[:-1]).astype(jnp.int32)
    return {"Out": jax.nn.one_hot(ids, depth, dtype=jnp.float32)}


@register_op("one_hot_v2")
def _one_hot_v2(ins, attrs):
    x = ins["X"][0].astype(jnp.int32)
    return {"Out": jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)}


# ---------------------------------------------------------------------------
# Misc NN
# ---------------------------------------------------------------------------

@register_op("label_smooth")
def _label_smooth(ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    if ins.get("PriorDist"):
        prior = ins["PriorDist"][0]
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return {"Out": out}


@register_op("interp_nearest")
def _interp_nearest(ins, attrs):
    x = ins["X"][0]
    oh, ow = attrs["out_h"], attrs["out_w"]
    n, c, h, w = x.shape
    ridx = (jnp.arange(oh) * (h / oh)).astype(jnp.int32)
    cidx = (jnp.arange(ow) * (w / ow)).astype(jnp.int32)
    return {"Out": x[:, :, ridx][:, :, :, cidx]}


@register_op("pad")
def _pad(ins, attrs):
    x = ins["X"][0]
    paddings = attrs["paddings"]
    value = attrs.get("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, cfg, constant_values=value)}


@register_op("pad2d")
def _pad2d(ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    cfg = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        return {"Out": jnp.pad(x, cfg,
                               constant_values=attrs.get("pad_value", 0.0))}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": jnp.pad(x, cfg, mode=jmode)}


# ---------------------------------------------------------------------------
# Fused scaled-dot-product attention (flash attention on TPU)
# ---------------------------------------------------------------------------

@register_op("scaled_dot_product_attention", needs_rng=True)
def _sdpa(ins, attrs):
    """Fused attention. Q: [B, H, S, D]; K: [B, Hkv, S, D]; V:
    [B, Hkv, S, Dv] with H a multiple of Hkv (grouped-query attention:
    query head j reads key/value head j // (H / Hkv); the flash kernel
    reads them in place). V has a head size of its own (latent
    attention: D 192, Dv 128): Out is [B, H, S, Dv] and the scale
    defaults to D ** -0.5 on the flash path, on `reference_attention`
    and on the unfused path alike. Optional KeyBias: [B, Sk] additive
    key bias. On TPU with no attention-prob dropout this lowers
    to the Pallas flash kernel (paddle_tpu/ops/pallas/flash_attention.py);
    otherwise the XLA reference path (identical semantics) runs, with
    upscale_in_train dropout on the normalized probs.

    Reference parity: fused CUDA attention in
    `paddle/fluid/operators/fused/multihead_matmul_op.cu` and
    `operators/math/bert_encoder_functor.cu` (inference-only there; this
    op also trains)."""
    from .pallas import flash_attention as _flash
    from .pallas import reference_attention as _ref_attn

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("KeyBias", [None])
    bias = bias[0] if bias else None
    mask = ins.get("Mask", [None])  # full additive mask, bcast to
    mask = mask[0] if mask else None  # [B, H, Sq, Sk]
    causal = attrs.get("causal", False)
    sm_scale = attrs.get("sm_scale", None)
    if sm_scale is not None and sm_scale <= 0:
        sm_scale = None
    p_drop = attrs.get("attn_dropout_prob", 0.0)
    is_test = attrs.get("is_test", False)
    drop_active = (not is_test) and p_drop > 0.0

    if mask is None:
        # The Pallas flash kernels from FLAGS_flash_attention_min_seq
        # keys up (4,096): no S^2 score buffer, and since PR 28 (operands
        # in their own dtype, 512 x 512 tiles) the faster path too.
        # Measured on a v5e (tools/attn_ab.py, B2 H12 D64 bfloat16,
        # forward + backward; PERF.md section 6, PR 28): 5.5 ms against
        # XLA's 20.2 at 4,096, 1.5 against 5.1 at 2,048, even from
        # 1,024 down (0.5 ms either way). The flag was not moved there:
        # below it the unfused path at the end of this op runs, which
        # the bert-base-s128 cell times (ROADMAP S8a).
        # Dropout-active training takes this path too: the kernel
        # applies prob-dropout in-VMEM (mask regenerated in backward
        # from the seed — no S^2 mask buffer in HBM).
        from ..utils import flags as _flags
        min_seq = int(_flags.get_flags(
            ["FLAGS_flash_attention_min_seq"])
            ["FLAGS_flash_attention_min_seq"])
        if jax.default_backend() == "tpu" and k.shape[-2] >= min_seq:
            seed = None
            if drop_active:
                seed = jax.random.randint(
                    attrs["_rng_key"], (1,), 0, 2 ** 31 - 1,
                    dtype=jnp.int32)
            return {"Out": _flash(q, k, v, key_bias=bias, causal=causal,
                                  sm_scale=sm_scale,
                                  dropout_p=p_drop if drop_active
                                  else 0.0,
                                  dropout_seed=seed)}
        if not drop_active:
            return {"Out": _ref_attn(q, k, v, key_bias=bias,
                                     causal=causal, sm_scale=sm_scale)}

    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1)
                for t in (k, v))
    # Unfused path with dropout on probs (matches layers.softmax+dropout).
    # MXU note: keep the matmul inputs in their compute dtype (bf16 under
    # AMP) with f32 ACCUMULATION — an f32 upcast before the einsum would
    # push the contraction off the bf16 MXU path (~3x slower on TPU).
    import math as _math
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / _math.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    if mask is not None:
        # [Sq,Sk] -> [1,1,Sq,Sk]; [B,Sq,Sk] -> [B,1,Sq,Sk] (head axis
        # inserted at dim 1, NOT prepended — [1,B,Sq,Sk] would misalign
        # batch with heads)
        if mask.ndim == 2:
            mask = mask[None, None]
        elif mask.ndim == 3:
            mask = mask[:, None]
        if mask.dtype == jnp.bool_:  # True = attend (paddle semantics)
            s = jnp.where(mask, s, -1e30)
        else:
            s = s + mask.astype(jnp.float32)
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(rows >= cols, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    if drop_active:
        keep = remat_names.keep(
            jax.random.bernoulli(attrs["_rng_key"], 1.0 - p_drop,
                                 probs.shape), remat_names.DROPOUT_MASK)
        probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return {"Out": out.astype(q.dtype)}


# ---------------------------------------------------------------------------
# recurrent cells over lax.scan (reference: operators/lstm_op.cc /
# gru_op.cc + python/paddle/fluid/layers/rnn.py LSTMCell/GRUCell).
# TPU-native: one op = the FULL sequence, scanned by XLA (static trip
# count -> unrolled/pipelined on device), gates fused into two matmuls
# per step that land on the MXU.
# ---------------------------------------------------------------------------

@register_op("lstm_seq")
def _lstm_seq(ins, attrs):
    """Single-layer LSTM over a [B,T,D] batch-major sequence.
    Gate layout i,f,g,o in the 4H weight axis."""
    x = ins["Input"][0]
    w_ih = ins["WeightIh"][0]   # (4H, D)
    w_hh = ins["WeightHh"][0]   # (4H, H)
    b = ins["Bias"][0]          # (4H,)
    h0 = ins["InitH"][0]        # (B, H)
    c0 = ins["InitC"][0]        # (B, H)
    reverse = attrs.get("is_reverse", False)
    xs = jnp.swapaxes(x, 0, 1)  # (T,B,D) scan axis first
    if reverse:
        xs = xs[::-1]
    x_proj = jnp.einsum("tbd,gd->tbg", xs, w_ih) + b  # hoisted MXU matmul

    def step(carry, xp):
        h, c = carry
        gates = xp + h @ w_hh.T
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (h_last, c_last), ys = lax.scan(step, (h0, c0), x_proj)
    if reverse:
        ys = ys[::-1]
    return {"Out": jnp.swapaxes(ys, 0, 1), "LastH": h_last,
            "LastC": c_last}


@register_op("gru_seq")
def _gru_seq(ins, attrs):
    """Single-layer GRU over [B,T,D]; gate layout r,z,n in the 3H axis."""
    x = ins["Input"][0]
    w_ih = ins["WeightIh"][0]   # (3H, D)
    w_hh = ins["WeightHh"][0]   # (3H, H)
    b_ih = ins["BiasIh"][0]     # (3H,)
    b_hh = ins["BiasHh"][0]     # (3H,)
    h0 = ins["InitH"][0]
    reverse = attrs.get("is_reverse", False)
    xs = jnp.swapaxes(x, 0, 1)
    if reverse:
        xs = xs[::-1]
    x_proj = jnp.einsum("tbd,gd->tbg", xs, w_ih) + b_ih

    def step(h, xp):
        hp = h @ w_hh.T + b_hh
        xr, xz, xn = jnp.split(xp, 3, axis=-1)
        hr, hz, hn = jnp.split(hp, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h = (1.0 - z) * n + z * h
        return h, h

    h_last, ys = lax.scan(step, h0, x_proj)
    if reverse:
        ys = ys[::-1]
    return {"Out": jnp.swapaxes(ys, 0, 1), "LastH": h_last}


# ---------------------------------------------------------------------------
# extended activations (reference: operators/activation_op.cc registrations)
# ---------------------------------------------------------------------------

@register_op("selu")
def _selu(ins, attrs):
    x = ins["X"][0]
    scale = attrs.get("scale", 1.0507009873554805)
    alpha = attrs.get("alpha", 1.6732632423543772)
    return {"Out": scale * jnp.where(x > 0, x,
                                     alpha * (jnp.exp(x) - 1.0))}


@register_op("softshrink")
def _softshrink(ins, attrs):
    x = ins["X"][0]
    l = attrs.get("lambda", attrs.get("threshold", 0.5))
    return {"Out": jnp.where(x > l, x - l, jnp.where(x < -l, x + l, 0.0))}


@register_op("hard_shrink")
def _hard_shrink(ins, attrs):
    x = ins["X"][0]
    t = attrs.get("threshold", 0.5)
    return {"Out": jnp.where(jnp.abs(x) > t, x, 0.0)}


@register_op("tanh_shrink")
def _tanh_shrink(ins, attrs):
    x = ins["X"][0]
    return {"Out": x - jnp.tanh(x)}


@register_op("brelu")
def _brelu(ins, attrs):
    x = ins["X"][0]
    t_min = attrs.get("t_min", 0.0)
    t_max = attrs.get("t_max", 24.0)
    return {"Out": jnp.clip(x, t_min, t_max)}


@register_op("soft_relu")
def _soft_relu(ins, attrs):
    x = ins["X"][0]
    t = attrs.get("threshold", 40.0)
    return {"Out": jnp.log1p(jnp.exp(jnp.clip(x, -t, t)))}


@register_op("expm1")
def _expm1(ins, attrs):
    return {"Out": jnp.expm1(ins["X"][0])}


@register_op("tan")
def _tan(ins, attrs):
    return {"Out": jnp.tan(ins["X"][0])}


@register_op("acosh")
def _acosh(ins, attrs):
    return {"Out": jnp.arccosh(ins["X"][0])}


@register_op("asinh")
def _asinh(ins, attrs):
    return {"Out": jnp.arcsinh(ins["X"][0])}


@register_op("atanh")
def _atanh(ins, attrs):
    return {"Out": jnp.arctanh(ins["X"][0])}


@register_op("maxout")
def _maxout(ins, attrs):
    # reference: maxout_op.cc — NCHW channel groups
    x = ins["X"][0]
    groups = attrs["groups"]
    axis = attrs.get("axis", 1)
    c = x.shape[axis]
    new_shape = (x.shape[:axis] + (c // groups, groups)
                 + x.shape[axis + 1:])
    return {"Out": jnp.max(x.reshape(new_shape), axis=axis + 1)}


@register_op("logit")
def _logit(ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("eps", 1e-6)
    xc = jnp.clip(x, eps, 1.0 - eps)
    return {"Out": jnp.log(xc / (1.0 - xc))}


@register_op("celu")
def _celu(ins, attrs):
    x = ins["X"][0]
    alpha = attrs.get("alpha", 1.0)
    return {"Out": jnp.where(x > 0, x,
                             alpha * (jnp.exp(x / alpha) - 1.0))}


# ---------------------------------------------------------------------------
# extended norm / conv / pool (reference: operators/*norm*, conv3d, pool3d,
# lrn_op, spectral_norm_op, data_norm_op, row_conv_op)
# ---------------------------------------------------------------------------

@register_op("norm")
def _norm(ins, attrs):
    # l2_normalize (reference: norm_op.cc)
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": x / n, "Norm": n}


@register_op("lrn")
def _lrn(ins, attrs):
    # reference: lrn_op.cc — local response norm across channels (NCHW)
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": x / jnp.power(mid, beta), "MidOut": mid}


@register_op("spectral_norm")
def _spectral_norm(ins, attrs):
    # reference: spectral_norm_op.cc — power-iteration weight norm
    w, u, v = ins["Weight"][0], ins["U"][0], ins["V"][0]
    dim = attrs.get("dim", 0)
    power_iters = attrs.get("power_iters", 1)
    eps = attrs.get("eps", 1e-12)
    wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
    for _ in range(max(power_iters, 0)):
        v = wm.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = wm @ v
        u = u / (jnp.linalg.norm(u) + eps)
    sigma = u @ wm @ v
    return {"Out": w / sigma}


@register_op("data_norm")
def _data_norm(ins, attrs):
    # reference: data_norm_op.cc — normalization by accumulated stats
    x = ins["X"][0]
    size = ins["BatchSize"][0]
    sums = ins["BatchSum"][0]
    sqs = ins["BatchSquareSum"][0]
    eps = attrs.get("epsilon", 1e-4)
    mean = sums / size
    scale = jnp.sqrt(size / (sqs - size * jnp.square(mean) + eps))
    y = (x - mean) * scale
    return {"Y": y, "Means": jnp.broadcast_to(mean, x.shape),
            "Scales": jnp.broadcast_to(scale, x.shape)}


@register_op("row_conv")
def _row_conv(ins, attrs):
    # reference: row_conv_op.cc — lookahead row convolution [B, T, D]
    x, filt = ins["X"][0], ins["Filter"][0]
    future = filt.shape[0]
    pad = jnp.pad(x, ((0, 0), (0, future - 1), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1]] * filt[i] for i in range(future))
    return {"Out": out}


@register_op("conv3d")
def _conv3d(ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    stride = attrs.get("strides", [1, 1, 1])
    pad = attrs.get("paddings", [0, 0, 0])
    dil = attrs.get("dilations", [1, 1, 1])
    groups = attrs.get("groups", 1)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": out}


@register_op("pool3d")
def _pool3d(ins, attrs):
    x = ins["X"][0]
    ksize = attrs.get("ksize", [2, 2, 2])
    stride = attrs.get("strides", ksize)
    pad = attrs.get("paddings", [0, 0, 0])
    ptype = attrs.get("pooling_type", "max")
    dims = (1, 1) + tuple(ksize)
    strides = (1, 1) + tuple(stride)
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides,
                                    pads)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                  pads)
        cnt = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                    dims, strides, pads)
        out = s / cnt
    return {"Out": out}


@register_op("max_pool2d_with_index")
def _max_pool2d_with_index(ins, attrs):
    x = ins["X"][0]
    ksize = attrs.get("ksize", [2, 2])
    stride = attrs.get("strides", ksize)
    pad = attrs.get("paddings", [0, 0])
    n, c, h, w = x.shape
    kh, kw = ksize
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])),
                 constant_values=-jnp.inf)
    oh = (h + 2 * pad[0] - kh) // stride[0] + 1
    ow = (w + 2 * pad[1] - kw) // stride[1] + 1
    # unfold windows: [n, c, oh, ow, kh*kw]
    idx_h = (jnp.arange(oh)[:, None] * stride[0]
             + jnp.arange(kh)[None, :])  # [oh, kh]
    idx_w = (jnp.arange(ow)[:, None] * stride[1]
             + jnp.arange(kw)[None, :])  # [ow, kw]
    wins = xp[:, :, idx_h[:, :, None, None], idx_w[None, None, :, :]]
    wins = wins.transpose(0, 1, 2, 4, 3, 5).reshape(n, c, oh, ow, kh * kw)
    out = jnp.max(wins, -1)
    amax = jnp.argmax(wins, -1)
    # flat index in the UNPADDED input (reference semantics)
    rh = amax // kw + idx_h[:, 0][None, None, :, None] - pad[0]
    rw = amax % kw + idx_w[:, 0][None, None, None, :] - pad[1]
    flat = (rh * w + rw).astype(jnp.int64)
    return {"Out": out, "Mask": flat}


@register_op("conv3d_transpose")
def _conv3d_transpose(ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    stride = attrs.get("strides", [1, 1, 1])
    pad = attrs.get("paddings", [0, 0, 0])
    out = jax.lax.conv_transpose(
        x, jnp.swapaxes(w, 0, 1), strides=stride,
        padding=[(p, p) for p in pad],
        dimension_numbers=("NCDHW", "IODHW", "NCDHW"),
        transpose_kernel=True)
    return {"Output": out}


@register_op("affine_channel")
def _affine_channel(ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    layout = attrs.get("data_layout", "NCHW")
    if layout == "NCHW":
        shape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        shape = (1,) * (x.ndim - 1) + (-1,)
    return {"Out": x * scale.reshape(shape) + bias.reshape(shape)}


@register_op("fsp")
def _fsp(ins, attrs):
    # reference: fsp_op.cc — flow of solution procedure matrix (distill)
    x, y = ins["X"][0], ins["Y"][0]
    n, cx = x.shape[0], x.shape[1]
    cy = y.shape[1]
    hw = x.shape[2] * x.shape[3]
    xf = x.reshape(n, cx, hw)
    yf = y.reshape(n, cy, hw)
    return {"Out": jnp.einsum("nch,ndh->ncd", xf, yf) / hw}


@register_op("pixel_shuffle")
def _pixel_shuffle(ins, attrs):
    x = ins["X"][0]
    r = attrs.get("upscale_factor", 1)
    n, c, h, w = x.shape
    oc = c // (r * r)
    out = x.reshape(n, oc, r, r, h, w).transpose(0, 1, 4, 2, 5, 3)
    return {"Out": out.reshape(n, oc, h * r, w * r)}


@register_op("shuffle_channel")
def _shuffle_channel(ins, attrs):
    x = ins["X"][0]
    group = attrs.get("group", 1)
    n, c, h, w = x.shape
    out = x.reshape(n, group, c // group, h, w).transpose(0, 2, 1, 3, 4)
    return {"Out": out.reshape(n, c, h, w)}


@register_op("space_to_depth")
def _space_to_depth(ins, attrs):
    x = ins["X"][0]
    b = attrs.get("blocksize", 1)
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // b, b, w // b, b)
    out = out.transpose(0, 3, 5, 1, 2, 4)
    return {"Out": out.reshape(n, c * b * b, h // b, w // b)}


@register_op("temporal_shift")
def _temporal_shift(ins, attrs):
    # reference: temporal_shift_op.cc — shift 1/4 channels +/-1 in time
    x = ins["X"][0]
    seg = attrs.get("seg_num", 1)
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg
    xr = x.reshape(n, seg, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    fwd = jnp.concatenate([xr[:, 1:, :c1], jnp.zeros_like(xr[:, :1, :c1])],
                          axis=1)
    back = jnp.concatenate([jnp.zeros_like(xr[:, :1, c1:c2]),
                            xr[:, :-1, c1:c2]], axis=1)
    keep = xr[:, :, c2:]
    out = jnp.concatenate([fwd, back, keep], axis=2)
    return {"Out": out.reshape(nt, c, h, w)}


@register_op("grid_sampler")
def _grid_sampler(ins, attrs):
    # reference: grid_sampler_op.cc — bilinear sampling, align_corners
    x, grid = ins["X"][0], ins["Grid"][0]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    x1, y1 = x0 + 1, y0 + 1
    wa = (x1 - gx) * (y1 - gy)
    wb = (x1 - gx) * (gy - y0)
    wc = (gx - x0) * (y1 - gy)
    wd = (gx - x0) * (gy - y0)

    def sample(yy, xx):
        yi = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xi = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0)
                 & (xx <= w - 1)).astype(x.dtype)
        ni = jnp.arange(n)[:, None, None]
        v = x[ni, :, yi, xi]  # [n, gh, gw, c]
        return v * valid[..., None]

    out = (sample(y0, x0) * wa[..., None] + sample(y1, x0) * wb[..., None]
           + sample(y0, x1) * wc[..., None]
           + sample(y1, x1) * wd[..., None])
    return {"Output": out.transpose(0, 3, 1, 2)}


@register_op("affine_grid")
def _affine_grid(ins, attrs):
    theta = ins["Theta"][0]
    out_shape = attrs.get("output_shape")
    if ins.get("OutputShape"):
        try:
            out_shape = [int(v) for v in ins["OutputShape"][0]]
        except Exception:  # traced under jit: static attr required
            pass
    n, _, h, w = out_shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], -1).reshape(1, h * w, 3)
    grid = base @ jnp.swapaxes(theta, 1, 2)  # [n, h*w, 2]
    return {"Output": grid.reshape(theta.shape[0], h, w, 2)}


@register_op("unfold")
def _unfold(ins, attrs):
    # reference: unfold_op.cc (im2col); out [N, C*kh*kw, L]
    x = ins["X"][0]
    k = attrs["kernel_sizes"]
    s = attrs.get("strides", [1, 1])
    p = attrs.get("paddings", [0, 0, 0, 0])
    d = attrs.get("dilations", [1, 1])
    n, c, h, w = x.shape
    if len(p) == 2:
        p = [p[0], p[1], p[0], p[1]]
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[2]), (p[1], p[3])))
    kh, kw = k
    oh = (xp.shape[2] - (d[0] * (kh - 1) + 1)) // s[0] + 1
    ow = (xp.shape[3] - (d[1] * (kw - 1) + 1)) // s[1] + 1
    ih = jnp.arange(oh)[:, None] * s[0] + jnp.arange(kh)[None, :] * d[0]
    iw = jnp.arange(ow)[:, None] * s[1] + jnp.arange(kw)[None, :] * d[1]
    cols = xp[:, :, ih[:, :, None, None], iw[None, None, :, :]]
    # [n, c, oh, kh, ow, kw] -> [n, c*kh*kw, oh*ow]
    cols = cols.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * kh * kw,
                                                    oh * ow)
    return {"Y": cols}


@register_op("im2sequence")
def _im2sequence(ins, attrs):
    # reference: im2sequence_op.cc — image patches to sequence rows
    x = ins["X"][0]
    k = attrs["kernels"]
    s = attrs.get("strides", [1, 1])
    p = attrs.get("paddings", [0, 0, 0, 0])
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[2]), (p[1], p[3])))
    kh, kw = k
    oh = (xp.shape[2] - kh) // s[0] + 1
    ow = (xp.shape[3] - kw) // s[1] + 1
    ih = jnp.arange(oh)[:, None] * s[0] + jnp.arange(kh)[None, :]
    iw = jnp.arange(ow)[:, None] * s[1] + jnp.arange(kw)[None, :]
    patches = xp[:, :, ih[:, :, None, None], iw[None, None, :, :]]
    # [n, c, oh, kh, ow, kw] -> [n*oh*ow, c*kh*kw]
    patches = patches.transpose(0, 2, 4, 1, 3, 5).reshape(
        n * oh * ow, c * kh * kw)
    return {"Out": patches}


@register_op("spp")
def _spp(ins, attrs):
    """Spatial pyramid pooling (reference: spp_op.h:26): levels
    p=0..pyramid_height-1 pool to 2^p x 2^p bins with
    kernel=ceil(dim/bins), pad=(kernel*bins-dim+1)//2, then flatten and
    concat along channels. Composes the registered pool2d kernel —
    XLA fuses the reduce_windows."""
    x = ins["X"][0]
    pyramid_height = int(attrs.get("pyramid_height", 1))
    ptype = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    import math as _math

    from .registry import run_op as _run

    outs = []
    for p in range(pyramid_height):
        bins = 2 ** p
        kh = _math.ceil(h / bins)
        kw = _math.ceil(w / bins)
        ph = (kh * bins - h + 1) // 2
        pw = (kw * bins - w + 1) // 2
        lvl = _run("pool2d", {"X": [x]},
                   {"pooling_type": ptype, "ksize": [kh, kw],
                    "strides": [kh, kw], "paddings": [ph, pw],
                    "exclusive": True})["Out"][0]
        outs.append(lvl.reshape(n, c * bins * bins))
    return {"Out": jnp.concatenate(outs, axis=1)}
