"""layers.* op wrappers (reference: `python/paddle/fluid/layers/nn.py`, 15k
LoC of ~300 builders). Each builder creates params via LayerHelper (init ops
go to the startup program) and appends its compute op; in dygraph mode the
same builders execute eagerly."""
from __future__ import annotations

import numpy as np

from .. import framework
from ..framework import Variable, in_dygraph_mode
from ..layer_helper import LayerHelper, apply_op
from ..initializer import ConstantInitializer, NormalInitializer
from ...core.types import normalize_dtype

__all__ = [
    "fc", "embedding", "conv2d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "group_norm", "instance_norm", "dropout", "relu",
    "sigmoid", "tanh", "sqrt", "square", "exp", "log", "abs", "ceil",
    "floor", "round", "reciprocal", "gelu", "leaky_relu", "elu", "relu6",
    "softplus", "softsign", "swish", "silu", "relu2", "hard_sigmoid", "hard_swish", "prelu",
    "softmax", "log_softmax", "matmul", "mul", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_max", "elementwise_min", "elementwise_pow",
    "elementwise_mod", "elementwise_floordiv", "reduce_sum",
    "reduce_mean", "reduce_max", "reduce_min", "reduce_prod", "reduce_all",
    "reduce_any", "mean", "accuracy", "topk", "one_hot", "clip",
    "clip_by_norm", "l2_normalize", "label_smooth", "pad", "pad2d",
    "unsqueeze", "squeeze", "stack", "unstack", "expand", "expand_as",
    "gather", "gather_nd", "scatter", "slice", "strided_slice", "split",
    "where", "cond_not_supported", "sequence_pool", "sequence_softmax",
    "sequence_mask", "sequence_expand", "sequence_reshape",
    "sequence_reverse", "image_resize", "resize_nearest", "flatten",
    "logsigmoid", "erf", "sin", "cos", "maximum", "minimum",
    "scaled_dot_product_attention",
]


def _single(op_type, inputs, attrs, dtype=None, helper=None):
    return apply_op(helper or op_type, op_type, inputs, attrs, ["Out"],
                    out_dtype=dtype)[0]


# ---------------------------------------------------------------------------
# parametric layers
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected (reference: layers/nn.py fc) = mul + bias + act."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    # one weight PER input; a named param_attr names only the first and
    # the copies auto-name (reference: LayerHelper.multiple_param_attr —
    # reusing the name would silently alias every input's weight)
    param_attrs = helper.multiple_param_attr(len(inputs))
    if not isinstance(param_attrs, (list, tuple)):
        param_attrs = [param_attrs] * len(inputs)
    mul_results = []
    for inp, w_attr in zip(inputs, param_attrs):
        in_dim = int(np.prod(inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(
            w_attr, shape=[in_dim, size], dtype=inp.dtype)
        out = _single("mul", {"X": [inp], "Y": [w]},
                      {"x_num_col_dims": num_flatten_dims,
                       "y_num_col_dims": 1}, dtype=inp.dtype, helper=helper)
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = _single("sum", {"X": mul_results}, {},
                           dtype=mul_results[0].dtype, helper=helper)
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(helper.param_attr, shape=list(size),
                                dtype=dtype)
    pad = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    return _single("lookup_table", {"W": [w], "Ids": [input]},
                   {"padding_idx": pad, "is_sparse": is_sparse,
                    "is_distributed": is_distributed},
                   dtype=dtype, helper=helper)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    """param_attr may be a Variable: convolve with that EXISTING filter
    instead of creating a parameter — the scan-over-blocks path passes
    per-iteration slices of stacked [L, out, in, kh, kw] filters
    (layers.Scan)."""
    helper = LayerHelper("conv2d",
                         param_attr=None if isinstance(param_attr, Variable)
                         else param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = (2.0 / fan_in) ** 0.5
    if isinstance(param_attr, Variable):
        if tuple(int(d) for d in param_attr.shape) != tuple(filter_shape):
            raise ValueError(
                "conv2d: provided filter var %r has shape %s, expected "
                "%s (pass the per-iteration slice, not the stack)"
                % (param_attr.name, tuple(param_attr.shape),
                   tuple(filter_shape)))
        w = param_attr
    else:
        w = helper.create_parameter(
            helper.param_attr, shape=filter_shape, dtype=input.dtype,
            default_initializer=NormalInitializer(0.0, std))
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = ([dilation, dilation] if isinstance(dilation, int)
                else list(dilation))
    pre_bias = apply_op(helper, "conv2d",
                        {"Input": [input], "Filter": [w]},
                        {"strides": stride, "paddings": padding,
                         "dilations": dilation, "groups": groups},
                        ["Output"], out_dtype=input.dtype)[0]
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape,
                                dtype=input.dtype)
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    pre_bias = apply_op(helper, "conv2d_transpose",
                        {"Input": [input], "Filter": [w]},
                        {"strides": stride, "paddings": padding,
                         "dilations": [dilation, dilation]
                         if isinstance(dilation, int) else list(dilation),
                         "groups": groups},
                        ["Output"], out_dtype=input.dtype)[0]
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True, data_format="NCHW"):
    attrs = {
        "pooling_type": pool_type,
        "ksize": [pool_size, pool_size] if isinstance(pool_size, int)
        else list(pool_size),
        "strides": [pool_stride, pool_stride]
        if isinstance(pool_stride, int) else list(pool_stride),
        "paddings": [pool_padding, pool_padding]
        if isinstance(pool_padding, int) else list(pool_padding),
        "global_pooling": global_pooling,
        "ceil_mode": ceil_mode,
        "exclusive": exclusive,
    }
    return _single("pool2d", {"X": [input]}, attrs, dtype=input.dtype)


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    return _single("pool2d", {"X": [input]},
                   {"pooling_type": pool_type, "ksize": list(pool_size),
                    "adaptive": True}, dtype=input.dtype)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=True,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = input.dtype if input.dtype != "float16" else "float32"
    scale = helper.create_parameter(
        helper.param_attr, shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, shape=[c], dtype=dtype,
                                   is_bias=True)

    if in_dygraph_mode():
        from ..dygraph import base as dy_base

        mean = dy_base.create_eager_parameter(
            None, [c], dtype, ConstantInitializer(0.0), trainable=False,
            name=moving_mean_name)
        var = dy_base.create_eager_parameter(
            None, [c], dtype, ConstantInitializer(1.0), trainable=False,
            name=moving_variance_name)
        outs = dy_base.trace_op(
            "batch_norm",
            {"X": [input], "Scale": [scale], "Bias": [bias],
             "Mean": [mean], "Variance": [var]},
            {"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
             "data_layout": data_layout,
             "use_global_stats": use_global_stats},
            ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"])
        mean._assign_value(outs[1])
        var._assign_value(outs[2])
        y = outs[0]
        return helper.append_activation(y)

    from ..framework import unique_name

    mean = helper.create_parameter(
        framework_attr_for(moving_mean_name or unique_name(
            helper.name + ".mean")),
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    mean.trainable = False
    var = helper.create_parameter(
        framework_attr_for(moving_variance_name or unique_name(
            helper.name + ".var")),
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    var.trainable = False

    saved_mean = helper.create_variable_for_type_inference(dtype)
    saved_var = helper.create_variable_for_type_inference(dtype)
    y = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(y)


def framework_attr_for(name):
    from ..param_attr import ParamAttr

    return ParamAttr(name=name, trainable=False)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """scale/shift accept a Variable to normalize with EXISTING affine
    vars instead of creating parameters — the scan-over-layers body
    passes per-iteration slices of stacked [L, H] scale/bias params
    (layers.Scan)."""
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if isinstance(scale, Variable):
        inputs["Scale"] = [scale]
    elif scale:
        s = helper.create_parameter(
            helper.param_attr, shape=norm_shape, dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if isinstance(shift, Variable):
        inputs["Bias"] = [shift]
    elif shift:
        b = helper.create_parameter(helper.bias_attr, shape=norm_shape,
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    outs = apply_op(helper, "layer_norm", inputs,
                    {"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
                    ["Y", "Mean", "Variance"], out_dtype=input.dtype)
    return helper.append_activation(outs[0])


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            helper.param_attr, shape=[c], dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            helper.bias_attr, shape=[c], dtype=input.dtype, is_bias=True)]
    outs = apply_op(helper, "group_norm", inputs,
                    {"groups": groups, "epsilon": epsilon},
                    ["Y", "Mean", "Variance"], out_dtype=input.dtype)
    return helper.append_activation(outs[0])


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            helper.param_attr, shape=[c], dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            helper.bias_attr, shape=[c], dtype=input.dtype, is_bias=True)]
    outs = apply_op(helper, "instance_norm", inputs, {"epsilon": epsilon},
                    ["Y", "SavedMean", "SavedVariance"],
                    out_dtype=input.dtype)
    return outs[0]


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    outs = apply_op("dropout", "dropout", {"X": [x]},
                    {"dropout_prob": dropout_prob, "is_test": is_test,
                     "seed": seed or 0,
                     "dropout_implementation": dropout_implementation},
                    ["Out", "Mask"], out_dtype=x.dtype)
    return outs[0]


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        helper.param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25))
    return apply_op(helper, "prelu", {"X": [x], "Alpha": [alpha]},
                    {"mode": mode}, ["Out"], out_dtype=x.dtype)[0]


# ---------------------------------------------------------------------------
# functional (no params)
# ---------------------------------------------------------------------------

def _make_act(op_type, **extra):
    def f(x, name=None, **kwargs):
        attrs = dict(extra)
        for k in list(kwargs):
            if k in ("alpha", "beta", "threshold", "slope", "offset",
                     "approximate", "scale"):
                attrs[k] = kwargs[k]
        return _single(op_type, {"X": [x]}, attrs, dtype=x.dtype)

    f.__name__ = op_type
    return f


relu = _make_act("relu")
sigmoid = _make_act("sigmoid")
tanh = _make_act("tanh")
sqrt = _make_act("sqrt")
square = _make_act("square")
exp = _make_act("exp")
log = _make_act("log")
abs = _make_act("abs")
ceil = _make_act("ceil")
floor = _make_act("floor")
round = _make_act("round")
reciprocal = _make_act("reciprocal")
gelu = _make_act("gelu")
leaky_relu = _make_act("leaky_relu")
elu = _make_act("elu")
relu6 = _make_act("relu6")
softplus = _make_act("softplus")
softsign = _make_act("softsign")
swish = _make_act("swish")
silu = _make_act("silu")
relu2 = _make_act("relu2")
hard_sigmoid = _make_act("hard_sigmoid")
hard_swish = _make_act("hard_swish")
logsigmoid = _make_act("logsigmoid")
erf = _make_act("erf")
sin = _make_act("sin")
cos = _make_act("cos")


def softmax(input, use_cudnn=False, name=None, axis=-1):
    return _single("softmax", {"X": [input]}, {"axis": axis},
                   dtype=input.dtype)


def log_softmax(input, axis=-1, name=None):
    return _single("log_softmax", {"X": [input]}, {"axis": axis},
                   dtype=input.dtype)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    return _single("matmul", {"X": [x], "Y": [y]},
                   {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                    "alpha": float(alpha)}, dtype=x.dtype)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    return _single("mul", {"X": [x], "Y": [y]},
                   {"x_num_col_dims": x_num_col_dims,
                    "y_num_col_dims": y_num_col_dims}, dtype=x.dtype)


def _make_elementwise(op_type):
    def f(x, y, axis=-1, act=None, name=None):
        out = _single(op_type, {"X": [x], "Y": [y]}, {"axis": axis},
                      dtype=x.dtype)
        if act:
            out = _single(act, {"X": [out]}, {}, dtype=x.dtype)
        return out

    f.__name__ = op_type
    return f


elementwise_add = _make_elementwise("elementwise_add")
elementwise_sub = _make_elementwise("elementwise_sub")
elementwise_mul = _make_elementwise("elementwise_mul")
elementwise_div = _make_elementwise("elementwise_div")
elementwise_max = _make_elementwise("elementwise_max")
elementwise_min = _make_elementwise("elementwise_min")
elementwise_pow = _make_elementwise("elementwise_pow")
elementwise_mod = _make_elementwise("elementwise_mod")
elementwise_floordiv = _make_elementwise("elementwise_floordiv")


def maximum(x, y, name=None):
    return _single("maximum", {"X": [x], "Y": [y]}, {}, dtype=x.dtype)


def minimum(x, y, name=None):
    return _single("minimum", {"X": [x], "Y": [y]}, {}, dtype=x.dtype)


def logical_and(x, y, out=None, name=None):
    return _single("logical_and", {"X": [x], "Y": [y]}, {}, dtype="bool")


def logical_or(x, y, out=None, name=None):
    return _single("logical_or", {"X": [x], "Y": [y]}, {}, dtype="bool")


def logical_xor(x, y, out=None, name=None):
    return _single("logical_xor", {"X": [x], "Y": [y]}, {}, dtype="bool")


def logical_not(x, out=None, name=None):
    return _single("logical_not", {"X": [x]}, {}, dtype="bool")


def _make_reduce(op_type):
    def f(input, dim=None, keep_dim=False, name=None):
        if dim is None:
            attrs = {"reduce_all": True, "dim": [0], "keep_dim": keep_dim}
        else:
            attrs = {"dim": dim if isinstance(dim, (list, tuple)) else [dim],
                     "keep_dim": keep_dim, "reduce_all": False}
        return _single(op_type, {"X": [input]}, attrs, dtype=input.dtype)

    f.__name__ = op_type
    return f


reduce_sum = _make_reduce("reduce_sum")
reduce_mean = _make_reduce("reduce_mean")
reduce_max = _make_reduce("reduce_max")
reduce_min = _make_reduce("reduce_min")
reduce_prod = _make_reduce("reduce_prod")
reduce_all = _make_reduce("reduce_all")
reduce_any = _make_reduce("reduce_any")


def mean(x, name=None):
    return _single("mean", {"X": [x]}, {}, dtype=x.dtype)


def accuracy(input, label, k=1, correct=None, total=None):
    topk_out, topk_indices = topk(input, k=k)
    outs = apply_op("accuracy", "accuracy",
                    {"Out": [topk_out], "Indices": [topk_indices],
                     "Label": [label]}, {},
                    ["Accuracy", "Correct", "Total"], out_dtype="float32")
    return outs[0]


def topk(input, k=1, name=None):
    outs = apply_op("top_k", "top_k", {"X": [input]}, {"k": k},
                    ["Out", "Indices"], out_dtype=input.dtype)
    return outs[0], outs[1]


def one_hot(input, depth, allow_out_of_range=False):
    return _single("one_hot", {"X": [input]}, {"depth": depth},
                   dtype="float32")


def clip(x, min, max, name=None):
    return _single("clip", {"X": [x]}, {"min": float(min), "max": float(max)},
                   dtype=x.dtype)


def clip_by_norm(x, max_norm, name=None):
    return _single("clip_by_norm", {"X": [x]}, {"max_norm": float(max_norm)},
                   dtype=x.dtype)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    sq = _single("square", {"X": [x]}, {}, dtype=x.dtype)
    ssum = reduce_sum(sq, dim=axis, keep_dim=True)
    norm = _single("sqrt", {"X": [elementwise_add(
        ssum, fill_like_eps(ssum, epsilon))]}, {}, dtype=x.dtype)
    return elementwise_div(x, norm)


def fill_like_eps(ref, eps):
    from . import tensor as t

    return t.fill_constant(shape=[1], dtype=ref.dtype, value=eps)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    ins = {"X": [label]}
    if prior_dist is not None:
        ins["PriorDist"] = [prior_dist]
    return _single("label_smooth", ins, {"epsilon": float(epsilon)},
                   dtype=dtype)


def pad(x, paddings, pad_value=0.0, name=None):
    return _single("pad", {"X": [x]},
                   {"paddings": list(paddings), "pad_value": pad_value},
                   dtype=x.dtype)


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    return _single("pad2d", {"X": [input]},
                   {"paddings": list(paddings), "mode": mode,
                    "pad_value": pad_value}, dtype=input.dtype)


def unsqueeze(input, axes, name=None):
    outs = apply_op("unsqueeze2", "unsqueeze2", {"X": [input]},
                    {"axes": list(axes)}, ["Out", "XShape"],
                    out_dtype=input.dtype)
    return outs[0]


def squeeze(input, axes, name=None):
    outs = apply_op("squeeze2", "squeeze2", {"X": [input]},
                    {"axes": list(axes)}, ["Out", "XShape"],
                    out_dtype=input.dtype)
    return outs[0]


def stack(x, axis=0, name=None):
    xs = x if isinstance(x, (list, tuple)) else [x]
    return apply_op("stack", "stack", {"X": list(xs)}, {"axis": axis},
                    ["Y"], out_dtype=xs[0].dtype)[0]


def unstack(x, axis=0, num=None):
    n = num or x.shape[axis]
    return apply_op("unstack", "unstack", {"X": [x]}, {"axis": axis},
                    {"Y": n}, out_dtype=x.dtype)


def expand(x, expand_times, name=None):
    return _single("expand", {"X": [x]}, {"expand_times": list(expand_times)},
                   dtype=x.dtype)


def expand_as(x, target_tensor, name=None):
    return _single("expand_as_v2", {"X": [x], "Y": [target_tensor]},
                   {"target_shape": list(target_tensor.shape)},
                   dtype=x.dtype)


def gather(input, index, overwrite=True):
    return _single("gather", {"X": [input], "Index": [index]}, {},
                   dtype=input.dtype)


def gather_nd(input, index, name=None):
    return _single("gather_nd", {"X": [input], "Index": [index]}, {},
                   dtype=input.dtype)


def scatter(input, index, updates, name=None, overwrite=True):
    return _single("scatter",
                   {"X": [input], "Ids": [index], "Updates": [updates]},
                   {"overwrite": overwrite}, dtype=input.dtype)


def slice(input, axes, starts, ends):
    return _single("slice", {"Input": [input]},
                   {"axes": list(axes), "starts": list(starts),
                    "ends": list(ends), "decrease_axis": []},
                   dtype=input.dtype)


def strided_slice(input, axes, starts, ends, strides):
    return _single("strided_slice", {"Input": [input]},
                   {"axes": list(axes), "starts": list(starts),
                    "ends": list(ends), "strides": list(strides)},
                   dtype=input.dtype)


def split(input, num_or_sections, dim=-1, name=None):
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": dim}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    return apply_op("split", "split", {"X": [input]}, attrs, {"Out": n},
                    out_dtype=input.dtype)


def where(condition, x=None, y=None, name=None):
    return _single("where", {"Condition": [condition], "X": [x], "Y": [y]},
                   {}, dtype=x.dtype)


def cond_not_supported(*a, **k):
    raise NotImplementedError(
        "layers.cond: use lax.cond-backed control flow (planned)")


def flatten(x, axis=1, name=None):
    outs = apply_op("flatten2", "flatten2", {"X": [x]}, {"axis": axis},
                    ["Out", "XShape"], out_dtype=x.dtype)
    return outs[0]


# -- sequence ops (padded + Length mask; SURVEY.md §7 hard part (a)) -------

def sequence_pool(input, pool_type, is_test=False, pad_value=0.0,
                  length=None):
    if pool_type.upper() not in ("AVERAGE", "SUM", "SQRT", "LAST",
                                 "FIRST", "MAX"):
        # construction-time validation, matching the reference's InEnum
        # (sequence_pool_op.cc:69)
        raise ValueError("sequence_pool pool_type must be one of "
                         "average/sum/sqrt/last/first/max, got %r"
                         % (pool_type,))
    ins = {"X": [input]}
    if length is not None:
        ins["Length"] = [length]
    outs = apply_op("sequence_pool", "sequence_pool", ins,
                    {"pooltype": pool_type.upper()}, ["Out", "MaxIndex"],
                    out_dtype=input.dtype)
    return outs[0]


def sequence_softmax(input, use_cudnn=False, name=None, length=None):
    ins = {"X": [input]}
    if length is not None:
        ins["Length"] = [length]
    return _single("sequence_softmax", ins, {}, dtype=input.dtype)


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    return _single("sequence_mask", {"X": [x]},
                   {"maxlen": maxlen or -1, "out_dtype": dtype}, dtype=dtype)


def sequence_expand(x, y, ref_level=-1, name=None):
    return _single("sequence_expand", {"X": [x], "Y": [y]},
                   {"ref_level": ref_level}, dtype=x.dtype)


def sequence_reshape(input, new_dim):
    return _single("sequence_reshape", {"X": [input]}, {"new_dim": new_dim},
                   dtype=input.dtype)


def sequence_reverse(x, name=None, length=None):
    ins = {"X": [x]}
    if length is not None:
        ins["Length"] = [length]
    return apply_op("sequence_reverse", "sequence_reverse", ins, {}, ["Y"],
                    out_dtype=x.dtype)[0]


def image_resize(input, out_shape=None, scale=None, resample="NEAREST",
                 name=None):
    if out_shape is None:
        h, w = input.shape[2] * scale, input.shape[3] * scale
    else:
        h, w = out_shape
    return _single("interp_nearest", {"X": [input]},
                   {"out_h": int(h), "out_w": int(w)}, dtype=input.dtype)


resize_nearest = image_resize


def scaled_dot_product_attention(q, k, v, key_bias=None, causal=False,
                                 sm_scale=None, attn_dropout_prob=0.0,
                                 is_test=False, name=None):
    """Fused attention over q [B, H, S, D], k [B, Hkv, S, D] and
    v [B, Hkv, S, Dv] (H a multiple of Hkv; V has a head size of its
    own, and the output [B, H, S, Dv] takes it); optional [B, Sk]
    additive key bias; `sm_scale` defaults to D ** -0.5. Lowers to the
    Pallas flash-attention kernel on TPU
    (paddle_tpu/ops/pallas/); reference fuses only inference attention
    (`operators/fused/multihead_matmul_op.cu`)."""
    ins = {"Q": [q], "K": [k], "V": [v]}
    if key_bias is not None:
        ins["KeyBias"] = [key_bias]
    return _single("scaled_dot_product_attention", ins,
                   {"causal": causal,
                    "sm_scale": -1.0 if sm_scale is None else float(sm_scale),
                    "attn_dropout_prob": float(attn_dropout_prob),
                    "is_test": is_test}, dtype=q.dtype)


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0,
                   name=None):
    """Reference: layers/nn.py uniform_random -> uniform_random op."""
    return _single("uniform_random", {},
                   {"shape": list(shape), "min": float(min),
                    "max": float(max), "seed": seed, "dtype": dtype},
                   dtype=dtype)


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32",
                    name=None):
    """Reference: layers/nn.py gaussian_random -> gaussian_random op."""
    return _single("gaussian_random", {},
                   {"shape": list(shape), "mean": float(mean),
                    "std": float(std), "seed": seed, "dtype": dtype},
                   dtype=dtype)
