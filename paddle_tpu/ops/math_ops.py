"""Dense math operators.

Reference parity: `paddle/fluid/operators/` — elementwise_* (with the `axis`
mid-broadcast rule, `elementwise_op_function.h`), `mul_op.cc` (x_num_col_dims
flattening), `matmul_op.cc` (transpose/alpha attrs), reduce_* ops, `scale`,
`sum`, `cast`, compare/logical ops. Each is a pure jax function; XLA fuses
elementwise chains into neighbouring matmuls (the reference needed dedicated
fusion passes, `ir/fuse_elewise_add_act_pass.cc`, to do this by hand).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register_op
from .remat_names import keep_narrow_product
from ..core.types import to_numpy_dtype


def _first(ins, slot):
    v = ins.get(slot) or []
    return v[0] if v else None


def _bcast_pair(x, y, axis):
    """Paddle elementwise broadcast: align y into x at `axis`."""
    if x.ndim == y.ndim:
        return x, y
    if x.ndim < y.ndim:
        y2, x2 = _bcast_pair(y, x, axis)
        return x2, y2
    if axis < 0:
        axis = x.ndim - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return x, y.reshape(new_shape)


def _register_elementwise(name, fn):
    @register_op("elementwise_" + name)
    def _ew(ins, attrs, _fn=fn):
        x, y = ins["X"][0], ins["Y"][0]
        x, y = _bcast_pair(x, y, attrs.get("axis", -1))
        return {"Out": _fn(x, y)}


_register_elementwise("add", jnp.add)
_register_elementwise("sub", jnp.subtract)
_register_elementwise("mul", jnp.multiply)
_register_elementwise("div", jnp.divide)
_register_elementwise("max", jnp.maximum)
_register_elementwise("min", jnp.minimum)
_register_elementwise("pow", jnp.power)
_register_elementwise("mod", jnp.mod)
_register_elementwise("floordiv", jnp.floor_divide)


@register_op("mul")
def _mul(ins, attrs):
    # reference: operators/mul_op.cc — flatten x to 2-D by x_num_col_dims.
    x, y = ins["X"][0], ins["Y"][0]
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = x.reshape((int(np.prod(x.shape[:xn])), -1))
    y2 = y.reshape((int(np.prod(y.shape[:yn])), -1))
    out = keep_narrow_product(x2 @ y2, y2)
    return {"Out": out.reshape(x.shape[:xn] + y.shape[yn:])}


@register_op("matmul")
def _matmul(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    tx, ty = attrs.get("transpose_X", False), attrs.get("transpose_Y", False)
    alpha = attrs.get("alpha", 1.0)
    if x.ndim == 1:
        x = x[None, :]
    if y.ndim == 1:
        y = y[:, None]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": keep_narrow_product(out, y)}


@register_op("matmul_v2")
def _matmul_v2(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    if attrs.get("trans_x", False):
        x = jnp.swapaxes(x, -1, -2)
    if attrs.get("trans_y", False):
        y = jnp.swapaxes(y, -1, -2)
    return {"Out": keep_narrow_product(jnp.matmul(x, y), y)}


@register_op("scale")
def _scale(ins, attrs):
    x = ins["X"][0]
    scale = attrs.get("scale", 1.0)
    bias = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        out = x * jnp.asarray(scale, x.dtype) + jnp.asarray(bias, x.dtype)
    else:
        out = (x + jnp.asarray(bias, x.dtype)) * jnp.asarray(scale, x.dtype)
    return {"Out": out}


@register_op("sum")
def _sum(ins, attrs):
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("mean")
def _mean(ins, attrs):
    return {"Out": jnp.mean(ins["X"][0]).reshape((1,))}


def _reduce_axes(x, attrs):
    if attrs.get("reduce_all", False):
        return None
    dim = attrs.get("dim", [0])
    if isinstance(dim, int):
        dim = [dim]
    return tuple(d % x.ndim for d in dim) or None


def _register_reduce(name, fn):
    @register_op("reduce_" + name)
    def _red(ins, attrs, _fn=fn):
        x = ins["X"][0]
        axes = _reduce_axes(x, attrs)
        out = _fn(x, axis=axes, keepdims=attrs.get("keep_dim", False))
        if out.ndim == 0:
            out = out.reshape((1,))
        return {"Out": out}


_register_reduce("sum", jnp.sum)
_register_reduce("mean", jnp.mean)
_register_reduce("max", jnp.max)
_register_reduce("min", jnp.min)
_register_reduce("prod", jnp.prod)
_register_reduce("any", jnp.any)
_register_reduce("all", jnp.all)


@register_op("cast")
def _cast(ins, attrs):
    from ..core.types import normalize_dtype
    out_dtype = to_numpy_dtype(normalize_dtype(attrs["out_dtype"]))
    return {"Out": ins["X"][0].astype(out_dtype)}


@register_op("clip")
def _clip(ins, attrs):
    x = ins["X"][0]
    return {"Out": jnp.clip(x, attrs.get("min"), attrs.get("max"))}


@register_op("clip_by_norm")
def _clip_by_norm(ins, attrs):
    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": x * scale.astype(x.dtype)}


@register_op("squared_l2_norm")
def _squared_l2_norm(ins, attrs):
    return {"Out": jnp.sum(jnp.square(ins["X"][0])).reshape((1,))}


@register_op("p_norm")
def _p_norm(ins, attrs):
    x = ins["X"][0]
    p = attrs.get("porder", 2.0)
    axis = attrs.get("axis", -1)
    keepdim = attrs.get("keepdim", False)
    out = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keepdim) ** (1.0 / p)
    return {"Out": out}


def _register_cmp(name, fn):
    @register_op(name)
    def _cmp(ins, attrs, _fn=fn):
        return {"Out": _fn(ins["X"][0], ins["Y"][0])}


_register_cmp("equal", jnp.equal)
_register_cmp("not_equal", jnp.not_equal)
_register_cmp("less_than", jnp.less)
_register_cmp("less_equal", jnp.less_equal)
_register_cmp("greater_than", jnp.greater)
_register_cmp("greater_equal", jnp.greater_equal)


@register_op("logical_and")
def _land(ins, attrs):
    return {"Out": jnp.logical_and(ins["X"][0], ins["Y"][0])}


@register_op("logical_or")
def _lor(ins, attrs):
    return {"Out": jnp.logical_or(ins["X"][0], ins["Y"][0])}


@register_op("logical_xor")
def _lxor(ins, attrs):
    return {"Out": jnp.logical_xor(ins["X"][0], ins["Y"][0])}


@register_op("logical_not")
def _lnot(ins, attrs):
    return {"Out": jnp.logical_not(ins["X"][0])}


@register_op("isfinite")
def _isfinite(ins, attrs):
    return {"Out": jnp.all(jnp.isfinite(ins["X"][0])).reshape((1,))}


@register_op("isfinite_v2")
def _isfinite_v2(ins, attrs):
    return {"Out": jnp.isfinite(ins["X"][0])}


@register_op("isnan_v2")
def _isnan(ins, attrs):
    return {"Out": jnp.isnan(ins["X"][0])}


@register_op("isinf_v2")
def _isinf(ins, attrs):
    return {"Out": jnp.isinf(ins["X"][0])}


@register_op("maximum")
def _maximum(ins, attrs):
    return {"Out": jnp.maximum(ins["X"][0], ins["Y"][0])}


@register_op("minimum")
def _minimum(ins, attrs):
    return {"Out": jnp.minimum(ins["X"][0], ins["Y"][0])}


@register_op("pow")
def _pow(ins, attrs):
    x = ins["X"][0]
    factor = _first(ins, "FactorTensor")
    if factor is None:
        factor = attrs.get("factor", 1.0)
    return {"Out": jnp.power(x, factor)}


@register_op("amp_check_finite_and_scale")
def _amp_check(ins, attrs):
    # reference: operators/amp/amp_check_finite_and_scale_op.cc — scales all
    # inputs by Scale and reports a global finiteness flag.
    scale = ins["Scale"][0]
    outs, finite = [], jnp.array(True)
    for x in ins["X"]:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(x)))
        outs.append(x * scale.astype(x.dtype))
    return {"Out": outs, "FoundInfinite": jnp.logical_not(finite).reshape((1,))}


@register_op("check_finite_and_unscale")
def _check_finite_unscale(ins, attrs):
    scale = ins["Scale"][0]
    inv = 1.0 / scale
    outs, finite = [], jnp.array(True)
    for x in ins["X"]:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(x)))
        outs.append(x * inv.astype(x.dtype))
    return {"Out": outs, "FoundInfinite": jnp.logical_not(finite).reshape((1,))}


@register_op("update_loss_scaling")
def _update_loss_scaling(ins, attrs):
    # reference: operators/amp/update_loss_scaling_op.cc
    found_inf = ins["FoundInfinite"][0].reshape(())
    scale = ins["PrevLossScaling"][0]
    good = ins["InGoodSteps"][0]
    bad = ins["InBadSteps"][0]
    incr_every = attrs.get("incr_every_n_steps", 1000)
    decr_every = attrs.get("decr_every_n_nan_or_inf", 2)
    incr_ratio = attrs.get("incr_ratio", 2.0)
    decr_ratio = attrs.get("decr_ratio", 0.5)

    new_bad = jnp.where(found_inf, bad + 1, jnp.zeros_like(bad))
    new_good = jnp.where(found_inf, jnp.zeros_like(good), good + 1)
    dec = new_bad >= decr_every
    inc = new_good >= incr_every
    new_scale = jnp.where(dec, scale * decr_ratio,
                          jnp.where(inc, scale * incr_ratio, scale))
    new_scale = jnp.maximum(new_scale, jnp.asarray(1.0, scale.dtype))
    new_good = jnp.where(inc, jnp.zeros_like(good), new_good)
    new_bad = jnp.where(dec, jnp.zeros_like(bad), new_bad)
    outs = [jnp.where(found_inf, jnp.zeros_like(x), x) for x in ins["X"]]
    return {"Out": outs, "LossScaling": new_scale,
            "OutGoodSteps": new_good, "OutBadSteps": new_bad}


@register_op("lgamma")
def _lgamma(ins, attrs):
    import jax.scipy.special as jsp

    return {"Out": jsp.gammaln(ins["X"][0])}


@register_op("digamma")
def _digamma(ins, attrs):
    import jax.scipy.special as jsp

    return {"Out": jsp.digamma(ins["X"][0])}


@register_op("erfinv")
def _erfinv(ins, attrs):
    import jax.scipy.special as jsp

    return {"Out": jsp.erfinv(ins["X"][0])}


@register_op("lerp")
def _lerp(ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    w = ins["Weight"][0] if ins.get("Weight") else attrs.get("weight", 0.5)
    return {"Out": x + w * (y - x)}


@register_op("frac")
def _frac(ins, attrs):
    x = ins["X"][0]
    return {"Out": x - jnp.trunc(x)}


@register_op("trunc")
def _trunc(ins, attrs):
    return {"Out": jnp.trunc(ins["X"][0])}


@register_op("take")
def _take(ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": jnp.take(x.reshape(-1), idx.astype(jnp.int32))}


@register_op("put_along_axis")
def _put_along_axis(ins, attrs):
    x, idx, v = ins["Input"][0], ins["Index"][0], ins["Value"][0]
    axis = attrs.get("Axis", attrs.get("axis", 0))
    reduce = attrs.get("Reduce", attrs.get("reduce", "assign"))
    idx = idx.astype(jnp.int32)
    return {"Result": _scatter_along(x, idx, v, axis,
                                     add=reduce == "add")}


def _scatter_along(x, idx, v, axis, add):
    # build full index grids for scatter along one axis
    grids = jnp.meshgrid(*[jnp.arange(s) for s in idx.shape],
                         indexing="ij")
    grids[axis] = idx
    vv = jnp.broadcast_to(v, idx.shape)
    if add:
        return x.at[tuple(grids)].add(vv)
    return x.at[tuple(grids)].set(vv)


@register_op("masked_fill")
def _masked_fill(ins, attrs):
    x, mask = ins["X"][0], ins["Mask"][0]
    value = attrs.get("value", 0.0)
    return {"Out": jnp.where(mask.astype(bool), value, x)}


@register_op("searchsorted")
def _searchsorted(ins, attrs):
    sorted_seq, values = ins["SortedSequence"][0], ins["Values"][0]
    side = "right" if attrs.get("right", False) else "left"
    return {"Out": jnp.searchsorted(sorted_seq.reshape(-1), values,
                                    side=side).astype(jnp.int64)}


@register_op("minus")
def _minus(ins, attrs):
    return {"Out": ins["X"][0] - ins["Y"][0]}


@register_op("l1_norm")
def _l1_norm(ins, attrs):
    # reference: l1_norm_op.cc — scalar sum of |x|
    return {"Out": jnp.sum(jnp.abs(ins["X"][0]))}


@register_op("frobenius_norm")
def _frobenius_norm(ins, attrs):
    x = ins["X"][0]
    dims = attrs.get("dim", None) or tuple(range(x.ndim))
    keep = attrs.get("keep_dim", False)
    return {"Out": jnp.sqrt(jnp.sum(x * x, axis=tuple(dims),
                                    keepdims=keep))}


@register_op("dist")
def _dist(ins, attrs):
    # reference: dist_op.cc — p-norm of elementwise (X - Y), broadcasting
    x, y = ins["X"][0], ins["Y"][0]
    p = float(attrs.get("p", 2.0))
    z = jnp.abs(x - y)
    if p == float("inf"):
        return {"Out": jnp.max(z)}
    if p == float("-inf"):
        return {"Out": jnp.min(z)}
    if p == 0.0:
        return {"Out": jnp.sum((z != 0).astype(x.dtype))}
    return {"Out": jnp.power(jnp.sum(jnp.power(z, p)), 1.0 / p)}
