"""Optimizer update operators.

Reference parity: `paddle/fluid/operators/optimizers/` — sgd, momentum
(+nesterov, lars), adam/adamax/adamw, adagrad/adadelta/decayed_adagrad,
rmsprop, ftrl, lamb, dpsgd — each with .cc+.cu kernels there; here each is a
pure functional update XLA fuses into one kernel per parameter (or one fused
update when the whole train step is jitted).

All follow the framework convention: Param/Grad/<state> inputs,
ParamOut/<state>Out outputs; the lowering aliases ParamOut back onto the
Param variable name (donated buffers — in-place on TPU).
"""
from __future__ import annotations

import jax.numpy as jnp

from .registry import register_op


def _lr(ins):
    return ins["LearningRate"][0].reshape(()).astype(jnp.float32)


@register_op("sgd")
def _sgd(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = _lr(ins).astype(p.dtype)
    return {"ParamOut": p - lr * g.astype(p.dtype)}


@register_op("momentum")
def _momentum(ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    lr = _lr(ins).astype(p.dtype)
    mu = attrs.get("mu", 0.9)
    g = g.astype(p.dtype)
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register_op("lars_momentum")
def _lars_momentum(ins, attrs):
    # reference: optimizers/lars_momentum_op.cc — layer-wise adaptive LR
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    lr = _lr(ins)
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    wd = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(gf)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * coeff * p_norm / (g_norm + wd * p_norm + eps), lr)
    v_out = mu * v.astype(jnp.float32) + local_lr * (gf + wd * pf)
    p_out = pf - v_out
    return {"ParamOut": p_out.astype(p.dtype),
            "VelocityOut": v_out.astype(v.dtype)}


@register_op("adam")
def _adam(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    gf = g.astype(jnp.float32)
    m1o = b1 * m1 + (1 - b1) * gf
    m2o = b2 * m2 + (1 - b2) * jnp.square(gf)
    b1pf = b1p.reshape(()).astype(jnp.float32)
    b2pf = b2p.reshape(()).astype(jnp.float32)
    alpha = lr * jnp.sqrt(1 - b2pf * b2) / (1 - b1pf * b1)
    p_out = p.astype(jnp.float32) - alpha * m1o / (jnp.sqrt(m2o) + eps)
    return {"ParamOut": p_out.astype(p.dtype), "Moment1Out": m1o,
            "Moment2Out": m2o, "Beta1PowOut": b1p * b1,
            "Beta2PowOut": b2p * b2}


@register_op("adamw")
def _adamw(ins, attrs):
    coeff = attrs.get("coeff", attrs.get("weight_decay", 0.01))
    outs = _adam(ins, attrs)
    p = ins["Param"][0]
    lr = _lr(ins).astype(jnp.float32)
    if attrs.get("with_decay", True):
        decayed = outs["ParamOut"].astype(jnp.float32) \
            - lr * coeff * p.astype(jnp.float32)
        outs["ParamOut"] = decayed.astype(p.dtype)
    return outs


@register_op("adamax")
def _adamax(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, n = ins["Moment"][0], ins["InfNorm"][0]
    b1p = ins["Beta1Pow"][0].reshape(()).astype(jnp.float32)
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    gf = g.astype(jnp.float32)
    m_out = b1 * m + (1 - b1) * gf
    n_out = jnp.maximum(b2 * n, jnp.abs(gf))
    p_out = p.astype(jnp.float32) - (lr / (1 - b1p)) * (m_out / (n_out + eps))
    return {"ParamOut": p_out.astype(p.dtype), "MomentOut": m_out,
            "InfNormOut": n_out}


@register_op("adagrad")
def _adagrad(ins, attrs):
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = _lr(ins)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    m_out = m + jnp.square(gf)
    p_out = p.astype(jnp.float32) - lr * gf / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": p_out.astype(p.dtype), "MomentOut": m_out}


@register_op("decayed_adagrad")
def _decayed_adagrad(ins, attrs):
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = _lr(ins)
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    m_out = decay * m + (1 - decay) * jnp.square(gf)
    p_out = p.astype(jnp.float32) - lr * gf / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": p_out.astype(p.dtype), "MomentOut": m_out}


@register_op("adadelta")
def _adadelta(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq_g, avg_sq_u = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    g_acc = rho * avg_sq_g + (1 - rho) * jnp.square(gf)
    update = -jnp.sqrt((avg_sq_u + eps) / (g_acc + eps)) * gf
    u_acc = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    p_out = p.astype(jnp.float32) + update
    return {"ParamOut": p_out.astype(p.dtype),
            "AvgSquaredGradOut": g_acc, "AvgSquaredUpdateOut": u_acc}


@register_op("rmsprop")
def _rmsprop(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    lr = _lr(ins)
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    momentum = attrs.get("momentum", 0.0)
    centered = attrs.get("centered", False)
    gf = g.astype(jnp.float32)
    ms_out = rho * ms + (1 - rho) * jnp.square(gf)
    if centered:
        mg = ins["MeanGrad"][0]
        mg_out = rho * mg + (1 - rho) * gf
        denom = ms_out - jnp.square(mg_out) + eps
    else:
        mg_out = None
        denom = ms_out + eps
    mom_out = momentum * mom + lr * gf / jnp.sqrt(denom)
    p_out = p.astype(jnp.float32) - mom_out
    outs = {"ParamOut": p_out.astype(p.dtype), "MeanSquareOut": ms_out,
            "MomentOut": mom_out}
    if mg_out is not None:
        outs["MeanGradOut"] = mg_out
    return outs


@register_op("ftrl")
def _ftrl(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    sq, lin = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    gf = g.astype(jnp.float32)
    new_sq = sq + jnp.square(gf)
    sigma = (new_sq ** (-lr_power) - sq ** (-lr_power)) / lr
    lin_out = lin + gf - sigma * p.astype(jnp.float32)
    x = jnp.clip(lin_out, -l1, l1) - lin_out
    y = new_sq ** (-lr_power) / lr + 2 * l2
    p_out = x / y
    return {"ParamOut": p_out.astype(p.dtype), "SquaredAccumOut": new_sq,
            "LinearAccumOut": lin_out}


@register_op("lamb")
def _lamb(ins, attrs):
    # reference: optimizers/lamb_op.cc — layer-adaptive large-batch Adam
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p = ins["Beta1Pow"][0].reshape(()).astype(jnp.float32)
    b2p = ins["Beta2Pow"][0].reshape(()).astype(jnp.float32)
    lr = _lr(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m1o = b1 * m1 + (1 - b1) * gf
    m2o = b2 * m2 + (1 - b2) * jnp.square(gf)
    m1hat = m1o / (1 - b1p * b1)
    m2hat = m2o / (1 - b2p * b2)
    r = m1hat / (jnp.sqrt(m2hat) + eps) + wd * pf
    p_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    p_out = pf - lr * trust * r
    return {"ParamOut": p_out.astype(p.dtype), "Moment1Out": m1o,
            "Moment2Out": m2o, "Beta1PowOut": ins["Beta1Pow"][0] * b1,
            "Beta2PowOut": ins["Beta2Pow"][0] * b2}


@register_op("dpsgd", needs_rng=True)
def _dpsgd(ins, attrs):
    import jax

    p, g = ins["Param"][0], ins["Grad"][0]
    lr = _lr(ins)
    clip = attrs.get("clip", 10.0)
    sigma = attrs.get("sigma", 1.0)
    batch_size = attrs.get("batch_size", 16.0)
    gf = g.astype(jnp.float32)
    g_norm = jnp.sqrt(jnp.sum(jnp.square(gf)))
    gf = gf / jnp.maximum(1.0, g_norm / clip)
    noise = jax.random.normal(attrs["_rng_key"], g.shape) * sigma * clip
    p_out = p.astype(jnp.float32) - lr / batch_size * (gf + noise)
    return {"ParamOut": p_out.astype(p.dtype)}


@register_op("lookahead_step")
def _lookahead_step(ins, attrs):
    """Lookahead slow-weight update (reference: optimizer.py:4777
    LookaheadOptimizer). Runs every step; the interpolation + snap-back
    applies only when the step counter hits a multiple of k."""
    p, slow = ins["Param"][0], ins["SlowParam"][0]
    step = ins["Step"][0]
    alpha = attrs.get("alpha", 0.5)
    k = int(attrs.get("k", 5))
    do = (jnp.reshape(step, ()).astype(jnp.int32) % k) == 0
    pf, sf = p.astype(jnp.float32), slow.astype(jnp.float32)
    slow2 = jnp.where(do, sf + alpha * (pf - sf), sf)
    p2 = jnp.where(do, slow2, pf)
    return {"ParamOut": p2.astype(p.dtype),
            "SlowParamOut": slow2.astype(slow.dtype)}


@register_op("proximal_gd")
def _proximal_gd(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    prox = p.astype(jnp.float32) - lr * g.astype(jnp.float32)
    out = jnp.sign(prox) * jnp.maximum(
        jnp.abs(prox) - lr * l1, 0.0) / (1.0 + lr * l2)
    return {"ParamOut": out.astype(p.dtype)}


@register_op("proximal_adagrad")
def _proximal_adagrad(ins, attrs):
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    lr = _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    gf = g.astype(jnp.float32)
    m_out = m + jnp.square(gf)
    alr = lr / jnp.sqrt(m_out)
    prox = p.astype(jnp.float32) - alr * gf
    out = jnp.sign(prox) * jnp.maximum(
        jnp.abs(prox) - alr * l1, 0.0) / (1.0 + alr * l2)
    return {"ParamOut": out.astype(p.dtype), "MomentOut": m_out}


@register_op("dgc_momentum")
def _dgc_momentum(ins, attrs):
    """Reference `optimizers/dgc_momentum_op.cc`: momentum update while
    current_step < rampup_begin_step (dense warmup), plain SGD after
    (the dgc op's own momentum correction takes over, so running
    momentum here too would double-apply it)."""
    p = ins["Param"][0]
    g = ins["Grad"][0]
    v = ins["Velocity"][0]
    lr = ins["LearningRate"][0].reshape(())
    step = ins["CurrentStep"][0].reshape(())
    mu = attrs.get("mu", 0.9)
    rampup = float(attrs.get("rampup_begin_step", 0.0))
    use_nesterov = attrs.get("use_nesterov", False)

    warm = step < rampup
    v_new = mu * v + g
    if use_nesterov:
        p_momentum = p - lr * (g + mu * v_new)
    else:
        p_momentum = p - lr * v_new
    p_sgd = p - lr * g
    p_out = jnp.where(warm, p_momentum, p_sgd)
    v_out = jnp.where(warm, v_new, v)
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register_op("average_accumulates")
def _average_accumulates(ins, attrs):
    """Sliding-window parameter-sum accumulator for ModelAverage
    (reference: average_accumulates_op.h:41). Per step: sum_1 += param,
    counters ++; precision shuffle every 16384 updates folds sum_1 into
    sum_2; when the window overflows (num_accumulates >= min_window and
    >= min(max_window, num_updates*average_window)) rotate:
    sum_3 <- sum_1+sum_2, zero sum_1/sum_2, old_num <- num (REPLACED),
    num <- 0. Masked jnp.where keeps it one jittable computation."""
    p = ins["Param"][0]
    s1 = ins["in_sum_1"][0]
    s2 = ins["in_sum_2"][0]
    s3 = ins["in_sum_3"][0]
    num = ins["in_num_accumulates"][0].reshape(()).astype(jnp.int64)
    old = ins["in_old_num_accumulates"][0].reshape(()).astype(jnp.int64)
    upd = ins["in_num_updates"][0].reshape(()).astype(jnp.int64)
    avg_win = attrs.get("average_window", 0.0)
    # int32-safe "unbounded" default: jnp would overflow on 2**62 with
    # x64 disabled (the repo default)
    max_win = min(int(attrs.get("max_average_window", 2 ** 31 - 1)),
                  2 ** 31 - 1)
    min_win = attrs.get("min_average_window", 10000)
    k_max_acc = 16384  # reference kMaxNumAccumulates

    upd = upd + 1
    num = num + 1
    s1 = s1 + p
    shuffle = (upd % k_max_acc) == 0
    s2 = jnp.where(shuffle, s1 + s2, s2)
    s1 = jnp.where(shuffle, jnp.zeros_like(s1), s1)

    thresh = jnp.minimum(
        jnp.asarray(max_win, num.dtype),
        (upd.astype(jnp.float32) * avg_win).astype(num.dtype))
    rotate = (num >= min_win) & (num >= thresh)
    s3 = jnp.where(rotate, s1 + s2, s3)
    s1 = jnp.where(rotate, jnp.zeros_like(s1), s1)
    s2 = jnp.where(rotate, jnp.zeros_like(s2), s2)
    old = jnp.where(rotate, num, old)
    num = jnp.where(rotate, jnp.int64(0), num)
    return {"out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
            "out_num_accumulates": num.reshape((1,)),
            "out_old_num_accumulates": old.reshape((1,)),
            "out_num_updates": upd.reshape((1,))}


@register_op("dgc_clip_by_norm")
def _dgc_clip_by_norm(ins, attrs):
    """clip_by_norm gated on the DGC rampup step (reference:
    dgc_clip_by_norm_op.h:23 — delegates to the registered clip_by_norm
    exactly as the reference kernel inherits ClipByNormKernel; both
    sides of the comparison truncate to int, mirroring the
    static_cast<int> semantics)."""
    from .math_ops import _clip_by_norm

    x = ins["X"][0]
    rampup = int(float(attrs.get("rampup_begin_step", 0.0)))
    if rampup < 0:  # reference: negative rampup disables clipping
        return {"Out": x}
    step = ins["current_step"][0].reshape(()).astype(jnp.int32) \
        if ins.get("current_step") else jnp.int32(0)
    clipped = _clip_by_norm(
        {"X": [x]}, {"max_norm": attrs.get("max_norm", 1.0)})["Out"]
    return {"Out": jnp.where(step >= rampup, clipped, x)}
